#!/usr/bin/env python3
"""What a device operation of a profiler trace carries besides its
name and times, and a test fixture with each operation's scope path.

    python3 benchmark/tests/dump_event_stats.py .benchmark_trace              # the stats
    python3 benchmark/tests/dump_event_stats.py .benchmark_trace out.json 60  # and the first
                                                      # 60 ms of the traced window, as events

For the five longest operations of the first device plane it prints
every ``(stat name, value)`` of the event itself (``ProfileData``'s
``ev.stats``) and of the event's metadata (``scope_reduce.metadata_stats``),
then how many operations have each stat, and how the window's busy time
falls by part and pass (``scope_reduce.reduce_scoped``).
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402

from benchmark import scope_reduce, trace_reduce  # noqa: E402

NAME_CHARS = 60  # of an operation's HLO line: enough for ``trace_reduce.op_family``


def main(trace_dir: str, out: str | None = None, first_ms: str = "60") -> None:
    path = trace_reduce.find_xplane(trace_dir)
    print(path, os.path.getsize(path), "bytes")
    by_plane = scope_reduce.metadata_stats(path)
    plane = next(p for p in jax.profiler.ProfileData.from_file(path).planes if p.name in by_plane)
    stats = by_plane[plane.name]
    (ops,) = [list(line.events) for line in plane.lines if line.name == trace_reduce.OPS_LINE]
    longest = {}  # one event per operation, longest first
    for ev in sorted(ops, key=lambda e: -e.duration_ns):
        longest.setdefault(ev.name, ev)
    for ev in list(longest.values())[:5]:
        print(f"{plane.name} {ev.name[:NAME_CHARS]!r} duration_ns={ev.duration_ns}")
        print("  own stats:", [(k, str(v)[:200]) for k, v in ev.stats])
        print("  metadata stats:", [(k, str(v)[:200]) for k, v in stats.get(ev.name, {}).items()])
    have = collections.Counter(k for of_op in stats.values() for k in of_op)
    print(f"{plane.name}: {len(stats)} operations; with each stat: {dict(have)}")
    events = scope_reduce.load_scoped_events(path)
    got = scope_reduce.reduce_scoped(events)
    if got is not None and got["steps"]:
        print(scope_reduce.format_table(got))
    else:
        print("reduce_scoped:", got)
    if out is None:
        return
    (lo,) = [s for _, _, n, s, _, _ in events if n == trace_reduce.WINDOW_SPAN]
    hi = lo + float(first_ms) * 1e6
    kept = []
    for plane, line, name, start, dur, scope in events:
        if name == trace_reduce.WINDOW_SPAN:
            kept.append([plane, line, name, 0.0, hi - lo, None])
        elif start + dur > lo and start < hi:
            kept.append([plane, line, name[:NAME_CHARS], start - lo, dur, scope])
    with open(out, "w") as f:
        json.dump(kept, f, separators=(",", ":"))
    print(f"wrote {len(kept)} events to {out} ({os.path.getsize(out)} bytes)")


if __name__ == "__main__":
    main(*sys.argv[1:])
