#!/usr/bin/env python3
"""Look at one profiler trace by hand, and cut a test fixture from it.

    python3 benchmark/tests/dump_trace.py .benchmark_trace            # planes, lines, counts
    python3 benchmark/tests/dump_trace.py .benchmark_trace out.json 50  # first 50 ms of the
                                                                       # traced window, as events
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402

from benchmark import trace_reduce  # noqa: E402


def main(trace_dir: str, out: str | None = None, first_ms: str = "50") -> None:
    path = trace_reduce.find_xplane(trace_dir)
    print(path, os.path.getsize(path), "bytes")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:4]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} duration_ns={ev.duration_ns}")
    if out is None:
        return
    events = trace_reduce.load_events(path)
    (lo,) = [s for _, _, n, s, _ in events if n == trace_reduce.WINDOW_SPAN]
    hi = lo + float(first_ms) * 1e6
    kept = []
    for plane, line, name, start, dur in events:
        if name == trace_reduce.WINDOW_SPAN:
            kept.append([plane, line, name, 0.0, hi - lo])
        elif start + dur > lo and start < hi:
            kept.append([plane, line, name, start - lo, dur])
    with open(out, "w") as f:
        json.dump(kept, f)
    print(f"wrote {len(kept)} events to {out}")


if __name__ == "__main__":
    main(*sys.argv[1:])
