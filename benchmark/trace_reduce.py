"""From a profiler trace to device busy time, idle gaps and top operations.

Two stages, so that the arithmetic can be checked on a small recorded
trace (``benchmark/tests``): :func:`load_events` flattens an
``.xplane.pb`` into plain tuples with nothing but JAX, and
:func:`reduce_events` does everything else on those tuples.

An event is ``(plane, line, name, start_ns, duration_ns)``. Device
planes are named ``/device:TPU:<n>``; the line that holds one event per
executed operation is ``XLA Ops``. Host planes carry the benchmark's
own ``jax.profiler.TraceAnnotation`` spans (``host:_input``,
``host:_dispatch``, ``host:_wait``, ``bench:_traced_window``) on the
same clock, which is what lets an idle gap be given to what the host
was doing in it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Iterable, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:_traced_window"
HOST_PREFIX = "host:_"
UNATTRIBUTED = "unattributed"
TOP = 10

Event = tuple  # (plane, line, name, start_ns, duration_ns)


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(found)}"
        )
    return found[0]


def load_events(xplane_path: str) -> list[Event]:
    """Device operations and the benchmark's host spans, flattened."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    events = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name == WINDOW_SPAN or ev.name.startswith(HOST_PREFIX):
                    events.append(
                        (plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
                    )
    return events


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy: Sequence[tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi]`` that ``busy`` (disjoint,
    sorted, clipped) leaves."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def op_family(name: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one family, ``fusion``. The
    TPU names an operation by its whole HLO line, ``%fusion.123 =
    (f32[...]) fusion(...)``: the family is taken from what stands
    before the ``=``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def reduce_events(events: Sequence[Event]) -> dict:
    """Busy seconds, idle share, top operations and attributed gaps.

    The traced window is the host span ``bench:_traced_window``: the
    benchmark opens it at a completion stamp with the next round of
    steps already queued, and closes it the same way, so the device has
    work at both edges and the profiler's own start and stop are
    outside it. Per chip, busy is the union of its operation intervals
    clipped to the window. ``busy_s`` is the mean over chips (the
    contract's number), ``idle_share_worst`` the idle share of the
    idlest chip. Each gap goes to the host span that covers most of it,
    or to ``unattributed`` where none covers any.
    """
    windows = [(s, s + d) for _, _, n, s, d in events if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    host = [(n, (s, s + d)) for _, _, n, s, d in events if n.startswith(HOST_PREFIX)]
    by_chip: dict[str, list] = defaultdict(list)
    for plane, _, name, start, dur in events:
        if DEVICE_PLANE.match(plane):
            by_chip[plane].append((name, (start, start + dur)))
    if not by_chip:
        raise ValueError("the trace holds no device operation")

    busy_by_chip, op_seconds, op_members = {}, defaultdict(float), defaultdict(set)
    gap_seconds: dict[str, float] = defaultdict(float)
    for plane, ops in by_chip.items():
        busy = clip(union(iv for _, iv in ops), lo, hi)
        busy_by_chip[plane] = sum(b - a for a, b in busy)
        for name, iv in ops:
            inside = _overlap(iv, (lo, hi))
            if inside > 0:
                op_seconds[op_family(name)] += inside
                op_members[op_family(name)].add(name)
        for gap in gaps(busy, lo, hi):
            cover = defaultdict(float)
            for name, iv in host:
                cover[name] += _overlap(gap, iv)
            best = max(cover, key=cover.get, default=None)
            owner = best if best and cover[best] > 0 else UNATTRIBUTED
            gap_seconds[owner] += gap[1] - gap[0]

    chips = len(by_chip)
    window_s = (hi - lo) * 1e-9
    if max(busy_by_chip.values()) <= 0:
        raise ValueError("no device operation ran inside the traced window")

    def top(table: dict, scale: float, label=lambda k: k):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[label(k), v * scale] for k, v in ranked]

    return {
        "chips": chips,
        "window_s": window_s,
        "busy_s": sum(busy_by_chip.values()) * 1e-9 / chips,
        "idle_share_worst": 1.0 - min(busy_by_chip.values()) * 1e-9 / window_s,
        # seconds summed over nested operations and over chips, so a
        # family can exceed the window; the order is what is read
        "device_ops": top(
            op_seconds, 1e-9, lambda k: f"{k}_x{len(op_members[k])}"
        ),
        # seconds per chip
        "idle_gaps": top(gap_seconds, 1e-9 / chips),
    }


def reduce_trace(trace_dir: str) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)))
