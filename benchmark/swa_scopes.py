"""Device time of a traced run under the two scopes that say which kind
of attention a layer's core is, and the work counted against the core.

``scope_reduce.classify`` stops at ``attn_core``, the outermost name it
knows, so full and window layers are one part there. This module reads
the same trace with the same event loading
(``scope_reduce.load_scoped_events``) and sums, every pass together,
the time of the operations whose path holds ``attn_full`` or
``attn_window`` (``multidisttorch_tpu/utils/profiling.py``). The three
readers ``attn_full_ms``, ``attn_window_ms`` and ``swa_core_roofline``
share it.

A reader that finds nothing to read (an untraced run, a program without
these scopes) gets ``None`` and its metric is left out; nothing here
raises into a run.
"""

from __future__ import annotations

import functools
import traceback
from collections import defaultdict
from typing import Callable, Sequence

from benchmark import flops_swa, peaks, scope_reduce
from benchmark.trace_reduce import DEVICE_PLANE, WINDOW_SPAN, clip, find_xplane

PARTS = ("attn_full", "attn_window")


def classify(path: str | None) -> str | None:
    """Which of the two scopes a path is under; ``None`` for a path
    under neither."""
    if not path:
        return None
    for component in path.split(":", 1)[0].split("/"):
        while (inner := scope_reduce._WRAPPER.match(component)):
            component = inner.group(1)
        if component in PARTS:
            return component
    return None


def reduce_by(events: Sequence[scope_reduce.ScopedEvent], classify: Callable) -> dict | None:
    """Seconds per chip by ``classify``'s part inside the traced window,
    and the optimizer steps the window holds (as
    ``scope_reduce.reduce_scoped`` counts them). ``None`` where nothing
    ran under any part."""
    (window,) = [(s, s + d) for _, _, n, s, d, _ in events if n == WINDOW_SPAN]
    lo, hi = window
    by_chip: dict[str, list] = defaultdict(list)
    for plane, _, _, start, dur, path in events:
        if DEVICE_PLANE.match(plane):
            by_chip[plane] += [(a, b, path) for a, b in clip([(start, start + dur)], lo, hi)]
    seconds: dict = defaultdict(float)
    for ops in by_chip.values():
        for path, ns in scope_reduce.innermost(ops).items():
            part = classify(path)
            if part is not None:
                seconds[part] += ns * 1e-9 / len(by_chip)
    steps = sum(
        1 for _, _, n, s, d, _ in events if n == scope_reduce.STEP_SPAN and lo <= s and s + d <= hi
    )
    if not seconds or not steps:
        return None
    return {"steps": steps, "seconds": dict(seconds)}


@functools.cache
def _table_of(trace_dir: str) -> dict | None:
    try:
        got = reduce_by(scope_reduce.load_scoped_events(find_xplane(trace_dir)), classify)
    except Exception as e:  # noqa: BLE001 - a reader leaves its metric out; it never fails the run
        traceback.print_exc()
        print(f"[benchmark] swa scopes: the trace was not reduced: {type(e).__name__}: {e}",
              flush=True)
        return None
    if got is not None:
        per_step = 1e3 / got["steps"]
        print("[benchmark] swa scopes ms/step " + " ".join(
            f"{part}={got['seconds'].get(part, 0.0) * per_step:.3f}" for part in PARTS
        ), flush=True)
    return got


def ms_per_step(record: dict, part: str) -> float | None:
    """Device ms per optimizer step under ``part``, every pass; 0 where
    the trace has one of the two scopes and nothing under this one."""
    if scope_reduce.table(record) is None:  # untraced, or not this record's trace
        return None
    got = _table_of(scope_reduce.TRACE_DIR)
    return None if got is None else 1e3 * got["seconds"].get(part, 0.0) / got["steps"]


def core_roofline_share(record: dict) -> float | None:
    """The attention core's useful FLOPs of a step (forward and
    backward once, over the pairs each layer's mask keeps) over the
    device time under ``attn_core``, as a share of the chip's bf16
    peak. Bound by compute at T = 16,384: the kernels read q, k and v
    once a pass and a block of keys once a group of query heads."""
    config = record.get("config", {})
    ms = scope_reduce.ms_per_step(record, parts=("attn_core",))
    if not ms or "sliding_window_layout" not in config:
        return None
    flops = flops_swa.attention_core_train_flops(
        config, record["sequence_length"], record["units_per_reading_per_chip"]
    )
    return 100.0 * flops / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "bf16_flops_per_s")
