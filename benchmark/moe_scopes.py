"""Device time of a traced run inside the expert layer, by its parts,
and the work counted against it.

``scope_reduce.classify`` takes the outermost name it knows, so all of
an expert layer (the flax module ``moe``) is one part there, ``mlp``.
This module reads the same trace with the same event loading
(``scope_reduce.load_scoped_events``) and looks inside ``moe``: the
first of the four ``jax.named_scope``s of
``multidisttorch_tpu/utils/profiling.py`` in an operation's path, the
innermost one after ``moe`` (the experts' products run inside the
exchange's scope and name their own), names its part, every pass
together; what runs under ``moe`` and under none of them is
``moe_other``. The seven readers
``router_ms``, ``expert_dispatch_ms``, ``experts_ms``,
``shared_expert_ms``, ``expert_load_max_over_mean``,
``mla_core_roofline`` and ``experts_roofline`` share it.

A reader that finds nothing to read (an untraced run, a program
without these scopes or without the counter) gets ``None`` and its
metric is left out; nothing here raises into a run.
"""

from __future__ import annotations

import functools
import statistics
import traceback
from collections import defaultdict
from typing import Sequence

from benchmark import flops_joyai, peaks, scope_reduce
from benchmark.trace_reduce import DEVICE_PLANE, WINDOW_SPAN, clip, find_xplane

MODULE = "moe"  # the flax name of the expert layer in a block
PARTS = ("router", "expert_dispatch", "experts", "shared_expert")
OTHER = "moe_other"


def classify(path: str | None) -> str | None:
    """The part of the expert layer a scope path is under, the
    innermost of the four names that follow ``moe``; ``None`` for a
    path that does not pass through ``moe``."""
    if not path:
        return None
    inside, part = False, OTHER
    for component in path.split(":", 1)[0].split("/"):
        while (inner := scope_reduce._WRAPPER.match(component)):
            component = inner.group(1)
        if inside and component in PARTS:
            part = component
        inside = inside or component == MODULE
    return part if inside else None


def reduce_inner(events: Sequence[scope_reduce.ScopedEvent]) -> dict | None:
    """Seconds per chip by part of the expert layer inside the traced
    window, and the optimizer steps the window holds (as
    ``scope_reduce.reduce_scoped`` counts them). ``None`` where nothing
    ran under ``moe``."""
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
        got = reduce_inner(scope_reduce.load_scoped_events(find_xplane(trace_dir)))
    except Exception as e:  # noqa: BLE001 - a reader leaves its metric out; it never fails the run
        traceback.print_exc()
        print(f"[benchmark] moe scopes: the trace was not reduced: {type(e).__name__}: {e}",
              flush=True)
        return None
    if got is not None:
        per_step = 1e3 / got["steps"]
        print("[benchmark] moe scopes ms/step " + " ".join(
            f"{part}={got['seconds'].get(part, 0.0) * per_step:.3f}" for part in PARTS + (OTHER,)
        ), flush=True)
    return got


def ms_per_step(record: dict, part: str) -> float | None:
    """Device ms per optimizer step under ``part`` of the expert
    layer, every pass; 0 where the trace has the layer and nothing of
    it under this part."""
    if scope_reduce.table(record) is None:  # untraced, or not this record's trace
        return None
    got = _table_of(scope_reduce.TRACE_DIR)
    return None if got is None else 1e3 * got["seconds"].get(part, 0.0) / got["steps"]


def assignments_per_step(record: dict) -> float | None:
    """Mean over the window's steps of the (token, expert) assignments
    to the experts held, every expert layer together, from the step's
    own counter."""
    counts = record.get("expert_counts")
    if counts is None or not len(counts):
        return None
    return float(counts.sum(axis=(1, 2)).mean())


def load_max_over_mean(record: dict) -> float | None:
    """The fullest expert held over the mean of those held, per expert
    layer and step; the median over the window's steps of the worst
    layer."""
    counts = record.get("expert_counts")
    if counts is None or not len(counts):
        return None
    ratio = counts.max(axis=-1) / counts.mean(axis=-1)  # (steps, layers)
    return float(statistics.median(ratio.max(axis=-1)))


def roofline_share(record: dict, flops_per_step: float | None, ms: float | None) -> float | None:
    """``flops_per_step`` over ``ms`` of device time a step, as a
    share of the chip's bf16 peak. Both parts are bound by compute at
    these shapes, so the FLOP roof is the roof."""
    if flops_per_step is None or not ms:
        return None
    peak = peaks.peak(record["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * flops_per_step / (ms * 1e-3) / peak


def mla_core_flops_per_step(record: dict) -> float | None:
    if "config" not in record:
        return None
    return flops_joyai.attention_core_train_flops(
        record["config"], record["sequence_length"], record["units_per_reading_per_chip"]
    )


def experts_flops_per_step(record: dict) -> float | None:
    assignments = assignments_per_step(record)
    if assignments is None:
        return None
    return assignments * flops_joyai.expert_train_flops_per_assignment(record["config"])
