"""Device time of a traced run under the hyper-connections' two scopes,
and the bytes their mixes have to move.

``scope_reduce.classify`` knows neither ``hc_maps`` nor ``hc_mix``
(``multidisttorch_tpu/utils/profiling.py``), which are opened outside
every name it does know: what runs under them inside a block is its
``block_other``. This module reads the same trace with the same event
loading (``scope_reduce.load_scoped_events``) and sums, every pass
together, the time of the operations whose path holds one of the two
names. The three readers ``hc_maps_ms``, ``hc_mix_ms`` and
``hc_mix_roofline`` share it.

A reader that finds nothing to read (an untraced run, a program without
these scopes) gets ``None`` and its metric is left out; nothing here
raises into a run.
"""

from __future__ import annotations

import functools
import traceback
from collections import defaultdict
from typing import Sequence

from benchmark import peaks, scope_reduce
from benchmark.flops_hc import SUBLAYERS
from benchmark.trace_reduce import DEVICE_PLANE, WINDOW_SPAN, clip, find_xplane

PARTS = ("hc_maps", "hc_mix")


def classify(path: str | None) -> str | None:
    """Which of the two scopes a path is under (the outermost, should
    both appear); ``None`` for a path under neither."""
    if not path:
        return None
    for component in path.split(":", 1)[0].split("/"):
        while (inner := scope_reduce._WRAPPER.match(component)):
            component = inner.group(1)
        if component in PARTS:
            return component
    return None


def reduce_hc(events: Sequence[scope_reduce.ScopedEvent]) -> dict | None:
    """Seconds per chip under each of the two scopes inside the traced
    window, and the optimizer steps the window holds (as
    ``scope_reduce.reduce_scoped`` counts them). ``None`` where nothing
    ran under either."""
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
        got = reduce_hc(scope_reduce.load_scoped_events(find_xplane(trace_dir)))
    except Exception as e:  # noqa: BLE001 - a reader leaves its metric out; it never fails the run
        traceback.print_exc()
        print(f"[benchmark] hc scopes: the trace was not reduced: {type(e).__name__}: {e}",
              flush=True)
        return None
    if got is not None:
        per_step = 1e3 / got["steps"]
        print("[benchmark] hc scopes ms/step " + " ".join(
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


def mix_bytes_per_step(config: dict, tokens: int, itemsize: int = 2) -> float:
    """The bytes any implementation of the mixes has to move in one
    optimizer step of ``tokens`` tokens, ``itemsize`` bytes an element
    of the compute dtype. Per sublayer and token, forward ``(2n + 2) C``
    elements (the ``n`` streams read once and written once, ``u``
    written, ``y`` read) and backward ``(3n + 2) C`` (the streams read
    once, their gradient read once and written once, the gradients of
    ``u`` and ``y``). Recomputation is not counted, nor the maps (a few
    numbers a token)."""
    n, c = config["hc_mult"], config["hidden_size"]
    per_token = ((2 * n + 2) + (3 * n + 2)) * c * itemsize
    return float(config["num_hidden_layers"] * SUBLAYERS * tokens * per_token)


def mix_roofline_share(record: dict) -> float | None:
    """:func:`mix_bytes_per_step` over the device time under ``hc_mix``,
    as a share of the chip's HBM bandwidth. The mixes are bound by
    memory (a multiply-add a byte or so), and only useful bytes are
    counted, so whatever implements them the share cannot pass 100%."""
    ms = ms_per_step(record, "hc_mix")
    if not ms or "config" not in record or "hc_mult" not in record["config"]:
        return None
    moved = mix_bytes_per_step(record["config"], record["units_per_reading_per_chip"])
    return 100.0 * moved / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "hbm_bytes_per_s")
