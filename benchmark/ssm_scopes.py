"""Device time of a traced run under the scopes of the decoder-hybrid-
decoder (``multidisttorch_tpu/models/ssm_hybrid.py``), and the work
counted against its scan and its attention core.

``scope_reduce.classify`` knows none of ``ssm_scan``, ``ssm_proj``,
``ssm_conv`` and ``gmu`` (``multidisttorch_tpu/utils/profiling.py``):
what runs under them inside a block is its ``block_other``; and it
stops at ``attn_core``, so the cross-attention layers' core
(``attn_cross``) is one part with the window and full layers' there.
This module reads the same trace with the same event loading and the
same sum (``swa_scopes.reduce_by``), every pass together, by the first
of the five names a path holds. The seven readers ``ssm_scan_ms``,
``ssm_proj_ms``, ``ssm_conv_ms``, ``gmu_ms``, ``attn_cross_ms``,
``ssm_scan_roofline`` and ``yoco_core_roofline`` share it.

A reader that finds nothing to read (an untraced run, a program without
these scopes) gets ``None`` and its metric is left out; nothing here
raises into a run.
"""

from __future__ import annotations

import functools
import traceback

from benchmark import flops_phi4flash, peaks, scope_reduce, swa_scopes
from benchmark.trace_reduce import find_xplane

PARTS = ("ssm_scan", "ssm_proj", "ssm_conv", "gmu", "attn_cross")


def classify(path: str | None) -> str | None:
    """Which of the five scopes a path is under; ``None`` for a path
    under none."""
    if not path:
        return None
    for component in path.split(":", 1)[0].split("/"):
        while (inner := scope_reduce._WRAPPER.match(component)):
            component = inner.group(1)
        if component in PARTS:
            return component
    return None


@functools.cache
def _table_of(trace_dir: str) -> dict | None:
    try:
        got = swa_scopes.reduce_by(scope_reduce.load_scoped_events(find_xplane(trace_dir)), classify)
    except Exception as e:  # noqa: BLE001 - a reader leaves its metric out; it never fails the run
        traceback.print_exc()
        print(f"[benchmark] ssm scopes: the trace was not reduced: {type(e).__name__}: {e}",
              flush=True)
        return None
    if got is not None:
        per_step = 1e3 / got["steps"]
        print("[benchmark] ssm scopes ms/step " + " ".join(
            f"{part}={got['seconds'].get(part, 0.0) * per_step:.3f}" for part in PARTS
        ), flush=True)
    return got


def ms_per_step(record: dict, part: str) -> float | None:
    """Device ms per optimizer step under ``part``, every pass; 0 where
    the trace has one of the five scopes and nothing under this one."""
    if scope_reduce.table(record) is None:  # untraced, or not this record's trace
        return None
    got = _table_of(scope_reduce.TRACE_DIR)
    return None if got is None else 1e3 * got["seconds"].get(part, 0.0) / got["steps"]


def _is_this_configuration(record: dict) -> bool:
    return "layer_kinds" in record.get("config", {})


def scan_roofline_share(record: dict) -> float | None:
    """The bytes any selective scan has to move in a step
    (``flops_phi4flash.scan_train_bytes``) over the device time under
    ``ssm_scan``, as a share of the chip's HBM bandwidth: useful bytes
    only, so what an implementation reads twice (B and C repeated
    along the lanes, the chunk states, the partial sums of dB and dC)
    lowers the share. The scan is bound by the vector unit before it is
    by memory (16 multiply-adds and an ``exp`` a state element and
    step), so the share says how far from the memory's limit the
    arithmetic leaves it."""
    ms = ms_per_step(record, "ssm_scan")
    if not ms or not _is_this_configuration(record):
        return None
    moved = flops_phi4flash.scan_train_bytes(record["config"], record["units_per_reading_per_chip"])
    return 100.0 * moved / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "hbm_bytes_per_s")


def core_roofline_share(record: dict) -> float | None:
    """The attention core's useful FLOPs of a step (window, full and
    cross layers, forward and backward once, over the pairs each mask
    keeps: ``flops_phi4flash.attention_core_train_flops``) over the
    device time under ``attn_core``, as a share of the chip's bf16
    peak. Heads 64 wide fill half of the MXU's 128-deep contraction, so
    a kernel that wastes nothing else reads half of what
    ``swa_core_roofline`` does."""
    ms = scope_reduce.ms_per_step(record, parts=("attn_core",))
    if not ms or not _is_this_configuration(record):
        return None
    flops = flops_phi4flash.attention_core_train_flops(
        record["config"], record["sequence_length"], record["units_per_reading_per_chip"]
    )
    return 100.0 * flops / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "bf16_flops_per_s")
