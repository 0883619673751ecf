"""Device time of a traced run under the scopes of the gated short-
convolution / attention expert model
(``multidisttorch_tpu/models/conv_moe.py``), and the work counted
against its gates and taps and its attention core.

``scope_reduce.classify`` knows none of ``conv_proj``, ``conv_mix`` and
``qk_norm`` (``multidisttorch_tpu/utils/profiling.py``): what runs
under them inside a block is its ``block_other``. This module reads the
same trace with the same event loading and the same sum
(``swa_scopes.reduce_by``), every pass together, by the first of the
three names a path holds. The five readers ``conv_proj_ms``,
``conv_mix_ms``, ``qk_norm_ms``, ``conv_mix_roofline`` and
``gqa64_core_roofline`` share it.

A reader that finds nothing to read (an untraced run, a program without
these scopes) gets ``None`` and its metric is left out; nothing here
raises into a run.
"""

from __future__ import annotations

import functools
import traceback

from benchmark import flops_lfm2, peaks, scope_reduce, swa_scopes
from benchmark.trace_reduce import find_xplane

PARTS = ("conv_proj", "conv_mix", "qk_norm")


def classify(path: str | None) -> str | None:
    """Which of the three scopes a path is under; ``None`` for a path
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
        print(f"[benchmark] conv scopes: the trace was not reduced: {type(e).__name__}: {e}",
              flush=True)
        return None
    if got is not None:
        per_step = 1e3 / got["steps"]
        print("[benchmark] conv scopes ms/step " + " ".join(
            f"{part}={got['seconds'].get(part, 0.0) * per_step:.3f}" for part in PARTS
        ), flush=True)
    return got


def ms_per_step(record: dict, part: str) -> float | None:
    """Device ms per optimizer step under ``part``, every pass; 0 where
    the trace has one of the three scopes and nothing under this one."""
    if scope_reduce.table(record) is None:  # untraced, or not this record's trace
        return None
    got = _table_of(scope_reduce.TRACE_DIR)
    return None if got is None else 1e3 * got["seconds"].get(part, 0.0) / got["steps"]


def _is_this_configuration(record: dict) -> bool:
    return "conv_L_cache" in record.get("config", {})


def mix_roofline_share(record: dict) -> float | None:
    """The bytes any gated short convolution has to move in a step
    (``flops_lfm2.conv_mix_train_bytes``) over the device time under
    ``conv_mix``, as a share of the chip's HBM bandwidth: useful bytes
    only, so the recomputed forward, an intermediate written and read
    again (``B * u``, the padded copy, the convolution's result before
    the second gate) and a gate fused into a neighbouring product's
    scope all move the share, the first two down."""
    ms = ms_per_step(record, "conv_mix")
    if not ms or not _is_this_configuration(record):
        return None
    moved = flops_lfm2.conv_mix_train_bytes(record["config"], record["units_per_reading_per_chip"])
    return 100.0 * moved / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "hbm_bytes_per_s")


def core_roofline_share(record: dict) -> float | None:
    """The attention core's useful FLOPs of a step (forward and
    backward once, over the pairs the causal mask keeps:
    ``flops_lfm2.attention_core_train_flops``) over the device time
    under ``attn_core``, as a share of the chip's bf16 peak. Heads 64
    wide fill half of the MXU's 128-deep contraction, so a kernel that
    wastes nothing else reads half of what ``swa_core_roofline``
    does."""
    ms = scope_reduce.ms_per_step(record, parts=("attn_core",))
    if not ms or not _is_this_configuration(record):
        return None
    flops = flops_lfm2.attention_core_train_flops(
        record["config"], record["sequence_length"], record["units_per_reading_per_chip"]
    )
    return 100.0 * flops / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "bf16_flops_per_s")
