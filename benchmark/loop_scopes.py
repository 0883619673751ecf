"""Device time of a traced run under the looped model's exit scope
(``multidisttorch_tpu/models/looped.py``, ``train/lm.py``), and the work
counted against its attention core and its head and loss.

``scope_reduce.classify`` knows neither ``loop_<t>`` nor ``loop_exit``
(``multidisttorch_tpu/utils/profiling.py``): a pass over the blocks is
classified by the names inside it, and the exit gate, the exit
distribution and its entropy are ``unscoped`` there. This module reads
the same trace with the same event loading and the same sum
(``swa_scopes.reduce_by``), every pass together, of the operations whose
path holds ``loop_exit``. The three readers ``loop_exit_ms``,
``mha128_core_roofline`` and ``head_loss_roofline`` share it.

A reader that finds nothing to read (an untraced run, a program without
these scopes, another configuration) gets ``None`` and its metric is
left out; nothing here raises into a run.
"""

from __future__ import annotations

import functools
import traceback

from benchmark import flops_ouro, peaks, scope_reduce, swa_scopes
from benchmark.trace_reduce import find_xplane

EXIT = "loop_exit"


def classify(path: str | None) -> str | None:
    """``loop_exit`` for a path under it; ``None`` otherwise."""
    if not path:
        return None
    for component in path.split(":", 1)[0].split("/"):
        while (inner := scope_reduce._WRAPPER.match(component)):
            component = inner.group(1)
        if component == EXIT:
            return EXIT
    return None


@functools.cache
def _table_of(trace_dir: str) -> dict | None:
    try:
        got = swa_scopes.reduce_by(scope_reduce.load_scoped_events(find_xplane(trace_dir)), classify)
    except Exception as e:  # noqa: BLE001 - a reader leaves its metric out; it never fails the run
        traceback.print_exc()
        print(f"[benchmark] loop scopes: the trace was not reduced: {type(e).__name__}: {e}",
              flush=True)
        return None
    if got is not None:
        print(f"[benchmark] loop scopes ms/step {EXIT}="
              f"{got['seconds'].get(EXIT, 0.0) * 1e3 / got['steps']:.3f}", flush=True)
    return got


def _is_this_configuration(record: dict) -> bool:
    return "total_ut_steps" in record.get("config", {})


def exit_ms_per_step(record: dict) -> float | None:
    """Device ms per optimizer step under ``loop_exit``, every pass."""
    if not _is_this_configuration(record) or scope_reduce.table(record) is None:
        return None
    got = _table_of(scope_reduce.TRACE_DIR)
    return None if got is None else 1e3 * got["seconds"].get(EXIT, 0.0) / got["steps"]


def _share_of_peak(flops: float, ms: float | None, record: dict) -> float | None:
    if not ms:
        return None
    return 100.0 * flops / (ms * 1e-3) / peaks.peak(record["device"]["kind"], "bf16_flops_per_s")


def core_roofline_share(record: dict) -> float | None:
    """The attention core's useful FLOPs of a step, every layer of every
    loop (``flops_ouro.attention_core_train_flops``: 4 x heads x 128 a
    kept pair forward, three times that trained) over the device time
    under ``attn_core``, as a share of the chip's bf16 peak."""
    if not _is_this_configuration(record):
        return None
    flops = flops_ouro.attention_core_train_flops(
        record["config"], record["sequence_length"], record["units_per_reading_per_chip"]
    )
    return _share_of_peak(flops, scope_reduce.ms_per_step(record, parts=("attn_core",)), record)


def head_loss_roofline_share(record: dict) -> float | None:
    """The head's three products of a step, every loop
    (``flops_ouro.head_train_flops``: 6 x d x V a position and loop),
    over the device time under ``head`` and ``loss`` (``ln_out`` with
    them, as ``head_loss_ms`` reads it), as a share of the chip's bf16
    peak."""
    if not _is_this_configuration(record):
        return None
    flops = flops_ouro.head_train_flops(record["config"], record["units_per_reading_per_chip"])
    return _share_of_peak(flops, scope_reduce.ms_per_step(record, parts=("head", "loss")), record)
