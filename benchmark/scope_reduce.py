"""Device time of a traced run by part of the model and by pass.

The program's scope path of a device operation (flax's module names,
``block_3/q``, the four ``jax.named_scope``s of
``multidisttorch_tpu/utils/profiling.py`` and JAX's own markers
``jvp(``, ``transpose(``, ``rematted_computation``) is in the HLO's
``op_name`` and, on the TPU, in the profiler's stat ``tf_op`` of the
operation. :func:`classify` turns one such path into ``(part, pass)``
on plain strings; :func:`reduce_scoped` sums a chip's busy time by the
two; :func:`table` does it for the run that just wrote
``.benchmark_trace`` and is what the eight readers under
``layer_metrics/`` share.

A fusion is charged whole to the one path its event carries, the path
of the fusion's root: a residual add fused into a projection counts as
the projection, and Adam's update of a weight matrix, which the TPU
compiler fuses into the matmul that makes its gradient, counts as that
matmul's backward (``PERF.md``, section 6). ``block_other`` (bare
``block_<i>``) and ``unscoped`` say how much the split leaks.

``jax.profiler.ProfileData`` hands out an event's own stats only, and
the TPU keeps ``tf_op`` with the event's metadata (one record per HLO
operation, shared by all its executions), so :func:`load_scoped_events`
reads the ``.xplane.pb`` with the protobuf runtime and the handful of
fields of ``xplane.proto`` declared in :func:`_xspace_class`.
"""

from __future__ import annotations

import functools
import heapq
import os
import re
import statistics
import traceback
from collections import defaultdict
from typing import Sequence

from benchmark.cells import ROOT
from benchmark.trace_reduce import (
    DEVICE_PLANE, HOST_PREFIX, WINDOW_SPAN, clip, find_xplane, load_events,
)

TRACE_DIR = os.path.join(ROOT, ".benchmark_trace")  # where benchmark/run.py traces to
PATH_STAT = "tf_op"  # "<op_name>:<op_type>"; jax leaves the type empty
STEP_SPAN = HOST_PREFIX + "wait"  # one per round of optimizer steps

PARTS = (
    "attn_core", "attn_proj", "mlp", "norm", "embed", "head", "loss",
    "optimizer", "block_other", "unscoped",
)
PASSES = ("forward", "recompute", "backward", "none")
SCOPED_PARTS = {"attn_core", "loss", "optimizer"}  # found by a ``jax.named_scope`` alone
# ``ln_out`` goes with the head whose input it norms (``_lm_head``), so
# that ``norm`` is the blocks' own and the parts stay a partition.
_COMPONENT = {
    "attn_core": "attn_core",
    "q": "attn_proj", "k": "attn_proj", "v": "attn_proj", "proj": "attn_proj",
    "mlp": "mlp", "up": "mlp", "down": "mlp", "moe": "mlp",
    "ln_attn": "norm", "ln_mlp": "norm",
    "tok_embed": "embed", "pos_embed": "embed",
    "ln_out": "head", "head": "head",
    "loss": "loss",
    "optimizer": "optimizer",
}
_BLOCK = re.compile(r"^block_\d+$")
_WRAPPER = re.compile(r"^\w+\((.*)\)$")  # jvp(loss), transpose(jvp(loss)), jit(_take)

ScopedEvent = tuple  # (plane, line, name, start_ns, duration_ns, path or None)


def classify(path: str | None) -> tuple[str, str]:
    """``(part, pass)`` of one scope path. The outermost recognised
    component names the part (``block_0/mlp/up`` is ``mlp``); a path
    that stops at ``block_<i>`` is ``block_other``; one with nothing
    recognised, or no path at all, is ``unscoped``."""
    if not path:
        return "unscoped", "none"
    if "rematted_computation" in path:
        which = "recompute"
    elif "transpose(" in path:
        which = "backward"
    elif "jvp(" in path:
        which = "forward"
    else:
        which = "none"
    part = "unscoped"
    for component in path.split(":", 1)[0].split("/"):
        while (inner := _WRAPPER.match(component)):
            component = inner.group(1)
        if component in _COMPONENT:
            return _COMPONENT[component], which
        if _BLOCK.match(component):
            part = "block_other"
    return part, which


def _xspace_class():
    """``XSpace`` of tsl's ``xplane.proto``, cut to the planes' names
    and their event and stat metadata; everything else (lines, events,
    the embedded HLO) is skipped unparsed. Strings are declared as
    bytes: a cut HLO line need not be valid UTF-8."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    field = descriptor_pb2.FieldDescriptorProto
    package = "benchmark_xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name=package + ".proto", package=package, syntax="proto2"  # fields know if they are set
    )
    schema = {
        "XSpace": [(1, "planes", "XPlane*")],
        "XPlane": [(2, "name", field.TYPE_BYTES), (4, "event_metadata", "EventEntry*"),
                   (5, "stat_metadata", "StatEntry*")],
        "XStat": [(1, "metadata_id", field.TYPE_INT64), (2, "double_value", field.TYPE_DOUBLE),
                  (3, "uint64_value", field.TYPE_UINT64), (4, "int64_value", field.TYPE_INT64),
                  (5, "str_value", field.TYPE_BYTES), (6, "bytes_value", field.TYPE_BYTES),
                  (7, "ref_value", field.TYPE_UINT64)],
        "XEventMetadata": [(2, "name", field.TYPE_BYTES), (5, "stats", "XStat*")],
        "XStatMetadata": [(2, "name", field.TYPE_BYTES)],
        "EventEntry": [(1, "key", field.TYPE_INT64), (2, "value", "XEventMetadata")],
        "StatEntry": [(1, "key", field.TYPE_INT64), (2, "value", "XStatMetadata")],
    }
    for message, fields in schema.items():
        m = file.message_type.add(name=message)
        for number, name, kind in fields:
            f = m.field.add(name=name, number=number, label=field.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = field.TYPE_MESSAGE, f".{package}.{kind.rstrip('*')}"
                if kind.endswith("*"):
                    f.label = field.LABEL_REPEATED
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(package + ".XSpace"))


def metadata_stats(xplane_path: str) -> dict[str, dict[str, dict]]:
    """``{device plane: {operation name: {stat name: value}}}``: the
    stats the profiler keeps once per HLO operation, which
    ``ProfileData`` does not show. An operation's name is its whole HLO
    line and so unique in its plane."""
    space = _xspace_class()()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    text = lambda b: b.decode("utf-8", "replace")
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(text(plane.name)):
            continue
        stat_names = {e.key: text(e.value.name) for e in plane.stat_metadata}

        def value(stat):
            for kind in ("str_value", "bytes_value", "double_value", "uint64_value", "int64_value"):
                if stat.HasField(kind):
                    got = getattr(stat, kind)
                    return text(got) if kind == "str_value" else got
            return stat_names.get(stat.ref_value)  # a reference to an interned string

        out[text(plane.name)] = {
            text(e.value.name): {stat_names.get(s.metadata_id): value(s) for s in e.value.stats}
            for e in plane.event_metadata
        }
    return out


def load_scoped_events(xplane_path: str) -> list[ScopedEvent]:
    """``trace_reduce.load_events``' events with each device
    operation's scope path as a sixth field, ``None`` where the trace
    has none."""
    by_plane = metadata_stats(xplane_path)
    return [
        (plane, line, name, start, dur, by_plane.get(plane, {}).get(name, {}).get(PATH_STAT))
        for plane, line, name, start, dur in load_events(xplane_path)
    ]


def innermost(intervals: Sequence[tuple[float, float, object]]) -> dict:
    """Time by key, each instant given to the latest-started of the
    ``(start, end, key)`` intervals that cover it, so that the keys'
    times sum to the union of the intervals and not beyond it."""
    out: dict = defaultdict(float)
    open_: list = []  # heap, latest start on top: (-start, -order, end, key)
    at = float("-inf")

    def advance(to: float) -> None:
        nonlocal at
        while open_ and at < to:
            _, _, end, key = open_[0]
            if end <= at:
                heapq.heappop(open_)
                continue
            upto = min(end, to)
            out[key] += upto - at
            at = upto
        at = max(at, to)

    # of two that start together the shorter is the inner one
    for order, (start, end, key) in enumerate(sorted(intervals, key=lambda e: (e[0], -e[1]))):
        advance(start)
        heapq.heappush(open_, (-start, -order, end, key))
    advance(float("inf"))
    return dict(out)


def reduce_scoped(events: Sequence[ScopedEvent]) -> dict | None:
    """Seconds per chip by ``(part, pass)`` inside the traced window,
    the optimizer steps the window holds and their median length.
    ``None`` where no device operation carries a path: a backend whose
    profiler does not record one."""
    (window,) = [(s, s + d) for _, _, n, s, d, _ in events if n == WINDOW_SPAN]
    lo, hi = window
    by_chip: dict[str, list] = defaultdict(list)
    for plane, _, _, start, dur, path in events:
        if DEVICE_PLANE.match(plane):
            by_chip[plane] += [(a, b, path) for a, b in clip([(start, start + dur)], lo, hi)]
    if not any(path for ops in by_chip.values() for _, _, path in ops):
        return None
    seconds: dict = defaultdict(float)
    for ops in by_chip.values():
        for path, ns in innermost(ops).items():
            seconds[classify(path)] += ns * 1e-9 / len(by_chip)
    # a round's wait ends when its step has; the window opens and
    # closes at such an end
    ends = sorted(s + d for _, _, n, s, d, _ in events if n == STEP_SPAN and lo <= s and s + d <= hi)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(seconds.values()),
        "steps": len(ends),
        "traced_step_ms": statistics.median(b - a for a, b in zip(ends, ends[1:])) * 1e-6
        if len(ends) > 1 else None,
        "seconds": dict(seconds),
    }


def format_table(got: dict) -> str:
    """The whole part x pass table, device ms per optimizer step."""
    per_step = 1e3 / got["steps"]
    cells = " ".join(
        f"{part}:{which}={got['seconds'][part, which] * per_step:.3f}"
        for part in PARTS for which in PASSES if (part, which) in got["seconds"]
    )
    traced = got["traced_step_ms"]
    return (f"scopes ms/step steps={got['steps']} busy={got['busy_s'] * per_step:.3f} "
            f"traced_step_ms={'%.3f' % traced if traced else None} {cells}")


@functools.cache
def _table_of(trace_dir: str) -> dict | None:
    try:
        got = reduce_scoped(load_scoped_events(find_xplane(trace_dir)))
    except Exception as e:  # noqa: BLE001 - a reader leaves its metric out; it never fails the run
        traceback.print_exc()
        print(f"[benchmark] scopes: the trace was not reduced: {type(e).__name__}: {e}", flush=True)
        return None
    if got is None:
        print(f"[benchmark] scopes: no device operation of the trace has a {PATH_STAT!r} stat",
              flush=True)
    elif got["steps"]:
        print("[benchmark] " + format_table(got), flush=True)
        missing = SCOPED_PARTS - {part for part, _ in got["seconds"]}
        if missing:
            # jax's compile-cache key leaves names out, so an executable
            # cached by a checkout without the scopes is loaded as it is
            print(f"[benchmark] scopes: nothing ran under {sorted(missing)}: the step's "
                  "executable was built without these scopes", flush=True)
    return got


def table(record: dict) -> dict | None:
    """:func:`reduce_scoped` of the run's own trace, parsed once per
    process. ``None`` untraced, on a backend without scope paths, and
    where ``.benchmark_trace`` is not this record's trace."""
    if record["trace"] is None:
        return None
    got = _table_of(TRACE_DIR)
    if got is None or not got["steps"] or got["window_s"] != record["trace"].get("window_s"):
        return None  # nothing to read, or another run's trace
    return got


def ms_per_step(record: dict, parts=PARTS, passes=PASSES) -> float | None:
    """Device ms per optimizer step under ``parts`` and ``passes``; 0
    where the trace has paths and none of them is under these."""
    got = table(record)
    if got is None:
        return None
    under = sum(v for (part, which), v in got["seconds"].items() if part in parts and which in passes)
    return 1e3 * under / got["steps"]
