"""Profiling / timing helpers.

The reference's only instrumentation is one wall-clock print per trial
(``/root/reference/vae-hpo.py:159,172-174``). Parity requires exactly
that (:func:`trial_timer`); :func:`profile_trace` adds the nearly-free
JAX profiler (TensorBoard-loadable traces incl. TPU device timelines).
:func:`span` times the host's part of a trial's admission where it
happens, into the process's compile log
(``utils/compile_cache.CompileLog``) and, under a profiler session, onto
the trace's own timeline; :func:`admission_split` reads the two
together. Per-step latency books are ``telemetry.metrics.StepSeries``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

from multidisttorch_tpu.utils.compile_cache import (
    STAGE_BACKEND,
    STAGE_LOWER,
    STAGE_RETRIEVAL,
    STAGE_SPAN,
    STAGE_TRACE,
    Sum,
    compile_log,
)


@contextlib.contextmanager
def trial_timer(label: str = "", printer=print):
    """Wall-clock a block, printing ``"<label> Done. time: <s>"`` —
    the reference's per-trial timing contract (``vae-hpo.py:174``)."""
    t0 = time.time()
    yield
    t1 = time.time()
    printer(f"{label}{' ' if label else ''}Done. time: {t1 - t0:f}")


# The ``jax.named_scope``s of the LM step, around work no flax module
# names (``models/transformer.py``, ``train/lm.py``). In a capture, group
# the device's operations by these and by flax's names (``block_3/q``).
SCOPE_ATTN_CORE = "attn_core"
SCOPE_MLP = "mlp"
SCOPE_LOSS = "loss"
SCOPE_OPTIMIZER = "optimizer"
# Inside the dropless expert layer (``ops/moe.py::RoutedExperts``, which
# a block names ``moe``): scores, top-k and weights; sorting, gathering
# the tokens and the weighted sum back; the grouped matrix products of
# the experts held; the shared expert.
SCOPE_ROUTER = "router"
SCOPE_EXPERT_DISPATCH = "expert_dispatch"
SCOPE_EXPERTS = "experts"
SCOPE_SHARED_EXPERT = "shared_expert"
# Latent attention's projections are several matrices and a norm a
# path; these scopes give them the names a plain block's projections
# carry as flax modules (``models/latent_moe.py``).
SCOPE_Q, SCOPE_K, SCOPE_V = "q", "k", "v"
# Hyper-connections (``ops/hyper_connection.py``), opened outside every
# scope above: making a sublayer's three maps (the norm over all the
# streams, the projections, the sigmoids, Sinkhorn), and the mixes of
# the streams (what the sublayer reads, what is written back).
SCOPE_HC_MAPS = "hc_maps"
SCOPE_HC_MIX = "hc_mix"
# A model whose layers alternate between full causal attention and a
# sliding window (``models/grouped_window_moe.py``) names, inside
# ``attn_core``, which of the two a layer's core is.
SCOPE_ATTN_FULL = "attn_full"
SCOPE_ATTN_WINDOW = "attn_window"
# The decoder-hybrid-decoder (``models/ssm_hybrid.py``): inside
# ``attn_core`` a third kind, the layers that attend with their own q
# over another layer's k and v; a Mamba layer's four projections and its
# gate, its causal convolution and its selective scan
# (``ops/selective_scan.py``, whatever implements it); all of a gated
# memory unit's mixer; a head that is the embedding's transpose and so
# no flax module of its own.
SCOPE_ATTN_CROSS = "attn_cross"
SCOPE_SSM_PROJ = "ssm_proj"
SCOPE_SSM_CONV = "ssm_conv"
SCOPE_SSM_SCAN = "ssm_scan"
SCOPE_GMU = "gmu"
SCOPE_HEAD = "head"
# The gated short-convolution / attention expert model
# (``models/conv_moe.py``): a conv operator's two projections (``W_in``
# and ``W_out``), and its two gates and taps between them; an attention
# layer's per-head norms of q and k and their rotation, which sit
# between the projections and the core.
SCOPE_CONV_PROJ = "conv_proj"
SCOPE_CONV_MIX = "conv_mix"
SCOPE_QK_NORM = "qk_norm"
# The looped model (``models/looped.py``), whose blocks run several times
# a token: each pass over the blocks runs under ``loop_<t>`` (t from 0),
# outside every name above; the exit gate, and in ``train/lm.py`` the exit
# distribution and its entropy, under ``loop_exit``.
SCOPE_LOOP = "loop_{}"
SCOPE_LOOP_EXIT = "loop_exit"

# Host spans of a trial's admission (:func:`span`), each opened where
# the work is done: ``parallel/mesh.py::setup_groups``; the whole of
# ``train/lm.py::create_lm_state`` and, inside it, ``model.init``,
# ``tx.init`` and the placement on the submesh. The step's own trace,
# lowering and load are in the compile log under the step's name
# (``train/lm.py::STEP_PROGRAM``), not under a span.
SPAN_SETUP_GROUPS = "admit:setup_groups"
SPAN_INIT_STATE = "admit:init_state"
SPAN_INIT_PARAMS = "admit:init_params"
SPAN_INIT_OPT = "admit:init_opt"
SPAN_PLACE_STATE = "admit:place_state"


@contextlib.contextmanager
def span(name: str):
    """Time a block of host work into the compile log, as the entry
    ``(span, name, end, secs)`` on ``time.perf_counter()``, under a
    ``jax.profiler.TraceAnnotation`` of the same name: in any profiler
    session (``run_hpo(profile_dir=)``'s or an operator's) the span lies
    on the trace's timeline, above the device operations it caused.
    Where no log is installed (``enable_compile_cache`` not called) the
    annotation is all there is."""
    import jax

    log = compile_log()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if log is not None:
            log.add_span(name, t0, time.perf_counter())


def admission_split(
    program: str, since: Optional[float], until: Optional[float]
) -> Optional[dict]:
    """Where the admissions between ``since`` and ``until`` spent their
    seconds, from the compile log; ``None`` where none is installed.

    Of the step ``program`` (``train.lm.STEP_PROGRAM``), summed over the
    trials that first called it in the interval: ``step_trace_s``
    (outermost), ``step_lower_s``, ``step_load_s`` (the backend's part:
    the cache key, the read and the deserialisation when the cache hit,
    the compile when it did not), ``step_retrieval_s`` (the read alone;
    0 on a miss) and ``step_programs``. Of the ``admit:init_state``
    spans that ended in it: ``init_s`` (their wall time),
    ``init_programs`` (``backend`` entries inside them: the programs
    ``model.init`` and ``tx.init`` dispatch one by one) and
    ``init_trace_s``, ``init_lower_s``, ``init_load_s``. What is left of
    ``init_s`` is those programs' own run time and the host's."""
    log = compile_log()
    if log is None:
        return None
    zero = Sum(0, 0.0)
    step = log.by_program(since, until).get(program, {})
    out = {
        "step_trace_s": step.get(STAGE_TRACE, zero).secs,
        "step_lower_s": step.get(STAGE_LOWER, zero).secs,
        "step_load_s": step.get(STAGE_BACKEND, zero).secs,
        "step_retrieval_s": step.get(STAGE_RETRIEVAL, zero).secs,
        "step_programs": step.get(STAGE_BACKEND, zero).n,
        "init_s": 0.0,
        "init_programs": 0,
        "init_trace_s": 0.0,
        "init_lower_s": 0.0,
        "init_load_s": 0.0,
    }
    key = {
        STAGE_TRACE: "init_trace_s",
        STAGE_LOWER: "init_lower_s",
        STAGE_BACKEND: "init_load_s",
    }
    for s in log.entries(since, until):
        if s.stage != STAGE_SPAN or s.program != SPAN_INIT_STATE:
            continue
        out["init_s"] += s.secs
        for e in log.entries(s.start, s.end):
            if e.stage in key and e.thread == s.thread:
                out[key[e.stage]] += e.secs
                if e.stage == STAGE_BACKEND:
                    out["init_programs"] += 1
    return out


def admission_line(init: dict, step: dict, admitted_s: float) -> str:
    """One line for an operator, when a trial's first step returns:
    ``init`` and ``step`` are :func:`admission_split` over the trial's
    state creation and over its first call of the step."""
    cached = "hit" if step["step_retrieval_s"] > 0 else "miss"
    return (
        f"admitted in {admitted_s:.1f} s: init {init['init_s']:.1f} s "
        f"({init['init_programs']} programs, trace+lower "
        f"{init['init_trace_s'] + init['init_lower_s']:.1f} s, load "
        f"{init['init_load_s']:.1f} s); step trace "
        f"{step['step_trace_s']:.1f} s, lower {step['step_lower_s']:.1f} s, "
        f"load {step['step_load_s']:.1f} s ({cached})"
    )


@contextlib.contextmanager
def profile_trace(log_dir: str):
    """Capture a JAX profiler trace (view with TensorBoard's profile
    plugin or Perfetto). Device timelines come for free on TPU."""
    import jax

    with jax.profiler.trace(log_dir, create_perfetto_link=False):
        yield


# One JAX profiler session may be active per process; ProfileWindow
# tracks its own so a second window degrades to a no-start instead of
# the profiler's RuntimeError.
_window_active = False


class ProfileWindow:
    """A bounded on-demand profiler capture: ``start()`` opens a
    ``jax.profiler`` trace, every ``tick()`` counts one dispatched
    step, and the window closes itself after ``steps`` ticks (or on an
    explicit :meth:`stop`).

    Built for the anomaly layer (``telemetry/anomaly.py``): when a
    straggler is flagged, the capture opens *while the slow phase is
    still running*, records the next N steps' device timeline, and
    stops — a trace small enough to keep and triggered exactly when it
    explains something. Best-effort throughout: a failed start (another
    session active, backend without profiler support) leaves
    ``active=False`` with the reason in ``error`` and never raises.
    """

    def __init__(self, log_dir: str, steps: int = 25):
        self.log_dir = log_dir
        self.remaining = max(1, int(steps))
        self.active = False
        self.error = None

    def start(self) -> bool:
        global _window_active
        if _window_active:
            self.error = "another profiler window is already active"
            return False
        import jax

        try:
            jax.profiler.start_trace(self.log_dir)
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            self.error = f"{type(e).__name__}: {e}"
            return False
        self.active = True
        _window_active = True
        return True

    def tick(self) -> None:
        """Count one step; stop the trace when the window is spent."""
        if not self.active:
            return
        self.remaining -= 1
        if self.remaining <= 0:
            self.stop()

    def stop(self) -> None:
        global _window_active
        if not self.active:
            return
        self.active = False
        _window_active = False
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — teardown is best-effort
            self.error = f"{type(e).__name__}: {e}"


def profile_window(log_dir: str, *, steps: int = 25) -> ProfileWindow:
    """Start a bounded profiler capture window of ``steps`` dispatches
    (see :class:`ProfileWindow`; ``active`` is False when the start
    failed — e.g. a window is already open)."""
    w = ProfileWindow(log_dir, steps=steps)
    w.start()
    return w
