"""Profiling / timing helpers.

The reference's only instrumentation is one wall-clock print per trial
(``/root/reference/vae-hpo.py:159,172-174``). Parity requires exactly
that (:func:`trial_timer`); :func:`profile_trace` adds the nearly-free
JAX profiler (TensorBoard-loadable traces incl. TPU device timelines),
and :class:`StepTimer` gives per-step latency stats for finding host-
side dispatch bottlenecks in multi-trial runs (SURVEY.md §7 "hard
parts": contention is host-side).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np


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


@dataclass
class StepTimer:
    """Rolling per-step latency collector.

    Note: in an async-dispatch loop, per-step host time measures
    *dispatch* cost; call ``mark(sync=True)`` (blocks on ``value``) at
    sparse intervals to sample true device-inclusive step time.

    **Stacked-mode semantics**: a mark that closes a K-lane stacked
    dispatch (docs/STACKING.md) is ONE dispatch but K lane-steps of
    training progress — pass ``lanes=K`` so the timing is attributed to
    the *bucket* and :meth:`stats` can report the per-lane effective
    step rate (``lane_steps / total_s``) instead of silently reading
    the bucket's latency as a single trial's step time. The sweep-wide
    generalization of this collector (per-key series, dispatch vs
    device-sampled books, fixed-bucket percentiles) lives in
    ``telemetry.metrics.StepSeries``, which absorbs these semantics.
    """

    times: list = field(default_factory=list)
    lanes: list = field(default_factory=list)
    synced: list = field(default_factory=list)
    _last: float = field(default_factory=time.perf_counter)

    def mark(self, value=None, sync: bool = False, lanes: int = 1):
        if sync and value is not None:
            import jax

            jax.block_until_ready(value)
        now = time.perf_counter()
        self.times.append(now - self._last)
        self.lanes.append(lanes)
        self.synced.append(bool(sync and value is not None))
        self._last = now

    def stats(self) -> dict:
        if not self.times:
            return {}
        arr = np.asarray(self.times)
        # Two populations, never mixed (StepSeries' two-books rule): a
        # sync=True mark includes the device drain a dispatch-only mark
        # doesn't, so pooling them let a handful of sparse synced
        # samples contaminate the dispatch p95. Headline percentiles
        # come from the dispatch-only marks; the synced samples get
        # their own block below.
        synced = np.asarray(self.synced, dtype=bool)
        disp = arr[~synced]
        pop = disp if disp.size else arr
        out = {
            "steps": len(arr),
            "mean_s": float(pop.mean()),
            "p50_s": float(np.percentile(pop, 50)),
            "p95_s": float(np.percentile(pop, 95)),
            "total_s": float(arr.sum()),
        }
        if synced.any() and disp.size:
            dev = arr[synced]
            out["device_sampled"] = {
                "count": int(dev.size),
                "mean_s": float(dev.mean()),
                "p50_s": float(np.percentile(dev, 50)),
                "p95_s": float(np.percentile(dev, 95)),
            }
        lane_steps = int(sum(self.lanes))
        if lane_steps != len(arr):  # at least one stacked mark
            out["lane_steps"] = lane_steps
            if out["total_s"] > 0:
                out["per_lane_steps_per_s"] = lane_steps / out["total_s"]
        return out
