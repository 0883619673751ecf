"""Measure single-host dispatch contention across concurrent trials.

SURVEY §7 calls host-side dispatch the "hard part" of the north-star
metric (>= 90% per-trial efficiency at 8 concurrent trials): every
trial's jit steps are enqueued from ONE Python host loop
(``hpo/driver.py``'s cooperative round-robin), so even with disjoint
submeshes the host can become the serializing resource. The hardware
half of the question needs >= 2 real chips; THIS half — where the
per-trial host time goes as concurrency rises — is measurable on the
8-virtual-CPU-device mesh today.

Protocol, per concurrency level N (1, 2, 4, 8):

- carve N disjoint submeshes, one flagship-VAE trial on each
  (scan-fused ``make_multi_step`` — the production dispatch shape);
- warm up every trial's compile;
- timed region: K rounds of round-robin dispatch. For every ``step()``
  call record the HOST time it takes to RETURN (async dispatch cost:
  arg validation/donation + enqueue — the serialized-on-the-host part),
  then block on all trials once and record the wall-clock.

Reported per N: mean/p99 per-dispatch host cost, aggregate dispatch
seconds, wall-clock, and dispatch share of wall — if the dispatch share
approaches 1, the host loop (not the devices) caps trial concurrency.
Set ``--trace DIR`` to wrap the LARGEST level's whole timed region in
``jax.profiler.trace`` for timeline evidence (TensorBoard/Perfetto) —
tracing perturbs that level's numbers, so take clean measurements from
a separate untraced pass.

CPU caveat, stated on the artifact: virtual CPU devices run the actual
math on the same host cores, so ``wall_s`` mixes compute contention
into the denominator; the *dispatch-cost* columns (host enqueue time)
are the transferable signal, device-kind-independent by construction.

Usage:
  JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/profile_dispatch.py [--rounds 30] [--trace /tmp/trace]

Prints one JSON object; findings are summarized in docs/DISPATCH.md.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

# Allow `python tools/profile_dispatch.py` from the repo root without
# installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

BATCH = 128
CHUNK_STEPS = 100  # optimizer updates fused per dispatch (bench parity);
# --chunk-steps 1 reproduces the reference's one-dispatch-per-batch
# loop shape (vae-hpo.py:67-74), the configuration where host dispatch
# CAN become the serializing resource.


def _setup_trials(n: int):
    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.steps import create_train_state, make_multi_step

    groups = setup_groups(n)
    model = VAE(hidden_dim=400, latent_dim=20)
    tx = optax.adam(1e-3)
    batches_np = (
        np.random.default_rng(0)
        .uniform(0, 1, (CHUNK_STEPS, BATCH, 784))
        .astype(np.float32)
    )
    trials = []
    for g in groups:
        state = create_train_state(g, model, tx, jax.random.key(g.group_id))
        step = make_multi_step(g, model, tx)
        batches = jax.device_put(
            jnp.asarray(batches_np), g.sharding(None, "data")
        )
        trials.append({"g": g, "state": state, "step": step, "batches": batches})
    return trials


def measure(
    n: int, rounds: int, trace_dir: str | None, queue_depth: int = 2
) -> dict:
    trials = _setup_trials(n)
    key = jax.random.key(1)

    # Warmup pass 1 — COMPILE, timed on its own. Round-5's level-1
    # artifact carried a 5053 ms dispatch p99 that was really this cost
    # plus queue backpressure bleeding into the timed window; the
    # sweep's one-off compile cost now lands in its own field instead of
    # inflating a percentile it doesn't belong to.
    t0 = time.perf_counter()
    for t in trials:
        t["state"], _ = t["step"](t["state"], t["batches"], key)
    for t in trials:
        jax.block_until_ready(t["state"].params)
    compile_s = time.perf_counter() - t0

    # Warmup pass 2 — steady state: donation paths and executable
    # caches warm, device queues empty when the timed window opens.
    for t in trials:
        t["state"], _ = t["step"](
            t["state"], t["batches"], jax.random.fold_in(key, 2**20)
        )
    for t in trials:
        jax.block_until_ready(t["state"].params)

    # Timed window with BOUNDED in-flight work: at most `queue_depth`
    # un-awaited chunks per trial. Without the bound, dispatch number
    # `depth+1` blocks inside step() until the device drains — time the
    # DEVICE owes showing up in the HOST-cost column (the round-5 p99
    # anomaly's second half). The block now happens on a retained
    # metrics handle OUTSIDE the dispatch timer and is reported as
    # backpressure, which is what it is.
    from collections import deque

    dispatch_ns = []
    backpressure_ns = 0
    pending: dict[int, deque] = {i: deque() for i in range(len(trials))}
    ctx = (
        jax.profiler.trace(trace_dir)
        if trace_dir
        else contextlib.nullcontext()
    )
    t_wall = time.perf_counter()
    with ctx:
        for r in range(rounds):
            for i, t in enumerate(trials):  # the driver's round-robin shape
                t0 = time.perf_counter_ns()
                t["state"], m = t["step"](
                    t["state"], t["batches"], jax.random.fold_in(key, r)
                )
                dispatch_ns.append(time.perf_counter_ns() - t0)
                q = pending[i]
                q.append(m["loss_sum"])
                if len(q) > queue_depth:
                    tb = time.perf_counter_ns()
                    jax.block_until_ready(q.popleft())
                    backpressure_ns += time.perf_counter_ns() - tb
        tb = time.perf_counter_ns()
        for t in trials:
            jax.block_until_ready(t["state"].params)
        backpressure_ns += time.perf_counter_ns() - tb
    wall = time.perf_counter() - t_wall

    d_ms = np.asarray(dispatch_ns, dtype=np.float64) / 1e6
    agg_dispatch_s = float(d_ms.sum()) / 1e3
    return {
        "num_trials": n,
        "rounds": rounds,
        "queue_depth": queue_depth,
        "compile_s": round(compile_s, 3),
        "dispatches": len(dispatch_ns),
        "dispatch_ms_mean": round(float(d_ms.mean()), 3),
        "dispatch_ms_p50": round(float(np.percentile(d_ms, 50)), 3),
        "dispatch_ms_p99": round(float(np.percentile(d_ms, 99)), 3),
        "dispatch_s_total": round(agg_dispatch_s, 3),
        # Time spent waiting on devices at the bounded queue edge —
        # device-owed time, attributed to its owner instead of to the
        # dispatch percentiles.
        "backpressure_s_total": round(backpressure_ns / 1e9, 3),
        "wall_s": round(wall, 3),
        # The serialized-host share: while step() has not returned, NO
        # other trial can be fed. This is the quantity that must stay
        # << 1 for the >= 0.90 north-star to be reachable at all.
        "host_dispatch_share_of_wall": round(agg_dispatch_s / wall, 3),
        "backpressure_share_of_wall": round(
            backpressure_ns / 1e9 / wall, 3
        ),
        "samples_per_sec_per_trial": round(
            rounds * CHUNK_STEPS * BATCH / wall, 1
        ),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=30)
    p.add_argument("--levels", type=int, nargs="*", default=[1, 2, 4, 8])
    p.add_argument("--chunk-steps", type=int, default=None,
                   help="override CHUNK_STEPS (1 = the reference's "
                   "dispatch-per-batch shape)")
    p.add_argument("--trace", default=None,
                   help="capture a jax.profiler trace of the LARGEST "
                   "level into this directory (adds overhead — run a "
                   "separate untraced pass for clean numbers)")
    p.add_argument("--queue-depth", type=int, default=2,
                   help="max un-awaited chunks in flight per trial; the "
                   "bound keeps device backpressure out of the "
                   "dispatch-time columns (reported separately)")
    args = p.parse_args()
    if args.chunk_steps:
        global CHUNK_STEPS
        CHUNK_STEPS = args.chunk_steps

    ndev = len(jax.devices())
    levels = [n for n in args.levels if n <= ndev]
    out = {
        "platform": jax.default_backend(),
        "n_devices": ndev,
        "chunk_steps": CHUNK_STEPS,
        "batch": BATCH,
        "cpu_caveat": (
            "virtual CPU devices share host cores: wall_s includes "
            "compute contention; dispatch_* columns are the "
            "transferable host-side signal"
        ) if jax.default_backend() == "cpu" else None,
        "levels": [
            measure(
                n, args.rounds,
                args.trace if n == max(levels) else None,
                queue_depth=args.queue_depth,
            )
            for n in levels
        ],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
