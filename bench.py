"""Benchmark: VAE training samples/sec/chip vs the reference implementation.

Measures the flagship workload (MNIST-shaped VAE, batch 128 — the
reference's defaults, /root/reference/vae-hpo.py:131,183) as a
jit-compiled train step on the available accelerator, against the
reference's torch train loop executed in-process on CPU (the only
hardware its stack can use here; the reference publishes no numbers of
its own — see BASELINE.md).

Prints exactly ONE JSON line:
  {"metric": "vae_train_samples_per_sec_per_chip", "value": ...,
   "unit": "samples/sec/chip", "vs_baseline": ...}

vs_baseline = our throughput / reference-loop throughput.
"""

import contextlib
import json
import subprocess
import sys
import time
import warnings
from functools import partial

warnings.filterwarnings("ignore")

import os

import jax

import jax.numpy as jnp
import numpy as np
import optax

BATCH = 128
HIDDEN, LATENT = 400, 20
CHUNK_STEPS = 100  # inner lax.scan steps per dispatch (make_multi_step)
CHUNK_STEPS_TPU = 1000  # on the real chip a 100-step chunk is ~1 ms of
# device time at the recorded rate — the same order as ONE host enqueue
# (docs/DISPATCH.md), so the flagship was host-bound on TPU. 1000 steps
# ≈ 10 ms device per dispatch (enqueue ≪ compute) at 401 MB of stacked
# batch data — comfortable in 16 GB HBM. CPU runs keep the smaller
# chunk (compute-bound there; bigger chunks only slow the fallback).
MEASURE_CHUNKS = 10
MEASURE_REPEATS = 5  # timed passes per number; report the median and
# a p10/p90 spread (VERDICT r4 item 4). Each pass is ~128k samples, so
# the extra passes cost well under a second.
TORCH_MEASURE_STEPS = 30


def _chunk_steps() -> int:
    """Backend-resolved scan chunk (one policy for every bench mode)."""
    return CHUNK_STEPS_TPU if jax.default_backend() == "tpu" else CHUNK_STEPS

def _ensure_backend() -> dict:
    """Initialise JAX once, in this process, and say what it found.

    The platform is the run's own: ``MDT_PLATFORM`` (parallel/cluster.py),
    else ``JAX_PLATFORMS``, else jax's default. No probe child — a chip
    belongs to one process, and this is the one that measures. A run
    that did not name ``cpu`` wants a chip: if jax comes up on the CPU
    anyway the run fails, so a CPU number is never printed under a TPU
    metric's name.
    """
    from multidisttorch_tpu.parallel.cluster import select_platform

    forced = select_platform()
    named = forced or os.environ.get("JAX_PLATFORMS", "")
    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu" and named.split(",")[0] != "cpu":
        raise SystemExit(
            "bench.py: no accelerator — jax came up on the cpu without "
            "being asked to. Set JAX_PLATFORMS=cpu to run the CPU drills "
            "on purpose."
        )
    out = {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "device_count": len(devs),
    }
    if forced:
        out["forced_by"] = "MDT_PLATFORM"
    return out


def _train_flops_per_sample() -> float:
    """Analytic matmul FLOPs for one optimizer step, per sample.

    Forward = 2·MACs over the five dense layers of the flagship VAE
    (784-400-(20,20)-400-784); backward for a dense stack is ~2x forward
    (grad-activations + grad-weights matmuls), so train ≈ 3x forward.
    Elementwise/optimizer FLOPs are negligible next to the matmuls.
    """
    dims = [
        (784, HIDDEN),
        (HIDDEN, LATENT),
        (HIDDEN, LATENT),
        (LATENT, HIDDEN),
        (HIDDEN, 784),
    ]
    fwd = 2.0 * sum(a * b for a, b in dims)
    return 3.0 * fwd


def _peak_flops_per_chip(device_kind: str) -> float | None:
    # The peak table moved to telemetry/device.py (the device books'
    # MFU needs it at sweep time); bench delegates so the two MFU
    # computations can never disagree on what "peak" means.
    from multidisttorch_tpu.telemetry.device import peak_flops_per_chip

    return peak_flops_per_chip(device_kind)


def _flops_agreement(
    analytic: float, fn, args, per_step_divisor: float, devices: int = 1
) -> dict:
    """Cross-check an analytic FLOPs estimate against XLA's own
    ``cost_analysis`` of the compiled program (telemetry/device.py).

    ``per_step_divisor`` converts the compiled dispatch's total FLOPs
    to the analytic estimate's unit (per sample / per token);
    ``devices`` is the submesh size the program is partitioned over —
    ``cost_analysis`` describes the PER-DEVICE module (measured:
    1/n of global on an n-device data-sharded program), while the
    divisor counts global samples/tokens, so the per-device figure is
    scaled back to global first. The banked MFU numbers stop being
    trust-me arithmetic: the artifact records both figures and flags
    >10% disagreement.

    Known caveat the flag is EXPECTED to trip on: XLA:CPU rewrites
    large dots to library custom calls (oneDNN/Eigen) whose FLOPs the
    analysis does not count, so the CPU fallback undercounts matmul-
    heavy programs. The check's authority is the TPU path, where dots
    stay HLO dots; a CPU-artifact flag documents that undercount
    rather than an arithmetic error."""
    from multidisttorch_tpu.telemetry.device import compiled_cost_analysis

    ca = compiled_cost_analysis(fn, args)
    if ca["flops"] is None:
        return {"analytic": analytic, "cost_analysis": None,
                "reason": ca["reason"]}
    measured = ca["flops"] * max(1, devices) / per_step_divisor
    ratio = measured / analytic if analytic else None
    return {
        "analytic": analytic,
        "cost_analysis": round(measured, 1),
        "ratio": round(ratio, 4) if ratio is not None else None,
        # XLA counts every op post-optimization; the analytic figure is
        # matmuls-only —>10% disagreement means the banked MFU's
        # numerator needs a second look, in either direction.
        "disagrees_over_10pct": (
            bool(abs(ratio - 1.0) > 0.10) if ratio is not None else None
        ),
    }


def _flagship_setup(num_groups: int = 1):
    """The benchmark subject shared by every mode: the flagship VAE at
    the reference's defaults (batch 128, Adam 1e-3 — vae-hpo.py:131,183)
    carved over ``num_groups`` submeshes. bfloat16 matmuls on the MXU,
    float32 params/loss — the TPU-first configuration; on CPU runs it
    silently behaves like float32."""
    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.mesh import setup_groups

    groups = setup_groups(num_groups)
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = VAE(hidden_dim=HIDDEN, latent_dim=LATENT, dtype=dtype)
    tx = optax.adam(1e-3)
    return groups, model, tx


def _timed_chunks(
    trial, model, tx, agreement: bool = True, **step_kwargs
) -> tuple[float, list, dict]:
    """The one measurement protocol: scan-fused dispatch (a
    backend-sized chunk of optimizer updates per host round-trip —
    ``_chunk_steps()`` — the TPU-idiomatic shape of the reference's
    per-batch loop, vae-hpo.py:67-74), one warmup
    compile, then MEASURE_REPEATS passes of MEASURE_CHUNKS timed chunks.
    Returns ``(median, per_pass_rates)`` in samples/sec (whole submesh) —
    a single pass is not a defensible number, so the artifact reports
    the distribution. Both single-trial throughput modes (the headline number
    and the fused-loss comparison that decides defaults against it) go
    through here so those two can't drift; bench_concurrency and
    bench_to_elbo measure deliberately different things (interleaved
    multi-trial dispatch; loss-gated wall-clock) with their own loops."""
    from multidisttorch_tpu.train.steps import create_train_state, make_multi_step
    from multidisttorch_tpu.utils.profiling import profile_trace

    chunk = _chunk_steps()
    state = create_train_state(trial, model, tx, jax.random.key(0))
    multi = make_multi_step(trial, model, tx, **step_kwargs)
    # Synthetic batches generated ON DEVICE, directly into the data
    # sharding: at the TPU chunk size this is 401 MB that would
    # otherwise cross from host to device per timed mode.
    batches = jax.jit(
        lambda k: jax.random.uniform(k, (chunk, BATCH, 784), jnp.float32),
        out_shardings=trial.sharding(None, "data"),
    )(jax.random.key(0))
    key = jax.random.key(1)
    state, _ = multi(state, batches, key)  # compile + warmup
    jax.block_until_ready(state.params)
    # MDT_BENCH_TRACE=<dir>: wrap the first timed pass in a JAX
    # profiler trace (TensorBoard/Perfetto-loadable; device timelines
    # on TPU) — evidence for where a bad number comes from.
    trace_dir = os.environ.get("MDT_BENCH_TRACE")
    rates = []
    for r in range(MEASURE_REPEATS):
        ctx = (
            profile_trace(trace_dir)
            if trace_dir and r == 0
            else contextlib.nullcontext()
        )
        with ctx:
            t0 = time.perf_counter()
            for i in range(MEASURE_CHUNKS):
                state, _ = multi(
                    state, batches,
                    jax.random.fold_in(key, r * MEASURE_CHUNKS + i),
                )
            jax.block_until_ready(state.params)
            dt = time.perf_counter() - t0
        rates.append(MEASURE_CHUNKS * chunk * BATCH / dt)
    # MFU cross-check (unit: FLOPs per sample): XLA's cost analysis of
    # the exact program timed above vs the analytic matmul count.
    # agreement=False skips it — the AOT lower+compile is a real extra
    # compile, wasted on callers that discard the dict (the fused-loss
    # comparison times two program variants and keeps only the rates).
    agree = (
        _flops_agreement(
            _train_flops_per_sample(), multi, (state, batches, key),
            chunk * BATCH, devices=trial.size,
        )
        if agreement
        else {}
    )
    return float(np.median(rates)), rates, agree


def bench_ours() -> dict:
    """Flagship throughput with its pass distribution (VERDICT r4 #4):
    median + p10/p90 over MEASURE_REPEATS timed windows in ONE process,
    so the headline is never a single-shot number."""
    ndev = len(jax.devices())
    (trial,), model, tx = _flagship_setup(1)
    med, rates, flops_agreement = _timed_chunks(trial, model, tx)
    per_chip = [r / ndev for r in rates]
    return {
        "samples_per_sec_per_chip": round(med / ndev, 1),
        "pass_samples_per_sec_per_chip": [round(r, 1) for r in per_chip],
        "p10": round(float(np.percentile(per_chip, 10)), 1),
        "p90": round(float(np.percentile(per_chip, 90)), 1),
        "passes": len(per_chip),
        # Analytic-vs-XLA FLOPs/sample for the timed program — the
        # flagship MFU's numerator, cross-checked (>10% flags).
        "flops_agreement": flops_agreement,
        # Measurement shape provenance: the chunk became
        # backend-dependent in r5, so cross-round artifact comparisons
        # need the value recorded next to the number it produced.
        "chunk_steps": _chunk_steps(),
    }


def bench_fused_loss_comparison() -> dict:
    """Pallas ELBO kernel vs XLA's own fusion, on real hardware only.

    VERDICT r3 item 5's decision data: the tiled kernel
    (ops/pallas_elbo.py) has never been timed against XLA on a TPU.
    This times the identical scan-fused train program with
    use_fused_loss on/off and records both rates; the winner decides
    use_fused_loss's default. Skipped off-TPU (interpret-mode Pallas
    timings are meaningless).
    """
    (trial,), model, tx = _flagship_setup(1)
    out = {}
    for label, fused in (("xla_loss", False), ("pallas_fused_loss", True)):
        med, rates, _agree = _timed_chunks(
            trial, model, tx, agreement=False, use_fused_loss=fused
        )
        out[label + "_samples_per_sec"] = round(med, 1)
        out[label + "_pass_rates"] = [round(r, 1) for r in rates]
    out["winner"] = (
        "pallas"
        if out["pallas_fused_loss_samples_per_sec"]
        > out["xla_loss_samples_per_sec"]
        else "xla"
    )
    return out


# Stacked-trial bench shape: a fixed pool of 8 pending flagship trials
# (the stacking precondition — trials outnumber groups), run at K lanes
# per single-device group through the vmapped stacked step
# (train.steps.make_stacked_train_step), per-step dispatch (chunk 1 —
# the loop shape where small-trial sweeps are host-bound,
# docs/DISPATCH.md: blocked share 0.85-0.98). K=1 is today's
# one-trial-per-group path; higher K packs the same trials onto fewer
# chips, one dispatch advancing K trials. The headline is
# samples/sec per OCCUPIED chip: the consolidation win — the same sweep
# on 1/K of the chips (equivalently, K sweeps on the same chips) — is
# exactly what stacking buys, and per-occupied-chip throughput is the
# number that states it without crediting idle hardware.
STACKED_TRIALS = 8
STACKED_MEASURE_STEPS = 100  # optimizer steps per trial per timed pass
STACKED_REPEATS = 3
STACKED_LEVELS = (1, 2, 4, 8)


def bench_stacked() -> dict:
    """Per-occupied-chip throughput of 8 flagship trials at K lanes/group.

    The artifact the trial-stacking mode is judged by (ISSUE 1
    acceptance: >= 1.5x samples/sec/chip at K=4 vs K=1 on the CPU
    fallback): same 8 trials, same per-trial batch, same model — only
    the lanes-per-group packing varies. ``dispatches_per_trial_step``
    (1/K) states the mechanism next to the outcome. On the CPU fallback
    the groups are virtual single-CPU devices (the same harness
    topology as bench_concurrency and docs/DISPATCH.md), and the same
    caveat applies: virtual chips share host cores, so the ratio is a
    methodology proof of the packing win, not a hardware number — the
    real-chip rerun banks itself through the suite when a TPU window
    opens.
    """
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.steps import (
        TrialHypers,
        create_stacked_train_state,
        make_stacked_train_step,
    )

    ndev = len(jax.devices())
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    from multidisttorch_tpu.models.vae import VAE

    model = VAE(hidden_dim=HIDDEN, latent_dim=LATENT, dtype=dtype)
    all_groups = setup_groups(ndev)  # single-device groups
    out = {
        "trials": STACKED_TRIALS,
        "chunk_steps": 1,
        "measure_steps": STACKED_MEASURE_STEPS,
        "n_devices": ndev,
        "levels": [],
    }
    if jax.default_backend() == "cpu":
        out["cpu_caveat"] = (
            "virtual CPU devices share host cores: per-occupied-chip "
            "ratios prove the packing methodology, not real-chip "
            "throughput (same caveat as bench --concurrency)"
        )
    rates = {}
    for k in [lv for lv in STACKED_LEVELS if lv <= STACKED_TRIALS]:
        buckets = STACKED_TRIALS // k
        chips_used = min(ndev, buckets)
        units = []
        for b in range(buckets):
            g = all_groups[b % chips_used]
            step = make_stacked_train_step(g, model)
            state = create_stacked_train_state(g, model, list(range(k)))
            base_rngs = jnp.stack(
                [jax.random.key(s + 1) for s in range(k)]
            )
            batch = jax.jit(
                lambda key, k=k, g=g: jax.random.uniform(
                    key, (k, BATCH, 784), jnp.float32
                ),
                out_shardings=g.sharding(None, "data"),
            )(jax.random.key(0))
            units.append(
                {
                    "step": step,
                    "state": state,
                    "base": base_rngs,
                    "batch": batch,
                    "hypers": TrialHypers.stack([1e-3] * k, [1.0] * k),
                }
            )
        lane_steps = [
            jnp.full((k,), i, jnp.int32)
            for i in range(STACKED_MEASURE_STEPS)
        ]
        for u in units:  # compile + warmup every unit
            u["state"], _ = u["step"](
                u["state"], u["hypers"], u["batch"], u["base"], lane_steps[0]
            )
        for u in units:
            jax.block_until_ready(u["state"].params)
        pass_rates = []
        for _ in range(STACKED_REPEATS):
            t0 = time.perf_counter()
            for i in range(STACKED_MEASURE_STEPS):
                for u in units:  # the driver's round-robin dispatch shape
                    u["state"], _ = u["step"](
                        u["state"], u["hypers"], u["batch"], u["base"],
                        lane_steps[i],
                    )
            for u in units:
                jax.block_until_ready(u["state"].params)
            dt = time.perf_counter() - t0
            agg = STACKED_MEASURE_STEPS * STACKED_TRIALS * BATCH / dt
            pass_rates.append(agg / chips_used)
        med = float(np.median(pass_rates))
        rates[k] = med
        out["levels"].append(
            {
                "k": k,
                "buckets": buckets,
                "chips_used": chips_used,
                "samples_per_sec_per_chip": round(med, 1),
                "pass_rates": [round(r, 1) for r in pass_rates],
                "dispatches_per_trial_step": round(1.0 / k, 4),
            }
        )
    for lvl in out["levels"]:
        lvl["speedup_vs_k1"] = round(rates[lvl["k"]] / rates[1], 3)
    out["k4_vs_k1"] = (
        round(rates[4] / rates[1], 3) if 4 in rates and 1 in rates else None
    )
    # Telemetry overhead A/B (ISSUE 3 acceptance: <= 2% step-time
    # overhead with telemetry ON vs OFF, both recorded in the artifact).
    out["telemetry_overhead"] = bench_telemetry_overhead()
    if any(lvl["chips_used"] < lvl["buckets"] for lvl in out["levels"]):
        # Fewer devices than buckets (e.g. the suite on a 1-chip TPU or
        # un-flagged CPU): buckets time-share chips, so per-occupied-
        # chip ratios no longer isolate the packing win the protocol
        # documents — say so in the artifact instead of leaving a
        # degenerate number that reads like a real one.
        out["packing_limited"] = True
        out["packing_note"] = (
            "buckets exceed devices at some K: levels time-share chips "
            "and speedup_vs_k1 is NOT the per-occupied-chip packing "
            "ratio of docs/STACKING.md (run via `bench.py --stacked`, "
            "which forces the 8-virtual-device topology on CPU)"
        )
    return out


DATAPLANE_LANES = 8
DATAPLANE_ROWS = 2048   # per lane-dataset; 16 batches/round at BATCH=128
DATAPLANE_ROUNDS = 4    # measured lockstep rounds per mode


def bench_dataplane() -> dict:
    """The production data plane's banked evidence (docs/DATA.md):
    K=8 heterogeneous lanes — eight DISTINCT datasets through one
    vmapped dispatch — comparing the pipelined sharded input path
    against the synchronous reference on three axes:

    - **bit-parity**: the fused heterogeneous dispatch's final params,
      lane by lane, against each lane's classic ``make_train_step`` run
      on its own dataset (the PR 1 parity recipe, now across dataset
      boundaries), and pipelined vs synchronous feeds byte-for-byte;
    - **input_bound_frac**: fraction of dispatch wall spent blocked on
      the host gather+transfer, pipeline ON vs OFF — the "gather is off
      the critical path" gate (< 5% with the pipeline);
    - **packing across datasets**: the service scheduler co-packs 8
      tenants with 8 different dataset refs of one shape class into ONE
      placement (no per-dataset bucket splitting).
    """
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.data.sampler import StackedTrialDataIterator
    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.steps import (
        TrialHypers,
        create_stacked_train_state,
        create_train_state,
        make_stacked_train_step,
        make_train_step,
    )

    K, rows, rounds = DATAPLANE_LANES, DATAPLANE_ROWS, DATAPLANE_ROUNDS
    g = setup_groups(1)[0]
    model = VAE(hidden_dim=HIDDEN, latent_dim=LATENT)
    datasets = [synthetic_mnist(rows, seed=100 + k) for k in range(K)]
    seeds = list(range(K))
    lrs = [1e-3 * (1 + 0.1 * k) for k in range(K)]
    hypers = TrialHypers.stack(lrs, [1.0] * K)
    base_rngs = jnp.stack([jax.random.key(s + 1) for s in seeds])
    sstep = make_stacked_train_step(g, model)
    steps_per_round = rows // BATCH

    def run_mode(prefetch: bool) -> dict:
        state = create_stacked_train_state(g, model, seeds)
        waits = {"wait_s": 0.0, "bytes": 0}

        def wait_hook(dt, nb):
            waits["wait_s"] += dt
            waits["bytes"] += nb

        it = StackedTrialDataIterator(
            datasets[0], g, BATCH, seeds, datasets=datasets,
            use_native=False, prefetch=prefetch, wait_hook=wait_hook,
        )
        # warmup compile outside the timed window — on a throwaway
        # state (the stacked step donates its input state buffers)
        warm_state = create_stacked_train_state(g, model, seeds)
        warm = jnp.zeros((K, BATCH, 784), jnp.float32)
        w, _ = sstep(
            warm_state, hypers, warm, base_rngs, jnp.zeros((K,), jnp.int32)
        )
        jax.block_until_ready(w.params)
        del warm_state, w
        step_no = 0
        t0 = time.perf_counter()
        for _ in range(rounds):
            for batch in it.round_batches():
                state, _ = sstep(
                    state, hypers, batch, base_rngs,
                    jnp.full((K,), step_no, jnp.int32),
                )
                step_no += 1
        jax.block_until_ready(state.params)
        wall = time.perf_counter() - t0
        return {
            "wall_s": round(wall, 4),
            "wait_s": round(waits["wait_s"], 4),
            "bytes": waits["bytes"],
            "input_bound_frac": round(waits["wait_s"] / wall, 4),
            "bytes_per_s": round(waits["bytes"] / wall, 1),
            "steps": step_no,
            "state": state,
        }

    sync = run_mode(False)
    pipe = run_mode(True)
    pipeline_parity = bool(
        jax.tree_util.tree_all(
            jax.tree.map(
                lambda a, b: bool(jnp.all(a == b)),
                sync["state"].params,
                pipe["state"].params,
            )
        )
    )

    # Per-lane classic reference across dataset boundaries: lane k's
    # final params must be bit-identical to make_train_step fed by a
    # TrialDataIterator-equivalent stream over ITS dataset.
    from multidisttorch_tpu.data.sampler import epoch_permutation

    lane_parity = True
    for k in range(K):
        su = create_train_state(
            g, model, optax.adam(lrs[k]), jax.random.key(seeds[k])
        )
        ustep = make_train_step(g, model, optax.adam(lrs[k]), beta=1.0)
        step_no = 0
        for epoch in range(1, rounds + 1):
            perm = epoch_permutation(
                seeds[k], epoch, np.arange(rows)
            )
            for b in range(steps_per_round):
                idx = perm[b * BATCH : (b + 1) * BATCH]
                batch = jax.device_put(
                    datasets[k].images[idx], g.batch_sharding
                )
                su, _ = ustep(
                    su, batch,
                    jax.random.fold_in(
                        jax.random.key(seeds[k] + 1), step_no
                    ),
                )
                step_no += 1
        lane_params = jax.tree.map(
            lambda x, k=k: x[k], pipe["state"].params
        )
        same = jax.tree_util.tree_all(
            jax.tree.map(
                lambda a, b: bool(jnp.all(a == b)), lane_params, su.params
            )
        )
        lane_parity = lane_parity and bool(same)

    # Scheduler-level co-pack across dataset refs: pure logic, no jax.
    from multidisttorch_tpu.service.scheduler import (
        FairShareScheduler,
        PendingTrial,
        SlicePool,
    )

    sched = FairShareScheduler()
    shape_bucket = (("shape",), (784, steps_per_round))
    for k in range(K):
        sched.push(
            PendingTrial(
                sub_id=f"s{k}",
                tenant=f"tenant-{k}",
                priority=1,
                cfg=None,
                bucket=shape_bucket,  # dataset identity NOT in the key
                size=1,
                cost=10.0,
                submit_ts=0.0,
                trial_id=k,
            )
        )
    placements = sched.schedule(SlicePool(2), max_lanes=K)
    copack = (
        len(placements) == 1 and placements[0].lanes == K
    )

    for mode in (sync, pipe):
        mode.pop("state")
    out = {
        "lanes": K,
        "rows_per_dataset": rows,
        "batch": BATCH,
        "rounds": rounds,
        "distinct_datasets": K,
        "prefetch_depth": int(
            os.environ.get("MDT_STACKED_PREFETCH_DEPTH", "2")
        ),
        "synchronous": sync,
        "pipelined": pipe,
        "wall_ratio_sync_over_pipelined": round(
            sync["wall_s"] / pipe["wall_s"], 3
        ),
        "bytes_per_s_per_host": pipe["bytes_per_s"],
        "gates": {
            "fused_bitwise_vs_per_lane_reference": lane_parity,
            "pipeline_bitwise_vs_synchronous": pipeline_parity,
            "input_bound_frac_pipelined_lt_5pct": (
                pipe["input_bound_frac"] < 0.05
            ),
            "copack_across_datasets_single_placement": copack,
        },
    }
    if jax.default_backend() == "cpu":
        out["cpu_caveat"] = (
            "virtual CPU devices share host cores with the gather "
            "threads: input_bound_frac proves the overlap methodology; "
            "absolute bytes/sec is not a TPU-host number"
        )
    return out


TELEMETRY_AB_PASSES = 6  # alternating OFF/ON timed passes (3 each)


def bench_pipeline() -> dict:
    """Giant-model trials' banked evidence (docs/PARALLEL.md): the
    ZeRO-style sharded weight update and cross-submesh MPMD pipeline
    parallelism, three gates:

    - **sharded-update parity + memory**: a zero_update trial's
      per-step losses match the replicated reference within the pinned
      tolerance, and its per-device optimizer bytes are <= 1/n_data x
      replicated + epsilon (analytic books — CPU included);
    - **service vector placement**: a 2-stage pipelined submission is
      placed by the real service as an ALL-OR-NOTHING vector of slice
      blocks (journal evidence) and completes;
    - **schedule model**: the completed trial's measured bubble
      fraction is within 10% of the analytic (S-1)/(S-1+M); stage
      parity of the pipelined execution against the single-mesh
      reference step rides the same run. Wall-clock recorded, never
      gated (CPU fallback time-shares one host — the standing MFU
      caveat; the device books carry null-with-reason until open
      item 5's real-TPU run).
    """
    import tempfile

    import optax

    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.data.sampler import TrialDataIterator
    from multidisttorch_tpu.hpo.driver import TrialConfig
    from multidisttorch_tpu.hpo.pipeline_run import (
        PIPELINE_BOOKS_NAME,
        run_pipeline_trial,
    )
    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.fsdp import (
        optimizer_state_bytes,
        place_zero_state,
    )
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.parallel.pipeline import (
        make_mpmd_reference_step,
        make_vae_stage_fns,
    )
    from multidisttorch_tpu.service.queue import SweepClient
    from multidisttorch_tpu.service.runtime import SweepService
    from multidisttorch_tpu.train.steps import (
        build_train_state,
        create_train_state,
        make_train_step,
    )

    ZERO_TOL = 2e-6  # pinned parity tolerance (docs/PARALLEL.md)
    EPS = 1.02  # small-leaf epsilon on the 1/n optimizer-bytes gate
    rows, batch, epochs, microbatches = 512, 64, 2, 4
    model = VAE()
    tx = optax.adam(1e-3)

    # -- gate 1: sharded weight update ------------------------------
    trial = setup_groups(2)[0]  # 4 devices
    n_data = trial.data_size
    ref_state = create_train_state(trial, model, tx, jax.random.key(0))
    z_state, z_sh = place_zero_state(
        trial, create_train_state(trial, model, tx, jax.random.key(0))
    )
    ref_bytes = optimizer_state_bytes(ref_state)
    z_bytes = optimizer_state_bytes(z_state)
    ref_step = make_train_step(trial, model, tx)
    z_step = make_train_step(trial, model, tx, shardings=z_sh)
    rs = np.random.RandomState(0)
    key = jax.random.key(1)
    max_rel = 0.0
    zero_losses = []
    for i in range(8):
        b = jax.device_put(
            jnp.asarray(rs.rand(batch, 784), jnp.float32),
            trial.batch_sharding,
        )
        r = jax.random.fold_in(key, i)
        ref_state, mr = ref_step(ref_state, b, r)
        z_state, mz = z_step(z_state, b, r)
        lr_, lz_ = float(mr["loss_sum"]), float(mz["loss_sum"])
        zero_losses.append([lz_, lr_])
        max_rel = max(max_rel, abs(lz_ - lr_) / max(1e-12, abs(lr_)))
    opt_ratio = z_bytes["per_device_bytes"] / ref_bytes["per_device_bytes"]
    sharded_update = {
        "n_data": n_data,
        "losses_zero_vs_replicated": zero_losses,
        "max_rel_loss_diff": max_rel,
        "tolerance": ZERO_TOL,
        "optimizer_bytes_replicated_per_device": ref_bytes[
            "per_device_bytes"
        ],
        "optimizer_bytes_zero_per_device": z_bytes["per_device_bytes"],
        "optimizer_bytes_ratio": round(opt_ratio, 4),
    }

    # -- gates 2+3: service MPMD placement + schedule model ---------
    train = synthetic_mnist(rows, seed=0)
    cfg_dict = {
        "epochs": epochs,
        "batch_size": batch,
        "grad_accum": microbatches,
        "pipeline_stages": 2,
    }
    svc_dir = tempfile.mkdtemp(prefix="bench_pipeline_")
    client = SweepClient(svc_dir, tenant="whale")
    sid = client.submit(dict(cfg_dict), size=2)
    t0 = time.perf_counter()
    svc = SweepService(svc_dir, train_data=train, verbose=False)
    served = svc.serve(exit_when_drained=True, max_wall_s=600)
    service_wall = time.perf_counter() - t0
    placed = [
        json.loads(line)
        for line in open(os.path.join(svc_dir, "queue.jsonl"))
        if '"placed"' in line
    ]
    placed = [p for p in placed if p.get("event") == "placed"]
    blocks = placed[0].get("blocks") if placed else None
    disjoint = False
    if blocks and len(blocks) == 2:
        spans = [set(range(s, s + n)) for s, n in blocks]
        disjoint = not (spans[0] & spans[1]) and all(
            len(sp) == 2 for sp in spans
        )
    tid = placed[0]["trial_id"] if placed else None
    sched_books = None
    if tid is not None:
        books_path = os.path.join(
            svc_dir, f"trial-{tid}", PIPELINE_BOOKS_NAME
        )
        if os.path.exists(books_path):
            sched_books = json.load(open(books_path))["schedule"]
    bubble_ok = False
    if sched_books and sched_books.get("measured_bubble") is not None:
        analytic = sched_books["analytic_bubble"]
        bubble_ok = (
            abs(sched_books["measured_bubble"] - analytic)
            <= 0.10 * analytic
        )

    # -- stage parity: the same pipelined mechanism (direct runner,
    # same data stream) against the single-mesh reference step -------
    groups = setup_groups(4)  # 4 x 2 devices
    cfg = TrialConfig(trial_id=0, **cfg_dict)
    par_dir = tempfile.mkdtemp(prefix="bench_pipeline_parity_")
    t0 = time.perf_counter()
    pres = run_pipeline_trial(
        cfg, train, stage_meshes=[groups[0], groups[1]],
        out_dir=par_dir, save_checkpoint=False,
    )
    pipeline_wall = time.perf_counter() - t0
    stage_fns, last_fn, _ = make_vae_stage_fns(model, cfg.beta)
    ref_mesh = groups[2]
    rstate = ref_mesh.device_put(
        build_train_state(model, tx, jax.random.key(cfg.seed))
    )
    rstep = make_mpmd_reference_step(
        ref_mesh, stage_fns, last_fn, tx, microbatches=microbatches
    )
    it = TrialDataIterator(train, ref_mesh, batch, seed=cfg.seed)
    rkey = jax.random.key(cfg.seed + 1)
    step_no = 0
    ref_history = []
    t0 = time.perf_counter()
    for epoch in range(1, epochs + 1):
        sum_dev = None
        for b in it.epoch(epoch):
            r = jax.random.fold_in(rkey, step_no)
            rstate, m = rstep(rstate, b, r)
            step_no += 1
            sum_dev = (
                m["loss_sum"] if sum_dev is None else sum_dev + m["loss_sum"]
            )
        ref_history.append(float(sum_dev) / it.samples_per_epoch)
    reference_wall = time.perf_counter() - t0
    parity_rel = max(
        abs(h["avg_train_loss"] - r) / max(1e-12, abs(r))
        for h, r in zip(pres.history, ref_history)
    )

    gates = {
        "sharded_update_loss_parity": max_rel <= ZERO_TOL,
        "optimizer_bytes_within_1_over_n": (
            z_bytes["per_device_bytes"]
            <= ref_bytes["per_device_bytes"] / n_data * EPS
        ),
        "service_vector_all_or_nothing": bool(
            placed
            and served["settled"].get(sid) == "completed"
            and disjoint
        ),
        "bubble_within_10pct_of_analytic": bubble_ok,
        "stage_parity_vs_single_mesh": parity_rel <= ZERO_TOL,
    }
    return {
        "protocol": {
            "rows": rows,
            "batch": batch,
            "epochs": epochs,
            "stages": 2,
            "microbatches": microbatches,
            "zero_tolerance": ZERO_TOL,
        },
        "sharded_update": sharded_update,
        "service": {
            "submission": sid,
            "settled": served["settled"],
            "placed_blocks": blocks,
            "wall_s": round(service_wall, 3),
        },
        "schedule": sched_books,
        "stage_parity": {
            "pipeline_history": [
                h["avg_train_loss"] for h in pres.history
            ],
            "reference_history": ref_history,
            "max_rel_diff": parity_rel,
            "pipeline_wall_s": round(pipeline_wall, 3),
            "reference_wall_s": round(reference_wall, 3),
            "pipeline_optimizer_state_bytes": pres.optimizer_state_bytes,
        },
        "gates": gates,
        # Standing caveat: CPU fallback time-shares one host — bubble
        # here is a SCHEDULE measurement; wall-clock overlap and MFU
        # need the real-TPU run (device books carry null-with-reason).
        "mfu": None,
        "mfu_reason": (
            "CPU fallback: no peak FLOP/s table; the pipeline's device "
            "cost books land per-trial via record_pipeline_cost and "
            "print MFU on a TPU backend"
        ),
    }


def bench_telemetry_overhead() -> dict:
    """Step-time overhead of the telemetry seams, ON vs OFF.

    The subject is the stacked K=4 flagship dispatch loop carrying
    EXACTLY the instrumentation the HPO driver threads per dispatch
    (``metrics.step_mark`` with the bucket key, lane count, and the
    sparse device-sample seam) — the hot-path cost the <= 2% budget
    (docs/OBSERVABILITY.md) bounds. Passes alternate OFF/ON so machine
    drift lands on both sides; each side reports its MIN-of-passes
    (the low-noise estimator of true cost — a CPU fallback's run-to-run
    variance would otherwise swamp a single-digit-percent comparison),
    plus a microbenched per-mark cost for scale.
    """
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.steps import (
        TrialHypers,
        create_stacked_train_state,
        make_stacked_train_step,
    )

    k = 4
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = VAE(hidden_dim=HIDDEN, latent_dim=LATENT, dtype=dtype)
    (g,) = setup_groups(1)
    step = make_stacked_train_step(g, model)
    state = create_stacked_train_state(g, model, list(range(k)))
    base_rngs = jnp.stack([jax.random.key(s + 1) for s in range(k)])
    hypers = TrialHypers.stack([1e-3] * k, [1.0] * k)
    batch = jax.jit(
        lambda key: jax.random.uniform(key, (k, BATCH, 784), jnp.float32),
        out_shardings=g.sharding(None, "data"),
    )(jax.random.key(0))
    lane_steps = [
        jnp.full((k,), i, jnp.int32) for i in range(STACKED_MEASURE_STEPS)
    ]
    state, _ = step(state, hypers, batch, base_rngs, lane_steps[0])
    jax.block_until_ready(state.params)

    from multidisttorch_tpu.telemetry import trace as ttrace

    # The ON side now also carries submission TRACING (ISSUE 14): the
    # service's per-dispatch seam installs/clears the prebuilt trace
    # attribution around every cooperative step (service/runtime.py
    # _step_actives), so the <=2% budget covers it too.
    trace_attr = ttrace.make_attribution(
        [(i, f"bench-trace-{i}") for i in range(k)]
    )

    def timed_pass(reg, mon) -> float:
        nonlocal state
        t0 = time.perf_counter()
        for i in range(STACKED_MEASURE_STEPS):
            if reg is not None:
                ttrace.set_attribution(trace_attr)
            state, m = step(state, hypers, batch, base_rngs, lane_steps[i])
            if reg is not None:
                # EXACTLY the driver's per-dispatch seam, device books
                # included: the mark plus the straggler detector's
                # observe (hpo/driver.py's _device_seam) — the <=2%
                # budget now covers the anomaly layer too.
                dt = reg.step_mark("bucket-g0", m["loss_sum"], lanes=k)
                if mon is not None and dt is not None:
                    mon.observe_step("bucket-g0", dt)
                ttrace.set_attribution(None)
        jax.block_until_ready(state.params)
        return (time.perf_counter() - t0) / STACKED_MEASURE_STEPS

    off_times, on_times = [], []
    # host/world tags on the bus: the ON side now carries the FLEET
    # identity stamping (ISSUE 6) too, so the <=2% gate covers it —
    # an elastic worker's bus is always tagged.
    with telemetry.telemetry_run(None, host=0, world=0):
        reg = telemetry.get_registry()
        mon = telemetry.get_monitor()
        for p in range(TELEMETRY_AB_PASSES):
            if p % 2 == 0:
                off_times.append(timed_pass(None, None))
            else:
                on_times.append(timed_pass(reg, mon))
        # Per-mark microbench: the emit seam's cost in isolation
        # (mark + anomaly observe, the full per-dispatch hot path).
        n = 10000
        t0 = time.perf_counter()
        for _ in range(n):
            dt = reg.step_mark("microbench", None, lanes=k)
            if mon is not None and dt is not None:
                mon.observe_step("microbench", dt)
        per_mark_us = (time.perf_counter() - t0) / n * 1e6
        # Per-EMIT microbench, tagged vs untagged bus (in-memory ring,
        # no sink): the incremental cost of the fleet identity stamp
        # at the event seam, for scale. Events fire at boundaries (not
        # per dispatch), so this is bookkeeping, not a hot-path term.
        from multidisttorch_tpu.telemetry.events import Bus

        per_emit_us = {}
        for label, bus_kw in (
            ("untagged", {}),
            ("tagged", {"host": 0, "world": 0}),
        ):
            b = Bus(path=None, queue_max=256, **bus_kw)
            for i in range(1000):  # warm the ring/allocator first
                b.emit("epoch", trial_id=1, step=i)
            t0 = time.perf_counter()
            for i in range(n):
                b.emit("epoch", trial_id=1, step=i)
            per_emit_us[label] = round(
                (time.perf_counter() - t0) / n * 1e6, 3
            )
            b.close()
    off_s, on_s = min(off_times), min(on_times)
    overhead = on_s / off_s - 1.0
    return {
        "k": k,
        "measure_steps": STACKED_MEASURE_STEPS,
        "passes_each": TELEMETRY_AB_PASSES // 2,
        "off_step_time_s": round(off_s, 8),
        "on_step_time_s": round(on_s, 8),
        "off_pass_step_times_s": [round(t, 8) for t in off_times],
        "on_pass_step_times_s": [round(t, 8) for t in on_times],
        "overhead_frac": round(overhead, 5),
        "within_2pct": bool(overhead <= 0.02),
        "per_mark_cost_us": round(per_mark_us, 3),
        "fleet_tags": {"host": 0, "world": 0},
        "per_emit_cost_us": per_emit_us,
        # ISSUE 14: the ON side runs with submission-trace attribution
        # installed/cleared per dispatch (the service's seam), so the
        # standing <=2% bound covers tracing ON.
        "tracing_on": True,
        # ISSUE 18: telemetry_run arms the control-plane profiler too
        # (telemetry.configure -> ctlprof.configure), so the measured
        # window holds the <=2% budget with ctlprof ARMED — its seams
        # live in the scheduler, not this dispatch loop, and the
        # zero-cost-off contract keeps the OFF side clean.
        "ctlprof_on": True,
        # ISSUE 19: telemetry.configure arms the incident plane too —
        # every ON-side emit feeds the flight ring and the root-cause
        # detector's tap — so the <=2% budget now covers the black-box
        # recorder ARMED. The OFF side still constructs nothing.
        "flight_ring_on": True,
        "aggregation": "min-of-passes, OFF/ON interleaved",
    }


# LM bench shape: sized so one TPU v5e chip (16 GB HBM) is comfortably
# matmul-dominated — the MFU story the tiny flagship VAE cannot tell
# (its 784x400 matmuls are dispatch/bandwidth-bound by construction).
# LM_STEPS optimizer updates run as ONE scan-fused dispatch
# (make_lm_multi_step): at ~1 ms of device time per step on a v5e, a
# step-per-dispatch loop would time the host, not the MXU
# (docs/DISPATCH.md).
LM_VOCAB, LM_DMODEL, LM_HEADS, LM_LAYERS = 32768, 512, 8, 8
LM_SEQ, LM_BATCH, LM_STEPS = 512, 16, 40


def _lm_train_flops_per_token(
    d: int | None = None, layers: int | None = None, t: int | None = None,
    vocab: int | None = None,
) -> float:
    """Analytic matmul FLOPs for one LM optimizer step, per token.

    Forward per token: 24·d² per layer (q,k,v,out projections = 8·d²
    FLOPs, MLP up+down at 4x width = 16·d²) + causal attention
    2·T·d (QKᵀ + AV at 4·T·d, halved by the causal mask) + the
    d·vocab head (2·d·V). Train ≈ 3x forward (same dense-stack
    argument as :func:`_train_flops_per_sample`); embedding lookups
    are gathers, not FLOPs. Defaults resolve to the LM_* module
    globals at CALL time (None sentinels, not def-time binding), so a
    shrunk configuration always gets a consistent figure.
    """
    d = LM_DMODEL if d is None else d
    layers = LM_LAYERS if layers is None else layers
    t = LM_SEQ if t is None else t
    vocab = LM_VOCAB if vocab is None else vocab
    fwd = layers * (24.0 * d * d + 2.0 * t * d) + 2.0 * d * vocab
    return 3.0 * fwd


PBT_BENCH_POPULATION = 4
PBT_BENCH_GENERATIONS = 4
PBT_BENCH_STEPS_PER_GEN = 10
PBT_BENCH_BATCH = 64


def bench_pbt() -> dict:
    """Fused-lane vs per-submesh PBT A/B on the VAE workload.

    The artifact the fused population mode is judged by (ISSUE 8
    acceptance): the SAME population — same seeds, same data streams,
    same explore draws (the docs/PBT.md seeding contract) — run once as
    K members on K submeshes with host-side exploit/explore
    (``run_pbt(fused=False)``) and once as K lanes of one fused
    generation program (``fused=True``) on a submesh of the SAME shape
    (group 0 of the same carving, so the two legs' programs are
    bit-comparable). Banks dispatches/generation and wall-clock/
    generation for both legs, the headline dispatch-reduction ratio
    (floor: >= 3x at K=4), bit-parity of the whole population
    trajectory (per-generation loss sums, ranking, exploit edges, AND
    final member states — stronger than the best-member floor the
    acceptance names), and the compile-registry evidence that the
    ``pbt_gen`` program compiled ONCE with a cache_hit on every later
    generation. Wall-clock ratios are recorded, not gated: virtual CPU
    devices time-share host cores (same caveat as --stacked).
    """
    import tempfile

    from multidisttorch_tpu import telemetry as _telemetry
    from multidisttorch_tpu.compile.registry import get_executable_registry
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.pbt import PBTConfig, run_pbt
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.telemetry.events import EVENTS_NAME, read_events
    from multidisttorch_tpu.telemetry.export import SweepFold

    cfg = PBTConfig(
        population=PBT_BENCH_POPULATION,
        generations=PBT_BENCH_GENERATIONS,
        steps_per_generation=PBT_BENCH_STEPS_PER_GEN,
        batch_size=PBT_BENCH_BATCH,
        hidden_dim=HIDDEN,
        latent_dim=LATENT,
        exploit_fraction=0.5,
        lr_min=1e-4,
        lr_max=1e-1,
        seed=0,
    )
    train = synthetic_mnist(4096, seed=0)
    # Eval set = one batch (E=1): the per-submesh leg's eval is then K
    # dispatches/generation, the honest minimum — the fused leg folds
    # even that into its one dispatch.
    evals = synthetic_mnist(cfg.batch_size, seed=1)
    groups = setup_groups(cfg.population)

    ref = run_pbt(
        cfg, train, evals, groups=groups, verbose=False,
        return_states=True,
    )
    tel_dir = tempfile.mkdtemp(prefix="bench_pbt_tel_")
    with _telemetry.telemetry_run(tel_dir):
        fus = run_pbt(
            cfg, train, evals, groups=[groups[0]], fused=True,
            verbose=False, return_states=True,
        )
        events = read_events(os.path.join(tel_dir, EVENTS_NAME))
    fold = SweepFold()
    for ev in events:
        fold.feed(ev)

    # --- bit-parity of the population trajectory across the two legs
    mismatches = []
    for g in range(cfg.generations):
        r, f = ref.history[g], fus.history[g]
        for field in ("loss_sums", "order", "exploits"):
            if r[field] != f[field]:
                mismatches.append(
                    {"generation": g, "field": field,
                     "submesh": r[field], "fused": f[field]}
                )
    best_trajectory = [
        {"generation": g, "best": h["order"][0],
         "best_loss_sum": h["loss_sums"][h["order"][0]]}
        for g, h in enumerate(ref.history)
    ]
    # Final states to a ulp bound, not bitwise: the K=1 reference
    # programs and the K-lane fused program reduce the latent heads'
    # bias gradients in different orders on XLA:CPU (docs/PBT.md).
    states_equal = True
    for k in range(cfg.population):
        for a, b in zip(
            jax.tree.leaves(ref.final_states[k]),
            jax.tree.leaves(fus.final_states[k]),
        ):
            a, b = np.asarray(a), np.asarray(b)
            try:
                if np.issubdtype(a.dtype, np.floating):
                    np.testing.assert_array_max_ulp(a, b, maxulp=16)
                else:
                    np.testing.assert_array_equal(a, b)
            except AssertionError:
                states_equal = False
                mismatches.append({"member": k, "field": "final_state"})
                break
    parity = not mismatches

    # --- compile-registry evidence: the pbt_gen program is in the
    # per-program table with ONE compile and a cache_hit per later
    # generation (the process-lifetime registry, PR 7).
    snap = get_executable_registry().snapshot()
    pbt_programs = {
        label: v for label, v in snap.items()
        if label.startswith("pbt_gen")
    }
    registry_ok = any(
        v["status"] == "ready" and v["hits"] >= cfg.generations - 1
        for v in pbt_programs.values()
    )
    compiles_ok = all(
        b["compiles"] == 1
        for p, b in fold.compile_books.items()
        if p.startswith("pbt_gen")
    ) and any(p.startswith("pbt_gen") for p in fold.compile_books)

    ref_dpg = ref.dispatch_book["dispatches_per_generation"]
    fus_dpg = fus.dispatch_book["dispatches_per_generation"]
    gens = max(1, cfg.generations)
    return {
        "config": {
            "population": cfg.population,
            "generations": cfg.generations,
            "steps_per_generation": cfg.steps_per_generation,
            "batch_size": cfg.batch_size,
            "hidden_dim": cfg.hidden_dim,
            "latent_dim": cfg.latent_dim,
            "exploit_fraction": cfg.exploit_fraction,
            "eval_batches": 1,
            "submesh_devices": groups[0].size,
        },
        "submesh": {
            "dispatch_book": ref.dispatch_book,
            "wall_s": round(ref.wall_s, 3),
            "wall_s_per_generation": round(ref.wall_s / gens, 3),
        },
        "fused": {
            "dispatch_book": fus.dispatch_book,
            "wall_s": round(fus.wall_s, 3),
            "wall_s_per_generation": round(fus.wall_s / gens, 3),
        },
        # the headline: K train + K eval dispatches + per-exploit host
        # round-trips per generation, collapsed into one dispatch
        "dispatch_reduction": round(ref_dpg / fus_dpg, 3),
        "wall_ratio_submesh_over_fused": (
            round(ref.wall_s / fus.wall_s, 3) if fus.wall_s else None
        ),
        "parity": parity,
        "parity_mismatches": mismatches[:10],
        "final_states_bit_identical": states_equal,
        "best_member_trajectory": best_trajectory,
        "exploits_total": sum(
            len(h["exploits"]) for h in ref.history
        ),
        "compile_registry": {
            "programs": pbt_programs,
            "one_compile_cache_hit_gen2plus": registry_ok,
            "compile_books_one_compile": compiles_ok,
        },
        "population_view": fold.pbt,
    }


def bench_lm() -> dict:
    """Transformer-LM training throughput + MFU on one chip.

    The flagship VAE matches the reference workload but its matmuls are
    too small to exercise the MXU; this is the framework's own
    MXU-bound headline (the TransformerLM that also drives the
    ring-attention long-context path). bf16 compute, f32 params, plain
    single-submesh training, median of MEASURE_REPEATS timed passes.
    On TPU, both attention paths are timed — XLA's dense softmax vs the
    Pallas flash kernel (ops/pallas_attention.py) — and the headline is
    the winner; the per-variant rates stay in the artifact as the
    kernel's keep-or-cut decision data.
    """
    from multidisttorch_tpu.models.transformer import TransformerLM
    from multidisttorch_tpu.ops.pallas_attention import make_flash_attention
    from multidisttorch_tpu.ops.ring_attention import dense_attention_reference
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.lm import (
        create_lm_state,
        lm_chunk_sharding,
        make_lm_multi_step,
    )

    (trial,) = setup_groups(1)
    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    tx = optax.adam(1e-3)
    # (LM_STEPS, B, T) stacked chunk, batch-sharded on dim 1 — one
    # scan-fused dispatch per timed pass.
    chunks = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(
                0, LM_VOCAB, (LM_STEPS, LM_BATCH, LM_SEQ), dtype=np.int32
            )
        ),
        lm_chunk_sharding(trial),
    )

    def timed(attention) -> tuple[float, list, float, dict]:
        model = TransformerLM(
            vocab_size=LM_VOCAB, d_model=LM_DMODEL, num_heads=LM_HEADS,
            num_layers=LM_LAYERS, max_len=LM_SEQ, dtype=dtype,
            attention=attention,
        )
        state = create_lm_state(
            trial, model, tx, jax.random.key(0), example_len=LM_SEQ
        )
        multi = make_lm_multi_step(trial, model, tx)
        state, _ = multi(state, chunks)  # compile + warmup
        jax.block_until_ready(state.params)
        rates = []
        for _ in range(MEASURE_REPEATS):
            t0 = time.perf_counter()
            state, metrics = multi(state, chunks)
            jax.block_until_ready(state.params)
            rates.append(
                LM_STEPS * LM_BATCH * LM_SEQ / (time.perf_counter() - t0)
            )
        # MFU cross-check: XLA's own cost analysis of the program just
        # timed, vs the analytic per-token estimate the MFU line uses.
        agreement = _flops_agreement(
            _lm_train_flops_per_token(), multi, (state, chunks),
            LM_STEPS * LM_BATCH * LM_SEQ, devices=trial.size,
        )
        return (
            float(np.median(rates)), rates, float(metrics["loss"][-1]),
            agreement,
        )

    # Named, not None: on one TPU chip a model given no attention runs
    # the flash kernel by itself (models/transformer.py::_default_causal).
    variants = {
        "dense_xla": timed(
            lambda q, k, v: dense_attention_reference(q, k, v, causal=True)
        )
    }
    if on_tpu:  # interpret-mode flash timings are meaningless off-TPU
        # A kernel the chip refuses fails the run: a dense number under
        # "attention_winner" would hide that the race never happened.
        variants["flash_pallas"] = timed(make_flash_attention(causal=True))
    winner = max(variants, key=lambda k: variants[k][0])
    tok_s, rates, final_loss, flops_agreement = variants[winner]

    ndev = len(jax.devices())
    flops = _lm_train_flops_per_token()
    d0 = jax.devices()[0]
    peak = _peak_flops_per_chip(d0.device_kind) if on_tpu else None
    return {
        "tokens_per_sec_per_chip": round(tok_s / ndev, 1),
        "attention_winner": winner,
        "variants": {
            k: {"tokens_per_sec": round(v[0], 1),
                "pass_rates": [round(r, 1) for r in v[1]]}
            for k, v in variants.items()
        },
        "train_flops_per_token": flops,
        # Analytic-vs-cost_analysis agreement for the winner's program
        # (unit: FLOPs per token): >10% disagreement is flagged so the
        # MFU line below is auditable, not trust-me arithmetic.
        "flops_agreement": flops_agreement,
        "mfu": round(tok_s / ndev * flops / peak, 5) if peak else None,
        "config": {
            "vocab": LM_VOCAB, "d_model": LM_DMODEL, "heads": LM_HEADS,
            "layers": LM_LAYERS, "seq_len": LM_SEQ, "batch": LM_BATCH,
        },
        "final_loss": final_loss,
    }


def bench_decode() -> dict:
    """KV-cached generation throughput (the serving-side metric).

    The sampler runs prefill + generation in one jitted program, so a
    raw end-to-end timing would mix the compute-bound prefill into the
    bandwidth-bound decode number. Two timed configurations isolate
    it: a full pass (prompt T/2) and a prefill-dominated pass (prompt
    T-1, one generated token); the difference in time over the
    difference in generated tokens is the per-token decode rate —
    which tracks HBM bandwidth (each token touches the whole cache +
    weights once), not MXU peak.
    """
    from multidisttorch_tpu.models.transformer import TransformerLM
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.lm import create_lm_state
    from multidisttorch_tpu.train.lm_decode import make_cached_lm_sample
    from multidisttorch_tpu.train.lm_quant import quantize_lm_params

    (trial,) = setup_groups(1)
    model = TransformerLM(
        vocab_size=LM_VOCAB, d_model=LM_DMODEL, num_heads=LM_HEADS,
        num_layers=LM_LAYERS, max_len=LM_SEQ,
    )
    state = create_lm_state(
        trial, model, optax.adam(1e-3), jax.random.key(0),
        example_len=LM_SEQ,
    )
    fn = make_cached_lm_sample(trial, model)
    prompt_len = LM_SEQ // 2
    buf = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(
                0, LM_VOCAB, (LM_BATCH, LM_SEQ), dtype=np.int32
            )
        ),
        trial.batch_sharding,
    )
    gen_full = LM_BATCH * (LM_SEQ - prompt_len)
    gen_pre = LM_BATCH * 1  # prompt T-1: prefill + one generated token
    ndev = len(jax.devices())

    def decode_rate(st) -> float | None:
        out = fn(st, buf, prompt_len, jax.random.key(1))  # compile
        jax.block_until_ready(out)

        def timed(plen: int) -> float:
            t0 = time.perf_counter()
            o = fn(st, buf, plen, jax.random.key(2))
            jax.block_until_ready(o)
            return time.perf_counter() - t0

        rates = []
        for _ in range(MEASURE_REPEATS):
            dt = timed(prompt_len) - timed(LM_SEQ - 1)
            if dt > 0:
                rates.append((gen_full - gen_pre) / dt)
        return float(np.median(rates)) / ndev if rates else None

    f32_rate = decode_rate(state)
    int8_rate = decode_rate(
        state.replace(params=quantize_lm_params(state.params))
    )
    measured = {
        k: v for k, v in (("f32", f32_rate), ("int8", int8_rate))
        if v is not None
    }
    if not measured:  # prefill noise swamped both decode deltas
        return {"error": "decode delta not measurable (timing noise)"}
    winner = max(measured, key=measured.get)
    return {
        "decode_tokens_per_sec_per_chip": round(measured[winner], 1),
        "weights_winner": winner,
        "variants": {
            "f32": round(f32_rate, 1) if f32_rate is not None else None,
            "int8": round(int8_rate, 1) if int8_rate is not None else None,
        },
        "generated_per_pass": gen_full,
        "prompt_len": prompt_len,
        "config": {
            "vocab": LM_VOCAB, "d_model": LM_DMODEL, "heads": LM_HEADS,
            "layers": LM_LAYERS, "seq_len": LM_SEQ, "batch": LM_BATCH,
        },
    }


def bench_kernel_smoke() -> dict:
    """Per-kernel, per-dtype compiled pass/fail for the Pallas set.

    VERDICT r4 item 3: interpret-mode tests cannot catch Mosaic dtype
    rules (the round-4 bf16 ELBO store failure class), so the banked
    suite artifact must itself prove each shipped kernel compiles and
    matches its XLA reference on the hardware it ran on. Tiny shapes,
    fwd AND bwd, f32 AND bf16 — run FIRST in the suite so a kernel
    regression is recorded even if a later timing section crashes.
    Off-TPU this still runs (interpret mode, semantics only); the
    ``platform`` field says which kind of proof the artifact carries.
    """
    from multidisttorch_tpu.ops.losses import elbo_loss_sum
    from multidisttorch_tpu.ops.pallas_attention import flash_attention
    from multidisttorch_tpu.ops.pallas_elbo import fused_elbo_loss_sum
    from multidisttorch_tpu.ops.ring_attention import dense_attention_reference

    out = {"platform": jax.default_backend()}
    rng = np.random.default_rng(0)

    def check(name, fn):
        t0 = time.perf_counter()
        fn()  # a refused or mismatching kernel fails the run
        out[name] = {
            "ok": True, "wall_s": round(time.perf_counter() - t0, 1)
        }

    def rel_close(got, want, tol):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        denom = max(float(np.max(np.abs(want))), 1e-6)
        err = float(np.max(np.abs(got - want))) / denom
        if not err <= tol:  # explicit raise: `assert` dies under -O and
            # would bank a false hardware proof (NaN err also lands here)
            raise ValueError(f"kernel mismatch: rel err {err:.3e} > {tol}")

    def flash_case(dt, tol, shape=(1, 256, 2, 64)):
        # Default shape: T=256 → the tiled 128-block grid path, fwd and
        # bwd. One body serves every flash smoke variant.
        q, k, v = (
            jnp.asarray(rng.normal(size=shape), dt) for _ in range(3)
        )

        def run(attn):
            f = lambda q, k, v: jnp.sum(
                attn(q, k, v, causal=True).astype(jnp.float32) ** 2
            )
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)

        (got, g_got), (want, g_want) = run(flash_attention), run(
            dense_attention_reference
        )
        rel_close(got, want, tol)
        for a, b in zip(g_got, g_want):
            rel_close(a.astype(jnp.float32), b.astype(jnp.float32), tol)

    for dt_name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        # bf16 operands round at ~2^-8; sums over hundreds of terms in a
        # shared-f32 accumulation still differ per-path at that scale.
        tol = 3e-2 if dt == jnp.bfloat16 else 2e-4

        def elbo_case(dt=dt, tol=tol):
            # batch 256 forces a multi-block grid under the shrunken
            # VMEM budget used in tests; here it just exercises the
            # production accumulation path (same 784/20 widths as the
            # flagship, targets f32 like the real train step feeds).
            logits = jnp.asarray(rng.normal(size=(256, 784)), dt)
            x = jnp.asarray(rng.uniform(size=(256, 784)), jnp.float32)
            mu = jnp.asarray(rng.normal(size=(256, 20)), dt)
            logvar = jnp.asarray(rng.normal(size=(256, 20)), dt)

            def run(loss_fn):
                f = lambda l, m, lv: loss_fn(l, x, m, lv, 1.0)
                return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
                    logits, mu, logvar
                )

            (got, g_got), (want, g_want) = run(fused_elbo_loss_sum), run(
                elbo_loss_sum
            )
            rel_close(got, want, tol)
            for a, b in zip(g_got, g_want):
                rel_close(a.astype(jnp.float32), b.astype(jnp.float32), tol)

        check(f"fused_elbo_{dt_name}", elbo_case)

        check(f"flash_attention_{dt_name}", partial(flash_case, dt, tol))

    # The causal pad-to-tile path for large non-128-divisible T (new in
    # r5): T=1300 pads to 1408 and must stay exact against the dense
    # reference, fwd and bwd. f32 only — one compile's worth of
    # hardware proof for the pad path's grid shape.
    check(
        "flash_attention_pad_f32",
        partial(flash_case, jnp.float32, 2e-4, shape=(1, 1300, 1, 32)),
    )
    return out


def bench_suite(checkpoint=None) -> dict:
    """Every measurement in ONE process: one backend start-up, one
    holder of the chip, compiles shared through jax's in-process caches.
    A section that fails fails the suite. ``checkpoint``, if given, is
    called with the partial results dict after EVERY section, so what
    was captured is on disk before a later section can fail or be
    killed at a time limit (kernel_smoke runs first: it is the cheapest
    evidence).
    """
    on_tpu = jax.default_backend() == "tpu"
    out = {}
    for name, fn in (
        # Kernel pass/fail FIRST: the cheapest section.
        ("kernel_smoke", bench_kernel_smoke),
        ("flagship", bench_ours),
        # Interpret-mode Pallas timings are meaningless and very slow —
        # same off-TPU gate as the default mode's comparison.
        ("fused_loss_comparison", bench_fused_loss_comparison if on_tpu
         else (lambda: {"skipped": "interpret-mode timings meaningless"})),
        # Full-size LM on the CPU is hours of wall-clock.
        ("lm", bench_lm if on_tpu
         else (lambda: {"skipped": "full-size LM needs the TPU"})),
        ("decode", bench_decode if on_tpu
         else (lambda: {"skipped": "full-size decode needs the TPU"})),
        ("to_elbo_150", lambda: bench_to_elbo(150.0)),
        ("loader", bench_loader),
        # Trial-stacking artifact (ISSUE 1): K trials per dispatch vs
        # one — cheap on any backend.
        ("stacked", bench_stacked),
    ):
        t0 = time.perf_counter()
        out[name] = fn()
        out[name]["wall_s"] = round(time.perf_counter() - t0, 1)
        if checkpoint is not None:
            try:
                checkpoint(out)
            except OSError as e:  # never let banking kill the capture
                print(f"suite checkpoint failed: {e!r}", file=sys.stderr)
    return out


def bench_reference_torch() -> float:
    """The reference's train inner loop (vae-hpo.py:61-74) on torch CPU."""
    import torch
    import torch.nn.functional as F
    from torch import nn, optim

    torch.manual_seed(0)

    class VAE(nn.Module):
        # Architecture per /root/reference/vae-hpo.py:19-45.
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(784, HIDDEN)
            self.fc21 = nn.Linear(HIDDEN, LATENT)
            self.fc22 = nn.Linear(HIDDEN, LATENT)
            self.fc3 = nn.Linear(LATENT, HIDDEN)
            self.fc4 = nn.Linear(HIDDEN, 784)

        def forward(self, x):
            h = F.relu(self.fc1(x))
            mu, logvar = self.fc21(h), self.fc22(h)
            std = torch.exp(0.5 * logvar)
            z = mu + torch.randn_like(std) * std
            recon = torch.sigmoid(self.fc4(F.relu(self.fc3(z))))
            return recon, mu, logvar

    model = VAE()
    opt = optim.Adam(model.parameters(), lr=1e-3)
    data = torch.rand(BATCH, 784)

    def one_step():
        opt.zero_grad()
        recon, mu, logvar = model(data)
        bce = F.binary_cross_entropy(recon, data, reduction="sum")
        kld = -0.5 * torch.sum(1 + logvar - mu.pow(2) - logvar.exp())
        (bce + kld).backward()
        opt.step()

    for _ in range(3):
        one_step()
    t0 = time.perf_counter()
    for _ in range(TORCH_MEASURE_STEPS):
        one_step()
    dt = time.perf_counter() - t0
    return TORCH_MEASURE_STEPS * BATCH / dt


def bench_concurrency(num_trials: int) -> dict:
    """North-star metric (BASELINE.md): per-chip throughput of N
    concurrent trials, each on its own disjoint submesh, relative to one
    trial running alone on an identical submesh. Target: >= 0.90 at 8
    trials."""
    from multidisttorch_tpu.train.steps import create_train_state, make_multi_step

    groups, model, tx = _flagship_setup(num_trials)
    # Same TPU chunk sizing as the flagship timing (docs/DISPATCH.md):
    # 100-step chunks on real chips would make this measure the host
    # loop, not per-trial chip efficiency.
    chunk = _chunk_steps()
    key = jax.random.key(1)

    def setup_trial(g):
        state = create_train_state(g, model, tx, jax.random.key(g.group_id))
        step = make_multi_step(g, model, tx)
        # On-device generation straight into each trial's submesh
        # sharding (same no-host-transfer rationale as _timed_chunks).
        batches = jax.jit(
            lambda k: jax.random.uniform(
                k, (chunk, BATCH, 784), jnp.float32
            ),
            out_shardings=g.sharding(None, "data"),
        )(jax.random.key(0))
        return {"state": state, "step": step, "batches": batches}

    trials = [setup_trial(g) for g in groups]

    def run_chunks(active, nchunks):
        # Interleaved async dispatch: each trial's chunks queue on its own
        # disjoint submesh; the host never blocks until the end.
        for i in range(nchunks):
            for t in active:
                t["state"], _ = t["step"](
                    t["state"], t["batches"], jax.random.fold_in(key, i)
                )
        for t in active:
            jax.block_until_ready(t["state"].params)

    # warmup all compilations
    run_chunks(trials, 1)

    # trial 0 alone on its submesh
    t0 = time.perf_counter()
    run_chunks(trials[:1], MEASURE_CHUNKS)
    alone_sps = (
        MEASURE_CHUNKS * chunk * BATCH / (time.perf_counter() - t0)
    )

    # all trials concurrently
    t0 = time.perf_counter()
    run_chunks(trials, MEASURE_CHUNKS)
    dt = time.perf_counter() - t0
    # each trial did MEASURE_CHUNKS * chunk steps
    per_trial_sps = MEASURE_CHUNKS * chunk * BATCH / dt

    ndev = len(jax.devices())
    out = {
        "num_trials": num_trials,
        "chunk_steps": chunk,  # measurement-shape provenance (r5)
        "alone_samples_per_sec": round(alone_sps, 1),
        "concurrent_per_trial_samples_per_sec": round(per_trial_sps, 1),
        "aggregate_samples_per_sec": round(per_trial_sps * num_trials, 1),
        "efficiency_vs_alone": round(per_trial_sps / alone_sps, 3),
        "n_devices": ndev,
        # The north-star config is 8 trials x >=1 chip each (BASELINE.md,
        # >=0.90 efficiency). Say in the artifact itself when this
        # environment cannot measure that for real (VERDICT r1 weak #8):
        # fewer devices than trials = time-slicing one chip; virtual CPU
        # devices = every "device" shares the same host cores, so
        # efficiency_vs_alone is a methodology proof, not a hardware
        # number.
        "hardware_limited": ndev < num_trials
        or jax.default_backend() == "cpu",
    }
    if jax.default_backend() == "cpu":
        out["methodology_note"] = (
            "virtual CPU devices share one host's cores; "
            "efficiency_vs_alone is not hardware-representative"
        )
    elif ndev < num_trials:
        out["methodology_note"] = (
            f"{num_trials} trials time-sliced over {ndev} real device(s); "
            "north-star needs >=1 chip per trial"
        )
    return out


def bench_loader(rows: int = 60000, dim: int = 784, batch: int = BATCH) -> dict:
    """Host batch-assembly throughput: C++ prefetching gatherer
    (csrc/fastloader.cpp) vs the equivalent pure-numpy gather.

    The data path is the host-side hot loop of every sweep (SURVEY §7
    "hard parts": contention is host-side). Two conditions:

    - ``bare``: fetch batches back to back. This measures raw copy
      speed, where numpy fancy-indexing usually WINS — the native
      gatherer pays an extra copy-out. Recorded because an honest
      artifact must show where the native path does not help.
    - ``interleaved``: a bandwidth-heavy numpy matmul between fetches.
      Deliberately adversarial to the prefetch thread (the matmul
      releases the GIL and saturates memory bandwidth) — kept in the
      artifact as the native path's worst case.
    - ``train_loop`` (the headline): the REAL consumer — a
      ``TrialDataIterator`` feeding scan-fused train dispatches — with
      the native gatherer on vs off. This is the condition the
      auto-enable default is judged by: device dispatch holds the GIL
      briefly and leaves bandwidth idle, which is exactly when the
      background gather pays."""
    from multidisttorch_tpu.data import native

    rng = np.random.default_rng(0)
    images = rng.uniform(0, 1, (rows, dim)).astype(np.float32)
    perm = rng.permutation(rows)
    n_batches = rows // batch
    work_a = rng.normal(size=(256, 256)).astype(np.float32)

    def work():
        return work_a @ work_a

    def timed(fetch, interleave: bool) -> float:
        t0 = time.perf_counter()
        for _ in range(n_batches):
            fetch()
            if interleave:
                work()
        return n_batches * batch / (time.perf_counter() - t0)

    def numpy_fetch(i=[0]):
        j = i[0] % n_batches
        i[0] += 1
        return images[perm[j * batch : (j + 1) * batch]]

    out = {
        "bare": {
            "numpy_samples_per_sec": round(timed(numpy_fetch, False), 1)
        },
        "interleaved": {
            "numpy_samples_per_sec": round(timed(numpy_fetch, True), 1)
        },
        "native_available": native.available(),
    }
    if native.available():
        g = native.NativeBatchGatherer(images)
        for cond, interleave in (("bare", False), ("interleaved", True)):
            n = g.start_epoch(perm, batch)  # warm epoch per condition
            for _ in range(n):
                g.next_batch()
            n = g.start_epoch(perm, batch)
            sps = timed(g.next_batch, interleave)
            out[cond]["native_samples_per_sec"] = round(sps, 1)
            out[cond]["native_vs_numpy"] = round(
                sps / out[cond]["numpy_samples_per_sec"], 3
            )
        g.close()

    # Real-consumer condition runs either way (python-only rate still
    # meaningful without the native library).
    out["train_loop"] = _loader_train_loop(
        rows, batch, with_native=native.available()
    )
    return out


def _loader_train_loop(rows: int, batch: int, *, with_native: bool) -> dict:
    """Real-consumer loader A/B: one epoch of scan-fused training fed by
    TrialDataIterator with the native gatherer off vs on."""
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.data.sampler import TrialDataIterator
    from multidisttorch_tpu.train.steps import create_train_state, make_multi_step

    chunk = 10
    (trial,), model, tx = _flagship_setup(1)
    data = synthetic_mnist(rows, seed=0)
    key = jax.random.key(1)
    res = {}
    for use_native in (False, True) if with_native else (False,):
        it = TrialDataIterator(
            data, trial, batch, seed=0, use_native=use_native
        )
        state = create_train_state(trial, model, tx, jax.random.key(0))
        multi = make_multi_step(trial, model, tx)
        state, _ = multi(state, next(it.stream_chunks(chunk)), key)
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        n = 0
        for i, item in enumerate(it.epoch_chunks(1, chunk)):
            if item[1].shape[0] != chunk:
                break
            state, _ = multi(state, item[1], jax.random.fold_in(key, i))
            n += chunk * batch
        jax.block_until_ready(state.params)
        label = "native" if use_native else "python"
        res[label + "_samples_per_sec"] = round(
            n / (time.perf_counter() - t0), 1
        )
    if "native_samples_per_sec" in res:
        res["native_vs_python"] = round(
            res["native_samples_per_sec"] / res["python_samples_per_sec"], 3
        )
    return res


def bench_to_elbo(target: float, max_steps: int = 20000) -> dict:
    """BASELINE.json's second metric: HPO wall-clock to target ELBO.

    Trains the flagship VAE (reference defaults: batch 128, Adam 1e-3)
    on MNIST-shaped data until the per-sample train ELBO drops below
    ``target``, using the production fused dispatch; loss is checked
    once per chunk (the logging cadence), so the measurement includes
    exactly the syncs a real sweep pays.
    """
    from multidisttorch_tpu.data.datasets import load_mnist
    from multidisttorch_tpu.data.sampler import TrialDataIterator
    from multidisttorch_tpu.train.steps import create_train_state, make_multi_step

    chunk = 20
    (trial,), model, tx = _flagship_setup(1)
    data = load_mnist(train=True)
    it = TrialDataIterator(data, trial, BATCH, seed=0)
    state = create_train_state(trial, model, tx, jax.random.key(0))
    multi = make_multi_step(trial, model, tx)
    key = jax.random.key(1)

    # Compile outside the timed region (the sweep's one-off cost).
    warm = next(it.stream_chunks(chunk))
    state, _ = multi(state, warm, key)
    jax.block_until_ready(state.params)
    state = create_train_state(trial, model, tx, jax.random.key(0))

    steps = 0
    t0 = time.perf_counter()
    for batches in it.stream_chunks(chunk):
        state, metrics = multi(state, batches, jax.random.fold_in(key, steps))
        steps += chunk
        last = float(metrics["loss_sum"][-1]) / BATCH
        if last <= target or steps >= max_steps:
            break
    wall = time.perf_counter() - t0
    return {
        "target_elbo": target,
        "reached": last <= target,
        "final_per_sample_elbo": round(last, 3),
        "steps": steps,
        "wall_s": round(wall, 3),
        "synthetic_data": bool(getattr(data, "synthetic", False)),
    }


def main():
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--concurrency", type=int, default=None,
        help="measure N concurrent trials' per-chip efficiency instead of "
        "the default single-chip throughput metric",
    )
    parser.add_argument(
        "--to-elbo", type=float, default=None,
        help="measure wall-clock (s) until the per-sample train ELBO "
        "drops below this target (BASELINE.json's second metric)",
    )
    parser.add_argument(
        "--loader", action="store_true",
        help="measure host batch-assembly throughput: native C++ "
        "gatherer vs pure numpy",
    )
    parser.add_argument(
        "--lm", action="store_true",
        help="measure Transformer-LM training tokens/sec/chip + MFU "
        "(the MXU-bound headline the tiny VAE cannot provide)",
    )
    parser.add_argument(
        "--decode", action="store_true",
        help="measure KV-cached generation throughput "
        "(tokens/sec/chip — the bandwidth-bound serving metric)",
    )
    parser.add_argument(
        "--stacked", action="store_true",
        help="measure K stacked trials per dispatch (K in {1,2,4,8}): "
        "samples/sec/chip and dispatches per trial-step — the "
        "trial-stacking mode's banked evidence",
    )
    parser.add_argument(
        "--chaos", action="store_true",
        help="run the standard fault schedule (faults/harness.py) "
        "against run_hpo supervision: recovery of every injected infra "
        "fault, goodput (useful/executed steps), and bit-parity of "
        "recovered trials vs the fault-free sweep",
    )
    parser.add_argument(
        "--chaos-mh", action="store_true",
        help="run the ELASTIC multi-host chaos drill (CPU, 3 virtual "
        "hosts under tools/sweep_supervisor.py): kill one host "
        "mid-sweep, supervised world-shrink restart, ledger-driven "
        "trial migration, goodput + bit-parity of recovered trials "
        "(docs/RESILIENCE.md \"Elastic multi-host\")",
    )
    parser.add_argument(
        "--pbt", action="store_true",
        help="A/B fused-lane PBT (whole generation = one dispatch of "
        "the registered pbt_gen program) vs per-submesh PBT on the VAE "
        "workload: dispatches/generation, wall/generation, bit-parity "
        "of the population trajectory, and the compile-registry "
        "one-compile evidence (docs/PBT.md; banks "
        "artifacts/bench_pbt_*.json)",
    )
    parser.add_argument(
        "--coldstart", action="store_true",
        help="measure cold vs precompiled (AOT farm) vs cache-warm "
        "(quarantined persistent cache) trial-admission latency over a "
        "fixed multi-bucket sweep, with a bit-parity gate across all "
        "three paths (docs/COMPILE.md; banks "
        "artifacts/bench_coldstart_*.json)",
    )
    parser.add_argument(
        "--service", action="store_true",
        help="run the sweep-service acceptance drill (docs/SERVICE.md): "
        "a real daemon killed with SIGKILL mid-sweep and restarted with "
        "zero lost submissions, 2-tenant fair-share ratio within 10% of "
        "weights, queue-wait/placement-latency books, and a "
        "defragmentation event that demonstrably unblocks a starved "
        "large-shape trial (banks artifacts/bench_service_*.json)",
    )
    parser.add_argument(
        "--dataplane", action="store_true",
        help="measure the per-tenant data plane (docs/DATA.md): K=8 "
        "heterogeneous lanes (8 distinct datasets, one vmapped "
        "dispatch) with the pipelined sharded input path vs the "
        "synchronous reference — bytes/sec per host, input_bound_frac "
        "< 5% gate, fused-vs-per-lane bit parity, and co-packing "
        "across dataset boundaries (banks "
        "artifacts/bench_dataplane_*.json)",
    )
    parser.add_argument(
        "--pipeline", action="store_true",
        help="run the giant-model-trial drill (docs/PARALLEL.md): "
        "ZeRO sharded-update loss parity vs the replicated reference "
        "+ per-device optimizer bytes <= 1/n_data, a 2-stage MPMD "
        "pipelined trial placed by the service as an all-or-nothing "
        "vector of slice blocks, and measured bubble fraction within "
        "10% of the analytic (S-1)/(S-1+M) schedule model (banks "
        "artifacts/bench_pipeline_*.json)",
    )
    parser.add_argument(
        "--fabric", action="store_true",
        help="run the service-fabric acceptance drill (docs/SERVICE.md "
        "\"Service fabric\"): 2 replica daemons, one SIGKILLed with "
        "work outstanding — the survivor adopts the orphaned shard "
        "through a lease-fenced epoch claim with zero lost submissions "
        "and bit-identical re-homed trials; a deadline trial "
        "checkpoint-drain preempts best-effort lanes within the "
        "anti-thrash budget; and a 1M-submission discrete-event "
        "loadgen replay against the pure scheduler core (p99 "
        "placement latency, fairness <= 10%, deadline hit rate, "
        "churn; MDT_FABRIC_LOADGEN_N overrides the count); plus the "
        "elastic-topology drills (docs/SERVICE.md \"Shard "
        "topology\"): a shard_split_lost fault SIGKILLs the "
        "splitting replica BETWEEN split-handoff records and the "
        "adopter must close the seam zero-lost/no-double-own, "
        "stacked + pipelined placements evict-and-resume "
        "bit-identical, and the loadgen scenario zoo "
        "(coordinated_burst, split_storm; MDT_FABRIC_SCENARIO_N "
        "overrides) holds the elastic arm within 10% of static "
        "routing (banks artifacts/bench_fabric_*.json)",
    )
    parser.add_argument(
        "--ckpt", action="store_true",
        help="run the checkpoint data-plane drill (docs/RESILIENCE.md "
        "\"Checkpoint format v2\"): v1<->v2 bitwise restore parity "
        "across classic/stacked/ZeRO/pipelined trials, incremental "
        "delta ratio < 0.5x full-model bytes on a multi-epoch "
        "fine-tune cadence, and the snapshot-fast drain — victim "
        "slices freed without blocking on persist, ledger `preempted` "
        "only after the persist lands, RAM-snapshot re-place (banks "
        "artifacts/bench_ckpt_*.json)",
    )
    parser.add_argument(
        "--telemetry-ab", action="store_true",
        help="run ONLY the standing telemetry overhead A/B (the "
        "stacked K=4 dispatch loop, OFF vs ON with device books, "
        "anomaly observe, fleet tags AND submission-trace attribution "
        "on the ON side) and bank it — the observability CI job's "
        "<=2% gate (banks artifacts/bench_telemetry_ab_*.json)",
    )
    parser.add_argument(
        "--incidents", action="store_true",
        help="replay the incident-plane chaos drill (docs/INCIDENTS.md): "
        "one scenario per fault family — daemon loss, fence race, "
        "wedged collective, torn split, backend wedge, SLO burn, "
        "divergence storm, checkpoint rot, preemption, host loss, "
        "duplicate steal grant — each through its own telemetry scope, "
        "gated on a 100% fault->verdict confusion-matrix diagonal, a "
        "zero-false-positive no-fault soak, published flight-ring "
        "bundles, and the offline autopsy re-deriving the torn-split "
        "verdict; re-measures the standing <=2% telemetry A/B with the "
        "flight ring armed (banks artifacts/bench_incidents_*.json)",
    )
    parser.add_argument(
        "--zoo", action="store_true",
        help="run the loadgen scenario zoo (docs/OBSERVABILITY.md "
        "\"Control-plane books\"): every named scenario "
        "(diurnal_wave, tenant_burst, deadline_gaming, "
        "pipeline_whale_shrimp, dataset_thrash, coordinated_burst, "
        "split_storm) replayed through the production scheduler "
        "classes with the control-plane profiler armed — banks one "
        "artifact per scenario (SLO verdicts + per-phase flight "
        "books + throughput headline) as artifacts/zoo_<name>_*.json "
        "and folds each round into artifacts/ctlprof_ledger.jsonl "
        "with cross-round drift flags (MDT_ZOO_N overrides the "
        "per-scenario submission count)",
    )
    parser.add_argument(
        "--zoo-n", type=int, default=None,
        help="submissions per zoo scenario (overrides MDT_ZOO_N and "
        "the scenario defaults)",
    )
    parser.add_argument(
        "--suite", action="store_true",
        help="bank every measurement (flagship, fused-loss comparison, "
        "LM, to-elbo, loader) in one process, which compiles once and "
        "holds the chip once",
    )
    args = parser.parse_args()

    if sum(x is not None and x is not False
           for x in (args.concurrency, args.to_elbo, args.loader,
                     args.lm, args.suite, args.decode, args.stacked,
                     args.chaos, args.chaos_mh, args.coldstart,
                     args.pbt, args.service, args.dataplane,
                     args.pipeline, args.fabric, args.ckpt,
                     args.telemetry_ab, args.zoo, args.incidents)) > 1:
        parser.error("--concurrency/--to-elbo/--loader/--lm/--decode/"
                     "--suite/--stacked/--chaos/--chaos-mh/--coldstart/"
                     "--pbt/--service/--dataplane/--pipeline/--fabric/"
                     "--ckpt/--telemetry-ab/--zoo/--incidents are "
                     "mutually exclusive")

    if (args.stacked or args.chaos or args.chaos_mh or args.pbt
            or args.service or args.dataplane or args.pipeline
            or args.fabric or args.ckpt or args.telemetry_ab
            or args.incidents) and \
            "xla_force_host_platform_device_count" not in (
        os.environ.get("XLA_FLAGS", "")
    ):
        # The stacked protocol measures PACKING — 8 pending trials at K
        # lanes per single-device group — so the CPU fallback needs
        # multiple virtual devices (the same harness topology as
        # bench --concurrency / docs/DISPATCH.md). XLA parses this flag
        # at backend init, not at import, so setting it here (before
        # _ensure_backend's first jax.devices()) is effective; it shapes
        # only the host-platform client, so a real TPU's device count
        # is untouched.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )

    backend = _ensure_backend()
    if args.coldstart and backend["platform"] != "cpu":
        # The drill runs each mode's sweep in a fresh child process. A
        # chip belongs to one process at a time and this one now holds
        # it, so the children would hang waiting for it.
        raise SystemExit(
            "bench.py --coldstart is a CPU-world drill (it starts one "
            f"compile child per mode); refusing platform "
            f"{backend['platform']!r}. Run it with JAX_PLATFORMS=cpu."
        )

    from multidisttorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.suite:
        # On the chip the suite banks its evidence after every section,
        # to a unique per-run filename (ADVICE r4: a later degraded run
        # must never clobber a previously banked good capture) plus a
        # refreshed _latest alias at the end.
        bank_path = None
        if backend.get("platform") == "tpu":
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            bank_path = f"artifacts/bench_tpu_suite_{stamp}.json"

        def payload_for(results: dict) -> dict:
            flagship = results.get("flagship", {})
            return {
                "metric": "vae_train_samples_per_sec_per_chip",
                "value": flagship.get("samples_per_sec_per_chip")
                if isinstance(flagship, dict) else None,
                "unit": "samples/sec/chip",
                "vs_baseline": None,
                "detail": {**results, "backend": backend},
            }

        def bank(payload: dict) -> None:
            # Atomic replace: an in-place "w" rewrite would truncate
            # the artifact first, so a mid-write kill (the driver's
            # timeout) or disk-full would destroy every previously
            # banked section — the exact loss the incremental
            # checkpointing exists to prevent.
            tmp = bank_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, bank_path)

        def checkpoint(partial: dict) -> None:
            if bank_path:  # marked partial until the final write lands
                bank({**payload_for(partial), "partial": True})

        r = bench_suite(checkpoint)
        payload = payload_for(r)
        print(json.dumps(payload))  # the primary contract, always first
        if bank_path:
            try:
                bank(payload)
                with open("artifacts/bench_tpu_suite_latest.json", "w") as f:
                    json.dump({**payload, "banked_as": bank_path}, f)
                print(f"banked TPU suite artifact: {bank_path}",
                      file=sys.stderr)
            except OSError as e:
                print(f"artifact banking failed: {e!r}", file=sys.stderr)
        return

    if args.lm:
        r = bench_lm()
        r.update(backend)
        print(
            json.dumps(
                {
                    "metric": "lm_train_tokens_per_sec_per_chip",
                    "value": r["tokens_per_sec_per_chip"],
                    "unit": "tokens/sec/chip",
                    "vs_baseline": None,
                    "mfu": r["mfu"],
                    "detail": r,
                }
            )
        )
        return

    if args.decode:
        r = bench_decode()
        r.update(backend)
        print(
            json.dumps(
                {
                    "metric": "lm_decode_tokens_per_sec_per_chip",
                    "value": r["decode_tokens_per_sec_per_chip"],
                    "unit": "tokens/sec/chip",
                    "vs_baseline": None,
                    "detail": r,
                }
            )
        )
        return

    if args.loader:
        r = bench_loader()
        r.update(backend)
        tl = r["train_loop"]
        # Headline is always a train-loop rate — python-path when the
        # native library is absent, never the bare memcpy number (three
        # orders of magnitude larger and not comparable).
        print(
            json.dumps(
                {
                    "metric": "loader_train_loop_throughput",
                    "value": tl.get(
                        "native_samples_per_sec",
                        tl["python_samples_per_sec"],
                    ),
                    "unit": "samples/sec",
                    "vs_baseline": tl.get("native_vs_python"),
                    "detail": r,
                }
            )
        )
        return

    if args.coldstart:
        import tempfile

        from multidisttorch_tpu.compile.coldstart import run_coldstart_bench

        r = run_coldstart_bench(tempfile.mkdtemp(prefix="bench_coldstart_"))
        r["backend"] = backend
        # Bank the artifact (ISSUE 7 acceptance): a timestamped file so
        # a later degraded run never clobbers banked evidence, plus a
        # _latest alias for the CI gate/console.
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_coldstart_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_coldstart_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        print(
            json.dumps(
                {
                    "metric": "coldstart_admission_speedup_precompiled",
                    "value": r["speedup_cold_over_precompiled"],
                    "unit": "x (cold mean / precompiled mean)",
                    # acceptance floor: >= 2x on the multi-bucket sweep
                    "vs_baseline": (
                        round(r["speedup_cold_over_precompiled"] / 2.0, 3)
                        if r["speedup_cold_over_precompiled"] is not None
                        else None
                    ),
                    "parity": r["parity"],
                    "admission_blocked_on_compile": r[
                        "admission_blocked_on_compile"
                    ],
                    "cache_warm_below_precompiled": r[
                        "cache_warm_below_precompiled"
                    ],
                    "cache_verdict": r["cache_verdict"],
                    "passed": r["passed"],
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.chaos:
        import tempfile

        from multidisttorch_tpu.faults.harness import run_chaos_bench

        # Telemetry lands in artifacts/ (not the throwaway work dir):
        # the Perfetto trace where every injected fault, retry, and
        # lane refill appears as a tagged event is part of the chaos
        # run's banked evidence (ISSUE 3 acceptance).
        tel_dir = os.path.join("artifacts", "chaos_telemetry")
        try:
            os.makedirs(tel_dir, exist_ok=True)
        except OSError:
            tel_dir = None  # harness falls back to the work dir
        r = run_chaos_bench(
            tempfile.mkdtemp(prefix="bench_chaos_"),
            telemetry_dir=tel_dir,
        )
        r["backend"] = backend
        tel = r.get("telemetry") or {}
        print(
            json.dumps(
                {
                    "metric": "chaos_goodput_useful_over_executed_steps",
                    "value": r["goodput"],
                    "unit": "fraction",
                    # acceptance floor: goodput >= 0.8 of fault-free
                    "vs_baseline": round(r["goodput"] / 0.8, 3),
                    "all_infra_faults_recovered": r[
                        "all_infra_faults_recovered"
                    ],
                    "final_metrics_bit_identical": r[
                        "final_metrics_bit_identical"
                    ],
                    "telemetry_trace": tel.get("trace"),
                    "all_faults_traced": tel.get("all_faults_traced"),
                    "detail": r,
                }
            )
        )
        return

    if args.chaos_mh:
        import tempfile

        from multidisttorch_tpu.faults.harness import run_chaos_mh_bench

        r = run_chaos_mh_bench(tempfile.mkdtemp(prefix="bench_chaos_mh_"))
        r["backend"] = backend
        fleet = r["fleet"]
        # The merged fleet artifacts land in artifacts/ (not the
        # throwaway work dir): the cross-host trace + summary ARE the
        # drill's banked evidence (ISSUE 6 acceptance), same policy as
        # --chaos's telemetry dir.
        bank_dir = os.path.join("artifacts", "chaos_mh_fleet")
        try:
            import shutil

            os.makedirs(bank_dir, exist_ok=True)
            banked = {}
            for key, src in fleet["paths"].items():
                if src and os.path.exists(src):
                    dst = os.path.join(bank_dir, os.path.basename(src))
                    shutil.copyfile(src, dst)
                    banked[key] = dst
            fleet["banked_paths"] = banked
        except OSError as e:
            fleet["banked_paths"] = {"error": repr(e)[:200]}
        print(
            json.dumps(
                {
                    "metric": "chaos_mh_goodput_useful_over_executed_steps",
                    "value": r["goodput"],
                    "unit": "fraction",
                    # acceptance floor: goodput >= 0.8 with 1-of-3
                    # hosts killed mid-sweep and the world re-formed
                    "vs_baseline": round(r["goodput"] / 0.8, 3),
                    "all_trials_settled": r["all_trials_settled"],
                    "recovered_bit_identical": r["recovered_bit_identical"],
                    "worlds_formed": r["worlds_formed"],
                    "hosts_lost": r["hosts_lost"],
                    # fleet observability gates (ISSUE 6): ONE merged
                    # skew-corrected timeline spanning every host and
                    # world, fired faults + the shrink present in it,
                    # and a non-null restart-tax breakdown
                    "all_hosts_traced": fleet["all_hosts_traced"],
                    "all_faults_traced": fleet["all_faults_traced"],
                    "restart_tax_nonnull": fleet["restart_tax_nonnull"],
                    "fleet_trace": fleet["banked_paths"].get(
                        "trace", fleet["paths"].get("trace")
                    ),
                    "fleet_summary": fleet["banked_paths"].get(
                        "summary", fleet["paths"].get("summary")
                    ),
                    "detail": r,
                }
            )
        )
        return

    if args.pipeline:
        r = bench_pipeline()
        r["backend"] = backend
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_pipeline_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_pipeline_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        print(
            json.dumps(
                {
                    "metric": "pipeline_measured_bubble_fraction",
                    "value": (
                        r["schedule"]["measured_bubble"]
                        if r["schedule"]
                        else None
                    ),
                    "unit": "idle fraction of the 2-stage GPipe "
                    "schedule at M=4 (analytic (S-1)/(S-1+M) = "
                    f"{r['schedule']['analytic_bubble'] if r['schedule'] else None})",
                    # acceptance: sharded-update parity + 1/n optimizer
                    # bytes, all-or-nothing vector placement by the
                    # service, bubble within 10% of the model, stage
                    # parity vs the single-mesh reference. Wall-clock
                    # recorded, not gated.
                    "optimizer_bytes_ratio": r["sharded_update"][
                        "optimizer_bytes_ratio"
                    ],
                    "ok": all(r["gates"].values()),
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.dataplane:
        r = bench_dataplane()
        r["backend"] = backend
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_dataplane_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_dataplane_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        print(
            json.dumps(
                {
                    "metric": "dataplane_host_to_device_bytes_per_s",
                    "value": r["bytes_per_s_per_host"],
                    "unit": "bytes/sec/host at K=8 heterogeneous lanes "
                    "(pipelined)",
                    # acceptance: fused dispatch bit-identical to the
                    # per-lane reference, input_bound_frac < 5% with
                    # the pipeline ON, co-packing across datasets
                    # preserved; wall ratio recorded, not gated.
                    "vs_baseline": r["wall_ratio_sync_over_pipelined"],
                    "input_bound_frac": [
                        r["synchronous"]["input_bound_frac"],
                        r["pipelined"]["input_bound_frac"],
                    ],
                    "ok": all(r["gates"].values()),
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.telemetry_ab:
        # The standing <=2% budget, standalone (the observability CI
        # job's gate): same protocol as the --stacked block, but
        # without the rest of the stacked artifact — the ON side
        # carries device books + anomaly observe + fleet tags +
        # submission-trace attribution.
        r = {"protocol": "telemetry_ab_v2", "backend": backend}
        r["telemetry_overhead"] = bench_telemetry_overhead()
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_telemetry_ab_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_telemetry_ab_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        ab = r["telemetry_overhead"]
        print(
            json.dumps(
                {
                    "metric": "telemetry_overhead_frac_tracing_on",
                    "value": ab.get("overhead_frac"),
                    "unit": "fractional step-time overhead, ON vs OFF "
                    "(min-of-passes, interleaved; ON = mark + device "
                    "books + anomaly + fleet tags + trace attribution)",
                    "within_2pct": ab.get("within_2pct"),
                    "per_mark_cost_us": ab.get("per_mark_cost_us"),
                    "ok": bool(ab.get("within_2pct")),
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.incidents:
        import contextlib
        import tempfile

        from multidisttorch_tpu.service.incident_drill import (
            run_incidents_bench,
        )

        # MDT_INCIDENT_KEEP_SCOPES pins the scenario scope dirs to a
        # survivable path (CI uploads the ledgers + bundles from there);
        # unset, each run gets a throwaway tempdir.
        work = os.environ.get("MDT_INCIDENT_KEEP_SCOPES")
        if work:
            os.makedirs(work, exist_ok=True)
        else:
            work = tempfile.mkdtemp(prefix="bench_incidents_")

        # The drill and the A/B narrate; keep the one-JSON-line stdout
        # contract by routing their prints to stderr.
        with contextlib.redirect_stdout(sys.stderr):
            r = run_incidents_bench(work)
            r["telemetry_overhead"] = bench_telemetry_overhead()
        r["backend"] = backend
        ab = r["telemetry_overhead"]
        r["gates"]["ab_within_2pct_ring_on"] = bool(ab.get("within_2pct"))
        r["ok"] = bool(r["ok"] and ab.get("within_2pct"))
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_incidents_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1, default=str)
            os.replace(tmp, banked)
            latest = "artifacts/bench_incidents_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1,
                          default=str)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        diag = sum(
            1 for sc in r["scenarios"].values() if sc["ok"]
        )
        print(
            json.dumps(
                {
                    "metric": "incident_confusion_diagonal",
                    "value": f"{diag}/{len(r['scenarios'])}",
                    "unit": "chaos scenarios producing exactly one "
                    "incident with the expected root-cause verdict "
                    "(gate: all, plus zero-incident soak, published "
                    "flight-ring bundles, offline autopsy agreement, "
                    "and the <=2% telemetry A/B with the ring armed)",
                    "soak_incidents": r["soak"]["n_incidents"],
                    "autopsy_verdict": r["autopsy"].get("verdict"),
                    "ab_overhead_frac": ab.get("overhead_frac"),
                    **r["gates"],
                    "ok": r["ok"],
                    "banked": banked,
                }
            )
        )
        if not r["ok"]:
            sys.exit(1)
        return

    if args.ckpt:
        import tempfile

        from multidisttorch_tpu.service.ckpt_drill import run_ckpt_bench

        r = run_ckpt_bench(tempfile.mkdtemp(prefix="bench_ckpt_"))
        r["backend"] = backend
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_ckpt_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_ckpt_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        prim = r["drain_primitive"]
        print(
            json.dumps(
                {
                    "metric": "ckpt_snapshot_drain_to_slices_freed_s",
                    "value": prim["arms"]["snapshot_v2"][
                        "drain_to_slices_freed_s"
                    ],
                    "vs_v1_full_persist_drain_s": prim["arms"][
                        "join_v1"
                    ]["drain_to_slices_freed_s"],
                    "speedup": prim["speedup"],
                    "unit": "seconds (wall ratios recorded, not "
                    "gated, on shared runners; the structural gates "
                    "below are what CI enforces)",
                    # acceptance: v2 restores bitwise-identical to v1
                    # across all four trial flavors; incremental saves
                    # < 0.5x full-model bytes on the fine-tune delta
                    # run; drain frees slices without blocking on
                    # persist + ledger honesty + RAM re-place.
                    **r["gates"],
                    "delta_ratio": r["delta"]["finetune"][
                        "delta_ratio_mean"
                    ],
                    "full_adam_contrast_ratio": r["delta"][
                        "full_adam_contrast"
                    ]["delta_ratio_mean"],
                    "ok": r["ok"],
                    "banked": banked,
                },
                indent=2,
            )
        )
        if not r["ok"]:
            sys.exit(1)
        return

    if args.zoo:
        from multidisttorch_tpu.service.loadgen import (
            run_scenario,
            zoo_names,
        )
        from multidisttorch_tpu.telemetry import ctlprof as _ctlprof

        n = args.zoo_n
        if n is None:
            env_n = os.environ.get("MDT_ZOO_N", "")
            n = int(env_n) if env_n else None
        os.makedirs("artifacts", exist_ok=True)
        stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
        platform = backend.get("platform", "cpu")
        ledger_path = "artifacts/ctlprof_ledger.jsonl"
        scenarios: dict = {}
        ok = True
        for name in zoo_names():
            # The sims are pure host logic but can narrate; keep the
            # one-JSON-line stdout contract.
            with contextlib.redirect_stdout(sys.stderr):
                art = run_scenario(
                    name,
                    n_submissions=n,
                    flame_path=f"artifacts/zoo_{name}_ctl_flame.txt",
                )
            art["backend"] = backend
            banked = None
            # Bank the Perfetto control-plane track standalone (CI
            # uploads it); the envelope keeps books only.
            ctl_trace = art.pop("ctl_trace", None)
            try:
                if ctl_trace and ctl_trace.get("traceEvents"):
                    tp = f"artifacts/zoo_{name}_ctl_trace.json"
                    with open(tp + ".tmp", "w") as f:
                        json.dump(ctl_trace, f)
                    os.replace(tp + ".tmp", tp)
                banked = f"artifacts/zoo_{name}_{platform}_{stamp}.json"
                tmp = banked + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(art, f, indent=1)
                os.replace(tmp, banked)
                latest = f"artifacts/zoo_{name}_latest.json"
                with open(latest + ".tmp", "w") as f:
                    json.dump({**art, "banked_as": banked}, f, indent=1)
                os.replace(latest + ".tmp", latest)
            except OSError as e:
                print(f"artifact banking failed: {e!r}", file=sys.stderr)
                banked = None
            folded = _ctlprof.fold_ledger_round(
                ledger_path,
                _ctlprof.ledger_record(
                    "zoo",
                    name,
                    art["ctl"],
                    platform=platform,
                    stamp=stamp,
                    n_submissions=art["spec"].get("n_submissions"),
                    submissions_per_wall_s=art["headline"][
                        "submissions_per_wall_s"
                    ],
                    slo_met=art["headline"]["slo_met"],
                    zero_lost=art["headline"]["zero_lost"],
                ),
            )
            scenario_ok = all(bool(v) for v in art["gates"].values())
            ok = ok and scenario_ok
            scenarios[name] = {
                "ok": scenario_ok,
                "gates": art["gates"],
                "headline": art["headline"],
                "vs_prev_rounds": folded.get("vs_prev_rounds"),
                "banked_as": banked,
            }
        print(
            json.dumps(
                {
                    "metric": "zoo_scenarios_ok",
                    "value": ok,
                    "unit": f"{len(scenarios)} named scenarios, "
                    "production scheduler classes under the "
                    "control-plane profiler",
                    # acceptance: every scenario's SLO verdicts +
                    # zero-lost hold, and every artifact carries
                    # per-phase control-plane flight books; drift
                    # vs prior ledger rounds is recorded, not gated.
                    "scenarios": scenarios,
                    "ledger": ledger_path,
                    "ok": ok,
                }
            )
        )
        return

    if args.fabric:
        import tempfile

        from multidisttorch_tpu.service.fabric_drill import (
            run_fabric_bench,
        )

        # The drills run real services in-process and their drivers
        # narrate (retry resumes etc.) on stdout; bench's stdout
        # contract is exactly ONE JSON line, so the narration joins
        # the diagnostics on stderr.
        with contextlib.redirect_stdout(sys.stderr):
            r = run_fabric_bench(tempfile.mkdtemp(prefix="bench_fabric_"))
        r["backend"] = backend
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_fabric_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_fabric_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        # CI-uploadable evidence next to the banked JSON: the split
        # drill's topology log (the elastic fabric's flight recorder)
        # and the failover drill's merged trace export.
        try:
            import shutil as _sh

            _sh.copy(
                r["split_chaos"]["topology"]["log_path"],
                "artifacts/fabric_topology_log.jsonl",
            )
            for k, p in r["failover"]["trace"]["exported"].items():
                _sh.copy(p, f"artifacts/fabric_trace_{k}.json")
        except (OSError, KeyError) as e:
            print(f"evidence copy failed: {e!r}", file=sys.stderr)
        lg = r["loadgen"]
        # The full replay is the ctlprof ledger's BASELINE round: the
        # pre-rebuild per-phase control-plane cost alongside
        # submissions/s — the row the raw-speed rebuild (ROADMAP item
        # 4's incremental indexes) must visibly move.
        try:
            from multidisttorch_tpu.telemetry import ctlprof as _ctlprof

            _ctlprof.fold_ledger_round(
                "artifacts/ctlprof_ledger.jsonl",
                _ctlprof.ledger_record(
                    "baseline",
                    f"fabric_replay_{lg['spec']['n_submissions']}",
                    lg.get("ctl") or {},
                    platform=backend.get("platform", "cpu"),
                    stamp=time.strftime(
                        "%Y%m%d_%H%M%S", time.gmtime()
                    ),
                    n_submissions=lg["spec"]["n_submissions"],
                    submissions_per_wall_s=lg["submissions_per_wall_s"],
                    slo_met=lg["slo"]["met"],
                    zero_lost=lg["zero_lost"],
                ),
            )
        except (OSError, KeyError) as e:
            print(f"ctlprof ledger fold failed: {e!r}", file=sys.stderr)
        print(
            json.dumps(
                {
                    "metric": "fabric_loadgen_p99_placement_latency_s",
                    "value": lg["placement_latency_s"].get("p99"),
                    "unit": "virtual seconds at "
                    f"{lg['submitted']} submissions (overload "
                    "regime, pure scheduler core at simulation "
                    "speed)",
                    # acceptance: replica SIGKILL with work
                    # outstanding -> survivor adopts the shard, zero
                    # lost, re-homed trials bit-identical; deadline
                    # preemption within the anti-thrash budget; 1M
                    # loadgen fairness <= 10% + deadline hit rate.
                    "kill_exercised": r["failover"]["kill_exercised"],
                    "zero_lost": r["failover"]["zero_lost"],
                    "rehomed_bit_identical": r["failover"]["parity"][
                        "bit_identical"
                    ],
                    "deadline_drill_ok": r["deadline"]["ok"],
                    # Elastic topology (ISSUE 17): the kill-mid-split
                    # seam closed by the adopter, movable stacked/
                    # pipelined placements, scenario zoo within 10%
                    # of static routing.
                    "split_kill_exercised": r["split_chaos"][
                        "split_kill_exercised"
                    ],
                    "split_zero_lost": r["split_chaos"]["zero_lost"],
                    "split_no_double_own": r["split_chaos"][
                        "no_double_own"
                    ],
                    "stacked_evict_resume_bit_identical": r["movable"][
                        "stacked"
                    ]["bit_identical"],
                    "pipelined_evict_resume_bit_identical": r["movable"][
                        "pipelined"
                    ]["bit_identical"],
                    "scenario_gates_ok": r["fabric_scenarios"]["ok"],
                    "fairness_max_abs_ratio_error": lg["fairness"][
                        "max_abs_ratio_error"
                    ],
                    "deadline_hit_rate": lg["deadline"]["hit_rate"],
                    "churn_per_1k_placements": lg["churn"][
                        "evictions_per_1k_placements"
                    ],
                    "submissions_per_wall_s": lg[
                        "submissions_per_wall_s"
                    ],
                    "ok": r["ok"],
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.service:
        import tempfile

        from multidisttorch_tpu.service.drill import run_service_bench

        r = run_service_bench(tempfile.mkdtemp(prefix="bench_service_"))
        r["backend"] = backend
        # Bank the scheduling artifact (ISSUE 10 acceptance):
        # timestamped + _latest alias, same policy as --pbt/--coldstart.
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_service_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_service_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        fair = r["kill_restart"]["fair_share"]
        print(
            json.dumps(
                {
                    "metric": "service_contended_fair_share_ratio",
                    "value": fair["contended_ratio"],
                    "unit": "tenant-A/tenant-B contended placements "
                    "(weights 2:1)",
                    # acceptance: ratio within 10% of the weights,
                    # zero lost submissions across SIGKILL+restart,
                    # and a defrag event unblocking a starved trial
                    "vs_baseline": (
                        round(
                            fair["contended_ratio"]
                            / fair["expected_ratio"],
                            3,
                        )
                        if fair["contended_ratio"] is not None
                        else None
                    ),
                    "zero_lost_submissions": r["gates"][
                        "zero_lost_submissions"
                    ],
                    "tenant_goodput": r["kill_restart"]["tenant_goodput"],
                    "defrag_unblocks_starved_trial": r["gates"][
                        "defrag_unblocks_starved_trial"
                    ],
                    "queue_wait_p50_p99": [
                        (r["kill_restart"].get("queue_wait") or {}).get(
                            "p50_s"
                        ),
                        (r["kill_restart"].get("queue_wait") or {}).get(
                            "p99_s"
                        ),
                    ],
                    "placement_p50_p99": [
                        (
                            r["kill_restart"].get("placement_latency")
                            or {}
                        ).get("p50_s"),
                        (
                            r["kill_restart"].get("placement_latency")
                            or {}
                        ).get("p99_s"),
                    ],
                    "ok": r["ok"],
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.pbt:
        r = bench_pbt()
        r["backend"] = backend
        # Bank the artifact (ISSUE 8 acceptance): timestamped file so a
        # later degraded run never clobbers banked evidence, plus a
        # _latest alias for the CI gate/console — same policy as
        # --coldstart.
        banked = None
        try:
            os.makedirs("artifacts", exist_ok=True)
            stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
            platform = backend.get("platform", "cpu")
            banked = f"artifacts/bench_pbt_{platform}_{stamp}.json"
            tmp = banked + ".tmp"
            with open(tmp, "w") as f:
                json.dump(r, f, indent=1)
            os.replace(tmp, banked)
            latest = "artifacts/bench_pbt_latest.json"
            with open(latest + ".tmp", "w") as f:
                json.dump({**r, "banked_as": banked}, f, indent=1)
            os.replace(latest + ".tmp", latest)
        except OSError as e:
            print(f"artifact banking failed: {e!r}", file=sys.stderr)
            banked = None
        print(
            json.dumps(
                {
                    "metric": "pbt_fused_dispatch_reduction",
                    "value": r["dispatch_reduction"],
                    "unit": "x fewer dispatches/generation (fused vs "
                    "per-submesh)",
                    # acceptance floor: >= 3x at K=4 with bit-identical
                    # trajectory
                    "vs_baseline": (
                        round(r["dispatch_reduction"] / 3.0, 3)
                        if r["dispatch_reduction"] is not None
                        else None
                    ),
                    "parity": r["parity"],
                    "final_states_bit_identical": r[
                        "final_states_bit_identical"
                    ],
                    "registry_one_compile_cache_hit": r[
                        "compile_registry"
                    ]["one_compile_cache_hit_gen2plus"],
                    "wall_ratio_submesh_over_fused": r[
                        "wall_ratio_submesh_over_fused"
                    ],
                    "banked_as": banked,
                    "detail": r,
                }
            )
        )
        return

    if args.stacked:
        r = bench_stacked()
        k4 = next(
            (lvl for lvl in r["levels"] if lvl["k"] == 4), r["levels"][-1]
        )
        r.update(backend)
        print(
            json.dumps(
                {
                    "metric": "stacked_vae_samples_per_sec_per_chip",
                    "value": k4["samples_per_sec_per_chip"],
                    "unit": "samples/sec/chip",
                    # the acceptance ratio: stacked K=4 over K=1, same
                    # protocol, same hardware, same timed window count
                    "vs_baseline": r["k4_vs_k1"],
                    "detail": r,
                }
            )
        )
        return

    if args.to_elbo is not None:
        r = bench_to_elbo(args.to_elbo)
        r.update(backend)
        print(
            json.dumps(
                {
                    "metric": "hpo_wallclock_to_target_elbo",
                    "value": r["wall_s"],
                    "unit": "seconds",
                    "vs_baseline": None,
                    "detail": r,
                }
            )
        )
        return

    if args.concurrency is not None and args.concurrency < 1:
        parser.error(f"--concurrency must be >= 1, got {args.concurrency}")
    if args.concurrency is not None:
        r = bench_concurrency(args.concurrency)
        r.update(backend)
        print(
            json.dumps(
                {
                    "metric": "concurrent_trial_efficiency",
                    "value": r["efficiency_vs_alone"],
                    "unit": "frac_of_single_trial_throughput",
                    "vs_baseline": round(r["efficiency_vs_alone"] / 0.90, 3),
                    "detail": r,
                }
            )
        )
        return

    flagship_stats = bench_ours()
    ours = flagship_stats["samples_per_sec_per_chip"]
    try:
        ref = bench_reference_torch()
    except Exception as e:
        print(f"reference torch bench failed: {e!r}", file=sys.stderr)
        ref = float("nan")
    vs = ours / ref if ref == ref and ref > 0 else float("nan")
    # MFU: hardware-meaningful single-chip framing (VERDICT r1 weak #3) —
    # fraction of the chip's peak dense bf16 FLOP/s the train loop
    # sustains. None off-TPU or on unknown device kinds.
    peak = (
        _peak_flops_per_chip(backend.get("device_kind", ""))
        if backend.get("platform") not in (None, "cpu")
        else None
    )
    mfu = (ours * _train_flops_per_sample() / peak) if peak else None
    detail = dict(backend)
    detail["flagship_passes"] = flagship_stats
    if peak:
        detail["peak_flops_per_chip"] = peak
        detail["train_flops_per_sample"] = _train_flops_per_sample()
    if jax.default_backend() == "tpu":
        # Kernel-vs-XLA decision data (only meaningful on hardware). A
        # kernel the chip refuses fails the run.
        detail["fused_loss_comparison"] = bench_fused_loss_comparison()
    print(
        json.dumps(
            {
                "metric": "vae_train_samples_per_sec_per_chip",
                "value": round(ours, 1),
                "unit": "samples/sec/chip",
                "vs_baseline": round(vs, 3) if vs == vs else None,
                "mfu": round(mfu, 5) if mfu is not None else None,
                "detail": detail,
            }
        )
    )


if __name__ == "__main__":
    main()
