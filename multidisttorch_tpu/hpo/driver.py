"""Host-side HPO driver: N concurrent trials on N disjoint submeshes.

Rebuild of the reference's trial dispatch (``/root/reference/
vae-hpo.py:177-202``), where each process loops over all groups, finds
the one it belongs to, and runs a DDP trial whose only hyperparameter is
``epochs + group_id``. Redesigned per SURVEY.md §7:

- **Real per-trial configs** (:class:`TrialConfig`: lr, β, epochs,
  batch size, seed, model dims — generalizing quirk Q7).
- **Cooperative round-robin dispatch**: all trials' jit steps are
  enqueued from one host loop; JAX's async dispatch keeps every submesh
  busy while the host cycles. A fast trial finishes and frees its
  submesh immediately — **no cross-trial barrier anywhere** (fixes Q3,
  where the reference's world-scoped barriers serialize the sweep on the
  slowest trial).
- **Per-trial output dirs** ``{out_dir}/trial-{id}/`` (fixes Q4's
  ``results-{rank}`` collision where group 0 and 1 overwrite each
  other's PNGs).
- In multi-controller SPMD each process runs only the trials whose
  submesh intersects its local devices (``TrialMesh.is_local_member``) —
  the same membership contract as the reference's
  ``dist.get_rank(group) >= 0`` (``vae-hpo.py:201``).
- **Elastic scheduling**: more configs than submeshes is legal — the
  reference hard-binds one trial per group forever (``vae-hpo.py:
  200-202``); here freed submeshes immediately pick up the next queued
  config (greedy single-controller; deterministic least-predicted-load
  assignment multi-controller — :func:`balanced_assignment` — where
  every process must schedule identically without communicating).
- **Failure isolation** (``resilient=True``): one trial's exception
  marks that trial failed and frees its submesh; the rest of the sweep
  proceeds. The reference has no failure handling at all — a dead rank
  hangs every world barrier (SURVEY.md §5).
- **Checkpoint/resume** (``resume=True``): per-epoch checkpoints; a
  re-run restores each trial at its last completed epoch (or skips it
  entirely if done). The reference persists nothing but PNGs.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field, asdict
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from multidisttorch_tpu.data.datasets import Dataset
from multidisttorch_tpu.data.sampler import (
    EvalDataIterator,
    StackedTrialDataIterator,
    TrialDataIterator,
)
from multidisttorch_tpu.hpo.ledger import SweepLedger, config_hash
from multidisttorch_tpu.hpo.supervision import (
    DIVERGENCE,
    FATAL,
    INFRA,
    PREEMPTION,
    RetryPolicy,
    UnretryableError,
    classify_failure,
)
from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.parallel.mesh import TrialMesh, setup_groups
from multidisttorch_tpu.train.checkpoint import (
    RAM_SNAPSHOT,
    default_format,
    restore_latest_valid,
    restore_state,
    save_state,
    snapshot_cache,
)
from multidisttorch_tpu.train.guards import DivergenceError, check_finite
from multidisttorch_tpu.train.steps import (
    TrialHypers,
    build_lane_state,
    create_stacked_train_state,
    create_train_state,
    make_eval_step,
    make_lane_ops,
    make_multi_step,
    make_sample_step,
    make_stacked_eval_step,
    make_stacked_multi_step,
    make_stacked_train_step,
    make_train_step,
    state_shardings,
    wrap_step_with_hooks,
)
from multidisttorch_tpu.telemetry import device as tele_device
from multidisttorch_tpu.telemetry.anomaly import get_monitor
from multidisttorch_tpu.telemetry.events import get_bus
from multidisttorch_tpu.telemetry.metrics import get_registry
from multidisttorch_tpu.utils.compile_cache import enable_compile_cache
from multidisttorch_tpu.utils.imaging import save_image_grid
from multidisttorch_tpu.utils.logging import log0, log0_enabled


@dataclass(frozen=True)
class TrialConfig:
    """One trial's hyperparameters (the reference's single knob was
    ``epochs + group_id``, ``vae-hpo.py:202``)."""

    trial_id: int
    epochs: int = 3
    batch_size: int = 128
    lr: float = 1e-3  # reference Adam lr, vae-hpo.py:131
    beta: float = 1.0
    seed: int = 0
    hidden_dim: int = 400
    latent_dim: int = 20
    log_interval: int = 10  # reference train log cadence, vae-hpo.py:61
    # Train steps fused into one device dispatch (make_multi_step's
    # lax.scan). 1 = the reference's one-dispatch-per-batch loop shape;
    # >1 amortizes host dispatch, the dominant cost at this model size.
    # Changes the per-step RNG stream (keys are split per chunk instead
    # of folded per step), so it participates in the resume
    # config-match check like any other hyperparameter.
    fused_steps: int = 1
    # Reference-parity eval semantics: the reference's test() runs the
    # full sampled forward (z drawn from the posterior —
    # /root/reference/vae-hpo.py:101-105 calling model(data), :42-45).
    # Default False = posterior-mean eval (deterministic, strictly
    # tighter bound); True reproduces the reference's sampled test-loss
    # metric for apples-to-apples quality comparison.
    eval_sampled: bool = False
    # Rematerialize activations in the backward pass (jax.checkpoint):
    # trade recompute FLOPs for HBM when the model or the fused-steps
    # scan outgrows device memory. Numerically identical training.
    remat: bool = False
    # Gradient accumulation: split each batch into this many equal
    # microbatches, accumulate grads in-step, one optimizer update —
    # the effective batch size can exceed HBM. Composes with remat.
    grad_accum: int = 1
    # Per-trial dataset reference (docs/DATA.md): "" = the sweep's
    # shared train_data (the pre-ref behavior, byte-compatible). A
    # non-empty spec ("synthetic-mnist?rows=512&seed=3", "file:...",
    # "cas:<sha256>") resolves through data/store.resolve_dataset — the
    # service resolves it against its content-addressed cache at
    # admission, run_hpo at sweep entry. It participates in the config
    # hash and the resume config-match like any other hyperparameter
    # (weights trained on one dataset must not silently resume under
    # another). Trials with DIFFERENT datasets of the same shape class
    # still co-pack into one stacked bucket (heterogeneous lanes).
    dataset: str = ""
    # ZeRO-style sharded weight update (docs/PARALLEL.md): partition
    # the Adam moments over the trial submesh's data axis — GSPMD
    # reduce-scatters the gradient into the owned shard's update and
    # all-gathers the fresh params (arXiv 2004.13336). Params stay
    # replicated, so the forward/backward is the plain DDP program;
    # per-device optimizer memory drops to ~1/n_data of replicated.
    # Runs the classic (unstacked) path; no-op on 1-device submeshes.
    zero_update: bool = False
    # Cross-submesh MPMD pipeline parallelism (docs/PARALLEL.md): >1
    # makes this trial a VECTOR of slice requests — each stage owns its
    # own submesh and programs, driven on a GPipe microbatch schedule
    # with device_put transfers between stages. `grad_accum` doubles as
    # the microbatch count M (the schedule IS gradient accumulation;
    # the single-mesh grad_accum=M step is the parity reference).
    # Placed by the sweep service (all-or-nothing multi-block) or run
    # directly via hpo.pipeline_run.run_pipeline_trial; run_hpo's
    # equal-groups carve cannot host it and rejects such configs.
    pipeline_stages: int = 1


@dataclass
class TrialResult:
    trial_id: int
    group_id: int
    config: TrialConfig
    history: list = field(default_factory=list)  # per-epoch dicts
    final_train_loss: float = float("nan")  # per-sample avg, last epoch
    final_test_loss: float = float("nan")
    wall_s: float = 0.0
    steps: int = 0
    out_dir: str = ""
    checkpoint: str = ""
    # "completed" | "failed" | "resumed_complete" | "diverged"
    # ("diverged" = non-finite loss: a terminal RESULT of the config,
    # recorded and never retried — see hpo/supervision.py)
    status: str = "completed"
    error: str = ""
    # Which attempt produced this result (1 = first try; >1 means the
    # supervisor retried infra faults — the ledger holds the history).
    attempt: int = 1
    # Optimizer step this attempt resumed from (0 = scratch): the
    # difference steps - resumed_from_step is the attempt's EXECUTED
    # work — what the chaos bench's goodput accounting sums.
    resumed_from_step: int = 0
    # Data provenance: which dataset the trial actually trained on, and
    # whether it was the synthetic zero-egress stand-in. The reference
    # always trains on real MNIST (vae-hpo.py:133-144); this repo can
    # silently degrade to synthetic (data/datasets.py), so a trial's
    # recorded metrics must say which world they came from.
    dataset: str = ""
    dataset_synthetic: bool = False
    # Host↔device round-trips the trial actually paid for metric
    # fetches (the O(1)-syncs discipline: ≤ log lines + 2 per epoch;
    # regression-tested in tests/test_hpo.py). For a stacked trial this
    # counts its whole bucket's fetches during the trial's lifetime —
    # the bucket pays them once for ALL lanes.
    host_syncs: int = 0
    # True when the trial ran as one lane of a stacked bucket
    # (docs/STACKING.md): K same-shape trials vmapped through one
    # compiled program on one submesh.
    stacked: bool = False
    # Analytic per-device optimizer-state footprint (docs/PARALLEL.md
    # memory books): what ONE device holds for this trial's Adam
    # moments, from each leaf's concrete sharding — the ZeRO win is
    # visible here without memory_stats() (CPU included). For a
    # stacked lane this is the lane's share of the bucket's stacked
    # state; for a pipelined trial, the sum over its stages.
    optimizer_state_bytes: int = 0


def all_completed(results: Sequence[TrialResult]) -> bool:
    """Whether every trial ran to its end. A ``failed`` (under
    ``resilient=True``) or ``diverged`` trial is a recorded result that
    ``run_hpo`` returns normally, so a caller whose exit code should
    mean "the sweep trained" (the examples) asks here."""
    return all(r.status in ("completed", "resumed_complete") for r in results)


def config_mismatch_vs_meta(cfg: TrialConfig, meta: dict) -> dict:
    """Fields (epochs excluded — extending epochs is the legitimate
    resume use) where a checkpoint's recorded config differs from
    ``cfg``; empty dict = match. Fields absent from an older
    checkpoint's sidecar compare against their TrialConfig default —
    a checkpoint written before a field existed was trained under its
    default. The ONE copy of the resume config-match rule: the classic
    ``_TrialRun`` and the pipelined runner's per-stage scan restore
    both gate on it."""
    from dataclasses import MISSING, fields as dc_fields

    field_defaults = {
        f.name: f.default
        for f in dc_fields(TrialConfig)
        if f.default is not MISSING
    }
    saved = {
        k: meta.get(k, field_defaults.get(k))
        for k in asdict(cfg)
        if k != "epochs" and (k in meta or k in field_defaults)
    }
    current = {k: v for k, v in asdict(cfg).items() if k != "epochs"}
    if not saved or saved == current:
        return {}
    return {
        k: (saved.get(k), current[k])
        for k in current
        if saved.get(k) != current[k]
    }


def _result_summary(result: TrialResult) -> dict:
    """The ledger's attempt_end payload: enough to reconstruct a
    TrialResult when a restarted sweep skips the trial entirely."""
    return {
        "group_id": result.group_id,
        "history": list(result.history),
        "final_train_loss": result.final_train_loss,
        "final_test_loss": result.final_test_loss,
        "wall_s": result.wall_s,
        "steps": result.steps,
        "out_dir": result.out_dir,
        "checkpoint": result.checkpoint,
        "dataset": result.dataset,
        "dataset_synthetic": result.dataset_synthetic,
        "stacked": result.stacked,
        "resumed_from_step": result.resumed_from_step,
        "optimizer_state_bytes": result.optimizer_state_bytes,
    }


def _result_from_summary(
    cfg: TrialConfig, rec: dict, status: str
) -> TrialResult:
    """Rebuild a TrialResult from a ledger attempt_end record (the
    restarted-sweep skip path — no state is touched)."""
    s = rec.get("summary") or {}
    return TrialResult(
        trial_id=cfg.trial_id,
        group_id=int(s.get("group_id", -1)),
        config=cfg,
        history=list(s.get("history", [])),
        final_train_loss=float(s.get("final_train_loss", float("nan"))),
        final_test_loss=float(s.get("final_test_loss", float("nan"))),
        wall_s=float(s.get("wall_s", 0.0)),
        steps=int(s.get("steps", 0)),
        out_dir=s.get("out_dir", ""),
        checkpoint=s.get("checkpoint", ""),
        status=status,
        error=rec.get("error", ""),
        dataset=s.get("dataset", ""),
        dataset_synthetic=bool(s.get("dataset_synthetic", False)),
        stacked=bool(s.get("stacked", False)),
        attempt=int(rec.get("attempt", 1)),
        resumed_from_step=int(s.get("resumed_from_step", 0)),
        optimizer_state_bytes=int(s.get("optimizer_state_bytes", 0)),
    )


class _TrialRun:
    """One trial's full lifecycle as a cooperative generator.

    Each ``next()`` dispatches one unit of training work async — a
    single train step, or a chunk of ``cfg.fused_steps`` scan-fused
    steps — and returns; host-device syncs happen only at the
    reference's logging cadence and at epoch boundaries. The generator
    shape is what makes the no-barrier scheduling work: the driver
    interleaves ``next()`` across trials, so every submesh has work
    queued at all times.
    """

    def __init__(
        self,
        trial: TrialMesh,
        cfg: TrialConfig,
        train_data: Dataset,
        test_data: Optional[Dataset],
        out_dir: str,
        *,
        shard_across_trials: bool = False,
        num_trials: int = 1,
        save_images: bool = True,
        save_checkpoint: bool = True,
        verbose: bool = True,
        model_builder=None,
        param_shardings_builder=None,
        resume=False,  # False | True (strict) | "scan" (supervised)
        agree_failures: bool = False,
        agree_timeout_s: Optional[float] = None,
        wedge_timeout_s: Optional[float] = None,
        injector=None,  # faults.inject.FaultInjector | None
        ckpt_keep_last: int = 1,
        ckpt_format: Optional[str] = None,
        ram_restore: bool = False,
        attempt: int = 1,
    ):
        if cfg.fused_steps < 1:
            raise ValueError(
                f"fused_steps must be >= 1, got {cfg.fused_steps} "
                f"(trial {cfg.trial_id})"
            )
        if cfg.pipeline_stages != 1:
            raise ValueError(
                f"trial {cfg.trial_id} has pipeline_stages="
                f"{cfg.pipeline_stages}: an MPMD pipelined trial is a "
                "vector of submeshes and runs through "
                "hpo.pipeline_run._PipelineTrialRun (service placement "
                "or run_pipeline_trial), not _TrialRun"
            )
        self.trial = trial
        self.cfg = cfg
        self.out_dir = os.path.join(out_dir, f"trial-{cfg.trial_id}")
        self.result = TrialResult(
            trial_id=cfg.trial_id,
            group_id=trial.group_id,
            config=cfg,
            out_dir=self.out_dir,
            dataset=train_data.name,
            dataset_synthetic=train_data.synthetic,
        )
        # Artifacts (images, checkpoints, metrics.json) are written by
        # exactly one process per group — on a shared filesystem,
        # every-owner-writes would race identical files (Q4's
        # multi-process half). Resume restores *state* on all owner
        # processes; only the writer re-reads sidecar metadata.
        self._is_writer = trial.is_writer_process
        # Uniform across owner processes (drives which programs are
        # compiled AND dispatched — dispatch gating must never be
        # writer-local on a process-spanning submesh, or SPMD execution
        # desynchronizes); the writer-gated flag below controls only
        # host-side fetch + file writes.
        self._images_requested = save_images
        self._save_images = save_images and self._is_writer
        self._save_checkpoint = save_checkpoint
        self._verbose = verbose
        self._test_data = test_data
        # Multi-host failure isolation (resilient sweeps on spanning
        # submeshes): writer-only host-I/O failures are deferred and
        # agreed at the epoch boundary via a submesh-scoped reduction
        # (collectives.group_all_ok), so every owner process kills the
        # trial identically instead of one process freeing the group
        # while peers keep stepping it.
        self._agree = agree_failures
        self._agree_timeout_s = agree_timeout_s
        # Wedge watchdog deadline for device-result fetches whose value
        # transits a cross-host collective (epoch/test loss, checkpoint
        # gather, completion drain): on a spanning submesh a peer that
        # stopped dispatching leaves these blocked forever — the
        # watchdog turns that into a named WedgedCollective within the
        # deadline (classified as preemption; exit-code contract in
        # docs/RESILIENCE.md). None/0 = unbounded; single-process and
        # non-spanning trials never pay the watchdog thread.
        self._wedge_timeout_s = wedge_timeout_s
        # This run's attempt number (1-based, ledger-monotonic): scopes
        # the cross-host restore agreement's sideband keys.
        self._attempt = attempt
        self._deferred_error: Optional[BaseException] = None
        self._host_syncs = 0
        # Fault-injection seams (None in production): chaos drills route
        # through the SAME dispatch/data/checkpoint paths real faults
        # take — see faults/inject.py for the hook contract.
        self._injector = injector
        self._ckpt_keep_last = ckpt_keep_last
        # Checkpoint data-plane format (docs/RESILIENCE.md "Checkpoint
        # format v2"): the driver writes v2 chunked manifests by
        # default (MDT_CKPT_FORMAT=v1 opts back into full-msgpack);
        # restore always sniffs per file, so a v1 history under a v2
        # primary resumes fine.
        self._ckpt_format = (
            ckpt_format if ckpt_format is not None else default_format()
        )
        # RAM-snapshot restore is an explicit opt-in (the service's
        # same-process re-place after a snapshot drain): supervised
        # retry drills outside the service keep pure disk semantics —
        # a chaos test that corrupts the on-disk history must observe
        # the scan-back degrade, not a warm cache.
        self._ram_restore = bool(ram_restore)
        self._last_ckpt_stats: dict = {}
        # Optimizer-step cursor mirrored as an attribute so the
        # injection hooks (closures built below, called from inside the
        # compiled-step wrappers) always see the current step.
        self._step_no = 0
        self._epoch_base_step = 0
        # Telemetry (all None when off — the zero-cost contract;
        # captured once so the hot loop pays one attribute read).
        # Step timings flow into the sweep-wide metrics registry under
        # this trial's series key; lifecycle events ride the bus; the
        # anomaly monitor watches step times and epoch losses; the
        # device books (cost analysis, memory watermarks) are recorded
        # through _device_seam at the same guarded sites.
        self._mreg = get_registry()
        self._mkey = f"trial-{cfg.trial_id}"
        self._amon = get_monitor()
        self._cost_done = False

        if model_builder is None:
            model = VAE(hidden_dim=cfg.hidden_dim, latent_dim=cfg.latent_dim)
        else:
            model = model_builder(cfg)
        tx = optax.adam(cfg.lr)
        self.model, self.tx = model, tx
        # Within-trial weight sharding (TP/EP/FSDP): the builder maps
        # (trial, model) -> a param-shardings pytree (e.g.
        # models.vae.vae_tp_shardings, models.moe_vae.moe_vae_ep_shardings);
        # the derived state shardings then pin every step's layout.
        param_sh = (
            param_shardings_builder(trial, model)
            if param_shardings_builder is not None
            else None
        )
        # AOT eligibility (docs/COMPILE.md): the program vocabulary
        # describes exactly the default model family with replicated
        # weights on a single controller — the same envelope as trial
        # stacking. Everything else keeps the plain jit paths.
        # MDT_AOT_ADMISSION=0 is the kill switch.
        aot_eligible = (
            model_builder is None
            and param_shardings_builder is None
            # The sharded-update variant pins different state shardings
            # into its programs — the registry's single-path keys don't
            # carry the mode, so a zero trial must never take (or
            # donate) a replicated twin's executable.
            and not cfg.zero_update
            and jax.process_count() == 1
            and os.environ.get("MDT_AOT_ADMISSION", "1") != "0"
        )
        self.state = None
        if aot_eligible:
            # The state-init program is itself part of the compile tax
            # (flax init traces+compiles per trial): take the farm's
            # executable if ready, else compile inline through the
            # registry — timed, attributed, and shared by every
            # same-bucket trial (lr twins included; init bakes no
            # hypers). Bit-identical to the eager path by construction
            # (elementwise RNG + zeros_like; regression-tested), and
            # any failure falls back to it.
            self.state = self._registry_init_state()
        if self.state is None:
            self.state = create_train_state(
                trial, model, tx, jax.random.key(cfg.seed),
                param_shardings=param_sh,
            )
        self._state_sh = (
            state_shardings(self.state) if param_sh is not None else None
        )
        # Sharded weight update (docs/PARALLEL.md): re-place the Adam
        # moments data-sharded and pin the layout into every step. The
        # forward/backward stays the replicated program; only the
        # update's reduce-scatter/all-gather schedule changes.
        if cfg.zero_update and trial.data_size > 1:
            if param_sh is not None:
                raise ValueError(
                    f"trial {cfg.trial_id}: zero_update composes with "
                    "weight sharding via parallel.fsdp."
                    "fsdp_compose_shardings, not via both knobs at once "
                    "(the param_shardings_builder already owns the "
                    "state layout)"
                )
            from multidisttorch_tpu.parallel.fsdp import place_zero_state

            self.state, self._state_sh = place_zero_state(trial, self.state)
        # Checkpointing a weight-sharded state: serialization needs the
        # whole array on the writer host. On a PROCESS-SPANNING submesh
        # the writer holds only its shards, so a gather-to-replicated
        # is DISPATCHED by every owner (uniform SPMD program — the same
        # rule as every other step); only the fetch stays writer-gated.
        # Single-controller sharded states (ZeRO, TP, FSDP) skip the
        # gather entirely under the v2 format — every shard is locally
        # addressable, the host fetch assembles them without a device
        # collective, and the manifest records the NamedSharding layout
        # the state trained under (the sharded-native save path).
        self._gather_state = (
            jax.jit(lambda s: s, out_shardings=trial.replicated_sharding)
            if self._state_sh is not None
            and (self._ckpt_format == "v1" or trial.spans_processes)
            else None
        )
        # Memory books (docs/PARALLEL.md): the analytic per-device
        # optimizer footprint from the placed state's CONCRETE
        # shardings — the ZeRO win is visible on every backend, no
        # memory_stats() needed.
        from multidisttorch_tpu.parallel.fsdp import optimizer_state_bytes

        _ob = optimizer_state_bytes(self.state)
        self.result.optimizer_state_bytes = _ob["per_device_bytes"]
        _bus = get_bus()
        if _bus is not None:
            _bus.emit(
                "optimizer_state",
                trial_id=cfg.trial_id,
                group_id=trial.group_id,
                per_device_bytes=_ob["per_device_bytes"],
                total_bytes=_ob["total_bytes"],
                zero_update=bool(cfg.zero_update),
            )
        self.train_step = make_train_step(
            trial, model, tx, beta=cfg.beta, remat=cfg.remat,
            grad_accum=cfg.grad_accum, shardings=self._state_sh,
        )
        self.multi_step = (
            make_multi_step(
                trial, model, tx, beta=cfg.beta, remat=cfg.remat,
                grad_accum=cfg.grad_accum, shardings=self._state_sh,
            )
            if cfg.fused_steps > 1
            else None
        )
        # Raw jit programs kept unwrapped for the AOT admission path
        # (compile/registry.py): a registry executable replaces the RAW
        # program, and the chaos hook-wrapping is re-applied around
        # whichever wins — hooks are pure host code either way.
        self._train_raw = self.train_step
        self._multi_raw = self.multi_step
        self.train_step = self._wrap_train(self.train_step)
        if self.multi_step is not None:
            self.multi_step = self._wrap_multi(self.multi_step)
        # AOT admission: "take the finished executable if ready, else
        # compile inline" (docs/COMPILE.md) — for the train programs,
        # resolved cooperatively in run() before the first dispatch.
        self._aot_keys: dict = {}
        self._admission = {"outcome": "jit", "wait_s": 0.0, "program": None}
        self._first_dispatched = False
        if aot_eligible:
            from multidisttorch_tpu.compile import programs as _cprog

            bucket = stack_bucket_key(cfg)
            self._aot_keys["train"] = _cprog.single_train_key(
                trial, cfg, bucket
            )
            if cfg.fused_steps > 1:
                self._aot_keys["multi"] = _cprog.single_multi_key(
                    trial, cfg, bucket
                )
        # Reconstructions are materialized (and all-gathered back to
        # replicated) only when images are wanted. Keyed on the uniform
        # save_images argument, NOT the per-process writer-gated flag:
        # all owner processes must compile the identical eval program.
        self.eval_step = make_eval_step(
            trial,
            model,
            beta=cfg.beta,
            with_recon=save_images,
            masked=True,
            sampled=cfg.eval_sampled,
            shardings=self._state_sh,
        )
        self.sample_step = make_sample_step(
            trial, model, shardings=self._state_sh
        )
        self.train_iter = TrialDataIterator(
            train_data,
            trial,
            cfg.batch_size,
            seed=cfg.seed,
            shard_across_trials=shard_across_trials,
            num_trials=num_trials,
            fault_hook=(
                None if injector is None else self._data_fault_hook
            ),
        )
        # Full-coverage eval (reference parity, vae-hpo.py:101-105): the
        # pad-and-mask iterator consumes every test row — including test
        # sets smaller than one batch, which round 1 silently skipped.
        self.test_iter = (
            EvalDataIterator(test_data, trial, cfg.batch_size)
            if test_data is not None and len(test_data) > 0
            else None
        )
        self._first_test_batch = None
        self._key = jax.random.key(cfg.seed + 1)

        # Resume: per-epoch checkpoints carry (state, completed_epochs,
        # history); restore at the last epoch boundary. Epoch data order
        # and step RNG are deterministic in (seed, epoch) / step number,
        # so a resumed run replays the exact remaining stream.
        self._ckpt_path = os.path.join(self.out_dir, "state.msgpack")
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_error: Optional[BaseException] = None
        self._start_epoch = 1
        if resume == "scan":
            # Supervised retry-with-resume: scan back past torn/corrupt
            # checkpoints to the newest VALID one whose recorded config
            # matches (train/checkpoint.py's CRC machinery); nothing
            # valid means retry from scratch. No strict errors here —
            # the supervisor's contract is "recover the most work
            # possible", not "diagnose for a human". On a spanning
            # submesh the choice is AGREED across owner processes
            # (min-over-hosts valid step) so one host's torn view of
            # the newest candidate cannot desynchronize SPMD.
            got = self._restore_scan()
            if got is not None:
                restored, meta, used = got
                done = int(meta.get("completed_epochs", 0))
                if done >= 1:
                    self.state = restored
                    self._start_epoch = done + 1
                    self._adopt_history(meta)
                    log0(
                        f"Trial {cfg.trial_id} retry resumes from epoch "
                        f"{done} checkpoint ({used})",
                        trial=trial,
                    )
        elif resume:
            meta_path = self._ckpt_path + ".json"
            if os.path.exists(self._ckpt_path) and os.path.exists(meta_path):
                with open(meta_path) as f:
                    meta = json.load(f)
                # Guard against resuming under silently-changed
                # hyperparameters: everything except the epoch target
                # (extending epochs is the legitimate resume use) must
                # match the checkpoint's saved config.
                diff = self._config_mismatch(meta)
                if diff:
                    raise UnretryableError(
                        f"resume: trial {cfg.trial_id} checkpoint at "
                        f"{self._ckpt_path} was written under different "
                        f"hyperparameters {diff} (saved vs current); "
                        "refusing to continue stale weights under a "
                        "changed config"
                    )
                done = int(meta.get("completed_epochs", 0))
                if done >= 1:
                    self.state = restore_state(
                        self.state, self._ckpt_path, trial,
                        shardings=self._state_sh,
                    )
                    restored_step = int(jax.device_get(self.state.step))
                    if "step" in meta and restored_step != int(meta["step"]):
                        raise UnretryableError(
                            f"resume: trial {cfg.trial_id} checkpoint is "
                            f"skewed — state.msgpack is at optimizer step "
                            f"{restored_step} but the metadata sidecar "
                            f"claims step {meta['step']} (epoch {done}). "
                            "A crash likely landed between the two "
                            "checkpoint file replaces; delete "
                            f"{self._ckpt_path}* to restart this trial "
                            "from scratch rather than silently re-train "
                            "an already-applied epoch"
                        )
                    self._start_epoch = done + 1
                    self._adopt_history(meta)
        # Executed-work accounting (chaos goodput): what step this
        # attempt starts from. Epoch data order is drop-tail-stable, so
        # the resume step is exactly epochs-done x batches-per-epoch.
        self.result.resumed_from_step = (
            (self._start_epoch - 1) * self.train_iter.num_batches
        )

    def _config_mismatch(self, meta: dict) -> dict:
        return config_mismatch_vs_meta(self.cfg, meta)

    def _restore_scan(self):
        """Scan-back restore for supervised retries and elastic
        restarts; returns ``(state, meta, used_path)`` or ``None`` for
        scratch.

        Single-owner submeshes take the plain local scan
        (``restore_latest_valid``). A PROCESS-SPANNING submesh runs the
        **cross-host restore agreement** (docs/RESILIENCE.md "Elastic
        multi-host", ``train.checkpoint.agreed_restore_step``): every
        owner verifies its candidates locally, the group agrees on the
        min of the newest locally-valid steps and confirms everyone
        holds the agreed candidate — over the coordination-service
        sideband (``cluster.agree_min_int``), never an on-mesh
        collective: recovery must work when the device world is the
        broken thing. Shared-filesystem views can disagree
        (close-to-open NFS races, a write torn under one reader) —
        without the agreement, owners would resume different weights
        and silently desync SPMD. Any disagreement degrades to scratch
        on every owner, never an error: recovery must degrade, not
        wedge.
        """
        def accept(meta: dict) -> bool:
            return not self._config_mismatch(meta)

        # Warm re-place (docs/RESILIENCE.md "Snapshot-fast drain"): a
        # preempted trial re-placed in the SAME process restores from
        # the still-warm RAM snapshot — no chunk reads, no msgpack
        # decode. The cache entry is written at the same device→host
        # fetch that feeds the durable write, so it is never older
        # than the newest disk candidate for this path; config-match
        # gates it exactly like a disk candidate's sidecar.
        snap = (
            snapshot_cache().get(self._ckpt_path)
            if self._ram_restore
            else None
        )
        if snap is not None:
            host_state, meta = snap
            if accept(meta) and int(meta.get("completed_epochs", 0)) >= 1:
                try:
                    restored = self.trial.device_put(
                        host_state, self._state_sh
                    )
                except Exception:  # noqa: BLE001 — fall back to disk
                    restored = None
                if restored is not None:
                    from multidisttorch_tpu.train.checkpoint import _count

                    _count(restores=1, restores_ram=1)
                    bus = get_bus()
                    if bus is not None:
                        bus.emit(
                            "ckpt_restore",
                            group_id=self.trial.group_id,
                            path=RAM_SNAPSHOT,
                            format="ram",
                            trial_id=self.cfg.trial_id,
                            step=meta.get("step"),
                        )
                    return restored, dict(meta), RAM_SNAPSHOT
            snapshot_cache().drop(self._ckpt_path)

        if not (jax.process_count() > 1 and self.trial.spans_processes):
            return restore_latest_valid(
                self.state,
                self._ckpt_path,
                self.trial,
                shardings=self._state_sh,
                accept_meta=accept,
            )
        from multidisttorch_tpu.train.checkpoint import agreed_restore_step

        got = agreed_restore_step(
            self._ckpt_path,
            # Attempt-scoped agreement keys: a retried trial's new
            # agreement never reads the previous attempt's votes (and
            # every re-formed world gets a fresh coordinator anyway).
            name=f"trial{self.cfg.trial_id}:a{self._attempt}",
            participants=self.trial.owner_processes,
            accept_meta=lambda meta: (
                accept(meta) and int(meta.get("completed_epochs", 0)) >= 1
            ),
            timeout_s=self._agree_timeout_s,
            what=(
                f"trial {self.cfg.trial_id} restore agreement over "
                f"submesh group {self.trial.group_id}"
            ),
            trial_id=self.cfg.trial_id,
            group_id=self.trial.group_id,
        )
        if got is None:
            return None  # disagreement degrades to scratch everywhere
        _step, cand, meta = got
        restored = restore_state(
            self.state, cand, self.trial, shardings=self._state_sh
        )
        return restored, meta, cand

    def _wedged_fetch(self, fn, what: str):
        """Run a host-side device fetch under the wedge watchdog when
        its result transits a cross-host collective (spanning submesh,
        multi-controller). A peer that stopped dispatching blocks such
        fetches forever; the watchdog converts that into a
        ``WedgedCollective`` within the deadline. Local fetches call
        straight through — no watchdog thread, no overhead."""
        if (
            not self._wedge_timeout_s
            or jax.process_count() == 1
            or not self.trial.spans_processes
        ):
            return fn()
        from multidisttorch_tpu.parallel.cluster import (
            WedgedCollective,
            call_with_timeout,
        )

        return call_with_timeout(
            fn,
            self._wedge_timeout_s,
            f"trial {self.cfg.trial_id} {what}",
            error_cls=WedgedCollective,
        )

    def _adopt_history(self, meta: dict) -> None:
        self.result.history = list(meta.get("history", []))
        if self.result.history:
            last = self.result.history[-1]
            self.result.final_train_loss = last.get(
                "avg_train_loss", float("nan")
            )
            self.result.final_test_loss = last.get(
                "test_loss", float("nan")
            )

    def _data_fault_hook(self, epoch: int, batch_index: int) -> None:
        """Data-iterator injection seam: maps the iterator's
        (epoch, batch_index) to the trial's global optimizer step."""
        self._injector.data_hook(
            self.cfg.trial_id, self._epoch_base_step + batch_index
        )

    def _log(self, *args, level: int = logging.INFO):
        if self._verbose:
            log0(*args, trial=self.trial, level=level)

    def _registry_init_state(self):
        """Materialize this trial's TrainState through the compile
        registry's init executable (docs/COMPILE.md): take the farm's
        finished program if READY, else compile it inline through the
        registry (coalescing with a mid-compile farm worker — never
        longer than the eager init compile this replaces, and the
        executable then serves every same-bucket trial). Returns the
        PLACED state, or None for the eager ``create_train_state``
        fallback (failed compile, torn registry, any exception)."""
        from multidisttorch_tpu.compile import programs as _cprog
        from multidisttorch_tpu.compile.registry import (
            READY,
            SOURCE_INLINE,
            get_executable_registry,
        )

        cfg, trial = self.cfg, self.trial
        try:
            key = _cprog.single_init_key(trial, cfg, stack_bucket_key(cfg))
            reg = get_executable_registry()
            ex = reg.take(key)
            if ex is None:
                entry = reg.compile_now(
                    key,
                    _cprog.build_init_fn(cfg, self.model),
                    _cprog.init_avals(),
                    source=SOURCE_INLINE,
                )
                if entry.status == READY:
                    ex = entry.compiled
            if ex is None:
                return None
            return trial.device_put(ex(jax.random.key(cfg.seed)))
        except Exception:  # noqa: BLE001 — init must never be the
            # reason a trial cannot start; the eager path always works.
            return None

    def _wrap_train(self, fn):
        """Chaos hook-wrapping for a single-step program (jit fn or AOT
        executable — both are plain callables to the hooks)."""
        if self._injector is None:
            return fn
        injector, tid = self._injector, self.cfg.trial_id
        return wrap_step_with_hooks(
            fn,
            before=lambda b: injector.step_hook(tid, self._step_no, 1),
            transform_batch=lambda b: injector.poison_batch(
                tid, self._step_no, b, 1
            ),
        )

    def _wrap_multi(self, fn):
        if self._injector is None:
            return fn
        injector, tid = self._injector, self.cfg.trial_id
        return wrap_step_with_hooks(
            fn,
            before=lambda b: injector.step_hook(
                tid, self._step_no, b.shape[0]
            ),
            transform_batch=lambda b: injector.poison_batch(
                tid, self._step_no, b, b.shape[0]
            ),
        )

    def _admit_programs(self) -> Iterator[None]:
        """Cooperative AOT admission (docs/COMPILE.md): swap registry
        executables in for the raw jit programs before the first
        dispatch. Yields while a farm worker is mid-compile — the host
        loop keeps every OTHER submesh stepping, so admission never
        blocks on XLA."""
        if not self._aot_keys:
            return
        from multidisttorch_tpu.compile import programs as _cprog

        primary = "multi" if self.cfg.fused_steps > 1 else "train"
        raw = {"train": self._train_raw, "multi": self._multi_raw}
        taken, self._admission = yield from _aot_admit(
            self._aot_keys,
            raw,
            lambda: _cprog.single_avals(self.cfg),
            self.state,
            primary,
        )
        if "train" in taken:
            self.train_step = self._wrap_train(taken["train"])
        if "multi" in taken:
            self.multi_step = self._wrap_multi(taken["multi"])

    def _note_first_dispatch(self) -> None:
        """One event per trial, right after the first step dispatch
        returns: its timestamp minus the attempt_start's is the trial's
        admission latency (setup + compile — the cold-start books'
        headline number), and the data says how the program arrived
        (hit/wait/inline/jit)."""
        self._first_dispatched = True
        bus = get_bus()
        if bus is not None:
            bus.emit(
                "first_dispatch",
                trial_id=self.cfg.trial_id,
                group_id=self.trial.group_id,
                **self._admission,
            )

    def _device_seam(self, dt, fn, args, *, steps: int = 1) -> None:
        """Per-dispatch device-book seam (reached only with telemetry
        ON — call sites sit inside the ``self._mreg is not None``
        guard): record the compiled step's XLA cost analysis ONCE per
        trial (shapes don't change after the first dispatch), then feed
        the straggler detector the per-step time the registry just
        measured (``dt`` is ``step_mark``'s return — no second clock
        read)."""
        if not self._cost_done:
            self._cost_done = True
            tele_device.record_step_cost(
                self._mkey, fn, args, steps=steps,
                devices=self.trial.devices,
                trial_id=self.cfg.trial_id,
                group_id=self.trial.group_id,
                # Same shape bucket + same arg shapes = same compiled
                # program up to scalar hypers: one AOT analysis serves
                # every same-shape trial and every retry attempt.
                cache_key=("single", stack_bucket_key(self.cfg)),
            )
            # The AOT lower+compile above took real wall time inside an
            # open interval — re-open so the next mark doesn't charge
            # the compile as one giant dispatch (it would inflate the
            # dispatch p95, deflate MFU, and seed the straggler
            # detector's baseline with a bogus sample).
            self._mreg.step_series(self._mkey).open_interval()
        if self._amon is not None and dt is not None:
            self._amon.observe_step(
                self._mkey, dt,
                trial_id=self.cfg.trial_id, step=self._step_no,
            )

    @contextmanager
    def _guard(self):
        """Collect writer-only host-I/O failures (image/checkpoint/
        metrics writes) for epoch-boundary agreement instead of raising
        on one process of a spanning submesh. No-op outside agreement
        mode: errors raise at the fault site, reference-honest."""
        if not self._agree:
            yield
            return
        try:
            yield
        except Exception as e:  # noqa: BLE001 — deferred to agreement
            # Preemption-class failures (host going away, wedged or
            # expired collective) are NOT writer-I/O failures to vote
            # on at the next boundary — the distributed state is
            # already unusable, and the next boundary's reduction
            # would wedge too. Propagate immediately.
            from multidisttorch_tpu.faults.inject import HostPreemption
            from multidisttorch_tpu.parallel.cluster import AgreementTimeout

            if isinstance(e, (HostPreemption, AgreementTimeout)):
                raise
            if self._deferred_error is None:
                self._deferred_error = e

    def _agree_boundary(self, where: str) -> None:
        """Epoch-boundary health agreement over the trial submesh.

        Every owner process calls this at the same point in the group's
        dispatch sequence (deterministic cadence: once per epoch + once
        at completion). If any owner deferred a failure, ALL owners
        raise here — the submesh is freed identically everywhere, and
        unrelated trials never participate (no world barrier; quirk Q3
        stays fixed). Deterministic compute failures need no agreement:
        SPMD determinism raises them identically on every owner.
        """
        if not self._agree:
            return
        from multidisttorch_tpu.parallel.cluster import WedgedCollective
        from multidisttorch_tpu.parallel.collectives import group_all_ok

        err, self._deferred_error = self._deferred_error, None
        # Deadline-bounded: a dead peer owner would otherwise hang this
        # reduction forever (the reference's exact lost-rank behavior).
        # On expiry a WedgedCollective propagates through the trial's
        # normal failure isolation (classified as preemption), naming
        # the trial and boundary.
        if not group_all_ok(
            self.trial,
            err is None,
            timeout_s=self._agree_timeout_s,
            what=(
                f"trial {self.cfg.trial_id} {where} health agreement "
                f"over submesh group {self.trial.group_id}"
            ),
            error_cls=WedgedCollective,
        ):
            if err is not None:
                raise err
            raise RuntimeError(
                f"trial {self.cfg.trial_id}: {where} failed on a peer "
                "owner process (agreed via submesh health reduction)"
            )

    def _write_ckpt(self, host_state, meta: dict) -> None:
        """Background checkpoint write. ``result.checkpoint`` is set only
        after the (atomic) write succeeds, so a failed write can never be
        reported as a valid checkpoint; failures are re-raised on the
        next :meth:`_join_ckpt` and flow through the trial's normal
        failure isolation."""
        try:
            save_state(
                host_state,
                self._ckpt_path,
                metadata=meta,
                keep_last=self._ckpt_keep_last,
                format=self._ckpt_format,
                # The layout record describes what was SNAPSHOTTED: a
                # gathered (replicated) snapshot must not claim the
                # live state's sharded layout.
                layouts=(
                    self._state_sh if self._gather_state is None else None
                ),
                stats_out=self._last_ckpt_stats,
            )
            self.result.checkpoint = self._ckpt_path
            if self._injector is not None:
                # Chaos seam: CKPT_CORRUPT garbles the file AFTER the
                # write lands — the bit-rot/torn artifact that
                # restore_latest_valid must scan past on retry.
                self._injector.checkpoint_hook(
                    self.cfg.trial_id,
                    int(meta.get("completed_epochs", 0)),
                    self._ckpt_path,
                )
        except BaseException as e:  # re-raised at the next join
            self._ckpt_error = e

    def _join_ckpt(self) -> None:
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None
        if self._ckpt_error is not None:
            e, self._ckpt_error = self._ckpt_error, None
            raise RuntimeError(
                f"trial {self.cfg.trial_id}: checkpoint write to "
                f"{self._ckpt_path} failed"
            ) from e

    def _ckpt_idle(self) -> bool:
        """No persist in flight (non-blocking — the snapshot-fast
        drain's poll; :meth:`_join_ckpt` is the blocking/raising
        sibling)."""
        t = self._ckpt_thread
        return t is None or not t.is_alive()

    def run(self) -> Iterator[None]:
        cfg = self.cfg
        t0 = time.time()
        if self._start_epoch > cfg.epochs:
            # Fully-trained checkpoint found: nothing to replay.
            self.result.status = "resumed_complete"
            self.result.steps = int(jax.device_get(self.state.step))
            self.result.checkpoint = self._ckpt_path
            self._log(f"Trial {cfg.trial_id} already complete; resumed.")
            return
        # AOT admission before the first dispatch: take/wait-for/claim
        # this trial's compiled programs (cooperative — yields keep the
        # other submeshes stepping while a farm worker compiles ours).
        yield from self._admit_programs()
        n_per_epoch = self.train_iter.samples_per_epoch
        # state.step counts optimizer updates, so it doubles as the
        # resume-safe global step for RNG folding. Kept as an attribute:
        # the fault-injection hook closures read it mid-dispatch.
        self._step_no = int(jax.device_get(self.state.step))
        for epoch in range(self._start_epoch, cfg.epochs + 1):
            self._epoch_base_step = self._step_no
            # Fresh timing interval per epoch: the gap since the last
            # mark holds boundary work (eval, checkpoint, a retry's
            # backoff), not a dispatch — without the break it reads as
            # one giant "step" and trips the straggler detector.
            if self._mreg is not None:
                self._mreg.step_series(self._mkey).open_interval()
            # On-device loss accumulation (mirrors the eval path below):
            # each batch's contribution is an async device add; the
            # single float() at the epoch boundary is the train loop's
            # only non-logging host sync.
            epoch_sum_dev = None

            def log_batch(epoch, i, loss_sum):
                # Per-STEP chatter rides DEBUG (per-trial lines stay
                # INFO): a sweep that raises the logger level skips the
                # device sync below entirely, not just the print.
                if not self._verbose or not log0_enabled(logging.DEBUG):
                    return  # don't pay the device sync for a dropped line
                # sync point for THIS trial only (reference logs
                # loss.item() here, vae-hpo.py:76-86)
                self._host_syncs += 1
                per_sample = float(loss_sum) / cfg.batch_size
                self._log(
                    "Train Epoch: {} [{}/{} ({:.0f}%)]\tLoss: {:.6f}".format(
                        epoch,
                        i * cfg.batch_size,
                        n_per_epoch,
                        100.0 * i / self.train_iter.num_batches,
                        per_sample,
                    ),
                    level=logging.DEBUG,
                )

            if self.multi_step is None:
                for i, batch in enumerate(self.train_iter.epoch(epoch)):
                    rng = jax.random.fold_in(self._key, self._step_no)
                    self.state, metrics = self.train_step(
                        self.state, batch, rng
                    )
                    self._step_no += 1
                    if not self._first_dispatched:
                        self._note_first_dispatch()
                    s = metrics["loss_sum"]  # on device, async
                    epoch_sum_dev = s if epoch_sum_dev is None else epoch_sum_dev + s
                    if self._mreg is not None:
                        dt = self._mreg.step_mark(self._mkey, s)
                        self._device_seam(
                            dt, self.train_step, (self.state, batch, rng)
                        )
                    if i % cfg.log_interval == 0:
                        log_batch(epoch, i, metrics["loss_sum"])
                    yield  # hand the host loop to the next trial
            else:
                # Scan-fused dispatch: fused_steps optimizer updates per
                # host round-trip. The log cadence is preserved exactly —
                # the chunk's per-step losses are indexable, so the batch
                # that would have logged in the per-step loop still does.
                K = cfg.fused_steps
                for item in self.train_iter.epoch_chunks(epoch, K):
                    i0, chunk = item[0], item[1]
                    c = chunk.shape[0]
                    if c == K:
                        rng = jax.random.fold_in(self._key, self._step_no)
                        self.state, metrics = self.multi_step(
                            self.state, chunk, rng
                        )
                        self._step_no += c
                        if not self._first_dispatched:
                            self._note_first_dispatch()
                        losses = metrics["loss_sum"]  # (K,) on device
                        s = losses.sum()  # device add, async
                        epoch_sum_dev = (
                            s if epoch_sum_dev is None else epoch_sum_dev + s
                        )
                        if self._mreg is not None:
                            dt = self._mreg.step_mark(self._mkey, s, steps=c)
                            self._device_seam(
                                dt, self.multi_step,
                                (self.state, chunk, rng), steps=c,
                            )
                        # Every batch index that would have logged in the
                        # per-step loop still logs (there can be several
                        # per chunk when log_interval < fused_steps).
                        j = -(-i0 // cfg.log_interval) * cfg.log_interval
                        while j < i0 + c:
                            log_batch(epoch, j, losses[j - i0])
                            j += cfg.log_interval
                    else:
                        # Tail shorter than the compiled chunk: step it
                        # batch-by-batch (no extra compilation).
                        for j in range(c):
                            rng = jax.random.fold_in(self._key, self._step_no)
                            self.state, metrics = self.train_step(
                                self.state, chunk[j], rng
                            )
                            self._step_no += 1
                            if not self._first_dispatched:
                                self._note_first_dispatch()
                            s = metrics["loss_sum"]
                            epoch_sum_dev = (
                                s
                                if epoch_sum_dev is None
                                else epoch_sum_dev + s
                            )
                            if self._mreg is not None:
                                dt = self._mreg.step_mark(self._mkey, s)
                                self._device_seam(
                                    dt, self.train_step,
                                    (self.state, chunk[j], rng),
                                )
                            if (i0 + j) % cfg.log_interval == 0:
                                log_batch(epoch, i0 + j, metrics["loss_sum"])
                    yield

            # One fetch for the whole epoch's average (O(1)-syncs rule).
            # Wedge-watchdog-bounded on spanning submeshes: the sum
            # transits the step's cross-host reduction, so a peer that
            # stopped dispatching wedges THIS fetch first.
            self._host_syncs += 1
            avg = self._wedged_fetch(
                lambda: float(epoch_sum_dev),
                f"epoch {epoch} loss fetch",
            ) / n_per_epoch
            # Device memory books ride the sync just paid (never the
            # dispatch hot loop) — sampled BEFORE the divergence gate
            # below so even a diverging trial's books close.
            if self._mreg is not None:
                tele_device.sample_memory(
                    self._mkey, self.trial.devices, where="epoch",
                    trial_id=cfg.trial_id, group_id=self.trial.group_id,
                )
            # Divergence gate at the sync the loop already pays: a
            # non-finite epoch average is a terminal trial RESULT
            # (deterministic training replays the same NaN on retry) —
            # raised before the checkpoint write below so NaN weights
            # are never persisted over a valid checkpoint.
            check_finite(
                avg,
                "epoch average train loss",
                step=self._step_no,
                trial_id=cfg.trial_id,
            )
            # Loss watch sees only finite losses: a non-finite average
            # is already a *terminal* verdict, not a precursor.
            if self._amon is not None:
                self._amon.observe_loss(
                    cfg.trial_id, epoch=epoch, train_loss=avg
                )
            self._log(
                "====> Epoch: {} Average loss: {:.4f}".format(epoch, avg)
            )
            epoch_record = {"epoch": epoch, "avg_train_loss": avg}

            if self.test_iter is not None:
                # On-device loss accumulation: the per-batch adds are
                # async dispatches; the single float() at the end is the
                # epoch's only eval host sync (round 1 synced every
                # batch, the last per-batch round-trip on the hot path).
                test_sum_dev, first_batch, first_recon = None, None, None
                for j, (tbatch, tweights) in enumerate(
                    self.test_iter.batches()
                ):
                    if cfg.eval_sampled:
                        # Distinct key per (epoch, batch), disjoint from
                        # the train stream (offset past any step count).
                        erng = jax.random.fold_in(
                            self._key, 2**28 + epoch * 2**16 + j
                        )
                        out = self.eval_step(
                            self.state, tbatch, tweights, erng
                        )
                    else:
                        out = self.eval_step(self.state, tbatch, tweights)
                    test_sum_dev = (
                        out["loss_sum"]
                        if test_sum_dev is None
                        else test_sum_dev + out["loss_sum"]
                    )
                    if j == 0 and self._save_images:
                        # batch values from the deterministic host view
                        # (the device batch is data-sharded and, on a
                        # process-spanning submesh, not fetchable whole);
                        # recon is replicated, hence fetchable anywhere.
                        if self._first_test_batch is None:
                            self._first_test_batch = (
                                self.test_iter.first_host_batch()
                            )
                        first_batch = self._first_test_batch
                        first_recon = np.asarray(out["recon"])
                    yield
                # Exact-count divisor: every real row was evaluated, the
                # padded rows carried weight 0.0.
                self._host_syncs += 1
                test_avg = self._wedged_fetch(
                    lambda: float(test_sum_dev),
                    f"epoch {epoch} test loss fetch",
                ) / self.test_iter.num_rows
                self._log("====> Test set loss: {:.4f}".format(test_avg))
                epoch_record["test_loss"] = test_avg
                self.result.final_test_loss = test_avg
                if self._save_images and first_batch is not None:
                    with self._guard():
                        # input-vs-recon grid (vae-hpo.py:106-116)
                        n = min(8, first_batch.shape[0])
                        comparison = np.concatenate(
                            [first_batch[:n], first_recon[:n]]
                        )
                        save_image_grid(
                            comparison,
                            os.path.join(
                                self.out_dir, f"reconstruction_{epoch}.png"
                            ),
                            nrow=n,
                        )

            if self._images_requested:
                # prior-sample grid (vae-hpo.py:163-170). The dispatch is
                # UNIFORM across owner processes (a jit program on the
                # submesh — writer-gating it would desynchronize SPMD on
                # a spanning group); only the fetch + PNG write below are
                # writer-only.
                # sample keys live in a disjoint fold_in range (steps
                # count up from 0; fold_in data must be non-negative)
                sample_out = self.sample_step(
                    self.state, jax.random.fold_in(self._key, 2**30 + epoch)
                )
                if self._save_images:
                    with self._guard():
                        save_image_grid(
                            np.asarray(sample_out),
                            os.path.join(self.out_dir, f"sample_{epoch}.png"),
                        )

            self.result.history.append(epoch_record)
            self.result.final_train_loss = avg
            bus = get_bus()
            if bus is not None:
                bus.emit(
                    "epoch",
                    trial_id=cfg.trial_id,
                    group_id=self.trial.group_id,
                    step=self._step_no,
                    **epoch_record,
                )
            if self._save_checkpoint:
                # Sharded states gather to replicated first — dispatched
                # on ALL owners (uniform program; a writer-local gather
                # would desynchronize a spanning submesh), making every
                # leaf fully addressable for the writer's fetch below.
                snap = (
                    self._gather_state(self.state)
                    if self._gather_state is not None
                    else self.state
                )
            if self._save_checkpoint and self._is_writer:
                with self._guard():
                    # Per-epoch checkpoint = the resume boundary. Keep
                    # the scheduler loop responsive: start the
                    # device→host copy async, yield once so other trials
                    # keep dispatching, then hand the serialize+disk-
                    # write to a background thread. The snapshot is
                    # taken before the next epoch's first step, so
                    # donation can't invalidate it (the gathered copy is
                    # its own buffer in the sharded case).
                    jax.tree.map(lambda x: x.copy_to_host_async(), snap)
                    yield
                    _snap_t0 = time.perf_counter()
                    host_state = self._wedged_fetch(
                        lambda: jax.device_get(snap),
                        f"epoch {epoch} checkpoint snapshot fetch",
                    )
                    # Checkpoint boundary is the trial's memory high-
                    # water moment (the gathered/host-bound snapshot is
                    # live alongside the training state) — sample it.
                    if self._mreg is not None:
                        tele_device.sample_memory(
                            self._mkey, self.trial.devices,
                            where="checkpoint",
                            trial_id=cfg.trial_id,
                            group_id=self.trial.group_id,
                        )
                    meta = {
                        **asdict(cfg),
                        "completed_epochs": epoch,
                        # Optimizer-step count at this epoch boundary:
                        # resume cross-checks it against the restored
                        # state so a crash landing between the two
                        # atomic replaces (state newer than sidecar) is
                        # detected, not silently re-trained.
                        "step": int(host_state.step),
                        "history": list(self.result.history),
                    }
                    # The device→host snapshot is the drain boundary
                    # (docs/RESILIENCE.md "Snapshot-fast drain"): once
                    # it lands in the RAM cache, a preemption can free
                    # this trial's slices and a same-process re-place
                    # can restore without touching disk — persistence
                    # below runs behind. Gated on the same opt-in as
                    # the read side: a standalone run_hpo must not pin
                    # host copies of large states nothing will read.
                    if self._ram_restore:
                        snapshot_cache().put(
                            self._ckpt_path, host_state, meta
                        )
                    if bus is not None:
                        bus.emit(
                            "ckpt_snapshot",
                            trial_id=cfg.trial_id,
                            group_id=self.trial.group_id,
                            step=int(host_state.step),
                            epoch=epoch,
                            wall_s=round(
                                time.perf_counter() - _snap_t0, 6
                            ),
                        )
                    self._join_ckpt()
                    self._ckpt_thread = threading.Thread(
                        target=self._write_ckpt,
                        args=(host_state, meta),
                        # Non-daemon: interpreter exit waits for the
                        # write (atexit joins it), so a crash elsewhere
                        # in the sweep can't kill a checkpoint
                        # mid-flight.
                        daemon=False,
                    )
                    self._ckpt_thread.start()
            # One agreement per epoch: all owners of a spanning submesh
            # kill the trial together if any of them deferred a failure.
            self._agree_boundary(f"epoch {epoch} boundary work")

        # drain the pipeline so wall-clock covers real completion
        # (wedge-watchdog-bounded: the last dispatched steps hold
        # cross-host collectives a lost peer never finishes)
        self._wedged_fetch(
            lambda: jax.block_until_ready(self.state.params),
            "completion block_until_ready",
        )
        with self._guard():
            self._join_ckpt()
        self.result.wall_s = time.time() - t0
        self.result.steps = self._step_no
        self.result.host_syncs = self._host_syncs
        if self._is_writer:
            with self._guard():
                os.makedirs(self.out_dir, exist_ok=True)
                with open(
                    os.path.join(self.out_dir, "metrics.json"), "w"
                ) as f:
                    json.dump(
                        {
                            "trial_id": self.result.trial_id,
                            "group_id": self.result.group_id,
                            "config": asdict(cfg),
                            "dataset": self.result.dataset,
                            "dataset_synthetic": self.result.dataset_synthetic,
                            "history": self.result.history,
                            "wall_s": self.result.wall_s,
                            "steps": self.result.steps,
                        },
                        f,
                        indent=2,
                    )
        self._agree_boundary("completion work")
        self._log(f"Done. time: {self.result.wall_s:f}")


# --- graceful drain on SIGTERM/SIGINT (docs/RESILIENCE.md) ----------
# run_hpo installs these around its scheduling loop. First signal: the
# loop finishes the current dispatch cycle, lands every pending
# checkpoint write, records all in-flight attempts as "preempted" in
# the ledger (fsync'd), and raises HostPreemption — a supervised
# worker maps that to cluster.PREEMPTION_EXIT_CODE
# (supervision.exit_code_for), and a resumed run_hpo loses at most one
# checkpoint cadence of work. Second signal: the operator means it —
# the default disposition is restored and the signal re-raised.
# Module-level state because the handler must outlive _run_hpo_body's
# closures and signal.signal only works on the main thread.
_DRAIN: dict = {"sig": None, "prev": None}


def _install_drain_handlers() -> None:
    import signal

    _DRAIN["sig"] = None
    if threading.current_thread() is not threading.main_thread():
        return  # signal.signal is main-thread-only; drain unavailable

    def on_signal(signum, frame):
        if _DRAIN["sig"] is not None:
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        _DRAIN["sig"] = signum

    prev = {}
    for s in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[s] = signal.signal(s, on_signal)
        except (ValueError, OSError):  # embedded/exotic hosts
            pass
    _DRAIN["prev"] = prev


def _restore_drain_handlers() -> None:
    import signal

    prev, _DRAIN["prev"] = _DRAIN.get("prev"), None
    _DRAIN["sig"] = None
    for s, h in (prev or {}).items():
        try:
            signal.signal(s, h)
        except (ValueError, OSError):
            pass


def _aot_admit(keys: dict, raw_fns: dict, avals_builder, state, primary):
    """The one admission protocol (generator), shared by the classic
    and stacked runners: for each program key, **take** a READY
    registry executable, **wait cooperatively** (yield — the host loop
    keeps other submeshes stepping) while a farm worker compiles the
    PRIMARY program, or **claim** an unstarted primary and compile it
    inline through the registry (same wall the jit path would pay at
    first dispatch, but timed, attributed, and reusable by every
    later same-program trial). Non-primary programs (the tail step of
    a fused config) are take-if-ready only — never worth waiting or
    inline-compiling for (jit compiles them lazily IF a tail exists).

    Returns ``(executables, admission)`` where ``admission`` records
    the primary's outcome: ``hit`` (ready at admission), ``wait``
    (farm finished it while we yielded), ``inline`` (we compiled it),
    ``jit`` (fallback — failed compile, aval mismatch, or wait
    deadline). A registry executable is swapped in only when its
    recorded avals structurally match the trial's REAL state (resume
    restores, vocabulary drift) — mismatch is a silent jit fallback,
    never a call-time TypeError mid-sweep.
    """
    from multidisttorch_tpu.compile import programs as _cprog
    from multidisttorch_tpu.compile.registry import (
        COMPILING,
        PENDING,
        READY,
        SOURCE_INLINE,
        get_executable_registry,
    )

    out: dict = {}
    admission = {"outcome": "jit", "wait_s": 0.0, "program": None}
    if not keys:
        return out, admission
    reg = get_executable_registry()
    t0 = time.perf_counter()
    wait_deadline = t0 + float(os.environ.get("MDT_AOT_WAIT_S", "600"))
    avals = None
    order = [primary] + [k for k in keys if k != primary]
    for which in order:
        key = keys[which]
        is_primary = which == primary
        waited = False
        if is_primary:
            # PENDING means a farm worker WILL compile this — wait for
            # it too, not just COMPILING: claiming a queued farm job
            # and compiling it inline would stall the host loop, which
            # is the one thing the farm exists to prevent. (A torn
            # farm shutdown releases its queued entries, so this wait
            # cannot outlive the farm; the deadline bounds the rest.)
            while (
                reg.status(key) in (PENDING, COMPILING)
                and time.perf_counter() < wait_deadline
            ):
                waited = True
                time.sleep(0.001)
                yield
        # The avals guard runs BEFORE take(): take() books a cache_hit
        # (event + hits counter), and a registry executable the guard
        # is about to reject (resume restores, vocabulary drift) was
        # never served — the books must show the jit fallback that
        # actually ran, not a phantom hit. (Avals are immutable once
        # READY, so check-then-take cannot race.)
        ex = None
        entry_avals = reg.avals(key)
        rejected = entry_avals is not None and not _cprog.avals_match(
            entry_avals[0], state
        )
        if not rejected:
            ex = reg.take(key)
        outcome = ("wait" if waited else "hit") if ex is not None else None
        if ex is None and not rejected and is_primary and reg.claim(key):
            if avals is None:
                try:
                    avals = avals_builder()
                except Exception as e:  # noqa: BLE001 — aval
                    # derivation failing is a registry problem, not a
                    # trial problem: the jit fallback must still run.
                    reg.fail(key, f"avals: {type(e).__name__}: {e}")
                    avals = None
            if avals is not None:
                e = reg.compile_now(
                    key, raw_fns[which], avals[which], source=SOURCE_INLINE
                )
                if e.status == READY:
                    ex = e.compiled
                    outcome = "inline"
        if ex is not None:
            entry_avals = reg.avals(key)
            if entry_avals is None or not _cprog.avals_match(
                entry_avals[0], state
            ):
                ex = None
                outcome = None
        if ex is not None:
            out[which] = ex
        if is_primary:
            admission = {
                "outcome": outcome or "jit",
                "wait_s": round(time.perf_counter() - t0, 4),
                "program": _cprog.program_label(key),
            }
    return out, admission


def stack_bucket_key(cfg: TrialConfig) -> tuple:
    """The shape signature under which trials may share one compiled
    stacked program: everything that changes an array shape or the
    compiled step structure. Scalar hypers (lr, beta, seed) and the
    epoch target deliberately stay OUT — they are the vmapped axis."""
    return (
        cfg.batch_size,
        cfg.hidden_dim,
        cfg.latent_dim,
        cfg.fused_steps,
        cfg.grad_accum,
        cfg.remat,
    )


def config_is_stackable(cfg: TrialConfig) -> bool:
    """Whether a config can ride a stacked bucket at all. Sampled eval
    is the one per-trial knob the stacked eval step does not carry
    (posterior-mean eval only); a sharded-update (zero_update) state
    shards over the submesh where stacked states replicate; a
    pipelined trial is a vector of submeshes. All three run their own
    paths."""
    return (
        not cfg.eval_sampled
        and not cfg.zero_update
        and cfg.pipeline_stages == 1
    )


def data_shape_sig(ds: Dataset, batch_size: int) -> tuple:
    """The dataset half of a co-pack decision: feature dim (batch-shape
    agreement) and per-epoch batch count (lockstep-round agreement).
    Deliberately NOT the dataset's identity — K lanes reading K
    different datasets of one shape class share a bucket (docs/DATA.md
    heterogeneous lanes)."""
    return (int(ds.images.shape[1]), len(ds) // max(1, int(batch_size)))


class _StackedBucketRun:
    """One shape-bucket of K stacked trials on ONE submesh, as a
    cooperative generator (the stacked sibling of :class:`_TrialRun`).

    All lanes advance in lockstep rounds of ``num_batches`` optimizer
    steps (one round = one epoch for every lane, since bucket members
    share dataset and batch size by construction); each dispatch is one
    vmapped program advancing every lane at once, scan-chunked by the
    bucket's ``fused_steps``. A lane that reaches its config's epoch
    target retires — its result and checkpoint are captured from a
    compiled lane-slice read — and is refilled in place from the
    bucket's pending queue (``write_lane``; traced lane index, so no
    recompilation ever) or masked inactive when the queue is dry.

    Per-trial RNG discipline matches the unstacked *per-step* path
    exactly (``fold_in(key(seed+1), step)``), so a stacked trial's
    weights are bit-identical to the same config run unstacked with
    ``fused_steps=1`` — the parity contract tests/test_stacking.py
    enforces.
    """

    def __init__(
        self,
        trial: TrialMesh,
        items: Sequence[tuple[int, TrialConfig]],
        train_data: Dataset,
        test_data: Optional[Dataset],
        out_dir: str,
        *,
        max_lanes: int = 8,
        save_checkpoint: bool = True,
        verbose: bool = True,
        injector=None,  # faults.inject.FaultInjector | None
        retry: Optional[RetryPolicy] = None,
        ledger: Optional[SweepLedger] = None,
        attempts: Optional[dict] = None,  # config index -> attempts started
        chashes: Optional[dict] = None,  # config index -> config hash
        infra_fails: Optional[dict] = None,  # config index -> infra failures
        datasets: Optional[dict] = None,  # config index -> Dataset
        ckpt_format: Optional[str] = None,
    ):
        template = items[0][1]
        for _, cfg in items:
            if stack_bucket_key(cfg) != stack_bucket_key(template):
                raise ValueError(
                    "stacked bucket mixes shape keys: "
                    f"{stack_bucket_key(cfg)} vs {stack_bucket_key(template)}"
                )
        # Heterogeneous lanes (docs/DATA.md): a member with its own
        # dataset reads it through its lane's slot of the one stacked
        # gather; members without one read the bucket's shared data.
        # Shape-class agreement (dim + per-epoch batches) is the
        # co-pack contract callers already grouped by — re-checked here
        # and by the iterator.
        self._default_data = train_data
        self._datasets = dict(datasets or {})
        self._ref_data = self._datasets.get(items[0][0], train_data)
        base_sig = data_shape_sig(self._ref_data, template.batch_size)
        for idx, _cfg in items:
            ds = self._datasets.get(idx, train_data)
            sig = data_shape_sig(ds, template.batch_size)
            if sig != base_sig:
                raise ValueError(
                    f"stacked bucket mixes dataset shape classes: "
                    f"{sig} vs {base_sig} (member {idx}, dataset "
                    f"{ds.name!r})"
                )
        self.trial = trial
        self.out_dir = out_dir
        self.queue: list[tuple[int, TrialConfig]] = list(items)
        self.results: dict[int, TrialResult] = {}
        self._save_checkpoint = save_checkpoint
        self._ckpt_format = (
            ckpt_format if ckpt_format is not None else default_format()
        )
        self._verbose = verbose
        self._host_syncs = 0
        self._is_writer = trial.is_writer_process
        # Lane supervision (docs/RESILIENCE.md): a faulted lane is
        # retired through the SAME mask-and-refill machinery finished
        # lanes use — the other K-1 lanes never stop. Retried lanes
        # restart from scratch (stacked lanes checkpoint only at
        # retirement, so there is no mid-trial checkpoint to resume;
        # the bucket queue's natural serialization stands in for
        # backoff).
        self._injector = injector
        self._retry = retry
        self._ledger = ledger
        self._attempts = attempts if attempts is not None else {}
        self._chashes = chashes if chashes is not None else {}
        self._infra_fails = infra_fails if infra_fails is not None else {}
        self._round_step0: dict[int, int] = {}
        # Telemetry: stacked step timings are attributed to the BUCKET
        # (one series per group's bucket, lanes= tagging the live lane
        # count), never to a single lane — the per-lane effective rate
        # is derived in the registry (telemetry.metrics.StepSeries).
        # Device books and straggler detection follow the same scoping:
        # the bucket is the dispatch unit, so its compiled program's
        # cost analysis and its step-time stream are bucket-keyed.
        self._mreg = get_registry()
        self._mkey = f"bucket-g{trial.group_id}"
        self._amon = get_monitor()
        self._cost_done = False
        # Cooperative bucket drain (the movable-stacked-placements
        # seam): request_drain() makes run() return at the NEXT round
        # boundary — every live lane's state then sits at an exact
        # epoch boundary, which is the only point the classic resume
        # path restores bit-identically. drain_snapshot() then fetches
        # each live lane device→host and persists the lane checkpoints
        # on one background writer (the classic runner's
        # _ckpt_thread/_ckpt_idle/_join_ckpt protocol, bucket-wide).
        self._drain_requested = False
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_error: Optional[BaseException] = None

        self.model = VAE(
            hidden_dim=template.hidden_dim, latent_dim=template.latent_dim
        )
        self.fused = template.fused_steps
        self.batch_size = template.batch_size

        k = min(len(self.queue), max_lanes)
        first = [self.queue.pop(0) for _ in range(k)]
        # Per-lane host bookkeeping; None = lane retired and unfillable.
        self.lanes: list[Optional[dict]] = [
            self._fresh_lane(i, cfg) for i, cfg in first
        ]
        for lane in self.lanes:
            self._note_attempt_start(lane)
        # Input-stall seam (docs/DATA.md): the iterator reports each
        # interval the dispatch loop sat blocked obtaining a batch.
        # Wired only when telemetry is on (metrics registry feeds the
        # StepSeries wait book; the bus gets a per-round input_wait
        # event) — OFF constructs nothing and reads no clocks.
        self._wait_counts = None
        wait_hook = None
        if self._mreg is not None or get_bus() is not None:
            self._wait_counts = {"wait_s": 0.0, "bytes": 0}
            series = (
                self._mreg.step_series(self._mkey)
                if self._mreg is not None
                else None
            )

            def wait_hook(dt, nbytes, _series=series):
                if _series is not None:
                    _series.note_wait(dt, nbytes)
                self._wait_counts["wait_s"] += dt
                self._wait_counts["bytes"] += nbytes
        self._input_t0 = time.time()
        self.data = StackedTrialDataIterator(
            self._ref_data, trial, self.batch_size,
            seeds=[lane["cfg"].seed for lane in self.lanes],
            datasets=[lane["data"] for lane in self.lanes],
            fault_hook=(
                None if injector is None else self._stacked_fault_hook
            ),
            wait_hook=wait_hook,
        )
        self.test_iter = (
            EvalDataIterator(test_data, trial, self.batch_size)
            if test_data is not None and len(test_data) > 0
            else None
        )
        step_kw = dict(remat=template.remat, grad_accum=template.grad_accum)
        self.sstep = make_stacked_train_step(trial, self.model, **step_kw)
        self.smulti = (
            make_stacked_multi_step(trial, self.model, **step_kw)
            if self.fused > 1
            else None
        )
        self.seval = (
            make_stacked_eval_step(trial, self.model)
            if self.test_iter is not None
            else None
        )
        self.read_lane, self.write_lane = make_lane_ops(trial)
        self.state = create_stacked_train_state(
            trial, self.model, [lane["cfg"].seed for lane in self.lanes]
        )
        self._refresh_lane_arrays()
        # AOT admission for the bucket's vmapped programs (the stacked
        # path is always the default family, single-controller — the
        # same eligibility envelope as the classic path's check).
        self._sstep_raw = self.sstep
        self._smulti_raw = self.smulti
        self._aot_keys: dict = {}
        self._admission = {"outcome": "jit", "wait_s": 0.0, "program": None}
        self._first_dispatched = False
        if os.environ.get("MDT_AOT_ADMISSION", "1") != "0":
            from multidisttorch_tpu.compile import programs as _cprog

            bucket = stack_bucket_key(template)
            lanes = len(self.lanes)
            self._aot_keys["train"] = _cprog.stacked_train_key(
                trial, bucket, lanes
            )
            if self.fused > 1:
                self._aot_keys["multi"] = _cprog.stacked_multi_key(
                    trial, bucket, lanes
                )
            self._aot_template = template

    def _data_of(self, idx: int) -> Dataset:
        """The dataset config-index ``idx``'s lane reads (its own per-
        submission dataset, else the bucket's shared default)."""
        return self._datasets.get(idx, self._default_data)

    def _fresh_lane(self, idx: int, cfg: TrialConfig) -> dict:
        return {
            "idx": idx,
            "cfg": cfg,
            "epochs_done": 0,
            "history": [],
            "steps": 0,
            "t0": time.time(),
            "syncs0": self._host_syncs,
            "data": self._data_of(idx),
        }

    def _refresh_lane_arrays(self) -> None:
        """Rebuild the per-dispatch (K,) arrays after fill/retire/refill.
        Retired lanes keep placeholder hypers under a 0.0 active mask —
        the compiled program never changes shape."""
        def per_lane(fn, default):
            return [
                fn(lane["cfg"]) if lane is not None else default
                for lane in self.lanes
            ]

        self.hypers = TrialHypers.stack(
            per_lane(lambda c: c.lr, 1e-3),
            per_lane(lambda c: c.beta, 1.0),
            active=per_lane(lambda c: 1.0, 0.0),
        )
        self.base_rngs = jnp.stack(
            [
                jax.random.key((lane["cfg"].seed if lane else 0) + 1)
                for lane in self.lanes
            ]
        )

    def _lane_steps(self):
        return jnp.asarray(
            [lane["steps"] if lane else 0 for lane in self.lanes], jnp.int32
        )

    def _log(self, *args, level: int = logging.INFO):
        if self._verbose:
            log0(*args, trial=self.trial, level=level)

    def _device_seam(self, dt, fn, args, *, steps: int = 1) -> None:
        """The bucket's device-book seam (telemetry ON only — call
        sites sit inside the ``self._mreg is not None`` guard). Cost
        analysis covers the COMPILED lane count (the vmapped program
        computes every lane, masked or live), recorded once per bucket;
        per-dispatch step times feed the straggler detector under the
        bucket key."""
        if not self._cost_done:
            self._cost_done = True
            template = next(
                lane for lane in self.lanes if lane is not None
            )["cfg"]
            tele_device.record_step_cost(
                self._mkey, fn, args, steps=steps, lanes=len(self.lanes),
                devices=self.trial.devices,
                group_id=self.trial.group_id,
                cache_key=(
                    "bucket", stack_bucket_key(template), len(self.lanes)
                ),
            )
            # Re-open after the AOT compile (see _TrialRun._device_seam).
            self._mreg.step_series(self._mkey).open_interval()
        if self._amon is not None and dt is not None:
            self._amon.observe_step(self._mkey, dt)

    def _emit_lane(self, kind: str, lane_k: int, trial_id=None, **data):
        """Lane-churn telemetry (retire/refill/fault/diverge/mask)."""
        bus = get_bus()
        if bus is not None:
            bus.emit(
                kind,
                trial_id=trial_id,
                lane=lane_k,
                group_id=self.trial.group_id,
                **data,
            )

    def _bump_steps(self, n: int) -> None:
        for lane in self.lanes:
            if lane is not None:
                lane["steps"] += n

    # -- lane supervision (chaos/retry support) ----------------------

    def _note_attempt_start(self, lane: dict) -> None:
        idx = lane["idx"]
        self._attempts[idx] = self._attempts.get(idx, 0) + 1
        if self._ledger is not None:
            self._ledger.attempt_start(
                lane["cfg"].trial_id,
                self._chashes.get(idx, ""),
                self._attempts[idx],
            )

    def _note_attempt_end(
        self, lane: dict, status: str, *, error: str = "", summary=None
    ) -> None:
        if self._ledger is not None:
            idx = lane["idx"]
            self._ledger.attempt_end(
                lane["cfg"].trial_id,
                self._chashes.get(idx, ""),
                self._attempts.get(idx, 1),
                status,
                error=error,
                summary=summary,
            )

    def lane_progress(self, idx: int) -> Optional[dict]:
        """Executed-work progress for config index ``idx`` if it is
        currently riding a live lane (stacked lanes always start from
        scratch, so resumed_from is 0 by construction)."""
        for lane in self.lanes:
            if lane is not None and lane["idx"] == idx:
                return {
                    "resumed_from_step": 0,
                    "steps_at_failure": lane["steps"],
                }
        return None

    def record_preempted(self, error_text: str) -> None:
        """Ledger 'preempted' events for every live lane — called when a
        preemption elsewhere in the sweep kills the driver (and this
        bucket with it)."""
        for lane in self.lanes:
            if lane is not None:
                self._note_attempt_end(
                    lane, "preempted", error=error_text,
                    summary=self.lane_progress(lane["idx"]),
                )

    def request_drain(self) -> None:
        """Arm the cooperative drain: :meth:`run` returns at the next
        round boundary instead of starting another round."""
        self._drain_requested = True

    def drain_snapshot(self, idxs, reason: str = "") -> None:
        """Snapshot every live lane in ``idxs`` at its epoch boundary
        (the PR 15 snapshot path, all lanes in one pass): each lane's
        slice is read out of the stacked state (compiled dynamic-index
        read), fetched device→host, seeded into the RAM snapshot cache
        (a same-process re-place restores without touching disk), and
        persisted to its ``trial-{id}/state.msgpack`` on ONE background
        writer thread — the classic runner's checkpoint protocol,
        bucket-wide. Callers must have driven :meth:`run` to a round
        boundary first (:meth:`request_drain`): only a boundary state
        resumes bit-identically through the classic scan restore."""
        wanted = set(idxs)
        jobs = []
        for k, lane in enumerate(self.lanes):
            if lane is None or lane["idx"] not in wanted:
                continue
            cfg: TrialConfig = lane["cfg"]
            lane_state = self.read_lane(self.state, np.int32(k))
            host_state = jax.device_get(lane_state)
            ckpt = os.path.join(
                self.out_dir, f"trial-{cfg.trial_id}", "state.msgpack"
            )
            meta = {
                **asdict(cfg),
                "completed_epochs": lane["epochs_done"],
                "step": int(host_state.step),
                "history": list(lane["history"]),
            }
            snapshot_cache().put(ckpt, host_state, meta)
            jobs.append((host_state, ckpt, meta))
        if not jobs or not self._is_writer:
            return
        self._join_ckpt()
        self._ckpt_thread = threading.Thread(
            target=self._write_drain_ckpts,
            args=(jobs, reason),
            daemon=False,
        )
        self._ckpt_thread.start()

    def _write_drain_ckpts(self, jobs, reason: str) -> None:
        try:
            for host_state, ckpt, meta in jobs:
                save_state(
                    host_state,
                    ckpt,
                    metadata=meta,
                    format=self._ckpt_format,
                )
        except BaseException as e:  # re-raised at the next join
            self._ckpt_error = e

    def _join_ckpt(self) -> None:
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None
        if self._ckpt_error is not None:
            e, self._ckpt_error = self._ckpt_error, None
            raise RuntimeError(
                f"stacked bucket g{self.trial.group_id}: drain "
                "checkpoint write failed"
            ) from e

    def _ckpt_idle(self) -> bool:
        """No drain persist in flight (the snapshot-fast drain's
        non-blocking poll; :meth:`_join_ckpt` is the blocking/raising
        sibling)."""
        t = self._ckpt_thread
        return t is None or not t.is_alive()

    def _stacked_fault_hook(self, batch_index: int, stacked):
        """Poison a DIVERGE-covered lane's slice of the (K, B, ...) host
        batch: the NaN flows through that lane only (the vmapped program
        keeps lanes independent), so exactly one trial diverges."""
        out = stacked
        for k, lane in enumerate(self.lanes):
            if lane is None:
                continue
            tid = lane["cfg"].trial_id
            step = self._round_step0.get(k, lane["steps"]) + batch_index
            if self._injector.diverge_covers(tid, step):
                if out is stacked:
                    out = np.array(stacked, copy=True)
                out[k] = self._injector.poison_batch(tid, step, out[k])
        return out

    def _round_start_faults(self) -> None:
        """Fire lane-scoped infra faults due inside the coming round.

        A faulted lane is retired and refilled through the same
        mask-and-refill path finished lanes take — the other K-1 lanes
        keep training in the same compiled program. HostPreemption is
        NOT lane-scoped (the host is going away): it propagates and
        fails the bucket, as a real preemption would.
        """
        if self._injector is None:
            return
        from multidisttorch_tpu.faults.inject import (
            HostPreemption,
            InfraFault,
        )

        round_len = self.data.num_batches
        k = 0
        while k < len(self.lanes):
            lane = self.lanes[k]
            if lane is None:
                k += 1
                continue
            tid = lane["cfg"].trial_id
            try:
                self._injector.step_hook(tid, lane["steps"], round_len)
                self._injector.data_hook(tid, lane["steps"], round_len)
            except HostPreemption:
                raise
            except InfraFault as e:
                self._fault_lane(k, e)
                # Re-scan lane k WITHOUT advancing: the refill occupant
                # is about to run its own first round, and its faults
                # due in [0, round_len) must fire now, not be skipped.
                # Bounded: max_fires caps firings, the retry budget
                # caps requeues, so the queue drains.
                continue
            k += 1

    def _fault_lane(self, k: int, exc: BaseException) -> None:
        """Infra fault scoped to one lane: retire it (no result capture
        — its weights are suspect), requeue per the retry budget, and
        refill the lane from the bucket queue."""
        lane = self.lanes[k]
        idx, cfg = lane["idx"], lane["cfg"]
        error_text = f"{type(exc).__name__}: {exc}"
        fails = self._infra_fails[idx] = self._infra_fails.get(idx, 0) + 1
        progress = {"resumed_from_step": 0, "steps_at_failure": lane["steps"]}
        retrying = self._retry is not None and self._retry.should_retry(
            fails, INFRA
        )
        self._emit_lane(
            "lane_fault",
            k,
            trial_id=cfg.trial_id,
            step=lane["steps"],
            error=error_text,
            infra_failures=fails,
            retrying=retrying,
        )
        if retrying:
            self._note_attempt_end(
                lane, "retrying", error=error_text, summary=progress
            )
            # Retry from scratch at the queue's tail: stacked lanes
            # checkpoint only at retirement, and the queue's natural
            # serialization stands in for backoff.
            self.queue.append((idx, cfg))
            self._log(
                f"Trial {cfg.trial_id} lane {k} FAULTED ({error_text}); "
                f"lane retired, trial requeued (infra failure {fails}), "
                f"{sum(l is not None for l in self.lanes) - 1} lanes "
                "continue"
            )
        else:
            result = TrialResult(
                trial_id=cfg.trial_id,
                group_id=self.trial.group_id,
                config=cfg,
                out_dir=os.path.join(self.out_dir, f"trial-{cfg.trial_id}"),
                status="failed",
                error=error_text,
                dataset=lane["data"].name,
                dataset_synthetic=lane["data"].synthetic,
                stacked=True,
                attempt=self._attempts.get(idx, 1),
            )
            self.results[idx] = result
            self._note_attempt_end(
                lane, "failed", error=error_text, summary=progress
            )
            self._log(
                f"Trial {cfg.trial_id} lane {k} FAILED ({error_text}); "
                "retry budget exhausted, lane freed"
            )
        self._refill_or_mask(k)

    def _diverge_lane(self, k: int, avg: float) -> None:
        """Terminal divergence scoped to one lane: record the result
        (never retried — the config reproduces its own NaN) and refill."""
        lane = self.lanes[k]
        idx, cfg = lane["idx"], lane["cfg"]
        err = DivergenceError(
            "lane epoch average train loss",
            avg,
            step=lane["steps"],
            trial_id=cfg.trial_id,
        )
        result = TrialResult(
            trial_id=cfg.trial_id,
            group_id=self.trial.group_id,
            config=cfg,
            history=list(lane["history"]),
            out_dir=os.path.join(self.out_dir, f"trial-{cfg.trial_id}"),
            steps=lane["steps"],
            wall_s=time.time() - lane["t0"],
            host_syncs=self._host_syncs - lane["syncs0"],
            status="diverged",
            error=str(err),
            dataset=lane["data"].name,
            dataset_synthetic=lane["data"].synthetic,
            stacked=True,
            attempt=self._attempts.get(idx, 1),
        )
        self.results[idx] = result
        self._note_attempt_end(
            lane, "diverged", error=str(err),
            summary=_result_summary(result),
        )
        self._emit_lane(
            "lane_diverge",
            k,
            trial_id=cfg.trial_id,
            step=lane["steps"],
            avg_train_loss=avg,
        )
        self._log(
            f"Trial {cfg.trial_id} DIVERGED (stacked lane {k}, "
            f"non-finite loss at step {lane['steps']}); lane freed"
        )
        self._refill_or_mask(k)

    def _retire(self, k: int) -> None:
        """Capture lane k's result + checkpoint, then refill or mask."""
        lane = self.lanes[k]
        cfg: TrialConfig = lane["cfg"]
        lane_out_dir = os.path.join(self.out_dir, f"trial-{cfg.trial_id}")
        result = TrialResult(
            trial_id=cfg.trial_id,
            group_id=self.trial.group_id,
            config=cfg,
            history=list(lane["history"]),
            out_dir=lane_out_dir,
            dataset=lane["data"].name,
            dataset_synthetic=lane["data"].synthetic,
            stacked=True,
        )
        last = lane["history"][-1]
        result.final_train_loss = last["avg_train_loss"]
        result.final_test_loss = last.get("test_loss", float("nan"))
        result.steps = lane["steps"]
        result.wall_s = time.time() - lane["t0"]
        result.host_syncs = self._host_syncs - lane["syncs0"]

        # Lane slice out of the stacked state: a compiled dynamic-index
        # read (traced k — every retirement reuses one executable).
        lane_state = self.read_lane(self.state, np.int32(k))
        # Memory books: a stacked lane's optimizer footprint is its
        # slice of the (replicated) stacked state — the number
        # comparable against an unstacked replicated or zero_update
        # twin in run_summary/sweep_top.
        from multidisttorch_tpu.parallel.fsdp import optimizer_state_bytes

        result.optimizer_state_bytes = optimizer_state_bytes(
            lane_state
        )["per_device_bytes"]
        _bus = get_bus()
        if _bus is not None:
            _bus.emit(
                "optimizer_state",
                trial_id=cfg.trial_id,
                group_id=self.trial.group_id,
                lane=k,
                per_device_bytes=result.optimizer_state_bytes,
                total_bytes=result.optimizer_state_bytes,
                zero_update=False,
            )
        if self._is_writer:
            if self._save_checkpoint:
                host_state = jax.device_get(lane_state)
                ckpt = os.path.join(lane_out_dir, "state.msgpack")
                save_state(
                    host_state,
                    ckpt,
                    metadata={
                        **asdict(cfg),
                        "completed_epochs": lane["epochs_done"],
                        "step": int(host_state.step),
                        "history": list(lane["history"]),
                    },
                    # Retired lanes ride the checkpoint data plane too:
                    # same-bucket lanes share one trial-dir-scoped
                    # chunk store per trial, and identical warm-start
                    # chunks dedup across retirements. Same format knob
                    # as the classic runner (the service threads its
                    # configured format through).
                    format=self._ckpt_format,
                )
                result.checkpoint = ckpt
            os.makedirs(lane_out_dir, exist_ok=True)
            with open(os.path.join(lane_out_dir, "metrics.json"), "w") as f:
                json.dump(
                    {
                        "trial_id": result.trial_id,
                        "group_id": result.group_id,
                        "config": asdict(cfg),
                        "dataset": result.dataset,
                        "dataset_synthetic": result.dataset_synthetic,
                        "history": result.history,
                        "wall_s": result.wall_s,
                        "steps": result.steps,
                        "stacked": True,
                    },
                    f,
                    indent=2,
                )
        result.attempt = self._attempts.get(lane["idx"], 1)
        self.results[lane["idx"]] = result
        self._note_attempt_end(
            lane, "completed", summary=_result_summary(result)
        )
        self._emit_lane(
            "lane_retire",
            k,
            trial_id=cfg.trial_id,
            step=lane["steps"],
            epochs=lane["epochs_done"],
            wall_s=round(result.wall_s, 6),
        )
        self._log(
            f"Trial {cfg.trial_id} done (stacked lane {k}). "
            f"time: {result.wall_s:f}"
        )
        self._refill_or_mask(k)

    def _refill_or_mask(self, k: int) -> None:
        """The mask-and-refill tail shared by retirement, lane faults,
        and lane divergence: pop the next queued config into lane ``k``
        (a compiled dynamic-index write — no recompilation), or mask the
        lane inactive when the queue is dry."""
        if self.queue:
            idx, nxt = self.queue.pop(0)
            self.lanes[k] = self._fresh_lane(idx, nxt)
            self._note_attempt_start(self.lanes[k])
            self.state = self.write_lane(
                self.state,
                self.trial.device_put(build_lane_state(self.model, nxt.seed)),
                np.int32(k),
            )
            # The data half of the refill: the new occupant's stream —
            # and, for a per-submission dataset, its own arrays — swap
            # into lane k with zero recompiles.
            self.data.set_lane(k, nxt.seed, dataset=self._data_of(idx))
            self._emit_lane("lane_refill", k, trial_id=nxt.trial_id)
            # Refill swaps a fresh lane state into the stacked tree —
            # a watermark moment (old + new lane buffers both live).
            if self._mreg is not None:
                tele_device.sample_memory(
                    self._mkey, self.trial.devices, where="lane_refill",
                    group_id=self.trial.group_id,
                )
            self._log(
                f"Trial {nxt.trial_id} refilled into stacked lane {k} "
                "(no recompilation)"
            )
        else:
            self.lanes[k] = None  # masked out by active=0.0
            self._emit_lane("lane_masked", k)
        self._refresh_lane_arrays()

    def unfinished(self) -> list[tuple[int, TrialConfig]]:
        """Config items not yet completed (failure-isolation support)."""
        live = [
            (lane["idx"], lane["cfg"])
            for lane in self.lanes
            if lane is not None and lane["idx"] not in self.results
        ]
        return live + list(self.queue)

    def _admit_programs(self) -> Iterator[None]:
        """Cooperative AOT admission for the bucket (see
        ``_TrialRun._admit_programs`` — same protocol, vmapped keys)."""
        if not self._aot_keys:
            return
        from multidisttorch_tpu.compile import programs as _cprog

        primary = "multi" if self.fused > 1 else "train"
        raw = {"train": self._sstep_raw, "multi": self._smulti_raw}
        lanes = len(self.lanes)
        taken, self._admission = yield from _aot_admit(
            self._aot_keys,
            raw,
            lambda: _cprog.stacked_avals(self._aot_template, lanes),
            self.state,
            primary,
        )
        if "train" in taken:
            self.sstep = taken["train"]
        if "multi" in taken:
            self.smulti = taken["multi"]

    def _note_first_dispatch(self) -> None:
        """Bucket sibling of ``_TrialRun._note_first_dispatch`` —
        group-scoped (no single trial owns the bucket's admission)."""
        self._first_dispatched = True
        bus = get_bus()
        if bus is not None:
            bus.emit(
                "first_dispatch",
                group_id=self.trial.group_id,
                lanes=len(self.lanes),
                **self._admission,
            )

    def run(self) -> Iterator[None]:
        yield from self._admit_programs()
        n_per_epoch = self.data.samples_per_epoch
        while any(lane is not None for lane in self.lanes):
            if self._drain_requested:
                # Cooperative drain: exit at this round boundary —
                # every live lane's state is at an exact epoch
                # boundary (epochs_done and history are settled for
                # the finished round), so drain_snapshot() writes
                # checkpoints the classic resume replays
                # bit-identically.
                return
            # Lane-scoped infra faults due this round fire BEFORE the
            # round dispatches: the faulted lane retires and refills,
            # the others never notice.
            self._round_start_faults()
            if not any(lane is not None for lane in self.lanes):
                break
            # Per-lane step counts at round start: the data fault hook
            # maps (lane, batch index) -> global optimizer step with
            # these (lane["steps"] itself advances mid-round).
            self._round_step0 = {
                k: lane["steps"]
                for k, lane in enumerate(self.lanes)
                if lane is not None
            }
            round_sum_dev = None  # (K,) on-device
            # Live lane count at round start: lanes only change at
            # round boundaries, so this tags every dispatch's metrics
            # mark with the bucket's true occupancy.
            k_live = sum(lane is not None for lane in self.lanes)
            # Fresh timing interval per round (see _TrialRun.run): the
            # gap since the last mark is boundary work — eval, lane
            # retirement/refill — not a dispatch.
            if self._mreg is not None:
                self._mreg.step_series(self._mkey).open_interval()

            def add(dev_sums):
                nonlocal round_sum_dev
                round_sum_dev = (
                    dev_sums
                    if round_sum_dev is None
                    else round_sum_dev + dev_sums
                )

            if self.smulti is None:
                for batch in self.data.round_batches():
                    self.state, m = self.sstep(
                        self.state, self.hypers, batch,
                        self.base_rngs, self._lane_steps(),
                    )
                    self._bump_steps(1)
                    if not self._first_dispatched:
                        self._note_first_dispatch()
                    add(m["loss_sum"])
                    if self._mreg is not None:
                        dt = self._mreg.step_mark(
                            self._mkey, round_sum_dev, lanes=k_live
                        )
                        self._device_seam(
                            dt, self.sstep,
                            (self.state, self.hypers, batch,
                             self.base_rngs, self._lane_steps()),
                        )
                    yield
            else:
                for start, chunk in self.data.round_chunks(self.fused):
                    s = chunk.shape[0]
                    if s == self.fused:
                        self.state, m = self.smulti(
                            self.state, self.hypers, chunk,
                            self.base_rngs, self._lane_steps(),
                        )
                        self._bump_steps(s)
                        if not self._first_dispatched:
                            self._note_first_dispatch()
                        add(m["loss_sum"].sum(axis=0))
                        if self._mreg is not None:
                            dt = self._mreg.step_mark(
                                self._mkey, round_sum_dev,
                                steps=s, lanes=k_live,
                            )
                            self._device_seam(
                                dt, self.smulti,
                                (self.state, self.hypers, chunk,
                                 self.base_rngs, self._lane_steps()),
                                steps=s,
                            )
                    else:
                        # Tail shorter than the compiled chunk: per-step
                        # stacked dispatches (no extra compilation).
                        for j in range(s):
                            self.state, m = self.sstep(
                                self.state, self.hypers, chunk[j],
                                self.base_rngs, self._lane_steps(),
                            )
                            self._bump_steps(1)
                            if not self._first_dispatched:
                                self._note_first_dispatch()
                            add(m["loss_sum"])
                            if self._mreg is not None:
                                dt = self._mreg.step_mark(
                                    self._mkey, round_sum_dev, lanes=k_live
                                )
                                self._device_seam(
                                    dt, self.sstep,
                                    (self.state, self.hypers, chunk[j],
                                     self.base_rngs, self._lane_steps()),
                                )
                    yield

            # One fetch for every lane's epoch average (O(1)-syncs rule:
            # the bucket pays per-round what one trial used to pay).
            self._host_syncs += 1
            train_sums = np.asarray(round_sum_dev)
            # Memory books ride the round boundary's existing sync.
            if self._mreg is not None:
                tele_device.sample_memory(
                    self._mkey, self.trial.devices, where="round",
                    group_id=self.trial.group_id,
                )
            # Input-stall books ride it too: one cumulative input_wait
            # event per round (docs/DATA.md) — the console/summary
            # mirror of the registry's StepSeries wait book.
            if self._wait_counts is not None:
                bus = get_bus()
                if bus is not None:
                    bus.emit(
                        "input_wait",
                        group_id=self.trial.group_id,
                        key=self._mkey,
                        wait_s=round(self._wait_counts["wait_s"], 6),
                        bytes=self._wait_counts["bytes"],
                        wall_s=round(time.time() - self._input_t0, 6),
                    )

            test_sums = None
            if self.test_iter is not None:
                test_dev = None
                for tbatch, tweights in self.test_iter.batches():
                    out = self.seval(self.state, self.hypers, tbatch, tweights)
                    test_dev = (
                        out["loss_sum"]
                        if test_dev is None
                        else test_dev + out["loss_sum"]
                    )
                    yield
                self._host_syncs += 1
                test_sums = np.asarray(test_dev)

            retiring = []
            diverged = []
            for k, lane in enumerate(self.lanes):
                if lane is None:
                    continue
                lane["epochs_done"] += 1
                avg = float(train_sums[k]) / n_per_epoch
                if not np.isfinite(avg):
                    # Terminal divergence, scoped to this lane — the
                    # vmapped program kept the NaN out of its
                    # neighbors (per-lane params/optimizer/losses).
                    diverged.append(k)
                    continue
                record = {"epoch": lane["epochs_done"], "avg_train_loss": avg}
                self._log(
                    "Trial {} ====> Epoch: {} Average loss: {:.4f}".format(
                        lane["cfg"].trial_id, lane["epochs_done"], avg
                    )
                )
                if test_sums is not None:
                    t = float(test_sums[k]) / self.test_iter.num_rows
                    record["test_loss"] = t
                    self._log(
                        "Trial {} ====> Test set loss: {:.4f}".format(
                            lane["cfg"].trial_id, t
                        )
                    )
                lane["history"].append(record)
                bus = get_bus()
                if bus is not None:
                    bus.emit(
                        "epoch",
                        trial_id=lane["cfg"].trial_id,
                        lane=k,
                        group_id=self.trial.group_id,
                        step=lane["steps"],
                        **record,
                    )
                if self._amon is not None:
                    self._amon.observe_loss(
                        lane["cfg"].trial_id,
                        epoch=lane["epochs_done"],
                        train_loss=avg,
                        lane=k,
                        group_id=self.trial.group_id,
                    )
                if lane["epochs_done"] >= lane["cfg"].epochs:
                    retiring.append(k)
            for k in diverged:
                self._diverge_lane(k, float(train_sums[k]) / n_per_epoch)
                yield
            for k in retiring:
                self._retire(k)
                yield
        jax.block_until_ready(self.state.params)


def run_hpo(
    configs: Sequence[TrialConfig],
    train_data: Dataset,
    test_data: Optional[Dataset] = None,
    *,
    groups: Optional[Sequence[TrialMesh]] = None,
    num_groups: Optional[int] = None,
    out_dir: str = "results",
    shard_across_trials: bool = False,
    save_images: bool = True,
    save_checkpoints: bool = True,
    verbose: bool = True,
    model_builder=None,
    model_parallel: int = 1,
    param_shardings_builder=None,
    resilient: bool = False,
    resume: bool = False,
    profile_dir: Optional[str] = None,
    stack_trials: bool = False,
    stack_max_lanes: int = 8,
    retry: Optional[RetryPolicy] = None,
    fault_plan=None,
    ledger: bool = True,
    ckpt_keep_last: int = 1,
    agree_timeout_s: Optional[float] = None,
    precompile: Optional[bool] = None,
) -> list[TrialResult]:
    """Run the configs over disjoint submeshes, concurrently, with no
    cross-trial synchronization.

    ``groups`` defaults to ``setup_groups(num_groups or len(configs))``.
    **More configs than groups is legal**: excess configs queue, and a
    submesh picks up its next trial the moment its current one finishes
    (greedy in single-controller mode; in multi-controller SPMD the
    assignment is the deterministic least-predicted-load schedule of
    :func:`balanced_assignment` — every process must make identical
    scheduling decisions without communicating, and trial durations are
    predictable from the configs). Trials whose submesh has no local
    devices are skipped on this process (multi-controller membership,
    ``vae-hpo.py:200-202``).

    ``model_builder(cfg)`` swaps the model family (e.g. ``ConvVAE`` for
    the β-VAE CIFAR config) while reusing all scaffolding; default is
    the flagship MLP VAE.

    ``model_parallel=m`` carves each trial's submesh 2-D (data × model),
    and ``param_shardings_builder(trial, model)`` maps a trial to its
    weight shardings (e.g. ``models.vae.vae_tp_shardings(trial)`` for
    Megatron TP, ``models.moe_vae.moe_vae_ep_shardings`` for expert
    parallelism, ``parallel.fsdp.fsdp_param_shardings`` for ZeRO-style
    state sharding) — every train/eval/sample step then pins that
    layout. Within-trial model sharding composed with trial parallelism
    from one driver call; the reference is DP-only (SURVEY.md §2c).

    ``resilient=True`` isolates failures: a trial raising marks its
    result ``status="failed"`` (exception text in ``.error``), frees the
    submesh, and the sweep continues. Default re-raises (honest errors,
    SURVEY.md Q8). Works multi-controller too: deterministic failures
    resolve identically on every owner process by SPMD determinism, and
    writer-only host-I/O failures are agreed at setup/epoch boundaries
    through a submesh-scoped health reduction — one trial's death frees
    its submesh on every owning process with no world barrier (contrast
    the reference, where a failed rank hangs the world's collectives).

    ``resume=True`` restores each trial from its per-epoch checkpoint
    under ``{out_dir}/trial-{id}/`` (skipping fully-trained trials), so
    an interrupted sweep re-run completes only the remaining work.

    ``profile_dir`` wraps the whole sweep in a JAX profiler trace
    (TensorBoard/Perfetto-loadable, device timelines included on TPU) —
    the tool for confirming submeshes stay busy and finding host-side
    dispatch contention (SURVEY.md §7 "hard parts").

    ``stack_trials=True`` enables the trial-stacking execution mode
    (docs/STACKING.md): when trials outnumber groups, configs sharing a
    shape bucket (:func:`stack_bucket_key` — same architecture and
    batch size, any lr/beta/seed/epochs) run K-at-a-time on ONE submesh
    through one vmapped program (``train.steps.make_stacked_*``), with
    finished trials retired and refilled in place without recompiling.
    Falls back to the classic one-trial-per-group path when there is
    nothing to stack (too few configs, or unstackable knobs). At most
    ``stack_max_lanes`` trials share one program. Single-controller
    only, default model family only; the driver raises on contradictory
    settings (``resume``, ``shard_across_trials``, custom
    ``model_builder`` / weight sharding) rather than silently running a
    different sweep; ``save_images`` is ignored for stacked buckets
    (no reconstruction/sample grids — run image trials unstacked).

    **Trial supervision** (docs/RESILIENCE.md): ``retry=RetryPolicy()``
    turns infra-class failures (worker exceptions, data-iterator faults,
    checkpoint I/O — ``hpo/supervision.py``'s classification) into
    supervised retries with capped exponential backoff; each retry
    resumes from the trial's last *valid* checkpoint
    (``train.checkpoint.restore_latest_valid`` scans back past torn or
    corrupt files), falling back to scratch when none survives. A
    non-finite loss is classified as **divergence** — a terminal trial
    result (``status="diverged"``, recorded, never retried, never
    raised: deterministic training replays the same NaN). A
    ``HostPreemption`` always propagates out of ``run_hpo`` — per-trial
    retry is meaningless when the host is going away; restart the driver
    instead. In stacked mode a faulted lane is retired and refilled
    through the mask-and-refill machinery (the other K-1 lanes never
    stop); retried lanes restart from scratch.

    ``ledger=True`` (default) appends every attempt's config hash and
    outcome to ``{out_dir}/sweep_ledger.jsonl`` (crash-safe JSONL,
    ``hpo/ledger.py``); with ``resume=True`` a killed-and-restarted
    ``run_hpo`` skips trials the ledger settled (completed/diverged
    under a byte-identical config) and re-runs only unfinished ones —
    the driver itself is preemption-safe.

    ``fault_plan`` (a ``faults.FaultPlan`` or ``FaultInjector``) arms
    deterministic chaos injection through the driver/step/data/
    checkpoint hook seams — CI-grade recovery drills, see
    ``tools/chaos_run.py``. ``ckpt_keep_last=K`` retains K checkpoint
    generations per trial (scan-back depth for retry-with-resume).
    ``agree_timeout_s`` bounds every multi-host health agreement so a
    dead peer produces a diagnosable ``TimeoutError`` instead of an
    indefinite hang (default: ``MDT_AGREE_TIMEOUT_S`` env, else 600 s).

    **Elastic multi-host** (docs/RESILIENCE.md "Elastic multi-host"):
    ``resume="scan"`` is the elastic-restart resume mode — settled
    trials are skipped via the ledger like ``resume=True``, but
    unfinished trials restore through the supervised scan-back
    (tolerating the torn/corrupt checkpoints a killed host leaves
    behind), with a cross-host restore agreement on spanning submeshes
    (min-over-owners valid step). Every cross-host device sync in the
    driver is wedge-watchdog-bounded (``MDT_WEDGE_TIMEOUT_S``, default
    = the agreement deadline): a peer that stops dispatching produces
    a named ``WedgedCollective`` (classified as preemption) instead of
    a hang. SIGTERM/SIGINT trigger a graceful drain: pending
    checkpoint writes land, in-flight attempts are recorded
    ``preempted`` in the ledger, and ``HostPreemption`` is raised (a
    supervised worker exits ``cluster.PREEMPTION_EXIT_CODE``); a
    second signal kills immediately. ``tools/sweep_supervisor.py``
    turns these contracts into automatic world-shrink restarts.

    **Compile farm** (docs/COMPILE.md): ``precompile=True`` (default:
    the ``MDT_PRECOMPILE=1`` env) walks the sweep's pending configs at
    entry and AOT-compiles every distinct train program — shape bucket
    x baked scalar hypers x predicted submesh — on background worker
    threads, so trial admission takes a finished executable instead of
    paying ``lower→compile`` on the host loop. Admission to a program
    still mid-compile waits *cooperatively* (other submeshes keep
    stepping); a program the farm has not reached is claimed and
    compiled inline (the pre-farm behavior, now timed and attributed
    per bucket as ``compile_start``/``compile_end``/``cache_hit``
    telemetry). Single-controller, default model family — the same
    envelope as stacking; other sweeps silently skip the farm. Every
    compile lands in the process-lifetime executable registry, so
    bucket-twin trials, retries, and refilled lanes never recompile
    even with the farm off.

    Returns results for locally-run trials, in config order.
    """
    if profile_dir is not None:
        from multidisttorch_tpu.utils.profiling import profile_trace

        trace_ctx = profile_trace(profile_dir)
    else:
        import contextlib

        trace_ctx = contextlib.nullcontext()
    enable_compile_cache()
    _install_drain_handlers()
    # The precompile farm (if the body starts one) is stashed here so
    # EVERY exit path — completion, failure isolation re-raise,
    # preemption, drain — tears it down: queued jobs are dropped and
    # in-flight compiles finish harmlessly into the registry.
    pool_holder: list = []
    try:
        with trace_ctx:
            return _run_hpo_body(
                configs,
                train_data,
                test_data,
                groups=groups,
                num_groups=num_groups,
                out_dir=out_dir,
                shard_across_trials=shard_across_trials,
                save_images=save_images,
                save_checkpoints=save_checkpoints,
                verbose=verbose,
                model_builder=model_builder,
                model_parallel=model_parallel,
                param_shardings_builder=param_shardings_builder,
                resilient=resilient,
                resume=resume,
                stack_trials=stack_trials,
                stack_max_lanes=stack_max_lanes,
                retry=retry,
                fault_plan=fault_plan,
                ledger=ledger,
                ckpt_keep_last=ckpt_keep_last,
                agree_timeout_s=agree_timeout_s,
                precompile=precompile,
                _pool_holder=pool_holder,
            )
    finally:
        for _pool in pool_holder:
            _pool.shutdown()
        _restore_drain_handlers()


def predicted_cost(cfg: TrialConfig, train_rows: int) -> int:
    """Relative duration estimate for one trial: optimizer steps to run.

    ``epochs`` is the reference's only duration knob (``vae-hpo.py:202``)
    and ``batch_size`` sets steps per epoch; both are known to every
    process before any trial starts, which is what lets the
    multi-controller scheduler balance load without communicating.
    """
    steps_per_epoch = max(1, train_rows // max(1, cfg.batch_size))
    return cfg.epochs * steps_per_epoch


def balanced_assignment(costs: Sequence[int], num_groups: int) -> list[int]:
    """Deterministic least-loaded assignment: config i → the group whose
    accumulated predicted cost is smallest (ties → lowest group index).

    Pure function of (costs, num_groups), so every process computes the
    identical schedule — the same no-communication constraint that
    forced the previous static round-robin. Least-loaded usually beats
    round-robin when epoch counts differ (costs [4,1,1,1] over 2 groups:
    round-robin loads (5,2), this gives (4,3)) but, like any online
    greedy rule, is not universally optimal (costs [2,1,1,2] favor
    round-robin); it never needs cost information round-robin lacks, and
    both are deterministic.
    """
    loads = [0] * num_groups
    out = []
    for c in costs:
        g = min(range(num_groups), key=lambda j: (loads[j], j))
        loads[g] += c
        out.append(g)
    return out


def _run_hpo_body(
    configs,
    train_data,
    test_data,
    *,
    groups,
    num_groups,
    out_dir,
    shard_across_trials,
    save_images,
    save_checkpoints,
    verbose,
    model_builder,
    model_parallel,
    param_shardings_builder,
    resilient,
    resume,
    stack_trials=False,
    stack_max_lanes=8,
    retry=None,
    fault_plan=None,
    ledger=True,
    ckpt_keep_last=1,
    agree_timeout_s=None,
    precompile=None,
    _pool_holder=None,
) -> list[TrialResult]:
    # Telemetry opt-in by environment (MDT_TELEMETRY[_DIR]) — a no-op
    # env read when off, and an explicit telemetry.configure() wins.
    from multidisttorch_tpu import telemetry as _telemetry

    _telemetry.configure_from_env()
    # Per-trial dataset references (docs/DATA.md): resolve every
    # distinct cfg.dataset ONCE at sweep entry (resolve_dataset's
    # process memo makes twin specs share one host array, preserving
    # the stacked gather's fused fast path). Resolution is
    # deterministic, so multi-controller processes agree without
    # communicating — but shard_across_trials partitions ONE shared
    # dataset across trials, which a per-trial dataset contradicts.
    if any(getattr(cfg, "pipeline_stages", 1) != 1 for cfg in configs):
        raise ValueError(
            "pipeline_stages > 1 trials are vectors of slice requests "
            "— run_hpo's equal-groups carve cannot host them. Submit "
            "them to the sweep service (multi-block placement, "
            "docs/SERVICE.md) or drive one directly with "
            "hpo.pipeline_run.run_pipeline_trial"
        )
    data_by_idx: dict[int, Dataset] = {}
    if any(getattr(cfg, "dataset", "") for cfg in configs):
        if shard_across_trials:
            raise ValueError(
                "per-trial cfg.dataset is incompatible with "
                "shard_across_trials (trial-sharding partitions the one "
                "shared dataset)"
            )
        from multidisttorch_tpu.data.store import resolve_dataset

        for i, cfg in enumerate(configs):
            if getattr(cfg, "dataset", ""):
                data_by_idx[i] = resolve_dataset(cfg.dataset)

    def data_of(i: int) -> Dataset:
        return data_by_idx.get(i, train_data)

    if groups is None:
        groups = setup_groups(
            num_groups if num_groups is not None else len(configs),
            model_parallel=model_parallel,
        )
    elif model_parallel != 1:
        raise ValueError(
            "model_parallel applies only when the driver carves the "
            "groups; carve your own with setup_groups(..., "
            "model_parallel=m) when passing groups="
        )
    if len(configs) < len(groups):
        raise ValueError(
            f"{len(configs)} configs but {len(groups)} device groups "
            "(fewer configs than groups would idle submeshes; carve "
            "fewer groups instead)"
        )
    # Multi-host failure isolation: failures must resolve identically on
    # every process owning a trial's submesh, or one process frees the
    # group while peers keep stepping it (desynchronized collectives —
    # the reference's failure mode is worse still: a dead rank hangs the
    # world, SURVEY.md §5). Two mechanisms, by failure class:
    #  - Deterministic failures (bad config, model build, NaN guards,
    #    data exhaustion): SPMD determinism raises them at the same
    #    dispatch point on every owner — identical local handling IS the
    #    agreement.
    #  - Writer-only host-I/O failures (image/checkpoint/metrics
    #    writes): deferred by _TrialRun._guard and agreed at setup /
    #    epoch boundaries via a submesh-scoped health reduction
    #    (collectives.group_all_ok) — no world barrier, unrelated trials
    #    unaffected.
    # Out of scope (documented): asymmetric failures *inside* the
    # dispatch stream (host OOM, device loss mid-epoch) — those desync
    # the submesh's program sequence itself and need runtime-level
    # preemption, which no SPMD framework recovers from at this layer.
    def needs_agreement(g: TrialMesh) -> bool:
        return resilient and jax.process_count() > 1 and g.spans_processes

    # --- trial supervision state (docs/RESILIENCE.md) ---------------
    injector = None
    if fault_plan is not None:
        from multidisttorch_tpu.faults.inject import FaultInjector
        from multidisttorch_tpu.faults.plan import FaultPlan

        if isinstance(fault_plan, FaultInjector):
            injector = fault_plan
        elif isinstance(fault_plan, FaultPlan):
            injector = FaultInjector(fault_plan)
        else:
            raise TypeError(
                f"fault_plan must be a FaultPlan or FaultInjector, got "
                f"{type(fault_plan).__name__}"
            )
        if jax.process_count() > 1:
            from multidisttorch_tpu.faults.plan import DIVERGE

            if any(s.kind == DIVERGE for s in injector.plan.specs):
                raise ValueError(
                    "fault_plan: DIVERGE injection is single-controller "
                    "only — the poison hook materializes the step's "
                    "batch host-side, which a process-spanning sharded "
                    "array cannot do. Drill divergence in a "
                    "single-process run; the other fault kinds work "
                    "multi-controller."
                )
    if agree_timeout_s is None:
        from multidisttorch_tpu.parallel.cluster import _env_timeout

        agree_timeout_s = _env_timeout("MDT_AGREE_TIMEOUT_S", 600.0)
    # The wedge watchdog's deadline for device-result fetches on
    # spanning submeshes (epoch/test loss, checkpoint gather,
    # completion drain): MDT_WEDGE_TIMEOUT_S, defaulting to the
    # agreement deadline so one knob bounds every cross-host sync.
    from multidisttorch_tpu.parallel.cluster import (
        _env_timeout as _wedge_env_timeout,
    )

    wedge_timeout_s = _wedge_env_timeout(
        "MDT_WEDGE_TIMEOUT_S", agree_timeout_s
    )
    # The sweep's durable control state: every attempt's config hash and
    # outcome. Writes are fsync'd JSONL appends (crash = at most one
    # torn, skipped line); only process 0 writes, every process reads
    # (skip decisions must be identical everywhere).
    chashes = {i: config_hash(asdict(cfg)) for i, cfg in enumerate(configs)}
    led = SweepLedger(
        out_dir, enabled=ledger, write=jax.process_index() == 0
    )
    prior_attempts = led.attempts() if led.enabled else {}
    attempts: dict[int, int] = {
        i: prior_attempts.get(chashes[i], 0) for i in range(len(configs))
    }
    # Retry budget bookkeeping is by infra FAILURE, not by attempt:
    # attempts also grow on preemption restarts, which must not eat the
    # budget (RetryPolicy.should_retry's contract).
    prior_fails = led.infra_failures() if led.enabled else {}
    infra_fails: dict[int, int] = {
        i: prior_fails.get(chashes[i], 0) for i in range(len(configs))
    }
    # Stacked-bucket SETUP failures are whole-bucket events (no lane
    # exists yet to attribute them to); their retry budget is counted
    # per bucket, keyed by the member-index tuple.
    bucket_setup_fails: dict[tuple, int] = {}

    results: dict[int, TrialResult] = {}
    skipped: set[int] = set()
    if resume and led.enabled:
        # Restart path: trials the ledger settled under a byte-identical
        # config are reconstructed from their recorded summary and never
        # scheduled — the driver re-runs only unfinished work.
        settled = led.finished()
        for i, cfg in enumerate(configs):
            rec = settled.get(chashes[i])
            if rec is None:
                continue
            status = (
                "resumed_complete"
                if rec.get("status") == "completed"
                else "diverged"
            )
            results[i] = _result_from_summary(cfg, rec, status)
            skipped.add(i)
        if skipped:
            log0(
                f"sweep ledger: {len(skipped)} of {len(configs)} trials "
                "already settled; re-running only the rest"
            )

    def make_run(
        trial: TrialMesh, i: int, cfg: TrialConfig, resume_mode,
        attempt: int = 1,
    ) -> _TrialRun:
        return _TrialRun(
            trial,
            cfg,
            data_of(i),
            test_data,
            out_dir,
            shard_across_trials=shard_across_trials,
            # Shard by submesh, not by config: with elastic scheduling
            # (more configs than groups) group_id::len(groups) is still a
            # valid partition of the dataset, config-count-based sharding
            # would leave rows unassigned.
            num_trials=len(groups),
            save_images=save_images,
            save_checkpoint=save_checkpoints,
            verbose=verbose,
            model_builder=model_builder,
            param_shardings_builder=param_shardings_builder,
            resume=resume_mode,
            agree_failures=needs_agreement(trial),
            agree_timeout_s=agree_timeout_s,
            wedge_timeout_s=wedge_timeout_s,
            injector=injector,
            ckpt_keep_last=ckpt_keep_last,
            attempt=attempt,
        )

    # Queue configs per group. Single-controller: one shared queue,
    # greedy — whichever submesh frees first takes the next config
    # (optimal when trials have unequal epoch counts). Multi-controller:
    # every process must make identical assignments WITHOUT
    # communicating, so the schedule is computed deterministically from
    # shared state (the configs themselves): each config goes to the
    # group with the least accumulated predicted cost (epochs x steps
    # per epoch — the knobs that set trial duration, vae-hpo.py:202).
    # Typically better than round-robin under unequal epoch counts
    # (queues are sized to their trials' predicted lengths up front; see
    # balanced_assignment's docstring for the caveat) while remaining
    # process-independent.
    single = jax.process_count() == 1
    if stack_trials:
        # Trial stacking is single-controller, default-model-family
        # territory; contradictory settings fail loudly rather than
        # silently running a different sweep than asked for.
        if not single:
            raise ValueError(
                "stack_trials: stacking is single-controller only (the "
                "stacked state lives on one submesh; multi-controller "
                "lane scheduling would need cross-process agreement)"
            )
        if resume:
            raise ValueError(
                "stack_trials is incompatible with resume= (lane "
                "restore into a stacked bucket is not implemented; run "
                "the resume sweep unstacked)"
            )
        if shard_across_trials:
            raise ValueError(
                "stack_trials is incompatible with shard_across_trials "
                "(stacked lanes each see the full dataset)"
            )
        if model_builder is not None or param_shardings_builder is not None \
                or model_parallel != 1:
            raise ValueError(
                "stack_trials supports the default VAE family with "
                "replicated weights only (custom model_builder / "
                "param_shardings_builder / model_parallel cannot share "
                "one vmapped program)"
            )

    # Work items: ("single", [(i, cfg)]) or ("bucket", [(i, cfg), ...]).
    # Stacking applies only when trials outnumber groups — otherwise
    # every trial gets its own submesh and stacking would only serialize.
    def build_items() -> list[tuple[str, list[tuple[int, TrialConfig]]]]:
        indexed = [
            (i, cfg) for i, cfg in enumerate(configs) if i not in skipped
        ]
        if not (stack_trials and len(configs) > len(groups)):
            return [("single", [item]) for item in indexed]
        buckets: dict[tuple, list] = {}
        singles: list = []
        for item in indexed:
            if config_is_stackable(item[1]):
                # Co-pack key = shape bucket + dataset SHAPE CLASS
                # (dim, batches/epoch) — never dataset identity, so
                # trials reading different datasets still share one
                # vmapped program (heterogeneous lanes).
                key = (
                    stack_bucket_key(item[1]),
                    data_shape_sig(data_of(item[0]), item[1].batch_size),
                )
                buckets.setdefault(key, []).append(item)
            else:
                singles.append(item)
        items = []
        for members in buckets.values():
            if len(members) >= 2:
                items.append(("bucket", members))
            else:
                singles.extend(members)
        items.extend(("single", [m]) for m in singles)
        # Don't idle submeshes behind one mega-bucket: split the largest
        # bucket until there is at least one work item per group (or
        # nothing left to split).
        bus = get_bus()
        while len(items) < len(groups):
            big = max(
                (it for it in items if it[0] == "bucket" and len(it[1]) >= 4),
                key=lambda it: len(it[1]),
                default=None,
            )
            if big is None:
                break
            items.remove(big)
            half = len(big[1]) // 2
            items.append(("bucket", big[1][:half]))
            items.append(("bucket", big[1][half:]))
            if bus is not None:
                bus.emit(
                    "stack_split",
                    members=[cfg.trial_id for _, cfg in big[1]],
                    split_at=half,
                )
        # Deterministic order: by first member's config index.
        items.sort(key=lambda it: it[1][0][0])
        if bus is not None:
            # Stacking decisions are telemetry: which trials share a
            # compiled program (and which ran classic) explains every
            # downstream lane event and throughput number.
            for kind_, members in items:
                if kind_ == "bucket":
                    bus.emit(
                        "stack_bucket",
                        members=[cfg.trial_id for _, cfg in members],
                        bucket_key=str(stack_bucket_key(members[0][1])),
                    )
            bus.emit(
                "stack_plan",
                buckets=sum(1 for it in items if it[0] == "bucket"),
                singles=sum(1 for it in items if it[0] == "single"),
            )
        return items

    # Queue items are (kind, members, ready_at): "single"/"retry" carry
    # one (i, cfg); "bucket" carries the stacked members. ready_at > now
    # = a retry still in its backoff window (skipped, not blocking —
    # other queued work runs first).
    shared = [(k, m, 0.0) for k, m in build_items()]
    # Background AOT precompile farm (docs/COMPILE.md): the work plan
    # above names every distinct program this sweep will compile, so
    # compile them NOW on worker threads — overlapped with the first
    # trials' setup and training — instead of inline at each admission.
    # Same eligibility envelope as the AOT admission path; the group
    # prediction (item j -> group j % n) only gates WHICH submesh an
    # executable is pinned to — a misprediction is a registry miss and
    # an inline compile, never a wrong program.
    if precompile is None:
        precompile = os.environ.get("MDT_PRECOMPILE") == "1"
    if (
        precompile
        and single
        and model_builder is None
        and param_shardings_builder is None
        and os.environ.get("MDT_AOT_ADMISSION", "1") != "0"
    ):
        from multidisttorch_tpu.compile.farm import PrecompilePool

        _farm = PrecompilePool()
        _farm.plan_sweep(
            [(k, m) for k, m, _ in shared],
            groups,
            max_lanes=stack_max_lanes,
        )
        if _pool_holder is not None:
            _pool_holder.append(_farm)
    per_group: dict[int, list] = {g.group_id: [] for g in groups}
    if not single:
        assignment = balanced_assignment(
            [
                predicted_cost(cfg, len(data_of(i)))
                for i, cfg in enumerate(configs)
            ],
            len(groups),
        )
        for i, cfg in enumerate(configs):
            if i in skipped:
                continue
            per_group[groups[assignment[i]].group_id].append(
                ("single", [(i, cfg)], 0.0)
            )
    queue_of = (
        (lambda g: shared) if single else (lambda g: per_group[g.group_id])
    )

    local_groups = [g for g in groups if g.is_local_member]
    # group -> (kind, config_index_or_None, run, generator) in flight
    active: dict[int, tuple] = {}

    def fail_items(g, members, error_text, *, status="failed",
                   progress_of=None) -> None:
        for i, cfg in members:
            if attempts.get(i, 0) == 0:
                # A member that never started (queued behind a bucket
                # that broke): this failure IS its first attempt — pair
                # a start with the end so the ledger's attempt history
                # stays well-formed and attempt numbering stays 1-based.
                attempts[i] = 1
                led.attempt_start(cfg.trial_id, chashes[i], 1)
            results[i] = TrialResult(
                trial_id=cfg.trial_id,
                group_id=g.group_id,
                config=cfg,
                status=status,
                error=error_text,
                attempt=attempts[i],
            )
            led.attempt_end(
                cfg.trial_id, chashes[i], attempts[i],
                status, error=error_text,
                summary=progress_of(i) if progress_of is not None else None,
            )

    def attempt_progress(run: Optional[_TrialRun]) -> dict:
        """Executed-work accounting for a failed/interrupted attempt
        (the chaos bench's goodput input)."""
        if run is None:
            return {"resumed_from_step": 0, "steps_at_failure": 0}
        return {
            "resumed_from_step": run.result.resumed_from_step,
            "steps_at_failure": run._step_no,
        }

    def schedule_retry(g: TrialMesh, i, cfg, error_text, progress=None) -> bool:
        """Consume one unit of the infra retry budget; returns False
        when the failure class or budget says the trial is done
        retrying."""
        if retry is None:
            return False
        fails = infra_fails[i] = infra_fails.get(i, 0) + 1
        if not retry.should_retry(fails, INFRA):
            return False
        # Backoff deadlines are wall-clock and therefore PROCESS-LOCAL;
        # on a spanning submesh every owner must make identical
        # scheduling decisions without communicating, so multi-
        # controller retries requeue immediately (FIFO order is shared
        # state; clocks are not). key= decorrelates jittered backoff
        # across trials felled by the same fault (thundering herd).
        delay = retry.backoff_s(fails, key=cfg.trial_id) if single else 0.0
        bus = get_bus()
        if bus is not None:
            bus.emit(
                "retry_scheduled",
                trial_id=cfg.trial_id,
                group_id=g.group_id,
                backoff_s=delay,
                infra_failures=fails,
                error=error_text,
            )
        led.attempt_end(
            cfg.trial_id, chashes[i], attempts[i], "retrying",
            error=error_text, summary=progress,
        )
        queue_of(g).append(("retry", [(i, cfg)], time.time() + delay))
        log0(
            f"Trial {cfg.trial_id} FAULTED ({error_text}); retrying from "
            f"last valid checkpoint in {delay:.2f}s "
            f"(infra failure {fails} of {retry.max_retries + 1} budget)",
            trial=g,
        )
        return True

    def record_preempted_peers(
        error_text: str = "host preemption (sweep-wide)",
    ) -> None:
        """A preemption (or drain) kills the whole driver, not one
        trial: every other in-flight attempt (single runs AND
        stacked-bucket lanes) dies with it. Record them all so restart
        accounting and the chaos goodput math see the full picture —
        after landing any in-flight checkpoint write (best-effort: the
        resumed sweep restores from it, so a write racing the death
        must finish, not vanish with its thread)."""
        for _gid, (k2, i2, run2, _g2) in list(active.items()):
            if k2 == "single":
                try:
                    run2._join_ckpt()
                except Exception:  # noqa: BLE001 — recording must go on
                    pass
                led.attempt_end(
                    run2.cfg.trial_id, chashes[i2], attempts[i2],
                    "preempted", error=error_text,
                    summary=attempt_progress(run2),
                )
            else:
                run2.record_preempted(error_text)

    def next_ready_at() -> Optional[float]:
        queues = [shared] if single else [
            per_group[g.group_id] for g in local_groups
        ]
        deadlines = [item[2] for q in queues for item in q]
        return min(deadlines) if deadlines else None

    def start_next(g: TrialMesh) -> bool:
        q = queue_of(g)
        for _ in range(len(q)):
            kind, members, ready_at = q.pop(0)
            if ready_at > time.time():
                q.append((kind, members, ready_at))  # backoff not over
                continue
            if kind == "bucket":
                try:
                    brun = _StackedBucketRun(
                        g, members, train_data, test_data, out_dir,
                        max_lanes=stack_max_lanes,
                        save_checkpoint=save_checkpoints,
                        verbose=verbose,
                        injector=injector,
                        retry=retry,
                        ledger=led,
                        attempts=attempts,
                        chashes=chashes,
                        infra_fails=infra_fails,
                        datasets={
                            i: data_by_idx[i]
                            for i, _ in members
                            if i in data_by_idx
                        },
                    )
                except Exception as e:  # noqa: BLE001 — setup isolation
                    error_text = f"{type(e).__name__}: {e}"
                    # Classified ONCE per failure: classification also
                    # emits the failure_classified telemetry event, and
                    # re-calling would duplicate it in the stream.
                    setup_class = classify_failure(e)
                    if setup_class == PREEMPTION:
                        # The host (or a peer) is gone: even resilient
                        # sweeps stop; the ledger sees every in-flight
                        # attempt before the driver dies.
                        fail_items(
                            g, members, error_text, status="preempted"
                        )
                        record_preempted_peers()
                        raise
                    # Same contract as the single-trial setup path: a
                    # transient infra fault (loader init, filesystem)
                    # gets the retry budget before K trials are failed
                    # permanently. Budget is per-bucket (no lane exists
                    # yet to charge), requeued at the queue's tail.
                    key = tuple(i for i, _ in members)
                    fails = bucket_setup_fails[key] = (
                        bucket_setup_fails.get(key, 0) + 1
                    )
                    if (
                        retry is not None
                        and setup_class == INFRA
                        and retry.should_retry(fails, INFRA)
                    ):
                        delay = (
                            retry.backoff_s(fails, key=members[0][0])
                            if single
                            else 0.0
                        )
                        q.append(("bucket", members, time.time() + delay))
                        log0(
                            f"Stacked bucket of {len(members)} trials "
                            f"FAULTED at setup ({error_text}); retrying "
                            f"in {delay:.2f}s (setup failure {fails} of "
                            f"{retry.max_retries + 1} budget)",
                            trial=g,
                        )
                        continue
                    fail_items(g, members, error_text)
                    if not resilient:
                        raise
                    log0(
                        f"Stacked bucket of {len(members)} trials FAILED "
                        f"at setup ({error_text}); sweep continues",
                        trial=g,
                    )
                    continue
                active[g.group_id] = ("bucket", None, brun, brun.run())
                return True
            i, cfg = members[0]
            attempts[i] += 1
            led.attempt_start(cfg.trial_id, chashes[i], attempts[i])
            # Retries resume via the scan-back path (tolerates the
            # torn/corrupt checkpoints a fault may have left); first
            # attempts keep the user-facing strict resume semantics.
            resume_mode = "scan" if kind == "retry" else resume
            err: Optional[BaseException] = None
            run: Optional[_TrialRun] = None
            try:
                run = make_run(g, i, cfg, resume_mode, attempt=attempts[i])
            except Exception as e:  # noqa: BLE001 — setup failure isolation
                err = e
            if needs_agreement(g):
                # Setup agreement: owners of a spanning submesh must all
                # start stepping or all skip — an asymmetric setup
                # failure (e.g. one host's data path) would otherwise
                # leave peers dispatching a trial that never runs here.
                from multidisttorch_tpu.parallel.cluster import (
                    WedgedCollective,
                )
                from multidisttorch_tpu.parallel.collectives import (
                    group_all_ok,
                )

                ok = group_all_ok(
                    g,
                    err is None,
                    timeout_s=agree_timeout_s,
                    what=f"trial {cfg.trial_id} setup agreement",
                    error_cls=WedgedCollective,
                )
            else:
                ok = err is None
            if not ok:
                error_text = (
                    f"{type(err).__name__}: {err}"
                    if err is not None
                    else "setup failed on a peer owner process"
                )
                # A broken setup (bad restore, dead data path) is an
                # infra fault like any other: supervised sweeps retry it
                # (the retry's scan-resume is what recovers a trial
                # whose strict resume chokes on a corrupt checkpoint).
                # FATAL setup errors — the strict-resume integrity
                # guards (UnretryableError) — are the exception: they
                # exist to stop for a human, and a scan-retry would
                # retrain over the checkpoint the guard protected.
                fatal = (
                    err is not None and classify_failure(err) == FATAL
                )
                if not fatal and schedule_retry(g, i, cfg, error_text):
                    continue
                results[i] = TrialResult(
                    trial_id=cfg.trial_id,
                    group_id=g.group_id,
                    config=cfg,
                    status="failed",
                    error=error_text,
                    attempt=attempts[i],
                )
                led.attempt_end(
                    cfg.trial_id, chashes[i], attempts[i], "failed",
                    error=error_text, summary=attempt_progress(run),
                )
                if not resilient:
                    if err is not None:
                        raise err
                    raise RuntimeError(error_text)
                log0(
                    f"Trial {cfg.trial_id} FAILED at setup "
                    f"({error_text}); sweep continues",
                    trial=g,
                )
                continue
            active[g.group_id] = ("single", i, run, run.run())
            return True
        return False

    bus = get_bus()
    if bus is not None:
        # Fleet identity rides the sweep header too (not just the
        # per-event tags): the console's one-line summary of a merged
        # stream needs "whose sweep_start is this" without scanning
        # tags. Only stamped when tagged — an untagged single-host
        # stream must stay byte-identical.
        fleet_id = {}
        if bus.host is not None:
            fleet_id["host_slot"] = bus.host
        if bus.world is not None:
            fleet_id["world_epoch"] = bus.world
        bus.emit(
            "sweep_start",
            configs=len(configs),
            groups=len(groups),
            stacked=bool(stack_trials),
            resume=bool(resume),
            resilient=bool(resilient),
            skipped_settled=len(skipped),
            **fleet_id,
        )

    def drain_now():
        from multidisttorch_tpu.faults.inject import (
            HostPreemption as _Drained,
        )

        sig = _DRAIN["sig"]
        error_text = f"graceful drain on signal {sig}"
        dbus = get_bus()
        if dbus is not None:
            dbus.emit("sweep_drain", signal=int(sig), in_flight=len(active))
        record_preempted_peers(error_text)
        raise _Drained(
            f"{error_text}: in-flight work checkpointed to the last "
            "epoch boundary and recorded in the ledger; resume with "
            "run_hpo(resume=True)"
        )

    for g in local_groups:
        start_next(g)

    # Cooperative round-robin: one async step dispatch per trial (or
    # stacked bucket — K trials per dispatch) per cycle. A finished (or
    # failed) item frees its submesh, which immediately starts its next
    # queued work — the sweep's wall-clock is bounded by real work,
    # never by barriers (Q3 fixed). Retries waiting out their backoff
    # never block live work; when ONLY backoff items remain, the loop
    # sleeps to the earliest deadline.
    while True:
        if _DRAIN["sig"] is not None:
            drain_now()
        for g in local_groups:
            if g.group_id not in active:
                start_next(g)  # a backoff retry may have matured
        if not active:
            deadline = next_ready_at()
            if deadline is None:
                break
            # Sliced sleep: a SIGTERM during a long backoff wait only
            # sets the drain flag (PEP 475 resumes the sleep), so one
            # monolithic sleep of up to backoff_max_s would outlast a
            # supervisor's kill grace and forfeit the drain. Wake every
            # quarter-second to honor the flag promptly.
            while time.time() < deadline and _DRAIN["sig"] is None:
                time.sleep(
                    min(0.25, max(0.0, deadline - time.time()) + 1e-3)
                )
            continue
        for g in local_groups:
            if g.group_id not in active:
                continue
            kind, i, run, gen = active[g.group_id]
            try:
                next(gen)
            except StopIteration:
                if kind == "bucket":
                    results.update(run.results)
                else:
                    run.result.attempt = attempts[i]
                    results[i] = run.result
                    led.attempt_end(
                        run.cfg.trial_id, chashes[i], attempts[i],
                        "completed", summary=_result_summary(run.result),
                    )
                del active[g.group_id]
                start_next(g)
            except Exception as e:  # noqa: BLE001 — failure isolation
                error_text = f"{type(e).__name__}: {e}"
                failure_class = classify_failure(
                    e,
                    trial_id=(
                        None if kind == "bucket" else run.cfg.trial_id
                    ),
                )
                if kind == "bucket":
                    # Lanes already retired keep their completed
                    # results; everything in flight or queued in the
                    # bucket fails together (they shared the broken
                    # program/state). Lane-scoped faults never reach
                    # here — the bucket absorbs them via mask-and-
                    # refill; this path is bucket-wide breakage.
                    results.update(run.results)
                    status = (
                        "preempted"
                        if failure_class == PREEMPTION
                        else "failed"
                    )
                    fail_items(
                        g, run.unfinished(), error_text, status=status,
                        progress_of=(
                            run.lane_progress
                            if failure_class == PREEMPTION
                            else None
                        ),
                    )
                    del active[g.group_id]
                    if failure_class == PREEMPTION:
                        record_preempted_peers()
                        raise
                    if not resilient:
                        raise
                    log0(
                        f"Stacked bucket FAILED ({error_text}); "
                        "submesh freed, sweep continues",
                        trial=g,
                    )
                    start_next(g)
                    continue
                del active[g.group_id]
                # Drain any in-flight checkpoint write before freeing the
                # submesh: run_hpo must not return while a writer thread
                # is still mutating result.checkpoint, and a failed write
                # must surface in the error, not vanish with the thread.
                try:
                    run._join_ckpt()
                except Exception as ce:  # noqa: BLE001
                    error_text += f"; also: {type(ce).__name__}: {ce}"
                if failure_class == PREEMPTION:
                    # The host is going away (or a peer already did, for
                    # an agreement TimeoutError): no per-trial retry
                    # makes sense, and even a resilient sweep must stop.
                    # The ledger records EVERY in-flight attempt — the
                    # raising trial and its still-running peers, single
                    # runs and stacked lanes alike, since they all die
                    # with the driver — so restart accounting and resume
                    # decisions see the whole picture; a restarted
                    # run_hpo(resume=True) re-runs only unfinished work.
                    led.attempt_end(
                        run.cfg.trial_id, chashes[i], attempts[i],
                        "preempted", error=error_text,
                        summary=attempt_progress(run),
                    )
                    record_preempted_peers()
                    raise
                if failure_class == DIVERGENCE:
                    # Terminal RESULT, not an error: the config drove
                    # training to a non-finite loss, and a deterministic
                    # re-run reproduces it. Recorded; never retried;
                    # never raised.
                    run.result.status = "diverged"
                    run.result.error = error_text
                    run.result.attempt = attempts[i]
                    # Steps executed up to detection: the work that
                    # produced the terminal verdict (normally stamped at
                    # completion, which a diverged run never reaches).
                    run.result.steps = run._step_no
                    results[i] = run.result
                    led.attempt_end(
                        run.cfg.trial_id, chashes[i], attempts[i],
                        "diverged", error=error_text,
                        summary=_result_summary(run.result),
                    )
                    log0(
                        f"Trial {run.cfg.trial_id} DIVERGED "
                        f"({error_text}); recorded as terminal result, "
                        "submesh freed",
                        trial=g,
                    )
                    start_next(g)
                    continue
                if failure_class != FATAL and schedule_retry(
                    g, i, run.cfg, error_text,
                    progress=attempt_progress(run),
                ):
                    start_next(g)
                    continue
                run.result.status = "failed"
                run.result.error = error_text
                run.result.attempt = attempts[i]
                # Work executed up to the failure (the completion path
                # never stamped it) — consumers of the returned results
                # see real counts, not zero, same as the diverged branch.
                run.result.steps = run._step_no
                results[i] = run.result
                led.attempt_end(
                    run.cfg.trial_id, chashes[i], attempts[i], "failed",
                    error=error_text, summary=attempt_progress(run),
                )
                if not resilient:
                    raise
                log0(
                    f"Trial {run.cfg.trial_id} FAILED ({run.result.error}); "
                    "submesh freed, sweep continues",
                    trial=g,
                )
                start_next(g)
    bus = get_bus()
    if bus is not None:
        statuses: dict[str, int] = {}
        for r in results.values():
            statuses[r.status] = statuses.get(r.status, 0) + 1
        bus.emit("sweep_end", results=len(results), statuses=statuses)
    return [results[i] for i in sorted(results)]
