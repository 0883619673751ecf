"""The pipelined trial runner: one MPMD trial across S stage submeshes.

The cross-submesh sibling of ``hpo/driver.py``'s ``_TrialRun``: the same
cooperative-generator contract (each ``next()`` dispatches one
optimizer step's GPipe schedule async and returns; host syncs only at
epoch boundaries), the same supervision surface the sweep service
drives (``.run()`` / ``.result`` / ``._join_ckpt()`` / ``._step_no``),
but the trial's devices are a *vector* of submeshes — one per pipeline
stage — and the compiled work is the per-stage program set of
``parallel.pipeline.MpmdPipeline`` (docs/PARALLEL.md).

Checkpoint/restore composes per stage: each stage's TrainState lands in
its own ``stage{c}.msgpack`` under the trial dir (one background writer
thread for all stages, the driver's atomic+CRC machinery per file), and
a supervised retry restores all stages at the NEWEST optimizer step
every stage can locally verify — one stage's torn checkpoint pulls the
whole pipeline back to the last step everyone holds, the per-stage
analog of the elastic restore agreement.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from dataclasses import asdict
from typing import Iterator, Optional

import jax
import optax

from multidisttorch_tpu.data.datasets import Dataset
from multidisttorch_tpu.data.sampler import (
    EvalDataIterator,
    TrialDataIterator,
)
from multidisttorch_tpu.hpo.driver import (
    TrialConfig,
    TrialResult,
    stack_bucket_key,
)
from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.parallel.pipeline import (
    MpmdPipeline,
    analytic_bubble_fraction,
    make_vae_stage_eval_fns,
    make_vae_stage_fns,
    split_stage_params,
)
from multidisttorch_tpu.telemetry import device as tele_device
from multidisttorch_tpu.telemetry.events import get_bus
from multidisttorch_tpu.telemetry.metrics import get_registry
from multidisttorch_tpu.train.checkpoint import (
    default_format,
    restore_state,
    save_state,
    snapshot_cache,
    valid_candidates_by_step,
)
from multidisttorch_tpu.train.guards import check_finite
from multidisttorch_tpu.train.steps import build_train_state
from multidisttorch_tpu.utils.logging import log0

PIPELINE_BOOKS_NAME = "pipeline_books.json"


def _emit(kind: str, **kw) -> None:
    bus = get_bus()
    if bus is not None:
        bus.emit(kind, **kw)


class _PipelineTrialRun:
    """One MPMD pipelined trial's lifecycle as a cooperative generator.

    ``stage_meshes`` is the placement's submesh vector (stage s trains
    on ``stage_meshes[s]``); ``cfg.pipeline_stages`` must match its
    length and ``cfg.grad_accum`` is the microbatch count M (the GPipe
    schedule IS gradient accumulation across stages — the single-mesh
    ``grad_accum=M`` step is the parity reference). Default VAE family
    only (2 stages: encoder+reparam | decoder+loss), single controller.
    """

    def __init__(
        self,
        stage_meshes,
        cfg: TrialConfig,
        train_data: Dataset,
        test_data: Optional[Dataset],
        out_dir: str,
        *,
        save_checkpoint: bool = True,
        verbose: bool = False,
        resume=False,  # False | "scan"
        ckpt_keep_last: int = 1,
        ckpt_format: Optional[str] = None,
        ram_restore: bool = False,
        attempt: int = 1,
    ):
        S = len(stage_meshes)
        if cfg.pipeline_stages != S:
            raise ValueError(
                f"cfg.pipeline_stages={cfg.pipeline_stages} but "
                f"{S} stage submeshes were placed"
            )
        if S != 2:
            raise ValueError(
                f"the VAE family splits into 2 MPMD stages; got {S} "
                "(deeper chains need a deeper model — see docs/PARALLEL.md)"
            )
        # Knobs the pipelined runner does not carry: reject loudly
        # rather than silently train/evaluate something else (the
        # service mirrors this at admission — rejected_invalid).
        if cfg.eval_sampled:
            raise ValueError(
                f"trial {cfg.trial_id}: eval_sampled is not supported "
                "on the pipelined path (stage eval is posterior-mean "
                "only) — run this config unpipelined"
            )
        if cfg.fused_steps != 1 or cfg.remat:
            raise ValueError(
                f"trial {cfg.trial_id}: fused_steps/remat are not "
                "wired through the MPMD stage programs — run this "
                "config unpipelined"
            )
        M = max(1, int(cfg.grad_accum))
        if cfg.batch_size % M:
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by "
                f"grad_accum={M} microbatches"
            )
        mb = cfg.batch_size // M
        for sm in stage_meshes:
            if mb % sm.data_size:
                raise ValueError(
                    f"microbatch of {mb} rows does not shard over stage "
                    f"submesh of {sm.data_size} devices"
                )
        self.stage_meshes = list(stage_meshes)
        # The service's single-run bookkeeping reads `.trial` for
        # group identity: stage 0's submesh anchors the trial.
        self.trial = stage_meshes[0]
        self.cfg = cfg
        self.M = M
        self.out_dir = os.path.join(out_dir, f"trial-{cfg.trial_id}")
        self._save_checkpoint = save_checkpoint
        self._verbose = verbose
        self._ckpt_keep_last = ckpt_keep_last
        self._ckpt_format = (
            ckpt_format if ckpt_format is not None else default_format()
        )
        # Same-process warm re-place only (the classic driver's rule):
        # disk drills must observe disk.
        self._ram_restore = bool(ram_restore)
        self._attempt = attempt
        self._host_syncs = 0
        self._step_no = 0
        self._mreg = get_registry()
        self._mkey = f"pipe-t{cfg.trial_id}"
        self._cost_done = False

        self.result = TrialResult(
            trial_id=cfg.trial_id,
            group_id=self.trial.group_id,
            config=cfg,
            out_dir=self.out_dir,
            dataset=train_data.name,
            dataset_synthetic=train_data.synthetic,
        )

        model = VAE(hidden_dim=cfg.hidden_dim, latent_dim=cfg.latent_dim)
        self.model = model
        stage_fns, last_fn, stage_keys = make_vae_stage_fns(
            model, beta=cfg.beta
        )
        full = build_train_state(
            model, optax.adam(cfg.lr), jax.random.key(cfg.seed)
        )
        stage_params = split_stage_params(full.params, stage_keys)

        from multidisttorch_tpu.compile.programs import pipeline_stage_keys

        self.pipe = MpmdPipeline(
            self.stage_meshes,
            stage_fns,
            last_fn,
            stage_params,
            lr=cfg.lr,
            microbatches=M,
            zero_update=cfg.zero_update,
            registry_keys=pipeline_stage_keys(
                self.stage_meshes,
                cfg,
                stack_bucket_key(cfg),
                microbatches=M,
            ),
            eval_fns=make_vae_stage_eval_fns(model, cfg.beta),
        )
        self.result.optimizer_state_bytes = self.pipe.optimizer_state_bytes()[
            "per_device_bytes"
        ]

        self.train_iter = TrialDataIterator(
            train_data, self.trial, cfg.batch_size, seed=cfg.seed
        )
        self.test_iter = (
            EvalDataIterator(test_data, self.trial, cfg.batch_size)
            if test_data is not None and len(test_data) > 0
            else None
        )
        self._key = jax.random.key(cfg.seed + 1)

        # Per-stage checkpoint paths + one background writer thread.
        self._ckpt_paths = [
            os.path.join(self.out_dir, f"stage{s}.msgpack")
            for s in range(S)
        ]
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_error: Optional[BaseException] = None
        self._start_epoch = 1
        if resume == "scan":
            got = self._restore_scan()
            if got is not None:
                done = got
                self._start_epoch = done + 1
                log0(
                    f"Pipelined trial {cfg.trial_id} retry resumes from "
                    f"epoch {done} (all {S} stages verified)",
                    trial=self.trial,
                )
        self.result.resumed_from_step = (
            (self._start_epoch - 1) * self.train_iter.num_batches
        )

    # -- checkpoint/restore -------------------------------------------

    def _accept_meta(self, meta: dict) -> bool:
        """Config-match gate per candidate (epochs may extend): the
        driver's ONE resume rule — fields absent from an older
        sidecar compare against their TrialConfig defaults, so a
        checkpoint trained before a field existed can never silently
        resume under a non-default value of it."""
        from multidisttorch_tpu.hpo.driver import config_mismatch_vs_meta

        return not config_mismatch_vs_meta(self.cfg, meta)

    def _restore_scan(self) -> Optional[int]:
        """Per-stage agreed restore: the newest optimizer step EVERY
        stage can locally verify (CRC + config match); one stage's torn
        file pulls the whole pipeline back together. Returns completed
        epochs, or None for scratch."""
        # Warm re-place: every stage's RAM snapshot present at one
        # agreed step (they are written together) restores without
        # touching disk — the pipelined analog of the classic driver's
        # snapshot-cache fast path.
        snaps = (
            [snapshot_cache().get(p) for p in self._ckpt_paths]
            if self._ram_restore
            else [None]
        )
        if all(s is not None for s in snaps):
            metas = [m for _, m in snaps]
            steps = {int(m.get("step", -1)) for m in metas}
            usable = (
                len(steps) == 1
                and self._accept_meta(metas[0])
                and int(metas[0].get("completed_epochs", 0)) >= 1
            )
            if usable:
                try:
                    states = [
                        self.stage_meshes[s].device_put(
                            host, self.pipe.state_shardings[s]
                        )
                        for s, (host, _) in enumerate(snaps)
                    ]
                except Exception:  # noqa: BLE001 — fall back to disk
                    states = None
                if states is not None:
                    from multidisttorch_tpu.train.checkpoint import _count

                    self.pipe.states = states
                    self.result.checkpoint = self._ckpt_paths[0]
                    self._adopt_history(metas[0])
                    _count(restores=1, restores_ram=1)
                    _emit(
                        "ckpt_restore",
                        trial_id=self.cfg.trial_id,
                        group_id=self.trial.group_id,
                        path="<ram-snapshot>",
                        format="ram",
                        step=metas[0].get("step"),
                    )
                    return int(metas[0].get("completed_epochs", 0))
            else:
                # Stale/rejected snapshots squat in the bounded LRU and
                # re-reject on every retry — drop them (the classic
                # driver's rule).
                for p in self._ckpt_paths:
                    snapshot_cache().drop(p)
        common: Optional[set] = None
        cands = []
        for path in self._ckpt_paths:
            by_step = valid_candidates_by_step(
                path, accept_meta=self._accept_meta
            )
            cands.append(by_step)
            steps = set(by_step)
            common = steps if common is None else (common & steps)
        if not common:
            return None
        step = max(common)
        states = []
        try:
            for s, by_step in enumerate(cands):
                path, meta = by_step[step]
                states.append(
                    restore_state(
                        self.pipe.states[s],
                        path,
                        self.stage_meshes[s],
                        shardings=self.pipe.state_shardings[s],
                    )
                )
        except Exception:  # noqa: BLE001 — degrade to scratch, never wedge
            return None
        meta = cands[0][step][1]
        done = int(meta.get("completed_epochs", 0))
        if done < 1:
            return None
        self.pipe.states = states
        self.result.checkpoint = self._ckpt_paths[0]
        self._adopt_history(meta)
        return done

    def _adopt_history(self, meta: dict) -> None:
        """Carry the restored checkpoint's per-epoch history into the
        result (the classic driver's `_adopt_history` contract): a
        resumed trial's settled summary must cover its WHOLE training,
        and a resumed_complete trial must still report its losses."""
        hist = list(meta.get("history", []))
        if not hist:
            return
        self.result.history = hist
        last = hist[-1]
        if last.get("avg_train_loss") is not None:
            self.result.final_train_loss = float(last["avg_train_loss"])
        if last.get("test_loss") is not None:
            self.result.final_test_loss = float(last["test_loss"])

    def _write_ckpt(self, host_states, meta: dict) -> None:
        try:
            for s, (path, host_state) in enumerate(
                zip(self._ckpt_paths, host_states)
            ):
                save_state(
                    host_state,
                    path,
                    metadata=meta,
                    keep_last=self._ckpt_keep_last,
                    # Per-stage manifests: every stage's family shares
                    # the trial dir's ONE chunk store, and each records
                    # its stage's NamedSharding layout (a zero_update
                    # stage's sharded moments stay sharded on disk).
                    format=self._ckpt_format,
                    layouts=self.pipe.state_shardings[s],
                )
            self.result.checkpoint = self._ckpt_paths[0]
        except BaseException as e:  # re-raised at the next join
            self._ckpt_error = e

    def _join_ckpt(self) -> None:
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None
        if self._ckpt_error is not None:
            e, self._ckpt_error = self._ckpt_error, None
            raise RuntimeError(
                f"pipelined trial {self.cfg.trial_id}: stage checkpoint "
                "write failed"
            ) from e

    def _ckpt_idle(self) -> bool:
        """No stage persist in flight (the snapshot-fast drain's
        non-blocking poll)."""
        t = self._ckpt_thread
        return t is None or not t.is_alive()

    # -- books --------------------------------------------------------

    def _record_cost(self) -> None:
        """One-shot device cost books over every stage program (MFU on
        backends with a peak table; null-with-reason on CPU)."""
        if self._cost_done or self._mreg is None:
            return
        self._cost_done = True
        parts = self.pipe.cost_parts()
        if not parts:
            return
        devices = [
            d for sm in self.stage_meshes for d in sm.devices
        ]
        tele_device.record_pipeline_cost(
            self._mkey,
            parts,
            devices=devices,
            trial_id=self.cfg.trial_id,
            group_id=self.trial.group_id,
        )

    def write_books(self) -> Optional[str]:
        """Land the trial's pipeline books (schedule measurement,
        optimizer memory, placement vector) as JSON in the trial dir."""
        books = {
            "trial_id": self.cfg.trial_id,
            "schedule": self.pipe.schedule_books(),
            "optimizer_state": self.pipe.optimizer_state_bytes(),
            "stage_groups": [
                {
                    "group_id": sm.group_id,
                    "devices": [d.id for d in sm.devices],
                }
                for sm in self.stage_meshes
            ],
        }
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            path = os.path.join(self.out_dir, PIPELINE_BOOKS_NAME)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(books, f, indent=2)
            os.replace(tmp, path)
            return path
        except OSError:
            return None

    def _log(self, *args, level: int = logging.INFO):
        if self._verbose:
            log0(*args, trial=self.trial, level=level)

    # -- the lifecycle ------------------------------------------------

    def run(self) -> Iterator[None]:
        cfg = self.cfg
        t0 = time.time()
        if self._start_epoch > cfg.epochs:
            self.result.status = "resumed_complete"
            self.result.steps = int(
                jax.device_get(self.pipe.states[0].step)
            )
            self._log(
                f"Pipelined trial {cfg.trial_id} already complete; resumed."
            )
            return
        n_per_epoch = self.train_iter.samples_per_epoch
        self._step_no = int(jax.device_get(self.pipe.states[0].step))
        _emit(
            "pipeline_start",
            trial_id=cfg.trial_id,
            group_id=self.trial.group_id,
            stages=self.pipe.S,
            microbatches=self.M,
            stage_groups=[sm.group_id for sm in self.stage_meshes],
            analytic_bubble=analytic_bubble_fraction(self.pipe.S, self.M),
            zero_update=cfg.zero_update,
        )
        ob = self.pipe.optimizer_state_bytes()
        _emit(
            "optimizer_state",
            trial_id=cfg.trial_id,
            group_id=self.trial.group_id,
            per_device_bytes=ob["per_device_bytes"],
            total_bytes=ob["total_bytes"],
            zero_update=cfg.zero_update,
            pipelined=True,
        )
        for epoch in range(self._start_epoch, cfg.epochs + 1):
            if self._mreg is not None:
                self._mreg.step_series(self._mkey).open_interval()
            epoch_sum_dev = None
            books0 = dict(self.pipe.books)
            for batch in self.train_iter.epoch(epoch):
                rng = jax.random.fold_in(self._key, self._step_no)
                metrics = self.pipe.step(batch, rng)
                self._step_no += 1
                s = metrics["loss_sum"]
                epoch_sum_dev = (
                    s if epoch_sum_dev is None else epoch_sum_dev + s
                )
                if self._mreg is not None:
                    self._mreg.step_mark(self._mkey, s)
                yield

            # One fetch per epoch (the O(1)-syncs discipline).
            self._host_syncs += 1
            avg = float(epoch_sum_dev) / n_per_epoch
            if self._mreg is not None:
                self._record_cost()
                devices = [
                    d for sm in self.stage_meshes for d in sm.devices
                ]
                tele_device.sample_memory(
                    self._mkey, devices, where="epoch",
                    trial_id=cfg.trial_id, group_id=self.trial.group_id,
                )
            check_finite(
                avg,
                "epoch average train loss",
                step=self._step_no,
                trial_id=cfg.trial_id,
            )
            self._log(
                "====> [pipeline] Epoch: {} Average loss: {:.4f}".format(
                    epoch, avg
                )
            )
            epoch_record = {"epoch": epoch, "avg_train_loss": avg}

            if self.test_iter is not None:
                test_sum_dev = None
                for tbatch, tweights in self.test_iter.batches():
                    out = self.pipe.eval_batch(tbatch, tweights)
                    test_sum_dev = (
                        out if test_sum_dev is None else test_sum_dev + out
                    )
                    yield
                self._host_syncs += 1
                test_avg = float(test_sum_dev) / self.test_iter.num_rows
                self._log(
                    "====> [pipeline] Test set loss: {:.4f}".format(test_avg)
                )
                epoch_record["test_loss"] = test_avg
                self.result.final_test_loss = test_avg

            self.result.history.append(epoch_record)
            self.result.final_train_loss = avg
            _emit(
                "epoch",
                trial_id=cfg.trial_id,
                group_id=self.trial.group_id,
                step=self._step_no,
                **epoch_record,
            )
            d = dict(self.pipe.books)
            _emit(
                "pipeline_epoch",
                trial_id=cfg.trial_id,
                group_id=self.trial.group_id,
                step=self._step_no,
                epoch=epoch,
                ticks=d["ticks"] - books0["ticks"],
                busy=d["busy"] - books0["busy"],
                transfers=d["transfers"] - books0["transfers"],
                transfer_bytes=(
                    d["transfer_bytes"] - books0["transfer_bytes"]
                ),
                measured_bubble=self.pipe.measured_bubble(),
                analytic_bubble=analytic_bubble_fraction(
                    self.pipe.S, self.M
                ),
            )

            if self._save_checkpoint:
                # Snapshot every stage (replicated leaves or gathered
                # shards are all addressable single-controller), start
                # the device→host copies async, then hand the
                # serialize+write to the background thread.
                _snap_t0 = time.perf_counter()
                snaps = [
                    jax.device_get(st) for st in self.pipe.states
                ]
                meta = {
                    **asdict(cfg),
                    "completed_epochs": epoch,
                    "step": int(snaps[0].step),
                    "history": list(self.result.history),
                    "pipeline_stage": True,
                }
                # Snapshot boundary per stage (the drain contract): a
                # same-process re-place restores every stage from RAM.
                # Same opt-in gate as the read side — no host-copy
                # retention outside the service path.
                if self._ram_restore:
                    for path, host_state in zip(self._ckpt_paths, snaps):
                        snapshot_cache().put(path, host_state, meta)
                _emit(
                    "ckpt_snapshot",
                    trial_id=cfg.trial_id,
                    group_id=self.trial.group_id,
                    step=int(snaps[0].step),
                    epoch=epoch,
                    stages=len(snaps),
                    wall_s=round(time.perf_counter() - _snap_t0, 6),
                )
                self._join_ckpt()
                self._ckpt_thread = threading.Thread(
                    target=self._write_ckpt,
                    args=(snaps, meta),
                    daemon=False,
                )
                self._ckpt_thread.start()
                yield

        for st in self.pipe.states:
            jax.block_until_ready(st.params)
        self._join_ckpt()
        self.result.wall_s = time.time() - t0
        self.result.steps = self._step_no
        self.result.host_syncs = self._host_syncs
        self.write_books()
        self._log(f"Pipelined trial done. time: {self.result.wall_s:f}")


def run_pipeline_trial(
    cfg: TrialConfig,
    train_data: Dataset,
    test_data: Optional[Dataset] = None,
    *,
    stage_meshes,
    out_dir: str = "results",
    save_checkpoint: bool = True,
    verbose: bool = False,
    resume=False,
) -> TrialResult:
    """Run one MPMD pipelined trial to completion (tests, benches, and
    one-off driving outside the service loop)."""
    run = _PipelineTrialRun(
        stage_meshes,
        cfg,
        train_data,
        test_data,
        out_dir,
        save_checkpoint=save_checkpoint,
        verbose=verbose,
        resume=resume,
    )
    for _ in run.run():
        pass
    return run.result
