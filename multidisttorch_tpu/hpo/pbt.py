"""Population-based training (BASELINE.md config 5: "inter-subgroup
weight broadcast/exploit across submeshes") — two execution modes over
one seeding contract.

**Per-submesh mode** (``fused=False``, the reference semantics): one
member per submesh, each generation one scan-fused dispatch per member,
exploit/explore host-side — rank the fetched scores, ``device_get`` the
winner's replicated state, ``device_put`` it onto the loser's submesh.
In the torch design this would need inter-group NCCL broadcasts
negotiated across communicators; here it is host metadata + one byte
move per exploited member.

**Fused-lane mode** (``fused=True``): the population IS the stacked
lane axis (PR 1, ``train/steps.py``) — K members run as lanes of ONE
vmapped program, and the generation boundary is an *in-program*
exploit/explore (``train.steps.pbt_exchange``): a stable lane-axis
argsort ranks members, a gather copies top-q params+opt-state into
bottom-q lanes, and the lr perturbation is a pure function of
(explore_key, generation, lane) applied to the batched ``TrialHypers``.
A whole generation (S-step train scan + E-batch eval scan + exchange)
is ONE dispatch — registered as the ``pbt_gen`` program kind in the
compile registry (``compile/programs.py``), so it compiles once ever
and every later generation (and every later ``run_pbt`` in the
process) is a registry ``cache_hit``.

Both modes follow the SAME seeding contract (docs/PBT.md): member k's
params init from ``key(seed + k)``, its per-step data RNG folds
``key(seed + k + 1)`` with the global optimizer-step count, its data
stream replays the ``(seed + k, epoch)`` permutations, and every
explore draw comes from :func:`~multidisttorch_tpu.train.steps
.pbt_perturb_factor`. That contract is what makes the two modes
bit-identical — member states, scores, exploit decisions, and lrs —
which ``tests/test_pbt_fused.py`` asserts.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from multidisttorch_tpu.data.datasets import Dataset
from multidisttorch_tpu.data.sampler import (
    EvalDataIterator,
    StackedTrialDataIterator,
)
from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.parallel.mesh import DATA_AXIS, TrialMesh, setup_groups
from multidisttorch_tpu.telemetry.events import get_bus
from multidisttorch_tpu.train.steps import (
    TrainState,
    TrialHypers,
    build_stacked_train_state,
    create_stacked_train_state,
    make_stacked_eval_scan,
    make_stacked_multi_step,
    pbt_explore_key,
    pbt_perturb_factor,
)
from multidisttorch_tpu.utils.logging import log0


@dataclass(frozen=True)
class PBTConfig:
    population: int = 4
    generations: int = 5
    steps_per_generation: int = 30
    batch_size: int = 64
    lr_min: float = 1e-4
    lr_max: float = 1e-2
    beta: float = 1.0
    exploit_fraction: float = 0.25  # bottom q exploits top q
    perturb_factors: tuple[float, float] = (0.8, 1.25)
    seed: int = 0
    hidden_dim: int = 400
    latent_dim: int = 20


@dataclass
class PBTResult:
    best_member: int
    best_eval_loss: float
    history: list = field(default_factory=list)  # per-generation dicts
    final_lrs: list = field(default_factory=list)
    wall_s: float = 0.0
    mode: str = "submesh"
    # Dispatch accounting, fused against per-submesh:
    # program_calls = compiled-program invocations, host_transfers =
    # exchange state moves through host memory.
    dispatch_book: dict = field(default_factory=dict)
    # Per-member final states (host pytrees, lane axis squeezed) when
    # run_pbt(return_states=True) — the bit-parity comparison surface.
    final_states: Optional[list] = None


def n_exploit_for(cfg: PBTConfig) -> int:
    """The exploit slot count: ``floor(exploit_fraction * K)`` floored
    at 1, clamped to ``K // 2`` so the top and bottom slices can never
    overlap (an overlapping slice would let an exploiter clone a state
    that was itself just overwritten in the same exchange). K=1 clamps
    to 0 — the degenerate population skips the exchange entirely."""
    n = max(1, int(np.floor(cfg.exploit_fraction * cfg.population)))
    return min(n, cfg.population // 2)


def _set_lr(
    state: TrainState, lr: float, trial: Optional[TrialMesh] = None
) -> TrainState:
    """Overwrite the injected learning rate inside an
    ``optax.inject_hyperparams`` optimizer state (the pre-lane-axis PBT
    representation; per-lane lrs now ride ``TrialHypers``, but external
    states built on inject_hyperparams still mutate through here).

    With ``trial``, the new scalar is placed replicated on the trial's
    submesh (required in multi-controller mode, where mixing a
    process-local scalar into a pytree of multi-process global arrays
    would fail at the next dispatch)."""
    opt = state.opt_state
    hp = dict(opt.hyperparams)
    new = jnp.asarray(lr, dtype=hp["learning_rate"].dtype)
    hp["learning_rate"] = trial.device_put(new) if trial is not None else new
    return state.replace(opt_state=opt._replace(hyperparams=hp))


def _init_lrs(cfg: PBTConfig) -> np.ndarray:
    """The population's initial log-uniform lrs, as f32 (the dtype the
    batched ``TrialHypers`` carry — both modes draw identically)."""
    rng = np.random.default_rng(cfg.seed)
    return np.exp(
        rng.uniform(np.log(cfg.lr_min), np.log(cfg.lr_max), cfg.population)
    ).astype(np.float32)


def _rank(sums: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side ranking, bit-matching the in-program exchange: NaN
    sanitized to +inf, stable ascending argsort (ties break by lane).
    Returns ``(order, sanitized)`` — the ONE host-side copy of the
    sanitization rule, so the exploit condition always compares the
    same values the ranking sorted (the ``jnp.where`` twin lives in
    ``train.steps.pbt_exchange``)."""
    sanitized = np.asarray(sums, np.float32).copy()
    sanitized[np.isnan(sanitized)] = np.inf
    return np.argsort(sanitized, kind="stable"), sanitized


def _emit_generation(
    mode: str,
    gen: int,
    scores: np.ndarray,
    order: np.ndarray,
    lrs: np.ndarray,
    exploits: list,
    prev_order: Optional[np.ndarray],
    global_step: int,
) -> None:
    """The ``pbt_*`` telemetry seam (zero-cost when off): one
    ``pbt_gen`` per generation boundary with the lane-axis population
    statistics (best/median loss, exploit count, rank churn, lr
    quantiles), one ``pbt_exploit`` per exchange edge."""
    bus = get_bus()
    if bus is None:
        return
    k = len(order)
    finite = scores[np.isfinite(scores)]
    churn = None
    if prev_order is not None:
        # rank churn: fraction of lanes whose rank position changed
        # since the previous generation's ordering.
        churn = float(np.mean(order != prev_order))
    data = dict(
        generation=gen,
        mode=mode,
        population=k,
        best_lane=int(order[0]),
        best_loss=float(scores[order[0]]),
        median_loss=(
            float(np.median(finite)) if finite.size else None
        ),
        exploit_count=len(exploits),
        lr_min=float(np.min(lrs)),
        lr_median=float(np.median(lrs)),
        lr_max=float(np.max(lrs)),
    )
    if churn is not None:
        data["rank_churn"] = round(churn, 4)
    bus.emit("pbt_gen", step=global_step, **data)
    for e in exploits:
        bus.emit(
            "pbt_exploit",
            step=global_step,
            lane=e["to"],
            generation=gen,
            mode=mode,
            src=e["from"],
            dst=e["to"],
            new_lr=e["new_lr"],
            src_loss=float(scores[e["from"]]),
            dst_loss=float(scores[e["to"]]),
        )


class _Member:
    """One per-submesh population member: a 1-lane stacked program.

    Running the reference members through the SAME vmapped lane body as
    the fused path (``_stacked_lane_body`` via the stacked step
    builders, K=1) is what makes fused-vs-submesh bit-parity provable:
    both modes share one step arithmetic, one RNG stream
    (``fold_in(key(seed+1), global_step)`` per inner step), and one
    data permutation recipe — only the dispatch structure differs.
    """

    def __init__(
        self,
        trial: TrialMesh,
        member_id: int,
        cfg: PBTConfig,
        model: Any,  # any VAE-family module: (recon_logits, mu, logvar)
        train_data: Dataset,
        eval_host: tuple[np.ndarray, np.ndarray],
        lr: float,
    ):
        self.trial = trial
        self.member_id = member_id
        seed = cfg.seed + member_id
        self.state = create_stacked_train_state(trial, model, [seed])
        self.hypers = trial.device_put(
            TrialHypers.stack([lr], [cfg.beta])
        )
        self.multi_step = make_stacked_multi_step(trial, model)
        self.eval_scan = make_stacked_eval_scan(trial, model)
        self.base_rngs = trial.device_put(
            jnp.stack([jax.random.key(seed + 1)])
        )
        self.train_iter = StackedTrialDataIterator(
            train_data, trial, cfg.batch_size, [seed]
        )
        self._chunks = self.train_iter.stream_chunks(
            cfg.steps_per_generation
        )
        # Pad-and-mask eval, the whole set pre-staged (E, B, ...) and
        # placed once on this member's submesh: every eval row scores
        # (the full-coverage contract of the HPO driver's test loop),
        # and a generation's scoring is ONE scan-eval dispatch —
        # structurally identical to the eval phase inside the fused
        # generation program, which is what keeps the two modes'
        # scores bit-identical (steps._scan_eval_sums).
        self.eval_batches, self.eval_weights = _place_eval(
            trial, *eval_host
        )
        self._step = 0

    def run_generation(self, book: dict):
        """Dispatch one generation's explore phase (async): S fused
        train steps on the next S batches of this member's stream."""
        batches = next(self._chunks)
        lane_steps = jnp.full((1,), self._step, jnp.int32)
        self.state, m = self.multi_step(
            self.state, self.hypers, batches, self.base_rngs, lane_steps
        )
        self._step += batches.shape[0]
        book["program_calls"] += 1
        return m

    def eval_loss_sum(self, book: dict) -> np.float32:
        """Summed masked eval loss over the full eval set (f32 — the
        rank statistic both modes share): one scan-eval dispatch, one
        host sync."""
        out = self.eval_scan(
            self.state, self.hypers, self.eval_batches, self.eval_weights
        )
        book["program_calls"] += 1
        return np.asarray(jax.device_get(out["loss_sum"]), np.float32)[0]

    def set_lr(self, lr: np.float32) -> None:
        self.hypers = self.trial.device_put(
            TrialHypers.stack([float(lr)], [float(self.hypers.beta[0])])
        )


def _final_states_from_members(
    members: dict, population: int
) -> list:
    out = [None] * population
    for i, m in members.items():
        host = jax.device_get(m.state)
        out[i] = jax.tree.map(lambda a: np.asarray(a)[0], host)
    return out


def run_pbt(
    cfg: PBTConfig,
    train_data: Dataset,
    eval_data: Dataset,
    *,
    groups: Optional[Sequence[TrialMesh]] = None,
    out_dir: Optional[str] = None,
    verbose: bool = True,
    model_builder=None,
    fused: bool = False,
    return_states: bool = False,
) -> PBTResult:
    """Run synchronous-generation PBT.

    ``model_builder(cfg)`` swaps the model family, same contract as
    ``run_hpo``: any module whose apply returns ``(recon_logits, mu,
    logvar)`` (VAE, ConvVAE, MoEVAE) rides the shared train/eval steps;
    the population trains the one architecture while PBT explores lr.

    ``fused=False`` (per-submesh): one member per submesh in
    ``groups`` (default ``setup_groups(cfg.population)``), host-side
    exploit/explore. Multi-controller SPMD: every process builds only
    the members whose submesh it owns, but all processes track every
    member's score and lr so scheduling decisions are identical
    everywhere (one ``process_allgather`` per generation; a
    cross-process exploit moves the winner's bytes with
    ``broadcast_one_to_all``).

    ``fused=True`` (lane-axis): the whole population runs as K lanes of
    one vmapped program on ONE submesh — ``groups`` must then carve
    exactly one (default: all devices). A generation is a single
    dispatch of the registered ``pbt_gen`` program; see the module
    docstring and docs/PBT.md. ``return_states=True`` attaches each
    member's final host-side state to the result (the parity surface).
    """
    from multidisttorch_tpu import telemetry as _telemetry

    _telemetry.configure_from_env()
    if fused:
        return _run_pbt_fused(
            cfg, train_data, eval_data, groups=groups, out_dir=out_dir,
            verbose=verbose, model_builder=model_builder,
            return_states=return_states,
        )

    multihost = jax.process_count() > 1
    if multihost:
        from jax.experimental import multihost_utils
    if groups is None:
        groups = setup_groups(cfg.population)
    if len(groups) != cfg.population:
        raise ValueError(
            f"population {cfg.population} but {len(groups)} device groups"
        )

    model = (
        model_builder(cfg)
        if model_builder is not None
        else VAE(hidden_dim=cfg.hidden_dim, latent_dim=cfg.latent_dim)
    )
    lrs = _init_lrs(cfg)  # (K,) f32 — every process draws identically
    eval_host_imgs, eval_host_w, num_eval_rows = _stage_eval_host(
        eval_data, groups[0], cfg.batch_size
    )
    members = {
        i: _Member(
            g, i, cfg, model, train_data,
            (eval_host_imgs, eval_host_w), float(lrs[i]),
        )
        for i, g in enumerate(groups)
        if g.is_local_member
    }

    # Broadcast buffer for processes that don't own an exploit's source
    # member: the same construction path as the real member states
    # (steps.build_stacked_train_state), so the trees can never drift.
    template = (
        jax.tree.map(
            np.asarray,
            jax.device_get(build_stacked_train_state(model, [0])),
        )
        if multihost
        else None
    )

    n_exploit = n_exploit_for(cfg)
    explore_key = pbt_explore_key(cfg.seed)
    book = {"program_calls": 0, "host_transfers": 0}
    result = PBTResult(
        best_member=-1, best_eval_loss=float("inf"), mode="submesh"
    )
    prev_order: Optional[np.ndarray] = None
    t0 = time.time()

    for gen in range(cfg.generations):
        # --- explore phase: one scan-fused dispatch per local member
        # puts a full generation of steps in flight on every submesh
        for m in members.values():
            m.run_generation(book)

        # --- score every member globally: local evals, then one
        # allgather-min (non-owned slots carry +inf; NaN propagates, so
        # a diverged member stays NaN — ranked last — everywhere)
        local_sums = np.full(cfg.population, np.inf, np.float32)
        for i, m in members.items():
            local_sums[i] = m.eval_loss_sum(book)
        if multihost:
            gathered = multihost_utils.process_allgather(local_sums)
            sums = np.asarray(gathered, np.float32).min(axis=0)
        else:
            sums = local_sums
        scores = sums.astype(np.float64) / num_eval_rows
        order, sanitized = _rank(sums)
        record = {
            "generation": gen,
            "scores": {int(i): float(scores[i]) for i in order},
            "loss_sums": [float(s) for s in sums],
            "order": [int(i) for i in order],
            "lrs": {i: float(lrs[i]) for i in range(cfg.population)},
            "exploits": [],
        }

        # --- exploit/explore: bottom slot i clones top slot i iff
        # strictly worse (== skips: a tied population has no winner to
        # copy, and all-NaN sanitizes to all-inf which never exchanges).
        # Decisions derive from the global scores, and perturbations
        # from the pure (explore_key, gen, target-lane) function, so
        # every process makes identical choices — and the in-program
        # exchange (train.steps.pbt_exchange) makes the same ones.
        top = order[:n_exploit]
        bottom = order[cfg.population - n_exploit:] if n_exploit else []
        for i, bad_id in enumerate(bottom):
            bad_id = int(bad_id)
            good_id = int(top[i])
            if not sanitized[bad_id] > sanitized[good_id]:
                continue
            good_trial, bad_trial = groups[good_id], groups[bad_id]
            factor = pbt_perturb_factor(
                explore_key, gen, bad_id, cfg.perturb_factors
            )
            new_lr = np.float32(
                jnp.clip(
                    jnp.float32(lrs[good_id]) * factor,
                    cfg.lr_min,
                    cfg.lr_max,
                )
            )
            # cross-submesh weight + optimizer-state transfer: the
            # winner's replicated state moves via host memory. When the
            # source lives on another process, one broadcast (from the
            # owner of the source's first device) hands every process
            # the bytes; target owners then place them on their mesh.
            # Ownership sets are global device metadata, so every
            # process computes the same answer: when everyone who needs
            # the state already owns the source, the world-collective
            # broadcast is pure waste — a full params+moments transfer
            # skipped.
            good_owners = {d.process_index for d in good_trial.devices}
            bad_owners = {d.process_index for d in bad_trial.devices}
            if multihost and not bad_owners <= good_owners:
                is_source = (
                    good_trial.devices[0].process_index
                    == jax.process_index()
                )
                # Only the is_source process's bytes are consumed by
                # the broadcast; every other process passes the
                # shape-only template rather than paying a full
                # params+moments device_get whose result is discarded.
                payload = (
                    jax.tree.map(
                        np.asarray, jax.device_get(members[good_id].state)
                    )
                    if is_source
                    else template
                )
                host_state = multihost_utils.broadcast_one_to_all(
                    payload, is_source=is_source
                )
                book["host_transfers"] += 1
            elif bad_id in members:
                # Non-broadcast path: fetch only where the state is
                # about to be consumed (the target's owners; they also
                # own the source here, or we'd be in the broadcast
                # branch).
                host_state = jax.device_get(members[good_id].state)
                book["host_transfers"] += 1
            if bad_id in members:
                bad = members[bad_id]
                bad.state = bad_trial.device_put(host_state)
                bad.set_lr(new_lr)
                book["host_transfers"] += 1
            lrs[bad_id] = new_lr
            record["exploits"].append(
                {"from": good_id, "to": bad_id, "new_lr": float(new_lr)}
            )
            if verbose and bad_id in members:
                log0(
                    f"PBT gen {gen}: member {bad_id} "
                    f"(loss {scores[bad_id]:.2f}) exploits "
                    f"{good_id} (loss {scores[good_id]:.2f}), "
                    f"lr -> {float(new_lr):.2e}",
                    trial=bad_trial,
                )

        _emit_generation(
            "submesh", gen, scores, order, lrs, record["exploits"],
            prev_order, (gen + 1) * cfg.steps_per_generation,
        )
        prev_order = order
        result.history.append(record)
        best = int(order[0])
        if scores[best] < result.best_eval_loss:
            result.best_eval_loss = float(scores[best])
            result.best_member = best

    result.wall_s = time.time() - t0
    result.final_lrs = [float(v) for v in lrs]
    _finish_books(result, cfg, book)
    if return_states and not multihost:
        result.final_states = _final_states_from_members(
            members, cfg.population
        )
    _write_report(result, out_dir)
    return result


def _finish_books(result: PBTResult, cfg: PBTConfig, book: dict) -> None:
    gens = max(1, cfg.generations)
    result.dispatch_book = dict(
        book,
        generations=cfg.generations,
        dispatches_per_generation=round(book["program_calls"] / gens, 3),
        transfers_per_generation=round(book["host_transfers"] / gens, 3),
    )


def _write_report(result: PBTResult, out_dir: Optional[str]) -> None:
    if out_dir and jax.process_index() != 0:
        out_dir = None  # one writer process for the shared report
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "pbt.json"), "w") as f:
        json.dump(
            {
                "mode": result.mode,
                "best_member": result.best_member,
                "best_eval_loss": result.best_eval_loss,
                "final_lrs": result.final_lrs,
                "history": result.history,
                "wall_s": result.wall_s,
                "dispatch_book": result.dispatch_book,
            },
            f,
            indent=2,
        )


def _stage_eval_host(
    eval_data: Dataset, trial: TrialMesh, batch_size: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Stage the full pad-and-mask eval set host-side ONCE: the
    ``(E, B, ...)`` images + ``(E, B)`` weights every scorer scans, plus
    the real row count. The host staging is shared by all K members of
    the per-submesh mode (only the per-trial device placement,
    :func:`_place_eval`, repeats); groups share a shape, so any one
    trial validates the batch divisibility for all."""
    it = EvalDataIterator(eval_data, trial, batch_size)
    imgs, weights = [], []
    for imgs_np, _labels, w_np in it.host_batches():
        imgs.append(imgs_np)
        weights.append(w_np)
    return (
        np.stack(imgs).astype(np.float32, copy=False),
        np.stack(weights),
        it.num_rows,
    )


def _place_eval(trial: TrialMesh, stacked: np.ndarray, w: np.ndarray):
    """Place a staged eval set on one trial's submesh (dim 1
    data-sharded), once per trial — the scorers scan it on device every
    generation, so eval costs zero further host transfers."""
    sh = trial.sharding(None, DATA_AXIS)
    if jax.process_count() == 1:
        return jax.device_put(stacked, sh), jax.device_put(w, sh)
    mk = jax.make_array_from_callback
    return (
        mk(stacked.shape, sh, lambda idx: stacked[idx]),
        mk(w.shape, sh, lambda idx: w[idx]),
    )


def _admit_fused_program(
    trial: TrialMesh, model, cfg: PBTConfig, n_exploit: int, E: int
):
    """Take the fused generation executable from the process-lifetime
    compile registry (one compile EVER per program; ``cache_hit`` on
    every later take — including generation 2+ of this run via
    :func:`_take_fused_again`), compiling inline through the registry's
    coalesced, timed, event-emitting path on first admission. Custom
    ``model_builder`` families bypass the registry (their architecture
    is not captured by the key vocabulary) and jit inline — the same
    policy as the HPO driver. Returns ``(callable, key_or_None)``."""
    from multidisttorch_tpu.compile import programs as _cprog
    from multidisttorch_tpu.compile.registry import (
        READY,
        SOURCE_INLINE,
        get_executable_registry,
    )

    build = lambda: _cprog.build_pbt_generation(  # noqa: E731
        trial,
        model,
        n_exploit=n_exploit,
        perturb_factors=cfg.perturb_factors,
        lr_min=cfg.lr_min,
        lr_max=cfg.lr_max,
    )
    if not isinstance(model, VAE):
        return build(), None
    bucket = (
        cfg.batch_size, model.hidden_dim, model.latent_dim, 1, 1, False,
    )
    key = _cprog.pbt_gen_key(
        trial,
        bucket,
        lanes=cfg.population,
        steps_per_generation=cfg.steps_per_generation,
        eval_batches=E,
        n_exploit=n_exploit,
        perturb_factors=cfg.perturb_factors,
        lr_min=cfg.lr_min,
        lr_max=cfg.lr_max,
    )
    reg = get_executable_registry()
    exe = reg.take(key)
    if exe is not None:
        return exe, key
    raw = build()
    try:
        avals = _cprog.pbt_gen_avals(
            model,
            lanes=cfg.population,
            steps_per_generation=cfg.steps_per_generation,
            eval_batches=E,
            batch_size=cfg.batch_size,
        )
    except Exception:  # noqa: BLE001 — aval derivation failing is a
        # registry problem, not a sweep problem: jit fallback.
        return raw, None
    reg.claim(key)
    entry = reg.compile_now(key, raw, avals, source=SOURCE_INLINE)
    if entry.status == READY and entry.compiled is not None:
        return entry.compiled, key
    return raw, None


def _take_fused_again(key: Optional[tuple], current):
    """Generation 2+ admission: re-take from the registry so the books
    (hits counter, ``cache_hit`` events) record that the generation
    reused the one compiled executable — the acceptance surface for
    "one compile, cache_hit on generation 2+"."""
    if key is None:
        return current
    from multidisttorch_tpu.compile.registry import (
        get_executable_registry,
    )

    exe = get_executable_registry().take(key)
    return exe if exe is not None else current


def _run_pbt_fused(
    cfg: PBTConfig,
    train_data: Dataset,
    eval_data: Dataset,
    *,
    groups: Optional[Sequence[TrialMesh]] = None,
    out_dir: Optional[str] = None,
    verbose: bool = True,
    model_builder=None,
    return_states: bool = False,
) -> PBTResult:
    """The fused-lane mode body (call through ``run_pbt(fused=True)``)."""
    if groups is None:
        groups = setup_groups(1)
    if len(groups) != 1:
        raise ValueError(
            "fused PBT runs the whole population as lanes of ONE "
            f"submesh; got {len(groups)} groups (carve one, e.g. "
            "setup_groups(1), or pass the shape the per-submesh A/B "
            "leg uses)"
        )
    trial = groups[0]
    K = cfg.population
    S = cfg.steps_per_generation
    model = (
        model_builder(cfg)
        if model_builder is not None
        else VAE(hidden_dim=cfg.hidden_dim, latent_dim=cfg.latent_dim)
    )
    n_exploit = n_exploit_for(cfg)
    seeds = [cfg.seed + k for k in range(K)]
    lrs = _init_lrs(cfg)

    state = create_stacked_train_state(trial, model, seeds)
    hypers = trial.device_put(
        TrialHypers.stack([float(v) for v in lrs], [cfg.beta] * K)
    )
    base_rngs = trial.device_put(
        jnp.stack([jax.random.key(s + 1) for s in seeds])
    )
    explore_key = trial.device_put(pbt_explore_key(cfg.seed))
    data_iter = StackedTrialDataIterator(
        train_data, trial, cfg.batch_size, seeds
    )
    chunks = data_iter.stream_chunks(S)
    eval_imgs, eval_w, num_eval_rows = _stage_eval_host(
        eval_data, trial, cfg.batch_size
    )
    eval_batches, eval_weights = _place_eval(trial, eval_imgs, eval_w)

    gen_step, prog_key = _admit_fused_program(
        trial, model, cfg, n_exploit, eval_imgs.shape[0]
    )

    book = {"program_calls": 0, "host_transfers": 0}
    result = PBTResult(
        best_member=-1, best_eval_loss=float("inf"), mode="fused"
    )
    prev_order: Optional[np.ndarray] = None
    t0 = time.time()

    for gen in range(cfg.generations):
        if gen > 0:
            gen_step = _take_fused_again(prog_key, gen_step)
        batches = next(chunks)
        lane_steps = trial.device_put(
            jnp.full((K,), gen * S, jnp.int32)
        )
        gen_arr = trial.device_put(jnp.asarray(gen, jnp.int32))
        lrs_before = lrs.copy()
        # ONE dispatch: S train steps x K lanes, E eval batches, and
        # the lane-axis exploit/explore — the whole generation.
        state, hypers, stats = gen_step(
            state, hypers, batches, eval_batches, eval_weights,
            base_rngs, lane_steps, gen_arr, explore_key,
        )
        book["program_calls"] += 1
        # One fetch per generation: the population books (scores,
        # ranking, exchange edges, new lrs) — K floats and ints, not
        # member states.
        host = jax.device_get(
            {k: stats[k] for k in ("order", "exploited", "src", "new_lr",
                                   "eval_loss_sum")}
        )
        sums = np.asarray(host["eval_loss_sum"], np.float32)
        order = np.asarray(host["order"])
        exploited = np.asarray(host["exploited"])
        src = np.asarray(host["src"])
        lrs = np.asarray(host["new_lr"], np.float32)
        scores = sums.astype(np.float64) / num_eval_rows
        exploits = [
            {
                "from": int(src[lane]),
                "to": int(lane),
                "new_lr": float(lrs[lane]),
            }
            # bottom slots in rank order — the same exploit-list order
            # the per-submesh path records.
            for lane in (order[K - n_exploit:] if n_exploit else [])
            if exploited[lane]
        ]
        record = {
            "generation": gen,
            "scores": {int(i): float(scores[i]) for i in order},
            "loss_sums": [float(s) for s in sums],
            "order": [int(i) for i in order],
            "lrs": {i: float(lrs_before[i]) for i in range(K)},
            "exploits": exploits,
        }
        if verbose:
            for e in exploits:
                log0(
                    f"PBT gen {gen}: lane {e['to']} "
                    f"(loss {scores[e['to']]:.2f}) exploits "
                    f"{e['from']} (loss {scores[e['from']]:.2f}), "
                    f"lr -> {e['new_lr']:.2e}",
                    trial=trial,
                )
        _emit_generation(
            "fused", gen, scores, order, lrs, exploits, prev_order,
            (gen + 1) * S,
        )
        prev_order = order
        result.history.append(record)
        best = int(order[0])
        if scores[best] < result.best_eval_loss:
            result.best_eval_loss = float(scores[best])
            result.best_member = best

    result.wall_s = time.time() - t0
    result.final_lrs = [float(v) for v in lrs]
    _finish_books(result, cfg, book)
    if return_states:
        host = jax.device_get(state)
        result.final_states = [
            jax.tree.map(lambda a, k=k: np.asarray(a)[k], host)
            for k in range(K)
        ]
    _write_report(result, out_dir)
    return result
