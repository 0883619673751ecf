"""Fused-lane PBT tests (ISSUE 8): the in-program exploit/explore must
be bit-identical to the host-side reference path under the shared
seeding contract (docs/PBT.md), NaN lanes must rank last and never
source an exploit, the degenerate ``n_exploit == 0`` population must
skip the exchange, the fused generation program must compile ONCE
through the registry with cache hits on generation 2+, and the stacked
host-gather prefetch must be bit-transparent."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multidisttorch_tpu.data.datasets import synthetic_mnist
from multidisttorch_tpu.data.sampler import StackedTrialDataIterator
from multidisttorch_tpu.hpo.pbt import PBTConfig, n_exploit_for, run_pbt
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.steps import (
    TrainState,
    TrialHypers,
    pbt_exchange,
    pbt_explore_key,
    pbt_perturb_factor,
)

pytestmark = pytest.mark.pbt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    defaults = dict(
        population=4,
        generations=3,
        steps_per_generation=3,
        batch_size=16,
        hidden_dim=16,
        latent_dim=4,
        exploit_fraction=0.5,
        lr_min=1e-4,
        lr_max=1e-1,
        seed=0,
    )
    defaults.update(kw)
    return PBTConfig(**defaults)


def _tree_equal(a, b) -> bool:
    flags = jax.tree.map(
        lambda x, y: bool(
            np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        ),
        a,
        b,
    )
    return all(jax.tree.leaves(flags))


# A K=1 lane program per submesh (the reference path) and one K-lane
# program (the fused path) are differently shaped XLA:CPU programs. On
# the installed jaxlib (0.9.0) XLA vectorizes the batch reduction behind
# the latent heads' bias gradients differently at K=1 — measured: 4 of
# 104,416 parameters off by 1 ulp, their Adam moments by at most 3 —
# while every discrete outcome (loss sums, ranking, exploit edges,
# scores, lrs) stays exactly equal. The bound is what a reassociated
# f32 sum over a 32-row batch can move, with headroom.
STATE_MAX_ULP = 16


def _tree_within_ulp(a, b, maxulp: int) -> None:
    def leaf(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_array_max_ulp(x, y, maxulp=maxulp)
        else:
            np.testing.assert_array_equal(x, y)

    jax.tree.map(leaf, a, b)


def _toy_state(k: int) -> TrainState:
    # A recognizable per-lane state: lane i's rows are all i, so a
    # gather's provenance is readable off the values.
    return TrainState(
        params={
            "w": jnp.tile(
                jnp.arange(k, dtype=jnp.float32)[:, None], (1, 3)
            )
        },
        opt_state={"m": jnp.arange(k, dtype=jnp.float32) * 10.0},
        step=jnp.full((k,), 7, jnp.int32),
    )


def _exchange(losses, n_exploit=2, gen=0, k=4):
    state = _toy_state(k)
    hypers = TrialHypers.stack([1e-3] * k, [1.0] * k)
    return pbt_exchange(
        state,
        hypers,
        jnp.asarray(losses, jnp.float32),
        gen,
        pbt_explore_key(0),
        n_exploit=n_exploit,
        perturb_factors=(0.8, 1.25),
        lr_min=1e-4,
        lr_max=1e-1,
    )


def test_exchange_nan_ranks_last_and_never_sources():
    # lane 1 diverged (NaN): it must rank strictly last, be exploited
    # (replaced by a healthy top lane), and never appear as a source.
    state, hypers, stats = _exchange([1.0, np.nan, 0.5, 2.0])
    order = np.asarray(stats["order"])
    assert list(order) == [2, 0, 3, 1]  # NaN last
    exploited = np.asarray(stats["exploited"])
    src = np.asarray(stats["src"])
    assert exploited[1] and exploited[3]
    assert src[1] == 0 and src[3] == 2
    assert 1 not in src[exploited]  # never a source
    # lane 1's whole state became lane 0's; lane 3's became lane 2's
    w = np.asarray(state.params["w"])
    assert np.all(w[1] == 0.0) and np.all(w[3] == 2.0)
    m = np.asarray(state.opt_state["m"])
    assert m[1] == 0.0 and m[3] == 20.0
    # exploited lanes' lrs were perturbed within bounds; winners kept
    lr = np.asarray(hypers.lr)
    assert lr[0] == np.float32(1e-3) and lr[2] == np.float32(1e-3)
    for lane in (1, 3):
        assert 1e-4 <= lr[lane] <= 1e-1
        assert lr[lane] != np.float32(1e-3)


def test_exchange_nan_same_under_jit():
    # the exchange runs jitted inside the fused generation program —
    # the NaN contract must hold identically compiled, with gen traced
    eager_state, eager_hypers, eager_stats = _exchange(
        [1.0, np.nan, 0.5, 2.0], gen=3
    )
    state = _toy_state(4)
    hypers = TrialHypers.stack([1e-3] * 4, [1.0] * 4)

    @jax.jit
    def go(state, hypers, losses, gen):
        return pbt_exchange(
            state, hypers, losses, gen, pbt_explore_key(0),
            n_exploit=2, perturb_factors=(0.8, 1.25),
            lr_min=1e-4, lr_max=1e-1,
        )

    jit_state, jit_hypers, jit_stats = go(
        state, hypers,
        jnp.asarray([1.0, np.nan, 0.5, 2.0], jnp.float32),
        jnp.int32(3),
    )
    assert _tree_equal(eager_state, jit_state)
    assert _tree_equal(eager_hypers, jit_hypers)
    assert _tree_equal(eager_stats, jit_stats)


def test_exchange_all_nan_is_identity():
    # an all-diverged population sanitizes to all-inf: inf > inf never
    # holds, so nothing exchanges (there is no winner to clone).
    state, hypers, stats = _exchange([np.nan] * 4)
    assert not np.asarray(stats["exploited"]).any()
    assert _tree_equal(state, _toy_state(4))
    assert np.array_equal(
        np.asarray(hypers.lr), np.full(4, 1e-3, np.float32)
    )


def test_exchange_tie_skips():
    state, hypers, stats = _exchange([1.5, 1.5, 1.5, 1.5])
    assert not np.asarray(stats["exploited"]).any()
    assert _tree_equal(state, _toy_state(4))


def test_exchange_n_exploit_zero_identity():
    state, hypers, stats = _exchange([3.0, 1.0], n_exploit=0, k=2)
    assert not np.asarray(stats["exploited"]).any()
    assert list(np.asarray(stats["order"])) == [1, 0]
    assert _tree_equal(state, _toy_state(2))


def test_n_exploit_clamps():
    assert n_exploit_for(_cfg(population=1)) == 0
    assert n_exploit_for(_cfg(population=2, exploit_fraction=0.9)) == 1
    assert n_exploit_for(_cfg(population=4, exploit_fraction=0.5)) == 2
    assert n_exploit_for(_cfg(population=8, exploit_fraction=0.25)) == 2


def test_perturb_factor_pure_deterministic_eager_equals_traced():
    ek = pbt_explore_key(7)
    factors = (0.8, 1.25)
    traced = jax.jit(
        lambda g, lane: pbt_perturb_factor(ek, g, lane, factors)
    )
    seen = set()
    for g in range(4):
        for lane in range(4):
            eager = float(pbt_perturb_factor(ek, g, lane, factors))
            assert eager in [float(np.float32(f)) for f in factors]
            assert eager == float(
                traced(jnp.int32(g), jnp.int32(lane))
            )
            # pure: a second eager draw is identical
            assert eager == float(pbt_perturb_factor(ek, g, lane, factors))
            seen.add((g, lane, eager))
    # the stream actually varies over (gen, lane)
    assert len({v for (_, _, v) in seen}) == 2


def test_fused_matches_submesh_reference_bitwise():
    # THE parity contract: same seeds, same data, same explore draws —
    # the fused lane-axis exchange must reproduce the host-side
    # reference path: per-generation loss sums, ranking, exploit edges,
    # scores and lrs bit-for-bit, and every member's final state to
    # STATE_MAX_ULP.
    cfg = _cfg()
    train = synthetic_mnist(128, seed=0)
    evals = synthetic_mnist(40, seed=1)  # 3 eval batches, one padded
    groups = setup_groups(cfg.population)
    ref = run_pbt(
        cfg, train, evals, groups=groups, verbose=False,
        return_states=True,
    )
    fus = run_pbt(
        cfg, train, evals, groups=[groups[0]], fused=True,
        verbose=False, return_states=True,
    )
    assert ref.mode == "submesh" and fus.mode == "fused"
    for g in range(cfg.generations):
        r, f = ref.history[g], fus.history[g]
        assert r["loss_sums"] == f["loss_sums"], f"gen {g} sums"
        assert r["order"] == f["order"], f"gen {g} order"
        assert r["exploits"] == f["exploits"], f"gen {g} exploits"
        assert r["scores"] == f["scores"], f"gen {g} scores"
    assert ref.final_lrs == fus.final_lrs
    assert ref.best_member == fus.best_member
    assert ref.best_eval_loss == fus.best_eval_loss
    for k in range(cfg.population):
        _tree_within_ulp(
            ref.final_states[k], fus.final_states[k], STATE_MAX_ULP
        )
    # at least one exploit actually fired, or the drill proves nothing
    assert sum(len(h["exploits"]) for h in ref.history) >= 1
    # and the dispatch collapse is real: one dispatch per generation
    # fused vs >= K train + K eval per generation on the reference path
    assert fus.dispatch_book["program_calls"] == cfg.generations
    assert (
        ref.dispatch_book["dispatches_per_generation"]
        >= 3 * fus.dispatch_book["dispatches_per_generation"]
    )


def test_fused_degenerate_population_one():
    # K=1: n_exploit clamps to 0, the exchange is identity, and the
    # single lane still trains and scores.
    cfg = _cfg(population=1, generations=2)
    train = synthetic_mnist(64, seed=0)
    evals = synthetic_mnist(16, seed=1)
    r = run_pbt(
        cfg, train, evals, groups=setup_groups(1), fused=True,
        verbose=False,
    )
    assert r.best_member == 0
    assert np.isfinite(r.best_eval_loss)
    assert all(h["exploits"] == [] for h in r.history)


def test_fused_registry_one_compile_cache_hit_gen2plus(tmp_path):
    # The pbt_gen program rides the PR 7 registry: ONE compile ever,
    # and generation 2+ admissions are registry cache hits — asserted
    # off both the registry snapshot and the emitted compile events.
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.compile.registry import (
        get_executable_registry,
    )

    # a protocol distinct from every other test in this module, so the
    # process-lifetime registry entry is provably THIS run's
    cfg = _cfg(generations=3, steps_per_generation=5)
    train = synthetic_mnist(128, seed=0)
    evals = synthetic_mnist(16, seed=1)
    with telemetry.telemetry_run(str(tmp_path)):
        run_pbt(
            cfg, train, evals, groups=setup_groups(1), fused=True,
            verbose=False,
        )
        events = telemetry.read_events(
            os.path.join(str(tmp_path), "events.jsonl")
        )
    snap = get_executable_registry().snapshot()
    mine = {
        label: v
        for label, v in snap.items()
        if label.startswith("pbt_gen") and "-S5-" in label
    }
    assert mine, f"pbt_gen program missing from registry: {list(snap)}"
    (entry,) = mine.values()
    assert entry["status"] == "ready"
    assert entry["hits"] >= cfg.generations - 1
    compile_ends = [
        e for e in events
        if e["kind"] == "compile_end"
        and str(e["data"].get("program", "")).startswith("pbt_gen")
    ]
    assert len(compile_ends) == 1
    assert compile_ends[0]["data"]["ok"] is True
    assert compile_ends[0]["data"]["program_kind"] == "pbt_gen"
    hits = [
        e for e in events
        if e["kind"] == "cache_hit"
        and "-S5-" in str(e["data"].get("program", ""))
    ]
    assert len(hits) >= cfg.generations - 1


def test_pbt_events_and_population_fold(tmp_path):
    # pbt_gen / pbt_exploit events feed the SweepFold population view
    # the console renders: per-generation best/median loss, exploit
    # count, rank churn, lr quantiles.
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.telemetry.export import SweepFold, run_summary

    cfg = _cfg(generations=2)
    train = synthetic_mnist(128, seed=0)
    evals = synthetic_mnist(16, seed=1)
    with telemetry.telemetry_run(str(tmp_path)):
        run_pbt(
            cfg, train, evals, groups=setup_groups(1), fused=True,
            verbose=False,
        )
        events = telemetry.read_events(
            os.path.join(str(tmp_path), "events.jsonl")
        )
    gens = [e for e in events if e["kind"] == "pbt_gen"]
    assert len(gens) == cfg.generations
    for e in gens:
        d = e["data"]
        assert d["mode"] == "fused" and d["population"] == cfg.population
        assert np.isfinite(d["best_loss"])
        assert d["lr_min"] <= d["lr_median"] <= d["lr_max"]
    # churn appears from generation 1 on (no previous ordering before)
    assert "rank_churn" not in gens[0]["data"]
    assert "rank_churn" in gens[1]["data"]
    exploits = [e for e in events if e["kind"] == "pbt_exploit"]
    assert len(exploits) == sum(
        g["data"]["exploit_count"] for g in gens
    )
    fold = SweepFold()
    for e in events:
        fold.feed(e)
    assert fold.pbt["mode"] == "fused"
    assert fold.pbt["population"] == cfg.population
    assert sorted(fold.pbt["generations"]) == list(
        range(cfg.generations)
    )
    assert fold.pbt["exploit_total"] == len(exploits)
    # run_summary carries the population view too
    assert run_summary(events)["pbt"]["population"] == cfg.population


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_top_population_view(tmp_path, capsys):
    from multidisttorch_tpu import telemetry

    # distinct S so this test's registry key never collides with the
    # compile-count assertions of the registry test above
    cfg = _cfg(generations=2, steps_per_generation=4)
    train = synthetic_mnist(128, seed=0)
    evals = synthetic_mnist(16, seed=1)
    with telemetry.telemetry_run(str(tmp_path)):
        run_pbt(
            cfg, train, evals, groups=setup_groups(1), fused=True,
            verbose=False,
        )
    sweep_top = _load_tool("sweep_top")
    assert sweep_top.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "population" in out
    assert "mode fused" in out
    assert "lr min/med/max" in out
    # one-shot machine-readable snapshot carries the same fold
    assert sweep_top.main([str(tmp_path), "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["pbt"]["mode"] == "fused"
    assert len(snap["pbt"]["generations"]) == cfg.generations


def test_stacked_stream_chunks_crosses_rounds():
    # stream_chunks must replay exactly the per-round batches, in
    # order, across round boundaries (fresh permutation each round),
    # and every chunk must be full.
    trial = setup_groups(1)[0]
    ds = synthetic_mnist(96, seed=3)
    seeds = [11, 12]
    a = StackedTrialDataIterator(ds, trial, 16, list(seeds))
    b = StackedTrialDataIterator(ds, trial, 16, list(seeds))
    # a: 4 chunks of 3 steps = 12 steps = 2 full rounds of 6 batches
    chunks = [np.asarray(c) for _, c in zip(range(4), a.stream_chunks(3))]
    flat = np.concatenate(chunks, axis=0)
    rounds = []
    for _ in range(2):
        rounds.extend(np.asarray(x) for x in b.round_batches())
    assert np.array_equal(flat, np.stack(rounds))
    for c in chunks:
        assert c.shape == (3, 2, 16, 784)


def test_stacked_prefetch_bit_parity_and_kill_switch(monkeypatch):
    trial = setup_groups(1)[0]
    ds = synthetic_mnist(128, seed=4)
    seeds = [3, 9, 27]
    on = StackedTrialDataIterator(ds, trial, 16, list(seeds), prefetch=True)
    off = StackedTrialDataIterator(
        ds, trial, 16, list(seeds), prefetch=False
    )
    assert on._prefetch and not off._prefetch
    for _ in range(2):  # two rounds: prefetch threads come and go
        # drain each round fully (zip would leave the shorter-pulled
        # generator paused before its epoch advance)
        ra = [np.asarray(x) for x in on.round_batches()]
        rb = [np.asarray(y) for y in off.round_batches()]
        assert len(ra) == len(rb) == on.num_batches
        for x, y in zip(ra, rb):
            assert np.array_equal(x, y)
    # the env kill switch forces the inline path
    monkeypatch.setenv("MDT_STACKED_PREFETCH", "0")
    assert not StackedTrialDataIterator(
        ds, trial, 16, [1]
    )._prefetch
    monkeypatch.delenv("MDT_STACKED_PREFETCH")
    assert StackedTrialDataIterator(ds, trial, 16, [1])._prefetch


def test_stacked_prefetch_fault_hook_timing():
    # An injected loader fault must surface at the SAME batch index
    # with prefetch on as off (the hook runs consumer-side), and the
    # batches before it must still be delivered.
    trial = setup_groups(1)[0]
    ds = synthetic_mnist(96, seed=5)

    class Boom(RuntimeError):
        pass

    def hook(b, stacked):
        if b == 2:
            raise Boom(f"batch {b}")
        return stacked

    for prefetch in (True, False):
        it = StackedTrialDataIterator(
            ds, trial, 16, [1], fault_hook=hook, prefetch=prefetch
        )
        got = []
        with pytest.raises(Boom, match="batch 2"):
            for x in it.round_batches():
                got.append(np.asarray(x))
        assert len(got) == 2, f"prefetch={prefetch}"


def test_stacked_prefetch_abandon_does_not_wedge():
    # Abandoning a prefetched round mid-way (lane refill, retirement,
    # an exception upstream) must leave no stuck producer: the next
    # round iterates cleanly and matches a fresh iterator.
    trial = setup_groups(1)[0]
    ds = synthetic_mnist(128, seed=6)
    it = StackedTrialDataIterator(ds, trial, 16, [5], prefetch=True)
    gen = it.round_batches()
    next(gen)
    gen.close()  # abandon mid-round
    # iterating a new round still works and epochs stayed consistent
    n = sum(1 for _ in it.round_batches())
    assert n == it.num_batches
