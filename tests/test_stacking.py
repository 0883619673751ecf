"""Trial-stacking tests: vmapped stacked steps, the stacked data
gatherer, mask-and-refill lane surgery, and the driver's bucket
scheduling — including the ISSUE 1 acceptance contract: a stacked
trial's final params match the unstacked path bit-for-bit (same seed,
same data order, same submesh shape)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from multidisttorch_tpu.data.datasets import synthetic_mnist
from multidisttorch_tpu.data.sampler import (
    StackedTrialDataIterator,
    TrialDataIterator,
)
from multidisttorch_tpu.hpo.driver import (
    TrialConfig,
    config_is_stackable,
    run_hpo,
    stack_bucket_key,
)
from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.steps import (
    TrialHypers,
    build_lane_state,
    create_stacked_train_state,
    create_train_state,
    make_lane_ops,
    make_stacked_eval_step,
    make_stacked_multi_step,
    make_stacked_train_step,
    make_train_step,
)


def _params_equal(a, b) -> bool:
    diffs = jax.tree.map(
        lambda x, y: bool(jnp.all(jnp.asarray(x) == jnp.asarray(y))), a, b
    )
    return all(jax.tree.leaves(diffs))


@pytest.fixture(scope="module")
def trial():
    return setup_groups(1)[0]  # all 8 virtual devices


@pytest.fixture(scope="module")
def model():
    return VAE(hidden_dim=32, latent_dim=8)


def test_stacked_step_bitwise_parity_with_unstacked(trial, model):
    # THE acceptance contract: K trials advanced by the vmapped stacked
    # step produce final params BIT-IDENTICAL to the same configs run
    # through make_train_step one at a time — same seeds, same batches,
    # same per-step RNG stream (fold_in(key(seed+1), step)), same
    # submesh. Different lrs, betas, and seeds per lane on purpose.
    K, B, steps = 3, 16, 3
    seeds, lrs, betas = [0, 5, 9], [1e-3, 3e-3, 2e-3], [1.0, 4.0, 1.0]
    hypers = TrialHypers.stack(lrs, betas)
    sstep = make_stacked_train_step(trial, model)
    state = create_stacked_train_state(trial, model, seeds)
    base = jnp.stack([jax.random.key(s + 1) for s in seeds])
    batches = jnp.asarray(
        np.random.default_rng(0).uniform(0, 1, (steps, K, B, 784)),
        jnp.float32,
    )
    for i in range(steps):
        state, metrics = sstep(
            state, hypers, batches[i], base, jnp.full((K,), i, jnp.int32)
        )
    assert metrics["loss_sum"].shape == (K,)

    for k in range(K):
        su = create_train_state(
            trial, model, optax.adam(lrs[k]), jax.random.key(seeds[k])
        )
        ustep = make_train_step(
            trial, model, optax.adam(lrs[k]), beta=betas[k]
        )
        for i in range(steps):
            su, _ = ustep(
                su, batches[i, k],
                jax.random.fold_in(jax.random.key(seeds[k] + 1), i),
            )
        lane_params = jax.tree.map(lambda x: x[k], state.params)
        assert _params_equal(lane_params, su.params), f"lane {k} diverged"
        lane_opt = jax.tree.map(lambda x: x[k], state.opt_state)
        assert _params_equal(lane_opt, su.opt_state), f"lane {k} opt state"


def test_stacked_multi_step_matches_per_step(trial, model):
    # Scan-chunked stacked steps use the SAME per-step fold_in stream,
    # so chunked == per-step bitwise (stronger than make_multi_step,
    # whose split-based stream is its own).
    K, B, S = 2, 16, 4
    seeds = [1, 2]
    hypers = TrialHypers.stack([1e-3] * K, [1.0] * K)
    base = jnp.stack([jax.random.key(s + 1) for s in seeds])
    batches = jnp.asarray(
        np.random.default_rng(1).uniform(0, 1, (S, K, B, 784)), jnp.float32
    )
    s_multi = create_stacked_train_state(trial, model, seeds)
    multi = make_stacked_multi_step(trial, model)
    s_multi, m = multi(
        s_multi, hypers, batches, base, jnp.zeros((K,), jnp.int32)
    )
    assert m["loss_sum"].shape == (S, K)

    s_step = create_stacked_train_state(trial, model, seeds)
    sstep = make_stacked_train_step(trial, model)
    for i in range(S):
        s_step, _ = sstep(
            s_step, hypers, batches[i], base, jnp.full((K,), i, jnp.int32)
        )
    assert _params_equal(s_multi.params, s_step.params)


def test_active_mask_freezes_lane(trial, model):
    # active=0.0 freezes a lane exactly (params AND opt state), while
    # live lanes continue; the compiled program is the same either way.
    K, B = 2, 16
    hypers_live = TrialHypers.stack([1e-3] * K, [1.0] * K)
    hypers_mask = TrialHypers.stack([1e-3] * K, [1.0] * K, active=[1.0, 0.0])
    sstep = make_stacked_train_step(trial, model)
    state = create_stacked_train_state(trial, model, [3, 4])
    base = jnp.stack([jax.random.key(s + 1) for s in (3, 4)])
    batch = jnp.asarray(
        np.random.default_rng(2).uniform(0, 1, (K, B, 784)), jnp.float32
    )
    frozen_before = jax.device_get(
        jax.tree.map(lambda x: x[1], state.params)
    )
    state, _ = sstep(
        state, hypers_live, batch, base, jnp.zeros((K,), jnp.int32)
    )
    live_after_one = jax.device_get(jax.tree.map(lambda x: x[1], state.params))
    state, _ = sstep(
        state, hypers_mask, batch, base, jnp.ones((K,), jnp.int32)
    )
    lane1 = jax.tree.map(lambda x: x[1], state.params)
    assert _params_equal(lane1, live_after_one)  # frozen at step-1 values
    assert not _params_equal(lane1, frozen_before)  # did train before mask
    # the one compiled program served both hypers values
    assert sstep._cache_size() == 1


def test_lane_ops_read_write_single_compile(trial, model):
    K = 4
    read, write = make_lane_ops(trial)
    state = create_stacked_train_state(trial, model, list(range(K)))
    fresh = trial.device_put(build_lane_state(model, 99))
    fresh_host = jax.device_get(fresh.params)
    before_lane0 = jax.device_get(jax.tree.map(lambda x: x[0], state.params))

    state2 = write(state, fresh, np.int32(2))
    # lane 2 replaced, lane 0 untouched
    assert _params_equal(
        jax.tree.map(lambda x: x[2], state2.params), fresh_host
    )
    assert _params_equal(
        jax.tree.map(lambda x: x[0], state2.params), before_lane0
    )
    # read slices what write wrote
    lane = read(state2, np.int32(2))
    assert _params_equal(lane.params, fresh_host)
    # traced lane index: every k reuses ONE executable each way
    for k in (0, 1, 3):
        _ = read(state2, np.int32(k))
        state2 = write(
            state2, trial.device_put(build_lane_state(model, 50 + k)),
            np.int32(k),
        )
    assert read._cache_size() == 1
    assert write._cache_size() == 1


def test_stacked_eval_step_matches_unstacked(trial, model):
    from multidisttorch_tpu.train.steps import make_eval_step

    K, B = 2, 16
    betas = [1.0, 4.0]
    hypers = TrialHypers.stack([1e-3] * K, betas)
    state = create_stacked_train_state(trial, model, [0, 7])
    seval = make_stacked_eval_step(trial, model)
    batch = jnp.asarray(
        np.random.default_rng(3).uniform(0, 1, (B, 784)), jnp.float32
    )
    weights = jnp.asarray(
        np.r_[np.ones(10), np.zeros(6)].astype(np.float32)
    )
    out = seval(state, hypers, batch, weights)
    assert out["loss_sum"].shape == (K,)
    for k in range(K):
        su = create_train_state(
            trial, model, optax.adam(1e-3), jax.random.key([0, 7][k])
        )
        ev = make_eval_step(
            trial, model, beta=betas[k], with_recon=False, masked=True
        )
        ref = ev(su, batch, weights)
        # The vmapped K-lane program and the plain one are different
        # XLA:CPU programs; on the installed jaxlib (0.9.0) their f32
        # loss sums over 16 rows x 784 features differ by 1 ulp
        # (5610.0728 vs 5610.0732). Bound: 4 ulp.
        np.testing.assert_array_max_ulp(
            np.float32(out["loss_sum"][k]), np.float32(ref["loss_sum"]),
            maxulp=4,
        )


def test_stacked_iterator_matches_trial_iterator(trial):
    data = synthetic_mnist(96, seed=0)
    seeds = [0, 11, 5]
    B = 16
    stacked = StackedTrialDataIterator(data, trial, B, seeds)
    singles = [
        TrialDataIterator(data, trial, B, seed=s, use_native=False)
        for s in seeds
    ]
    # two lockstep rounds == each lane's epochs 1 and 2, bit-identical
    for epoch in (1, 2):
        per_lane = [list(it.epoch(epoch)) for it in singles]
        for b, stacked_batch in enumerate(stacked.round_batches()):
            got = np.asarray(stacked_batch)
            assert got.shape == (len(seeds), B, 784)
            for k in range(len(seeds)):
                np.testing.assert_array_equal(
                    got[k], np.asarray(per_lane[k][b])
                )


def test_stacked_iterator_set_lane_refill_stream(trial):
    data = synthetic_mnist(64, seed=0)
    B = 16
    stacked = StackedTrialDataIterator(data, trial, B, [0, 3])
    list(stacked.round_batches())  # both lanes consume epoch 1
    stacked.set_lane(1, seed=42)  # refill lane 1 mid-sweep
    fresh = TrialDataIterator(data, trial, B, seed=42, use_native=False)
    lane0 = TrialDataIterator(data, trial, B, seed=0, use_native=False)
    fresh_batches = list(fresh.epoch(1))  # refilled lane restarts epoch 1
    lane0_batches = list(lane0.epoch(2))  # neighbor continues at epoch 2
    for b, stacked_batch in enumerate(stacked.round_batches()):
        got = np.asarray(stacked_batch)
        np.testing.assert_array_equal(got[0], np.asarray(lane0_batches[b]))
        np.testing.assert_array_equal(got[1], np.asarray(fresh_batches[b]))


def test_stacked_iterator_round_chunks_tail(trial):
    data = synthetic_mnist(80, seed=1)  # 5 batches of 16 -> chunks 2+2+1
    stacked = StackedTrialDataIterator(data, trial, 16, [0, 1])
    chunks = list(stacked.round_chunks(2))
    assert [c[0] for c in chunks] == [0, 2, 4]
    assert [c[1].shape[0] for c in chunks] == [2, 2, 1]
    assert chunks[0][1].shape[1:] == (2, 16, 784)
    # chunked rows == the per-step rows, same round
    stacked2 = StackedTrialDataIterator(data, trial, 16, [0, 1])
    flat = np.concatenate([np.asarray(c[1]) for c in chunks])
    steps = np.stack([np.asarray(b) for b in stacked2.round_batches()])
    np.testing.assert_array_equal(flat, steps)


def test_bucket_key_and_stackability():
    base = dict(trial_id=0, epochs=1, batch_size=16, hidden_dim=32,
                latent_dim=8)
    a = TrialConfig(**base)
    assert stack_bucket_key(a) == stack_bucket_key(
        TrialConfig(**{**base, "trial_id": 1, "lr": 9e-3, "beta": 7.0,
                       "seed": 4, "epochs": 5, "log_interval": 3})
    )
    assert stack_bucket_key(a) != stack_bucket_key(
        TrialConfig(**{**base, "hidden_dim": 64})
    )
    assert stack_bucket_key(a) != stack_bucket_key(
        TrialConfig(**{**base, "batch_size": 32})
    )
    assert config_is_stackable(a)
    assert not config_is_stackable(
        TrialConfig(**{**base, "eval_sampled": True})
    )


def _small_cfg(i, **kw):
    d = dict(trial_id=i, epochs=1, batch_size=16, hidden_dim=32,
             latent_dim=8, log_interval=100)
    d.update(kw)
    return TrialConfig(**d)


@pytest.fixture(scope="module")
def data():
    return synthetic_mnist(128, seed=0), synthetic_mnist(32, seed=1)


def test_run_hpo_stacked_end_to_end(tmp_path, data):
    # 5 same-shape configs on 2 groups: trials outnumber groups, so the
    # driver buckets and stacks; unequal epoch targets exercise
    # mask-and-refill retirement mid-bucket.
    train, test = data
    configs = [
        _small_cfg(0),
        _small_cfg(1, lr=3e-3),
        _small_cfg(2, epochs=2, beta=4.0),
        _small_cfg(3, seed=7),
        _small_cfg(4, epochs=3),
    ]
    results = run_hpo(
        configs, train, test, num_groups=2, out_dir=str(tmp_path),
        verbose=False, save_images=False, stack_trials=True,
    )
    assert [r.trial_id for r in results] == [0, 1, 2, 3, 4]
    for r in results:
        assert r.status == "completed"
        assert r.stacked
        assert r.steps == 8 * r.config.epochs
        assert len(r.history) == r.config.epochs
        assert np.isfinite(r.final_train_loss)
        assert np.isfinite(r.final_test_loss)
        assert r.checkpoint and os.path.exists(r.checkpoint)
        with open(os.path.join(r.out_dir, "metrics.json")) as f:
            metrics = json.load(f)
        assert metrics["trial_id"] == r.trial_id
        assert metrics["stacked"] is True
        assert metrics["dataset"] == "synthetic-mnist"
    # per-trial hypers took effect within the shared program
    assert results[0].final_train_loss != results[1].final_train_loss


def test_run_hpo_stacked_parity_with_unstacked(tmp_path, data):
    # Driver-level acceptance: every stacked trial's losses equal the
    # same config run unstacked on the same submesh shape, bitwise —
    # the stacked per-step RNG stream matches fused_steps=1 exactly.
    train, test = data
    configs = [_small_cfg(0, epochs=2), _small_cfg(1, lr=3e-3, epochs=2),
               _small_cfg(2, beta=2.0, seed=5, epochs=2)]
    stacked = run_hpo(
        configs, train, test, num_groups=1, out_dir=str(tmp_path / "s"),
        verbose=False, save_images=False, stack_trials=True,
    )
    assert all(r.stacked for r in stacked)
    for i, cfg in enumerate(configs):
        (un,) = run_hpo(
            [cfg], train, test, num_groups=1,
            out_dir=str(tmp_path / f"u{i}"),
            verbose=False, save_images=False,
        )
        assert stacked[i].final_train_loss == un.final_train_loss
        assert stacked[i].final_test_loss == un.final_test_loss


def test_run_hpo_stacked_checkpoint_resumes_unstacked(tmp_path, data):
    # A stacked lane's retirement checkpoint carries the same metadata
    # contract as the classic path: a later unstacked resume recognizes
    # the trial as complete and skips it.
    train, _ = data
    cfgs = [_small_cfg(0), _small_cfg(1, lr=2e-3)]
    run_hpo(
        cfgs, train, None, num_groups=1, out_dir=str(tmp_path),
        verbose=False, save_images=False, stack_trials=True,
    )
    (r,) = run_hpo(
        [cfgs[0]], train, None, num_groups=1, out_dir=str(tmp_path),
        verbose=False, save_images=False, resume=True,
    )
    assert r.status == "resumed_complete"
    assert r.steps == 8


def test_run_hpo_stacked_mixed_with_unstackable(tmp_path, data):
    # An eval_sampled config can't stack; it runs the classic path in
    # the same sweep while the rest bucket together.
    train, test = data
    configs = [
        _small_cfg(0), _small_cfg(1, lr=3e-3), _small_cfg(2, seed=2),
        _small_cfg(3, eval_sampled=True),
    ]
    results = run_hpo(
        configs, train, test, num_groups=2, out_dir=str(tmp_path),
        verbose=False, save_images=False, stack_trials=True,
    )
    assert [r.trial_id for r in results] == [0, 1, 2, 3]
    assert all(r.status == "completed" for r in results)
    assert [r.stacked for r in results] == [True, True, True, False]


def test_run_hpo_stacked_falls_back_when_groups_suffice(tmp_path, data):
    # Trials do NOT outnumber groups -> classic path, stacked=False.
    train, _ = data
    results = run_hpo(
        [_small_cfg(0), _small_cfg(1)], train, None, num_groups=2,
        out_dir=str(tmp_path), verbose=False, save_images=False,
        save_checkpoints=False, stack_trials=True,
    )
    assert all(not r.stacked for r in results)
    assert all(r.status == "completed" for r in results)


def test_run_hpo_stacked_rejects_contradictory_modes(tmp_path, data):
    train, _ = data
    cfgs = [_small_cfg(0), _small_cfg(1)]
    with pytest.raises(ValueError, match="resume"):
        run_hpo(cfgs, train, None, num_groups=1, out_dir=str(tmp_path),
                stack_trials=True, resume=True)
    with pytest.raises(ValueError, match="shard_across_trials"):
        run_hpo(cfgs, train, None, num_groups=1, out_dir=str(tmp_path),
                stack_trials=True, shard_across_trials=True)
    with pytest.raises(ValueError, match="model_builder"):
        run_hpo(cfgs, train, None, num_groups=1, out_dir=str(tmp_path),
                stack_trials=True, model_builder=lambda cfg: VAE())


def test_run_hpo_stacked_fused_steps_bucket(tmp_path, data):
    # fused_steps>1 buckets use the scan-chunked stacked multi-step
    # (with the per-step tail); counts and history match the contract.
    train, _ = data
    configs = [_small_cfg(i, fused_steps=3, epochs=2) for i in range(3)]
    results = run_hpo(
        configs, train, None, num_groups=1, out_dir=str(tmp_path),
        verbose=False, save_images=False, stack_trials=True,
    )
    assert all(r.stacked for r in results)
    assert all(r.steps == 16 for r in results)
    assert all(len(r.history) == 2 for r in results)


def test_run_hpo_stacked_host_syncs_o1(tmp_path, data):
    # The bucket pays O(1) fetches per ROUND for all lanes together: 2
    # per epoch (train avg + test avg) regardless of lane count.
    train, test = data
    configs = [_small_cfg(i, epochs=2) for i in range(4)]
    results = run_hpo(
        configs, train, test, num_groups=1, out_dir=str(tmp_path),
        verbose=False, save_images=False, stack_trials=True,
    )
    for r in results:
        assert r.host_syncs == 2 * 2
