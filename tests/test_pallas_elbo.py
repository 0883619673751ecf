"""Fused Pallas ELBO kernel: value + gradient parity with the jnp path
(interpreter mode on CPU; same code compiles for real TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multidisttorch_tpu.ops.losses import elbo_loss_sum
from multidisttorch_tpu.ops.pallas_elbo import fused_elbo_loss_sum


@pytest.fixture(scope="module")
def arrays():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 2, (16, 784)).astype(np.float32))
    x = jnp.asarray(rng.uniform(0, 1, (16, 784)).astype(np.float32))
    mu = jnp.asarray(rng.normal(0, 1, (16, 20)).astype(np.float32))
    logvar = jnp.asarray(rng.normal(0, 0.5, (16, 20)).astype(np.float32))
    return logits, x, mu, logvar


def test_value_parity(arrays):
    logits, x, mu, logvar = arrays
    fused = float(fused_elbo_loss_sum(logits, x, mu, logvar, 1.0))
    plain = float(elbo_loss_sum(logits, x, mu, logvar, 1.0))
    assert fused == pytest.approx(plain, rel=1e-5)


def test_value_parity_beta(arrays):
    logits, x, mu, logvar = arrays
    fused = float(fused_elbo_loss_sum(logits, x, mu, logvar, 4.0))
    plain = float(elbo_loss_sum(logits, x, mu, logvar, 4.0))
    assert fused == pytest.approx(plain, rel=1e-5)


def test_gradient_parity(arrays):
    logits, x, mu, logvar = arrays

    g_fused = jax.grad(
        lambda l, m, lv: fused_elbo_loss_sum(l, x, m, lv, 2.0), argnums=(0, 1, 2)
    )(logits, mu, logvar)
    g_plain = jax.grad(
        lambda l, m, lv: elbo_loss_sum(l, x, m, lv, 2.0), argnums=(0, 1, 2)
    )(logits, mu, logvar)
    for a, b in zip(g_fused, g_plain):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_multi_block_grid_matches_plain(monkeypatch):
    # VERDICT r3 item 5: the kernel must tile over batch blocks instead
    # of staging whole operands in VMEM. Shrink the budget so a modest
    # batch needs a multi-step grid, and check value+grad parity through
    # the SMEM scalar accumulation across grid steps.
    from multidisttorch_tpu.ops import pallas_elbo

    monkeypatch.setattr(pallas_elbo, "_VMEM_BUDGET_BYTES", 64 * 1024)
    rng = np.random.default_rng(7)
    b, d, lat = 96, 784, 20
    logits = jnp.asarray(rng.normal(0, 2, (b, d)).astype(np.float32))
    x = jnp.asarray(rng.uniform(0, 1, (b, d)).astype(np.float32))
    mu = jnp.asarray(rng.normal(0, 1, (b, lat)).astype(np.float32))
    logvar = jnp.asarray(rng.normal(0, 0.5, (b, lat)).astype(np.float32))
    assert pallas_elbo._block_rows(logits, x, mu, logvar) < b  # grid > 1

    fused = float(fused_elbo_loss_sum(logits, x, mu, logvar, 1.5))
    plain = float(elbo_loss_sum(logits, x, mu, logvar, 1.5))
    assert fused == pytest.approx(plain, rel=1e-5)

    g_fused = jax.grad(
        lambda l, m, lv: fused_elbo_loss_sum(l, x, m, lv, 1.5),
        argnums=(0, 1, 2),
    )(logits, mu, logvar)
    g_plain = jax.grad(
        lambda l, m, lv: elbo_loss_sum(l, x, m, lv, 1.5), argnums=(0, 1, 2)
    )(logits, mu, logvar)
    for a, b_ in zip(g_fused, g_plain):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-6
        )


def test_block_rows_divides_batch():
    from multidisttorch_tpu.ops.pallas_elbo import _block_rows

    for batch in (1, 7, 96, 128, 10000):
        for dt in (jnp.float32, jnp.bfloat16):
            args = (
                jnp.zeros((batch, 784), dt),
                jnp.zeros((batch, 784), jnp.float32),
                jnp.zeros((batch, 20), dt),
                jnp.zeros((batch, 20), dt),
            )
            bb = _block_rows(*args)
            assert 1 <= bb <= batch and batch % bb == 0
    # bf16 operands halve the bytes per row -> at least as many rows
    # per grid step as f32 under the same VMEM budget.
    f32 = (jnp.zeros((10000, 784)), jnp.zeros((10000, 784)),
           jnp.zeros((10000, 20)), jnp.zeros((10000, 20)))
    b16 = tuple(a.astype(jnp.bfloat16) for a in f32[:1]) + (f32[1],) + tuple(
        a.astype(jnp.bfloat16) for a in f32[2:]
    )
    assert _block_rows(*b16) >= _block_rows(*f32)


def test_bf16_inputs_match_plain(arrays):
    # The TPU train path feeds bf16 activations (logits/mu/logvar) with
    # f32 targets; the first real-TPU bench run crashed on exactly this
    # mix ("Invalid dtype for `swap`: f32 ref, bf16 value"). The kernel
    # must accept mixed dtypes, reduce in f32, and hand back cotangents
    # in each primal's own dtype.
    logits, x, mu, logvar = arrays
    lb, mb, vb = (a.astype(jnp.bfloat16) for a in (logits, mu, logvar))

    fused = float(fused_elbo_loss_sum(lb, x, mb, vb, 1.0))
    plain = float(
        elbo_loss_sum(
            lb.astype(jnp.float32), x,
            mb.astype(jnp.float32), vb.astype(jnp.float32), 1.0,
        )
    )
    assert fused == pytest.approx(plain, rel=1e-5)

    g_fused = jax.grad(
        lambda l, m, lv: fused_elbo_loss_sum(l, x, m, lv, 1.0),
        argnums=(0, 1, 2),
    )(lb, mb, vb)
    g_plain = jax.grad(
        lambda l, m, lv: elbo_loss_sum(l, x, m, lv, 1.0), argnums=(0, 1, 2)
    )(logits, mu, logvar)
    for got, ref, primal in zip(g_fused, g_plain, (lb, mb, vb)):
        assert got.dtype == primal.dtype
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(ref),
            rtol=2e-2, atol=2e-2,  # bf16 storage precision
        )


def test_bf16_multi_block_accumulator(monkeypatch):
    # The round-4 hardware failure ("Invalid dtype for `swap`: Ref
    # float32 vs value bfloat16", a TPU run of 2026-07-30)
    # lived in the fwd kernel's SMEM accumulator when bf16 operands
    # crossed a multi-block grid — the one path the earlier bf16 test
    # (single block) and multi-block test (f32) each missed. Interpret
    # mode can't reproduce Mosaic's swap dtype check, so this pins the
    # code-level contract instead: bf16 inputs + shrunken VMEM budget
    # force the grid>1 accumulate store, and values must still match the
    # plain path (the explicit .astype(out_ref.dtype) casts keep the
    # stored dtype equal to the ref dtype by construction — the same
    # program Mosaic compiles; test_lowers_for_tpu below runs the
    # lowering's own check, chip_smoke.py the hardware proof).
    from multidisttorch_tpu.ops import pallas_elbo

    monkeypatch.setattr(pallas_elbo, "_VMEM_BUDGET_BYTES", 64 * 1024)
    rng = np.random.default_rng(11)
    b, d, lat = 96, 784, 20
    logits = jnp.asarray(rng.normal(0, 2, (b, d)), jnp.bfloat16)
    x = jnp.asarray(rng.uniform(0, 1, (b, d)).astype(np.float32))
    mu = jnp.asarray(rng.normal(0, 1, (b, lat)), jnp.bfloat16)
    logvar = jnp.asarray(rng.normal(0, 0.5, (b, lat)), jnp.bfloat16)
    assert pallas_elbo._block_rows(logits, x, mu, logvar) < b  # grid > 1

    fused = float(fused_elbo_loss_sum(logits, x, mu, logvar, 1.0))
    plain = float(
        elbo_loss_sum(
            logits.astype(jnp.float32), x,
            mu.astype(jnp.float32), logvar.astype(jnp.float32), 1.0,
        )
    )
    assert fused == pytest.approx(plain, rel=1e-5)

    g_fused = jax.grad(
        lambda l, m, lv: fused_elbo_loss_sum(l, x, m, lv, 1.0),
        argnums=(0, 1, 2),
    )(logits, mu, logvar)
    for got, primal in zip(g_fused, (logits, mu, logvar)):
        # cotangents come back at each primal's own storage dtype
        assert got.dtype == primal.dtype
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


def test_works_under_jit_and_scaling(arrays):
    logits, x, mu, logvar = arrays

    @jax.jit
    def f(l):
        return fused_elbo_loss_sum(l, x, mu, logvar, 1.0) * 2.0

    expected = 2.0 * float(elbo_loss_sum(logits, x, mu, logvar, 1.0))
    assert float(f(logits)) == pytest.approx(expected, rel=1e-5)
    # cotangent scaling flows through the custom VJP
    g = jax.grad(f)(logits)
    g_ref = jax.grad(lambda l: 2.0 * elbo_loss_sum(l, x, mu, logvar, 1.0))(logits)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=1e-5, atol=1e-6
    )


def test_fused_loss_in_train_step_matches_plain():
    # The use_fused_loss train-step path must train identically.
    import optax

    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.steps import (
        create_train_state,
        make_train_step,
    )

    model = VAE(hidden_dim=16, latent_dim=4)
    tx = optax.adam(1e-3)
    trial = setup_groups(8)[0]
    batch = jnp.asarray(
        np.random.default_rng(5).uniform(0, 1, (8, 784)).astype(np.float32)
    )
    key = jax.random.key(0)
    s1 = create_train_state(trial, model, tx, jax.random.key(1))
    s2 = create_train_state(trial, model, tx, jax.random.key(1))
    s1, m1 = make_train_step(trial, model, tx)(s1, batch, key)
    s2, m2 = make_train_step(trial, model, tx, use_fused_loss=True)(
        s2, batch, key
    )
    assert float(m1["loss_sum"]) == pytest.approx(
        float(m2["loss_sum"]), rel=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s1.params,
        s2.params,
    )


def test_fused_loss_sharded_submesh_matches_plain():
    # Multi-device submesh: the fused loss runs per-shard under
    # shard_map + psum; training must match the plain path.
    import optax

    from multidisttorch_tpu.models.vae import VAE
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.steps import (
        create_train_state,
        make_train_step,
    )

    model = VAE(hidden_dim=16, latent_dim=4)
    tx = optax.adam(1e-3)
    trial = setup_groups(2)[0]  # 4 devices
    batch = jnp.asarray(
        np.random.default_rng(6).uniform(0, 1, (16, 784)).astype(np.float32)
    )
    key = jax.random.key(0)
    s1 = create_train_state(trial, model, tx, jax.random.key(1))
    s2 = create_train_state(trial, model, tx, jax.random.key(1))
    s1, m1 = make_train_step(trial, model, tx)(s1, batch, key)
    s2, m2 = make_train_step(trial, model, tx, use_fused_loss=True)(
        s2, batch, key
    )
    assert float(m1["loss_sum"]) == pytest.approx(
        float(m2["loss_sum"]), rel=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s1.params,
        s2.params,
    )


def _lower_for_tpu(fn, *avals):
    """Lower ``fn`` for the TPU from this CPU process, interpret mode
    off: the Pallas TPU lowering (block-shape rules, ref/value dtype
    checks on every store) runs without a chip."""
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [128, 4096])  # flagship; multi-block
def test_lowers_for_tpu(monkeypatch, batch, dtype):
    # The callers' shapes (chip_smoke.py phase 6): activations at the
    # train dtype, f32 targets. Would have caught the round-4 swap dtype
    # error; a row block that is not a whole number of sublane tiles
    # fails here too.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    wide = jax.ShapeDtypeStruct((batch, 784), dtype)
    x = jax.ShapeDtypeStruct((batch, 784), jnp.float32)
    narrow = jax.ShapeDtypeStruct((batch, 20), dtype)
    loss = lambda l, x, m, lv: fused_elbo_loss_sum(l, x, m, lv, 1.0)
    _lower_for_tpu(loss, wide, x, narrow, narrow)
    _lower_for_tpu(
        jax.grad(loss, argnums=(0, 2, 3)), wide, x, narrow, narrow
    )


def test_block_rows_are_whole_sublane_tiles():
    from multidisttorch_tpu.ops.pallas_elbo import _block_rows

    for batch in (250, 1000, 4096, 10000):
        for dt, tile in ((jnp.float32, 8), (jnp.bfloat16, 16)):
            bb = _block_rows(
                jnp.zeros((batch, 784), dt), jnp.zeros((batch, 784)),
                jnp.zeros((batch, 20), dt), jnp.zeros((batch, 20), dt),
            )
            assert bb == batch or bb % tile == 0, (batch, dt, bb)
