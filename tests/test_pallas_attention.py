"""Flash (blockwise Pallas) attention: value + gradient parity with the
dense reference (interpreter mode on CPU; same kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multidisttorch_tpu.ops.pallas_attention import (
    flash_attention,
    latent_attention,
    make_flash_attention,
)
from multidisttorch_tpu.ops.ring_attention import dense_attention_reference


_BLOCK = 128  # the smallest block edge: T = 2 * _BLOCK tiles when asked to


def _qkv(b=2, t=64, h=2, d=16, *, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (b, t, h, d)).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_value_parity_single_block(causal):
    q, k, v = _qkv(t=64)  # t < _BLOCK: one whole-sequence block
    out = flash_attention(q, k, v, causal=causal)
    ref = dense_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


@pytest.mark.parametrize("causal", [False, True])
def test_value_parity_multi_block(causal):
    # t = 2 * _BLOCK exercises the online-softmax carry across K blocks
    # and (causal) the skipped above-diagonal block.
    q, k, v = _qkv(t=2 * _BLOCK, h=1, d=8)
    out = flash_attention(q, k, v, causal=causal, block=_BLOCK)
    ref = dense_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


@pytest.mark.parametrize("causal", [False, True])
def test_gradient_parity(causal):
    q, k, v = _qkv(t=2 * _BLOCK, h=1, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block=_BLOCK) ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(
            dense_attention_reference(q, k, v, causal=causal) ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-6
        )


@pytest.mark.parametrize(
    "dtype, tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"]
)
@pytest.mark.parametrize(
    "shape, block",
    [
        # two heads of 64 a lane block, read from the (B, T, H*D) array
        ((2, 256, 4, 64), None),  # T is one block: 256, larger than 128
        ((2, 256, 4, 64), 128),  # two blocks: the loop below the diagonal
        ((1, 256, 2, 128), None),  # one head a lane block
        ((1, 128, 2, 64), None),  # the smallest tile, one step on the diagonal
    ],
    ids=["d64-one-block", "d64-two-blocks", "d128", "d64-t128"],
)
def test_packed_heads_match_dense(shape, block, dtype, tol):
    # The layout and the blocks the LM cells run (head width 64, blocks
    # chosen from T), at the tolerances chip_smoke.py holds the chip to.
    q, k, v = (a.astype(dtype) for a in _qkv(*shape, seed=5))
    w = _qkv(*shape, seed=6)[0]
    up = lambda a: a.astype(jnp.float32)

    def out_and_grads(attn, *qkv, **kw):
        loss = lambda q, k, v: jnp.sum(up(attn(q, k, v, causal=True, **kw)) * w)
        return attn(*qkv, causal=True, **kw), jax.grad(loss, (0, 1, 2))(*qkv)

    out, grads = out_and_grads(flash_attention, q, k, v, block=block)
    ref, ref_grads = out_and_grads(dense_attention_reference, up(q), up(k), up(v))
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    np.testing.assert_allclose(up(out), ref, rtol=tol, atol=tol)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(up(g), r, rtol=tol, atol=4 * tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_inside_remat_block_under_value_and_grad(dtype):
    # What the LM step does with it: the kernel inside the models'
    # rematerialised Block, differentiated, so the forward kernel runs
    # once (its output and logsumexp are saved for the recomputed
    # block) and the fused backward once, against the same block over
    # dense attention.
    from multidisttorch_tpu.models.decoder import remat_block
    from multidisttorch_tpu.models.transformer import Block

    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 256, 128)), dtype)
    mk = lambda cls, attn: cls(d_model=128, num_heads=2, attention=attn, dtype=dtype)
    dense = mk(Block, lambda q, k, v: dense_attention_reference(q, k, v, causal=True))
    flash = mk(remat_block(Block), make_flash_attention(causal=True))
    params = dense.init(jax.random.key(0), x)
    loss = lambda m: lambda p, x: jnp.sum(m.apply(p, x).astype(jnp.float32) ** 2)
    (val, (gp, gx)), (ref, (rp, rx)) = (
        jax.jit(jax.value_and_grad(loss(m), (0, 1)))(params, x) for m in (flash, dense)
    )
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(val, ref, rtol=tol)
    norm = lambda t: np.sqrt(sum(float(jnp.sum(a.astype(jnp.float32) ** 2)) for a in jax.tree.leaves(t)))
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), (gp, gx), (rp, rx))
    assert norm(diff) < tol * norm((rp, rx))


def test_odd_head_dim_and_seq():
    # Head dims off the VPU lane width (20) and non-128-divisible
    # sequences (96 -> one whole-sequence block) must still be exact.
    q, k, v = _qkv(b=1, t=96, h=2, d=20, seed=9)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )
    g = jax.grad(
        lambda q: jnp.sum(flash_attention(q, k, v, causal=True) ** 2)
    )(q)
    g_ref = jax.grad(
        lambda q: jnp.sum(
            dense_attention_reference(q, k, v, causal=True) ** 2
        )
    )(q)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=5e-5, atol=5e-6
    )


def test_large_nondivisible_causal_pads_exactly(monkeypatch):
    # A non-128-divisible T above the whole-block threshold must take
    # the pad-to-tile-edge path and stay exact, values AND gradients
    # (padded keys are causally unreachable; sliced rows carry zero
    # cotangent). Shrink the threshold so T=200 exercises it cheaply.
    import multidisttorch_tpu.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "_MAX_WHOLE_BLOCK", 64)
    q, k, v = _qkv(b=1, t=200, h=1, d=8, seed=3)
    out = flash_attention(q, k, v, causal=True)
    assert out.shape == q.shape
    ref = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)
    g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(dense_attention_reference), argnums=(0, 1, 2))(
        q, k, v
    )
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-6
        )


def test_large_nondivisible_noncausal_raises(monkeypatch):
    # Non-causal can't be padded exactly (appended keys WOULD be
    # attended); the documented contract is a clear error instead of a
    # VMEM blowup at Mosaic compile time (ADVICE r4).
    import multidisttorch_tpu.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "_MAX_WHOLE_BLOCK", 64)
    q, k, v = _qkv(b=1, t=200, h=1, d=8)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, k, v, causal=False)


def test_bf16_roundtrip():
    q, k, v = _qkv(t=64, dtype=np.float32)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref),
        rtol=3e-2, atol=3e-2,  # bf16 storage precision
    )
    # gradients flow and come back in the primal dtype
    g = jax.grad(
        lambda q: jnp.sum(
            flash_attention(q, kb, vb, causal=True).astype(jnp.float32) ** 2
        )
    )(qb)
    assert g.dtype == jnp.bfloat16 and bool(jnp.isfinite(g.astype(jnp.float32)).all())


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(causal):
    # The full composition: K/V ring over 8 devices, Pallas flash
    # kernel inside each hop, logsumexp combination across hops.
    from multidisttorch_tpu.ops.pallas_attention import (
        make_ring_flash_attention,
    )
    from multidisttorch_tpu.parallel.mesh import DATA_AXIS, setup_groups

    (trial,) = setup_groups(1)
    t = 16 * trial.size
    q, k, v = _qkv(b=2, t=t, h=2, d=8, seed=3)
    q, k, v = (
        jax.device_put(a, trial.sharding(None, DATA_AXIS))
        for a in (q, k, v)
    )
    out = make_ring_flash_attention(trial, causal=causal)(q, k, v)
    ref = dense_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_ring_flash_gradient_matches_dense():
    # Gradients flow through the hop combination into the kernel's VJP
    # — including the lse cotangent (the hop-weight term), which only
    # this path exercises.
    from multidisttorch_tpu.ops.pallas_attention import (
        make_ring_flash_attention,
    )
    from multidisttorch_tpu.parallel.mesh import DATA_AXIS, setup_groups

    (trial,) = setup_groups(1)
    t = 16 * trial.size
    q, k, v = _qkv(b=1, t=t, h=1, d=8, seed=4)
    sh = trial.sharding(None, DATA_AXIS)
    qs, ks, vs = (jax.device_put(a, sh) for a in (q, k, v))
    ring = make_ring_flash_attention(trial, causal=True)

    g_ring = jax.grad(
        lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), argnums=(0, 1, 2)
    )(qs, ks, vs)
    g_dense = jax.grad(
        lambda q, k, v: jnp.sum(
            dense_attention_reference(q, k, v, causal=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        )


def test_ring_flash_2d_sequence_x_head_parallel():
    # (data x model) mesh: flash-kernel hops with heads sharded over
    # the model axis — kernel grid rows shrink to BH/m per device.
    from multidisttorch_tpu.ops.pallas_attention import (
        make_ring_flash_attention,
    )
    from multidisttorch_tpu.parallel.mesh import setup_groups

    (trial,) = setup_groups(1, model_parallel=2)
    q, k, v = _qkv(b=2, t=16, h=4, d=8, seed=11)
    ring = make_ring_flash_attention(trial, causal=True)
    assert ring.head_sharded
    out = ring(q, k, v)
    ref = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )
    g = jax.grad(lambda q: jnp.sum(ring(q, k, v) ** 2))(q)
    g_ref = jax.grad(
        lambda q: jnp.sum(
            dense_attention_reference(q, k, v, causal=True) ** 2
        )
    )(q)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=5e-4, atol=5e-5
    )


def test_ring_flash_drives_sequence_parallel_lm():
    # End to end: the TransformerLM trains sequence-parallel with
    # ring-flash as its attention — loss decreases over steps.
    import optax

    from multidisttorch_tpu.models.transformer import TransformerLM
    from multidisttorch_tpu.ops.pallas_attention import (
        make_ring_flash_attention,
    )
    from multidisttorch_tpu.parallel.mesh import DATA_AXIS, setup_groups
    from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step

    (trial,) = setup_groups(1)
    t = 8 * trial.size
    model = TransformerLM(
        vocab_size=32, d_model=32, num_heads=2, num_layers=1, max_len=t,
        attention=make_ring_flash_attention(trial, causal=True),
    )
    tx = optax.adam(3e-3)
    state = create_lm_state(trial, model, tx, jax.random.key(0),
                            example_len=t)
    step = make_lm_train_step(trial, model, tx, sequence_parallel=True)
    tokens = jax.device_put(
        jnp.asarray(
            np.tile(np.arange(t) % 32, (2, 1)).astype(np.int32)
        ),
        trial.sharding(None, DATA_AXIS),
    )
    state, m0 = step(state, tokens)
    for _ in range(10):
        state, m = step(state, tokens)
    assert float(m["loss"]) < float(m0["loss"])


def test_drives_transformer_lm():
    # The kernel is the TransformerLM's single-chip attention: one real
    # optimizer step decreases the loss and matches the dense-attention
    # model's loss on identical params.
    import optax

    from multidisttorch_tpu.models.transformer import TransformerLM
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step

    (trial,) = setup_groups(1)
    mk = lambda attn: TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2,
        max_len=64, attention=attn,
    )
    flash_model = mk(make_flash_attention(causal=True))
    dense_model = mk(None)
    tx = optax.adam(1e-3)
    state = create_lm_state(trial, flash_model, tx, jax.random.key(0),
                            example_len=64)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (8, 64), dtype=np.int32)
    )  # batch divisible by the trial's 8-device data axis

    step_flash = make_lm_train_step(trial, flash_model, tx)
    s1, m1 = step_flash(state, tokens)
    # identical params through the dense model -> same loss
    state_d = create_lm_state(trial, dense_model, tx, jax.random.key(0),
                              example_len=64)
    _, m2 = make_lm_train_step(trial, dense_model, tx)(state_d, tokens)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    # training continues and improves
    s2, m3 = step_flash(s1, tokens)
    assert float(m3["loss"]) < float(m1["loss"])


@pytest.mark.parametrize(
    "t, h, dk, dv, block, dtype, tol",
    [
        (256, 2, 192, 128, None, jnp.float32, 2e-5),  # latent attention's widths
        (256, 2, 192, 128, 128, jnp.float32, 2e-5),  # two blocks a sequence
        (256, 2, 192, 128, None, jnp.bfloat16, 3e-2),
        (128, 2, 256, 128, None, jnp.float32, 2e-5),  # already whole lanes: no padding
        (96, 3, 24, 16, None, jnp.float32, 2e-5),  # the flattened layout, q padded to 128
    ],
)
def test_wider_q_and_k_than_v_match_dense(t, h, dk, dv, block, dtype, tol):
    """q and k of one width, v of another (MLA: 128 + 64 beside 128):
    the kernels read q and k padded to whole lanes and v at its own
    width, the scores are divided by the root of the width q came with.
    Forward and every gradient against the dense path."""
    ks = jax.random.split(jax.random.key(7), 4)
    q, k = (jax.random.normal(x, (2, t, h, dk), jnp.float32).astype(dtype) for x in ks[:2])
    v = jax.random.normal(ks[2], (2, t, h, dv), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[3], (2, t, h, dv), jnp.float32)

    def run(attn):
        loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
        out = attn(q, k, v)
        return out, jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        out, grads = run(lambda q, k, v: flash_attention(q, k, v, causal=True, block=block))
        want, want_grads = run(lambda q, k, v: dense_attention_reference(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal=True))
    assert out.shape == (2, t, h, dv) and out.dtype == dtype
    rel = lambda a, b: float(
        jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
    assert rel(out, want) < tol
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape and rel(got, ref) < tol


def _latent_operands(b, t, h, dtype, seed=11):
    ks = jax.random.split(jax.random.key(seed), 5)
    mk = lambda k, *shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    return (mk(ks[0], b, t, h, 128), mk(ks[1], b, t, h, 64), mk(ks[2], b, t, h, 128),
            mk(ks[3], b, t, 64), mk(ks[4], b, t, h, 128))


def _assembled(q_nope, q_rope, k_nope, k_rope, v):
    """The ``(q, k, v)`` that latent attention's five operands stand
    for: the rotary key copied to every head."""
    every_head = jnp.broadcast_to(k_rope[:, :, None, :], q_rope.shape)
    return (jnp.concatenate([q_nope, q_rope], -1), jnp.concatenate([k_nope, every_head], -1), v)


@pytest.mark.parametrize(
    "t, h, block, causal, rotated, dtype, tol",
    [
        (128, 2, None, True, True, jnp.float32, 2e-5),  # one block a sequence
        (256, 2, 128, True, True, jnp.float32, 2e-5),  # two: the walk below the diagonal
        (256, 4, 128, True, True, jnp.float32, 2e-5),  # two pairs of heads: the key's gradient
        (256, 2, 128, True, False, jnp.float32, 2e-5),  # q's rotary part used as it comes
        (256, 2, 128, False, True, jnp.float32, 2e-5),
        (128, 2, None, True, True, jnp.bfloat16, 3e-2),
        (256, 2, 128, True, True, jnp.bfloat16, 3e-2),
    ],
)
def test_latent_operands_match_dense_on_the_assembled(t, h, block, causal, rotated, dtype, tol):
    """``latent_attention`` on the parts of q and k against the dense
    path on the assembled q and k (q's rotary part rotated as the model
    rotates it, where the kernels are asked to): the output and all
    five gradients, the rotary key's against the sum over the heads of
    the assembled k's rotary part."""
    from multidisttorch_tpu.models.decoder import rope_angles
    from multidisttorch_tpu.models.latent_moe import rope_interleaved

    parts = _latent_operands(2, t, h, dtype)
    w = jax.random.normal(jax.random.key(12), (2, t, h, 128), jnp.float32)
    positions, theta = jnp.arange(t), 1e4
    angle = rope_angles(positions, theta, 64)
    rotation = (jnp.cos(angle), jnp.sin(angle)) if rotated else None

    def assembled(q_nope, q_rope, k_nope, k_rope, v):
        if rotated:
            q_rope = rope_interleaved(q_rope, positions, theta)
        return tuple(x.astype(jnp.float32) for x in _assembled(q_nope, q_rope, k_nope, k_rope, v))

    def dense(*parts):
        return dense_attention_reference(*assembled(*parts), causal=causal)

    def run(attn):
        loss = lambda *x: jnp.sum(attn(*x).astype(jnp.float32) * w)
        return attn(*parts), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*parts)

    with jax.default_matmul_precision("highest"):
        out, grads = run(
            lambda *x: latent_attention(*x, q_rotation=rotation, causal=causal, block=block)
        )
        want, want_grads = run(dense)
    assert out.shape == (2, t, h, 128) and out.dtype == dtype
    rel = lambda a, b: float(
        jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
        / jnp.linalg.norm(b.astype(jnp.float32)))
    assert rel(out, want) < tol
    for got, ref, part in zip(grads, want_grads, parts, strict=True):
        assert got.shape == part.shape and got.dtype == dtype and rel(got, ref) < tol
    # the same through the assembled k: d k_rope is the heads' sum
    dk = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention_reference(q, k, v, causal=causal) * w), argnums=1
    )(*assembled(*parts))
    assert rel(grads[3], dk[..., 128:].sum(axis=2)) < tol


@pytest.mark.parametrize(
    "h, nope, rope, dv",
    [(3, 128, 64, 128), (2, 128, 32, 128), (2, 96, 64, 128), (2, 128, 64, 64)],
    ids=["odd-heads", "rope-32", "nope-96", "v-64"],
)
def test_latent_operands_that_do_not_tile_are_refused(h, nope, rope, dv):
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match="do not tile"):
        latent_attention(
            z(1, 128, h, nope), z(1, 128, h, rope), z(1, 128, h, nope), z(1, 128, rope),
            z(1, 128, h, dv),
        )


def test_latent_operands_lower_for_tpu(monkeypatch):
    # the cell moe-mla-t4096's attention as its block hands it over: 4 x
    # 4,096, 32 heads, the parts of q and k apart, q's rotary part
    # rotated in the kernels; forward and backward, interpret mode off.
    # Nothing with a 192- or 256-wide head is made around the kernels,
    # and no copy of the rotary key a head.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    part = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    parts = (part(4, 4096, 32, 128), part(4, 4096, 32, 64), part(4, 4096, 32, 128),
             part(4, 4096, 64), part(4, 4096, 32, 128))
    angles = jnp.zeros((4096, 32), jnp.float32)
    rotation = jnp.cos(angles), jnp.sin(angles)
    fwd = lambda *x: latent_attention(*x, q_rotation=rotation, causal=True)
    bwd = jax.grad(lambda *x: fwd(*x).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))
    for fn, calls in ((fwd, 1), (bwd, 2)):
        text = jax.jit(fn).trace(*parts).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("stablehlo.custom_call @tpu_custom_call") == calls
        assert "32x4096x4096" not in text  # no (B, H, T, T) scores outside the kernel
        for head in ("32x192x", "32x256x", "x6144x", "x8192x"):
            assert head not in text, head
        # the rotary key goes in once, side by side for a pair of heads
        assert "tensor<4x4096x128xbf16>" in text


def test_latent_attention_widths_lower_for_tpu(monkeypatch):
    # the cell moe-mla-t4096's attention: 4 x 4,096, 32 heads, q and k
    # 192 wide, v 128; forward and backward, interpret mode off
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    qk = jax.ShapeDtypeStruct((4, 4096, 32, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16)
    fwd = lambda q, k, v: flash_attention(q, k, v, causal=True)
    bwd = jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )
    for fn in (fwd, bwd):
        text = jax.jit(fn).trace(qk, qk, v).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text
        assert "32x4096x4096" not in text  # no (B, H, T, T) scores outside the kernel


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "shape",
    [
        (16, 1024, 16, 64),  # lm-dense: one 1,024 block a sequence
        (64, 256, 16, 64),  # lm-short-t256
        (16, 512, 8, 64),  # the LM bench shape
        (2, 2048, 8, 64),  # T > 1024, tiled
        (2, 1100, 4, 64),  # causal pad to 1152
        (2, 200, 4, 64),  # one whole-sequence block, B*H > 1
    ],
)
def test_lowers_for_tpu(monkeypatch, shape, dtype):
    # Lowered for the TPU from this CPU process with interpret mode
    # off, forward and backward: the check a builder without a chip can
    # run. The (1, bq) logsumexp block over a (B*H, T) array failed it
    # for every B*H > 1 until the rows moved to a (B*H, 1, T) layout.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    qkv = [jax.ShapeDtypeStruct(shape, dtype)] * 3
    fwd = lambda q, k, v: flash_attention(q, k, v, causal=True)
    bwd = jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )
    for fn in (fwd, bwd):
        jax.jit(fn).trace(*qkv).lower(lowering_platforms=("tpu",))


def test_ring_flash_lowers_for_tpu(monkeypatch):
    from multidisttorch_tpu.ops.pallas_attention import (
        make_ring_flash_attention,
    )
    from multidisttorch_tpu.parallel.mesh import setup_groups

    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    (trial,) = setup_groups(1, devices=jax.devices()[:4])
    ring = make_ring_flash_attention(trial, causal=True)
    qkv = [jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.float32)] * 3
    bwd = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), (0, 1, 2))
    for fn in (ring, bwd):
        jax.jit(fn).trace(*qkv).lower(lowering_platforms=("tpu",))


@pytest.mark.slow  # loads libtpu (~5 s) and is not part of tier-1
def test_kernels_compile_for_a_v5e_topology(monkeypatch):
    # One step past lowering, still without a chip: libtpu's compile-only
    # client takes the programs through the real TPU compiler, Mosaic
    # included, for a described v5e host (VMEM limits and all).
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from multidisttorch_tpu.ops.pallas_elbo import fused_elbo_loss_sum

    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    on_chip = SingleDeviceSharding(topo.devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=on_chip)
    attn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    grad = jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    # the LM bench shape and the benchmark's two cells; chip_smoke.py
    # runs them under "highest", which Mosaic takes for f32 operands only
    for shape in [(16, 512, 8, 64), (16, 1024, 16, 64), (64, 256, 16, 64)]:
        qkv = [aval(shape, jnp.bfloat16)] * 3
        with jax.default_matmul_precision("highest"):
            jax.jit(attn).lower(*qkv).compile()
            jax.jit(grad).lower(*qkv).compile()
    # moe-mla-t4096: latent attention's five operands
    parts = [aval((4, 4096, 32, w), jnp.bfloat16) for w in (128, 64, 128)]
    parts += [aval((4, 4096, 64), jnp.bfloat16), aval((4, 4096, 32, 128), jnp.bfloat16)]
    unit = jnp.ones((4096, 32), jnp.float32)
    latent = lambda *x: latent_attention(*x, q_rotation=(unit, unit), causal=True)
    jax.jit(latent).lower(*parts).compile()
    jax.jit(
        jax.grad(lambda *x: latent(*x).astype(jnp.float32).sum(), (0, 1, 2, 3, 4))
    ).lower(*parts).compile()
    elbo_args = (
        aval((4096, 784), jnp.bfloat16), aval((4096, 784), jnp.float32),
        aval((4096, 20), jnp.bfloat16), aval((4096, 20), jnp.bfloat16),
    )
    loss = lambda l, x, m, lv: fused_elbo_loss_sum(l, x, m, lv, 1.0)
    jax.jit(loss).lower(*elbo_args).compile()
    jax.jit(jax.grad(loss, (0, 2, 3))).lower(*elbo_args).compile()
