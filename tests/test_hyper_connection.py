"""Hyper-connections (``ops/hyper_connection.py``) and the
``LatentMoELM`` that runs its blocks behind them, with YaRN's rotary
scaling, against the benchmark's plain float32 reference of the
``xing4.0-29b-a4b`` configuration, on the CPU at a tiny size.

The reference is ``benchmark/configs/xing4.0-29b-a4b.reference.py``,
which imports nothing of the program; the weights reach it through
``benchmark/entries/hc_moe_lm_trial.py::reference_weights``, the
renaming the chip run's comparison uses. Everything here is float32 at
``default_matmul_precision("highest")``, seeded, and counts or compares
numbers; nothing is timed.
"""

import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.entries import hc_moe_lm_trial, moe_lm_trial
from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.models.latent_moe import (
    LatentMoEBlock, LatentMoELM, YarnScaling,
)
from multidisttorch_tpu.ops import hyper_connection
from multidisttorch_tpu.ops.moe import RoutedExperts
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step
from multidisttorch_tpu.train.steps import TrainState

REFERENCE = cells.load_module("benchmark/configs/xing4.0-29b-a4b.reference.py")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
# The configuration's keys at a toy size: 16 experts, 4 a token, one
# dense layer and two expert layers, four streams.
TINY = {
    "vocab_size": 64, "hidden_size": 32, "num_attention_heads": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0, "intermediate_size": 48,
    "router_width": 16, "experts_held": [0, 16], "num_experts_per_tok": 4,
    "moe_intermediate_size": 24, "n_shared_experts": 1, "routed_scaling_factor": 2.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 32, "rope_scaling": YARN,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "assumed": {"compute_dtype": "float32", "remat": False},
}


def _config(**changes):
    return {**TINY, **changes}


def _tokens(seed=1, b=2, t=16):
    return jax.random.randint(jax.random.key(seed), (b, t), 0, TINY["vocab_size"])


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("streams", [4, 2], ids=["n4", "n2"])
@pytest.mark.parametrize("held", [[0, 16], [4, 8]], ids=["all", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_agrees_with_the_reference(held, remat, streams):
    """Logits, loss, every gradient leaf (the connections' ten a
    sublayer among them), the experts chosen and both counters, through
    ``create_lm_state`` and ``make_lm_train_step`` as a trial runs them
    (the gradient is read back from one SGD(1.0) step), YaRN on."""
    config = _config(
        experts_held=held, hc_mult=streams,
        assumed={"compute_dtype": "float32", "remat": remat},
    )
    model = hc_moe_lm_trial.build_model(config)
    assert model.hc_mult == streams and model.rope_scaling.factor == 64
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    sgd = optax.sgd(1.0)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        state = create_lm_state(group, model, sgd, jax.random.key(0))
        params = jax.tree.map(jnp.copy, state.params)
        logits, chosen = jax.jit(
            lambda p, t: moe_lm_trial.chosen_experts(model, p, t, config)
        )(params, tokens)
        after, metrics = make_lm_train_step(group, model, sgd)(state, tokens)
        grads = jax.tree.map(jnp.subtract, params, after.params)
        ref_logits, ref_loss, ref_grads, routing = jax.jit(
            lambda w, t: REFERENCE.logits_loss_grads(w, t, config)
        )(hc_moe_lm_trial.reference_weights(params, config), tokens)

    assert _rel(logits, ref_logits) < 1e-5
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 1e-5 * float(ref_loss)
    np.testing.assert_array_equal(jnp.sort(chosen, -1), jnp.sort(routing["chosen"], -1))
    np.testing.assert_array_equal(metrics["expert_counts"], routing["expert_counts"])
    # every Hres made at the seeded size is doubly stochastic after 20
    # iterations, and the program's figure is the reference's
    assert float(metrics["hc_marginal_err"]) < 1e-4
    assert abs(float(metrics["hc_marginal_err"]) - float(routing["hc_marginal_err"])) < 2e-6
    got = hc_moe_lm_trial.reference_weights(grads, config)
    flat_want = jax.tree_util.tree_leaves_with_path(ref_grads)
    assert sum("hc_" in jax.tree_util.keystr(p) for p, _ in flat_want) == 3 * 2 * 10
    for (path, want), have in zip(flat_want, jax.tree.leaves(got), strict=True):
        name = jax.tree_util.keystr(path)
        if "score_bias" in name:  # chooses, never weighs: no gradient
            assert not jnp.any(want) and not jnp.any(have), name
        else:
            # read back as a difference of parameters: a gate or a norm's
            # scale (value 1) keeps its gradient to float32's step at 1
            quantum = 1.2e-7 * math.sqrt(want.size)
            assert float(jnp.linalg.norm(have - want)) < 5e-4 * float(
                jnp.linalg.norm(want)) + quantum, (name, _rel(have, want))


def _lowered_step(model):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tx = optax.adam(1e-3)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((2, 16), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    return make_lm_train_step(group, model, tx).lower(
        state, jax.ShapeDtypeStruct((2, 16), jnp.int32)
    ), params


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_without_streams_and_scaling_the_step_is_the_one_before_them(remat):
    """The guard of ``moe-mla-t4096``: with ``hc_mult`` and
    ``rope_scaling`` left alone the parameter tree has no connection in
    it, the fields' defaults change nothing, and the lowered step is,
    character for character, the one ``LatentMoELM`` lowered to before
    it knew of either (its SHA-256, taken from the parent commit's
    checkout, is in ``tests/fixtures/latent_moe_step.sha256``; a PR
    that changes that model on purpose records the new one: ``python -c
    "import tests.test_hyper_connection as t; t.record_step_digests()"``).
    The digest is of the text less the numbers jax appends to its
    private functions' names (``@_where_151``): they count the trace's
    equations, ``checkpoint_name``s among them, which lower to nothing,
    so a value named for ``remat_block`` leaves the ``plain`` digest
    as it was."""
    model = LatentMoELM(vocab_size=64, remat=remat, max_len=16)
    lowered, params = _lowered_step(model)
    text = lowered.as_text()
    assert "hc_" not in " ".join(jax.tree_util.keystr(p) for p, _ in
                                 jax.tree_util.tree_leaves_with_path(params))
    explicit = model.clone(hc_mult=1, rope_scaling=None)
    assert _lowered_step(explicit)[0].as_text() == text
    with open(os.path.join(FIXTURES, "latent_moe_step.sha256")) as f:
        recorded = dict(line.split() for line in f if line.strip())
    assert _digest(text) == recorded["remat" if remat else "plain"]


def _digest(text):
    return hashlib.sha256(re.sub(r"@(\w+?)_\d+\b", r"@\1", text).encode()).hexdigest()


def record_step_digests():
    with open(os.path.join(FIXTURES, "latent_moe_step.sha256"), "w") as f:
        for name, remat in (("plain", False), ("remat", True)):
            text = _lowered_step(LatentMoELM(vocab_size=64, remat=remat, max_len=16))[0].as_text()
            f.write(f"{name} {_digest(text)}\n")


def test_default_logits_ignore_the_new_fields():
    """The same weights give the same logits whether the fields are
    absent or spelled out at their defaults."""
    tokens = _tokens()
    model = LatentMoELM(vocab_size=64)
    params = model.init({"params": jax.random.key(0)}, tokens)["params"]
    want, _ = model.apply({"params": params}, tokens)
    got, counters = model.clone(hc_mult=1, rope_scaling=None).apply({"params": params}, tokens)
    np.testing.assert_array_equal(got, want)
    assert set(counters) == {"expert_counts"}


def _reference_config(**changes):
    return {"hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
            "mhc_h_res_clamp_max": 30, **changes}


def test_sinkhorn_beyond_the_clamp_is_finite_and_the_reference_s():
    """Logits far past the clamp at both ends (``exp`` of them would be
    0 and inf): the program's tiles-in-front layout against the
    reference's loop over ``(N, n, n)``."""
    n, tokens = 4, 256
    logits = 40.0 * jax.random.normal(jax.random.key(0), (tokens, n, n))
    logits = logits.at[0].set(1e4).at[1].set(-1e4).at[2, 0].set(1e4).at[3, :, 1].set(-1e4)
    want = REFERENCE.sinkhorn(logits, _reference_config(), unrolled=True)
    np.testing.assert_allclose(REFERENCE.sinkhorn(logits, _reference_config()), want, rtol=1e-5)
    got = hyper_connection.sinkhorn(
        logits.transpose(1, 2, 0).reshape(n, n, 2, 128), 20, 1e-6, (-30.0, 30.0)
    ).reshape(n, n, tokens).transpose(2, 0, 1)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-30)
    # the clamp is what made it finite
    assert not bool(jnp.all(jnp.isfinite(REFERENCE.sinkhorn(
        logits, _reference_config(mhc_h_res_clamp_min=-1e5, mhc_h_res_clamp_max=1e5)))))


@pytest.mark.parametrize("iters", [20, 3])
def test_sinkhorn_gradient_is_the_unrolled_loop_s(iters):
    """The gradient through the program's ``fori_loop`` against the
    gradient through the reference's Python loop, few iterations (far
    from converged) and all of them; the reference's own ``fori_loop``,
    which it runs at the cell's size, against the same."""
    n, tokens = 4, 128
    logits = 0.7 * jax.random.normal(jax.random.key(1), (tokens, n, n))
    co = jax.random.normal(jax.random.key(2), (tokens, n, n))
    config = _reference_config(hc_sinkhorn_iters=iters)
    want = jax.grad(lambda z: jnp.sum(REFERENCE.sinkhorn(z, config, unrolled=True) * co))(logits)
    looped = jax.grad(lambda z: jnp.sum(REFERENCE.sinkhorn(z, config) * co))(logits)
    assert _rel(looped, want) < 1e-6

    def program(z):
        tiled = z.transpose(1, 2, 0).reshape(n, n, 1, tokens)
        out = hyper_connection.sinkhorn(tiled, iters, 1e-6, (-30.0, 30.0))
        return jnp.sum(out.reshape(n, n, tokens).transpose(2, 0, 1) * co)

    got = jax.grad(program)(logits)
    assert _rel(got, want) < 1e-5
    res = REFERENCE.sinkhorn(logits, config)
    err = float(REFERENCE.marginal_err(res))
    assert err < 1e-4 if iters == 20 else err > 1e-4


def test_maps_and_mixes_are_the_reference_s():
    """One connection alone: the three maps of :class:`HyperConnection`,
    ``read`` and ``write`` against the reference's ``connection_maps``
    and its two einsums, and the counter against ``marginal_err``."""
    n, b, t, d = 4, 2, 64, 32
    streams = tuple(jax.random.normal(k, (b, t, d)) for k in jax.random.split(jax.random.key(3), n))
    y = jax.random.normal(jax.random.key(4), (b, t, d))
    module = hyper_connection.HyperConnection()
    with jax.default_matmul_precision("highest"):
        params = module.init(jax.random.key(5), streams)["params"]
        maps = module.apply({"params": params}, streams)
        x = jnp.stack(streams, axis=2)  # (B, T, n, d), as the reference holds them
        pre, post, res = REFERENCE.connection_maps(
            x.reshape(b * t, n, d), params, _reference_config(rms_norm_eps=1e-6))
    assert maps.pre.shape == (n, b, t) and maps.res.shape == (n, n, b, t)
    np.testing.assert_allclose(maps.pre.reshape(n, -1).T, pre, rtol=2e-5)
    np.testing.assert_allclose(maps.post.reshape(n, -1).T, post, rtol=2e-5)
    np.testing.assert_allclose(maps.res.reshape(n, n, -1).transpose(2, 0, 1), res, rtol=2e-4)
    assert float(jnp.std(res[:, 0, 0])) > 0.02  # the maps differ from token to token
    assert abs(float(maps.marginal_err) - float(REFERENCE.marginal_err(res))) < 2e-6
    assert float(maps.marginal_err) < 1e-4
    want_u = jnp.einsum("nj,njd->nd", pre, x.reshape(b * t, n, d)).reshape(b, t, d)
    assert _rel(hyper_connection.read(maps, streams), want_u) < 1e-5
    want = jnp.einsum("nij,njd->nid", res, x.reshape(b * t, n, d)).reshape(b, t, n, d) \
        + post.reshape(b, t, n, 1) * y[:, :, None, :]
    got = jnp.stack(hyper_connection.write(maps, streams, y), axis=2)
    assert _rel(got, want) < 1e-5
    assert _rel(hyper_connection.merge(streams), jnp.sum(x, axis=2)) < 1e-6


def _block(held, shared=1):
    return LatentMoEBlock(
        num_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, rope_theta=10000.0, hidden_dim=24, num_experts=16, experts_held=held,
        top_k=4, shared_experts=shared, routed_scaling=2.0, hc_mult=4,
        rope_scaling=hc_moe_lm_trial.build_model(_config()).rope_scaling,
    )


def test_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares, one whole layer behind its two
    hyper-connections: every share computes the attention sublayer, both
    connections' maps and the shared expert alike; the shares' routed
    parts summed, with the shared expert and both connections counted
    once, written back through ``Hpost`` and beside ``Hres X``, are the
    uncut reference's layer."""
    n, b, t, d = 4, 2, 16, 32
    streams = tuple(jax.random.normal(k, (b, t, d)) for k in jax.random.split(jax.random.key(6), n))
    config = _config()
    captured = lambda mdl, _: isinstance(mdl, (RoutedExperts, hyper_connection.HyperConnection)) \
        or mdl.name == "shared_down"
    with jax.default_matmul_precision("highest"):
        whole = _block((0, 16)).init(jax.random.key(7), streams)["params"]
        parts = []
        for first in range(0, 16, 4):
            cut = {**whole, "moe": {
                **whole["moe"],
                **{k: whole["moe"][k][first:first + 4] for k in ("w_gate", "w_up", "w_down")},
            }}
            (out, counts, _), state = _block((first, 4)).apply(
                {"params": cut}, streams, capture_intermediates=captured)
            got = state["intermediates"]
            parts.append({
                "out": out, "counts": counts, "y": got["moe"]["__call__"][0][0],
                "shared": got["moe"]["shared_down"]["__call__"][0].reshape(b, t, d),
                "attn_maps": got["hc_attn"]["__call__"][0], "maps": got["hc_mlp"]["__call__"][0],
            })
        # what every chip computes alike is alike
        for part in parts[1:]:
            for name in ("attn_maps", "maps", "shared"):
                for a, b_ in zip(jax.tree.leaves(part[name]), jax.tree.leaves(parts[0][name])):
                    np.testing.assert_array_equal(a, b_)
        first = parts[0]
        routed = sum(p["y"].reshape(b, t, d) - p["shared"] for p in parts)
        y = routed + first["shared"]  # the shared expert once
        # a share's layer is Hres X + Hpost^T y_share: put the whole y in y_share's place
        delta = y - first["y"].reshape(b, t, d)
        total = jnp.stack(
            [out + post[..., None] * delta for out, post in zip(first["out"], first["maps"].post)],
            axis=2,
        )
        ref_w = hc_moe_lm_trial.reference_weights({"block_0": whole, **_ends()}, _config(
            num_hidden_layers=1, first_k_dense_replace=0))["blocks"][0]
        want, (_, want_counts), _ = REFERENCE.block(jnp.stack(streams, axis=2), ref_w, config)
    assert _rel(total, want) < 1e-5
    np.testing.assert_array_equal(jnp.concatenate([p["counts"] for p in parts]), want_counts)
    assert int(want_counts.sum()) == b * t * 4  # every choice of every token, once


def _ends():
    """The leaves ``reference_weights`` reads outside the blocks."""
    leaf = jnp.zeros((1,))
    return {"tok_embed": {"embedding": leaf}, "ln_out": {"scale": leaf}, "head": {"kernel": leaf}}


def test_yarn_by_hand_at_factor_64():
    """The configuration's numbers worked by hand: theta 10,000, a
    64-wide rotary part, 4,096 original positions, ``beta_fast`` 32,
    ``beta_slow`` 1, ``mscale`` = ``mscale_all_dim`` = 1."""
    yarn = YarnScaling(factor=64, original_max_position=4096, beta_fast=32, beta_slow=1,
                       mscale=1, mscale_all_dim=1)
    # 64 ln(4096 / (2 pi 32)) / (2 ln 10000) = 10.47 and, at beta 1, 22.51
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))) == 23
    got = yarn.inv_freq(10000.0, 64)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-12)  # up to low: untouched
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-12)  # from high: divided
    ramp = (16 - 10) / (23 - 10)  # pair 16 lies between
    np.testing.assert_allclose(got[16], plain[16] * (ramp / 64 + 1 - ramp), rtol=1e-12)
    assert np.all(np.diff(got) < 0)
    assert abs(yarn.score_scale - (0.1 * math.log(64) + 1) ** 2) < 1e-12
    assert abs(yarn.score_scale - 2.0047) < 1e-4 and yarn.rotation_scale == 1.0
    # mscale_all_dim 0 (the family's other convention): the scores keep
    # their scale and cos and sin carry it
    other = YarnScaling(factor=64, original_max_position=4096)
    assert other.score_scale == 1.0 and abs(other.rotation_scale - 1.41589) < 1e-5
    # the reference works the same numbers out on its own
    np.testing.assert_allclose(REFERENCE.yarn_inv_freq(10000.0, 64, YARN), got, rtol=1e-12)
    angle = decoder.rope_angles(jnp.arange(4096), 10000.0, 64, yarn)
    np.testing.assert_allclose(angle[4095, 31], 4095 * plain[31] / 64, rtol=1e-6)


def test_no_rope_scaling_gives_the_angles_of_before_bit_for_bit():
    positions = jnp.arange(4096)
    for theta, width in ((10000.0, 8), (32000000.0, 64)):
        before = positions.astype(jnp.float32)[:, None] * (
            theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width))[None, :]
        np.testing.assert_array_equal(decoder.rope_angles(positions, theta, width), before)
        np.testing.assert_array_equal(decoder.rope_angles(positions, theta, width, None), before)


def test_bf16_step_trains_and_counts():
    """The trial path at the cell's dtypes: the loss falls and both
    counters come out beside it."""
    model = LatentMoELM(vocab_size=64, dtype=jnp.bfloat16, remat=True, experts_held=(2, 4),
                        hc_mult=4, rope_scaling=YarnScaling(64, 4096, mscale_all_dim=1.0))
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tx = optax.adam(1e-2)
    state = create_lm_state(group, model, tx, jax.random.key(0))
    step = make_lm_train_step(group, model, tx)
    tokens = group.device_put(_tokens(t=32), group.batch_sharding)
    losses = []
    for _ in range(8):
        state, metrics = step(state, tokens)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert metrics["expert_counts"].shape == (2, 4)
    assert metrics["hc_marginal_err"].shape == () and metrics["hc_marginal_err"].dtype == jnp.float32
    assert isinstance(state, TrainState)


def test_projection_of_bf16_streams_is_the_float32_product():
    """``project_bf16`` (three bf16 parts of the weights side by side,
    one pass) against the float32 product at ``HIGHEST`` of the same
    bf16 ``x``: values and the weights' gradient to float32's last bits,
    ``x``'s gradient to bf16's."""
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (256, 512), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (512, 24), jnp.float32) / 22.0
    co = jax.random.normal(ks[2], (256, 24), jnp.float32)
    exact = lambda x, w: jnp.dot(x.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST)
    loss = lambda dot: lambda x, w: jnp.sum(dot(x, w) * co)
    assert _rel(hyper_connection.project_bf16(x, w), exact(x, w)) < 1e-6
    assert hyper_connection._project(x, w).dtype == jnp.float32
    dx, dw = jax.grad(loss(hyper_connection.project_bf16), argnums=(0, 1))(x, w)
    want_dx, want_dw = jax.grad(loss(exact), argnums=(0, 1))(x, w)
    assert _rel(dw, want_dw) < 1e-6
    assert dx.dtype == jnp.bfloat16
    assert _rel(dx.astype(jnp.float32), want_dx.astype(jnp.float32)) < 4e-3
    # float32 streams take XLA's own product
    np.testing.assert_array_equal(
        hyper_connection._project(x.astype(jnp.float32), w), exact(x, w))
