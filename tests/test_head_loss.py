"""The head and the loss as one walk (``ops/head_loss.py``) against the
definition it replaces in a one-device training step:
``train/lm.py::lm_loss_mean`` differentiated through a plain float32
head. Values on the CPU; nothing is timed."""

import hashlib
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from multidisttorch_tpu.models.grouped_window_moe import GroupedWindowMoELM
from multidisttorch_tpu.models.latent_moe import LatentMoELM
from multidisttorch_tpu.models.ssm_hybrid import SambaYLM
from multidisttorch_tpu.models.transformer import MoETransformerLM, TransformerLM
from multidisttorch_tpu.ops import head_loss
from multidisttorch_tpu.ops.head_loss import lm_head_loss, lm_head_loss_weighted, num_blocks
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, lm_loss_mean, make_lm_train_step

B, T, D, V = 4, 16, 24, 257  # 257: no multiple of 128, nor of 8
LOGITS_BYTES = B * T * V * 4
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "sharded_lm_step.sha256")


@pytest.fixture
def blocks(request, monkeypatch):
    """The walk in ``request.param`` blocks of the ``(B x T, V)`` logits."""
    monkeypatch.setattr(head_loss, "LOGITS_BLOCK_BYTES", LOGITS_BYTES // request.param)
    assert num_blocks(B * T, V) == request.param
    return request.param


def _operands(kind, dtype=jnp.float32):
    rng = np.random.default_rng(3)
    hidden = jnp.asarray(rng.normal(0, 1, (B, T, D)), dtype)
    weights = jnp.asarray(rng.normal(0, 0.3, (V, D) if kind == "tied" else (D, V)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (V,)), jnp.float32) if kind == "bias" else None
    tokens = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    return hidden, weights, bias, tokens


def _defined(hidden, weights, bias, tokens, tied, operand=jnp.float32):
    """``lm_loss_mean`` through a plain head: float32 logits of the
    operands as rounded to ``operand``, every digit of the product kept."""
    rounded = lambda a: a.astype(operand).astype(jnp.float32)
    logits = jnp.einsum(
        "btd,vd->btv" if tied else "btd,dv->btv", rounded(hidden), rounded(weights),
        precision="highest",
    )
    return lm_loss_mean(logits if bias is None else logits + bias, tokens)


def _both(kind, cotangent=1.0, dtype=jnp.float32):
    hidden, weights, bias, tokens = _operands(kind, dtype)
    tied = kind == "tied"
    walk = jax.jit(jax.value_and_grad(
        lambda h, w, b: cotangent * lm_head_loss(h, w, b, tokens, dtype, tied), argnums=(0, 1, 2)
    ))(hidden, weights, bias)
    defined = jax.jit(jax.value_and_grad(
        lambda h, w, b: cotangent * _defined(h, w, b, tokens, tied, dtype), argnums=(0, 1, 2)
    ))(hidden, weights, bias)
    return walk, defined


@pytest.mark.parametrize("kind", ["bias", "no-bias", "tied"])
@pytest.mark.parametrize("blocks", [1, 2, 4], indirect=True)
def test_walk_is_lm_loss_mean_through_a_float32_head(blocks, kind):
    (loss, grads), (want, want_grads) = _both(kind)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert (grads[2] is None) == (kind != "bias")
    for got, wanted in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads), strict=True):
        assert got.shape == wanted.shape and got.dtype == wanted.dtype
        assert float(jnp.abs(wanted).max()) > 1e-4
        np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["bias", "tied"])
@pytest.mark.parametrize("blocks", [1, 2], indirect=True)
def test_the_loss_cotangent_scales_all_three(blocks, kind):
    (loss, grads), (want, want_grads) = _both(kind, cotangent=-2.5)
    (_, unit), _ = _both(kind)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    for got, one, wanted in zip(
        jax.tree.leaves(grads), jax.tree.leaves(unit), jax.tree.leaves(want_grads), strict=True
    ):
        np.testing.assert_allclose(got, -2.5 * one, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=2.5e-6)


@pytest.mark.parametrize("blocks", [1, 2], indirect=True)
def test_bf16_operands_are_the_products_roundings_and_no_other(blocks):
    """At a bf16 compute type the logits are the float32 product of
    the operands rounded to bf16 (what the TPU's default precision
    makes of a float32 head): the loss is that product's to float32
    rounding. The logits' gradient is rounded once more, to bf16, for
    the two products that read it: 2^-9 an element."""
    (loss, grads), (want, want_grads) = _both("bias", dtype=jnp.bfloat16)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    assert grads[0].dtype == jnp.bfloat16 and grads[1].dtype == grads[2].dtype == jnp.float32
    for got, wanted in zip(grads, want_grads, strict=True):
        scale = float(jnp.abs(wanted.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            got.astype(jnp.float32), wanted.astype(jnp.float32), atol=2**-7 * scale
        )


def _weighted_defined(hidden, weights, bias, tokens, tied, position_weights):
    """``(sum(w * CE) / positions, CE)`` through a plain float32 head,
    ``CE`` 0 at each sequence's last position."""
    logits = jnp.einsum(
        "btd,vd->btv" if tied else "btd,dv->btv", hidden, weights, precision="highest"
    )
    logp = jax.nn.log_softmax(logits if bias is None else logits + bias, axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0] * (jnp.arange(T) < T - 1)
    return jnp.sum(ce * position_weights) / (B * (T - 1)), ce


@pytest.mark.parametrize("kind", ["bias", "no-bias", "tied"])
@pytest.mark.parametrize("blocks", [1, 2, 4], indirect=True)
def test_weighted_walk_is_the_weighted_sum_through_a_float32_head(blocks, kind):
    """Per-position weights: the value, ``d hidden``, ``d head weights``,
    ``d bias`` and ``d w`` (``CE / positions``) are those of
    ``sum(w * CE) / positions`` differentiated through a float32 head,
    and the cross-entropies handed back are ``CE``; weights of 1 give
    the unweighted walk."""
    hidden, weights, bias, tokens = _operands(kind)
    tied = kind == "tied"
    position_weights = jnp.asarray(np.random.default_rng(4).uniform(0, 2, (B, T)), jnp.float32)

    def walk(h, w, b, pw):
        return lm_head_loss_weighted(h, w, b, tokens, jnp.float32, tied, pw)

    operands = (hidden, weights, bias, position_weights)
    loss, ce = jax.jit(walk)(*operands)
    grads = jax.jit(jax.grad(lambda *a: walk(*a)[0], argnums=(0, 1, 2, 3)))(*operands)
    (want, want_ce), want_grads = jax.value_and_grad(
        lambda h, w, b, pw: _weighted_defined(h, w, b, tokens, tied, pw),
        argnums=(0, 1, 2, 3), has_aux=True,
    )(hidden, weights, bias, position_weights)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(ce, want_ce, rtol=1e-5, atol=1e-6)
    assert (grads[2] is None) == (kind != "bias")
    for got, wanted in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads), strict=True):
        assert got.shape == wanted.shape and got.dtype == wanted.dtype
        assert float(jnp.abs(wanted).max()) > 1e-4
        np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=1e-6)
    # the cross-entropies are a reading: nothing flows back through them
    through_ce = jax.grad(lambda *a: jnp.sum(walk(*a)[1]), argnums=(0, 1, 3))(*operands)
    assert not any(jnp.any(g) for g in through_ce)
    unweighted = jax.jit(lambda h, w, b: lm_head_loss(h, w, b, tokens, jnp.float32, tied))
    np.testing.assert_allclose(
        walk(hidden, weights, bias, jnp.ones((B, T)))[0], unweighted(hidden, weights, bias),
        rtol=1e-6,
    )


def test_blocks_come_from_the_shapes():
    assert num_blocks(B * T, V) == 1  # a small array takes no loop
    assert head_loss.LOGITS_BLOCK_BYTES == 1 << 29
    # the six cells' heads: 16,384 positions a step (8,192 in moe-mhc-t4096)
    assert [num_blocks(16_384, v) for v in (50_257, 25_008, 18_992, 16_160)] == [8, 4, 4, 2]
    assert num_blocks(8_192, 16_384) == 1  # exactly the limit
    assert num_blocks(3 * 5 * 1_000, 50_257) == 6  # the fewest that divide the rows and fit
    assert num_blocks(7 * 1_000, 50_257) == 4


# --- the training step of each LM, on the walk and on the logits ---

_TINY = dict(vocab_size=V, max_len=T, remat=True)
LMS = {
    "dense": lambda: TransformerLM(d_model=32, num_heads=4, num_layers=2, **_TINY),
    "moe": lambda: MoETransformerLM(d_model=32, num_heads=4, num_layers=2, **_TINY),
    "latent": lambda: LatentMoELM(**_TINY),
    "grouped": lambda: GroupedWindowMoELM(**_TINY),
    "ssm-tied": lambda: SambaYLM(**_TINY),
}


def _logits_only(model):
    """A model that offers no hidden state, as a user's own module: the
    step asks it for logits and differentiates ``lm_loss_mean``."""
    return types.SimpleNamespace(apply=model.apply, init=model.init)


def _tokens():
    return jnp.asarray(np.random.default_rng(1).integers(0, V, (B, T)), jnp.int32)


def _one_step(group, model, state, tokens):
    step = make_lm_train_step(group, model, optax.sgd(1.0)).lower(state, tokens)
    # as tests/test_default_attention.py compares programs whose remat
    # structure differs: rounded where the program says so
    return step.compile(compiler_options={"xla_allow_excess_precision": False})(state, tokens)


@pytest.mark.parametrize("blocks", [1, 2], indirect=True)
@pytest.mark.parametrize("name", list(LMS))
def test_a_step_on_the_walk_is_the_step_on_the_logits(name, blocks):
    """One optimizer step (plain SGD at 1: the parameters move by the
    gradients) of each LM under remat: the walk's loss, counters and
    updated parameters are those of the parent's program."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model, tokens = LMS[name](), _tokens()
    fresh = lambda: create_lm_state(group, model, optax.sgd(1.0), jax.random.key(0))
    before = fresh().params
    state, metrics = _one_step(group, model, fresh(), tokens)
    want_state, want_metrics = _one_step(group, _logits_only(model), fresh(), tokens)
    assert set(metrics) == set(want_metrics)
    for key in metrics:
        np.testing.assert_allclose(metrics[key], want_metrics[key], rtol=1e-6)
    moved = jax.tree.map(lambda a, b: a - b, state.params, before)
    want_moved = jax.tree.map(lambda a, b: a - b, want_state.params, before)
    assert all(float(jnp.abs(m).max()) > 0 for m in jax.tree.leaves(want_moved["ln_out"]))
    for (path, got), wanted in zip(
        jax.tree_util.tree_leaves_with_path(moved), jax.tree.leaves(want_moved), strict=True
    ):
        np.testing.assert_allclose(
            got, wanted, rtol=2e-4, atol=2e-6, err_msg=jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("name", list(LMS))
def test_apply_returns_the_logits_and_the_state_under_them(name):
    """``model.apply`` is the parent's: logits first; with ``head``
    false the state after ``ln_out`` stands where they would, and the
    head's weights, as ``head_weights`` names them, make the logits."""
    tokens, model = _tokens(), LMS[name]()
    params = model.init({"params": jax.random.key(0)}, tokens)["params"]
    out = model.apply({"params": params}, tokens)
    hidden = model.apply({"params": params}, tokens, head=False)
    if isinstance(out, tuple):
        (out, aux), (hidden, aux_hidden) = out, hidden
        for a, b in zip(jax.tree.leaves(aux), jax.tree.leaves(aux_hidden), strict=True):
            np.testing.assert_array_equal(a, b)
    weights, bias, tied = model.head_weights(params)
    assert out.shape == (B, T, V) and out.dtype == jnp.float32
    assert hidden.shape == (B, T, weights.shape[1 if tied else 0])
    assert tied == (name == "ssm-tied") and (bias is None) == (name not in ("dense", "moe"))
    logits = jnp.einsum("btd,vd->btv" if tied else "btd,dv->btv", hidden, weights)
    np.testing.assert_allclose(
        logits if bias is None else logits + bias, out, rtol=1e-5, atol=1e-5
    )


def _lowered_text(group, model, placed=True):
    tx = optax.adam(1e-3)
    state = jax.eval_shape(lambda k: create_lm_state(group, model, tx, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    if placed:
        on = lambda tree, sharding: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )
        state, tokens = on(state, group.replicated_sharding), on(tokens, group.batch_sharding)
    return make_lm_train_step(group, model, tx).lower(state, tokens).as_text()


_WHOLE_LOGITS = (f"tensor<{B * T}x{V}xf32>", f"tensor<{B}x{T}x{V}xf32>")


@pytest.mark.parametrize("blocks", [2, 4], indirect=True)
@pytest.mark.parametrize("name", ["dense", "ssm-tied"])
def test_no_float32_array_of_the_logits_size_in_the_lowered_step(name, blocks):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = LMS[name]()
    text = _lowered_text(group, model)
    assert not any(shape in text for shape in _WHOLE_LOGITS)
    assert f"tensor<{B * T // blocks}x{V}xf32>" in text and "stablehlo.while" in text
    assert _WHOLE_LOGITS[1] in _lowered_text(group, _logits_only(model))  # the parent's program


def _digest(text):
    return hashlib.sha256(re.sub(r"@(\w+?)_\d+\b", r"@\1", text).encode()).hexdigest()


def _two_device_text():
    (group,) = setup_groups(1, devices=jax.devices()[:2])
    model = LMS["dense"]()
    return group, model, _lowered_text(group, model)


def record_sharded_step_digest():
    """``python -c "import sys; sys.path.insert(0, 'tests'); import
    test_head_loss as t; t.record_sharded_step_digest()"``, from the
    parent's checkout of a PR that changes that step on purpose (there
    ``LMS`` may need the parent's constructors)."""
    with open(FIXTURE, "w") as f:
        f.write(f"dense-two-devices {_digest(_two_device_text()[2])}\n")


def test_a_trial_on_two_devices_keeps_the_logits_and_lm_loss_mean(monkeypatch):
    """Operands on more than one device: a walk over blocks of rows of
    a batch-sharded array would make GSPMD gather it, so the step is the
    one the parent lowered (its SHA-256, from the parent's checkout of
    PR 38, in ``tests/fixtures/sharded_lm_step.sha256``), whatever the
    block: the program a model that offers no hidden state gets."""
    monkeypatch.setattr(head_loss, "LOGITS_BLOCK_BYTES", LOGITS_BYTES // 4)
    group, model, text = _two_device_text()
    assert _WHOLE_LOGITS[1] in text and "stablehlo.while" not in text
    assert text == _lowered_text(group, _logits_only(model))
    with open(FIXTURE) as f:
        recorded = dict(line.split() for line in f if line.strip())
    assert _digest(text) == recorded["dense-two-devices"]
