"""``LatentMoELM`` (latent attention, dropless routed experts) against
the benchmark's plain float32 reference, on the CPU at a tiny size.

The reference is ``benchmark/configs/joyai-llm-flash.reference.py``,
which imports nothing of the program; the weights reach it through
``benchmark/entries/moe_lm_trial.py::reference_weights``, the renaming
the chip run's comparison uses. Everything here is float32 at
``default_matmul_precision("highest")``, seeded, and counts or compares
numbers; nothing is timed.
"""

import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.entries import moe_lm_trial
from multidisttorch_tpu.models.latent_moe import LatentMoELM, rope_interleaved
from multidisttorch_tpu.ops.moe import RoutedExperts
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step
from multidisttorch_tpu.train.steps import TrainState

REFERENCE = cells.load_module("benchmark/configs/joyai-llm-flash.reference.py")

# The configuration's keys at a toy size: 16 experts, 4 a token, one
# dense layer and two expert layers.
TINY = {
    "vocab_size": 64, "hidden_size": 32, "num_attention_heads": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0, "intermediate_size": 48,
    "router_width": 16, "experts_held": [0, 16], "num_experts_per_tok": 4,
    "moe_intermediate_size": 24, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "float32", "remat": False},
}


def _config(**changes):
    return {**TINY, **changes}


def _params(model, seed=0, t=16):
    return model.init({"params": jax.random.key(seed)}, jnp.zeros((1, t), jnp.int32))["params"]


def _tokens(seed=1, b=2, t=16):
    return jax.random.randint(jax.random.key(seed), (b, t), 0, TINY["vocab_size"])


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("held", [[0, 16], [4, 8]], ids=["all", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_agrees_with_the_reference(held, remat):
    """Logits, loss, every gradient leaf, the experts chosen and the
    counter, through ``create_lm_state`` and ``make_lm_train_step`` as a
    trial runs them (the gradient is read back from one SGD(1.0) step)."""
    config = _config(experts_held=held, assumed={"compute_dtype": "float32", "remat": remat})
    model = moe_lm_trial.build_model(config)
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
        )(moe_lm_trial.reference_weights(params, config), tokens)

    assert _rel(logits, ref_logits) < 1e-5
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 1e-5 * float(ref_loss)
    np.testing.assert_array_equal(jnp.sort(chosen, -1), jnp.sort(routing["chosen"], -1))
    np.testing.assert_array_equal(metrics["expert_counts"], routing["expert_counts"])
    assert metrics["expert_counts"].shape == (2, held[1])
    got = moe_lm_trial.reference_weights(grads, config)
    flat_want = jax.tree_util.tree_leaves_with_path(ref_grads)
    for (path, want), have in zip(flat_want, jax.tree.leaves(got), strict=True):
        name = jax.tree_util.keystr(path)
        if "score_bias" in name:  # chooses, never weighs: no gradient
            assert not jnp.any(want) and not jnp.any(have), name
        else:
            assert _rel(have, want) < 2e-4, (name, _rel(have, want))


def _layer(held, **kw):
    return RoutedExperts(
        num_experts=16, experts_held=held, top_k=4, hidden_dim=24, routed_scaling=2.5, **kw
    )


def _layer_weights(whole, first, count):
    """The whole layer's parameters cut to one share's experts."""
    cut = lambda a: a[first:first + count]
    return {**whole, **{k: cut(whole[k]) for k in ("w_gate", "w_up", "w_down")}}


def _sigmoid_whole(x):
    """The uncut sigmoid-routed layer with its shared expert: ``(the
    layer's parameters without the shared expert's, what the reference
    gives for the whole layer, its counts, what every share computes
    alike)``."""
    whole = _layer((0, 16), shared_hidden_dim=24).init(jax.random.key(3), x)["params"]
    ref_w = {
        "router": whole["router"], "score_bias": whole["score_bias"],
        "e_gate": whole["w_gate"], "e_up": whole["w_up"], "e_down": whole["w_down"],
        "s_gate": whole["shared_gate"]["kernel"], "s_up": whole["shared_up"]["kernel"],
        "s_down": whole["shared_down"]["kernel"],
    }
    want, _, want_counts = REFERENCE.experts(x, ref_w, _config())
    shared = REFERENCE.swiglu(x, ref_w["s_gate"], ref_w["s_up"], ref_w["s_down"])
    routed_only = {k: v for k, v in whole.items() if not k.startswith("shared_")}
    return routed_only, want, want_counts, shared


def _softmax_whole(x, router_input):
    """The same for the scoring of the window/full hybrid models (top-k
    of the logits, a softmax over the chosen, ReGLU, no shared expert,
    the router reading ``router_input``), against that configuration's
    own reference."""
    reference = cells.load_module("benchmark/configs/smallthinker-21b-a3b.reference.py")
    config = {"moe_num_active_primary_experts": 4, "experts_held": [0, 16]}
    whole = _layer((0, 16), scoring="softmax", activation="relu").init(
        jax.random.key(3), x)["params"]
    ref_w = {"router": whole["router"], "e_gate": whole["w_gate"], "e_up": whole["w_up"],
             "e_down": whole["w_down"]}
    chosen, weights = reference.route(router_input, ref_w, config)
    want, want_counts = reference.experts(x, chosen, weights * 2.5, ref_w, config)
    return whole, want, want_counts, jnp.zeros_like(x)


@pytest.mark.parametrize(
    "scoring, shares, kw",
    [("sigmoid", 4, {}), ("softmax", 8, {}), ("softmax", 8, {"absent_share_grad": False})],
    ids=["sigmoid-4-shares", "softmax-8-shares", "softmax-8-shares-held-still"],
)
def test_shares_add_up_to_the_uncut_layer(scoring, shares, kw):
    """16 experts over 4 or 8 shares: the shares' routed parts, plus
    what every share computes alike (the shared expert) once, are the
    uncut reference's layer, under either scoring (and whatever the
    backward pass is told of a share: forward it is the same layer)."""
    x = jax.random.normal(jax.random.key(2), (40, 32))
    router_input = jax.random.normal(jax.random.key(7), (40, 32))
    held = 16 // shares
    with jax.default_matmul_precision("highest"):
        if scoring == "sigmoid":
            whole, want, want_counts, total = _sigmoid_whole(x)
            call = {}
        else:
            whole, want, want_counts, total = _softmax_whole(x, router_input)
            kw, call = {"scoring": "softmax", "activation": "relu", **kw}, {"router_input": router_input}
        counts = []
        for first in range(0, 16, held):
            part, c = _layer((first, held), **kw).apply(
                {"params": _layer_weights(whole, first, held)}, x, **call
            )
            total = total + part
            counts.append(c)
    assert _rel(total, want) < 1e-5
    np.testing.assert_array_equal(jnp.concatenate(counts), want_counts)
    assert int(want_counts.sum()) == 40 * 4  # every choice of every token, once


@pytest.mark.parametrize("absent_share_grad", [True, False], ids=["cut-alone", "held-still"])
def test_share_held_still_tells_the_router_nothing_of_the_cut(absent_share_grad):
    """One share of 8 with ``absent_share_grad=False``: the same output,
    and a router's gradient in which the absent experts' columns are nothing
    and the held experts' columns add up to nothing (a token's weight
    moves among its experts held, never onto them); the cut alone
    gives neither."""
    x = jax.random.normal(jax.random.key(2), (40, 32))
    co = jax.random.normal(jax.random.key(9), (40, 32))
    kw = {"scoring": "softmax", "activation": "relu"}
    layer = _layer((4, 2), **kw, absent_share_grad=absent_share_grad)
    with jax.default_matmul_precision("highest"):
        params = layer.init(jax.random.key(5), x)["params"]
        loss = lambda p, layer: jnp.sum(layer.apply({"params": p}, x)[0] * co)
        out = layer.apply({"params": params}, x)[0]
        alone = _layer((4, 2), **kw).apply({"params": params}, x)[0]
        router = jax.grad(loss)(params, layer)["router"]  # (32, 16)
    assert _rel(out, alone) < 1e-6
    held, absent = router[:, 4:6], jnp.delete(router, jnp.arange(4, 6), axis=1)
    scale = float(jnp.linalg.norm(held))
    assert scale > 0
    nothing = lambda a: float(jnp.linalg.norm(a)) < 1e-5 * scale
    still = nothing(absent) and nothing(held.sum(-1))
    assert still is not absent_share_grad


@pytest.mark.parametrize("rows", ["usual", "worst"])
def test_no_token_is_dropped_under_uneven_routing(rows):
    """A selection bias that sends every token to the experts held
    (the worst the routing allows: 8 times the mean load, 4 times the
    usual buffer) makes the layer walk the expert order a buffer at a
    time; a bias that keeps them away leaves the buffer empty. Either
    way the layer and its gradients are the reference's, and the
    counter is the reference's count."""
    x = jax.random.normal(jax.random.key(4), (64, 32))
    co = jax.random.normal(jax.random.key(9), (64, 32))
    layer = _layer((4, 4))
    config = _config(experts_held=[4, 4])
    none = {"s_gate": jnp.zeros((32, 1)), "s_up": jnp.zeros((32, 1)), "s_down": jnp.zeros((1, 32))}
    names = {"router": "router", "score_bias": "score_bias", "e_gate": "w_gate",
             "e_up": "w_up", "e_down": "w_down"}
    with jax.default_matmul_precision("highest"):
        params = layer.init(jax.random.key(5), x)["params"]
        push = 10.0 if rows == "worst" else -10.0
        params["score_bias"] = params["score_bias"].at[4:8].add(push)

        def program(p, x):
            out, counts = layer.apply({"params": p}, x)
            return jnp.sum(out * co), (out, counts)

        def reference(p, x):
            out, _, counts = REFERENCE.experts(
                x, {ref: p[own] for ref, own in names.items()} | none, config)
            return jnp.sum(out * co), (out, counts)

        (_, (got, counts)), grads = jax.jit(
            jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(params, x)
        (_, (want, want_counts)), want_grads = jax.jit(
            jax.value_and_grad(reference, argnums=(0, 1), has_aux=True))(params, x)
    np.testing.assert_array_equal(counts, want_counts)
    if rows == "usual":
        assert int(counts.sum()) == 0 and not jnp.any(got)
        assert all(not jnp.any(g) for g in jax.tree.leaves(grads))
        return
    assert int(counts.sum()) == 64 * 4  # all four choices of every token land here
    assert _rel(got, want) < 1e-5
    for got_g, want_g in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads), strict=True):
        if jnp.any(want_g):
            assert _rel(got_g, want_g) < 1e-4
        else:
            assert not jnp.any(got_g)


def test_a_walk_skips_the_buffers_past_the_count():
    """A bias on two of the four experts held sends them two choices of
    every token: more than the usual buffer of 128 rows and fewer than
    the worst case's 256, so the walk's last buffers hold no row and
    are not run. Output, gradients and counter are the reference's."""
    x = jax.random.normal(jax.random.key(4), (64, 32))
    co = jax.random.normal(jax.random.key(9), (64, 32))
    layer, config = _layer((4, 4)), _config(experts_held=[4, 4])
    names = {"router": "router", "score_bias": "score_bias", "e_gate": "w_gate",
             "e_up": "w_up", "e_down": "w_down"}
    none = {"s_gate": jnp.zeros((32, 1)), "s_up": jnp.zeros((32, 1)), "s_down": jnp.zeros((1, 32))}
    with jax.default_matmul_precision("highest"):
        params = layer.init(jax.random.key(5), x)["params"]
        params["score_bias"] = params["score_bias"].at[4:6].add(10.0)

        def program(p, x):
            out, counts = layer.apply({"params": p}, x)
            return jnp.sum(out * co), counts

        def reference(p, x):
            out, _, counts = REFERENCE.experts(
                x, {ref: p[own] for ref, own in names.items()} | none, config)
            return jnp.sum(out * co), counts

        grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(params, x)
        ((got, counts), grads), ((want, want_counts), want_grads) = grad(program), grad(reference)
    np.testing.assert_array_equal(counts, want_counts)
    assert 128 < int(counts.sum()) <= 192  # two or three of the four buffers hold rows
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for got_g, want_g in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads), strict=True):
        assert _rel(got_g, want_g) < 1e-4 if jnp.any(want_g) else not jnp.any(got_g)


def test_selection_bias_moves_the_choice_and_not_the_weights():
    x = jax.random.normal(jax.random.key(6), (32, 32))
    layer = _layer((0, 16))
    params = layer.init(jax.random.key(7), x)["params"]

    def chosen_and_out(p):
        (out, _), state = layer.apply({"params": p}, x, mutable=["intermediates"])
        return state["intermediates"]["chosen"][0], out

    chosen, out = chosen_and_out(params)
    # the same shift for every expert leaves the choice, and with it
    # the output, as it was: the bias is in no weight
    shifted = {**params, "score_bias": params["score_bias"] + 3.0}
    same_chosen, same_out = chosen_and_out(shifted)
    np.testing.assert_array_equal(chosen, same_chosen)
    np.testing.assert_array_equal(out, same_out)
    # a large bias on one expert puts it among every token's choices
    pushed = {**params, "score_bias": params["score_bias"].at[9].add(10.0)}
    new_chosen, new_out = chosen_and_out(pushed)
    assert jnp.all(jnp.any(new_chosen == 9, axis=-1))
    assert not jnp.allclose(out, new_out)
    grads = jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x)[0] ** 2))(params)
    assert not jnp.any(grads["score_bias"]) and jnp.any(grads["router"])


def test_rotary_interleaving_is_a_rotation_of_pairs():
    x = jax.random.normal(jax.random.key(8), (2, 5, 3, 8))
    theta = 10000.0
    got = rope_interleaved(x, jnp.arange(5), theta)
    want = np.zeros_like(x)
    for pos in range(5):
        for i in range(4):
            angle = pos * theta ** (-2 * i / 8)
            c, s = np.cos(angle), np.sin(angle)
            a, b = x[:, pos, :, 2 * i], x[:, pos, :, 2 * i + 1]
            want[:, pos, :, 2 * i] = a * c - b * s
            want[:, pos, :, 2 * i + 1] = a * s + b * c
    np.testing.assert_allclose(got, want, atol=1e-5)
    # position 0 is left alone, and a rotation keeps each pair's length
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)
    pairs = lambda a: np.asarray(a).reshape(2, 5, 3, 4, 2)
    np.testing.assert_allclose(
        np.linalg.norm(pairs(got), axis=-1), np.linalg.norm(pairs(x), axis=-1), atol=1e-5
    )


def test_bf16_step_trains_and_counts():
    """The trial path at the cell's dtypes: the loss falls, Adam leaves
    the selection bias where it was, the counter has one row a layer."""
    model = LatentMoELM(vocab_size=64, dtype=jnp.bfloat16, remat=True, experts_held=(2, 4))
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tx = optax.adam(1e-2)
    state = create_lm_state(group, model, tx, jax.random.key(0))
    bias = jax.tree.map(jnp.copy, state.params["block_1"]["moe"]["score_bias"])
    step = make_lm_train_step(group, model, tx)
    tokens = group.device_put(_tokens(t=32), group.batch_sharding)
    losses = []
    for _ in range(8):
        state, metrics = step(state, tokens)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert metrics["expert_counts"].shape == (2, 4) and metrics["expert_counts"].dtype == jnp.int32
    np.testing.assert_array_equal(state.params["block_1"]["moe"]["score_bias"], bias)
    assert isinstance(state, TrainState)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_kernel_grouped_dot_matches_xla_ragged_dot(dtype):
    """The Pallas grouped matmul a one-chip TPU model runs for its
    experts (interpreter here) against XLA's ragged dot, forward and
    both gradients, with rows past the groups' sum left out of the
    comparison as the layer leaves them out. Both hand the product on
    in the operands' dtype: at bf16 the float32 sum rounded once, and
    the cotangent that comes back is bf16, so the backward products
    run bf16 by bf16."""
    from multidisttorch_tpu.ops.moe import kernel_grouped_dot, ragged_grouped_dot

    ks = jax.random.split(jax.random.key(10), 3)
    lhs = jax.random.normal(ks[0], (1024, 128), jnp.float32).astype(dtype)
    rhs = jax.random.normal(ks[1], (4, 128, 256), jnp.float32).astype(dtype)
    co = jax.random.normal(ks[2], (1024, 256), jnp.float32)
    sizes = jnp.array([300, 0, 411, 100], jnp.int32)  # 811 of 1,024 rows; one empty group
    valid = (jnp.arange(1024) < 811)[:, None]

    def run(dot):
        def loss(lhs, rhs):
            out = jnp.where(valid, dot(jnp.where(valid, lhs, 0), rhs, sizes), 0)
            return jnp.sum(out * co), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs)
        return out, grads

    out, (g_lhs, g_rhs) = run(kernel_grouped_dot.experts)
    want, (w_lhs, w_rhs) = run(ragged_grouped_dot.experts)
    assert out.dtype == want.dtype == dtype and out.shape == (1024, 256)
    assert g_lhs.dtype == w_lhs.dtype == g_rhs.dtype == w_rhs.dtype == dtype
    exact = jnp.where(valid, ragged_grouped_dot.experts(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes), 0)
    _assert_rounded_once(out, exact, dtype)
    _assert_rounded_once(want, exact, dtype)
    rel = lambda a, b: _rel(a.astype(jnp.float32), b.astype(jnp.float32))
    close = 2e-2 if dtype == jnp.bfloat16 else 1e-5  # bf16: cotangents and gradients rounded
    assert rel(g_lhs, w_lhs) < close and rel(g_rhs, w_rhs) < close
    assert not jnp.any(g_rhs[1])  # the empty group's weights get no gradient


# --- the exchange on the buffer's rows (ops/moe.py: _dispatch, _combine) ---

_EX = {"n": 768, "k": 4, "m": 1024, "d": 128}  # three blocks of 256 tokens, eight tiles of 128 rows


def _exchange_case(case, dtype, seed=11):
    """A buffer of ``m`` rows for ``n`` tokens of ``k`` slots: which
    slots have a row (``total`` of them, rows ``0 .. total`` in random
    order, as the expert order leaves them), their weights, and rows
    past ``total`` holding NaN and any token. Token 300 has all its
    slots held in every case with a row at all."""
    n, k, m, d = (_EX[name] for name in "nkmd")
    rng = np.random.default_rng(seed)
    tokens = np.arange(256, 512) if case == "empty_blocks" else np.arange(n)
    slots = [(t, j) for t in tokens for j in range(k)]
    total = {"none": 0, "full": m, "some": 600, "empty_blocks": 500}[case]
    rest = [s for s in slots if s[0] != 300]
    picked = [(300, j) for j in range(k)] + [rest[i] for i in rng.permutation(len(rest))]
    picked = [picked[i] for i in rng.permutation(total)] if total else []
    row = np.zeros((n, k), np.int32)
    held = np.zeros((n, k), bool)
    tok = rng.integers(0, n, m).astype(np.int32)
    for r, (t, j) in enumerate(picked):
        row[t, j], held[t, j], tok[r] = r, True, t
    w = np.where(held, rng.uniform(0.05, 1.0, (n, k)), 0.0).astype(np.float32)
    valid = np.arange(m) < total
    w_of_row = np.zeros(m, np.float32)
    w_of_row[row[held]] = w[held]
    pair = np.full(m, n * k, np.int32)  # a row's (token, slot) as one index; none: one past the last
    pair[row[held]] = np.flatnonzero(held.reshape(-1))
    ys = rng.standard_normal((m, d)).astype(np.float32)
    ys = jnp.asarray(ys).astype(dtype)
    return dict(
        row=jnp.asarray(row), held=jnp.asarray(held), tok=jnp.asarray(tok), w=jnp.asarray(w),
        valid=jnp.asarray(valid), key=jnp.asarray(np.where(valid, tok, n)), pair=jnp.asarray(pair),
        w_of_row=jnp.asarray(w_of_row), ys=ys, ys_nan=jnp.where(valid[:, None], ys, jnp.nan),
        x=jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)).astype(dtype),
        g_out=jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)).astype(dtype),
    )


def _half_ulp_bf16(a):
    """Half a unit in the last of bf16's 8 significant bits, at ``a``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 1e-30))) - 8)


def _assert_rounded_once(got, want, dtype):
    """``got`` is the float32 ``want`` within float32's own rounding
    (1e-6), or rounded to bf16 once (half a unit in the last of 8
    bits, and the float32 noise under it)."""
    assert got.dtype == dtype
    got, want = np.asarray(got.astype(jnp.float32)), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = np.abs(want).max() if want.size and np.abs(want).max() > 0 else 1.0
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)
    else:
        assert (np.abs(got - want) <= _half_ulp_bf16(want) * (1 + 1e-3) + 1e-6 * scale).all()


@pytest.mark.parametrize("case", ["some", "none", "full", "empty_blocks"])
@pytest.mark.parametrize("path", ["plain", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_exchange_sums_over_the_rows_that_landed(dtype, path, case):
    """``_combine``, its two transposes and ``_dispatch``'s against the
    plain float32 statement of them over a token's slots: ``out[t] =
    sum_j w[t, j] * ys[row[t, j]]``. Both forms of the sums (XLA's
    scatter-add; the kernel, interpreted), a buffer with no row, a full
    one, token blocks with no row, a token with every slot held; the
    rows past the step's count hold NaN and are never read."""
    from multidisttorch_tpu.ops import moe

    dot = {"plain": moe.ragged_grouped_dot, "kernel": moe.kernel_grouped_dot}[path]
    c = _exchange_case(case, dtype)
    f32 = lambda a: a.astype(jnp.float32)
    slots = lambda rows: jnp.where(c["held"][..., None], f32(rows)[c["row"]], 0.0)  # (n, k, d)

    # forward: the weighted sum of a token's slots
    out, back = jax.vjp(
        lambda ys, w: moe._combine(dot, ys, c["tok"], c["key"], c["pair"], w), c["ys_nan"], c["w"]
    )
    _assert_rounded_once(out, jnp.sum(c["w"][..., None] * slots(c["ys"]), axis=1), dtype)
    # its transposes: a row gets its token's cotangent by its weight, a
    # slot's weight the inner product of its row and that cotangent
    g_ys, g_w = back(c["g_out"])
    want_g_ys = jnp.where(c["valid"][:, None], c["w_of_row"][:, None] * f32(c["g_out"])[c["tok"]], 0.0)
    _assert_rounded_once(g_ys, want_g_ys, dtype)
    want_g_w = jnp.sum(slots(c["ys"]) * f32(c["g_out"])[:, None, :], axis=-1)
    np.testing.assert_allclose(g_w, want_g_w, rtol=1e-5, atol=1e-5)

    # the gather's transpose: every held slot's row, weight 1
    xs, back = jax.vjp(lambda x: moe._dispatch(dot, x, c["tok"], c["key"]), c["x"])
    np.testing.assert_array_equal(f32(xs), f32(c["x"])[c["tok"]])
    g_rows = jnp.where(c["valid"][:, None], c["ys"], jnp.nan)  # NaN past the count here too
    (g_x,) = back(g_rows)
    _assert_rounded_once(g_x, jnp.sum(slots(c["ys"]), axis=1), dtype)


def _all_equations(jaxpr):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if hasattr(sub, "eqns") or hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    yield from _all_equations(sub)


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_nothing_is_gathered_to_every_slot(path):
    """The traced layer, forward and backward, the usual buffer and
    the walk: no value of ``n * k * d`` elements with rows ``d`` wide
    (what a gather of every token's ``k`` slots would make); the sums
    by token are the kernel's calls or XLA's scatter-adds
    (``tests/test_lm_scopes.py`` finds them under the exchange's
    scope in the compiled step)."""
    from multidisttorch_tpu.ops import moe

    n, k, d = 512, 4, 256
    dot = {"plain": moe.ragged_grouped_dot, "kernel": moe.kernel_grouped_dot}[path]
    layer = RoutedExperts(
        num_experts=16, experts_held=(4, 4), top_k=k, hidden_dim=128, shared_hidden_dim=128,
        dtype=jnp.bfloat16, grouped_dot=dot, name="moe",
    )
    x = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((n, d), jnp.bfloat16)))
    assert moe._buffer_rows(n, k, 4, 16) == (1024, 2048)  # the walk is in the trace

    def loss(p, x):
        return jnp.sum(layer.apply(p, x)[0].astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    kernels = scatters = 0
    for eqn in _all_equations(jaxpr):
        for var in eqn.outvars:
            shape = var.aval.shape
            assert not (shape and shape[-1] == d and var.aval.size >= n * k * d), (eqn, shape)
        kernels += eqn.primitive.name == "pallas_call" and eqn.params["name"] == "token_sums"
        scatters += eqn.primitive.name == "scatter-add"
    # the layer's output and its input's gradient, in the usual buffer and in the
    # walk: kernel calls, or scatter-adds
    assert kernels == (4 if path == "kernel" else 0)
    assert scatters >= (0 if path == "kernel" else 4)


# --- the dtype between the experts' products ---

# A layer whose buffer's shapes are each its own: n tokens choosing k of
# 16 experts, 4 held, through M = 1,024 rows (twice a tile of the
# experts' kernel), d wide, the experts h wide; the walk is in the trace.
_STEP = {"n": 512, "k": 4, "d": 128, "h": 256}
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _layer_step(dtype, path):
    """The layer's forward and backward, lowered as text, and traced."""
    from multidisttorch_tpu.ops import moe

    n, k, d, h = (_STEP[name] for name in "nkdh")
    dot = {"plain": moe.ragged_grouped_dot, "kernel": moe.kernel_grouped_dot}[path]
    layer = RoutedExperts(
        num_experts=16, experts_held=(4, 4), top_k=k, hidden_dim=h, dtype=dtype, grouped_dot=dot
    )
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((n, d), dtype)))
    x = jax.ShapeDtypeStruct((n, d), dtype)
    step = jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply(p, x)[0].astype(jnp.float32)), argnums=(0, 1)
    )
    return jax.jit(step).lower(params, x).as_text(), jax.make_jaxpr(step)(params, x)


def _step_digest(text):
    """SHA-256 of the text less the numbers jax appends to its private
    functions' names, which count the trace's equations."""
    return hashlib.sha256(re.sub(r"@(\w+?)_\d+\b", r"@\1", text).encode()).hexdigest()


def record_layer_step_digests():
    """Write the float32 layer's digests: ``MDT_PALLAS_INTERPRET=1
    JAX_PLATFORMS=cpu python -c "import tests.test_latent_moe as t;
    t.record_layer_step_digests()"``."""
    with open(os.path.join(FIXTURES, "routed_experts_f32_step.sha256"), "w") as f:
        for path in ("plain", "kernel"):
            f.write(f"{path} {_step_digest(_layer_step(jnp.float32, path)[0])}\n")


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_bf16_experts_hand_on_bf16_and_f32_is_the_program_before(path):
    """At bf16 every grouped product of the layer, forward, recomputed
    and backward, in the usual buffer and in the walk, takes bf16 and
    gives bf16, and nothing under the experts' scope is a float32
    ``(M, 2h)`` or ``(M, d)`` array: no float32 ``gate_up``, ``ys`` or
    cotangent of either between the products. At float32 the lowered
    layer is, character for character, the one it lowered to when the
    products handed on float32 whatever their operands (its SHA-256,
    taken from that checkout, is in
    ``tests/fixtures/routed_experts_f32_step.sha256``; a change to the
    float32 layer on purpose records the new one with
    :func:`record_layer_step_digests`)."""
    from multidisttorch_tpu.ops import moe

    n, k, d, h = (_STEP[name] for name in "nkdh")
    m = moe._buffer_rows(n, k, 4, 16)[0]
    assert (m, moe._TILE_ROWS) == (1024, 512)
    _, jaxpr = _layer_step(jnp.bfloat16, path)
    kernels = ragged = 0
    for eqn in _all_equations(jaxpr):
        name = eqn.primitive.name
        if name == "ragged_dot_general" or (name == "pallas_call" and eqn.params["name"] != "token_sums"):
            kernels += name == "pallas_call"
            ragged += name == "ragged_dot_general"
            floats = [v.aval.dtype for v in (*eqn.invars, *eqn.outvars)
                      if jnp.issubdtype(v.aval.dtype, jnp.floating)]
            assert floats and all(t == jnp.bfloat16 for t in floats), eqn
        if re.search(r"\bexperts\b", str(eqn.source_info.name_stack)):
            for var in eqn.outvars:
                wide = var.aval.shape in ((m, 2 * h), (m, d))
                assert not (wide and var.aval.dtype == jnp.float32), (eqn, var.aval)
    # two products forward and four backward in the usual buffer (gmm and
    # tgmm, or ragged dots), and the walk's ragged dots, forward, recomputed
    # and backward
    assert (kernels, ragged) == ((6, 8) if path == "kernel" else (0, 14))
    with open(os.path.join(FIXTURES, "routed_experts_f32_step.sha256")) as f:
        recorded = dict(line.split() for line in f if line.strip())
    assert _step_digest(_layer_step(jnp.float32, path)[0]) == recorded[path]
