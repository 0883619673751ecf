"""``LoopedLM`` (a stack of sandwich-norm blocks run several times a
token, an exit gate after every pass, the loss of every pass weighed by
its exit probability) against the benchmark's plain float32 reference,
on the CPU at a tiny size.

The reference is ``benchmark/configs/ouro-2.6b.reference.py``, which
imports nothing of the program; the weights reach it through
``benchmark/entries/looped_lm_trial.py::reference_weights``, the
renaming the chip run's comparison uses. Everything is float32 at
``default_matmul_precision("highest")``, seeded, and counts or compares
numbers; nothing is timed.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.entries import looped_lm_trial
from multidisttorch_tpu.models.looped import LoopedLM, exit_log_probs
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, lm_loss_mean, make_lm_train_step

REFERENCE = cells.load_module("benchmark/configs/ouro-2.6b.reference.py")
CONFIG_FILE = os.path.join(cells.ROOT, "benchmark/configs/ouro-2.6b.json")

# The published ``config.json`` of ``Ouro-2.6B``, as the catalog row
# ``architectures.jsonl`` holds it, copied.
CATALOG_CONFIG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}

# The configuration's keys at a toy size: 2 layers of 4 heads of 16, 4 loops.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "total_ut_steps": 4, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "float32", "remat": False, "exit_entropy_weight": 0.1},
}
B, T = 2, 32


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _tokens():
    return jnp.asarray(np.random.default_rng(5).integers(0, TINY["vocab_size"], (B, T)), jnp.int32)


def _sgd_step(model, tokens):
    """``(initial params, params after one SGD(1.0) step, metrics)``:
    the parameters move by minus the gradient."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    sgd = optax.sgd(1.0)
    with jax.default_matmul_precision("highest"):
        state = create_lm_state(group, model, sgd, jax.random.key(3))
        before = jax.tree.map(jnp.copy, state.params)
        after, metrics = make_lm_train_step(group, model, sgd)(state, tokens)
    return before, after.params, metrics


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_agrees_with_the_reference(remat):
    """Every loop's logits, the exit distribution's means (``exit_p``),
    each loop's cross-entropy (``loop_loss``), the loss and every
    gradient leaf, the gate's among them, through ``create_lm_state``
    and ``make_lm_train_step`` as a trial runs them."""
    config = {**TINY, "assumed": {**TINY["assumed"], "remat": remat}}
    model, tokens = looped_lm_trial.build_model(config), _tokens()
    before, after, metrics = _sgd_step(model, tokens)
    grads = looped_lm_trial.reference_weights(jax.tree.map(jnp.subtract, before, after))
    weights = looped_lm_trial.reference_weights(before)
    states, loss, ref_grads, counters = REFERENCE.hidden_loss_grads(weights, tokens, config)
    with jax.default_matmul_precision("highest"):
        logits, gates = model.apply({"params": before}, tokens)
    assert logits.shape == (4, B, T, TINY["vocab_size"]) and gates.shape == (4, B, T)
    for u in range(4):
        assert _rel(logits[u], REFERENCE.logits_of(states[u], weights)) < 1e-5, u
    np.testing.assert_allclose(metrics["loss"], loss, rtol=1e-5)
    for name in ("exit_p", "loop_loss"):
        assert metrics[name].shape == (4,)
        np.testing.assert_allclose(metrics[name], counters[name], rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(metrics["exit_p"])), 1.0, rtol=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(ref_grads)
    assert len(leaves) == 1 + 2 * 11 + 4  # wte, two blocks, lnf, the gate's two, head
    for (path, want), got in zip(leaves, jax.tree.leaves(grads), strict=True):
        assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
        assert _rel(got, want) < 2e-4, jax.tree_util.keystr(path)


def test_the_exit_distribution_is_the_products_it_is():
    g = jax.random.normal(jax.random.key(0), (4, 3, 5)) * 3.0
    log_p = exit_log_probs(g)
    np.testing.assert_allclose(jnp.exp(log_p), REFERENCE.exit_distribution(g), rtol=1e-4)
    np.testing.assert_allclose(jnp.sum(jnp.exp(log_p), axis=0), 1.0, rtol=1e-6)
    # the last loop's gate is not read
    np.testing.assert_array_equal(log_p, exit_log_probs(g.at[-1].set(100.0)))
    # one loop leaves after it with certainty
    np.testing.assert_array_equal(exit_log_probs(g[:1]), jnp.zeros((1, 3, 5)))


def test_one_loop_is_the_plain_stack():
    """With U = 1 the exit distribution is ``p_1 = 1``: the loss is the
    plain next-token loss of the stack's logits (``lm_loss_mean``), its
    entropy 0, and the gradients are that loss's; the gate learns
    nothing."""
    model = looped_lm_trial.build_model({**TINY, "total_ut_steps": 1})
    tokens = _tokens()
    before, after, metrics = _sgd_step(model, tokens)
    np.testing.assert_array_equal(metrics["exit_p"], [1.0])

    def plain(params):
        return lm_loss_mean(model.apply({"params": params}, tokens)[0][0], tokens)

    with jax.default_matmul_precision("highest"):
        loss, want = jax.value_and_grad(plain)(before)
    np.testing.assert_allclose(metrics["loss"], loss, rtol=1e-6)
    np.testing.assert_allclose(metrics["loop_loss"], [loss], rtol=1e-6)
    got = jax.tree.map(jnp.subtract, before, after)
    assert not jnp.any(got["exit_gate"]["kernel"]) and not jnp.any(got["exit_gate"]["bias"])
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6, err_msg=jax.tree_util.keystr(path))


def _count(model):
    params = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return sum(a.size for a in jax.tree.leaves(params)), jax.tree.structure(params)


def test_the_parameters_do_not_depend_on_the_loops():
    counts = {u: _count(LoopedLM(vocab_size=256, loops=u)) for u in (1, 2, 4, 7)}
    assert len({c for c, _ in counts.values()}) == 1
    assert len({s for _, s in counts.values()}) == 1


def test_the_file_holds_the_catalog_row_and_the_built_model_s_count():
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    for key, value in CATALOG_CONFIG.items():
        assert key in config, key
        if config[key] != value:
            assert key in config["reduced"], key
    assert sorted(config["reduced"]) == ["layer_types", "max_position_embeddings",
                                         "num_hidden_layers"]
    assert config["layer_types"] == CATALOG_CONFIG["layer_types"][:config["num_hidden_layers"]]
    count, _ = _count(looped_lm_trial.build_model(config))
    assert count == 509_661_185
    assert config["parameters"].startswith(f"{count:,} on this chip")
    whole = count + (48 - 6) * (4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048)
    assert f"{whole:,}" in config["parameters"]


def test_the_kernel_path_agrees_with_the_reference(as_v5e):
    """On one v5e chip by name (the kernels interpreted): heads 128 wide,
    one query head a KV head, q rotated in the grouped kernels; every
    loop's counters, the loss and every gradient leaf against the
    reference, in float32."""
    config = {**TINY, "hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 2,
              "head_dim": 128, "max_position_embeddings": 256,
              "assumed": {**TINY["assumed"], "remat": True}}
    model = looped_lm_trial.build_model(config)
    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 256, (1, 256)), jnp.int32)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tokens = group.device_put(tokens, group.batch_sharding)
    text = make_lm_train_step(group, model, optax.sgd(1.0)).lower(
        create_lm_state(group, model, optax.sgd(1.0), jax.random.key(3)), tokens).as_text()
    assert text.count("grouped_fwd") >= 1 and text.count("grouped_bwd") >= 1
    before, after, metrics = _sgd_step(model, tokens)
    grads = looped_lm_trial.reference_weights(jax.tree.map(jnp.subtract, before, after))
    _, loss, ref_grads, counters = REFERENCE.hidden_loss_grads(
        looped_lm_trial.reference_weights(before), tokens, config)
    np.testing.assert_allclose(metrics["loss"], loss, rtol=1e-5)
    for name in ("exit_p", "loop_loss"):
        np.testing.assert_allclose(metrics[name], counters[name], rtol=1e-5)
    for (path, want), got in zip(jax.tree_util.tree_leaves_with_path(ref_grads),
                                 jax.tree.leaves(grads), strict=True):
        assert _rel(got, want) < 2e-4, jax.tree_util.keystr(path)


def test_a_trial_over_two_devices_trains_on_the_logits_the_same_objective():
    """Operands over two devices keep the logits (no walk): the loss,
    the counters and the step's gradients are the one-device walk's."""
    model, tokens = looped_lm_trial.build_model(TINY), _tokens()
    before, after, metrics = _sgd_step(model, tokens)
    (group,) = setup_groups(1, devices=jax.devices()[:2])
    sgd = optax.sgd(1.0)
    with jax.default_matmul_precision("highest"):
        state = create_lm_state(group, model, sgd, jax.random.key(3))
        step = make_lm_train_step(group, model, sgd)
        placed = group.device_put(tokens, group.batch_sharding)
        assert f"tensor<{B}x{T}x{TINY['vocab_size']}xf32>" in step.lower(state, placed).as_text()
        two, two_metrics = step(state, placed)
    for name in ("loss", "exit_p", "loop_loss"):
        np.testing.assert_allclose(two_metrics[name], metrics[name], rtol=1e-5)
    two = jax.device_get(two.params)
    for got, want in zip(jax.tree.leaves(jax.tree.map(np.subtract, before, two)),
                         jax.tree.leaves(jax.tree.map(jnp.subtract, before, after)), strict=True):
        assert _rel(got, want) < 1e-4
