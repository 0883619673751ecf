"""``GroupedWindowMoELM`` (grouped KV heads, window and full layers in
a pattern, a router that reads the block's input, ReGLU experts) against
the benchmark's plain float32 reference, on the CPU at a tiny size
(the kernels alone: ``tests/test_grouped_attention.py``).

The reference is ``benchmark/configs/smallthinker-21b-a3b.reference.py``,
which imports nothing of the program; the weights reach it through
``benchmark/entries/swa_moe_lm_trial.py::reference_weights``, the
renaming the chip run's comparison uses. Everything is float32 at
``default_matmul_precision("highest")``, seeded, and counts or compares
numbers; nothing is timed.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.entries import swa_moe_lm_trial
from multidisttorch_tpu.models.grouped_window_moe import GroupedWindowMoELM
from multidisttorch_tpu.ops.pallas_attention import grouped_attention
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step

REFERENCE = cells.load_module("benchmark/configs/smallthinker-21b-a3b.reference.py")

# The configuration's keys at a toy size: two periods of the pattern, 8
# query heads over 2 KV heads, a window shorter than T, 16 experts, 4 a
# token.
TINY = {
    "vocab_size": 64, "hidden_size": 32, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "num_hidden_layers": 8, "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "sliding_window_size": 6, "rope_theta": 10000.0,
    "router_width": 16, "experts_held": [0, 16], "moe_num_active_primary_experts": 4,
    "moe_ffn_hidden_size": 24, "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "float32", "remat": False, "embedding_stddev": 3.0},
}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize(
    "held, remat, absent_share_grad",
    [([0, 16], False, True), ([4, 8], True, True), ([4, 8], True, False)],
    ids=["all-plain", "share-remat", "share-held-still"],
)
def test_model_agrees_with_the_reference(held, remat, absent_share_grad):
    """Logits, loss, every gradient leaf, the experts chosen and the
    counter, through ``create_lm_state`` and ``make_lm_train_step`` as a
    trial runs them (the gradient is read back from one SGD(1.0) step);
    the last case with the held experts' share of a token's weight a
    constant to the backward pass, as the benchmark's cell trains."""
    assumed = {**TINY["assumed"], "remat": remat, "absent_share_grad": absent_share_grad}
    config = {**TINY, "experts_held": held, "assumed": assumed}
    model = swa_moe_lm_trial.build_model(config)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    sgd = optax.sgd(1.0)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, TINY["vocab_size"])
    with jax.default_matmul_precision("highest"):
        state = create_lm_state(group, model, sgd, jax.random.key(0))
        params = jax.tree.map(jnp.copy, state.params)
        logits, chosen = jax.jit(
            lambda p, t: swa_moe_lm_trial.chosen_experts(model, p, t, config)
        )(params, tokens)
        after, metrics = make_lm_train_step(group, model, sgd)(state, tokens)
        grads = jax.tree.map(jnp.subtract, params, after.params)
        ref_logits, ref_loss, ref_grads, routing = jax.jit(
            lambda w, t: REFERENCE.logits_loss_grads(w, t, config)
        )(swa_moe_lm_trial.reference_weights(params, config), tokens)

    assert _rel(logits, ref_logits) < 1e-5
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 1e-5 * float(ref_loss)
    np.testing.assert_array_equal(jnp.sort(chosen, -1), jnp.sort(routing["chosen"], -1))
    np.testing.assert_array_equal(metrics["expert_counts"], routing["expert_counts"])
    assert metrics["expert_counts"].shape == (8, held[1])
    got = swa_moe_lm_trial.reference_weights(grads, config)
    flat_want = jax.tree_util.tree_leaves_with_path(ref_grads)
    for (path, want), have in zip(flat_want, jax.tree.leaves(got), strict=True):
        assert _rel(have, want) < 2e-4, (jax.tree_util.keystr(path), _rel(have, want))


def test_parameters_are_the_configuration_s_count():
    """The built model at the published widths holds what the
    configuration's file says (shapes only: nothing is allocated)."""
    cell = cells.load_cell("moe-swa-t16384")
    model = swa_moe_lm_trial.build_model(cell.config)
    shapes = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, 256), jnp.int32)
    )["params"]
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert count == 643_852_800
    assert f"{count:,}" in cell.config["parameters"]
    assert set(shapes["block_0"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}  # no bias
    assert model.window_layout == model.rope_layout == (0, 1, 1, 1, 0, 1, 1, 1)
    # the seeded embedding's deviation is the file's; the library's default is nn.Embed's own
    assert model.embed_stddev == cell.config["assumed"]["embedding_stddev"] == 3.0
    assert GroupedWindowMoELM(vocab_size=64).embed_stddev is None
    # and so is the share's gradient: a chip's share trained alone holds it still
    assert model.absent_share_grad is cell.config["assumed"]["absent_share_grad"] is False
    assert GroupedWindowMoELM(vocab_size=64).absent_share_grad is True


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_on_the_kernels_is_the_model_on_the_plain_path(remat):
    """Heads 128 wide, one full and one window layer, the kernels
    injected (interpreted): logits and gradients are the plain path's."""
    fields = dict(
        vocab_size=64, d_model=64, num_heads=2, num_kv_heads=1, head_dim=128, num_layers=2,
        window_layout=(0, 1), rope_layout=(0, 1), window=100, num_experts=4, top_k=2,
        max_len=256, remat=remat,
    )
    plain = GroupedWindowMoELM(**fields)
    kernels = GroupedWindowMoELM(
        **fields, attention=lambda q, k, v, **kw: grouped_attention(q, k, v, block=128, **kw)
    )
    tokens = jax.random.randint(jax.random.key(1), (1, 256), 0, 64)
    with jax.default_matmul_precision("highest"):
        params = plain.init({"params": jax.random.key(0)}, tokens)["params"]
        loss = lambda model: lambda p: jnp.mean(model.apply({"params": p}, tokens)[0] ** 2)
        got = jax.value_and_grad(loss(kernels))(params)
        want = jax.value_and_grad(loss(plain))(params)
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert _rel(have, need) < 2e-5


@pytest.mark.parametrize("rope_layout", [(0, 1), (1, 1), (0, 0)], ids=["nope+rope", "rope", "nope"])
def test_heads_64_wide_on_a_tpu_rotate_on_the_plain_path(request, monkeypatch, rope_layout):
    """A full and a window layer of 4 heads over 2 KV heads of 64 with
    the operands placed as a trial's, the CPU device under a v5e's name:
    a layer without positions takes the 64-wide grouped kernels, a
    rotary layer the plain path (those kernels rotate nothing), and
    either way the step lowers for the TPU (interpret mode off) and
    gives the plain path's loss and gradients (interpreted)."""
    model = GroupedWindowMoELM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=2, head_dim=64, num_layers=2,
        window_layout=(0, 1), rope_layout=rope_layout, window=100, num_experts=4, top_k=2,
        max_len=256,
    )
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tokens = group.device_put(
        np.asarray(jax.random.randint(jax.random.key(1), (1, 256), 0, 64)), group.batch_sharding)
    params = group.device_put(model.init({"params": jax.random.key(0)}, tokens)["params"])
    loss = lambda p, t: jnp.mean(model.apply({"params": p}, t)[0] ** 2)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(loss))(params, tokens)
        request.getfixturevalue("as_v5e")
        on_tpu = jax.jit(jax.value_and_grad(loss))  # a new jit: traced under the v5e's name
        got = on_tpu(params, tokens)
        monkeypatch.delenv("MDT_PALLAS_INTERPRET")
        text = jax.jit(jax.value_and_grad(loss)).trace(params, tokens).lower(
            lowering_platforms=("tpu",)).as_text()
    # a forward and a backward kernel a layer without positions
    calls = [line for line in text.splitlines() if "custom_call @tpu_custom_call" in line]
    for kernel in ("grouped64_fwd", "grouped64_bwd"):
        assert sum(kernel in line for line in calls) == rope_layout.count(0)
    assert not any("grouped_fwd" in line for line in calls)
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert _rel(have, need) < 2e-5
