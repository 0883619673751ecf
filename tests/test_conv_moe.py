"""``ShortConvMoELM`` (gated short-convolution layers among QK-normed
rotary attention over grouped KV heads, a dense layer then sigmoid-
routed experts, a tied head) against the benchmark's plain float32
reference, on the CPU at a tiny size (the kernels alone:
``tests/test_grouped_attention.py``).

The reference is ``benchmark/configs/lfm2-24b-a2b.reference.py``, which
imports nothing of the program; the weights reach it through
``benchmark/entries/conv_moe_lm_trial.py::reference_weights``, the
renaming the chip run's comparison uses. Everything is float32 at
``default_matmul_precision("highest")``, seeded, and counts or compares
numbers; nothing is timed.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.entries import conv_moe_lm_trial
from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.models.conv_moe import (
    ShortConvMoEBlock,
    ShortConvMoELM,
    gated_short_conv,
)
from multidisttorch_tpu.ops.moe import RoutedExperts
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step

REFERENCE = cells.load_module("benchmark/configs/lfm2-24b-a2b.reference.py")

# The catalog row's ``config`` (``architectures.jsonl`` beside the
# model-configs guide, ``LFM2-24B-A2B``), copied.
PUBLISHED_LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 \
    + ["full_attention", "conv"]
CATALOG_CONFIG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": PUBLISHED_LAYER_TYPES, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}

# The configuration's keys at a toy size: the published first 10 layers
# (two dense, two attention layers among them), 8 query heads over 2 KV
# heads, 16 experts, 4 a token.
TINY = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 24,
    "layer_types": PUBLISHED_LAYER_TYPES[:10], "num_hidden_layers": 10, "num_dense_layers": 2,
    "conv_L_cache": 3, "num_attention_heads": 8, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 10000.0}, "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "router_width": 16,
    "experts_held": [0, 16], "num_experts_per_tok": 4, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "float32", "remat": False, "embedding_stddev": 1.0,
                "tie_word_embeddings": True, "absent_share_grad": True},
}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _conv_by_positions(y, w_in, taps, w_out):
    """The conv operator one position at a time, numpy float64."""
    y, w_in, taps, w_out = (np.asarray(a, np.float64) for a in (y, w_in, taps, w_out))
    d = y.shape[-1]
    out = np.zeros_like(y)
    for n in range(y.shape[0]):
        gated = []
        for t in range(y.shape[1]):
            bcu = y[n, t] @ w_in
            b_gate, c_gate, u = bcu[:d], bcu[d:2 * d], bcu[2 * d:]
            gated.append(b_gate * u)
            z = sum(taps[j] * gated[t - 2 + j] for j in range(3) if t - 2 + j >= 0)
            out[n, t] = (c_gate * z) @ w_out
    return out


def _conv_operator(params, y):
    """The block's conv operator alone, from its own parameters."""
    bcu = y @ params["in_proj"]["kernel"]
    return gated_short_conv(bcu, params["conv_w"]) @ params["out_proj"]["kernel"]


@pytest.fixture(scope="module")
def conv_case():
    y = jax.random.normal(jax.random.key(0), (2, 9, 16))
    block = ShortConvMoEBlock(kind="conv", hidden_dim=32)
    with jax.default_matmul_precision("highest"):
        params = block.init(jax.random.key(1), y)["params"]
    return y, params


def test_conv_operator_is_the_loop_over_positions(conv_case):
    y, params = conv_case
    with jax.default_matmul_precision("highest"):
        got = _conv_operator(params, y)
    want = _conv_by_positions(
        y, params["in_proj"]["kernel"], params["conv_w"], params["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert params["conv_w"].shape == (3, 16) and "bias" not in params["in_proj"]


@pytest.mark.parametrize("leaf", ["in_proj", "conv_w", "out_proj"])
def test_conv_operator_s_gradients_are_the_loop_s(conv_case, leaf):
    """The gradient of ``W_in``, the taps and ``W_out`` against central
    differences of the position-by-position loop in float64."""
    y, params = conv_case
    co = np.asarray(jax.random.normal(jax.random.key(2), y.shape), np.float64)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: jnp.sum(_conv_operator(p, y) * co))(params)
    got = grads[leaf]["kernel"] if leaf != "conv_w" else grads[leaf]
    arrays = {"in_proj": params["in_proj"]["kernel"], "conv_w": params["conv_w"],
              "out_proj": params["out_proj"]["kernel"]}
    arrays = {k: np.asarray(a, np.float64) for k, a in arrays.items()}
    loss = lambda a: float(np.sum(
        _conv_by_positions(y, a["in_proj"], a["conv_w"], a["out_proj"]) * co))
    rng = np.random.default_rng(0)
    for _ in range(6):  # six elements of the leaf, drawn
        at = tuple(rng.integers(0, n) for n in arrays[leaf].shape)
        up, down = ({**arrays, leaf: arrays[leaf].copy()} for _ in range(2))
        up[leaf][at] += 1e-4
        down[leaf][at] -= 1e-4
        want = (loss(up) - loss(down)) / 2e-4
        assert abs(float(got[at]) - want) < 1e-3 * max(1.0, abs(want)), (leaf, at)


def test_conv_operator_is_causal(conv_case):
    """A change at position t moves nothing before t, and reaches no
    further than two positions on through the taps alone."""
    y, params = conv_case
    moved = y.at[:, 5].add(1.0)
    with jax.default_matmul_precision("highest"):
        before, after = _conv_operator(params, y), _conv_operator(params, moved)
    differs = np.any(np.asarray(before != after), axis=(0, 2))
    assert differs.tolist() == [False] * 5 + [True] * 3 + [False]


def test_qk_norm_then_rotation_is_the_written_out_form():
    """An attention block's q and k, as its core receives them, are the
    per-head RMS norm (one scale for q, one for k) and then the halves
    rotation, written out here."""
    seen = {}

    def attention(q, k, v, *, window, q_rotation):
        seen.update(q=q, k=k, window=window, q_rotation=q_rotation)
        return q

    block = ShortConvMoEBlock(
        kind="full_attention", hidden_dim=32, num_heads=4, num_kv_heads=2, head_dim=8,
        attention=attention, eps=1e-5,
    )
    x = jax.random.normal(jax.random.key(0), (2, 12, 16))
    with jax.default_matmul_precision("highest"):
        params = block.init(jax.random.key(1), x)["params"]
        params = {**params, "q_norm": {"scale": jnp.linspace(0.5, 1.5, 8)},
                  "k_norm": {"scale": jnp.linspace(2.0, 1.0, 8)}}
        block.apply({"params": params}, x)
        y = REFERENCE.rms(x, params["ln_attn"]["scale"], 1e-5)
        for name, heads in (("q", 4), ("k", 2)):
            a = (y @ params[name]["kernel"]).reshape(2, 12, heads, 8)
            a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + 1e-5)
            a = a * params[f"{name}_norm"]["scale"]
            angle = jnp.arange(12)[:, None] * 10000.0 ** (-jnp.arange(0, 8, 2) / 8)[None, :]
            cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
            want = jnp.concatenate(
                [a[..., :4] * cos - a[..., 4:] * sin, a[..., 4:] * cos + a[..., :4] * sin], -1)
            assert _rel(seen[name], want) < 1e-5
            angles = decoder.rope_angles(jnp.arange(12), 10000.0, 8)  # the model's own helpers agree
            np.testing.assert_allclose(
                decoder.rope_halves(a, jnp.cos(angles), jnp.sin(angles)), want, rtol=1e-4, atol=1e-5)
    assert seen["window"] is None and seen["q_rotation"] is None  # the kernels get plain operands


@pytest.mark.parametrize(
    "held, remat, absent_share_grad",
    [([0, 16], False, True), ([4, 8], True, True), ([4, 8], True, False)],
    ids=["all-plain", "share-remat", "share-held-still"],
)
def test_model_agrees_with_the_reference(held, remat, absent_share_grad):
    """Logits, loss, every gradient leaf, each expert layer's choices
    and the counter, through ``create_lm_state`` and
    ``make_lm_train_step`` as a trial runs them (the gradient is read
    back from one SGD(1.0) step); the last case with the held experts'
    share of a token's weight a constant to the backward pass, as the
    benchmark's cell trains."""
    assumed = {**TINY["assumed"], "remat": remat, "absent_share_grad": absent_share_grad}
    config = {**TINY, "experts_held": held, "assumed": assumed}
    model = conv_moe_lm_trial.build_model(config)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    sgd = optax.sgd(1.0)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, TINY["vocab_size"])
    with jax.default_matmul_precision("highest"):
        state = create_lm_state(group, model, sgd, jax.random.key(0))
        params = jax.tree.map(jnp.copy, state.params)
        logits, chosen = jax.jit(
            lambda p, t: conv_moe_lm_trial.chosen_experts(model, p, t, config)
        )(params, tokens)
        after, metrics = make_lm_train_step(group, model, sgd)(state, tokens)
        grads = jax.tree.map(jnp.subtract, params, after.params)
        ref_logits, ref_loss, ref_grads, routing = jax.jit(
            lambda w, t: REFERENCE.logits_loss_grads(w, t, config)
        )(conv_moe_lm_trial.reference_weights(params, config), tokens)

    assert _rel(logits, ref_logits) < 1e-5
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 1e-5 * float(ref_loss)
    np.testing.assert_array_equal(jnp.sort(chosen, -1), jnp.sort(routing["chosen"], -1))
    np.testing.assert_array_equal(metrics["expert_counts"], routing["expert_counts"])
    assert metrics["expert_counts"].shape == (8, held[1])  # the 8 expert layers of the 10
    got = conv_moe_lm_trial.reference_weights(grads, config)
    flat_want = jax.tree_util.tree_leaves_with_path(ref_grads)
    for (path, want), have in zip(flat_want, jax.tree.leaves(got), strict=True):
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            assert not np.any(np.asarray(have)) and not np.any(np.asarray(want))
        else:
            assert _rel(have, want) < 2e-4, (jax.tree_util.keystr(path), _rel(have, want))


def test_remat_is_bit_equal_in_float32():
    """Per-block rematerialization changes what is kept, not a bit of
    the logits, the loss or any gradient (the plain path, float32)."""
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    models = [
        conv_moe_lm_trial.build_model({**TINY, "assumed": {**TINY["assumed"], "remat": remat}})
        for remat in (False, True)
    ]
    params = models[0].init({"params": jax.random.key(0)}, tokens)["params"]

    def loss(model):
        def of(p):
            logits, counters = model.apply({"params": p}, tokens)
            return REFERENCE.next_token_loss(logits, tokens), (logits, counters)
        return jax.jit(jax.value_and_grad(of, has_aux=True))

    plain, remat = (loss(m)(params) for m in models)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat), strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("layer", [2, 3], ids=["attention-layer", "conv-layer"])
def test_the_eight_shares_routed_sums_add_up_to_the_uncut_layer_s(layer):
    """The share tied to the model: an expert layer of the tiny model
    given experts ``2 s`` and ``2 s + 1`` of 16 for ``s`` in 0 to 7,
    each with its slice of the uncut layer's weights, answers parts
    that sum to what the layer holding all 16 answers, counters and
    choices alike; the reference's expert layer says the same of its
    own shares."""
    config = {**TINY}
    model = conv_moe_lm_trial.build_model(config)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    with jax.default_matmul_precision("highest"):
        params = model.init({"params": jax.random.key(0)}, tokens)["params"]
        moe = params[f"block_{layer}"]["moe"]
        z = jax.random.normal(jax.random.key(3), (32, 64))
        layer_of = lambda held: RoutedExperts(
            num_experts=16, experts_held=held, top_k=4, hidden_dim=24, scoring="sigmoid",
            activation="silu")
        whole, whole_counts = layer_of((0, 16)).apply({"params": moe}, z)
        parts, counts = zip(*(
            layer_of((2 * s, 2)).apply(
                {"params": {**moe, **{k: moe[k][2 * s:2 * s + 2]
                                      for k in ("w_gate", "w_up", "w_down")}}}, z)
            for s in range(8)
        ))
        assert _rel(sum(parts), whole) < 1e-5
        np.testing.assert_array_equal(jnp.concatenate(counts), whole_counts)
        assert int(whole_counts.sum()) == 32 * 4
        # and the reference's own shares
        w = conv_moe_lm_trial.reference_weights(params, config)["blocks"][layer]
        chosen, weights = REFERENCE.route(z, w, config)
        ref_whole, _ = REFERENCE.experts(z, chosen, weights, w, config)
        ref_parts = [
            REFERENCE.experts(
                z, chosen, weights,
                {k: w[k][2 * s:2 * s + 2] for k in ("e_gate", "e_up", "e_down")},
                {**config, "experts_held": [2 * s, 2]},
            )[0]
            for s in range(8)
        ]
        assert _rel(sum(ref_parts), ref_whole) < 1e-5
        assert _rel(whole, ref_whole) < 1e-5


def test_the_tied_head_has_no_weights_of_its_own_and_its_gradient_is_the_embedding_s():
    """Tied, the tree has no ``head`` and the embedding's gradient is
    the sum of the lookup's and the head's (an untied model's two
    leaves, given the same table twice)."""
    fields = dict(vocab_size=48, d_model=32, layer_types=("conv", "full_attention", "conv"),
                  num_heads=4, num_kv_heads=2, head_dim=8, max_len=16)
    tied, untied = ShortConvMoELM(**fields), ShortConvMoELM(**fields, tie_embeddings=False)
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, 48)
    with jax.default_matmul_precision("highest"):
        params = tied.init({"params": jax.random.key(0)}, tokens)["params"]
        assert "head" not in params
        table = params["tok_embed"]["embedding"]
        loss = lambda model: lambda p: REFERENCE.next_token_loss(
            model.apply({"params": p}, tokens)[0], tokens)
        got = jax.jit(jax.grad(loss(tied)))(params)
        want = jax.jit(jax.grad(loss(untied)))({**params, "head": {"kernel": table.T}})
    both = want["tok_embed"]["embedding"] + want["head"]["kernel"].T
    assert _rel(got["tok_embed"]["embedding"], both) < 1e-5
    assert tied.head_weights(params) == (table, None, True)
    assert _rel(got["block_1"]["q_norm"]["scale"], want["block_1"]["q_norm"]["scale"]) < 1e-5


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_on_the_kernels_is_the_model_on_the_plain_path(request, remat):
    """A conv and an attention layer of 8 heads over 2 KV heads of 64
    (4 query heads a KV head, as the configuration's 32 over 8), the
    CPU device under a v5e's name: the 64-wide grouped kernels
    (interpreted) give the plain path's loss and gradients, and the
    block hands them q and k normed and rotated, no ``q_rotation``."""
    model = ShortConvMoELM(
        vocab_size=64, d_model=128, layer_types=("conv", "full_attention", "conv"),
        num_heads=8, num_kv_heads=2, head_dim=64, num_experts=4, top_k=2, max_len=256,
        remat=remat,
    )
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tokens = group.device_put(
        np.asarray(jax.random.randint(jax.random.key(1), (1, 256), 0, 64)), group.batch_sharding)
    params = group.device_put(model.init({"params": jax.random.key(0)}, tokens)["params"])
    loss = lambda: jax.jit(jax.value_and_grad(
        lambda p, t: jnp.mean(model.apply({"params": p}, t)[0] ** 2)))
    with jax.default_matmul_precision("highest"):
        want = loss()(params, tokens)
        request.getfixturevalue("as_v5e")
        on_kernels = loss()
        text = str(jax.make_jaxpr(on_kernels)(params, tokens))
        assert "grouped64_fwd" in text and "grouped64_bwd" in text
        got = on_kernels(params, tokens)
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        if np.any(np.asarray(need)):  # the selection bias's gradient is zero on both paths
            assert _rel(have, need) < 5e-5
        else:
            assert not np.any(np.asarray(have))


def test_configuration_file_is_the_catalog_row_but_for_what_it_lists():
    cell = cells.load_cell("moe-conv-t8192")
    config = cell.config
    reduced = ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
               "vocab_size", "max_position_embeddings"]
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, published in CATALOG_CONFIG.items():
        if key in config["reduced"]:
            assert config[key] != published, key
        else:
            assert config[key] == published, key
    # the cut: published layers 1 to 5, one leading dense layer and one whole period
    assert config["layer_types"] == PUBLISHED_LAYER_TYPES[1:6]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 5
    assert config["num_dense_layers"] == 1
    assert config["vocab_size"] * 8 == CATALOG_CONFIG["vocab_size"]  # an eighth, the guide's floor
    assert config["router_width"] == CATALOG_CONFIG["num_experts"] == 64
    assert config["experts_held"] == [16, 8] and config["num_experts"] == 8
    assert cell.traffic["sequence_length"] == config["max_position_embeddings"] == 8192
    assert cell.traffic["batch_sequences"] == 4
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert entry["reduced"] == reduced


def test_parameters_are_the_configuration_s_count():
    """The built model at the published widths holds what the
    configuration's file says (shapes only: nothing is allocated), and
    the published model by the same sums is the published 24B."""
    cell = cells.load_cell("moe-conv-t8192")
    model = conv_moe_lm_trial.build_model(cell.config)
    shapes = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, 256), jnp.int32)
    )["params"]
    size = lambda tree: sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
    assert size(shapes) == 469_285_248
    assert f"{size(shapes):,}" in cell.config["parameters"]
    by_layer = [size(shapes[f"block_{i}"]) for i in range(5)]
    assert by_layer == [89_139_200, 86_118_592, 92_416_064, 92_416_064, 92_416_064]
    assert "head" not in shapes and size(shapes["tok_embed"]) == 8_192 * 2_048
    conv, attention = 16_783_360, 10_485_888
    assert size({k: shapes["block_0"][k] for k in ("in_proj", "conv_w", "out_proj")}) == conv
    assert size({k: shapes["block_1"][k]
                 for k in ("q", "k", "v", "proj", "q_norm", "k_norm")}) == attention
    expert, router, norms = 9_437_184, 131_072 + 64, 2 * 2_048
    whole = (30 * conv + 10 * attention + 2 * 72_351_744 + 38 * (64 * expert + router)
             + 40 * norms + 65_536 * 2_048 + 2_048)
    assert 23.7e9 < whole < 23.9e9
    assert model.layer_types == tuple(cell.config["layer_types"]) and model.remat
    assert model.embed_stddev == cell.config["assumed"]["embedding_stddev"] == 3.0
    assert model.absent_share_grad is cell.config["assumed"]["absent_share_grad"] is False
    assert model.tie_embeddings and (model.top_k, model.num_experts) == (4, 64)
    assert ShortConvMoELM(vocab_size=64).embed_stddev is None
    assert ShortConvMoELM(vocab_size=64).absent_share_grad is True
