"""``models/ssm_hybrid.py`` (``SambaYLM``: Mamba layers, window and full
attention, gated memory units and cross-attention over one layer's
memory and k, v; a tied head) against the configuration's plain
reference, ``benchmark/configs/phi-4-mini-flash.reference.py``, at a toy
size on seeded weights; the layout's default; the memory's and the
shared k, v's gradients summed over two readers; the model on the
kernels (interpreted) against the plain path; the configuration's file
against the built model and the catalog row. Nothing is timed."""

import collections
import json
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.entries import ssm_lm_trial
from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.models.ssm_hybrid import KINDS, SambaYLM, default_layer_kinds
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, lm_loss_mean, make_lm_train_step

REFERENCE = cells.load_module("benchmark/configs/phi-4-mini-flash.reference.py")

# The configuration's keys at a toy size: 8 layers by the published rule
# (3 Mamba, 2 window, the full layer, one memory unit, one cross layer),
# d 64, 4 query heads over 2 KV heads of 16, a window shorter than T.
TINY = {
    "vocab_size": 96, "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "num_hidden_layers": 8, "mb_per_layer": 2, "sliding_window": 8,
    "layer_kinds": list(default_layer_kinds(8)), "layer_norm_eps": 1e-5,
    "max_position_embeddings": 32, "tie_word_embeddings": True,
    "assumed": {"compute_dtype": "float32", "remat": False, "d_state": 16, "d_conv": 4,
                "expand": 2, "dt_rank": 4},
}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    yield from _equations(inner)


def _kernels(jaxpr) -> int:
    """``pallas_call`` equations of a jaxpr, the nested ones with them."""
    return sum(eqn.primitive.name == "pallas_call" for eqn in _equations(jaxpr))


def _tiny(**changes):
    config = {**TINY, **{k: v for k, v in changes.items() if k != "assumed"}}
    config["assumed"] = {**TINY["assumed"], **changes.get("assumed", {})}
    return config


def _against_the_reference(config, tokens):
    """``(program, reference)``: each ``(logits, loss, gradients under
    the reference's names, ssm_state_rms, what Adam's first step added
    to the parameters)``, the program's through
    ``create_lm_state`` and ``make_lm_train_step`` as a trial runs them
    (the gradient is read back from Adam's first moment after one step,
    as the benchmark's entry reads it: a parameter's change under SGD
    would round ``A_log``'s 1e-5 away beside its 2.8)."""
    model = ssm_lm_trial.build_model(config)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    adam = optax.adam(1e-3)
    with jax.default_matmul_precision("highest"):
        state = create_lm_state(group, model, adam, jax.random.key(0))
        params = jax.tree.map(jnp.copy, state.params)
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t)[0])(params, tokens)
        after, metrics = make_lm_train_step(group, model, adam)(state, tokens)
        grads = jax.tree.map(
            lambda mu: mu / (1.0 - ssm_lm_trial.ADAM_B1),
            optax.tree_utils.tree_get(after.opt_state, "mu"),
        )
        ref = jax.jit(lambda w, t: REFERENCE.logits_loss_grads(w, t, config))(
            ssm_lm_trial.reference_weights(params, config), tokens
        )
    moved = jax.tree.map(jnp.subtract, after.params, params)
    program = (logits, metrics["loss"], ssm_lm_trial.reference_weights(grads, config),
               metrics["ssm_state_rms"], ssm_lm_trial.reference_weights(moved, config))
    return program, (ref[0], ref[1], ref[2], ref[3]["ssm_state_rms"],
                     REFERENCE.adam_first_step(program[2], 1e-3))


def _assert_agree(program, reference):
    (logits, loss, grads, rms, _), (ref_logits, ref_loss, ref_grads, ref_rms, _) = program, reference
    assert _rel(logits, ref_logits) < 1e-5
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    np.testing.assert_allclose(rms, ref_rms, rtol=1e-4)
    flat_want = jax.tree_util.tree_leaves_with_path(ref_grads)
    for (path, want), have in zip(flat_want, jax.tree.leaves(grads), strict=True):
        assert _rel(have, want) < 2e-4, (jax.tree_util.keystr(path), _rel(have, want))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_agrees_with_the_reference(remat):
    """Logits, loss, every gradient leaf (the tied embedding's among
    them: what the head and what the lookup add to it) and
    ``ssm_state_rms``, 8 layers by the published rule."""
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, TINY["vocab_size"])
    program, reference = _against_the_reference(_tiny(assumed={"remat": remat}), tokens)
    _assert_agree(program, reference)
    assert program[3].shape == (3,)  # one a Mamba layer


def test_timed_step_moves_the_parameters_by_the_reference_s_adam_step():
    """What the entry's ``param_change_rel_l2`` reads: the change of
    every leaf after one step of the trial's own Adam against the
    reference's Adam step on the gradients that step holds (float32
    rounding of a sum of 1e-3 and a weight), and 1 for a step that
    leaves the parameters as they were."""
    tokens = jax.random.randint(jax.random.key(4), (2, 32), 0, TINY["vocab_size"])
    program, reference = _against_the_reference(_tiny(), tokens)
    moved, want = jax.tree.leaves(program[4]), jax.tree.leaves(reference[4])
    for have, need in zip(moved, want, strict=True):
        assert _rel(have, need) < 1e-3
        assert _rel(jnp.zeros_like(have), need) == 1.0
    assert max(float(jnp.max(jnp.abs(have))) for have in moved) == pytest.approx(1e-3, rel=1e-2)


def test_remat_is_bit_equal_in_float32():
    tokens = jax.random.randint(jax.random.key(2), (2, 32), 0, TINY["vocab_size"])
    plain = ssm_lm_trial.build_model(TINY)
    params = plain.init({"params": jax.random.key(0)}, tokens)["params"]

    def loss_of(model):
        def loss(p):
            logits, counters = model.apply({"params": p}, tokens)
            return lm_loss_mean(logits, tokens), (logits, counters)

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    got, want = loss_of(plain.clone(remat=True)), loss_of(plain)
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(have, need)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_gradients_of_the_memory_and_the_shared_kv_sum_over_two_readers(remat):
    """Two gated memory units and two cross layers read one layer's
    memory and one layer's k and v: through ``nn.remat`` the makers'
    gradient leaves (``w_in`` of the Mamba layer, ``wqkv`` of the full
    layer) are the reference's, which sums over the readers; with one
    reader's weights zeroed they are another number."""
    kinds = ["mamba_memory", "full_kv", "gmu", "cross", "gmu", "cross"]
    config = _tiny(layer_kinds=kinds, num_hidden_layers=6, assumed={"remat": remat})
    tokens = jax.random.randint(jax.random.key(3), (2, 32), 0, TINY["vocab_size"])
    program, reference = _against_the_reference(config, tokens)
    _assert_agree(program, reference)
    # the second readers do reach the makers: without them the makers' gradients differ
    model = ssm_lm_trial.build_model(config)
    params = model.init({"params": jax.random.key(0)}, tokens)["params"]
    loss = lambda p: lm_loss_mean(model.apply({"params": p}, tokens)[0], tokens)
    deaf = jax.tree.map(jnp.copy, params)
    deaf["block_4"]["out_proj"]["kernel"] = jnp.zeros_like(deaf["block_4"]["out_proj"]["kernel"])
    deaf["block_5"]["proj"]["kernel"] = jnp.zeros_like(deaf["block_5"]["proj"]["kernel"])
    both, one = jax.grad(loss)(params), jax.grad(loss)(deaf)
    assert _rel(one["block_0"]["in_proj"]["kernel"], both["block_0"]["in_proj"]["kernel"]) > 1e-2
    assert _rel(one["block_1"]["qkv"]["kernel"], both["block_1"]["qkv"]["kernel"]) > 1e-2


def test_the_tied_head_has_no_weights_of_its_own_and_its_gradient_is_the_embedding_s():
    tokens = jax.random.randint(jax.random.key(4), (2, 16), 0, 96)
    small = dict(vocab_size=96, num_layers=4)  # mamba, window, mamba_memory, full_kv
    tied, untied = SambaYLM(**small), SambaYLM(**small, tie_embeddings=False)
    params = tied.init({"params": jax.random.key(0)}, tokens)["params"]
    own = untied.init({"params": jax.random.key(0)}, tokens)["params"]
    assert "head" not in params and own["head"]["kernel"].shape == (64, 96)
    assert set(own["head"]) == {"kernel"}  # no bias
    # the same numbers once the untied head holds the embedding's transpose
    own = {**params, "head": {"kernel": params["tok_embed"]["embedding"].T}}
    loss = lambda model: lambda p: lm_loss_mean(model.apply({"params": p}, tokens)[0], tokens)
    with jax.default_matmul_precision("highest"):
        g_tied, g_own = jax.grad(loss(tied))(params), jax.grad(loss(untied))(own)
    want = g_own["tok_embed"]["embedding"] + g_own["head"]["kernel"].T
    assert _rel(g_tied["tok_embed"]["embedding"], want) < 1e-5


def test_default_layout_is_the_published_one():
    kinds = default_layer_kinds(32, 2)
    assert [kinds.count(k) for k in KINDS] == [8, 8, 1, 1, 7, 7]  # 9 Mamba with the memory's
    assert [i for i, k in enumerate(kinds) if k in ("mamba", "mamba_memory")] == list(range(0, 17, 2))
    assert kinds[16] == "mamba_memory" and kinds[17] == "full_kv"
    assert [i for i, k in enumerate(kinds) if k == "window"] == list(range(1, 16, 2))
    assert [i for i, k in enumerate(kinds) if k == "gmu"] == list(range(18, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == "cross"] == list(range(19, 32, 2))
    assert SambaYLM(vocab_size=8, num_layers=32).kinds() == kinds
    assert default_layer_kinds(8) == (
        "mamba", "window", "mamba", "window", "mamba_memory", "full_kv", "gmu", "cross")
    with pytest.raises(ValueError, match="multiple of 4"):
        default_layer_kinds(6)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="before the layer that makes"):
        SambaYLM(vocab_size=8, layer_kinds=("gmu", "mamba_memory")).init(
            {"params": jax.random.key(0)}, tokens)


def test_starts_of_the_scan_s_own_weights():
    """``A_log`` is ``log(1..N)`` a channel, ``dt``'s bias gives steps
    in [0.001, 0.1] through ``softplus``, ``D`` is 1."""
    params = SambaYLM(vocab_size=8).init({"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))
    mamba = params["params"]["block_0"]
    np.testing.assert_allclose(jnp.exp(mamba["A_log"]), jnp.tile(jnp.arange(1.0, 17.0), (128, 1)),
                               rtol=1e-6)
    steps = jax.nn.softplus(mamba["dt_bias"])
    assert 1e-3 * 0.999 <= float(steps.min()) and float(steps.max()) <= 1e-1 * 1.001
    assert float(steps.max()) > 10 * float(steps.min())  # spread, not one value
    np.testing.assert_array_equal(mamba["D"], jnp.ones((128,)))
    assert set(mamba) == {"ln_attn", "in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
                          "A_log", "D", "out_proj", "ln_mlp", "gate", "up", "down"}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_on_the_kernels_is_the_model_on_the_plain_path(request, remat):
    """One layer of each kind at heads 64 wide and 512 channels, the CPU
    device under a v5e's name: the scan's kernel pair and the 64-wide
    grouped kernels (interpreted; a window, none, and k, v from another
    layer) give the plain path's loss and gradients."""
    fields = dict(
        vocab_size=64, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64, mlp_width=128,
        layer_kinds=("mamba", "window", "mamba_memory", "full_kv", "gmu", "cross"), window=100,
        max_len=256, remat=remat,
    )
    model = SambaYLM(**fields)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tokens = group.device_put(
        np.asarray(jax.random.randint(jax.random.key(1), (1, 256), 0, 64)), group.batch_sharding)
    params = group.device_put(model.init({"params": jax.random.key(0)}, tokens)["params"])
    loss = jax.jit(jax.value_and_grad(
        lambda p, t: jnp.mean(model.apply({"params": p}, t)[0] ** 2)))
    with jax.default_matmul_precision("highest"):
        want = loss(params, tokens)
        request.getfixturevalue("as_v5e")  # the attention's rules and the scan's own
        on_kernels = jax.jit(jax.value_and_grad(
            lambda p, t: jnp.mean(model.apply({"params": p}, t)[0] ** 2)))
        # a forward and a backward kernel a Mamba and an attention layer, however nested
        assert _kernels(jax.make_jaxpr(on_kernels)(params, tokens).jaxpr) >= 10
        got = on_kernels(params, tokens)
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert _rel(have, need) < 5e-5


V5E = "TPU v5 lite"
SIX_KINDS = ("mamba", "window", "mamba_memory", "full_kv", "gmu", "cross")


def _recomputed(jaxpr) -> collections.Counter:
    """Equations inside the gradient's recomputed blocks, by primitive."""
    inside = collections.Counter()
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "remat2":
            inside.update(e.primitive.name for e in _equations(eqn.params["jaxpr"]))
    return inside


def _without_mlp_hidden():
    """``remat_block``'s policy less ``SAVED_MLP_HIDDEN``: what the
    parent's recomputed ``SambaYBlock`` kept."""
    return jax.checkpoint_policies.save_only_these_names(
        decoder.SAVED_OUT, decoder.SAVED_LSE, decoder.SAVED_MAPS, decoder.SAVED_Y,
        decoder.SAVED_RESIDUAL, decoder.SAVED_ROUTING, decoder.SAVED_QKV,
        decoder.SAVED_SCAN_OUT, decoder.SAVED_SCAN_STATES)


@pytest.mark.parametrize(
    "device_kind, devices",
    [(V5E, 1), (V5E, 4), ("cpu", 1)],
    ids=["one-v5e-chip", "four-v5e-chips", "one-cpu-device"],
)
def test_one_chip_block_keeps_gate(request, monkeypatch, device_kind, devices):
    """On one TPU chip ``SambaYBlock`` names gate's output before
    ``silu`` (up's is made again: both do not fit), and the policy
    keeps it: each layer's recomputed block holds one product fewer
    than under the policy without the name (the parent's). Over several
    chips and off the TPU nothing is named there, and the recomputed
    blocks are the parent's. Against ``nn.remat`` with no policy the
    kept stream after the mixer spares the same products everywhere."""
    if device_kind == V5E:
        request.getfixturevalue("as_v5e")
    (group,) = setup_groups(1, devices=jax.devices()[:devices])
    model = SambaYLM(vocab_size=64, layer_kinds=SIX_KINDS, mlp_width=128, remat=True)
    state = create_lm_state(group, model, optax.sgd(1.0), jax.random.key(0))
    tokens = group.device_put(np.zeros((4, 32), np.int32), group.batch_sharding)

    def gradient():  # traced anew each time: make_jaxpr remembers a function's trace
        summed = lambda p, t: model.apply({"params": p}, t)[0].sum()
        return jax.make_jaxpr(jax.grad(summed))(state.params, tokens)

    kept = gradient()
    named = collections.Counter(
        e.params["name"] for e in _equations(kept) if e.primitive.name == "name")
    monkeypatch.setattr(decoder, "_KEEP_ACROSS_REMAT", _without_mlp_hidden())
    parent = _recomputed(gradient())
    monkeypatch.setattr(decoder, "remat_block", nn.remat)
    bare = _recomputed(gradient())
    kept = _recomputed(kept)
    layers = len(SIX_KINDS)
    assert bare["dot_general"] - parent["dot_general"] == layers + 2  # the mixers' outputs
    if (device_kind, devices) == (V5E, 1):
        assert named[decoder.SAVED_MLP_HIDDEN] == layers
        assert parent["dot_general"] - kept["dot_general"] == layers  # gate
        assert kept["logistic"] == parent["logistic"]  # silu made again from the kept gate
    else:
        assert decoder.SAVED_MLP_HIDDEN not in named
        assert kept == parent


def _loss_and_grads(model, tokens, group):
    """Loss and gradients of a seeded ``model`` on ``tokens``, placed by
    ``group`` where given, rounded where the program says so."""
    params = model.init({"params": jax.random.key(0)}, tokens)["params"]
    if group is not None:
        params, tokens = group.device_put(params), group.device_put(tokens, group.batch_sharding)
    loss = lambda p: lm_loss_mean(model.apply({"params": p}, tokens)[0], tokens)
    # XLA may keep bf16 values at f32 between the operations it fuses,
    # and fuses a recomputed block otherwise than the forward's
    step = jax.jit(jax.value_and_grad(loss)).lower(params)
    return step.compile(compiler_options={"xla_allow_excess_precision": False})(params)


@pytest.mark.parametrize("placed", [False, True], ids=["unplaced", "one-v5e-chip"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_kept_gate_leaves_the_gradients_bit_equal(request, monkeypatch, dtype, placed):
    """What the policy keeps of the MLP is what the recomputed block
    would have made again: loss and every gradient leaf equal to the
    last bit under ``remat_block``, under ``nn.remat`` with no policy
    and with no remat at all, placed on one TPU chip (gate's output
    named) and not."""
    group = None
    if placed:
        request.getfixturevalue("as_v5e")
        (group,) = setup_groups(1, devices=jax.devices()[:1])
    tokens = jax.random.randint(jax.random.key(5), (2, 32), 0, 64)
    make = lambda remat: SambaYLM(
        vocab_size=64, layer_kinds=SIX_KINDS, mlp_width=128, dtype=dtype, remat=remat)
    plain = _loss_and_grads(make(False), tokens, group)
    saved = _loss_and_grads(make(True), tokens, group)
    monkeypatch.setattr(decoder, "remat_block", nn.remat)  # only a block's input is saved
    bare = _loss_and_grads(make(True), tokens, group)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(saved[1]))
    for other in (bare, plain):
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(other), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# The catalog row's ``config`` (``architectures.jsonl`` beside the
# model-configs guide, ``Phi-4-mini-flash-reasoning``), copied.
CATALOG_CONFIG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
}


def test_configuration_file_is_the_catalog_row_but_for_what_it_lists():
    cell = cells.load_cell("ssm-yoco-t16384")
    config = cell.config
    assert sorted(config["reduced"]) == ["max_position_embeddings", "num_hidden_layers", "vocab_size"]
    for key, published in CATALOG_CONFIG.items():
        if key in config["reduced"]:
            assert config[key] != published and str(f"{published:,}") in config["reduced"][key] \
                or str(published) in config["reduced"][key], key
        else:
            assert config[key] == published, key
    assert config["vocab_size"] * 8 == CATALOG_CONFIG["vocab_size"]  # an eighth, the guide's floor
    assert config["num_hidden_layers"] == len(config["layer_kinds"]) == 6
    published = default_layer_kinds(32, config["mb_per_layer"])
    assert [published[i] for i in config["published_layers_held"]] == config["layer_kinds"]
    assert sorted(config["layer_kinds"]) == sorted(KINDS)  # one layer of each kind
    assert cell.traffic["sequence_length"] == config["max_position_embeddings"] == 16384
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == "phi-4-mini-flash"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size", "max_position_embeddings"]


def test_parameters_are_the_configuration_s_count():
    """The built model at the published widths holds what the
    configuration's file says (shapes only: nothing is allocated), and
    a layer of each kind what the published model's count is made of."""
    cell = cells.load_cell("ssm-yoco-t16384")
    model = ssm_lm_trial.build_model(cell.config)
    shapes = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, 256), jnp.int32)
    )["params"]
    size = lambda tree: sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
    assert size(shapes) == 697_072_640
    assert f"{size(shapes):,}" in cell.config["parameters"]
    mlp_and_norms = 78_643_200 + 10_240
    by_kind = dict(zip(cell.config["layer_kinds"], (size(shapes[f"block_{i}"]) for i in range(6))))
    assert by_kind == {
        "mamba": 41_241_600 + mlp_and_norms, "mamba_memory": 41_241_600 + mlp_and_norms,
        "window": 19_660_800 + mlp_and_norms, "full_kv": 19_660_800 + mlp_and_norms,
        "gmu": 26_214_400 + mlp_and_norms, "cross": 13_107_200 + mlp_and_norms,
    }
    assert "head" not in shapes and size(shapes["tok_embed"]) == 25_008 * 2_560
    # the published model by the same sums: 3.85B
    whole = (9 * by_kind["mamba"] + 9 * by_kind["window"] + 7 * by_kind["gmu"]
             + 7 * by_kind["cross"] + 200_064 * 2_560 + 2 * 2_560)
    assert 3.84e9 < whole < 3.86e9
    assert model.kinds() == tuple(cell.config["layer_kinds"]) and model.remat
    assert (model.d_state, model.d_conv, model.expand, model.dt_rank) == (16, 4, 2, 160)
