"""Which attention a model runs when none is injected.

``ops/attention.py`` decides while tracing, from what it can see of the
operands (``parallel/mesh.py::placement``: device kind and device count
of their mesh) and from their shapes, by asking
``ops.pallas_attention.default_takes_kernel``. The CPU suite's models
stay dense; these tests ask the rule as a plain function, and reach the
kernel path of a whole step by telling ``placement`` that the virtual
CPU devices are a TPU (``as_v5e``: the device count stays the real one).
Counts in jaxprs only; nothing is timed.
"""

import collections
import hashlib
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.models.transformer import (
    MoETransformerLM,
    TransformerLM,
    transformer_tp_shardings,
)
from multidisttorch_tpu.ops.pallas_attention import (
    SAVED_LSE,
    SAVED_OUT,
    default_takes_kernel,
    grouped_attention,
    latent_takes_kernel,
    make_flash_attention,
)
from multidisttorch_tpu.parallel import mesh
from multidisttorch_tpu.parallel.mesh import MODEL_AXIS, setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step
from multidisttorch_tpu.train.steps import state_shardings

V5E = "TPU v5 lite"
T, LAYERS = 256, 2
CFG = dict(vocab_size=64, d_model=128, num_heads=2, num_layers=LAYERS, max_len=T)


@pytest.mark.parametrize(
    "device_kind, num_devices, seq_len, num_heads, head_dim, kernel",
    [
        (V5E, 1, 1024, 16, 64, True),  # lm-dense
        (V5E, 1, 256, 16, 64, True),  # lm-short-t256: the shortest length raced
        (V5E, 1, 4096, 5, 128, True),
        ("TPU v4", 1, 512, 8, 64, True),
        ("cpu", 1, 1024, 16, 64, False),  # the CPU suite, interpreter or not
        ("NVIDIA H100", 1, 1024, 16, 64, False),
        (V5E, 4, 1024, 16, 64, False),  # a batch or heads split over chips
        (V5E, 2, 256, 16, 64, False),
        (V5E, 1, 128, 16, 64, False),  # shorter than anything raced
        (V5E, 1, 200, 16, 64, False),  # 128 does not divide it
        (V5E, 1, 1100, 16, 64, False),
        (V5E, 1, 1024, 25, 64, False),  # GPT-2 XL's heads do not pair up
        (V5E, 1, 1024, 16, 32, False),  # widths not run on the chip
        (V5E, 1, 1024, 16, 96, False),
        (V5E, 1, 1024, 4, 256, False),
    ],
)
def test_rule(device_kind, num_devices, seq_len, num_heads, head_dim, kernel):
    assert default_takes_kernel(
        device_kind, num_devices, seq_len, num_heads, head_dim
    ) is kernel


@pytest.mark.parametrize(
    "num_devices, seq_len, head_dim, v_head_dim, kernel",
    [
        (1, 4096, 192, 128, True),  # latent attention: moe-mla-t4096
        (1, 256, 192, 128, True),
        (4, 4096, 192, 128, False),  # over several chips it is dense, as every width
        (1, 200, 192, 128, False),
        (1, 4096, 192, 192, False),  # pairs of widths not run on the chip
        (1, 4096, 128, 64, False),
        (1, 4096, 64, 128, False),
        (1, 1024, 64, 64, True),  # v's width given and equal: the rule above
    ],
)
def test_rule_with_a_width_of_its_own_for_v(num_devices, seq_len, head_dim, v_head_dim, kernel):
    assert default_takes_kernel(V5E, num_devices, seq_len, 32, head_dim, v_head_dim) is kernel


@pytest.mark.parametrize(
    "num_devices, seq_len, num_heads, nope, rope, dv, kernel",
    [
        (1, 4096, 32, 128, 64, 128, True),  # moe-mla-t4096
        (1, 256, 2, 128, 64, 128, True),
        (4, 4096, 32, 128, 64, 128, False),  # where the assembled widths get no kernel
        (1, 200, 32, 128, 64, 128, False),
        (1, 4096, 31, 128, 64, 128, False),  # the rotary parts' heads do not pair up
        (1, 4096, 32, 160, 32, 128, False),  # 192 together, but not parts the kernels tile
        (1, 4096, 32, 16, 8, 16, False),  # the toy widths of tests and examples
    ],
)
def test_rule_for_the_parts_of_latent_attention(
    num_devices, seq_len, num_heads, nope, rope, dv, kernel
):
    assert latent_takes_kernel(V5E, num_devices, seq_len, num_heads, nope, rope, dv) is kernel
    assert not latent_takes_kernel("cpu", num_devices, seq_len, num_heads, nope, rope, dv)


def _latent_lm(**fields):
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    return LatentMoELM(
        vocab_size=64, d_model=128, num_heads=2, num_layers=LAYERS, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, max_len=T, **fields,
    )


def _assembled_heads(jaxpr, batch=4):
    """Shapes of what ``pad`` and ``concatenate`` make as ``(B, T,
    heads, width)`` with a head of 192 (q or k assembled) or 256
    (padded for the kernel) lanes."""
    return [
        (eqn.primitive.name, var.aval.shape)
        for eqn in _equations(jaxpr) if eqn.primitive.name in ("pad", "concatenate")
        for var in eqn.outvars
        if len(var.aval.shape) == 4 and var.aval.shape[:2] == (batch, T)
        and var.aval.shape[-1] in (192, 256)
    ]


def _kernels(jaxpr):
    return collections.Counter(
        eqn.params["name"] for eqn in _equations(jaxpr) if eqn.primitive.name == "pallas_call"
    )


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_one_chip_latent_attention_step_runs_the_kernel(as_v5e, remat):
    """``LatentMoELM`` given no attention: q and k 128 + 64 wide, v
    128. On one chip the kernel on the parts of q and k as the
    projections make them, one forward a block (under remat too: its
    output and logsumexp are saved) and one fused backward, and neither
    q nor k assembled or padded anywhere; over four chips, as on the
    CPU, dense on the assembled q and k."""
    model = _latent_lm(remat=remat)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    jaxpr = _step_jaxpr(group, model)
    # beside them the expert layer's sums by token (d_model is whole lanes)
    assert _kernels(jaxpr) == {"latent_fwd": LAYERS, "latent_bwd": LAYERS, "token_sums": 2}
    assert not _assembled_heads(jaxpr)
    (four,) = setup_groups(1, devices=jax.devices()[:4])
    assert _count(_step_jaxpr(four, model), "pallas_call") == 0


def test_injected_attention_gets_latent_attention_assembled(as_v5e):
    """An ``attention=`` of the ``(q, k, v)`` kind is handed q and k at
    192 a head, on one chip too: the kernel at the padded width."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    jaxpr = _step_jaxpr(group, _latent_lm(attention=make_flash_attention(causal=True)))
    assert _kernels(jaxpr) == {"flash_fwd": LAYERS, "flash_bwd": LAYERS, "token_sums": 2}
    made = _assembled_heads(jaxpr)
    assert ("concatenate", (4, T, 2, 192)) in made and ("pad", (4, T, 2, 256)) in made


# The latent-attention LM's step (remat) where the parts' rule says no,
# equation by primitive: counted at the parent of the PR that brought
# the kernel on the parts (PR 30), and again at PR 32, whose expert
# layer sums over the buffer's rows (a sort of every slot, a gather
# and a product over them less; a scatter of the weights' gradient
# and one scatter-add more), and at PR 34, whose remat rule keeps the
# stream after attention and the router's results (seven names, four of
# them floats that jax rounds where they are kept; out of the recomputed
# blocks went ``proj`` twice and the expert layer's router product,
# top-k, gather of the picked scores, sort into expert order and count).
_ASSEMBLED_STEP = {
    "add": 222, "add_any": 49, "and": 13, "broadcast_in_dim": 230, "concatenate": 27,
    "convert_element_type": 41, "cos": 8, "cumsum": 2, "div": 157, "dot_general": 82,
    "dynamic_slice": 2, "eq": 17, "exp": 5, "gather": 12, "ge": 1, "integer_pow": 67, "iota": 25,
    "jit": 101, "le": 4, "log": 1, "logistic": 8, "lt": 31, "max": 9, "min": 4, "mul": 353,
    "name": 7, "ne": 24, "neg": 26, "pad": 29, "pow": 10, "ragged_dot_general": 8,
    "reduce_max": 5, "reduce_precision": 4, "reduce_sum": 69, "rem": 12, "remat2": 2,
    "reshape": 94, "reshard": 7, "rsqrt": 17, "scatter": 1, "scatter-add": 7, "select_n": 59,
    "sign": 8, "sin": 8, "slice": 60, "sort": 1, "split": 13, "sqrt": 32, "square": 17,
    "squeeze": 1, "stop_gradient": 6, "sub": 42, "top_k": 1, "transpose": 32,
}


# What the step on one device holds otherwise since PR 38: the head and
# the loss as one walk (``ops/head_loss.py``: the logsumexp, the logits'
# gradient and the head's two backward products written out in the
# forward rule) where the four-device step differentiates ``nn.Dense``
# and ``lm_loss_mean``.
_THE_WALK = {
    "add": 1, "add_any": -1, "broadcast_in_dim": -1, "convert_element_type": 2, "div": -4,
    "eq": 1, "exp": 1, "gather": -1, "iota": 1, "jit": -3, "lt": -1, "max": -1, "mul": 1,
    "neg": -3, "pad": -1, "reduce_sum": -2, "reshape": 2, "scatter-add": -1,
    "stop_gradient": -1, "sub": 2, "transpose": -1,
}


@pytest.mark.parametrize("devices", [1, 4])
def test_latent_attention_step_elsewhere_is_the_assembled_one(devices, request):
    """On the CPU, and over four chips whatever their kind, the step is
    the program it was before the parts had a kernel, primitive for
    primitive (on one device with the walk in the head's and the loss's
    place); plain and remat differ in the recomputation alone."""
    (group,) = setup_groups(1, devices=jax.devices()[:devices])
    counts = _counts(_step_jaxpr(group, _latent_lm(remat=True)))
    walk = _THE_WALK if devices == 1 else {}
    assert dict(counts) == {k: n + walk.get(k, 0) for k, n in _ASSEMBLED_STEP.items()}
    if devices == 4:
        request.getfixturevalue("as_v5e")
        assert _counts(_step_jaxpr(group, _latent_lm(remat=True))) == counts
    plain = sum(_counts(_step_jaxpr(group, _latent_lm())).values())
    assert plain == 1616 + 7 + sum(walk.values())  # the names


# The parameter tree of the latent-attention LM: what checkpoints and
# ``benchmark/entries/moe_lm_trial.py::reference_weights`` read by name.
_LATENT_ATTENTION_TREE = {
    "ln_attn": {"scale": (128,)},
    "q_a": {"kernel": (128, 48)}, "q_norm": {"scale": (48,)}, "q_b": {"kernel": (48, 2 * 192)},
    "kv_a": {"kernel": (128, 32 + 64)}, "kv_norm": {"scale": (32,)},
    "kv_b": {"kernel": (32, 2 * 256)},
    "proj": {"kernel": (2 * 128, 128)},
    "ln_mlp": {"scale": (128,)},
}


def test_both_paths_share_one_parameter_tree_and_one_init(request):
    """Names, shapes and, at one seed, the initial values to the bit:
    whether ``model.init`` traces the assembled path (as it does on
    every backend: nobody placed its dummy batch) or the kernel on the
    parts."""
    model, tokens = _latent_lm(), jnp.zeros((2, T), jnp.int32)
    assembled = model.init(jax.random.key(3), tokens)["params"]
    assert set(assembled) == {"tok_embed", "ln_out", "head", "block_0", "block_1"}
    shapes = jax.tree.map(jnp.shape, assembled)
    assert {k: v for k, v in shapes["block_0"].items() if k in _LATENT_ATTENTION_TREE} == (
        _LATENT_ATTENTION_TREE
    )
    assert set(shapes["block_0"]) - set(_LATENT_ATTENTION_TREE) == {"gate", "up", "down"}
    assert set(shapes["block_1"]) - set(_LATENT_ATTENTION_TREE) == {"moe"}
    request.getfixturevalue("as_v5e")  # the unplaced dummy batch read as one chip
    traced = jax.make_jaxpr(lambda: model.init(jax.random.key(3), tokens))()
    assert _kernels(traced) == {"latent_fwd": LAYERS, "token_sums": 1}  # the expert layer's output
    by_parts = model.init(jax.random.key(3), tokens)["params"]
    assert jax.tree.structure(by_parts) == jax.tree.structure(assembled)
    for a, b in zip(jax.tree.leaves(by_parts), jax.tree.leaves(assembled), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "dtype, tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 1e-1)], ids=["f32", "bf16"]
)
def test_kernel_on_the_parts_matches_the_assembled_path(request, dtype, tol):
    """Loss and every gradient leaf of the two-block LM: the kernel on
    the parts (interpreted), with remat and without, against the dense
    path on the assembled q and k."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tokens = group.device_put(
        np.random.default_rng(0).integers(0, 64, (2, T)).astype(np.int32), group.batch_sharding
    )
    params = group.device_put(_latent_lm().init(jax.random.key(0), tokens)["params"])

    def loss_and_grads(model):
        def loss(p):
            logits, _ = model.apply({"params": p}, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            ).mean()

        step = jax.jit(jax.value_and_grad(loss))
        return _kernels(jax.make_jaxpr(step)(params)), step(params)

    kernels, (want, want_grads) = loss_and_grads(_latent_lm(dtype=dtype))
    assert not kernels
    request.getfixturevalue("as_v5e")
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    for remat in (False, True):
        kernels, (got, grads) = loss_and_grads(_latent_lm(dtype=dtype, remat=remat))
        assert kernels == {"latent_fwd": LAYERS, "latent_bwd": LAYERS, "token_sums": 2}
        assert abs(float(got) - float(want)) < tol * float(want)
        worst = max(jax.tree.leaves(jax.tree.map(rel, grads, want_grads)))
        assert worst < tol, worst


@pytest.mark.parametrize(
    "device_kind, num_devices, rows, k, n, kernel",
    [
        (V5E, 1, 16384, 2048, 768, True),  # moe-mla-t4096: gate and up
        (V5E, 1, 16384, 768, 2048, True),  # down
        ("cpu", 1, 16384, 2048, 768, False),
        (V5E, 4, 16384, 2048, 768, False),  # no partitioning rule around the kernel
        (V5E, 1, 16000, 2048, 768, False),  # not whole tiles of rows
        (V5E, 1, 16384, 2048, 32, False),  # not whole lanes
    ],
)
def test_grouped_dot_rule(device_kind, num_devices, rows, k, n, kernel):
    from multidisttorch_tpu.ops.moe import grouped_dot_takes_kernel

    assert grouped_dot_takes_kernel(device_kind, num_devices, rows, k, n) is kernel


def test_one_chip_expert_layer_runs_the_grouped_kernel(as_v5e):
    """On one chip, at shapes the kernel tiles, the experts' products
    are Pallas calls too: two forward (gate and up as one, then down)
    and for each of them the two of its backward, and so are the two
    sums of the buffer's rows by token (the layer's output, and the
    gradient of its input); over several chips, as on the CPU, they are
    XLA's ragged dot and scatter-add."""
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    model = LatentMoELM(
        vocab_size=64, d_model=128, num_heads=2, num_layers=2, max_len=T,
        expert_hidden_dim=128, num_experts=4, top_k=2,
    )
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    # T = 256 with widths 24/16: the attention stays dense, so every call is the experts'
    jaxpr = _step_jaxpr(group, model)
    assert _kernels(jaxpr)["token_sums"] == 2
    assert _count(jaxpr, "pallas_call") == 2 * 3 + 2
    (four,) = setup_groups(1, devices=jax.devices()[:4])
    assert _count(_step_jaxpr(four, model), "pallas_call") == 0


def test_cpu_latent_attention_stays_dense():
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    model = LatentMoELM(vocab_size=64, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, max_len=T)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    assert _count(_step_jaxpr(group, model), "pallas_call") == 0


def _equations(jaxpr, kernels=True):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, call
    sites of shared inner jaxprs (``jit``, ``remat``, ``custom_vjp``,
    ``scan``) one by one; ``kernels=False`` stays out of a
    ``pallas_call``'s body."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not kernels:
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if hasattr(sub, "eqns") or hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    yield from _equations(sub, kernels)


def _counts(jaxpr) -> collections.Counter:
    """Equations of ``jaxpr`` by primitive."""
    return collections.Counter(eqn.primitive.name for eqn in _equations(jaxpr))


def _count(jaxpr, primitive: str) -> int:
    return _counts(jaxpr)[primitive]


def _step_jaxpr(group, model, param_shardings=None, batch=4, t=T):
    tx = optax.adam(1e-3)
    state = create_lm_state(
        group, model, tx, jax.random.key(0), param_shardings=param_shardings
    )
    step = make_lm_train_step(
        group, model, tx,
        shardings=None if param_shardings is None else state_shardings(state),
    )
    tokens = group.device_put(np.zeros((batch, t), np.int32), group.batch_sharding)
    return jax.make_jaxpr(step)(state, tokens)


def test_placement_is_what_the_state_and_batch_were_put_on():
    seen = []
    spy = lambda q, k, v: seen.append(mesh.placement(q)) or q
    for n in (1, 4):
        (group,) = setup_groups(1, devices=jax.devices()[:n])
        _step_jaxpr(group, TransformerLM(attention=spy, remat=True, **CFG))
    # None: model.init's dummy batch, which nobody placed. Shapes alone
    # or such an array show nothing, and the default is dense there.
    assert set(seen) == {None, ("cpu", 1), ("cpu", 4)}
    assert mesh.placement(jnp.zeros((2, 4))) is None


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("model_cls", [TransformerLM, MoETransformerLM], ids=["dense", "moe"])
def test_one_chip_step_runs_the_kernel(as_v5e, model_cls, remat):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    jaxpr = _step_jaxpr(group, model_cls(remat=remat, **CFG))
    # a forward kernel and one fused backward kernel a block: where
    # remat runs a block's forward again, the kernel's output and
    # logsumexp were saved and the kernel is not in it
    assert _count(jaxpr, "pallas_call") == LAYERS * 2
    forward = jax.make_jaxpr(
        lambda p, t: model_cls(**CFG).apply({"params": p}, t)
    )(*_params_and_tokens(group, model_cls(**CFG)))
    assert _count(forward, "pallas_call") == LAYERS


def _params_and_tokens(group, model, t=T):
    state = create_lm_state(group, model, optax.sgd(1.0), jax.random.key(0))
    return state.params, group.device_put(np.zeros((4, t), np.int32), group.batch_sharding)


def test_cpu_default_stays_dense():
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    assert _count(_step_jaxpr(group, TransformerLM(remat=True, **CFG)), "pallas_call") == 0


@pytest.mark.parametrize(
    "why, devices, t",
    [
        ("a T that 128 does not divide", 1, 200),
        ("a data-parallel batch over four chips", 4, T),
    ],
)
def test_falls_back_to_dense(as_v5e, why, devices, t):
    (group,) = setup_groups(1, devices=jax.devices()[:devices])
    model = TransformerLM(**dict(CFG, max_len=max(T, t)))
    assert _count(_step_jaxpr(group, model, t=t), "pallas_call") == 0, why


def test_heads_sharded_by_auto_tp_stay_dense(as_v5e):
    """``transformer_tp_shardings(..., "auto")`` reads ``attention is
    None`` as per-head local and shards q/k/v/proj over the model axis:
    the default must then be the dense path, which GSPMD partitions
    over heads, not a kernel it would gather the heads for."""
    (group,) = setup_groups(1, devices=jax.devices()[:4], model_parallel=2)
    model = TransformerLM(**CFG)
    shardings = transformer_tp_shardings(group, model)
    assert MODEL_AXIS in tuple(shardings["block_0"]["q"]["kernel"].spec)
    assert _count(_step_jaxpr(group, model, shardings), "pallas_call") == 0
    # asked for by name it is the kernel, and auto keeps heads whole
    flash = TransformerLM(attention=make_flash_attention(causal=True), **CFG)
    unsharded = transformer_tp_shardings(group, flash)
    assert MODEL_AXIS not in tuple(unsharded["block_0"]["q"]["kernel"].spec)
    assert _count(_step_jaxpr(group, flash, unsharded), "pallas_call") == 2 * LAYERS


def test_default_and_injected_flash_are_the_same_program(as_v5e):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    default = _step_jaxpr(group, TransformerLM(remat=True, **CFG))
    injected = _step_jaxpr(
        group, TransformerLM(remat=True, attention=make_flash_attention(causal=True), **CFG)
    )
    assert str(default) == str(injected)


# --- what rematerialization keeps (decoder.remat_block) ---


def _loss_and_grads(model, init=TransformerLM(**CFG), t=T, group=None):
    """One jitted ``value_and_grad`` of an LM (by default a two-block
    one over the kernel, interpreted), parameters and inputs from fixed
    seeds; with ``group`` both placed on it, as a trial places them, so
    that the blocks see the mesh."""
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, t)), jnp.int32)
    params = init.init(jax.random.key(0), tokens)["params"]
    if group is not None:
        params, tokens = group.device_put(params), group.device_put(tokens, group.batch_sharding)

    def loss(p):
        out = model.apply({"params": p}, tokens)
        logits = (out[0] if isinstance(out, tuple) else out).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        ).mean()

    # XLA may keep bf16 values at f32 between the operations it fuses,
    # and fuses a recomputed block otherwise than the forward's; the
    # interpreted kernel is such operations too. Rounding where the
    # program says so leaves only the program to compare.
    step = jax.jit(jax.value_and_grad(loss)).lower(params)
    return step.compile(compiler_options={"xla_allow_excess_precision": False})(params)


@pytest.mark.parametrize("placed", [False, True], ids=["unplaced", "one-v5e-chip"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_saved_kernel_results_leave_the_gradients_bit_equal(request, monkeypatch, dtype, placed):
    """What the policy saves is what the second forward call would have
    made again: loss and every gradient leaf equal to the last bit
    under the models' remat rule, under ``nn.remat`` with no policy,
    and with no remat at all. Placed on one TPU chip the blocks also
    keep q, k, v and ``up``'s output, and the same holds."""
    group = None
    if placed:
        request.getfixturevalue("as_v5e")
        (group,) = setup_groups(1, devices=jax.devices()[:1])
    make = lambda remat: TransformerLM(
        attention=make_flash_attention(causal=True), dtype=dtype, remat=remat, **CFG
    )
    plain = _loss_and_grads(make(False), group=group)
    saved = _loss_and_grads(make(True), group=group)
    monkeypatch.setattr(decoder, "remat_block", nn.remat)  # only a block's input is saved
    bare = _loss_and_grads(make(True), group=group)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(saved[1]))
    for other in (bare, plain):
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(other), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_policy_is_inert_on_the_dense_path(monkeypatch):
    """A CPU-default ``TransformerLM(remat=True)`` has no kernel and so
    none of the names the policy saves: the step is the program
    ``nn.remat`` with no policy gives, primitive for primitive, and
    apart from the ``checkpoint`` equations' ``policy`` equation for
    equation."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = TransformerLM(remat=True, **CFG)
    with_policy = _step_jaxpr(group, model)
    monkeypatch.setattr(decoder, "remat_block", nn.remat)
    bare = _step_jaxpr(group, model)
    counts = _counts(bare)
    assert _counts(with_policy) == counts
    assert counts["remat2"] == LAYERS and not counts["pallas_call"]
    policy = re.compile(r"policy=[^\n\]]*")
    assert policy.findall(str(with_policy)) != policy.findall(str(bare))
    assert policy.sub("", str(with_policy)) == policy.sub("", str(bare))


@pytest.mark.parametrize(
    "device_kind, devices",
    [(V5E, 1), (V5E, 4), ("cpu", 1)],
    ids=["one-v5e-chip", "four-v5e-chips", "one-cpu-device"],
)
def test_one_chip_block_recomputes_proj_alone(request, monkeypatch, device_kind, devices):
    """On one TPU chip ``Block`` names q, k and v as the projections
    write them and ``up``'s output before ``gelu``, and the policy
    keeps them: of a recomputed block's products only ``proj`` is made
    again (``ln_mlp`` reads its sum), beside the backward's two products
    of each of its six matrices. Over several chips and off the TPU
    nothing is named, and the recomputed blocks are bare ``nn.remat``'s,
    the parent's (the CPU's dense attention adds its own products)."""
    if device_kind == V5E:
        request.getfixturevalue("as_v5e")
    (group,) = setup_groups(1, devices=jax.devices()[:devices])
    model = TransformerLM(remat=True, **CFG)
    params, tokens = _params_and_tokens(group, model)

    def gradient():  # traced anew each time: make_jaxpr remembers a function's trace
        summed = lambda p, tokens: model.apply({"params": p}, tokens).sum()
        return jax.make_jaxpr(jax.grad(summed))(params, tokens)

    kept = gradient()
    names = {e.params["name"] for e in _equations(kept) if e.primitive.name == "name"}
    monkeypatch.setattr(decoder, "remat_block", nn.remat)
    bare = _recomputed(gradient())
    kept = _recomputed(kept)
    if (device_kind, devices) == (V5E, 1):
        assert names == {decoder.SAVED_QKV, decoder.SAVED_MLP_HIDDEN, SAVED_OUT, SAVED_LSE}
        assert kept["dot_general"] == LAYERS * (1 + 2 * 6)
        assert bare["dot_general"] - kept["dot_general"] == LAYERS * 4  # q, k, v, up
    else:
        assert not names & {decoder.SAVED_QKV, decoder.SAVED_MLP_HIDDEN}
        assert kept == bare


# A GroupedWindowMoELM over the grouped kernels (an injected attention of
# their signature; widths they tile): a full layer and a rotary window layer.
_GROUPED_KERNELS = dict(
    attention=grouped_attention, d_model=128, num_heads=2, num_kv_heads=1, head_dim=128,
    num_layers=2, window_layout=(0, 1), rope_layout=(0, 1), window=128, num_experts=4, max_len=256,
)


@pytest.mark.parametrize(
    "kind, fields",
    [
        ("latent", {}),  # sigmoid scoring, every expert held
        ("latent", {"experts_held": (2, 3)}),  # one chip's share: most choices land elsewhere
        ("grouped", {}),  # softmax scoring, the router on the block's input
        ("grouped", {"experts_held": (2, 3), "absent_share_grad": False}),
        ("grouped", _GROUPED_KERNELS),  # the kernels, interpreted: q comes to them unrotated
        ("grouped", {**_GROUPED_KERNELS, "experts_held": (1, 2), "absent_share_grad": False}),
    ],
    ids=["sigmoid-whole", "sigmoid-cut", "softmax-whole", "softmax-cut",
         "softmax-kernels", "softmax-kernels-cut"],
)
def test_the_kept_residual_and_routing_leave_the_gradients_bit_equal(monkeypatch, kind, fields):
    """The residual after attention and the router's results, kept by
    name (on the dense path too: this is the CPU), are what the
    recomputed block would have made again: in float32, rounded where
    the program says so, loss and every gradient leaf under the models'
    remat rule equal those under ``nn.remat`` with no policy to the
    last bit, and the rule does keep them (a block's ``proj`` and an
    expert layer's router product, sort into expert order and, with
    sigmoid scoring, ``top_k`` are not in the recomputed blocks). The
    grouped block also keeps q, k and v as its attention reads them, on
    the plain path (both rotated) and on the kernels' (k alone): the
    three products are not in its recomputed blocks, nor a rotation;
    the angles are made again, for the rotations' backward."""
    from multidisttorch_tpu.models.grouped_window_moe import GroupedWindowMoELM
    from multidisttorch_tpu.models.latent_moe import LatentMoELM
    from multidisttorch_tpu.ops.moe import SAVED_ROUTING

    make = {"latent": LatentMoELM, "grouped": GroupedWindowMoELM}[kind]
    model = make(**{"vocab_size": 64, "max_len": 16, "remat": True, **fields})
    t = model.max_len
    tokens = jnp.zeros((2, t), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]

    def gradient():  # traced anew each time: make_jaxpr remembers a function's trace
        logits = lambda p: model.apply({"params": p}, tokens)[0]
        return jax.make_jaxpr(jax.grad(lambda p: logits(p).sum()))(params)

    saved, kept = _loss_and_grads(model, init=model, t=t), gradient()
    monkeypatch.setattr(decoder, "remat_block", nn.remat)  # only a block's input is saved
    bare, again = _loss_and_grads(model, init=model, t=t), _recomputed(gradient())
    names = {e.params["name"] for e in _equations(kept) if e.primitive.name == "name"}
    operands = {decoder.SAVED_QKV} if kind == "grouped" else set()
    kernels = {SAVED_OUT, SAVED_LSE} if model.attention else set()
    assert names == {decoder.SAVED_RESIDUAL, SAVED_ROUTING} | operands | kernels
    kept = _recomputed(kept)
    routers = model.num_layers - getattr(model, "dense_layers", 0)
    products = model.num_layers + routers + (3 * model.num_layers if operands else 0)
    assert again["dot_general"] - kept["dot_general"] == products
    if operands:
        rotary = sum(model.rope_layout)
        # rope_halves joins its halves once a call; the kernels' tables are two joins a layer
        assert again["concatenate"] - kept["concatenate"] == (1 if kernels else 2) * rotary
        assert again["cos"] == kept["cos"] == rotary  # the angles: the rotations' backward reads them
        calls = len(kernels) and model.num_layers  # the backward kernel is in the block's transpose
        assert (again["pallas_call"], kept["pallas_call"]) == (2 * calls, calls)
    assert again["sort"] - kept["sort"] == routers
    assert (again["top_k"], kept["top_k"]) == (routers, 0 if kind == "latent" else routers)
    still = [g for g in jax.tree.leaves(saved[1]) if not float(jnp.abs(g).max())]
    assert len(still) <= routers  # the selection biases: they choose and never weigh
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(bare), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tiny_latent(**fields):
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    return LatentMoELM(vocab_size=64, remat=True, max_len=16, **fields)


# The blocks that give no name for q, k and v: their tiny remat steps as
# lowered at the parent of the PR that brought ``SAVED_QKV`` (PR 36).
_OTHER_BLOCKS = {
    "dense": lambda: TransformerLM(remat=True, **{**CFG, "max_len": 16}),
    "latent": _tiny_latent,
    "latent-streams": lambda: _tiny_latent(hc_mult=4),
}
_OTHER_BLOCKS_DIGESTS = os.path.join(
    os.path.dirname(__file__), "fixtures", "steps_without_operands.sha256"
)


def _lowered_digest(model) -> str:
    """SHA-256 of a model's lowered step on shapes alone (2 x 16
    tokens), less the numbers jax appends to its private functions'
    names (they count the trace's equations)."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    state = jax.eval_shape(lambda k: create_lm_state(group, model, tx, k), jax.random.key(0))
    text = make_lm_train_step(group, model, tx).lower(state, tokens).as_text()
    return hashlib.sha256(re.sub(r"@(\w+?)_\d+\b", r"@\1", text).encode()).hexdigest()


def record_other_blocks_digests():
    """``python -c "import sys; sys.path.insert(0, 'tests'); import
    test_default_attention as t; t.record_other_blocks_digests()"``,
    for a PR that changes one of the three steps on purpose."""
    with open(_OTHER_BLOCKS_DIGESTS, "w") as f:
        for name, make in _OTHER_BLOCKS.items():
            f.write(f"{name} {_lowered_digest(make())}\n")


@pytest.mark.parametrize("name", list(_OTHER_BLOCKS))
def test_a_name_no_trace_holds_changes_no_step(name):
    """``SAVED_QKV`` is in the one policy of every model, and
    ``GroupedWindowMoEBlock`` alone gives it: the lowered tiny steps of
    ``TransformerLM`` and of ``LatentMoELM`` with one stream and with
    four are, character for character, what they lowered to before the
    policy had the name (``tests/fixtures/steps_without_operands.sha256``,
    taken from that parent's checkout)."""
    with open(_OTHER_BLOCKS_DIGESTS) as f:
        recorded = dict(line.split() for line in f if line.strip())
    assert _lowered_digest(_OTHER_BLOCKS[name]()) == recorded[name]


def _recomputed(jaxpr) -> collections.Counter:
    """Equations inside the gradient's recomputed blocks, by primitive;
    a kernel is one equation."""
    inside = collections.Counter()
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "remat2":
            inside.update(e.primitive.name for e in _equations(eqn.params["jaxpr"], kernels=False))
    return inside


def test_bare_remat_runs_the_forward_kernel_twice(as_v5e, monkeypatch):
    """The other side of ``test_one_chip_step_runs_the_kernel``'s
    count: without the policy the recomputed block holds the forward
    kernel again."""
    monkeypatch.setattr(decoder, "remat_block", nn.remat)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    jaxpr = _step_jaxpr(group, TransformerLM(remat=True, **CFG))
    assert _count(jaxpr, "pallas_call") == LAYERS * 3


@pytest.mark.parametrize("saved", [False, True], ids=["bare", "policy"])
def test_hops_under_checkpoint_keep_the_logsumexp_gradient(saved):
    # Ring-flash's hop, as _ring_flash_local combines it (weights
    # exp(lse_h - m)), with the hop under jax.checkpoint: alone, and
    # with the policy that saves the kernel's named results. Either way
    # the cotangent of lse must reach the backward kernel (a dropped one
    # leaves dQ and dK wrong against dense), and with the policy a hop's
    # forward kernel is not run again.
    from multidisttorch_tpu.ops.pallas_attention import _attend
    from multidisttorch_tpu.ops.ring_attention import dense_attention_reference

    hop = jax.checkpoint(
        lambda q, k, v: _attend(q, k, v, causal=False),
        policy=decoder._KEEP_ACROSS_REMAT if saved else None,  # the models' own
    )
    per_row = lambda x: x.transpose(0, 2, 1)[..., None]  # (B, H, T) on (B, T, H, D)

    def two_hops(q, k, v):
        half = k.shape[1] // 2
        (o1, lse1), (o2, lse2) = hop(q, k[:, :half], v[:, :half]), hop(q, k[:, half:], v[:, half:])
        m = jnp.maximum(lse1, lse2)
        w1, w2 = jnp.exp(lse1 - m), jnp.exp(lse2 - m)
        return (per_row(w1) * o1 + per_row(w2) * o2) / per_row(w1 + w2)

    rng = np.random.default_rng(5)
    k, v = (jnp.asarray(rng.normal(0, 1, (1, 64, 2, 8)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(0, 1, (1, 32, 2, 8)), jnp.float32)  # a hop is square: 32 x (32 + 32)
    grads = lambda attn: jax.grad(lambda *qkv: jnp.sum(attn(*qkv) ** 2), argnums=(0, 1, 2))
    g_dense = grads(lambda q, k, v: dense_attention_reference(q, k, v, causal=False))(q, k, v)
    for a, b in zip(grads(two_hops)(q, k, v), g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)
    # a forward a hop, again where the checkpoint saved nothing, and a backward a hop
    assert _count(jax.make_jaxpr(grads(two_hops))(q, k, v), "pallas_call") == 2 * (2 if saved else 3)
