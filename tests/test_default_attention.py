"""Which attention a model runs when none is injected.

``models/transformer.py::_default_causal`` decides while tracing, from
what it can see of the operands (``_placement``: device kind and device
count of their mesh) and from their shapes, by asking
``ops.pallas_attention.default_takes_kernel``. The CPU suite's models
stay dense; these tests ask the rule as a plain function, and reach the
kernel path of a whole step by telling ``_placement`` that the virtual
CPU devices are a TPU (the device count stays the real one). Counts in
jaxprs only; nothing is timed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import multidisttorch_tpu.models.transformer as transformer
from multidisttorch_tpu.models.transformer import (
    MoETransformerLM,
    TransformerLM,
    transformer_tp_shardings,
)
from multidisttorch_tpu.ops.pallas_attention import (
    default_takes_kernel,
    make_flash_attention,
)
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


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_one_chip_latent_attention_step_runs_the_kernel(as_tpu, remat):
    """``LatentMoELM`` given no attention: q and k 192 wide, v 128, on
    one chip the kernel, a forward a block (twice under remat) and one
    fused backward; on the CPU as it is, dense."""
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    model = LatentMoELM(
        vocab_size=64, d_model=128, num_heads=2, num_layers=LAYERS, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, max_len=T, remat=remat,
    )
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    assert _count(_step_jaxpr(group, model), "pallas_call") == LAYERS * (3 if remat else 2)
    (four,) = setup_groups(1, devices=jax.devices()[:4])
    assert _count(_step_jaxpr(four, model), "pallas_call") == 0


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


def test_one_chip_expert_layer_runs_the_grouped_kernel(as_tpu):
    """On one chip, at shapes the kernel tiles, the experts' products
    are Pallas calls too: two forward (gate and up as one, then down)
    and for each of them the two of its backward; over several chips,
    as on the CPU, they are XLA's ragged dot."""
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    model = LatentMoELM(
        vocab_size=64, d_model=128, num_heads=2, num_layers=2, max_len=T,
        expert_hidden_dim=128, num_experts=4, top_k=2,
    )
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    # T = 256 with widths 24/16: the attention stays dense, so every call is the experts'
    assert _count(_step_jaxpr(group, model), "pallas_call") == 2 * 3
    (four,) = setup_groups(1, devices=jax.devices()[:4])
    assert _count(_step_jaxpr(four, model), "pallas_call") == 0


def test_cpu_latent_attention_stays_dense():
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    model = LatentMoELM(vocab_size=64, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, max_len=T)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    assert _count(_step_jaxpr(group, model), "pallas_call") == 0


def _count(jaxpr, primitive: str) -> int:
    """Equations of ``primitive`` in ``jaxpr``, call sites of shared
    inner jaxprs (``jit``, ``remat``, ``custom_vjp``, ``scan``)
    counted one by one."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if hasattr(sub, "eqns") or hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    n += _count(sub, primitive)
    return n


@pytest.fixture
def as_tpu(monkeypatch):
    """The operands' mesh as tracing sees it, its device kind replaced
    by a v5e's."""
    real = transformer._placement

    def placement(x):
        seen = real(x)
        return seen and (V5E, seen[1])

    monkeypatch.setattr(transformer, "_placement", placement)


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
    spy = lambda q, k, v: seen.append(transformer._placement(q)) or q
    for n in (1, 4):
        (group,) = setup_groups(1, devices=jax.devices()[:n])
        _step_jaxpr(group, TransformerLM(attention=spy, remat=True, **CFG))
    # None: model.init's dummy batch, which nobody placed. Shapes alone
    # or such an array show nothing, and the default is dense there.
    assert set(seen) == {None, ("cpu", 1), ("cpu", 4)}
    assert transformer._placement(jnp.zeros((2, 4))) is None


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("model_cls", [TransformerLM, MoETransformerLM], ids=["dense", "moe"])
def test_one_chip_step_runs_the_kernel(as_tpu, model_cls, remat):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    jaxpr = _step_jaxpr(group, model_cls(remat=remat, **CFG))
    # a forward kernel a block, once more a block where remat runs the
    # forward again, and one fused backward kernel a block
    assert _count(jaxpr, "pallas_call") == LAYERS * (3 if remat else 2)
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
def test_falls_back_to_dense(as_tpu, why, devices, t):
    (group,) = setup_groups(1, devices=jax.devices()[:devices])
    model = TransformerLM(**dict(CFG, max_len=max(T, t)))
    assert _count(_step_jaxpr(group, model, t=t), "pallas_call") == 0, why


def test_heads_sharded_by_auto_tp_stay_dense(as_tpu):
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


def test_default_and_injected_flash_are_the_same_program(as_tpu):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    default = _step_jaxpr(group, TransformerLM(remat=True, **CFG))
    injected = _step_jaxpr(
        group, TransformerLM(remat=True, attention=make_flash_attention(causal=True), **CFG)
    )
    assert str(default) == str(injected)
