"""The LM step's scope names, as the compiled program carries them.

The benchmark splits a step's device time by the scope path of each
operation (``benchmark/scope_reduce.py``): flax's module names, JAX's
own markers for the pass (``jvp(``, ``transpose(``,
``rematted_computation``) and the four ``jax.named_scope``s of
``utils/profiling.py``. These tests compile the tiny step through
``make_lm_train_step`` and count names in the optimized HLO: a
refactor that drops a scope, or lets one into flax's parameter paths,
fails here before it blinds a trace. Counts only; nothing is timed.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from multidisttorch_tpu.models.transformer import MoETransformerLM, TransformerLM
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import make_lm_train_step
from multidisttorch_tpu.train.steps import TrainState
from multidisttorch_tpu.utils.profiling import (
    SCOPE_ATTN_CORE, SCOPE_LOSS, SCOPE_MLP, SCOPE_OPTIMIZER,
)

LAYERS = 2
# Path components that say which part of the model an instruction
# belongs to: the four scopes and flax's module names.
RECOGNISED = {
    SCOPE_ATTN_CORE, SCOPE_MLP, SCOPE_LOSS, SCOPE_OPTIMIZER,
    "q", "k", "v", "proj", "up", "down", "moe", "ln_attn", "ln_mlp", "ln_out",
    "head", "tok_embed", "pos_embed",
}
# Without the four scopes 866 of the dense step's 3,752 instructions
# (23%) had no recognised component. With them what is left is the
# embeddings' sum, the blocks' residual adds and reshapes, and the
# step counter: 3.5% (moe), 3.8% (dense), 4.1% and 4.8% with remat.
UNRECOGNISED_BOUND = 0.06


def _lowered(model_cls, remat, **fields):
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = model_cls(
        vocab_size=64, d_model=32, num_heads=4, num_layers=LAYERS, max_len=16, remat=remat,
        **fields,
    )
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((2, 16), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    return make_lm_train_step(group, model, tx).lower(state, tokens), params


def _components(op_name):
    """A path's components with JAX's wrappers taken off:
    ``transpose(jvp(loss))`` is ``loss``."""
    out = []
    for component in op_name.split("/"):
        while (inner := re.match(r"^\w+\((.*)\)$", component)):
            component = inner.group(1)
        out.append(component)
    return out


def _pass(op_name):
    """JAX's own markers: which pass an instruction belongs to."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward" if "jvp(" in op_name else "none"


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("model_cls", [TransformerLM, MoETransformerLM], ids=["dense", "moe"])
def test_scopes_reach_the_compiled_step(model_cls, remat):
    lowered, _ = _lowered(model_cls, remat)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    assert len(step) > 500

    def passes(scope):
        return {_pass(n) for n in step if scope in _components(n)}

    both = {"forward", "backward"}
    assert passes(SCOPE_ATTN_CORE) == both | ({"recompute"} if remat else set())
    # the head and the loss are one walk that makes its gradients as it goes
    # (ops/head_loss.py): both read as the forward pass, the two backward products too
    assert passes(SCOPE_LOSS) == passes("head") == {"forward"}
    assert passes(SCOPE_OPTIMIZER) == {"none"}
    for i in range(LAYERS):
        assert any({f"block_{i}", SCOPE_ATTN_CORE} <= set(_components(n)) for n in step)
    if model_cls is TransformerLM:
        assert passes(SCOPE_MLP) >= both
        # flax's names sit inside the scope, not beside it
        assert any(f"/{SCOPE_MLP}/up/" in n for n in step)
        assert any(f"/{SCOPE_MLP}/down/" in n for n in step)
    else:
        assert passes("moe") >= both and not passes(SCOPE_MLP)

    unrecognised = [n for n in step if not RECOGNISED & set(_components(n))]
    assert len(unrecognised) / len(step) < UNRECOGNISED_BOUND, (
        len(unrecognised), len(step), sorted(set(unrecognised))[:20]
    )


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_attention_kernel_is_under_attn_core_once_a_pass(remat):
    """Where the kernel runs (here interpreted: its operations carry
    the names of the two jitted calls), ``attn_core`` holds the forward
    kernel in the forward pass and the backward kernel in the backward
    pass. Under remat too: the recomputed block holds no kernel, since
    the forward's output and logsumexp were saved."""
    from benchmark import scope_reduce
    from multidisttorch_tpu.ops.pallas_attention import make_flash_attention

    lowered, _ = _lowered(TransformerLM, remat, attention=make_flash_attention(causal=True))
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    for call, where in (("jit(_fwd_call)", "forward"), ("jit(_bwd_call)", "backward")):
        found = {scope_reduce.classify(n) for n in names if call in n.split("/")}
        assert found == {("attn_core", where)}, (call, found)
        for i in range(LAYERS):
            assert any({f"block_{i}", call} <= set(n.split("/")) for n in names)


def test_scopes_stay_out_of_the_parameter_tree():
    """Checkpoints and the benchmark's reference read
    ``params["block_0"]["up"]``: ``mlp`` must not become a level."""
    _, params = _lowered(TransformerLM, True)
    assert set(params) == {"tok_embed", "pos_embed", "ln_out", "head"} | {
        f"block_{i}" for i in range(LAYERS)
    }
    for i in range(LAYERS):
        assert set(params[f"block_{i}"]) == {
            "ln_attn", "q", "k", "v", "proj", "ln_mlp", "up", "down"
        }


def test_scopes_are_metadata_only(monkeypatch):
    """The program jax hashes for its compile cache (locations
    stripped) is the same with the scopes and without: no compile is
    invalidated, no executable changes. The other side of that coin: a
    cached executable built before the scopes is loaded as it is and
    shows none of them in a trace."""
    with_scopes = _lowered(TransformerLM, True)[0]
    assert SCOPE_ATTN_CORE in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _lowered(TransformerLM, True)[0]
    assert SCOPE_ATTN_CORE not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


# --- the latent-attention, routed-expert LM (models/latent_moe.py) ---


def _lowered_latent(remat, t=16, placed=False, **fields):
    """The latent-attention LM's step lowered for ``(2, t)`` tokens;
    ``placed``: state and batch carry the one-device group's shardings,
    as a trial's do (what ``parallel/mesh.py::placement`` reads)."""
    from multidisttorch_tpu.models.latent_moe import LatentMoELM

    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = LatentMoELM(
        **{"vocab_size": 64, "num_layers": LAYERS + 1, "max_len": t, "remat": remat, **fields}
    )
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((2, t), jnp.int32)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((2, t), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    if placed:
        on = lambda tree, sharding: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )
        state, tokens = on(state, group.replicated_sharding), on(tokens, group.batch_sharding)
    return make_lm_train_step(group, model, tx).lower(state, tokens), params


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_expert_layer_scopes_reach_the_compiled_step(remat):
    """The four scopes inside ``moe`` and the names the benchmark's
    split knows, in the compiled step of the latent-attention LM: as
    the test above counts the four of the dense LM."""
    from benchmark import moe_scopes, scope_reduce
    from multidisttorch_tpu.utils.profiling import (
        SCOPE_EXPERT_DISPATCH, SCOPE_EXPERTS, SCOPE_ROUTER, SCOPE_SHARED_EXPERT,
    )

    lowered, _ = _lowered_latent(remat)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    assert len(step) > 500

    both = {"forward", "backward"} | ({"recompute"} if remat else set())
    inner = (SCOPE_ROUTER, SCOPE_EXPERT_DISPATCH, SCOPE_EXPERTS, SCOPE_SHARED_EXPERT)
    assert inner == moe_scopes.PARTS
    for scope in inner:
        under = [n for n in step if moe_scopes.classify(n) == scope]
        assert {_pass(n) for n in under} >= both, scope
        # the benchmark's split charges all of them to ``mlp``
        assert {scope_reduce.classify(n)[0] for n in under} == {"mlp"}, scope
    for i in range(1, LAYERS + 1):  # block_0 is the dense layer
        for scope in inner:
            assert any({f"block_{i}", "moe", scope} <= set(_components(n)) for n in step)
    assert not any("moe" in _components(n) and "block_0" in _components(n) for n in step)
    # the sums of the buffer's rows by token (here XLA's scatter-adds), the layer's
    # output and, from a custom_vjp's backward rule, its input's gradient: the exchange's
    sums = [n for n in step if n.endswith(f"{SCOPE_EXPERT_DISPATCH}/scatter-add")]
    assert {moe_scopes.classify(n) for n in sums} == {SCOPE_EXPERT_DISPATCH}
    assert {_pass(n) for n in sums} >= {"forward", "backward"}
    # nearly all of the layer is under one of the four
    in_moe = [n for n in step if moe_scopes.classify(n) is not None]
    other = [n for n in in_moe if moe_scopes.classify(n) == moe_scopes.OTHER]
    assert len(other) / len(in_moe) < 0.05, sorted(set(other))[:20]

    # latent attention's pieces carry the names of a plain block's
    parts = {scope_reduce.classify(n)[0] for n in step}
    assert {"attn_core", "attn_proj", "mlp", "norm", "embed", "head", "loss", "optimizer"} <= parts
    for name in ("q", "k", "v"):
        assert {_pass(n) for n in step if name in _components(n)} >= both, name
    # the stream after attention is kept across remat: ``proj`` is not multiplied again
    assert {_pass(n) for n in step if "proj" in _components(n)} == {"forward", "backward"}
    assert {_pass(n) for n in step if SCOPE_MLP in _components(n)} >= both  # the dense layer
    unrecognised = [n for n in step if scope_reduce.classify(n)[0] in ("unscoped", "block_other")]
    assert len(unrecognised) / len(step) < UNRECOGNISED_BOUND, (
        len(unrecognised), len(step), sorted(set(unrecognised))[:20]
    )


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_latent_attention_on_the_parts_keeps_the_scopes(as_v5e, remat):
    """Where the block hands the kernel the parts of q and k (one TPU
    chip; here the CPU device under a v5e's name, the kernels
    interpreted), what it runs instead of the assembly carries the
    names the split reads: the products of ``q_b``'s and ``kv_b``'s
    column groups under ``q``, ``k`` and ``v``, the key's rotation
    under ``k``, the two kernels (which rotate q) under ``attn_core``
    once a pass, and nothing new without a name."""
    from benchmark import moe_scopes, scope_reduce

    lowered, _ = _lowered_latent(
        remat, t=256, placed=True,  # 256: the shortest the kernels take
        d_model=128, num_heads=2, num_layers=LAYERS, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128,
    )
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]

    calls = (("jit(_latent_fwd_call)", "forward"), ("jit(_latent_bwd_call)", "backward"))
    for call, where in calls:
        found = {scope_reduce.classify(n) for n in step if call in n.split("/")}
        assert found == {("attn_core", where)}, (call, found)
        for i in range(LAYERS):
            assert any({f"block_{i}", call} <= set(n.split("/")) for n in step)
    assert not any("flash" in n for n in step)
    # the expert layer's sums by token are a kernel here too, the exchange's in both passes
    sums = [n for n in step if "token_sums" in n.split("/")]
    assert {moe_scopes.classify(n) for n in sums} == {"expert_dispatch"}
    assert {_pass(n) for n in sums} >= {"forward", "backward"}
    every = {"forward", "backward"} | ({"recompute"} if remat else set())
    for scope, module in (("q", "q_b"), ("k", "kv_b"), ("v", "kv_b")):
        under = [n for n in step if {scope, module} <= set(_components(n))]
        assert {_pass(n) for n in under} >= every, (scope, module)
        assert {scope_reduce.classify(n)[0] for n in under} == {"attn_proj"}
    # the one key is rotated here, q's rotary part in the kernels
    assert any("k" in _components(n) and "jit(_roll_static)" in n for n in step)
    assert not any("q" in _components(n) and "jit(_roll_static)" in n for n in step)
    unrecognised = [n for n in step if scope_reduce.classify(n)[0] in ("unscoped", "block_other")]
    assert len(unrecognised) / len(step) < UNRECOGNISED_BOUND
    # what is left without a name is what the assembled path leaves too:
    # the residual adds (the one after attention rounded where remat keeps
    # it), the positions' iota and remat's own equations
    assert {n.rsplit("/", 1)[-1] for n in unrecognised} <= {
        "add", "reduce_precision", "iota", "remat2"
    }


def test_latent_scopes_stay_out_of_the_parameter_tree():
    _, params = _lowered_latent(True)
    assert set(params) == {"tok_embed", "ln_out", "head"} | {
        f"block_{i}" for i in range(LAYERS + 1)
    }
    attention = {"ln_attn", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "proj", "ln_mlp"}
    assert set(params["block_0"]) == attention | {"gate", "up", "down"}
    assert set(params["block_1"]) == attention | {"moe"}
    assert set(params["block_1"]["moe"]) == {
        "router", "score_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down",
    }


# --- hyper-connections around the latent-attention LM's sublayers ---


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_hyper_connection_scopes_reach_the_compiled_step(remat):
    """``hc_maps`` and ``hc_mix`` (``ops/hyper_connection.py``) in the
    compiled step of the LM with four streams: in every block, under
    both connections' flax names for the maps, in every pass; opened
    outside the names the benchmark's split knows, so that those parts
    are charged as before and the new time is the blocks' own
    (``block_other``), not ``unscoped``."""
    from benchmark import hc_scopes, scope_reduce
    from multidisttorch_tpu.utils.profiling import SCOPE_HC_MAPS, SCOPE_HC_MIX

    assert hc_scopes.PARTS == (SCOPE_HC_MAPS, SCOPE_HC_MIX)
    lowered, _ = _lowered_latent(remat, hc_mult=4)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    every = {"forward", "backward"} | ({"recompute"} if remat else set())
    for scope in hc_scopes.PARTS:
        under = [n for n in step if hc_scopes.classify(n) == scope]
        assert {_pass(n) for n in under} >= every, scope
        for i in range(LAYERS + 1):  # once a sublayer: both connections of every block
            in_block = [n for n in under if f"block_{i}" in _components(n)]
            assert in_block, (scope, i)
            if scope == SCOPE_HC_MAPS:
                for connection in ("hc_attn", "hc_mlp"):
                    assert any(connection in _components(n) for n in in_block), (i, connection)
        # nothing the split knows moved under them; inside a block they are block_other
        moved = {"attn_core", "q", "k", "v", "proj", "mlp", "moe", "ln_attn", "ln_mlp"}
        assert not any(moved & set(_components(n)) for n in under), scope
        parts = {scope_reduce.classify(n)[0] for n in under}
        assert parts <= {"block_other", "unscoped"} and "block_other" in parts, (scope, parts)
        # what is under a scope outside every block is the sum of the streams before ln_out
        outside = [n for n in under if scope_reduce.classify(n)[0] == "unscoped"]
        assert scope == SCOPE_HC_MIX or not outside, outside[:5]
    # the Sinkhorn iterations are one loop, not 20 copies of its body
    assert any("while" in n for n in step if hc_scopes.classify(n) == SCOPE_HC_MAPS)
    # the parts the split knows are all still there, in every pass
    parts = {scope_reduce.classify(n) for n in step}
    for part in ("attn_core", "attn_proj", "mlp", "norm"):
        assert {which for p, which in parts if p == part} >= every, part
    # and what has no name at all is no more than it was
    unscoped = [n for n in step if scope_reduce.classify(n)[0] == "unscoped"
                and hc_scopes.classify(n) is None]
    assert len(unscoped) / len(step) < UNRECOGNISED_BOUND


def test_hyper_connection_scopes_stay_out_of_the_parameter_tree():
    _, params = _lowered_latent(True, hc_mult=4)
    attention = {"ln_attn", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "proj", "ln_mlp"}
    connections = {"hc_attn", "hc_mlp"}
    assert set(params["block_0"]) == attention | connections | {"gate", "up", "down"}
    assert set(params["block_1"]) == attention | connections | {"moe"}
    for name in connections:
        assert set(params["block_1"][name]) == {
            "norm", "phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res",
            "a_pre", "a_post", "a_res",
        }
    # without streams the tree is the one the test above lists
    _, plain = _lowered_latent(True)
    assert not connections & set(plain["block_1"])


def _lowered_grouped(remat, t=16, placed=False, **fields):
    """The grouped-head window/full LM's step lowered for ``(1, t)``
    tokens; ``placed`` as :func:`_lowered_latent`."""
    from multidisttorch_tpu.models.grouped_window_moe import GroupedWindowMoELM

    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = GroupedWindowMoELM(**{"vocab_size": 64, "max_len": t, "remat": remat, **fields})
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((1, t), jnp.int32)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, t), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    if placed:
        on = lambda tree, sharding: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )
        state, tokens = on(state, group.replicated_sharding), on(tokens, group.batch_sharding)
    return make_lm_train_step(group, model, tx).lower(state, tokens), params


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_window_and_full_scopes_reach_the_compiled_step(remat):
    """``attn_full`` and ``attn_window`` inside ``attn_core``, by the
    layer pattern, and ``moe/router`` for the router that reads the
    block's input, in the compiled tiny step (the plain path: the CPU);
    the accepted splits read the step unedited."""
    from benchmark import moe_scopes, scope_reduce, swa_scopes
    from multidisttorch_tpu.utils.profiling import (
        SCOPE_ATTN_FULL, SCOPE_ATTN_WINDOW, SCOPE_EXPERT_DISPATCH, SCOPE_EXPERTS, SCOPE_ROUTER,
    )

    lowered, _ = _lowered_grouped(remat)  # the default pattern: full, window, window, window
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    assert len(step) > 500
    every = {"forward", "backward"} | ({"recompute"} if remat else set())
    assert swa_scopes.PARTS == (SCOPE_ATTN_FULL, SCOPE_ATTN_WINDOW)
    for kind, blocks in ((SCOPE_ATTN_FULL, {"block_0"}), (SCOPE_ATTN_WINDOW,
                                                          {"block_1", "block_2", "block_3"})):
        under = [n for n in step if swa_scopes.classify(n) == kind]
        assert {_pass(n) for n in under} >= every, kind
        assert {scope_reduce.classify(n)[0] for n in under} == {"attn_core"}, kind
        assert {c for n in under for c in _components(n) if c.startswith("block_")} == blocks
    core = [n for n in step if scope_reduce.classify(n)[0] == "attn_core"]
    assert all(swa_scopes.classify(n) is not None for n in core)  # attn_core_ms is the two's sum
    for scope in (SCOPE_ROUTER, SCOPE_EXPERT_DISPATCH, SCOPE_EXPERTS):
        under = [n for n in step if moe_scopes.classify(n) == scope]
        assert {_pass(n) for n in under} >= every, scope
        assert {scope_reduce.classify(n)[0] for n in under} == {"mlp"}, scope
    for i in range(4):
        assert any({f"block_{i}", "moe", SCOPE_ROUTER} <= set(_components(n)) for n in step)
    parts = {scope_reduce.classify(n)[0] for n in step}
    assert {"attn_core", "attn_proj", "mlp", "norm", "embed", "head", "loss", "optimizer"} <= parts
    # the rotations of q and k (window layers only) are the projections';
    # under remat the block keeps all four's results: none is made again
    for name in ("q", "k", "v", "proj"):
        assert {_pass(n) for n in step if name in _components(n)} == {"forward", "backward"}, name
    unrecognised = [n for n in step if scope_reduce.classify(n)[0] in ("unscoped", "block_other")]
    assert len(unrecognised) / len(step) < UNRECOGNISED_BOUND, (
        len(unrecognised), len(step), sorted(set(unrecognised))[:20]
    )


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_grouped_kernels_are_under_their_layer_s_scope_once_a_pass(as_v5e, remat):
    """Where the block takes the kernels (one TPU chip; here the CPU
    device under a v5e's name, the kernels interpreted): one forward and
    one backward call a layer under ``attn_core`` and the layer's kind,
    none in the recomputed block; k is rotated outside them and q is
    not."""
    from benchmark import scope_reduce, swa_scopes

    lowered, _ = _lowered_grouped(
        remat, t=256, placed=True, d_model=128, num_heads=2, num_kv_heads=1, head_dim=128,
        num_layers=2, window_layout=(0, 1), rope_layout=(0, 1), window=128, num_experts=4,
    )
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    calls = (("jit(_grouped_fwd_call)", "forward"), ("jit(_grouped_bwd_call)", "backward"))
    for call, where in calls:
        found = {scope_reduce.classify(n) for n in step if call in n.split("/")}
        assert found == {("attn_core", where)}, (call, found)
        kinds = {(swa_scopes.classify(n), c) for n in step if call in n.split("/")
                 for c in _components(n) if c.startswith("block_")}
        assert kinds == {("attn_full", "block_0"), ("attn_window", "block_1")}
    rotated = lambda name: any(
        {name, "block_1"} <= set(_components(n)) and n.endswith("/concatenate") for n in step
    )
    assert rotated("k") and not rotated("q")
    unrecognised = [n for n in step if scope_reduce.classify(n)[0] in ("unscoped", "block_other")]
    assert len(unrecognised) / len(step) < UNRECOGNISED_BOUND


# --- what remat keeps of a block (decoder.remat_block) ---

_REMAT_STEPS = {
    "dense": lambda: _lowered(TransformerLM, True),  # the control: its block names nothing
    "latent": lambda: _lowered_latent(True),  # sigmoid scoring
    "latent-cut": lambda: _lowered_latent(True, experts_held=(2, 3)),
    "grouped": lambda: _lowered_grouped(True),  # softmax scoring
}


@pytest.mark.parametrize("model", list(_REMAT_STEPS))
def test_the_recomputed_block_leaves_out_what_remat_keeps(model):
    """In the compiled tiny steps under remat the recomputed blocks of
    the two expert models hold no operation of ``proj`` (the stream
    after attention is kept; ``TransformerLM``'s block does not name it
    and multiplies by ``proj`` again); q, k and v are made again in
    ``TransformerLM`` and ``LatentMoELM`` and not in
    ``GroupedWindowMoELM``, whose block keeps what its attention reads:
    no operation under ``q``, ``k`` or ``v`` there, product or
    rotation, while both norms still run again; of the router they
    hold no product and, with sigmoid scoring, no top-k and no gather
    either (logits, choices and picked scores are kept; the sigmoid and
    the normalisation are made again); with softmax scoring ``top_k``
    runs again on the kept logits, its derivative rule reading its own
    indices; and the exchange holds no sort (the order into expert
    order is kept; the sums by token are XLA's scatter-adds here and
    sort nothing)."""
    from benchmark import moe_scopes
    from multidisttorch_tpu.utils.profiling import SCOPE_EXPERT_DISPATCH, SCOPE_ROUTER

    names = re.findall(r'op_name="([^"]*)"', _REMAT_STEPS[model]()[0].compile().as_text())
    again = [n for n in names if n.startswith("jit(step_fn)") and _pass(n) == "recompute"]
    for name in ("ln_attn", "ln_mlp"):
        assert any(name in _components(n) for n in again), name
    for name in ("q", "k", "v"):
        under = [n for n in again if name in _components(n)]
        assert bool(under) == (model != "grouped"), (name, under[:5])
    assert any("proj" in _components(n) for n in again) == (model == "dense")
    made = lambda scope: {n.rsplit("/", 1)[-1] for n in again if moe_scopes.classify(n) == scope}
    router = made(SCOPE_ROUTER)
    if model == "dense":
        assert not router
        return
    assert router and not router & {"dot_general", "sort", "gather"}
    assert ("top_k" in router) == (model == "grouped")
    assert ("exp" in router) and "div" in router  # the scoring itself is cheap, and made again
    assert "sort" not in made(SCOPE_EXPERT_DISPATCH)


def test_grouped_scopes_stay_out_of_the_parameter_tree():
    _, params = _lowered_grouped(True)
    assert set(params) == {"tok_embed", "ln_out", "head"} | {f"block_{i}" for i in range(4)}
    for i in range(4):
        assert set(params[f"block_{i}"]) == {"ln_attn", "q", "k", "v", "proj", "ln_mlp", "moe"}
        assert set(params[f"block_{i}"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}


# --- the decoder-hybrid-decoder (models/ssm_hybrid.py) ---


def _lowered_hybrid(remat, t=16, placed=False, **fields):
    """``SambaYLM``'s step lowered for ``(1, t)`` tokens; ``placed`` as
    :func:`_lowered_latent`."""
    from multidisttorch_tpu.models.ssm_hybrid import SambaYLM

    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = SambaYLM(**{"vocab_size": 64, "max_len": t, "remat": remat, **fields})
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((1, t), jnp.int32)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, t), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    if placed:
        on = lambda tree, sharding: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )
        state, tokens = on(state, group.replicated_sharding), on(tokens, group.batch_sharding)
    return make_lm_train_step(group, model, tx).lower(state, tokens), params


# the default 8 layers: which blocks hold which scope
_HYBRID_BLOCKS = {
    "ssm_scan": {"block_0", "block_2", "block_4"}, "ssm_proj": {"block_0", "block_2", "block_4"},
    "ssm_conv": {"block_0", "block_2", "block_4"}, "gmu": {"block_6"}, "attn_cross": {"block_7"},
    "attn_window": {"block_1", "block_3"}, "attn_full": {"block_5"},
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_hybrid_scopes_reach_the_compiled_step(remat):
    """``ssm_proj``, ``ssm_conv``, ``ssm_scan``, ``gmu`` and, inside
    ``attn_core``, ``attn_window``, ``attn_full`` and ``attn_cross`` in
    the compiled tiny step (the plain path: the CPU), each in the blocks
    of its kind and in every pass it has; the tied head under ``head``;
    the accepted splits read the step unedited: the state-space and
    memory scopes are the blocks' own (``block_other``), not
    ``unscoped``."""
    from benchmark import scope_reduce, ssm_scopes, swa_scopes
    from multidisttorch_tpu.utils import profiling

    assert ssm_scopes.PARTS == (
        profiling.SCOPE_SSM_SCAN, profiling.SCOPE_SSM_PROJ, profiling.SCOPE_SSM_CONV,
        profiling.SCOPE_GMU, profiling.SCOPE_ATTN_CROSS,
    )
    lowered, _ = _lowered_hybrid(remat)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    assert len(step) > 500
    both = {"forward", "backward"}
    every = both | ({"recompute"} if remat else set())
    blocks_of = lambda under: {c for n in under for c in _components(n) if c.startswith("block_")}
    for scope in ssm_scopes.PARTS:
        under = [n for n in step if ssm_scopes.classify(n) == scope]
        # under remat the scan's output and chunk states are kept: no scan is made again
        assert {_pass(n) for n in under} >= (both if scope == "ssm_scan" else every), scope
        assert blocks_of(under) == _HYBRID_BLOCKS[scope], scope
        # (a reshape fused across the scope's edge carries two paths, the projection's first)
        parts = {"attn_core", "attn_proj"} if scope == "attn_cross" else {"block_other"}
        found = {scope_reduce.classify(n)[0] for n in under}
        assert found <= parts and parts - {"attn_proj"} <= found, (scope, found)
    if remat:  # of the scan the recomputed block holds its operands' slices and -exp(A_log) alone
        again = {n.rsplit("/", 1)[-1] for n in step
                 if ssm_scopes.classify(n) == "ssm_scan" and _pass(n) == "recompute"}
        assert again <= {"exp", "neg", "slice"}, again
    for scope in swa_scopes.PARTS:
        under = [n for n in step if swa_scopes.classify(n) == scope]
        assert {_pass(n) for n in under} >= every and blocks_of(under) == _HYBRID_BLOCKS[scope]
    core = [n for n in step if scope_reduce.classify(n)[0] == "attn_core"]
    assert all((swa_scopes.classify(n) or ssm_scopes.classify(n)) for n in core)  # the three's sum
    head = [n for n in step if profiling.SCOPE_HEAD in _components(n)]
    assert {_pass(n) for n in head} == {"forward"}  # the walk's: its three products are one pass
    assert {scope_reduce.classify(n)[0] for n in head} == {"head"}
    parts = {scope_reduce.classify(n)[0] for n in step}
    assert {"attn_core", "attn_proj", "mlp", "norm", "embed", "head", "loss", "optimizer",
            "block_other"} <= parts
    unscoped = [n for n in step if scope_reduce.classify(n)[0] == "unscoped"]
    assert len(unscoped) / len(step) < UNRECOGNISED_BOUND, sorted(set(unscoped))[:20]
    # what a block leaves without one of its own names is its residual adds and what
    # remat wraps them in, a small part
    bare = [n for n in step if scope_reduce.classify(n)[0] == "block_other"
            and ssm_scopes.classify(n) is None]
    assert len(bare) / len(step) < UNRECOGNISED_BOUND, sorted(set(bare))[:20]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_hybrid_kernels_are_under_their_layer_s_scope_once_a_pass(as_v5e, remat):
    """Where the block takes the kernels (one TPU chip; here the CPU
    device under a v5e's name, the kernels interpreted): one forward and
    one backward scan a Mamba layer under ``ssm_scan`` and one forward
    and one backward 64-wide grouped kernel an attention layer under
    ``attn_core`` and the layer's kind, none of either in the recomputed
    block."""
    from benchmark import scope_reduce, ssm_scopes, swa_scopes

    lowered, _ = _lowered_hybrid(
        remat, t=256, placed=True, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
        mlp_width=64, window=128,
        layer_kinds=("mamba", "window", "mamba_memory", "full_kv", "gmu", "cross"),
    )
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    kind_of = lambda n: swa_scopes.classify(n) or ssm_scopes.classify(n)
    for call, where in (("jit(_kernel_fwd)", "forward"), ("jit(_kernel_bwd)", "backward")):
        found = {(kind_of(n), _pass(n), c) for n in step if call in n.split("/")
                 for c in _components(n) if c.startswith("block_")}
        assert found == {("ssm_scan", where, "block_0"), ("ssm_scan", where, "block_2")}, found
    for call, where in (("jit(_grouped64_fwd_call)", "forward"),
                        ("jit(_grouped64_bwd_call)", "backward")):
        found = {scope_reduce.classify(n) for n in step if call in n.split("/")}
        assert found == {("attn_core", where)}, (call, found)
        kinds = {(kind_of(n), c) for n in step if call in n.split("/")
                 for c in _components(n) if c.startswith("block_")}
        assert kinds == {("attn_window", "block_1"), ("attn_full", "block_3"),
                         ("attn_cross", "block_5")}
    assert not any("_grouped_fwd_call" in n or "flash" in n for n in step)


def test_hybrid_scopes_stay_out_of_the_parameter_tree():
    _, params = _lowered_hybrid(True)
    assert set(params) == {"tok_embed", "ln_out"} | {f"block_{i}" for i in range(8)}  # no head
    shared = {"ln_attn", "ln_mlp", "gate", "up", "down"}
    mamba = {"in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias", "A_log", "D", "out_proj"}
    assert set(params["block_0"]) == set(params["block_4"]) == shared | mamba
    assert set(params["block_1"]) == set(params["block_5"]) == shared | {"qkv", "proj"}
    assert set(params["block_6"]) == shared | {"in_proj", "out_proj"}
    assert set(params["block_7"]) == shared | {"q", "proj"}


# --- the gated short-convolution / attention expert model (models/conv_moe.py) ---


def _lowered_conv(remat, t=16, placed=False, **fields):
    """``ShortConvMoELM``'s step lowered for ``(1, t)`` tokens;
    ``placed`` as :func:`_lowered_latent`."""
    from multidisttorch_tpu.models.conv_moe import ShortConvMoELM

    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = ShortConvMoELM(**{"vocab_size": 64, "max_len": t, "remat": remat, **fields})
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((1, t), jnp.int32)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, t), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    if placed:
        on = lambda tree, sharding: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )
        state, tokens = on(state, group.replicated_sharding), on(tokens, group.batch_sharding)
    return make_lm_train_step(group, model, tx).lower(state, tokens), params


# the default 4 layers (conv, conv, full_attention, conv; one dense): which blocks hold which scope
_CONV_BLOCKS = {
    "conv_proj": {"block_0", "block_1", "block_3"}, "conv_mix": {"block_0", "block_1", "block_3"},
    "qk_norm": {"block_2"},
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_conv_scopes_reach_the_compiled_step(remat):
    """``conv_proj``, ``conv_mix`` and ``qk_norm`` in the compiled tiny
    step (the plain path: the CPU), each in the blocks of its kind and
    in every pass; ``attn_full`` inside ``attn_core``, the dense layer
    under ``mlp`` and the expert layers' parts under ``moe``, as the
    accepted splits read them; the step's other names as the other
    models'."""
    from benchmark import conv_scopes, moe_scopes, scope_reduce, swa_scopes
    from multidisttorch_tpu.utils.profiling import (
        SCOPE_ATTN_FULL, SCOPE_CONV_MIX, SCOPE_CONV_PROJ, SCOPE_EXPERT_DISPATCH, SCOPE_EXPERTS,
        SCOPE_QK_NORM, SCOPE_ROUTER,
    )

    lowered, _ = _lowered_conv(remat)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    assert len(step) > 400
    every = {"forward", "backward"} | ({"recompute"} if remat else set())
    assert conv_scopes.PARTS == (SCOPE_CONV_PROJ, SCOPE_CONV_MIX, SCOPE_QK_NORM)
    for scope, blocks in _CONV_BLOCKS.items():
        under = [n for n in step if conv_scopes.classify(n) == scope]
        assert {_pass(n) for n in under} >= {"forward", "backward"}, scope
        assert {c for n in under for c in _components(n) if c.startswith("block_")} == blocks
        assert {scope_reduce.classify(n)[0] for n in under} == {"block_other"}, scope
    # the recomputed conv block makes all of its operator again, W_out too
    # (it keeps nothing). The attention layer's q, k and v are kept as the
    # projections leave them: the head norms and the rotation run again,
    # the three products and proj (the stream after it is kept) do not
    again = [n for n in step if _pass(n) == "recompute"]
    for name in ("in_proj", "out_proj"):
        assert any(name in _components(n) for n in again) == remat, name
    assert any(conv_scopes.classify(n) == SCOPE_CONV_MIX for n in again) == remat
    assert any(conv_scopes.classify(n) == SCOPE_QK_NORM for n in again) == remat
    for name in ("q", "k", "v", "proj"):
        assert {_pass(n) for n in step if name in _components(n)} == {"forward", "backward"}, name
    core = [n for n in step if scope_reduce.classify(n)[0] == "attn_core"]
    assert core and {swa_scopes.classify(n) for n in core} == {SCOPE_ATTN_FULL}
    assert {c for n in core for c in _components(n) if c.startswith("block_")} == {"block_2"}
    assert {_pass(n) for n in core} >= every
    for scope in (SCOPE_ROUTER, SCOPE_EXPERT_DISPATCH, SCOPE_EXPERTS):
        under = [n for n in step if moe_scopes.classify(n) == scope]
        assert {_pass(n) for n in under} >= every, scope
        assert {scope_reduce.classify(n)[0] for n in under} == {"mlp"}, scope
        assert {c for n in under for c in _components(n) if c.startswith("block_")} == {
            "block_1", "block_2", "block_3"}  # block_0 is the dense layer
    dense = [n for n in step if "block_0" in _components(n) and "mlp" in _components(n)]
    assert {c for n in dense for c in _components(n)} >= {"gate", "up", "down"}
    parts = {scope_reduce.classify(n)[0] for n in step}
    assert {"attn_core", "attn_proj", "mlp", "norm", "embed", "head", "loss", "optimizer"} <= parts
    unscoped = [n for n in step if scope_reduce.classify(n)[0] == "unscoped"]
    assert len(unscoped) / len(step) < UNRECOGNISED_BOUND, sorted(set(unscoped))[:20]
    # what neither the accepted split nor the three new scopes name
    nowhere = [n for n in step if scope_reduce.classify(n)[0] == "block_other"
               and conv_scopes.classify(n) is None]
    assert len(nowhere) / len(step) < UNRECOGNISED_BOUND, sorted(set(nowhere))[:20]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_conv_model_s_kernels_are_under_attn_full_once_a_pass(as_v5e, remat):
    """With the operands placed as a trial's and the device a v5e by
    name, the attention layer of 8 heads over 2 KV heads of 64 (4 query
    heads a KV head) lowers the 64-wide kernel pair under
    ``attn_core/attn_full``, one forward and one backward call, no
    kernel in the recomputed block, and q and k reach it normed and
    rotated under ``qk_norm``."""
    from benchmark import conv_scopes, scope_reduce, swa_scopes

    lowered, _ = _lowered_conv(
        remat, t=256, placed=True, d_model=128, num_heads=8, num_kv_heads=2, head_dim=64,
    )
    text = lowered.compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    step = [n for n in names if n.startswith("jit(step_fn)")]
    calls = (("jit(_grouped64_fwd_call)", "forward"), ("jit(_grouped64_bwd_call)", "backward"))
    for call, where in calls:
        under = [n for n in step if call in n.split("/")]
        assert {scope_reduce.classify(n) for n in under} == {("attn_core", where)}, call
        assert {(swa_scopes.classify(n), c) for n in under for c in _components(n)
                if c.startswith("block_")} == {("attn_full", "block_2")}
    normed = [n for n in step if conv_scopes.classify(n) == "qk_norm"]
    assert {_pass(n) for n in normed} == {"forward", "backward"} | ({"recompute"} if remat else set())
    assert any(n.endswith("/concatenate") for n in normed)  # the rotation, q's and k's


def test_conv_scopes_stay_out_of_the_parameter_tree():
    _, params = _lowered_conv(True)
    assert set(params) == {"tok_embed", "ln_out"} | {f"block_{i}" for i in range(4)}  # no head
    conv, norms = {"in_proj", "conv_w", "out_proj"}, {"ln_attn", "ln_mlp"}
    attention = {"q", "k", "v", "q_norm", "k_norm", "proj"}
    assert set(params["block_0"]) == norms | conv | {"gate", "up", "down"}
    assert set(params["block_1"]) == set(params["block_3"]) == norms | conv | {"moe"}
    assert set(params["block_2"]) == norms | attention | {"moe"}
    assert set(params["block_1"]["moe"]) == {"router", "score_bias", "w_gate", "w_up", "w_down"}


# --- the head and the loss as one walk (ops/head_loss.py), in every LM ---


def _walk_lms():
    from multidisttorch_tpu.models.conv_moe import ShortConvMoELM
    from multidisttorch_tpu.models.grouped_window_moe import GroupedWindowMoELM
    from multidisttorch_tpu.models.latent_moe import LatentMoELM
    from multidisttorch_tpu.models.ssm_hybrid import SambaYLM

    small = dict(d_model=32, num_heads=4, num_layers=LAYERS)
    return {
        "dense": lambda **kw: TransformerLM(**small, **kw),
        "moe": lambda **kw: MoETransformerLM(**small, **kw),
        "latent": LatentMoELM, "grouped": GroupedWindowMoELM, "hybrid": SambaYLM,
        "conv": ShortConvMoELM,
    }


@pytest.mark.parametrize("blocks", [1, 2], ids=["one-block", "loop"])
@pytest.mark.parametrize("name", ["dense", "moe", "latent", "grouped", "hybrid", "conv"])
def test_head_and_loss_of_the_walk_reach_the_compiled_step(monkeypatch, name, blocks):
    """``head`` and ``loss`` in the compiled tiny step of every LM, as
    ``benchmark/scope_reduce.classify`` files them: the walk's three
    products under ``head``, the passes over a block's logits under
    ``loss``, in the loop's body too when the rule gives two blocks."""
    from benchmark import scope_reduce
    from multidisttorch_tpu.ops import head_loss

    vocab, shape = 72, (2, 16)
    monkeypatch.setattr(head_loss, "LOGITS_BLOCK_BYTES", shape[0] * shape[1] * vocab * 4 // blocks)
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = _walk_lms()[name](vocab_size=vocab, max_len=shape[1], remat=True)
    tx = optax.adam(1e-3)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros(shape, jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    tokens = jax.ShapeDtypeStruct(shape, jnp.int32)
    text = make_lm_train_step(group, model, tx).lower(state, tokens).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    step = [n for n in names if n.startswith("jit(step_fn)")]
    for scope in ("head", SCOPE_LOSS):
        under = [n for n in step if scope in _components(n)]
        assert {scope_reduce.classify(n) for n in under} == {(scope, "forward")}, scope
        assert all(("while/body" in n) == (blocks > 1) for n in under if "jvp()" in n), scope
    for op in ("exp", "reduce_max", "log"):  # the logsumexp's passes
        assert any(_components(n)[-2:] == [SCOPE_LOSS, op] for n in step), op
    # rows x weights, gradient x weights, rows x gradient: the head's three products
    dots = re.findall(r' dot\([^\n]*op_name="([^"]*)"', text)
    assert sum(_components(n)[-2:] == ["head", "dot_general"] for n in dots) == 3


# --- the looped model (models/looped.py) ---


def _lowered_looped(remat, t=16, placed=False, **fields):
    """``LoopedLM``'s step lowered for ``(1, t)`` tokens: 2 layers run 4
    times; ``placed`` as :func:`_lowered_latent`."""
    from multidisttorch_tpu.models.looped import LoopedLM

    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = LoopedLM(**{"vocab_size": 64, "max_len": t, "remat": remat, **fields})
    tx = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((1, t), jnp.int32)
    params = jax.eval_shape(
        model.init, {"params": jax.random.key(0)}, jnp.zeros((1, t), jnp.int32)
    )["params"]
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=tx.init(p), step=jnp.zeros((), jnp.int32)),
        params,
    )
    if placed:
        on = lambda tree, sharding: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )
        state, tokens = on(state, group.replicated_sharding), on(tokens, group.batch_sharding)
    return make_lm_train_step(group, model, tx).lower(state, tokens), params


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_looped_scopes_reach_the_compiled_step(remat):
    """``loop_exit`` in the compiled tiny looped step (the plain path:
    the CPU), in both passes: the gate after every pass (under its
    ``loop_<t>``), the exit distribution and its entropy; every pass's
    blocks under their ``loop_<t>``; the two norms after the sublayers
    counted as norms; the step's other names as the other models'."""
    from benchmark import loop_scopes, scope_reduce
    from multidisttorch_tpu.utils.profiling import SCOPE_LOOP, SCOPE_LOOP_EXIT

    lowered, _ = _lowered_looped(remat)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    assert len(step) > 400
    loops = [SCOPE_LOOP.format(t) for t in range(4)]
    under = [n for n in step if loop_scopes.classify(n) == SCOPE_LOOP_EXIT]
    assert {_pass(n) for n in under} >= {"forward", "backward"}
    gate = [n for n in under if "exit_gate" in _components(n)]
    assert {c for n in gate for c in _components(n) if c.startswith("loop_")} >= set(loops)
    assert any("exit_gate" not in _components(n) for n in under)  # the distribution, the entropy
    assert {scope_reduce.classify(n)[0] for n in under} == {"unscoped"}
    for loop in loops:
        cores = [n for n in step if loop in _components(n)
                 and scope_reduce.classify(n)[0] == "attn_core"]
        assert {c for n in cores for c in _components(n) if c.startswith("block_")} == {
            "block_0", "block_1"}, loop
    post = [n for n in step if {"ln_attn_out", "ln_mlp_out"} & set(_components(n))]
    assert post and {scope_reduce.classify(n)[0] for n in post} == {"norm"}
    parts = {scope_reduce.classify(n)[0] for n in step}
    assert {"attn_core", "attn_proj", "mlp", "norm", "embed", "head", "loss", "optimizer"} <= parts
    unscoped = [n for n in step if scope_reduce.classify(n)[0] == "unscoped"
                and loop_scopes.classify(n) is None]
    assert len(unscoped) / len(step) < UNRECOGNISED_BOUND, sorted(set(unscoped))[:20]


def test_looped_scopes_stay_out_of_the_parameter_tree():
    _, params = _lowered_looped(True)
    assert set(params) == {"tok_embed", "ln_out", "exit_gate", "head", "block_0", "block_1"}
    assert set(params["block_0"]) == {
        "ln_attn", "q", "k", "v", "proj", "ln_attn_out", "ln_mlp", "gate", "up", "down",
        "ln_mlp_out",
    }
    assert set(params["exit_gate"]) == {"kernel", "bias"}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_looped_kernels_are_under_attn_core_once_a_pass(as_v5e, remat):
    """With the operands placed as a trial's and the device a v5e by
    name, 2 query heads over 2 KV heads of 128 lower the grouped kernel
    pair under ``attn_core`` in every loop, forward and backward, none
    in the recomputed block; there q, k and v are kept (``SAVED_QKV``),
    so their products run forward and backward only, and the MLP's
    first half and ``proj`` are made again."""
    from benchmark import scope_reduce

    lowered, _ = _lowered_looped(remat, t=256, placed=True, d_model=256, num_heads=2,
                                 num_kv_heads=2, head_dim=128)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    step = [n for n in names if n.startswith("jit(step_fn)")]
    for call, where in (("jit(_grouped_fwd_call)", "forward"),
                        ("jit(_grouped_bwd_call)", "backward")):
        under = [n for n in step if call in n.split("/")]
        assert {scope_reduce.classify(n) for n in under} == {("attn_core", where)}, call
        assert {c for n in under for c in _components(n) if c.startswith("loop_")} == {
            f"loop_{t}" for t in range(4)}
    again = {c for n in step if _pass(n) == "recompute" for c in _components(n)}
    assert not {"q", "k", "v"} & again  # nor a kernel: both calls read forward and backward above
    assert ({"gate", "up", "proj"} <= again) == remat
