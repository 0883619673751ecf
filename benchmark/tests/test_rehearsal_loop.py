"""CPU rehearsal of the ``looped_lm_trial`` entry at a tiny size (control
flow, the record its readers take, the counters compared with the
reference's), the ``loop_exit`` split of ``loop_scopes`` on hand-made
events, and the counts ``mha128_core_roofline``, ``head_loss_roofline``
and the cell's ``mfu`` divide by against counts by hand. No number from
here is a device number."""

import json
import os

import jax
import pytest

from benchmark import cells, flops_ouro, loop_scopes, scope_reduce, swa_scopes, trace_reduce
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "looped_lm_trial",
    "reference": "benchmark/configs/ouro-2.6b.reference.py",
    "vocab_size": 61, "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 2,
    "total_ut_steps": 4, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "bfloat16", "remat": True, "exit_entropy_weight": 0.1},
    # wide: bf16 at a toy width
    "compared": {"logits_rel_rms": 0.1, "loss_rel": 0.02, "exit_p_rel": 0.1,
                 "loop_loss_rel": 0.02, "grad_rel_l2": 0.6, "gate_grad_rel_l2": 0.6,
                 "param_change_rel_l2": 0.9},
}
TINY_TRAFFIC = {"name": "tiny", "batch_sequences": 2, "sequence_length": 32,
                "learning_rates": [1e-2]}
NEW_METRICS = {"loop_exit_ms", "mha128_core_roofline", "head_loss_roofline"}


@pytest.fixture(scope="module")
def record():
    real = cells.load_cell("loop-ut4-t4096")
    assert real.config["entry"] == "looped_lm_trial" and real.traffic["batch_sequences"] == 2
    cell = cells.Cell(name=real.name, chips=1, config=TINY_CONFIG, traffic=TINY_TRAFFIC,
                      end_to_end=real.end_to_end, per_layer=real.per_layer)
    # one trial on the first of the four virtual devices, a seed past 32 signed bits
    got = cell.entry().run(cell, jax.devices()[:1], 2147483659, 4.0, None, CompileBook())
    got["t_process_start"] = got["stamps"][0] - 1.0
    got["t_entry"] = got["stamps"][0] - 0.5
    got["device"] = {"kind": "TPU v5 lite", "count": 1}  # for the peak table only
    return cell, got


def test_one_trial_untraced(record):
    cell, got = record
    assert got["checks"] == {
        "reference": True, "losses_finite": True, "losses_falling": True,
        "nothing_compiled_in_window": True,
    }, got["reference"]["notes"]
    assert got["correct"] and got["failed"] == 0
    assert got["attempted"] == len(got["stamps"]) - 1
    assert set(cells.read_metrics(cell.end_to_end, "end_to_end", got)) == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(got["reference"]["errors"]) == set(TINY_CONFIG["compared"])
    assert got["flops_per_unit"] == pytest.approx(
        flops_ouro.train_flops_per_token(TINY_CONFIG, 32), rel=1e-12)


def test_the_cell_reports_the_new_metrics_and_leaves_the_traced_ones_out(record):
    cell, got = record
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS | {"mfu", "attn_core_ms", "head_loss_ms", "unscoped_share"} <= names
    assert not {"router_ms", "gqa64_core_roofline", "attn_full_ms", "ssm_scan_ms"} & names
    read = cells.read_metrics(cell.per_layer, "layer_metrics", got)
    assert {"mfu", "step_ms"} <= set(read)
    assert not NEW_METRICS & set(read)


LM = "jit(step_fn)/jvp(LoopedLM)"
BACK = "jit(step_fn)/transpose(jvp(LoopedLM))"


@pytest.mark.parametrize("path, expected, accepted", [
    (f"{LM}/loop_3/loop_exit/exit_gate/dot_general", "loop_exit", "unscoped"),
    ("jit(step_fn)/jvp(loop_exit)/exp", "loop_exit", "unscoped"),
    (f"{BACK}/loop_exit/mul", "loop_exit", "unscoped"),
    (f"{LM}/loop_0/block_1/attn_core/jit(_grouped_fwd_call)/grouped_fwd", None, "attn_core"),
    (f"{LM}/loop_2/block_0/ln_attn/ln_attn_out/mul", None, "norm"),
    (f"{LM}/loop_2/block_0/mlp/gate/dot_general", None, "mlp"),
    (f"{LM}/loop_1/ln_out/mul", None, "head"),
    ("", None, "unscoped"), (None, None, "unscoped"),
])
def test_classify_finds_the_exit_scope(path, expected, accepted):
    assert loop_scopes.classify(path) == expected
    assert scope_reduce.classify(path)[0] == accepted  # what the accepted split makes of it


def test_reduce_on_hand_made_events():
    """Two steps in the window; on one chip 3 ms under ``loop_exit`` (an
    operation nested in another counts once) and operations under none."""
    ms = 1_000_000
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        (host, "t", trace_reduce.WINDOW_SPAN, 0, 100 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 1 * ms, 40 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 50 * ms, 40 * ms, None),
        (dev, "ops", "fusion.1", 10 * ms, 2 * ms, f"{LM}/loop_0/loop_exit/exit_gate/dot"),
        (dev, "ops", "inner", 10 * ms, 1 * ms, f"{LM}/loop_0/loop_exit/exit_gate/dot/a"),
        (dev, "ops", "fusion.2", 20 * ms, 1 * ms, f"{BACK}/loop_exit/mul"),
        (dev, "ops", "fusion.3", 30 * ms, 7 * ms, f"{LM}/loop_1/block_0/q/dot_general"),
        (dev, "ops", "late", 200 * ms, 9 * ms, f"{LM}/loop_0/loop_exit/exp"),  # past it
    ]
    got = swa_scopes.reduce_by(events, loop_scopes.classify)
    assert got["steps"] == 2 and got["seconds"] == pytest.approx({"loop_exit": 3e-3})
    # a program without the scope: nothing to read, and nothing raised
    plain = [e for e in events if loop_scopes.classify(e[5]) is None]
    assert swa_scopes.reduce_by(plain, loop_scopes.classify) is None


def test_readers_find_nothing_in_a_record_without_a_trace():
    record = {"trace": None, "config": TINY_CONFIG, "device": {"kind": "TPU v5 lite"},
              "sequence_length": 32, "units_per_reading_per_chip": 64}
    assert loop_scopes.exit_ms_per_step(record) is None
    assert loop_scopes.core_roofline_share(record) is None
    assert loop_scopes.head_loss_roofline_share(record) is None
    # another configuration's record: nothing, whatever its trace holds
    other = {**record, "config": {"conv_L_cache": 3}}
    assert loop_scopes.core_roofline_share(other) is None


def test_counts_against_counts_by_hand():
    with open(os.path.join(cells.ROOT, "benchmark/configs/ouro-2.6b.json")) as f:
        config = json.load(f)
    t, tokens = 4096, 2 * 4096
    pairs = 4096 * 4097 // 2
    # 4 x 16 x 128 FLOPs a kept pair forward, 3 x that trained, 6 layers x 4 loops
    assert flops_ouro.attention_core_forward_per_pair(config) == 4 * 16 * 128
    core = flops_ouro.attention_core_train_flops(config, t, tokens)
    assert core == 3 * 2 * pairs * 4 * 16 * 128 * 6 * 4
    # 6 x d x V a position and loop
    head = flops_ouro.head_train_flops(config, tokens)
    assert head == 6 * 2048 * 49152 * 4 * tokens
    parts = flops_ouro.forward_flops_by_part(config, t)
    assert parts["attn_proj"] == 24 * 2 * 4 * 2048 * 2048
    assert parts["mlp"] == 24 * 2 * 3 * 2048 * 5632
    assert parts["head"] == 4 * 2 * 2048 * 49152 and parts["exit_gate"] == 4 * 2 * 2048
    step = flops_ouro.train_flops_per_token(config, t) * tokens
    assert 89e12 < step < 91e12  # about 90 TFLOP a step
    blocks = 3 * tokens * (parts["attn_proj"] + parts["mlp"])
    assert 0.66 < blocks / step < 0.68  # blocks' matmuls 67%
    assert 0.21 < head / step < 0.23  # head and loss 22%
    assert 0.10 < core / step < 0.12  # the attention core 11%
    assert core == pytest.approx(3 * tokens * parts["attn_core"], rel=1e-12)
    assert head == pytest.approx(3 * tokens * parts["head"], rel=1e-12)
