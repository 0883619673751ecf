"""CPU rehearsal of the ``conv_moe_lm_trial`` entry at a tiny size
(control flow, the record its readers take, the routing compared with
the reference's), the three-scope split of ``conv_scopes`` on hand-made
events, and the counts ``gqa64_core_roofline``, ``conv_mix_roofline``,
``experts_roofline`` and the cell's ``mfu`` divide by against counts by
hand. No number from here is a device number."""

import json
import os

import jax
import pytest

from benchmark import (
    cells, conv_scopes, flops_joyai, flops_lfm2, moe_scopes, scope_reduce, swa_scopes,
    trace_reduce,
)
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "conv_moe_lm_trial",
    "reference": "benchmark/configs/lfm2-24b-a2b.reference.py",
    "vocab_size": 61, "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"], "num_hidden_layers": 5,
    "num_dense_layers": 1, "conv_L_cache": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 10000.0}, "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "router_width": 8,
    "experts_held": [2, 4], "num_experts": 4, "num_experts_per_tok": 2,
    "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "bfloat16", "remat": True, "embedding_stddev": 1.0,
                "tie_word_embeddings": True, "absent_share_grad": False},
    # wide: bf16 at a toy width
    "compared": {"logits_rel_rms": 0.1, "loss_rel": 0.02, "grad_rel_l2": 0.6,
                 "conv_grad_rel_l2": 0.5, "attn_grad_rel_l2": 0.5, "router_grad_rel_l2": 0.9,
                 "routing_diff_share": 0.3, "param_change_rel_l2": 0.9},
}
TINY_TRAFFIC = {"name": "tiny", "batch_sequences": 4, "sequence_length": 32,
                "learning_rates": [1e-2]}


@pytest.fixture(scope="module")
def record():
    real = cells.load_cell("moe-conv-t8192")
    assert real.config["entry"] == "conv_moe_lm_trial" and real.traffic["batch_sequences"] == 4
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
    assert len(jax.devices()) == 4
    assert got["checks"] == {
        "reference": True, "losses_finite": True, "losses_falling": True,
        "nothing_compiled_in_window": True,
    }, got["reference"]["notes"]
    assert got["correct"] and got["failed"] == 0
    assert got["attempted"] == len(got["stamps"]) - 1
    assert set(cells.read_metrics(cell.end_to_end, "end_to_end", got)) == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(got["reference"]["errors"]) == set(TINY_CONFIG["compared"])
    # the step's counter: (steps of the window, expert layers, experts held)
    assert got["expert_counts"].shape[1:] == (4, 4)
    a_layer = float(got["expert_counts"].sum(axis=-1).mean()) / (4 * 32)  # assignments a token
    assert got["flops_per_unit"] == pytest.approx(
        flops_lfm2.train_flops_per_token(TINY_CONFIG, 32, a_layer), rel=1e-6)
    assert moe_scopes.load_max_over_mean(got) >= 1.0


def test_the_cell_reports_the_shared_metrics_and_leaves_the_traced_ones_out(record):
    cell, got = record
    names = {m["name"] for m in cell.per_layer}
    assert {"conv_proj_ms", "conv_mix_ms", "qk_norm_ms", "conv_mix_roofline",
            "gqa64_core_roofline", "attn_full_ms", "router_ms", "expert_dispatch_ms",
            "experts_ms", "experts_roofline", "expert_load_max_over_mean", "mfu",
            "attn_core_ms", "attn_proj_ms", "mlp_ms", "unscoped_share"} <= names
    assert not {"attn_window_ms", "swa_core_roofline", "yoco_core_roofline", "mla_core_roofline",
                "shared_expert_ms", "hc_mix_ms", "ssm_scan_ms"} & names
    read = cells.read_metrics(cell.per_layer, "layer_metrics", got)
    assert {"mfu", "step_ms", "expert_load_max_over_mean"} <= set(read)
    assert not {"conv_proj_ms", "conv_mix_ms", "qk_norm_ms", "conv_mix_roofline",
                "gqa64_core_roofline", "experts_roofline", "router_ms"} & set(read)


LM = "jit(step_fn)/jvp(ShortConvMoELM)"
BACK = "jit(step_fn)/transpose(jvp(ShortConvMoELM))/jvp(ShortConvMoELM)/checkpoint"


@pytest.mark.parametrize("path, expected, accepted", [
    (f"{LM}/block_0/conv_proj/in_proj/dot_general", "conv_proj", "block_other"),
    (f"{BACK}/block_2/conv_proj/out_proj/dot_general:", "conv_proj", "block_other"),
    (f"{BACK}/rematted_computation/block_0/conv_mix/mul", "conv_mix", "block_other"),
    (f"{LM}/block_1/qk_norm/q_norm/mul", "qk_norm", "block_other"),
    (f"{LM}/block_1/attn_core/attn_full/jit(_grouped64_fwd_call)/grouped64_fwd", None,
     "attn_core"),
    (f"{LM}/block_1/q/q/dot_general", None, "attn_proj"),
    (f"{LM}/block_0/mlp/gate/dot_general", None, "mlp"),
    (f"{LM}/block_3/moe/router/dot_general", None, "mlp"),
    (f"{LM}/head/dot_general", None, "head"),
    ("", None, "unscoped"), (None, None, "unscoped"),
])
def test_classify_finds_the_three_scopes(path, expected, accepted):
    assert conv_scopes.classify(path) == expected
    assert scope_reduce.classify(path)[0] == accepted  # what the accepted split makes of it


def test_reduce_on_hand_made_events():
    """Two steps in the window; on one chip 6 ms under ``conv_mix`` (an
    operation nested in another counts once), 5 under ``conv_proj``, 2
    under ``qk_norm`` and an operation under none of the three."""
    ms = 1_000_000
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        (host, "t", trace_reduce.WINDOW_SPAN, 0, 100 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 1 * ms, 40 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 50 * ms, 40 * ms, None),
        (dev, "ops", "fusion.1", 10 * ms, 6 * ms, f"{LM}/block_0/conv_mix/mul"),
        (dev, "ops", "inner", 11 * ms, 1 * ms, f"{LM}/block_0/conv_mix/mul/b"),
        (dev, "ops", "fusion.2", 20 * ms, 5 * ms, f"{BACK}/block_2/conv_proj/in_proj/c"),
        (dev, "ops", "fusion.3", 26 * ms, 2 * ms, f"{LM}/block_1/qk_norm/k_norm/mul"),
        (dev, "ops", "fusion.4", 30 * ms, 7 * ms, f"{LM}/block_1/q/q/dot_general"),
        (dev, "ops", "late", 200 * ms, 9 * ms, f"{LM}/block_0/conv_mix/mul"),  # past it
    ]
    got = swa_scopes.reduce_by(events, conv_scopes.classify)
    assert got["steps"] == 2
    assert got["seconds"] == pytest.approx({"conv_mix": 6e-3, "conv_proj": 5e-3, "qk_norm": 2e-3})
    # a program without the scopes (the parent): nothing to read, and nothing raised
    plain = [e for e in events if conv_scopes.classify(e[5]) is None]
    assert swa_scopes.reduce_by(plain, conv_scopes.classify) is None


def test_readers_find_nothing_in_a_record_without_a_trace():
    record = {"trace": None, "config": {}, "device": {"kind": "TPU v5 lite"}}
    assert conv_scopes.ms_per_step(record, "conv_mix") is None
    assert conv_scopes.mix_roofline_share(record) is None
    assert conv_scopes.core_roofline_share(record) is None


def test_counts_against_counts_by_hand():
    with open(os.path.join(cells.ROOT, "benchmark/configs/lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    t, tokens = 8192, 4 * 8192
    assert flops_lfm2.kept_pairs(t) == 8192 * 8193 // 2 == 33_558_528
    # 2 x 32 x 128 FLOPs a kept pair forward, 3 x that trained, one attention layer
    assert flops_lfm2.attention_core_forward_per_pair(config) == 2 * 32 * 128
    core = flops_lfm2.attention_core_train_flops(config, t, tokens)
    assert core == 3 * 4 * 33_558_528 * 2 * 32 * 128
    # the experts' reader takes this file's own keys: 6 x 3 x 2,048 x 1,536 an assignment
    assert flops_joyai.expert_train_flops_per_assignment(config) == 6 * 3 * 2048 * 1536
    # an even load: 4 of 64 choices land on the 8 held, half an assignment a token and layer
    parts = flops_lfm2.forward_flops_by_part(config, t, 0.5)
    assert parts["conv_proj"] == 4 * 2 * (16_783_360 - 6_144)  # the four conv mixers' matrices
    assert parts["attn_proj"] == 2 * (10_485_888 - 128)
    assert parts["dense_mlp"] == 2 * 72_351_744
    assert parts["experts"] == 4 * 2 * 9_437_184 * 0.5
    assert parts["head"] == 2 * 2048 * 8192
    forward = sum(parts.values())
    assert 400e6 < forward < 412e6  # the issue's 406 MFLOP a token
    assert 0.35 < parts["dense_mlp"] / forward < 0.37  # 36% of the forward work
    assert flops_lfm2.train_flops_per_token(config, t, 0.5) == 3 * forward
    # the gates' and taps' bytes: 4 conv layers, 11 arrays of tokens x d in bf16, the taps' gradient
    assert flops_lfm2.conv_mix_train_bytes(config, tokens) == 4 * (
        11 * tokens * 2048 * 2 + 3 * 2048 * 4)
    per_pass = 4 * tokens * 2048 * 2
    assert 0.53e9 < per_pass < 0.55e9  # the issue's 0.54 GB a layer and forward pass
