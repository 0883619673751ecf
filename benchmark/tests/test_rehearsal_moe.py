"""CPU rehearsal of the ``moe_lm_trial`` entry at a tiny size: control
flow, the record its readers take, the counter beside the losses. No
number from here is a device number."""

import jax
import pytest

from benchmark import cells, flops_joyai, moe_scopes
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "moe_lm_trial",
    "reference": "benchmark/configs/joyai-llm-flash.reference.py",
    "vocab_size": 61, "hidden_size": 32, "num_attention_heads": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0, "intermediate_size": 48,
    "router_width": 16, "experts_held": [4, 4], "num_experts_per_tok": 4,
    "moe_intermediate_size": 24, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "bfloat16", "remat": True},
    # wide: at this size one changed choice of four moves a token's logits
    "compared": {"logits_rel_rms": 0.15, "loss_rel": 0.02, "grad_rel_l2": 0.4,
                 "router_grad_rel_l2": 0.8,
                 "routing_diff_share": 0.3},
}
TINY_TRAFFIC = {"name": "tiny", "batch_sequences": 4, "sequence_length": 32,
                "learning_rates": [1e-2]}


@pytest.fixture(scope="module")
def record():
    real = cells.load_cell("moe-mla-t4096")
    cell = cells.Cell(name=real.name, chips=1, config=TINY_CONFIG, traffic=TINY_TRAFFIC,
                      end_to_end=real.end_to_end, per_layer=real.per_layer)
    got = cell.entry().run(cell, jax.devices()[:1], 2147483659, 1.0, None, CompileBook())
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
    assert any("experts chosen differ in" in note for note in got["reference"]["notes"])


def test_the_counter_is_kept_step_by_step(record):
    _, got = record
    counts = got["expert_counts"]  # (steps of the window, expert layers, experts held)
    assert counts.shape == (got["attempted"], 2, 4)
    # 128 tokens choose 4 of 16 experts, 4 of them held: 128 a layer on average
    assert 0 < counts.sum(axis=-1).mean() < 4 * 128
    per_token = counts.sum(axis=-1).mean() / 128
    assert got["flops_per_unit"] == flops_joyai.train_flops_per_token(TINY_CONFIG, 32, per_token)
    assert moe_scopes.assignments_per_step(got) == counts.sum(axis=(1, 2)).mean()
    assert moe_scopes.load_max_over_mean(got) >= 1.0


def test_readers_of_an_untraced_run_leave_the_traced_metrics_out(record):
    cell, got = record
    new = [m for m in cell.per_layer if m.get("workloads") == ["moe-mla-t4096"]]
    assert len(new) == 7
    read = cells.read_metrics(new, "layer_metrics", got)
    assert set(read) == {"expert_load_max_over_mean"}


def test_classify_looks_inside_the_expert_layer():
    path = "jit(step_fn)/jit(main)/transpose(jvp(LatentMoELM))/block_2/moe/experts/ragged_dot"
    assert moe_scopes.classify(path) == "experts"
    assert moe_scopes.classify(path.replace("experts", "cond/branch_1_fun/expert_dispatch")) \
        == "expert_dispatch"
    assert moe_scopes.classify("jit(step_fn)/block_2/moe/add") == moe_scopes.OTHER
    assert moe_scopes.classify("jit(step_fn)/block_2/q/q_a/dot_general") is None
    assert moe_scopes.classify("jit(step_fn)/block_0/mlp/experts/x") is None  # not inside moe
    assert moe_scopes.classify(None) is None


def test_flops_match_the_issue_s_count():
    """ISSUE 27's arithmetic: 2.39 GFLOP a trained token at T = 4,096
    with half an assignment a token and layer landing here."""
    import json
    import os
    with open(os.path.join(cells.ROOT, "benchmark/configs/joyai-llm-flash.json")) as f:
        config = json.load(f)
    per_token = flops_joyai.train_flops_per_token(config, 4096, 0.5)
    assert abs(per_token / 1e9 - 2.39) < 0.01
    core = flops_joyai.attention_core_train_flops(config, 4096, 1)
    assert abs(core / 1e9 - 0.755) < 0.001
    assert flops_joyai.expert_train_flops_per_assignment(config) == 6 * 3 * 2048 * 768
