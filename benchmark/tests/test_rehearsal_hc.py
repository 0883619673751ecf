"""CPU rehearsal of the ``hc_moe_lm_trial`` entry at a tiny size
(control flow, the record its readers take, the two counters beside the
losses), the two-scope split of ``hc_scopes`` on hand-made events, and
the counts ``hc_mix_roofline`` and the cell's ``mfu`` divide by. No
number from here is a device number."""

import json
import os

import jax
import pytest

from benchmark import cells, flops_hc, flops_joyai, hc_scopes, scope_reduce, trace_reduce
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "hc_moe_lm_trial",
    "reference": "benchmark/configs/xing4.0-29b-a4b.reference.py",
    # two layers, a dense and an expert one: XLA:CPU fuses the third layer's sums of
    # stream gradients into two loops that take 2 s a step
    "vocab_size": 61, "hidden_size": 32, "num_attention_heads": 2, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0, "intermediate_size": 48,
    "router_width": 16, "experts_held": [4, 4], "num_experts_per_tok": 4,
    "moe_intermediate_size": 24, "n_shared_experts": 1, "routed_scaling_factor": 2.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "assumed": {"compute_dtype": "bfloat16", "remat": True},
    # wide: at this size one changed choice of four moves a token's logits, and a gate's
    # gradient is a sum of 128 terms that nearly cancel
    "compared": {"logits_rel_rms": 0.15, "loss_rel": 0.02, "grad_rel_l2": 0.9,
                 "router_grad_rel_l2": 0.8, "hc_grad_rel_l2": 0.9, "routing_diff_share": 0.3},
}
TINY_TRAFFIC = {"name": "tiny", "batch_sequences": 4, "sequence_length": 32,
                "learning_rates": [1e-2]}


@pytest.fixture(scope="module")
def record():
    real = cells.load_cell("moe-mhc-t4096")
    assert real.config["entry"] == "hc_moe_lm_trial" and real.traffic["batch_sequences"] == 2
    cell = cells.Cell(name=real.name, chips=1, config=TINY_CONFIG, traffic=TINY_TRAFFIC,
                      end_to_end=real.end_to_end, per_layer=real.per_layer)
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
    notes = got["reference"]["notes"]
    assert any("experts chosen differ in" in note for note in notes)
    assert any(note.startswith("hc_marginal_err program") and "reference" in note
               for note in notes)
    assert any(note.startswith("the connections' ") for note in notes)


def test_both_counters_are_kept_step_by_step(record):
    _, got = record
    assert got["expert_counts"].shape == (got["attempted"], 1, 4)
    errs = got["hc_marginal_err"]
    assert errs.shape == (got["attempted"],) and (errs >= 0).all() and (errs < 0.5).all()
    per_token = got["expert_counts"].sum(axis=-1).mean() / 128
    assert got["flops_per_unit"] == flops_hc.train_flops_per_token(TINY_CONFIG, 32, per_token)


def test_the_cell_reports_the_shared_metrics_and_leaves_the_traced_ones_out(record):
    cell, got = record
    names = {m["name"] for m in cell.per_layer}
    assert {"hc_maps_ms", "hc_mix_ms", "hc_mix_roofline", "mfu", "attn_core_ms",
            "unscoped_share"} <= names
    assert not {"router_ms", "mla_core_roofline"} & names  # limited to moe-mla-t4096
    read = cells.read_metrics(cell.per_layer, "layer_metrics", got)
    assert "mfu" in read and "step_ms" in read
    assert not {"hc_maps_ms", "hc_mix_ms", "hc_mix_roofline"} & set(read)  # untraced: left out


LM = "jit(step_fn)/jvp(LatentMoELM)"
BACK = "jit(step_fn)/transpose(jvp(LatentMoELM))/jvp(LatentMoELM)/checkpoint"


@pytest.mark.parametrize("path, expected", [
    (f"{LM}/block_2/hc_attn/hc_maps/dot_general", "hc_maps"),
    (f"{LM}/block_2/hc_mlp/hc_maps/while/body/div", "hc_maps"),
    (f"{BACK}/rematted_computation/block_0/hc_mix/mul", "hc_mix"),
    (f"{BACK}/block_0/hc_mix/reduce_sum:", "hc_mix"),
    (f"{LM}/hc_mix/add", "hc_mix"),  # the streams summed before ln_out
    (f"{LM}/block_2/q/q_a/dot_general", None),
    (f"{LM}/block_2/moe/experts/ragged_dot", None),
    (f"{LM}/block_2/add", None),
    ("", None), (None, None),
])
def test_classify_finds_the_two_scopes(path, expected):
    assert hc_scopes.classify(path) == expected
    if expected and "block_" in path:  # what the accepted split makes of the same path
        assert scope_reduce.classify(path)[0] == "block_other"


def test_reduce_hc_on_hand_made_events():
    """Two steps in the window; on one chip 3 ms under ``hc_maps`` (one
    operation nested in another counts once), 5 under ``hc_mix``, and
    an operation under neither."""
    ms = 1_000_000
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        (host, "t", trace_reduce.WINDOW_SPAN, 0, 100 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 1 * ms, 40 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 50 * ms, 40 * ms, None),
        (dev, "ops", "fusion.1", 10 * ms, 3 * ms, f"{LM}/block_0/hc_attn/hc_maps/while"),
        (dev, "ops", "fusion.2", 11 * ms, 1 * ms, f"{LM}/block_0/hc_attn/hc_maps/while/body/div"),
        (dev, "ops", "fusion.3", 20 * ms, 5 * ms, f"{BACK}/block_0/hc_mix/mul"),
        (dev, "ops", "fusion.4", 30 * ms, 7 * ms, f"{LM}/block_0/q/q_a/dot_general"),
        (dev, "ops", "fusion.5", 200 * ms, 9 * ms, f"{LM}/block_0/hc_mix/mul"),  # past the window
    ]
    got = hc_scopes.reduce_hc(events)
    assert got["steps"] == 2
    assert got["seconds"] == pytest.approx({"hc_maps": 3e-3, "hc_mix": 5e-3})
    # a program without the scopes: nothing to read, and nothing raised
    assert hc_scopes.reduce_hc([e for e in events if "hc_" not in (e[5] or "")]) is None


def test_readers_find_nothing_in_a_record_without_a_trace():
    record = {"trace": None, "config": {}, "device": {"kind": "TPU v5 lite"}}
    assert hc_scopes.ms_per_step(record, "hc_mix") is None
    assert hc_scopes.mix_roofline_share(record) is None


def test_counts_at_the_published_widths():
    with open(os.path.join(cells.ROOT, "benchmark/configs/xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    # per sublayer and token (2*4 + 2) + (3*4 + 2) = 24 widths of 3,584, bf16; 10 sublayers
    per_step = hc_scopes.mix_bytes_per_step(config, 8192)
    assert per_step == 10 * 8192 * 24 * 3584 * 2 == 14_092_861_440
    assert abs(per_step / 819e9 * 1e3 - 17.2) < 0.05  # ms a step at the HBM roof
    # the maps' products: 14,336 x 24 weights a connection, 2 FLOPs each, x 3 for training
    assert flops_hc.maps_forward_per_token(config) == 10 * 2 * 14336 * 24
    whole = flops_hc.train_flops_per_token(config, 4096, 0.5)
    assert whole - flops_joyai.train_flops_per_token(config, 4096, 0.5) == 3 * 10 * 2 * 14336 * 24
    assert 2.5e9 < whole < 3.5e9
