"""CPU rehearsal of the ``ssm_lm_trial`` entry at a tiny size (control
flow, the record its readers take, the counter compared with the
reference's), the five-scope split of ``ssm_scopes`` on hand-made
events, and the counts ``yoco_core_roofline``, ``ssm_scan_roofline``
and the cell's ``mfu`` divide by against counts by hand. No number from
here is a device number."""

import json
import os

import jax
import pytest

from benchmark import cells, flops_phi4flash, scope_reduce, ssm_scopes, swa_scopes, trace_reduce
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "ssm_lm_trial",
    "reference": "benchmark/configs/phi-4-mini-flash.reference.py",
    "vocab_size": 61, "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 64, "num_hidden_layers": 6, "mb_per_layer": 2, "sliding_window": 8,
    "layer_kinds": ["mamba", "window", "mamba_memory", "full_kv", "gmu", "cross"],
    "layer_norm_eps": 1e-5, "max_position_embeddings": 32, "tie_word_embeddings": True,
    "assumed": {"compute_dtype": "bfloat16", "remat": True, "d_state": 8, "d_conv": 4,
                "expand": 2, "dt_rank": 2},
    # wide: bf16 at a toy width
    "compared": {"logits_rel_rms": 0.1, "loss_rel": 0.02, "grad_rel_l2": 0.5,
                 "scan_grad_rel_l2": 0.5, "ssm_state_rms_rel": 0.1, "param_change_rel_l2": 0.9},
}
TINY_TRAFFIC = {"name": "tiny", "batch_sequences": 4, "sequence_length": 32,
                "learning_rates": [1e-2]}


@pytest.fixture(scope="module")
def record():
    real = cells.load_cell("ssm-yoco-t16384")
    assert real.config["entry"] == "ssm_lm_trial" and real.traffic["batch_sequences"] == 1
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
    assert set(got["reference"]["errors"]) == set(TINY_CONFIG["compared"])
    assert any("ssm_state_rms program" in note for note in got["reference"]["notes"])
    assert got["flops_per_unit"] == flops_phi4flash.train_flops_per_token(TINY_CONFIG, 32)


def test_the_cell_reports_the_shared_metrics_and_leaves_the_traced_ones_out(record):
    cell, got = record
    names = {m["name"] for m in cell.per_layer}
    assert {"ssm_scan_ms", "ssm_proj_ms", "ssm_conv_ms", "gmu_ms", "attn_cross_ms",
            "ssm_scan_roofline", "yoco_core_roofline", "attn_full_ms", "attn_window_ms", "mfu",
            "attn_core_ms", "attn_proj_ms", "mlp_ms", "unscoped_share"} <= names
    assert not {"router_ms", "swa_core_roofline", "mla_core_roofline", "hc_mix_ms"} & names
    read = cells.read_metrics(cell.per_layer, "layer_metrics", got)
    assert {"mfu", "step_ms"} <= set(read)
    assert not {"ssm_scan_ms", "ssm_scan_roofline", "yoco_core_roofline", "gmu_ms"} & set(read)


LM = "jit(step_fn)/jvp(SambaYLM)"
BACK = "jit(step_fn)/transpose(jvp(SambaYLM))/jvp(SambaYLM)/checkpoint"


@pytest.mark.parametrize("path, expected, accepted", [
    (f"{LM}/block_0/ssm_scan/jit(_kernel_fwd)/scan_fwd", "ssm_scan", "block_other"),
    (f"{BACK}/block_2/ssm_scan/jit(_kernel_bwd)/scan_bwd:", "ssm_scan", "block_other"),
    (f"{LM}/block_0/ssm_proj/in_proj/dot_general", "ssm_proj", "block_other"),
    (f"{BACK}/rematted_computation/block_0/ssm_conv/mul", "ssm_conv", "block_other"),
    (f"{LM}/block_4/gmu/out_proj/dot_general", "gmu", "block_other"),
    (f"{LM}/block_5/attn_core/attn_cross/jit(_grouped64_fwd_call)/grouped64_fwd", "attn_cross",
     "attn_core"),
    (f"{LM}/block_3/attn_core/attn_full/jit(_grouped64_fwd_call)/grouped64_fwd", None,
     "attn_core"),
    (f"{LM}/block_5/q/q/dot_general", None, "attn_proj"),
    (f"{LM}/head/dot_general", None, "head"),
    ("", None, "unscoped"), (None, None, "unscoped"),
])
def test_classify_finds_the_five_scopes(path, expected, accepted):
    assert ssm_scopes.classify(path) == expected
    assert scope_reduce.classify(path)[0] == accepted  # what the accepted split makes of it


def test_reduce_on_hand_made_events():
    """Two steps in the window; on one chip 6 ms under ``ssm_scan`` (an
    operation nested in another counts once), 5 under ``gmu``, and an
    operation under none of the five."""
    ms = 1_000_000
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        (host, "t", trace_reduce.WINDOW_SPAN, 0, 100 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 1 * ms, 40 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 50 * ms, 40 * ms, None),
        (dev, "ops", "scan_fwd.1", 10 * ms, 6 * ms, f"{LM}/block_0/ssm_scan/a"),
        (dev, "ops", "inner", 11 * ms, 1 * ms, f"{LM}/block_0/ssm_scan/a/b"),
        (dev, "ops", "fusion.2", 20 * ms, 5 * ms, f"{BACK}/block_4/gmu/in_proj/c"),
        (dev, "ops", "fusion.4", 30 * ms, 7 * ms, f"{LM}/block_1/q/qkv/dot_general"),
        (dev, "ops", "late", 200 * ms, 9 * ms, f"{LM}/block_0/ssm_scan/a"),  # past it
    ]
    got = swa_scopes.reduce_by(events, ssm_scopes.classify)
    assert got["steps"] == 2
    assert got["seconds"] == pytest.approx({"ssm_scan": 6e-3, "gmu": 5e-3})
    # a program without the scopes (the parent): nothing to read, and nothing raised
    plain = [e for e in events if ssm_scopes.classify(e[5]) is None]
    assert swa_scopes.reduce_by(plain, ssm_scopes.classify) is None


def test_readers_find_nothing_in_a_record_without_a_trace():
    record = {"trace": None, "config": {}, "device": {"kind": "TPU v5 lite"}}
    assert ssm_scopes.ms_per_step(record, "ssm_scan") is None
    assert ssm_scopes.scan_roofline_share(record) is None
    assert ssm_scopes.core_roofline_share(record) is None


def test_counts_against_counts_by_hand():
    with open(os.path.join(cells.ROOT, "benchmark/configs/phi-4-mini-flash.json")) as f:
        config = json.load(f)
    t = 16384
    full, window = flops_phi4flash.kept_pairs(t, None), flops_phi4flash.kept_pairs(t, 512)
    assert (full, window) == (134_225_920, 512 * 513 // 2 + (t - 512) * 512)
    assert flops_phi4flash.core_pairs(config, t) == 2 * full + window  # full, cross; window
    # 2 x 40 x 128 FLOPs a kept pair forward, 3 x that trained
    assert flops_phi4flash.attention_core_forward_per_pair(config) == 2 * 40 * 128
    core = flops_phi4flash.attention_core_train_flops(config, t, t)
    assert core == 3 * (2 * full + window) * 2 * 40 * 128
    # the mixers' weights a token meets, from the issue's parameter counts less what no
    # token is multiplied by (the convolution, A_log, D, dt's bias)
    assert flops_phi4flash.mixer_weights(config, "mamba") == 41_241_600 - 25_600 - 81_920 - 2 * 5_120
    assert flops_phi4flash.mixer_weights(config, "window") == 19_660_800
    assert flops_phi4flash.mixer_weights(config, "gmu") == 26_214_400
    assert flops_phi4flash.mixer_weights(config, "cross") == 13_107_200
    whole = flops_phi4flash.train_flops_per_token(config, t) * t
    assert 76e12 < whole < 79e12  # 25.7 TFLOP forward, x 3
    # the scan's bytes: 2 layers, E = 5,120, N = 16, bf16
    e, n = 5120, 16
    assert flops_phi4flash.scan_train_bytes(config, t) == 2 * t * 2 * (
        (3 * e + 2 * n) + (5 * e + 4 * n))
