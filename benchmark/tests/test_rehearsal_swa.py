"""CPU rehearsal of the ``swa_moe_lm_trial`` entry at a tiny size
(control flow, the record its readers take, the counter beside the
losses), the two-scope split of ``swa_scopes`` on hand-made events, and
the counts ``swa_core_roofline`` and the cell's ``mfu`` divide by
against counts by loops. No number from here is a device number."""

import json
import os

import jax
import pytest

from benchmark import cells, flops_swa, moe_scopes, scope_reduce, swa_scopes, trace_reduce
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "swa_moe_lm_trial",
    "reference": "benchmark/configs/smallthinker-21b-a3b.reference.py",
    "vocab_size": 61, "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "sliding_window_size": 8, "rope_theta": 10000.0,
    "router_width": 16, "experts_held": [4, 4], "moe_num_active_primary_experts": 4,
    "moe_ffn_hidden_size": 24, "rms_norm_eps": 1e-6, "max_position_embeddings": 32,
    "assumed": {"compute_dtype": "bfloat16", "remat": True, "embedding_stddev": 3.0},
    # wide: at this size one changed choice of four moves a token's logits
    "compared": {"logits_rel_rms": 0.15, "loss_rel": 0.02, "grad_rel_l2": 0.9,
                 "attn_grad_rel_l2": 0.9, "router_grad_rel_l2": 0.9, "routing_diff_share": 0.3},
}
TINY_TRAFFIC = {"name": "tiny", "batch_sequences": 4, "sequence_length": 32,
                "learning_rates": [1e-2]}


@pytest.fixture(scope="module")
def record():
    real = cells.load_cell("moe-swa-t16384")
    assert real.config["entry"] == "swa_moe_lm_trial" and real.traffic["batch_sequences"] == 1
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
    assert any("experts chosen differ in" in note for note in got["reference"]["notes"])


def test_the_counter_is_kept_step_by_step(record):
    _, got = record
    assert got["expert_counts"].shape == (got["attempted"], 4, 4)
    per_token = got["expert_counts"].sum(axis=-1).mean() / 128
    assert got["flops_per_unit"] == flops_swa.train_flops_per_token(TINY_CONFIG, 32, per_token)
    assert {"config", "sequence_length", "units_per_reading_per_chip"} <= set(got)
    assert moe_scopes.load_max_over_mean(got) >= 1.0


def test_the_cell_reports_the_shared_metrics_and_leaves_the_traced_ones_out(record):
    cell, got = record
    names = {m["name"] for m in cell.per_layer}
    assert {"attn_full_ms", "attn_window_ms", "swa_core_roofline", "mfu", "attn_core_ms",
            "attn_proj_ms", "mlp_ms", "unscoped_share"} <= names
    assert {"router_ms", "expert_dispatch_ms", "experts_ms",
            "expert_load_max_over_mean"} <= names  # the expert layer's split, as moe-mla-t4096's
    # nothing to read, or (experts_roofline) a time that leaves the walk's ragged dots out
    assert not {"shared_expert_ms", "mla_core_roofline", "experts_roofline", "hc_mix_ms"} & names
    read = cells.read_metrics(cell.per_layer, "layer_metrics", got)
    assert {"mfu", "step_ms", "expert_load_max_over_mean"} <= set(read)
    assert not {"attn_full_ms", "swa_core_roofline", "router_ms", "experts_roofline"} & set(read)  # untraced


LM = "jit(step_fn)/jvp(GroupedWindowMoELM)"
BACK = "jit(step_fn)/transpose(jvp(GroupedWindowMoELM))/jvp(GroupedWindowMoELM)/checkpoint"


@pytest.mark.parametrize("path, expected", [
    (f"{LM}/block_0/attn_core/attn_full/jit(_grouped_fwd_call)/grouped_fwd", "attn_full"),
    (f"{BACK}/block_5/attn_core/attn_window/jit(_grouped_bwd_call)/grouped_bwd:", "attn_window"),
    (f"{BACK}/rematted_computation/block_1/attn_core/attn_window/reduce_precision", "attn_window"),
    (f"{LM}/block_2/q/dot_general", None),
    (f"{LM}/block_2/moe/router/dot_general", None),
    ("", None), (None, None),
])
def test_classify_finds_the_two_scopes(path, expected):
    assert swa_scopes.classify(path) == expected
    if expected:  # what the accepted split makes of the same path
        assert scope_reduce.classify(path)[0] == "attn_core"


def test_reduce_on_hand_made_events():
    """Two steps in the window; on one chip 6 ms under ``attn_full``
    (one operation nested in another counts once), 5 under
    ``attn_window``, and an operation under neither."""
    ms = 1_000_000
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        (host, "t", trace_reduce.WINDOW_SPAN, 0, 100 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 1 * ms, 40 * ms, None),
        (host, "t", scope_reduce.STEP_SPAN, 50 * ms, 40 * ms, None),
        (dev, "ops", "grouped_fwd.1", 10 * ms, 6 * ms, f"{LM}/block_0/attn_core/attn_full/a"),
        (dev, "ops", "inner", 11 * ms, 1 * ms, f"{LM}/block_0/attn_core/attn_full/a/b"),
        (dev, "ops", "grouped_bwd.1", 20 * ms, 5 * ms, f"{BACK}/block_1/attn_core/attn_window/c"),
        (dev, "ops", "fusion.4", 30 * ms, 7 * ms, f"{LM}/block_0/q/dot_general"),
        (dev, "ops", "late", 200 * ms, 9 * ms, f"{LM}/block_0/attn_core/attn_full/a"),  # past it
    ]
    got = swa_scopes.reduce_by(events, swa_scopes.classify)
    assert got["steps"] == 2
    assert got["seconds"] == pytest.approx({"attn_full": 6e-3, "attn_window": 5e-3})
    # a program without the scopes: nothing to read, and nothing raised
    plain = [e for e in events if "attn_" not in (e[5] or "")]
    assert swa_scopes.reduce_by(plain, swa_scopes.classify) is None


def test_readers_find_nothing_in_a_record_without_a_trace():
    record = {"trace": None, "config": {}, "device": {"kind": "TPU v5 lite"}}
    assert swa_scopes.ms_per_step(record, "attn_full") is None
    assert swa_scopes.core_roofline_share(record) is None


def test_counts_against_counts_by_loops():
    with open(os.path.join(cells.ROOT, "benchmark/configs/smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    # kept pairs, by the mask's own statement, at a size loops can walk
    for t, window in ((64, None), (64, 16), (64, 64), (64, 100), (48, 1)):
        by_loops = sum(
            1 for i in range(t) for j in range(t)
            if j <= i and (window is None or i - j < window)
        )
        assert flops_swa.kept_pairs(t, window) == by_loops, (t, window)
    t = 16384
    full, window = flops_swa.kept_pairs(t, None), flops_swa.kept_pairs(t, 4096)
    assert (full, window) == (134_225_920, 58_722_304)
    assert abs(window / full - 0.4375) < 1e-3  # what a window layer's core should cost of a full one's
    # the core: 2 full and 6 window layers, 28 heads, q k^T and p v 128 deep, x 3 trained
    core = flops_swa.attention_core_train_flops(config, t, t)
    assert core == 3 * (2 * full + 6 * window) * 2 * 28 * 256
    # the step: projections, router and head by their weights, 2 FLOPs each; the experts by
    # the assignments counted (0.75 a token and layer at even routing)
    weights = 8 * (2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560 + 2560 * 64) + 2560 * 18992
    experts = 8 * 0.75 * 3 * 2560 * 768
    whole = flops_swa.train_flops_per_token(config, t, 0.75)
    assert whole == pytest.approx(3 * (2 * (weights + experts) + core / 3 / t))
    assert 50e12 < whole * t < 53e12  # 51.6 TFLOP a step
