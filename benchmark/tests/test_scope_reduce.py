"""The split of device time by scope: ``classify`` on real path
strings and on every op_name of the tiny step's own HLO, the
innermost-event rule on hand-made events, the reader of the profiler's
file on a hand-made ``XSpace``, and ``reduce_scoped`` on a trace
recorded on the chip (``data/``)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark import cells, scope_reduce as sr, trace_reduce as tr
from multidisttorch_tpu.models.transformer import TransformerLM
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # ns
LM = "jit(step_fn)/jvp(TransformerLM)"
BACK = "jit(step_fn)/transpose(jvp(TransformerLM))"


@pytest.mark.parametrize("path, expected", [
    # ISSUE 25's own examples
    (f"{LM}/block_0/q", ("attn_proj", "forward")),
    (f"{BACK}/jvp(TransformerLM)/checkpoint/block_0/q", ("attn_proj", "backward")),
    (f"{BACK}/jvp(TransformerLM)/checkpoint/rematted_computation/block_0/q",
     ("attn_proj", "recompute")),
    (f"{BACK}/block_0/q", ("attn_proj", "backward")),  # without remat
    # the four scopes; JAX wraps a scope opened outside the model in its own markers
    (f"{LM}/block_3/attn_core/bqhd,bkhd->bhqk/dot_general", ("attn_core", "forward")),
    (f"{BACK}/jvp(TransformerLM)/checkpoint/rematted_computation/block_3/attn_core/exp",
     ("attn_core", "recompute")),
    (f"{LM}/block_0/mlp/up/dot_general", ("mlp", "forward")),
    (f"{BACK}/jvp(TransformerLM)/checkpoint/block_1/mlp/mul", ("mlp", "backward")),
    ("jit(step_fn)/jvp(loss)/jit(log_softmax)/reduce_max", ("loss", "forward")),
    ("jit(step_fn)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add", ("loss", "backward")),
    ("jit(step_fn)/optimizer/mul", ("optimizer", "none")),
    # flax's names
    (f"{LM}/block_0/moe/router/dot_general", ("mlp", "forward")),
    (f"{LM}/block_11/ln_mlp/rsqrt", ("norm", "forward")),
    (f"{LM}/ln_out/mul", ("head", "forward")),
    (f"{BACK}/head/dot_general", ("head", "backward")),
    (f"{LM}/tok_embed/jit(_take)/gather", ("embed", "forward")),
    (f"{BACK}/pos_embed/jit(_take)/scatter-add", ("embed", "backward")),
    # what the split leaks
    (f"{LM}/block_7/add", ("block_other", "forward")),
    (f"{LM}/add", ("unscoped", "forward")),
    ("jit(step_fn)/add", ("unscoped", "none")),
    ("state.params['block_0']['up']['kernel']", ("unscoped", "none")),
    ("", ("unscoped", "none")),
    (None, ("unscoped", "none")),
    # the profiler's stat is "<op_name>:<op_type>"
    (f"{LM}/block_0/k/dot_general:", ("attn_proj", "forward")),
    # a primitive's name is no scope
    ("jit(step_fn)/jvp(TransformerLM)/block_0/proj/add", ("attn_proj", "forward")),
])
def test_classify(path, expected):
    assert sr.classify(path) == expected


def test_classify_every_op_name_of_the_tiny_step():
    """The step as the benchmark builds it (remat on), 2 layers: every
    part and every pass is found, and what lands in ``unscoped`` is the
    parameters, the embeddings' sum and the step counter."""
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    model = TransformerLM(vocab_size=64, d_model=32, num_heads=4, num_layers=2, max_len=16,
                          remat=True)
    tx = optax.adam(1e-3)
    state = create_lm_state(group, model, tx, jax.random.key(0))
    step = make_lm_train_step(group, model, tx)
    text = step.lower(state, jnp.zeros((2, 16), jnp.int32)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    got = [(n, *sr.classify(n)) for n in names]
    assert {part for _, part, _ in got} == set(sr.PARTS)
    assert {which for _, _, which in got} == set(sr.PASSES)
    for part in ("attn_core", "attn_proj", "mlp", "norm"):
        assert {w for _, p, w in got if p == part} == {"forward", "recompute", "backward"}
    assert {w for _, p, w in got if p == "loss"} == {"forward", "backward"}
    assert {w for _, p, w in got if p == "optimizer"} == {"none"}
    in_step = [g for g in got if g[0].startswith("jit(step_fn)")]
    unscoped = [n for n, part, _ in in_step if part == "unscoped"]
    assert len(unscoped) / len(in_step) < 0.05
    # outside the step's own name stack: parameters, and the bodies of reductions
    for n, part, _ in got:
        if not n.startswith("jit(step_fn)"):
            assert part == "unscoped" and (n.startswith(("state.", "tokens")) or "/" not in n), n


def dev(chip, path, start_ms, dur_ms):
    return (f"/device:TPU:{chip}", tr.OPS_LINE, "op", start_ms * MS, dur_ms * MS, path)


def host(name, start_ms, dur_ms):
    return ("/host:CPU", "python3", name, start_ms * MS, dur_ms * MS, None)


def test_innermost():
    """An instant belongs to the latest-started event that covers it."""
    got = sr.innermost([(0, 100, "while"), (10, 30, "a"), (30, 60, "b"), (40, 50, "c"),
                        (120, 130, "a"), (125, 140, "d")])
    assert got == {"while": 10 + 40, "a": 20 + 5, "b": 20, "c": 10, "d": 15}
    assert sum(got.values()) == sum(b - a for a, b in tr.union(
        [(0, 100), (10, 30), (30, 60), (40, 50), (120, 130), (125, 140)]))
    # of two that start together the shorter is the inner one
    assert sr.innermost([(0, 10, "outer"), (0, 4, "inner")]) == {"outer": 6, "inner": 4}
    assert sr.innermost([]) == {}


def test_reduce_scoped_known_answer():
    """Window 100 ms, two steps, two chips. Chip 0 runs a ``while``
    (no path) that holds an attention op and an mlp op; chip 1 runs the
    optimizer, partly before the window."""
    events = [
        host(tr.WINDOW_SPAN, 1000, 100),
        host("host:_wait", 1001, 48), host("host:_wait", 1052, 47.5),
        host("host:_wait", 1099.8, 3),  # ends outside: not a step of the window
        dev(0, None, 1000, 80),
        dev(0, f"{LM}/block_0/attn_core/exp:", 1010, 20),
        dev(0, f"{BACK}/jvp(TransformerLM)/checkpoint/rematted_computation/block_0/mlp/up/dot_general:",
            1040, 30),
        dev(1, "jit(step_fn)/optimizer/mul:", 990, 50),
    ]
    got = sr.reduce_scoped(events)
    assert got["steps"] == 2 and got["window_s"] == pytest.approx(0.100)
    assert got["traced_step_ms"] == pytest.approx(50.5)
    assert got["seconds"] == pytest.approx({
        ("unscoped", "none"): 0.030 / 2, ("attn_core", "forward"): 0.020 / 2,
        ("mlp", "recompute"): 0.030 / 2, ("optimizer", "none"): 0.040 / 2,
    })
    assert got["busy_s"] == pytest.approx(tr.reduce_events([e[:5] for e in events])["busy_s"])
    line = sr.format_table(got)
    assert "steps=2" in line and "attn_core:forward=5.000" in line and "mlp:recompute=7.500" in line


def test_no_path_anywhere_is_none():
    """The CPU's profiler records no scope path: nothing to report."""
    assert sr.reduce_scoped([host(tr.WINDOW_SPAN, 0, 10), dev(0, None, 1, 5)]) is None


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 7000000 }
    events { metadata_id: 1 offset_ps: 25000000 duration_ps: 7000000 }
    events { metadata_id: 2 offset_ps: 15000000 duration_ps: 3000000 } }
  lines { id: 2 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[] fusion()"
      stats { metadata_id: 3 str_value: "jit(step_fn)/jvp(TransformerLM)/block_0/q/dot_general:" }
      stats { metadata_id: 4 uint64_value: 123 } stats { metadata_id: 5 ref_value: 6 } } }
  event_metadata { key: 2 value { id: 2 name: "%copy-start.1 = f32[] copy-start()" } }
  stat_metadata { key: 3 value { id: 3 name: "tf_op" } }
  stat_metadata { key: 4 value { id: 4 name: "flops" } }
  stat_metadata { key: 5 value { id: 5 name: "hlo_category" } }
  stat_metadata { key: 6 value { id: 6 name: "convolution" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 17000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 18000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 18000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:_traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "host:_wait" } }
  event_metadata { key: 3 value { id: 3 name: "somebody else's span" } } }
"""


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """A hand-made profiler file where ``benchmark/run.py`` keeps its own."""
    from jax._src.lib import _profile_data

    where = tmp_path / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        _profile_data.ProfileData.text_proto_to_serialized_xspace(XSPACE))
    monkeypatch.setattr(sr, "TRACE_DIR", str(tmp_path))
    return str(tmp_path)


def test_the_file_reader_finds_the_metadata_stats(trace_dir):
    path = tr.find_xplane(trace_dir)
    assert sr.metadata_stats(path) == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()": {
            "tf_op": "jit(step_fn)/jvp(TransformerLM)/block_0/q/dot_general:",
            "flops": 123, "hlo_category": "convolution"},
        "%copy-start.1 = f32[] copy-start()": {},
    }}
    events = sr.load_scoped_events(path)
    assert [e[:5] for e in events] == tr.load_events(path)
    assert sorted({e[5] for e in events if e[5]}) == [
        "jit(step_fn)/jvp(TransformerLM)/block_0/q/dot_general:"]


def test_readers_over_a_traced_record(trace_dir, capsys):
    """All eight readers over one record: one parse, one progress line
    with the table, ``None`` untraced and for another run's trace."""
    reduced = tr.reduce_trace(trace_dir)
    record = {"trace": reduced}
    names = ["attn_core_ms", "attn_proj_ms", "mlp_ms", "head_loss_ms", "optimizer_ms",
             "recompute_ms", "backward_ms", "unscoped_share"]
    metrics = [m for m in cells.load_cell("lm-dense").per_layer if m["name"] in names]
    assert [m["name"] for m in metrics] == names
    got = cells.read_metrics(metrics, "layer_metrics", record)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "attn_core_ms": 0.0, "attn_proj_ms": 14e-3 / 2, "mlp_ms": 0.0, "head_loss_ms": 0.0,
        "optimizer_ms": 0.0, "recompute_ms": 0.0, "backward_ms": 0.0,
        "unscoped_share": 100 * 3 / 17,
    })
    out = capsys.readouterr().out
    assert out.count("[benchmark] scopes ms/step steps=2") == 1
    assert "attn_proj:forward=0.007" in out and "nothing ran under" in out
    assert cells.read_metrics(metrics, "layer_metrics", {"trace": None}) == {}
    stale = {"trace": dict(reduced, window_s=reduced["window_s"] * 2)}
    assert cells.read_metrics(metrics, "layer_metrics", stale) == {}


def test_a_trace_that_cannot_be_read_leaves_the_metrics_out(tmp_path, monkeypatch, capsys):
    """No file, or one the reader cannot parse: no metric, no exception."""
    monkeypatch.setattr(sr, "TRACE_DIR", str(tmp_path))
    metrics = [m for m in cells.load_cell("lm-dense").per_layer if m["name"] == "mlp_ms"]
    assert cells.read_metrics(metrics, "layer_metrics", {"trace": {"window_s": 1.0}}) == {}
    assert "[benchmark] scopes: the trace was not reduced: FileNotFoundError" in capsys.readouterr().out


def test_recorded_v5e_trace():
    """The first 60 ms of ``lm-short-t256``'s traced part as the v5e
    recorded it with the scopes in (PR 25, ``dump_event_stats.py``;
    operation names cut to 60 characters): the parts sum to the busy
    time ``trace_reduce`` gives for the same events."""
    with open(os.path.join(DATA, "v5e_lm_short_t256_scoped_first60ms.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    got = sr.reduce_scoped(events)
    busy = tr.reduce_events([e[:5] for e in events])["busy_s"]
    assert got["busy_s"] == pytest.approx(busy, rel=1e-3)
    assert got["window_s"] == pytest.approx(0.060)
    assert got["steps"] == 0  # a step is 375 ms
    # read off this file when it was cut: the first blocks' forward
    assert got["seconds"] == pytest.approx({
        ("attn_core", "forward"): 0.010366249, ("attn_proj", "forward"): 0.014492983,
        ("mlp", "forward"): 0.035059322, ("norm", "forward"): 1.623e-06,
        ("unscoped", "none"): 7.8539e-05,
    }, abs=1e-9)
