"""The program's compile log (``utils/compile_cache.CompileLog``) and the
admission spans (``utils/profiling.span``): what a trial's admission
traced, lowered, compiled or loaded, by program, on one clock with the
host spans of ``create_lm_state`` and ``setup_groups``. Counts and
containment only; no time is asserted beyond "no larger than the wall
time around the call".
"""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from multidisttorch_tpu.models.latent_moe import LatentMoELM
from multidisttorch_tpu.models.transformer import TransformerLM
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import (
    STEP_PROGRAM,
    create_lm_state,
    make_lm_train_step,
)
from multidisttorch_tpu.utils import compile_cache, profiling
from multidisttorch_tpu.utils.compile_cache import (
    STAGE_BACKEND,
    STAGE_LOWER,
    STAGE_RETRIEVAL,
    STAGE_SPAN,
    STAGE_TRACE,
    CompileLog,
    compile_log,
    enable_compile_cache,
)

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

MODELS = {
    "transformer": lambda: TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2, max_len=16
    ),
    "latent_moe": lambda: LatentMoELM(vocab_size=64, max_len=16),
}


@pytest.fixture
def log(tmp_path):
    """The process's log, with the persistent cache in an empty
    directory of the test's own: its first compile of anything misses."""
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()  # jax binds its directory once
    enable_compile_cache()
    yield compile_log()
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _admit(model):
    """One trial as ``examples/lm_hpo.py`` admits it: carve, state, the
    step's first call. Returns the stamps around the three."""
    key = jax.random.key(0)  # a program of its own, not create_lm_state's
    t0 = time.perf_counter()
    (group,) = setup_groups(1, devices=jax.devices()[:1])
    tx = optax.adam(1e-3)
    state = create_lm_state(group, model, tx, key, example_len=16)
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    step = make_lm_train_step(group, model, tx)
    assert step.__name__ == STEP_PROGRAM
    tokens = group.device_put(
        jnp.zeros((2, 16), jnp.int32), group.batch_sharding
    )
    _, metrics = step(state, tokens)
    metrics["loss"].block_until_ready()
    return t0, t1, time.perf_counter()


@pytest.mark.parametrize("name", sorted(MODELS))
def test_cold_then_warm_admission(log, name):
    """A miss then a hit for ``step_fn``: the backend's entry is a
    compile the first time and, after ``jax.clear_caches()``, the
    persistent cache's read, with the retrieval logged beside it."""
    jax.clear_caches()  # what earlier tests compiled is not in this directory
    before = log.snapshot()
    t0, t1, t2 = _admit(MODELS[name]())
    cold = log.by_program(t1, t2)
    # ``step_fn`` and ``jit(step_fn)`` are one program
    assert "jit(step_fn)" not in cold
    assert {s: v.n for s, v in cold[STEP_PROGRAM].items()} == {
        STAGE_TRACE: 1, STAGE_LOWER: 1, STAGE_BACKEND: 1,
    }
    mid = log.snapshot()
    assert mid["misses"] > before["misses"]

    jax.clear_caches()
    t3, t4, t5 = _admit(MODELS[name]())
    warm = log.by_program(t4, t5)[STEP_PROGRAM]
    assert {s: v.n for s, v in warm.items()} == {
        STAGE_TRACE: 1, STAGE_LOWER: 1, STAGE_RETRIEVAL: 1, STAGE_BACKEND: 1,
    }
    assert warm[STAGE_RETRIEVAL].secs <= warm[STAGE_BACKEND].secs
    after = log.snapshot()
    assert after["misses"] == mid["misses"]  # nothing compiled again
    assert after["hits"] > mid["hits"]

    split = profiling.admission_split(STEP_PROGRAM, t3, t5)
    assert split["step_programs"] == 1 and split["step_retrieval_s"] > 0
    assert split["init_programs"] > 1
    assert (
        split["step_trace_s"] + split["step_lower_s"] + split["step_load_s"]
        <= t5 - t4
    )
    assert (
        split["init_trace_s"] + split["init_lower_s"] + split["init_load_s"]
        <= split["init_s"]
        <= t4 - t3
    )
    line = profiling.admission_line(split, split, t5 - t3)
    assert line.startswith("admitted in ") and line.endswith("(hit)")
    assert f"({split['init_programs']} programs, " in line


def test_nested_traces_add_to_no_sum(log):
    """jax reports the inner ``jit``s of a trace each with its own
    seconds; the log keeps the outermost entry and the count."""
    raw = []

    def on_secs(event, secs, **kw):
        if event == TRACE_EVENT:
            raw.append(secs)

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * jnp.where(x > 0, x, 0.5)

    def outer_fn(x):
        return inner(x) + inner(x * 2).sum()

    x = jnp.arange(7.0)
    jax.monitoring.register_event_duration_secs_listener(on_secs)
    t0 = time.perf_counter()
    try:
        jax.jit(outer_fn)(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_secs)
    wall = time.perf_counter() - t0

    traces = [e for e in log.entries(t0) if e.stage == STAGE_TRACE]
    assert [e.program for e in traces] == ["outer_fn"]
    assert traces[0].nested == len(raw) - 1 > 0
    assert traces[0].secs <= wall
    assert sum(raw) > traces[0].secs  # the plain sum counts the inner twice
    # the aggregates hold the outermost seconds too
    assert "inner" not in log.by_program()
    assert log.by_program()["outer_fn"][STAGE_TRACE].secs == traces[0].secs


def test_init_state_span_holds_its_parts_and_its_programs(log):
    jax.clear_caches()
    t0, t1, _ = _admit(MODELS["transformer"]())
    spans = {
        e.program: e for e in log.entries(t0, t1) if e.stage == STAGE_SPAN
    }
    assert set(spans) == {
        profiling.SPAN_SETUP_GROUPS, profiling.SPAN_INIT_STATE,
        profiling.SPAN_INIT_PARAMS, profiling.SPAN_INIT_OPT,
        profiling.SPAN_PLACE_STATE,
    }
    whole = spans[profiling.SPAN_INIT_STATE]
    parts = [
        spans[n]
        for n in (
            profiling.SPAN_INIT_PARAMS, profiling.SPAN_INIT_OPT,
            profiling.SPAN_PLACE_STATE,
        )
    ]
    for earlier, later in zip(parts, parts[1:]):
        assert earlier.end <= later.start
    assert whole.start <= parts[0].start and parts[-1].end <= whole.end
    assert spans[profiling.SPAN_SETUP_GROUPS].end <= whole.start
    # every program create_lm_state sent to the backend lies inside
    backends = [e for e in log.entries(t0, t1) if e.stage == STAGE_BACKEND]
    assert len(backends) > 5
    assert all(whole.start <= e.start and e.end <= whole.end for e in backends)
    split = profiling.admission_split(STEP_PROGRAM, t0, t1)
    assert split["init_programs"] == len(backends)
    assert split["step_programs"] == 0 and split["step_trace_s"] == 0.0


def test_enabling_twice_installs_one_listener(log):
    from jax._src import monitoring

    listeners = len(monitoring.get_event_time_span_listeners())
    assert enable_compile_cache() == jax.config.jax_compilation_cache_dir
    assert compile_cache.install_compile_log() is log
    assert len(monitoring.get_event_time_span_listeners()) == listeners
    x = jnp.ones(3)
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3.5 - 1)(x).block_until_ready()
    backends = [e for e in log.entries(t0) if e.stage == STAGE_BACKEND]
    assert len(backends) == 1  # one entry a compile


def test_ten_thousand_steps_add_no_entry(log):
    step = jax.jit(lambda x: x * 1.0001 + 1e-3)
    x = step(jnp.ones(8))
    x.block_until_ready()
    t0 = time.perf_counter()
    before = log.snapshot()
    for _ in range(10_000):
        x = step(x)
    x.block_until_ready()
    assert log.entries(t0) == []
    assert log.snapshot() == before


def test_admit_spans_lie_on_the_profilers_timeline(log, tmp_path):
    """Under any profiler session the ``admit:`` spans are host events
    of the trace, on the timeline of the run's other events."""
    model = MODELS["transformer"]()
    trace_dir = str(tmp_path / "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("test:_around"):
            _admit(model)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(("admit:", "test:")):
                    events[event.name] = (
                        event.start_ns, event.start_ns + event.duration_ns
                    )
    assert set(events) >= {
        profiling.SPAN_SETUP_GROUPS, profiling.SPAN_INIT_STATE,
        profiling.SPAN_INIT_PARAMS, profiling.SPAN_INIT_OPT,
        profiling.SPAN_PLACE_STATE,
    }
    around = events["test:_around"]
    whole = events[profiling.SPAN_INIT_STATE]
    assert around[0] <= whole[0] and whole[1] <= around[1]
    for name in (profiling.SPAN_INIT_PARAMS, profiling.SPAN_PLACE_STATE):
        assert whole[0] <= events[name][0] and events[name][1] <= whole[1]


# -- the log by itself, fed by hand ------------------------------------


def _feed(log, event, start, end, name):
    """As jax's ``log_elapsed_time`` reports a stage: wall-clock stamps
    taken ``end - start`` seconds apart, the later one now."""
    now = time.time()
    log.on_time_span(event, now - (end - start), now, fun_name=name)


def test_the_deque_is_bounded_and_the_aggregates_count_on():
    log = CompileLog(maxlen=4)
    for i in range(10):
        _feed(log, BACKEND_EVENT, 0.0, 0.25, f"jit(program_{i % 2})")
        time.sleep(0.001)  # apart: no entry inside another's interval
    assert len(log.entries()) == 4
    totals = log.by_program()
    assert totals["program_0"][STAGE_BACKEND].n == 5
    assert totals["program_1"][STAGE_BACKEND].secs == pytest.approx(1.25)
    assert log.snapshot()["backend_s"] == pytest.approx(2.5)


def test_by_program_leaves_out_what_ended_after_until():
    log = CompileLog()
    _feed(log, LOWER_EVENT, 0.0, 1e-4, "jit(early)")
    time.sleep(0.002)
    until = time.perf_counter()
    time.sleep(0.002)
    _feed(log, LOWER_EVENT, 0.0, 1e-4, "jit(late)")
    assert set(log.by_program(None, until)) == {"early"}
    assert set(log.by_program(until, None)) == {"late"}
    assert set(log.by_program()) == {"early", "late"}
    assert [e.program for e in log.entries(until)] == ["late"]


@pytest.mark.parametrize(
    "case", ["same_stage", "other_stage", "other_thread", "span"]
)
def test_what_folds_into_an_outer_entry(case):
    """Only an entry of the outer one's stage and thread, inside its
    interval, is nested; a lowering inside a trace, another thread's
    trace and a host span stay entries of their own."""
    log = CompileLog()
    seen = []
    log.subscribe(seen.append)

    def inner():
        if case == "span":
            t = time.perf_counter()
            log.add_span("admit:inner", t - 1e-4, t)
        else:
            event = LOWER_EVENT if case == "other_stage" else TRACE_EVENT
            _feed(log, event, 0.0, 1e-4, "inner")

    t0 = time.time()
    time.sleep(0.002)
    if case == "other_thread":
        worker = threading.Thread(target=inner)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
    else:
        inner()
    time.sleep(0.002)
    log.on_time_span(TRACE_EVENT, t0, time.time(), fun_name="outer")

    kept = [(e.stage, e.program, e.nested) for e in log.entries()]
    if case == "same_stage":
        assert kept == [(STAGE_TRACE, "outer", 1)]
        assert set(log.by_program()) == {"outer"}
        assert log.snapshot()["trace_s"] == log.entries()[0].secs
    else:
        assert len(kept) == 2 and kept[1] == (STAGE_TRACE, "outer", 0)
    assert len(seen) == 2  # a sink sees every entry as it is logged


def test_a_cache_read_is_named_by_its_backend_entry():
    log = CompileLog()
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration(RETRIEVAL_EVENT, 0.125)
    log.on_duration(TRACE_EVENT, 9.0)  # the log reads the spans, not these
    time.sleep(0.001)
    now = time.time()
    log.on_time_span(BACKEND_EVENT, now - 0.5, now, fun_name="jit(step_fn)")
    time.sleep(0.001)
    _feed(log, BACKEND_EVENT, 0.0, 1e-4, "jit(compiled)")  # a miss: no read
    log.on_event("/jax/compilation_cache/cache_misses")
    by = log.by_program()
    assert by["step_fn"][STAGE_RETRIEVAL] == (1, 0.125)
    assert by["step_fn"][STAGE_BACKEND].secs == pytest.approx(0.5)
    assert STAGE_RETRIEVAL not in by["compiled"]
    snap = log.snapshot()
    assert (snap["hits"], snap["misses"]) == (1, 1)
    assert snap["retrieval_s"] == 0.125 and snap["trace_s"] == 0


def test_a_span_without_a_log_is_an_annotation_alone(monkeypatch):
    monkeypatch.setattr(compile_cache, "_log", None)
    assert compile_log() is None
    with profiling.span("admit:nothing"):
        pass
    assert profiling.admission_split(STEP_PROGRAM, None, None) is None
