"""CPU rehearsal of the five admission readers (``step_trace_s``,
``step_lower_s``, ``step_load_s``, ``state_init_programs``,
``state_init_compile_s``) over tiny runs of the ``lm-dense`` and
``moe-mla-t4096`` entries: they read the program's compile log between
the entry's call and the stamp that opens the window, fit inside the
benchmark's own spans, and say nothing where no log is installed. No
number from here is a device number."""

import time

import jax
import pytest

from benchmark import cells
from benchmark.compile_book import CompileBook
from benchmark.tests import test_rehearsal, test_rehearsal_moe
from multidisttorch_tpu.utils import compile_cache

READERS = ("step_trace_s", "step_lower_s", "step_load_s", "state_init_programs",
           "state_init_compile_s")
TINY = {
    "lm-dense": (test_rehearsal.TINY_CONFIG, test_rehearsal.tiny_traffic(1)),
    "moe-mla-t4096": (test_rehearsal_moe.TINY_CONFIG, test_rehearsal_moe.TINY_TRAFFIC),
}


@pytest.fixture(scope="module", params=sorted(TINY))
def run(request):
    """One tiny run of the cell's entry as ``run.py`` starts it: the
    program's cache rule (and with it the log) on, the clock read just
    before the entry is called."""
    compile_cache.enable_compile_cache()
    jax.clear_caches()  # the second cell meets the first one's programs cold too
    real = cells.load_cell(request.param)
    config, traffic = TINY[request.param]
    cell = cells.Cell(name=real.name, chips=1, config=config, traffic=traffic,
                      end_to_end=real.end_to_end, per_layer=real.per_layer)
    t_entry = time.perf_counter()
    record = cell.entry().run(cell, jax.devices()[:1], 2147483659, 1.0, None, CompileBook())
    record["t_process_start"] = t_entry - 1.0
    record["t_entry"] = t_entry
    record["device"] = {"kind": "TPU v5 lite", "count": 1}  # for the peak table only
    new = [m for m in cell.per_layer if m["name"] in READERS]
    return record, new


def test_the_cell_lists_the_five(run):
    _, new = run
    assert [m["name"] for m in new] == list(READERS)
    assert all("workloads" not in m and m["moves"] == "setup_s" for m in new)


def test_the_split_fits_inside_the_spans_it_divides(run, capsys):
    record, new = run
    assert record["correct"], (record["checks"], record["reference"]["notes"])
    got = {k: v["value"] for k, v in cells.read_metrics(new, "layer_metrics", record).items()}
    assert set(got) == set(READERS)
    spans = record["spans"]
    assert 0 < got["step_trace_s"] and 0 < got["step_lower_s"] and 0 < got["step_load_s"]
    assert got["step_trace_s"] + got["step_lower_s"] + got["step_load_s"] <= spans["step_ready_s"]
    assert 0 < got["state_init_compile_s"] <= spans["state_init_s"]
    assert got["state_init_programs"] > 5
    # the progress line: the cache's read beside the load, the log beside compile_s
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("[benchmark] admission step_trace_s=") and "step_retrieval_s=" in line
    assert "(compile_s " in line


def test_the_reference_checks_programs_are_not_counted(run):
    """The check compiles a step of its own after the window; the log
    holds it, the readers' interval ends before it."""
    record, new = run
    from multidisttorch_tpu.train.lm import STEP_PROGRAM

    log = compile_cache.compile_log()
    opened = record["stamps"][0]
    before = log.by_program(record["t_entry"], opened)[STEP_PROGRAM]["backend"]
    assert before.n == 1  # one trial, one step program
    got = cells.read_metrics(new, "layer_metrics", record)
    assert got["step_load_s"]["value"] == before.secs
    # the state made again for the check is a span after the window
    later = [e for e in log.entries(opened) if e.program == "admit:init_state"]
    assert len(later) == 1


def test_without_a_log_the_readers_say_nothing(run, monkeypatch):
    record, new = run
    monkeypatch.setattr(compile_cache, "_log", None)
    assert cells.read_metrics(new, "layer_metrics", record) == {}
