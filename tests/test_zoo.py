"""Scenario zoo (ISSUE 18): named, seeded, bit-reproducible workload
scenarios driving the PRODUCTION scheduler classes with the
control-plane profiler armed. Drills: registry round-trip, seeded
determinism (bit-identical reports modulo wall clock), per-scenario SLO
verdict wiring (honest-tenant judgment for deadline_gaming, dynamic-arm
judgment for fabric scenarios), ctl flight books in every envelope, and
the default-spec bit-identity guarantee (zoo knobs off = zero extra rng
draws)."""

from __future__ import annotations

import pytest

from multidisttorch_tpu.service.loadgen import (
    SCENARIOS,
    LoadSpec,
    run_loadgen,
    run_scenario,
    zoo_names,
)
from multidisttorch_tpu.telemetry import ctlprof

pytestmark = pytest.mark.ctlprof

# Small-N: the zoo's contracts (determinism, SLO wiring, books) hold at
# any N.
N = 1500


@pytest.fixture(autouse=True)
def _no_leaked_profiler():
    ctlprof.disable()
    yield
    ctlprof.disable()


# -- registry ----------------------------------------------------------


def test_registry_round_trip():
    names = zoo_names()
    assert names == sorted(names)
    assert set(names) == set(SCENARIOS)
    # The promoted fabric drills ride in the same registry:
    assert {"coordinated_burst", "split_storm"} <= set(names)
    assert {
        "diurnal_wave", "tenant_burst", "deadline_gaming",
        "pipeline_whale_shrimp", "dataset_thrash",
    } <= set(names)
    for name in names:
        ent = SCENARIOS[name]
        assert ent["kind"] in ("pool", "fabric")
        if ent["kind"] == "pool":
            assert ent["latency_threshold_s"] > 0
            assert 0 < ent["latency_objective"] <= 1
            assert 0 < ent["deadline_objective"] <= 1


def test_unknown_scenario_raises():
    with pytest.raises(ValueError, match="unknown scenario"):
        run_scenario("no_such_scenario")


# -- seeded determinism ------------------------------------------------


_WALL_KEYS = frozenset(
    {"wall_s", "submissions_per_wall_s", "ctl_passes_per_s"}
)


def _scrub(obj):
    """Drop wall-clock-derived fields; everything left must be
    bit-identical across reruns of the same (scenario, seed, N)."""
    if isinstance(obj, dict):
        return {
            k: _scrub(v) for k, v in obj.items() if k not in _WALL_KEYS
        }
    if isinstance(obj, list):
        return [_scrub(v) for v in obj]
    return obj


@pytest.mark.parametrize(
    "name", ["diurnal_wave", "deadline_gaming", "pipeline_whale_shrimp"]
)
def test_scenario_bit_reproducible(name):
    a = run_scenario(name, n_submissions=N, ctl=False)
    b = run_scenario(name, n_submissions=N, ctl=False)
    assert _scrub(a["report"]) == _scrub(b["report"])
    assert _scrub(a["slo"]) == _scrub(b["slo"])
    assert a["gates"] == b["gates"]


def test_seed_changes_workload():
    a = run_scenario("tenant_burst", n_submissions=N, ctl=False, seed=0)
    b = run_scenario("tenant_burst", n_submissions=N, ctl=False, seed=1)
    assert _scrub(a["report"]) != _scrub(b["report"])


def test_zoo_knobs_off_keep_default_spec_bit_identical():
    """Every zoo knob at its off-value must consume ZERO extra rng
    draws — the pre-zoo default workload replays bit-identically, so
    every historical loadgen baseline stays comparable."""
    base = run_loadgen(LoadSpec(n_submissions=800, seed=7))
    explicit = run_loadgen(LoadSpec(
        n_submissions=800, seed=7,
        wave_amp=0.0, burst_share=0.0, burst_tenant=None,
        gamer_tenant=None, whale_frac=0.0, thrash_buckets=0,
    ))
    assert _scrub(base) == _scrub(explicit)


# -- SLO verdict wiring ------------------------------------------------


def test_pool_scenario_slo_wiring_and_books():
    assert ctlprof.get_ctlprof() is None
    art = run_scenario("diurnal_wave", n_submissions=N)
    # run_scenario armed its OWN profiler and retired it:
    assert ctlprof.get_ctlprof() is None
    ent = SCENARIOS["diurnal_wave"]
    thr = ent["latency_threshold_s"]
    slos = art["slo"]["slos"]
    assert f"placement_p_{int(thr)}s" in slos
    assert "deadline_hit_rate" in slos
    # Exact offline evaluation — thresholds sit ON bucket bounds:
    assert all(s["exact"] for s in slos.values())
    assert art["gates"]["slo_exact"]
    assert set(art["gates"]) == {"zero_lost", "slo_met", "slo_exact"}
    # Fairness is informational, never a zoo gate (scenarios skew
    # offered demand on purpose):
    assert "fairness_max_abs_ratio_error" in art["headline"]
    # Every envelope carries per-phase ctl flight books:
    ctl = art["ctl"]
    assert ctl["enabled"] is True
    assert ctl["passes"]["count"] > 0
    for ph in ("bin_pack_scan", "edf_insert", "fair_share_pick"):
        blk = ctl["phases"][ph]
        assert blk["calls"] > 0
        lo, hi = blk["bucket_err"]["p99_s"]
        assert lo <= blk["p99_s"] <= hi
    assert ctl["work_touched"]["examined"] > 0
    assert art["ctl_trace"]["traceEvents"]


def test_deadline_gaming_judges_honest_tenants_only():
    art = run_scenario("deadline_gaming", n_submissions=N, ctl=False)
    dl = art["report"]["deadline"]
    # The report banks the honest/gamer split; the gamer's
    # self-inflicted tight-slack misses must not sink the verdict.
    assert dl["honest"]["completed_tagged"] > 0
    assert dl["gamer"]["completed_tagged"] > 0
    honest_rate = dl["honest"]["hits"] / dl["honest"]["completed_tagged"]
    gamer_rate = dl["gamer"]["hits"] / dl["gamer"]["completed_tagged"]
    assert honest_rate > gamer_rate  # EDF contains the gamer
    ev = art["slo"]["slos"]["deadline_hit_rate"]
    assert ev["total"] == dl["honest"]["completed_tagged"]
    assert ev["total"] - ev["bad"] == dl["honest"]["hits"]


def test_fabric_scenario_judged_on_dynamic_arm():
    art = run_scenario("split_storm", n_submissions=800)
    assert art["kind"] == "fabric"
    # The static arm is the designed-to-degrade control; the verdict
    # reads the dynamic arm and the drill's relative gates.
    assert art["slo"]["met"] == art["slo"]["dynamic"]["met"]
    assert "static" in art["slo"]
    assert "p99_within_10pct_of_static" in art["gates"]
    assert art["gates"]["zero_lost"]
    # Fabric-only phases landed in the books:
    assert art["ctl"]["enabled"]
    assert art["ctl"]["passes"]["count"] > 0


def test_whale_scenario_places_vector_shapes():
    from multidisttorch_tpu.service.loadgen import _Sim

    ent = SCENARIOS["pipeline_whale_shrimp"]
    kw = dict(ent["overrides"])
    kw.update(n_submissions=N, seed=0)
    sim = _Sim(LoadSpec(**kw))
    report = sim.run()
    whales = [st for st in sim.trials.values() if st.entry.sizes]
    assert whales, "whale_frac > 0 produced no vector submissions"
    # All-or-nothing vector placements drained to completion — the
    # multi-block alloc + block-by-block free path carried real load.
    assert all(st.done_at is not None for st in whales)
    assert report["zero_lost"]
