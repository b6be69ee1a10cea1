"""Observability tier: conservation invariants, exporters, telemetry.

The load-bearing guarantees:

  * blame conservation — the critical-path decomposition's components sum
    to the makespan within float tolerance, for every rate policy under
    every golden regime (static / dynamic / migration / deadline-shaped)
    plus the strict-shaped migration variant;
  * NIC conservation — the utilization step timeline's integral equals
    the bytes delivered through each machine's NIC exactly;
  * the Perfetto export round-trips through disk and structural
    validation with the span counts intact;
  * ``flow_log`` contract — ``None`` means "never recorded" and the
    trace builder refuses it with actionable guidance;
  * the jax backend's in-program aggregates match the numpy trace's
    post-hoc aggregates on identical inputs;
  * scenario blame decomposes the static-vs-replan wall-clock gap into
    component deltas that sum to the measured delta.
"""
import json
import math

import numpy as np
import pytest

from repro.core import build_gnn_workload, heterogeneous_cluster, ifs_placement, simulate
from repro.core.units import US_PER_SECOND
from repro.obs import REGISTRY, MetricsRegistry
from repro.obs.blame import COMPONENTS, blame, blame_delta, combine
from repro.obs.metrics import NULL, Counter, Gauge, Histogram
from repro.obs.perfetto import to_trace_events, validate_trace_events, write_trace
from repro.obs.trace import ScheduleTrace

from test_golden_schedules import POLICIES, _cases

# golden matrix + the strict-shaped migration variant (the golden suite
# pins deadline shaping as its "priority" regime; strict rides here)
CASES = []
for case in _cases():
    name, regime, wl, cluster, placement, r, tr, flows, shaping = case
    CASES.append((f"{name}-{regime}", wl, cluster, placement, r, tr, flows, shaping))
    if regime == "migration":
        CASES.append(
            (f"{name}-migration-strict", wl, cluster, placement, r, tr, flows,
             "strict")
        )

CASE_IDS = [c[0] for c in CASES]


def _trace_for(case, policy):
    _, wl, cluster, placement, r, tr, flows, shaping = case
    res = simulate(
        wl, cluster, placement, r, policy=policy, trace=tr,
        migrations=flows, shaping=shaping, record=True, backend="numpy",
    )
    return res, ScheduleTrace.from_result(
        res, wl, cluster, placement, r, trace=tr, migrations=flows,
        shaping=shaping,
    )


# ---------------------------------------------------------------------------
# conservation invariants
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", CASES, ids=CASE_IDS)
@pytest.mark.parametrize("policy", POLICIES)
def test_blame_conserves_makespan_and_nic_bytes(case, policy):
    res, trace = _trace_for(case, policy)
    rep = blame(trace)
    # components sum to the makespan (telescoping critical path)
    tol = 1e-9 * max(1.0, trace.makespan)
    assert abs(rep.residual) < tol, (
        f"blame residual {rep.residual} on {case[0]}/{policy}: "
        f"{rep.components}"
    )
    assert set(rep.components) == set(COMPONENTS)
    # critical-path spans actually chain: each starts no earlier than its
    # predecessor's end (up to engine EPS slack folded into 'dependency')
    for a, b in zip(rep.path, rep.path[1:]):
        assert b.start >= a.start - 1e-9
    # NIC conservation: integral of the rate timeline == delivered bytes
    for m in range(trace.M):
        for direction in ("in", "out"):
            integ = trace.utilization_integral(m, direction)
            truth = trace.delivered_gb(m, direction)
            assert math.isclose(integ, truth, rel_tol=1e-9, abs_tol=1e-9)


@pytest.mark.parametrize(
    "case", [c for c in CASES if c[7] is not None], ids=[c[0] for c in CASES if c[7] is not None]
)
def test_shaping_component_only_under_shaping(case):
    """Background-flow overhang lands in 'shaping' when a mode is active;
    the unshaped run books the same flows under 'contention'."""
    unshaped = case[:7] + (None,)
    rep_shaped = blame(_trace_for(case, "oes")[1])
    rep_plain = blame(_trace_for(unshaped, "oes")[1])
    assert rep_plain.components["shaping"] == 0.0
    assert abs(rep_shaped.residual) < 1e-9 * max(1.0, rep_shaped.makespan)
    assert abs(rep_plain.residual) < 1e-9 * max(1.0, rep_plain.makespan)


def test_combine_preserves_conservation():
    reps = [blame(_trace_for(c, "oes")[1]) for c in CASES[:3]]
    tot = combine(reps)
    assert math.isclose(tot.makespan, sum(r.makespan for r in reps))
    assert abs(tot.residual) < 1e-9 * max(1.0, tot.makespan)
    table = blame_delta(reps[0], reps[1], "a", "b")
    assert "makespan" in table and "contention" in table


# ---------------------------------------------------------------------------
# flow_log contract
# ---------------------------------------------------------------------------
def test_flow_log_none_when_unrecorded():
    name, wl, cluster, placement, r, tr, flows, shaping = CASES[0]
    res = simulate(wl, cluster, placement, r, record=False, backend="numpy")
    assert res.flow_log is None
    with pytest.raises(ValueError, match="backend='numpy'"):
        ScheduleTrace.from_result(res, wl, cluster, placement, r)
    # recorded schedules keep the list (possibly empty for all-local plans)
    rec = simulate(wl, cluster, placement, r, record=True, backend="numpy")
    assert isinstance(rec.flow_log, list) and rec.flow_log


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------
def test_perfetto_roundtrip(tmp_path):
    _, trace = _trace_for(CASES[0], "oes")
    path = tmp_path / "trace.json"
    obj = write_trace(trace, path)
    loaded = json.loads(path.read_text())
    counts = validate_trace_events(loaded)
    assert counts == validate_trace_events(obj)
    # every task/flow span became exactly one complete slice
    assert counts["X"] == len(trace.tasks) + len(trace.flows)
    # 3 metadata events per machine (process + 2 thread names)
    assert counts["M"] == 3 * trace.M
    assert counts["C"] > 0
    assert loaded["otherData"]["makespan_s"] == pytest.approx(trace.makespan)
    # slices never extend past the makespan
    for e in loaded["traceEvents"]:
        if e["ph"] == "X":
            assert e["ts"] + e["dur"] <= trace.makespan * US_PER_SECOND + 1e-3


def test_perfetto_validator_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace_events({})
    bad_phase = {"traceEvents": [{"ph": "B", "pid": 0, "name": "x"}]}
    with pytest.raises(ValueError, match="phase"):
        validate_trace_events(bad_phase)
    neg_dur = {
        "traceEvents": [
            {"ph": "X", "pid": 0, "tid": 1, "name": "x", "ts": 0.0, "dur": -1.0}
        ]
    }
    with pytest.raises(ValueError, match="dur"):
        validate_trace_events(neg_dur)
    bad_meta = {
        "traceEvents": [{"ph": "M", "pid": 0, "name": "nope", "args": {}}]
    }
    with pytest.raises(ValueError, match="metadata"):
        validate_trace_events(bad_meta)


# ---------------------------------------------------------------------------
# jax aggregates vs numpy post-hoc aggregates
# ---------------------------------------------------------------------------
def test_jax_aggregates_match_numpy_trace():
    pytest.importorskip("jax")
    from repro.core.engine_jax import simulate_batch_jax

    name, wl, cluster, placement, r, tr, flows, shaping = CASES[0]
    res_jax = simulate_batch_jax(
        wl, cluster, [placement], [r], utilization=True
    )[0]
    assert res_jax.flow_log is None
    agg = res_jax.aggregates
    assert agg is not None
    _, trace = _trace_for(CASES[0], "oes")
    ref = trace.aggregates()
    np.testing.assert_allclose(
        agg["nic_in_gb"], ref["nic_in_gb"], rtol=1e-6, atol=1e-9
    )
    np.testing.assert_allclose(
        agg["nic_out_gb"], ref["nic_out_gb"], rtol=1e-6, atol=1e-9
    )
    np.testing.assert_allclose(
        agg["busy_s"], ref["busy_s"], rtol=1e-6, atol=1e-6
    )
    for cls_id, gb in ref["class_gb"].items():
        assert agg["class_gb"][cls_id] == pytest.approx(gb, rel=1e-6)
    # aggregates are opt-in: the default jax run carries none
    res_plain = simulate_batch_jax(wl, cluster, [placement], [r])[0]
    assert res_plain.aggregates is None


FANIN = [c for c in CASES if c[0].startswith("fanin-")]


@pytest.mark.parametrize("case", FANIN, ids=[c[0] for c in FANIN])
def test_trace_from_jax_recorded_result_matches_numpy(case):
    """A schedule recorded on the jax engine lifts into the numpy
    schedule's trace: the same task and flow spans, times at the engines'
    parity tolerance, and the same NIC and class aggregates."""
    pytest.importorskip("jax")
    from repro.core.engine_jax import PARITY_ATOL, PARITY_RTOL

    _, wl, cluster, placement, r, tr, flows, shaping = case

    def lifted(backend):
        res = simulate(
            wl, cluster, placement, r, policy="oes", trace=tr,
            migrations=flows, shaping=shaping, record=True, backend=backend,
        )
        return ScheduleTrace.from_result(
            res, wl, cluster, placement, r, trace=tr, migrations=flows,
            shaping=shaping,
        )

    ref, got = lifted("numpy"), lifted("jax")
    close = dict(rtol=PARITY_RTOL, atol=PARITY_ATOL)
    assert got.makespan == pytest.approx(ref.makespan, rel=PARITY_RTOL)

    def spans(tr):
        return (sorted(tr.tasks, key=lambda s: (s.task, s.iter)),
                sorted(tr.flows, key=lambda f: (f.edge, f.iter)))

    (t_ref, f_ref), (t_got, f_got) = spans(ref), spans(got)
    assert f_ref
    assert [(s.task, s.iter, s.machine, s.name) for s in t_got] == [
        (s.task, s.iter, s.machine, s.name) for s in t_ref]
    assert [(f.edge, f.iter, f.src, f.dst, f.gb, f.cls, f.name) for f in f_got] == [
        (f.edge, f.iter, f.src, f.dst, f.gb, f.cls, f.name) for f in f_ref]
    np.testing.assert_allclose([[s.start, s.end] for s in t_got + f_got],
                               [[s.start, s.end] for s in t_ref + f_ref], **close)
    np.testing.assert_allclose([f.ideal_s for f in f_got],
                               [f.ideal_s for f in f_ref], **close)
    agg_ref, agg_got = ref.aggregates(), got.aggregates()
    for k in ("nic_in_gb", "nic_out_gb", "busy_s"):
        np.testing.assert_allclose(agg_got[k], agg_ref[k], **close)
    assert agg_got["class_gb"] == pytest.approx(agg_ref["class_gb"], rel=PARITY_RTOL)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_disabled_hands_out_shared_null():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("a")
    assert c is NULL and reg.histogram("b") is NULL and reg.gauge("c") is NULL
    c.inc(5.0)  # no-op, no state
    assert reg.snapshot() == {}


def test_registry_enabled_counts_and_snapshots():
    reg = MetricsRegistry(enabled=True)
    reg.counter("x").inc()
    reg.counter("x").inc(2.5)
    reg.gauge("g").set(7)
    h = reg.histogram("h")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["x"]["value"] == 3.5
    assert snap["g"]["value"] == 7.0
    assert snap["h"]["count"] == 3 and snap["h"]["min"] == 1.0
    assert snap["h"]["mean"] == pytest.approx(2.0)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    reg.reset()
    assert reg.snapshot() == {}


def test_registry_env_gate(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "1")
    assert MetricsRegistry().enabled
    monkeypatch.setenv("REPRO_OBS", "off")
    assert not MetricsRegistry().enabled
    monkeypatch.delenv("REPRO_OBS")
    assert not MetricsRegistry().enabled


def test_engine_counters_when_enabled():
    name, wl, cluster, placement, r, tr, flows, shaping = CASES[0]
    was = REGISTRY.enabled
    REGISTRY.enable()
    try:
        REGISTRY.reset()
        off = simulate(wl, cluster, placement, r, backend="numpy")
        snap = REGISTRY.snapshot()
        assert snap["engine.simulate.calls"]["value"] == 1.0
    finally:
        REGISTRY.enabled = was
        REGISTRY.reset()
    # metrics are observational: identical schedule either way
    on = simulate(wl, cluster, placement, r, backend="numpy")
    assert on.makespan == off.makespan


# ---------------------------------------------------------------------------
# planner + scenario telemetry
# ---------------------------------------------------------------------------
def _tiny_job():
    wl = build_gnn_workload(
        n_stores=2, n_workers=2, samplers_per_worker=1, n_ps=1, n_iters=6,
        store_to_sampler_gb=0.8, sampler_to_worker_gb=0.4, grad_gb=0.25,
        store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2, pmr=1.3,
    )
    cluster = heterogeneous_cluster(3, seed=1)
    return wl, cluster


def test_search_telemetry_fields():
    from repro.core.placement import etp_multichain
    from repro.obs.telemetry import search_telemetry

    wl, cluster = _tiny_job()
    res = etp_multichain(wl, cluster, n_chains=2, budget=30, seed=0,
                         sim_iters=3)
    t = search_telemetry(res)
    assert t["proposals"] >= t["accepted"] >= 0
    assert 0.0 <= t["acceptance_rate"] <= 1.0
    assert t["evaluations"] > 0 and t["objective_trajectory"]
    assert len(t["chains"]) == 2
    for ch in t["chains"]:
        assert {"seed", "evaluations", "proposals", "accepted"} <= set(ch)


def test_cache_telemetry_hit_rate():
    from repro.cache.policies import replay
    from repro.cache.trace import AccessTrace
    from repro.obs.telemetry import cache_telemetry

    rng = np.random.default_rng(0)
    accesses = [  # [samplers=2][iters=4]
        [rng.integers(0, 50, size=30) for _ in range(4)] for _ in range(2)
    ]
    tr = AccessTrace(accesses=accesses, n_nodes=50, bytes_per_node=1024)
    was = REGISTRY.enabled
    REGISTRY.enable()
    try:
        REGISTRY.reset()
        assert cache_telemetry() is None  # nothing replayed yet
        out = replay(tr, "lru", capacity_nodes=20, k=2)
        t = cache_telemetry()
        assert t is not None and 0.0 <= t["hit_rate"] <= 1.0
        # registry's pooled rate reproduces the replay's weighted mean
        acc = np.array([sum(len(a) for a in per) for per in tr.merged(2)])
        assert t["hit_rate"] == pytest.approx(
            float((out * acc).sum() / acc.sum())
        )
    finally:
        REGISTRY.enabled = was
        REGISTRY.reset()


def test_scenario_blame_delta_decomposes_gap():
    from repro.dynamics import ReplanConfig, drift_trace, run_scenario

    wl, cluster = _tiny_job()
    trace = drift_trace(cluster, horizon_s=60.0, n_segments=6, seed=0,
                        bw_scale_range=(0.3, 1.0))
    cfg = ReplanConfig(budget=40, sim_iters=3, drift_threshold=0.1)
    outs = {}
    for strat in ("static", "replan"):
        outs[strat] = run_scenario(
            wl, cluster, trace, strategy=strat, n_intervals=2,
            iters_per_interval=3, seed=0, replan_config=cfg,
            collect_traces=True,
        )
        assert len(outs[strat].traces) == 2
    reps = {k: v.blame() for k, v in outs.items()}
    for k, rep in reps.items():
        # combined components conserve the scenario's wall-clock total
        assert rep.makespan == pytest.approx(outs[k].total_s)
        assert abs(rep.residual) < 1e-9 * max(1.0, rep.makespan)
    # the static-vs-replan gap decomposes into component deltas exactly
    dsum = sum(
        reps["replan"].components[k] - reps["static"].components[k]
        for k in COMPONENTS
    )
    gap = outs["replan"].total_s - outs["static"].total_s
    assert dsum == pytest.approx(gap, abs=1e-6)


def test_scenario_blame_requires_traces():
    from repro.dynamics import drift_trace, run_scenario

    wl, cluster = _tiny_job()
    trace = drift_trace(cluster, horizon_s=60.0, n_segments=4, seed=0)
    out = run_scenario(
        wl, cluster, trace, strategy="static", n_intervals=1,
        iters_per_interval=3, seed=0,
    )
    assert out.traces == []
    with pytest.raises(ValueError, match="collect_traces"):
        out.blame()
