"""Host spans, engine counters and device named scopes (``repro.obs.spans``).

A tiny ``plan()`` on the jax engine and one ``Replanner.on_leave`` run
under ``jax.profiler`` on the CPU; the ``.xplane.pb`` it writes is read
back and every span is checked for presence, arguments and nesting.  The
registry's ``engine.jax.*`` counters are checked against the results they
count, ``runner_scopes()`` against a CPU-compiled runner, and a plan made
with the profiler and the registry off against one made with both on.
"""
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import build_gnn_workload, heterogeneous_cluster, ifs_placement
from repro.core import engine_jax
from repro.core.dgtp import plan
from repro.core.engine_jax import SCOPES, runner_scopes, simulate_batch_jax
from repro.dynamics import ReplanConfig, Replanner
from repro.obs import REGISTRY
from repro.obs.spans import NO_SPAN, span

ENGINE_CHILDREN = ["assemble", "dispatch", "fetch", "unpack"]


def tiny_job():
    return build_gnn_workload(
        n_stores=2, n_workers=1, samplers_per_worker=1, n_ps=1, n_iters=3,
        store_to_sampler_gb=0.5, sampler_to_worker_gb=0.3, grad_gb=0.2,
        store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2,
    )


def tiny_plan():
    wl = tiny_job()
    cluster = heterogeneous_cluster(4, seed=0)
    return plan(wl, cluster, realization=wl.realize(seed=0), budget=8,
                sim_iters=3, n_chains=2, backend="jax")


def read_spans(trace_dir: Path):
    """Every ``repro.*`` host event: (name, start, end, args), by start."""
    from jax.profiler import ProfileData

    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns), dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def children(spans, parent, prefix):
    return [e for e in spans if e[0].startswith(prefix) and inside(e, parent)]


@pytest.fixture(scope="module")
def fresh_runners():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_jax, "_RUNNERS", {})
        yield


@pytest.fixture(scope="module")
def profiled(tmp_path_factory, fresh_runners):
    """Spans of one plan and one re-plan after a machine leave, made with
    the registry on too."""
    out = tmp_path_factory.mktemp("xplane")
    wl = tiny_job()
    cluster = heterogeneous_cluster(4, seed=0)
    rp = Replanner(wl, cluster, ifs_placement(wl, cluster, seed=0),
                   config=ReplanConfig(budget=4, sim_iters=3, backend="jax"))
    was = REGISTRY.enabled
    REGISTRY.enable()
    jax.profiler.start_trace(str(out))
    try:
        got = tiny_plan()
        rec = rp.on_leave(int(rp.placement.y[0]))
    finally:
        jax.profiler.stop_trace()
        REGISTRY.enabled = was
        REGISTRY.reset()
    return read_spans(out), got, rec


def test_plan_spans_nest_as_documented(profiled):
    spans, got, _ = profiled
    (top,) = [e for e in spans if e[0] == "repro.plan"]
    assert top[3]["budget"] == 8 and top[3]["seq"] > 0
    (search,) = children(spans, top, "repro.plan.search")
    (sim,) = children(spans, top, "repro.plan.commit.simulate")
    (audit,) = children(spans, top, "repro.plan.commit.audit")
    assert search[2] <= sim[1] and sim[2] <= audit[1]
    engines = [e for e in children(spans, top, "repro.engine") if e[0] == "repro.engine"]
    # every engine call of the plan lies inside its search, but one: the
    # commit, a width-1 call inside its simulate span
    searched = [e for e in engines if inside(e, search)]
    (commit,) = [e for e in engines if inside(e, sim)]
    assert len(searched) >= 2 and len(searched) + 1 == len(engines)
    assert commit[3]["width"] == 1
    assert got.etp.evaluations > 0


def test_engine_spans_hold_four_phases_in_order(profiled, fresh_runners):
    spans = profiled[0]
    engines = [e for e in spans if e[0] == "repro.engine"]
    assert engines
    for eng in engines:
        kids = children(spans, eng, "repro.engine.")
        assert [k[0].rsplit(".", 1)[1] for k in kids] == ENGINE_CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        assert 1 <= eng[3]["width"] <= eng[3]["padded"]
    # the runner argument names a runner the program can map to scopes
    ids = {e[3]["runner"] for e in engines}
    assert ids <= set(runner_scopes())


def test_replan_spans_after_a_leave(profiled):
    spans, _, rec = profiled
    (top,) = [e for e in spans if e[0] == "repro.replan"]
    assert top[3]["seq"] > 0
    names = [e[0] for e in children(spans, top, "repro.replan.")]
    assert names == ["repro.replan.remap", "repro.replan.search", "repro.replan.price"]
    (search,) = children(spans, top, "repro.replan.search")
    engines = [e for e in children(spans, top, "repro.engine") if e[0] == "repro.engine"]
    assert engines and all(inside(e, search) for e in engines)
    assert rec.trigger == "leave"


def test_plan_and_replan_share_one_request_counter(profiled):
    spans = profiled[0]
    seqs = [e[3]["seq"] for e in spans if e[0] in ("repro.plan", "repro.replan")]
    assert len(seqs) == 2 and seqs[1] > seqs[0]


def test_engine_counters_count_calls_rows_and_builds(fresh_runners, monkeypatch):
    monkeypatch.setattr(engine_jax, "_RUNNERS", {})
    wl = tiny_job()
    cluster = heterogeneous_cluster(3, seed=0)
    ps = [ifs_placement(wl, cluster, seed=s) for s in range(3)]
    reals = [wl.realize(seed=s) for s in range(3)]
    was = REGISTRY.enabled
    REGISTRY.enable()
    REGISTRY.reset()
    try:
        calls = [(ps, reals), (ps, reals), (ps[:1], reals[:1])]
        results = [simulate_batch_jax(wl, cluster, p, r) for p, r in calls]
        snap = REGISTRY.snapshot()
    finally:
        REGISTRY.enabled = was
        REGISTRY.reset()
    value = {k: v["value"] for k, v in snap.items() if k.startswith("engine.jax.")}
    # the oes filling loop runs at most 4 * M rounds a lock-step iteration
    rounds = value.pop("engine.jax.fill_rounds")
    assert 0 < rounds <= 4 * cluster.M * value["engine.jax.lockstep_iters"]
    assert value == {
        "engine.jax.calls": 3,
        "engine.jax.rows": 7,
        "engine.jax.padded_rows": 2,  # width 3 pads to 4, twice
        "engine.jax.lockstep_iters": sum(max(r.n_events for r in res) for res in results),
        "engine.jax.runner_builds": 2,  # widths 4 and 1; the repeat is a hit
    }


def test_plan_unchanged_and_nothing_recorded_when_off(profiled):
    got = profiled[1]
    assert not REGISTRY.enabled
    REGISTRY.reset()
    off = tiny_plan()
    assert REGISTRY.snapshot() == {}
    assert np.array_equal(off.placement.y, got.placement.y)
    assert off.schedule.makespan == got.schedule.makespan
    assert off.schedule.task_events == got.schedule.task_events
    assert off.certificate.lower_bound == got.certificate.lower_bound
    assert off.etp.best_makespan == got.etp.best_makespan
    assert (off.delta, off.traffic) == (got.delta, got.traffic)


def test_runner_scopes_name_the_three_phases(fresh_runners, monkeypatch):
    monkeypatch.setattr(engine_jax, "_RUNNERS", {})
    wl = tiny_job()
    cluster = heterogeneous_cluster(3, seed=0)
    p = ifs_placement(wl, cluster, seed=0)
    simulate_batch_jax(wl, cluster, [p, p], [wl.realize(seed=0), wl.realize(seed=1)])
    (rid,) = [r.rid for r in engine_jax._RUNNERS.values()]
    scopes = runner_scopes()
    assert list(scopes) == [rid]
    names = scopes[rid]
    assert names and all(n.startswith("%") for n in names)
    for scope in SCOPES:
        assert any(f"/{scope}/" in op for op in names.values()), scope


def test_span_without_jax_is_a_null_span(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax")
    sp = span("repro.test", seq=1)
    assert sp is NO_SPAN
    with sp as entered:
        entered.set_metadata(runner="x")
