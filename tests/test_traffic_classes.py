"""Traffic classes + rate-policy robustness (ISSUE 5).

Tentpole certificates:
  * a ShapedPolicy with no class contrast (or no migrations at all) is a
    bit-identical pass-through to its base policy, for all five policies;
  * strict de-prioritisation: with UNGATED migration flows the training
    tasks' trajectory is the clean (migration-free) trajectory — migration
    only ever gets leftover capacity — and never ends later than under
    unshaped competition;
  * deadline mode with infinite deadlines IS strict (bit-identical), and a
    tight deadline escalates a gated restore early enough to beat strict's
    starvation on the gated task's start;
  * scalar/batch bit-parity for every (policy x shaping mode) pair with
    heterogeneous per-instance migration flow sets on dynamic traces;
  * the slotted Alg.-1 oracle agrees with the shaped event engine in the
    slot -> 0 limit (both shaping modes);
  * per-job QoS classes on merged workloads: the prioritised job's flows
    never see the background job's contention.

Satellite regressions (zero-bandwidth + integer-bandwidth hazards):
  * MRTFRate.order no longer divides by a dead NIC's 0 bandwidth;
  * OMCoflowRate.rates no longer NaNs when a coflow's flows all hit dead
    NICs (the NaN used to poison ``remaining`` and deadlock the engine);
  * _WaterfillRate coerces integer bandwidth arrays to float64 (in-place
    ``rem -= give`` silently truncated before), scalar AND batched.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import (
    CLASS_MIGRATION,
    CLASS_TRAINING,
    FIFORate,
    MigrationFlow,
    MRTFRate,
    OMCoflowRate,
    ShapedPolicy,
    build_gnn_workload,
    heterogeneous_cluster,
    ifs_placement,
    resolve_policy,
    simulate,
    simulate_batch,
    simulate_slotted,
)
from repro.core.cluster import ClusterSpec
from repro.core.multijob import (
    merge_workloads,
    merged_edge_classes,
    per_job_makespans,
    realize_merged,
)
from repro.dynamics import (
    DynamicsEvent,
    ReplanConfig,
    Replanner,
    drift_trace,
    run_scenario,
    trace_from_events,
)

ALL_POLICIES = ("oes", "oes_strict", "fifo", "mrtf", "omcoflow")
MODES = ("strict", "deadline")


def small_job(seed=0, n_iters=4):
    return build_gnn_workload(
        n_stores=2, n_workers=2, samplers_per_worker=2, n_ps=1,
        n_iters=n_iters, store_to_sampler_gb=1.0, sampler_to_worker_gb=0.5,
        grad_gb=0.2, store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2, pmr=1.3,
    )


def _setup(seed=0):
    wl = small_job(seed=seed)
    cluster = heterogeneous_cluster(3, seed=seed)
    p = ifs_placement(wl, cluster, seed=0)
    r = wl.realize(seed=seed)
    return wl, cluster, p, r


def _gated_flows(wl, p, M, **kw):
    return [
        MigrationFlow(src=int((p.y[0] + 1) % M), dst=int(p.y[0]), gb=2.0,
                      task=0, **kw),
        MigrationFlow(src=int((p.y[wl.J - 1] + 2) % M),
                      dst=int(p.y[wl.J - 1]), gb=0.7, task=wl.J - 1, **kw),
        MigrationFlow(src=0, dst=1, gb=1.0),
    ]


# ---------------------------------------------------------------------------
# shaping wrapper semantics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ALL_POLICIES)
@pytest.mark.parametrize("mode", MODES)
def test_shaped_without_migrations_is_bit_identical(policy, mode):
    """One traffic class present -> the wrapper is a pass-through."""
    wl, cluster, p, r = _setup(seed=1)
    ref = simulate(wl, cluster, p, r, policy=policy, record=True)
    got = simulate(wl, cluster, p, r, policy=policy, record=True, shaping=mode)
    assert ref.makespan == got.makespan
    assert ref.n_events == got.n_events
    assert ref.task_events == got.task_events
    assert ref.flow_log == got.flow_log


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_strict_shaping_training_rides_clean_trajectory(policy):
    """With UNGATED state flows, strict shaping computes training rates
    from the training flow set alone — the training schedule IS the clean
    schedule, and never ends later than under unshaped competition.

    Exactness caveat: mrtf/omcoflow rates read ``remaining``, so the extra
    migration events refine the integration grid and legitimately perturb
    their rates mid-interval — for those two the clean-trajectory claim is
    approximate (the perturbation is the grid, not migration contention);
    for the topology-only policies it is tight."""
    wl, cluster, p, r = _setup(seed=0)
    migs = [dataclasses.replace(f, task=-1)
            for f in _gated_flows(wl, p, cluster.M)]
    clean = simulate(wl, cluster, p, r, policy=policy, record=True)
    unshaped = simulate(wl, cluster, p, r, policy=policy, record=True,
                        migrations=migs)
    shaped = simulate(wl, cluster, p, r, policy=policy, record=True,
                      migrations=migs, shaping="strict")
    t_clean = max(ev.end for ev in clean.task_events)
    t_un = max(ev.end for ev in unshaped.task_events)
    t_sh = max(ev.end for ev in shaped.task_events)
    rel = 1e-9 if policy in ("oes", "oes_strict", "fifo") else 1e-3
    assert t_sh == pytest.approx(t_clean, rel=rel)
    assert t_sh <= t_un * (1 + rel)
    # per-event: every training task start matches the clean run
    starts_c = clean.task_start_matrix(wl.J, r.n_iters)
    starts_s = shaped.task_start_matrix(wl.J, r.n_iters)
    np.testing.assert_allclose(starts_s, starts_c, rtol=rel, atol=1e-12)
    # the migration bytes still land (work conservation), at last as late
    # as under equal-priority competition on this contended testbed
    mig_end_sh = max(t for e, _, _, t in shaped.flow_log if e >= wl.E)
    mig_end_un = max(t for e, _, _, t in unshaped.flow_log if e >= wl.E)
    assert shaped.makespan >= mig_end_sh - 1e-12
    assert mig_end_sh >= mig_end_un - 1e-9


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_deadline_with_infinite_deadlines_is_strict(policy):
    wl, cluster, p, r = _setup(seed=2)
    migs = _gated_flows(wl, p, cluster.M)  # default deadline: inf
    st = simulate(wl, cluster, p, r, policy=policy, record=True,
                  migrations=migs, shaping="strict")
    dl = simulate(wl, cluster, p, r, policy=policy, record=True,
                  migrations=migs, shaping="deadline")
    assert st.makespan == dl.makespan
    assert st.n_events == dl.n_events
    assert st.task_events == dl.task_events
    assert st.flow_log == dl.flow_log


def test_deadline_escalation_relieves_gated_starvation():
    """Strict shaping starves a gated restore behind sustained training
    traffic, delaying the gated task; a deadline at the task's clean-slack
    point escalates the restore and recovers (most of) that delay."""
    wl, cluster, p, r = _setup(seed=0)
    migs = _gated_flows(wl, p, cluster.M)
    clean = simulate(wl, cluster, p, r, policy="fifo", record=True)
    slack = {ev.task: ev.start for ev in clean.task_events if ev.iter == 1}
    migs_dl = [
        dataclasses.replace(f, deadline=slack[f.task]) if f.task >= 0 else f
        for f in migs
    ]
    st = simulate(wl, cluster, p, r, policy="fifo", record=True,
                  migrations=migs, shaping="strict")
    dl = simulate(wl, cluster, p, r, policy="fifo", record=True,
                  migrations=migs_dl, shaping="deadline")
    st_start = st.task_start_matrix(wl.J, r.n_iters)[0, 0]
    dl_start = dl.task_start_matrix(wl.J, r.n_iters)[0, 0]
    assert dl_start < st_start  # the gated store starts earlier
    assert dl.makespan < st.makespan  # and the whole schedule recovers


def test_deadline_escalation_wakes_between_events():
    """Regression: escalation is its own event source.  One 32s training
    flow saturates the only NIC pair with NO events in between; a starved
    background flow with deadline d must escalate at d - gb/bw (not at the
    training flow's completion) and land EXACTLY at its deadline — the
    EDF certificate.  Pre-fix the engine only re-evaluated urgency at
    pre-existing events, so the flow escalated ~30s late."""
    from repro.core.cluster import Placement

    wl = build_gnn_workload(
        n_stores=1, n_workers=1, samplers_per_worker=1, n_ps=1, n_iters=1,
        store_to_sampler_gb=40.0, sampler_to_worker_gb=0.1, grad_gb=0.05,
        store_exec_s=0.1, sampler_exec_s=0.1, worker_exec_s=0.1,
        ps_exec_s=0.1, pmr=1.0,
    )
    cluster = heterogeneous_cluster(2, seed=3)
    p = Placement(np.array([0, 1, 1, 1], dtype=np.int64))
    r = wl.realize(seed=0)
    for dl in (2.0, 4.0):
        migs = [MigrationFlow(src=0, dst=1, gb=2.0, deadline=dl)]
        st = simulate(wl, cluster, p, r, migrations=migs, shaping="strict",
                      record=True)
        dd = simulate(wl, cluster, p, r, migrations=migs, shaping="deadline",
                      record=True)
        st_end = [f for f in st.flow_log if f[0] >= wl.E][0][3]
        dd_end = [f for f in dd.flow_log if f[0] >= wl.E][0][3]
        assert st_end > 30.0  # strict: starved until the long flow drains
        assert dd_end == pytest.approx(dl, abs=1e-6)  # EDF lands AT d
        # batch path mirrors the wake-up bit-for-bit
        bb = simulate_batch(wl, cluster, [p], [r], migrations=[migs],
                            shaping="deadline", record=True)[0]
        assert bb.makespan == dd.makespan
        assert bb.flow_log == dd.flow_log
        assert bb.n_events == dd.n_events


def test_escalation_outranks_negative_qos_classes():
    """Regression: the promoted class must sit strictly above EVERY class
    present, including user QoS classes below CLASS_TRAINING — a fixed
    promotion to -1 would only tie with (or lose to) a class <= -1 job."""
    from repro.core.engine import _effective_classes

    cls = np.array([-2, 0, 1], dtype=np.int64)  # qos / training / migration
    dl = np.array([np.inf, np.inf, 1.0])
    rem = np.array([5.0, 5.0, 5.0])
    src = np.zeros(3, dtype=np.int64)
    dst = np.ones(3, dtype=np.int64)
    bw = np.array([10.0, 10.0])
    eff = _effective_classes("deadline", cls, dl, rem, src, dst, bw, bw, 0.9)
    assert eff[2] < eff[0] < eff[1]  # escalated above even the -2 job


def test_escalation_fires_at_its_own_wake_time():
    """Regression: the escalation wake is scheduled at
    ``deadline - remaining / lim`` only while that lies beyond ``now +
    EPS``, so the urgency test must take the same EPS.  With an exact
    ``deadline - now <= need`` a flow woken at its own computed time missed
    escalation on a rounding of ``deadline - (deadline - need)`` (about 4
    draws in 10 below) and then had no wake left: it escalated only at the
    next unrelated event and landed past its deadline."""
    from repro.core.engine import _effective_classes

    rng = np.random.default_rng(0)
    one = np.zeros(1, dtype=np.int64)
    cls = np.array([CLASS_MIGRATION], dtype=np.int64)
    for _ in range(1000):
        dl = rng.uniform(0.1, 10.0, 1)
        rem = rng.uniform(0.01, 5.0, 1)
        bw = rng.uniform(0.5, 12.5, 1)
        wake = float(dl[0] - rem[0] / bw[0])
        eff = _effective_classes("deadline", cls, dl, rem, one, one, bw, bw, wake)
        assert eff[0] < CLASS_TRAINING


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_feasible_deadline_flows_land_at_their_deadline(policy):
    """EDF certificate on the golden "priority" regime: the gated tail
    move (deadline 3.0, feasible) lands AT its deadline on every golden job
    under every base policy.  Before the wake/urgency EPS fix it landed up
    to 0.8 s late wherever rounding skipped the escalation."""
    from test_golden_schedules import _cases

    for name, regime, wl, cluster, p, r, trace, flows, shaping in _cases():
        if regime != "priority":
            continue
        res = simulate(wl, cluster, p, r, policy=policy, record=True,
                       trace=trace, migrations=flows, shaping=shaping)
        (tail_end,) = [t for e, _, _, t in res.flow_log if e == wl.E + 1]
        assert flows[1].deadline == 3.0
        assert tail_end == pytest.approx(3.0, abs=1e-6), name


@pytest.mark.parametrize("policy", ALL_POLICIES)
@pytest.mark.parametrize("mode", MODES)
def test_batch_matches_scalar_shaped(policy, mode):
    """Bit-identical lock-step parity for every (policy x shaping mode)
    pair: heterogeneous per-instance migration sets (including none, and
    mixed finite/infinite deadlines) on a dynamic drift trace."""
    for seed in range(2):
        wl = small_job(seed=seed)
        cluster = heterogeneous_cluster(3, seed=seed)
        placements = [ifs_placement(wl, cluster, seed=s) for s in range(3)]
        reals = [wl.realize(seed=s) for s in range(3)]
        tr = drift_trace(cluster, horizon_s=8.0, n_segments=5, seed=seed)
        mlists = [
            _gated_flows(wl, placements[0], cluster.M, deadline=1.5),
            None,
            [MigrationFlow(src=2, dst=0, gb=0.5, task=wl.J - 1)],
        ]
        batch = simulate_batch(
            wl, cluster, placements, reals, policy=policy, record=True,
            trace=tr, migrations=mlists, shaping=mode,
        )
        for b, (p, r, m) in enumerate(zip(placements, reals, mlists)):
            ref = simulate(
                wl, cluster, p, r, policy=policy, record=True, trace=tr,
                migrations=m, shaping=mode,
            )
            assert ref.makespan == batch[b].makespan, (policy, mode, seed, b)
            assert ref.n_events == batch[b].n_events, (policy, mode, seed, b)
            assert ref.task_events == batch[b].task_events, (policy, mode, seed, b)
            assert ref.flow_log == batch[b].flow_log, (policy, mode, seed, b)


@pytest.mark.parametrize("mode", MODES)
def test_slotted_oracle_agrees_with_shaped_flows(mode):
    """Slot->0 agreement between the shaped Alg.-1 oracle and the event
    engine under ``oes_strict+<mode>``, static and dynamic cluster."""
    wl, cluster, p, _ = _setup(seed=0)
    r = wl.realize(seed=2)
    migs = _gated_flows(wl, p, cluster.M, deadline=1.0)
    tr = trace_from_events(
        cluster, [DynamicsEvent(t0=2.0, t1=6.0, machine=0, bw_scale=0.5)]
    )
    for trace in (None, tr):
        ev = simulate(
            wl, cluster, p, r, policy="oes_strict", trace=trace,
            migrations=migs, shaping=mode,
        ).makespan
        last_rel = np.inf
        for slot, tol in ((0.25, 0.35), (0.05, 0.1), (0.01, 0.02)):
            sl = simulate_slotted(
                wl, cluster, p, r, slot=slot, trace=trace, migrations=migs,
                shaping=mode,
            ).makespan * slot
            rel = abs(sl - ev) / ev
            assert rel <= tol, (mode, trace is not None, slot, sl, ev)
            assert rel <= last_rel + 1e-9
            last_rel = rel


def test_shaping_api_validation():
    with pytest.raises(ValueError, match="unknown shaping mode"):
        ShapedPolicy("oes", "aggressive")
    with pytest.raises(ValueError, match="cannot wrap"):
        ShapedPolicy(ShapedPolicy("oes"), "strict")
    assert resolve_policy("mrtf+deadline").name == "mrtf+deadline"
    with pytest.raises(ValueError, match="already shaped"):
        resolve_policy("oes+strict", shaping="deadline")
    wl, cluster, p, r = _setup()
    with pytest.raises(ValueError, match="NaN deadline"):
        simulate(wl, cluster, p, r,
                 migrations=[MigrationFlow(0, 1, 1.0, deadline=float("nan"))])
    with pytest.raises(ValueError, match="edge_classes"):
        simulate(wl, cluster, p, r, shaping="strict",
                 edge_classes=np.zeros(wl.E + 1, dtype=np.int64))


# ---------------------------------------------------------------------------
# per-job QoS classes on merged workloads
# ---------------------------------------------------------------------------
def test_merged_qos_classes_isolate_the_prioritised_job():
    jobs = [small_job(seed=0, n_iters=3), small_job(seed=1, n_iters=3)]
    mj = merge_workloads(jobs)
    cluster = heterogeneous_cluster(4, seed=3)
    p = ifs_placement(mj.workload, cluster, seed=0)
    r = realize_merged(mj, jobs, seed=0)
    ec = merged_edge_classes(mj, [CLASS_TRAINING, CLASS_MIGRATION])
    # mapping: job 0's edges class 0, job 1's class 1, covering every edge
    assert ec.shape == (mj.workload.E,)
    assert (ec[:jobs[0].E] == 0).all() and (ec[jobs[0].E:] == 1).all()
    un = simulate(mj.workload, cluster, p, r, policy="oes", record=True)
    sh = simulate(mj.workload, cluster, p, r, policy="oes", record=True,
                  shaping="strict", edge_classes=ec)
    ends_un = per_job_makespans(mj, un)
    ends_sh = per_job_makespans(mj, sh)
    # the prioritised job never sees the background job's contention...
    assert ends_sh[0] <= ends_un[0] * (1 + 1e-9)
    # ...and the background job still completes (work conservation)
    assert np.isfinite(ends_sh[1]) and ends_sh[1] > 0
    with pytest.raises(ValueError, match="job_classes"):
        merged_edge_classes(mj, [0])


# ---------------------------------------------------------------------------
# replanner + scenario threading
# ---------------------------------------------------------------------------
def replan_job(n_iters=30):
    return build_gnn_workload(
        n_stores=3, n_workers=3, samplers_per_worker=2, n_ps=1,
        n_iters=n_iters, store_to_sampler_gb=1.0, sampler_to_worker_gb=0.5,
        grad_gb=0.1, store_exec_s=0.1, sampler_exec_s=0.2,
        worker_exec_s=0.4, ps_exec_s=0.1, pmr=1.2,
    )


@pytest.mark.parametrize("mode", MODES)
def test_replanner_scores_and_commits_under_shaping(mode):
    """on_leave with shaping: the committed record is coherent, and under
    deadline mode the gated restore flows carry FINITE deadlines filled
    from the clean-variant task starts."""
    wl = replan_job()
    cluster = heterogeneous_cluster(4, seed=3, gpu_range=(2, 4))
    p0 = ifs_placement(wl, cluster, seed=0)
    cfg = ReplanConfig(budget=30, sim_iters=6, shaping=mode)
    rp = Replanner(wl, cluster, p0.copy(), config=cfg)
    dead = int(p0.y[0])
    orphans = set(np.nonzero(p0.y == dead)[0].tolist())
    rec = rp.on_leave(dead)
    assert rec.trigger == "leave" and rec.replanned
    assert {f.task for f in rec.flows} >= orphans
    assert np.isfinite(rec.objective) and np.isfinite(rec.makespan)
    assert rec.objective == pytest.approx(
        rec.makespan + max(0.0, rec.overlap_s)
    )
    if mode == "deadline":
        gated = [f for f in rec.flows if f.task >= 0]
        assert gated and all(np.isfinite(f.deadline) for f in gated)
        assert all(f.deadline >= 0.0 for f in gated)
    else:
        assert all(np.isinf(f.deadline) for f in rec.flows)


def test_scenario_threads_shaping_into_interval_sims():
    wl = replan_job()
    cluster = heterogeneous_cluster(4, seed=3, gpu_range=(2, 4))
    tr = drift_trace(cluster, horizon_s=60.0, n_segments=8, seed=1)
    kw = dict(n_intervals=3, iters_per_interval=8, seed=0)
    base = run_scenario(
        wl, cluster, tr, strategy="replan",
        replan_config=ReplanConfig(budget=40, sim_iters=8), **kw,
    )
    shaped = run_scenario(
        wl, cluster, tr, strategy="replan",
        replan_config=ReplanConfig(budget=40, sim_iters=8, shaping="strict"),
        **kw,
    )
    assert base.shaping is None and shaped.shaping == "strict"
    assert shaped.n_replans >= 1
    assert np.isfinite(shaped.total_s) and shaped.total_s > 0
    # static strategy never rides flows, so its shaping slot stays None
    static = run_scenario(
        wl, cluster, tr, strategy="static",
        replan_config=ReplanConfig(budget=40, sim_iters=8, shaping="strict"),
        **kw,
    )
    assert static.shaping is None


# ---------------------------------------------------------------------------
# zero-bandwidth robustness (satellites 1 + 2)
# ---------------------------------------------------------------------------
def test_mrtf_order_survives_zero_bandwidth():
    """Regression: a dead NIC's 0 bandwidth made t_rem inf/NaN.  Dead-NIC
    flows must sort last and no float warnings may fire."""
    src = np.array([0, 1, 2])
    dst = np.array([1, 2, 0])
    bw = np.array([0.0, 5.0, 5.0])  # NIC 0 dead
    with np.errstate(divide="raise", invalid="raise"):
        order = MRTFRate().order(
            src, dst, np.array([1.0, 1.0, 1.0]), np.zeros(3), bw, bw
        )
    # flow 2 (into dead NIC 0) and flow 0 (out of dead NIC 0) sort last
    assert order[0] == 1
    assert set(order[1:]) == {0, 2}


def test_omcoflow_rates_survive_dead_coflow():
    """Regression: a coflow whose flows ALL hit dead NICs got gsum == 0 ->
    NaN rates that poisoned the engine's remaining arithmetic."""
    src = np.array([0, 0])
    dst = np.array([1, 1])
    bw_in = np.array([5.0, 0.0])  # the shared destination NIC is dead
    bw_out = np.array([5.0, 5.0])
    with np.errstate(divide="raise", invalid="raise"):
        r = OMCoflowRate().rates(
            src, dst, np.array([1.0, 2.0]), np.zeros(2),
            np.array([0, 0]), bw_in, bw_out,
        )
    assert np.isfinite(r).all()
    assert (r >= 0).all()
    np.testing.assert_allclose(r, 0.0, atol=1e-6)  # dead NIC: no throughput


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_engine_survives_zero_bandwidth_dip(policy):
    """A trace segment that drives EVERY NIC to exactly zero (then
    recovers) must stall the schedule, not poison it: finite makespan no
    smaller than the undisturbed run, no NaN anywhere."""
    wl, cluster, p, r = _setup(seed=1)
    base = simulate(wl, cluster, p, r, policy=policy).makespan
    dead = trace_from_events(
        cluster, [DynamicsEvent(t0=1.0, t1=3.0, machine=None, bw_scale=0.0)]
    )
    res = simulate(wl, cluster, p, r, policy=policy, trace=dead, record=True)
    assert np.isfinite(res.makespan)
    assert res.makespan >= base - 1e-9
    starts = res.task_start_matrix(wl.J, r.n_iters)
    assert np.isfinite(starts).all()
    # batch path takes the same guarded code
    got = simulate_batch(
        wl, cluster, [p], [r], policy=policy, trace=dead, record=True
    )[0]
    assert got.makespan == res.makespan
    assert got.task_events == res.task_events


# ---------------------------------------------------------------------------
# integer-bandwidth coercion (satellite 3)
# ---------------------------------------------------------------------------
def _int_bw_cluster(seed=1):
    cluster = heterogeneous_cluster(3, seed=seed)
    intd = ClusterSpec(machines=cluster.machines)
    intd.bw_in = np.ceil(cluster.bw_in).astype(np.int64)
    intd.bw_out = np.ceil(cluster.bw_out).astype(np.int64)
    ref = ClusterSpec(machines=cluster.machines)
    ref.bw_in = intd.bw_in.astype(np.float64)
    ref.bw_out = intd.bw_out.astype(np.float64)
    return intd, ref


@pytest.mark.parametrize("rate_cls", [FIFORate, MRTFRate])
def test_waterfill_rates_coerce_integer_bandwidth(rate_cls):
    """Regression: int bw arrays silently truncated ``rem -= give``.
    Three flows sharing one egress NIC of capacity 10: the first takes 4
    (its ingress cap), the leftovers must be 6 and 0 — not int-truncated
    garbage."""
    src = np.array([0, 0, 0])
    dst = np.array([1, 2, 1])
    bw_in = np.array([10, 4, 7], dtype=np.int64)
    bw_out = np.array([10, 10, 10], dtype=np.int64)
    rem = np.array([1.0, 2.0, 3.0])
    r_int = rate_cls().rates(src, dst, rem, np.arange(3.0), None, bw_in, bw_out)
    r_flt = rate_cls().rates(
        src, dst, rem, np.arange(3.0), None,
        bw_in.astype(np.float64), bw_out.astype(np.float64),
    )
    np.testing.assert_array_equal(r_int, r_flt)
    assert r_int.sum() == pytest.approx(10.0)  # egress NIC fully used


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_engine_matches_on_integer_bandwidth_cluster(policy):
    """A user-built ClusterSpec carrying int bandwidth vectors must
    schedule bit-identically to the same cluster in float64 — scalar and
    batched, across every waterfill (and other) policy."""
    wl = small_job(seed=1)
    intd, ref = _int_bw_cluster(seed=1)
    p = ifs_placement(wl, ref, seed=0)
    r = wl.realize(seed=0)
    want = simulate(wl, ref, p, r, policy=policy, record=True)
    got = simulate(wl, intd, p, r, policy=policy, record=True)
    assert want.makespan == got.makespan
    assert want.task_events == got.task_events
    assert want.flow_log == got.flow_log
    batch = simulate_batch(wl, intd, [p, p], [r, wl.realize(seed=1)],
                           policy=policy, record=True)
    assert batch[0].makespan == want.makespan
    assert batch[0].task_events == want.task_events


# ---------------------------------------------------------------------------
# golden-suite regen guard (satellite: CI / tooling)
# ---------------------------------------------------------------------------
def test_regen_refuses_to_overwrite_unnamed_regimes(tmp_path):
    from test_golden_schedules import REGIMES, regen_golden

    path = tmp_path / "golden.json"
    full = {
        "fanin": {r: {"v": 2} for r in REGIMES},
        "chain": {r: {"v": 2} for r in REGIMES},
        "ring": {r: {"v": 2} for r in REGIMES},
    }

    def gen(needed=None):
        # mirror _generate's contract: only needed cells are produced
        return {
            n: {r: json.loads(json.dumps(v)) for r, v in regs.items()
                if needed is None or (n, r) in needed}
            for n, regs in full.items()
        }
    # no file yet: everything is written
    golden, written, preserved = regen_golden([], path=path, generate=gen)
    assert golden == full and not preserved
    path.write_text(json.dumps({"fanin": {"static": {"v": 1}}}))
    # bare regen: the pinned regime survives, missing ones are filled in
    golden, written, preserved = regen_golden([], path=path, generate=gen)
    assert golden["fanin"]["static"] == {"v": 1}
    assert all(golden["fanin"][r] == {"v": 2} for r in REGIMES if r != "static")
    assert ("fanin", "static") in preserved
    # naming the regime is the only way to re-pin it
    golden, written, preserved = regen_golden(
        ["static"], path=path, generate=gen
    )
    assert golden["fanin"]["static"] == {"v": 2}
    assert ("fanin", "static") in written
    with pytest.raises(ValueError, match="unknown regime"):
        regen_golden(["stattic"], path=path, generate=gen)
