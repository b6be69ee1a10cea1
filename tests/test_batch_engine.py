"""Batched evaluation on the numpy reference: exactness certificates.

``simulate_batch`` on numpy is the reference ``simulate`` run instance by
instance, so its results are BIT-IDENTICAL to ``simulate`` on each
(placement, realization) alone, for every rate policy.  The batched
callers built on it (``expected_makespan``, ``expected_makespan_many``,
lock-step ``etp_multichain``, the merged-job objective) are pinned here to
the loops of scalar calls they stand for.  The slotted transcription of
Alg. 1 (oes_slotted.py) stays the fidelity anchor in the slot->0 limit.
(The jitted lock-step engine is pinned to this reference at
``PARITY_RTOL`` in tests/test_jax_engine.py.)
"""
import numpy as np
import pytest

from repro.core import (
    build_gnn_workload,
    expected_makespan,
    expected_makespan_many,
    heterogeneous_cluster,
    ifs_placement,
    monte_carlo_draws,
    simulate,
    simulate_batch,
    simulate_slotted,
)
from repro.core.multijob import (
    SEED_NS_CHAIN,
    SEED_NS_DRAW,
    derive_seed,
    merge_workloads,
    merged_batch_cost,
    realize_merged,
)
from repro.core.placement import distdgl_placement, etp_multichain, etp_search

ALL_POLICIES = ("oes", "oes_strict", "fifo", "mrtf", "omcoflow")


def small_job(seed=0, n_iters=5):
    rng = np.random.default_rng(seed)
    return build_gnn_workload(
        n_stores=int(rng.integers(2, 4)),
        n_workers=int(rng.integers(1, 4)),
        samplers_per_worker=int(rng.integers(1, 3)),
        n_ps=1,
        n_iters=n_iters,
        store_to_sampler_gb=float(rng.uniform(0.2, 2.0)),
        sampler_to_worker_gb=float(rng.uniform(0.2, 1.0)),
        grad_gb=float(rng.uniform(0.05, 0.4)),
        store_exec_s=0.3,
        sampler_exec_s=0.4,
        worker_exec_s=0.8,
        ps_exec_s=0.2,
        pmr=1.3,
    )


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_batch_matches_scalar_exactly(policy):
    """Random small jobs: batch-of-5 schedules == scalar schedules, bitwise,
    for all five rate policies."""
    for seed in range(3):
        wl = small_job(seed=seed)
        cluster = heterogeneous_cluster(3, seed=seed)
        try:
            placements = [ifs_placement(wl, cluster, seed=s) for s in range(5)]
        except ValueError:
            continue  # cluster cannot host the job: draw another
        reals = [wl.realize(seed=s) for s in range(5)]
        batch = simulate_batch(wl, cluster, placements, reals, policy=policy, record=True)
        for b, (p, r) in enumerate(zip(placements, reals)):
            ref = simulate(wl, cluster, p, r, policy=policy, record=True)
            assert ref.makespan == batch[b].makespan, (policy, seed, b)
            assert ref.n_events == batch[b].n_events, (policy, seed, b)
            assert ref.task_events == batch[b].task_events, (policy, seed, b)
            assert ref.flow_log == batch[b].flow_log, (policy, seed, b)


def test_fused_expected_makespan_matches_loop():
    """expected_makespan == the mean of one scalar simulate per canonical
    draw (``monte_carlo_draws``), summed in order."""
    wl = small_job(seed=1)
    cluster = heterogeneous_cluster(3, seed=1)
    p = ifs_placement(wl, cluster, seed=0)
    for n_draws in (1, 2, 4):
        total = 0.0
        for r in monte_carlo_draws(wl, seed=5, n_iters=20, n_draws=n_draws):
            total += simulate(wl, cluster, p, r).makespan
        fused = expected_makespan(wl, cluster, p, n_draws=n_draws, seed=5)
        assert fused == total / n_draws, n_draws


def test_expected_makespan_many_matches_per_placement():
    wl = small_job(seed=2)
    cluster = heterogeneous_cluster(3, seed=2)
    placements = [ifs_placement(wl, cluster, seed=s) for s in range(4)]
    many = expected_makespan_many(wl, cluster, placements, n_draws=2, seed=3)
    ref = [
        expected_makespan(wl, cluster, p, n_draws=2, seed=3)
        for p in placements
    ]
    assert many == ref


def test_batched_engine_matches_slotted_oracle():
    """Slot->0 agreement of the BATCHED strict-OES path with the paper's
    Alg. 1 transcription — same certificate the scalar engine carries."""
    wl = small_job(seed=4)
    cluster = heterogeneous_cluster(3, seed=4)
    p = ifs_placement(wl, cluster, seed=0)
    r = wl.realize(seed=2)
    ev = simulate_batch(wl, cluster, [p], [r], policy="oes_strict")[0].makespan
    for slot, tol in ((0.25, 0.35), (0.05, 0.1)):
        sl = simulate_slotted(wl, cluster, p, r, slot=slot).makespan * slot
        assert sl == pytest.approx(ev, rel=tol), (slot, sl, ev)


def _sequential_chains(wl, cluster, *, n_chains, budget, seed, **kw):
    """What ``etp_multichain`` promises, written as a loop: one
    ``etp_search`` per chain with ``budget // n_chains`` transitions, seed
    ``derive_seed(seed, SEED_NS_CHAIN, c)``, chain 1 from the DistDGL
    placement; best of (feasible first, then lowest makespan).  Returns
    the best result and every chain's result."""
    runs = [
        etp_search(
            wl, cluster, budget=max(1, budget // n_chains),
            seed=derive_seed(seed, SEED_NS_CHAIN, c),
            init=distdgl_placement(wl, cluster) if c == 1 else None, **kw,
        )
        for c in range(n_chains)
    ]
    best = runs[0]
    for r in runs[1:]:
        if (best.fallback, best.best_makespan) > (r.fallback, r.best_makespan):
            best = r
    return best, runs


def _assert_same_chains(bat, runs):
    """Each lock-step chain's telemetry row equals its etp_search run."""
    assert len(bat.chain_stats) == len(runs)
    for st, r in zip(bat.chain_stats, runs):
        assert st["objective_trajectory"] == [float(c) for c in r.cost_trace]
        assert (st["evaluations"], st["cache_hits"]) == (r.evaluations, r.cache_hits)
        assert (st["proposals"], st["accepted"]) == (r.proposals, r.accepted)


def test_multichain_batch_matches_sequential():
    """Lock-step batched chains == a loop of single etp_search chains: same
    per-chain trajectories and counters, same best placement, makespan,
    cost trace and eval/cache counters."""
    wl = small_job(seed=5, n_iters=8)
    cluster = heterogeneous_cluster(4, seed=6)
    kw = dict(n_chains=3, budget=45, sim_iters=8, sim_draws=2, seed=0)
    seq, runs = _sequential_chains(wl, cluster, **kw)
    bat = etp_multichain(wl, cluster, **kw)
    _assert_same_chains(bat, runs)
    assert np.array_equal(seq.placement.y, bat.placement.y)
    assert seq.best_makespan == bat.best_makespan
    assert seq.cost_trace == bat.cost_trace
    assert seq.evaluations == bat.evaluations
    assert seq.cache_hits == bat.cache_hits


def test_multichain_explicit_cost_fn_beats_batch_cost_fn():
    """An explicit scalar cost_fn wins over batch_cost_fn: the lock-step
    search optimises the same objective as a loop of etp_search chains
    under that cost_fn, and never consults batch_cost_fn."""
    wl = small_job(seed=6, n_iters=6)
    cluster = heterogeneous_cluster(3, seed=3)

    def scalar_cost(p):
        return float(np.sum(p.y))  # deterministic, trivially cheap

    consulted = []

    def batch_cost(ps):
        consulted.append(len(ps))
        return [1e9] * len(ps)  # would wreck the search if ever consulted

    seq, runs = _sequential_chains(
        wl, cluster, n_chains=2, budget=20, seed=0, cost_fn=scalar_cost
    )
    bat = etp_multichain(
        wl, cluster, n_chains=2, budget=20, seed=0, cost_fn=scalar_cost,
        batch_cost_fn=batch_cost,
    )
    assert not consulted
    _assert_same_chains(bat, runs)
    assert seq.best_makespan == bat.best_makespan
    assert seq.cost_trace == bat.cost_trace
    assert bat.best_makespan < 1e9


def test_batch_rejects_mismatched_realizations():
    wl = small_job(seed=0)
    cluster = heterogeneous_cluster(3, seed=0)
    p = ifs_placement(wl, cluster, seed=0)
    with pytest.raises(ValueError):
        simulate_batch(wl, cluster, [p, p], [wl.realize(seed=0)])
    with pytest.raises(ValueError):
        simulate_batch(
            wl, cluster, [p, p],
            [wl.realize(seed=0, n_iters=4), wl.realize(seed=0, n_iters=5)],
        )


def test_merged_job_batch_cost_matches_scalar_sim():
    """Multi-job batch sizing: the merged-job batched objective equals
    per-placement scalar simulation of the merged realizations."""
    j1 = small_job(seed=7, n_iters=6)
    j2 = small_job(seed=8, n_iters=4)
    mj = merge_workloads([j1, j2])
    cluster = heterogeneous_cluster(4, seed=9, gpu_range=(2, 4))
    placements = [ifs_placement(mj.workload, cluster, seed=s) for s in range(3)]
    cost = merged_batch_cost(mj, [j1, j2], cluster, n_draws=2, seed=0)
    got = cost(placements)
    for p, c in zip(placements, got):
        ref = 0.0
        for d in range(2):
            r = realize_merged(mj, [j1, j2], seed=derive_seed(0, SEED_NS_DRAW, d))
            ref += simulate(mj.workload, cluster, p, r, policy="oes").makespan
        assert c == ref / 2
