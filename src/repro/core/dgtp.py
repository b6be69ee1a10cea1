"""DGTP (Alg. 4): ETP placement search + OES online scheduling, end to end.

``plan()`` is the public API: given a workload and a cluster it returns the
chosen placement, the online schedule for a realization, and the audit
quantities (Delta, chain lower bound, traffic summary) used throughout
benchmarks and tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from .analysis import ChainCertificate, chain_lower_bound, max_degree, traffic_summary
from .cluster import ClusterSpec, Placement
from .engine import ScheduleResult, resolve_backend, simulate
from .placement import (
    ETPResult,
    distdgl_placement,
    etp_multichain,
    etp_search,
    ifs_placement,
)
from .workload import Realization, Workload
from ..obs.spans import next_seq, span

# Default ETP chain count per engine backend, re-derived from the measured
# chain sweep (ROADMAP perf log; pinned by tests/test_jax_engine.py).
# numpy: 8 — the PR-1 sweet spot.  jax: 16 — on the planner-scale sweep
# (budget 512, 6 machines) the jitted engine plans in ~1.0s at 16 chains
# vs ~0.8s at 8 and vs numpy-8's ~6.2s, with best-makespan flat from 8 up
# — doubling the basin count is nearly free on the jax backend.  Beyond 16
# the per-chain memoisation caches stop overlapping their own history
# (more cache misses = more simulations), costing wall with no measured
# quality gain.
DEFAULT_N_CHAINS = {"numpy": 8, "jax": 16}


@dataclass
class Plan:
    placement: Placement
    schedule: ScheduleResult
    certificate: ChainCertificate
    etp: Optional[ETPResult]
    delta: int
    traffic: dict


def plan(
    workload: Workload,
    cluster: ClusterSpec,
    *,
    realization: Optional[Realization] = None,
    budget: int = 1000,
    mu: float = 1.0,
    beta: float = 0.1,
    sim_iters: int = 20,
    seed: int = 0,
    policy: str = "oes",
    search: bool = True,
    time_budget_s: Optional[float] = None,
    n_chains: Optional[int] = None,
    backend: Optional[str] = None,
) -> Plan:
    """Run DGTP: search placement (ETP) then schedule online (OES).

    Default search is multi-chain: one chain from IFS, one warm-started
    from the DistDGL colocation heuristic, the rest from random IFS machine
    orders — DGTP's placement is then at least as good as every baseline's
    under its own scheduler, for any budget (the single-chain
    paper-faithful search is etp_search).  The chains advance in lock-step
    with their candidate placements evaluated in one batched simulation
    (engine.simulate_batch), so planning wall time shrinks with the chain
    count at identical search semantics — which is why the default is 8
    chains: at a fixed transition ``budget`` the batch width quadruples vs
    the old 2-chain default (wall time drops accordingly,
    benchmarks/bench_etp.py) at comparable placement quality (8 shallower
    chains explore more basins but walk each less; the two effects roughly
    cancel on the testbed jobs).  Raising ``n_chains`` with ``budget``
    scaled proportionally is never worse — chains are seed-nested in that
    regime (tests/test_cache.py).

    ``backend`` selects the simulation engine for the search's batched
    evaluations (``engine.resolve_backend``: explicit >
    ``REPRO_ENGINE_BACKEND`` > numpy) and with it the ``n_chains``
    default (``DEFAULT_N_CHAINS``): the jax engine evaluates each
    lock-step batch in one jitted call, so its default runs MORE chains
    at the same budget (wider batches, more basins — re-derived from the
    measured sweep in benchmarks/bench_engine.py).  The committed schedule
    runs on the same engine, recorded: its task events and ``flow_log``
    feed the chain certificate."""
    with span("repro.plan", seq=next_seq(), budget=budget):
        realization = realization or workload.realize(seed=seed)
        backend = resolve_backend(backend)
        if n_chains is None:
            n_chains = DEFAULT_N_CHAINS[backend]
        etp: Optional[ETPResult] = None
        with span("repro.plan.search"):
            if search:
                etp = etp_multichain(
                    workload,
                    cluster,
                    n_chains=n_chains,
                    budget=budget,
                    mu=mu,
                    beta=beta,
                    sim_iters=sim_iters,
                    seed=seed,
                    policy=policy,
                    time_budget_s=time_budget_s,
                    backend=backend,
                )
                placement = etp.placement
            else:
                placement = ifs_placement(workload, cluster, seed=seed)
        with span("repro.plan.commit.simulate"):
            schedule = simulate(
                workload, cluster, placement, realization, policy=policy,
                record=True, backend=backend,
            )
        with span("repro.plan.commit.audit"):
            cert = chain_lower_bound(workload, cluster, placement, realization, schedule)
            delta = max_degree(workload, placement, cluster)
            traffic = traffic_summary(workload, placement, realization)
    return Plan(
        placement=placement,
        schedule=schedule,
        certificate=cert,
        etp=etp,
        delta=delta,
        traffic=traffic,
    )


def plan_baseline(
    workload: Workload,
    cluster: ClusterSpec,
    *,
    baseline: str,
    realization: Optional[Realization] = None,
    seed: int = 0,
) -> Plan:
    """Baselines of §VI-B: 'distdgl' (own placement + FIFO flows);
    'omcoflow' / 'mrtf' (DGTP's placement is supplied by the caller via
    plan() instead — here they use IFS for a placement-free comparison)."""
    realization = realization or workload.realize(seed=seed)
    if baseline == "distdgl":
        placement = distdgl_placement(workload, cluster)
        policy = "fifo"
    else:
        placement = ifs_placement(workload, cluster, seed=seed)
        policy = baseline
    schedule = simulate(
        workload, cluster, placement, realization, policy=policy, record=True,
        backend="numpy",
    )
    cert = chain_lower_bound(workload, cluster, placement, realization, schedule)
    return Plan(
        placement=placement,
        schedule=schedule,
        certificate=cert,
        etp=None,
        delta=max_degree(workload, placement, cluster),
        traffic=traffic_summary(workload, placement, realization),
    )
