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

# Default ETP chain count per engine backend (pinned by
# tests/test_jax_engine.py).  numpy: 8.  jax: 16 — in a planner-scale CPU
# sweep (budget 512, 6 machines) the jitted engine planned in about 1.0 s
# at 16 chains and 0.8 s at 8, with best-makespan flat from 8 up, so
# doubling the basin count was nearly free there.  Beyond 16 the per-chain
# memoisation caches stop overlapping their own history (more cache misses
# = more simulations), costing wall with no measured quality gain.  Neither
# value has been re-derived on a TPU.
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
    with each step's candidate placements evaluated in one
    ``engine.simulate_batch`` call.  At a fixed transition ``budget`` more
    chains explore more basins but walk each less; the two effects roughly
    cancel on the testbed jobs.  Raising ``n_chains`` with ``budget``
    scaled proportionally is never worse — chains are seed-nested in that
    regime (tests/test_cache.py).

    ``backend`` selects the simulation engine for the search's batched
    evaluations (``engine.resolve_backend``: explicit >
    ``REPRO_ENGINE_BACKEND`` > numpy) and with it the ``n_chains``
    default (``DEFAULT_N_CHAINS``): the jax engine evaluates each
    lock-step batch in one jitted call, so its default runs MORE chains
    at the same budget (wider batches, more basins).  The committed
    schedule runs on the same engine, recorded: its task events and
    ``flow_log`` feed the chain certificate."""
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
