"""The traffic generator: one caller's closed loop of scheduler operations.

A traffic mix is a data file, ``chipbench/traffic/<name>.json``.  Its
``"op"`` names the operation a caller sends and the rest are that
operation's parameters; this module is the one generator that reads them.
Every input is drawn from the run's seed.  Three operations exist:

* ``plan``: ``repro.core.plan(workload, cluster, ...)`` for one job, the
  wait users have before a job starts;
* ``score``: ``repro.core.expected_makespan_many`` over a batch of
  candidate placements, one lock-step step of the ETP search;
* ``replan``: a fresh ``repro.dynamics.Replanner`` that sees bandwidth
  drift (``observe``) and then loses a machine (``on_leave``).

Each ``Ops`` object builds its inputs (``__init__``), warms the engine
shapes its operations use (``warm``), runs one operation (``run``, which
returns a record), and afterwards compares a sample of the records with the
deployment's plain reference (``check``).  An operation reaches the
deployment only through its ``Deployment``, so that every kind of
deployment runs the same operations.  Only public entry points of
``repro`` are called, through their module attributes, so that a test can
break the timed path underneath.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .deploy import Deployment

OP_SEED_BOUND = 2**31 - 1  # op seeds stay inside 32 signed bits


def rel_err(got: float, ref: float) -> float:
    if not np.isfinite(got):
        return float("inf")
    return abs(got - ref) / abs(ref)


@dataclass
class Record:
    """What one operation returned that the check reads, and its work."""

    ops: int  # operations as the caller counts them: plans, re-plans, calls
    units: int  # the work a rate counts: plans, re-plans or simulations
    data: Dict[str, Any] = field(default_factory=dict)


class Ops:
    """Base of the three operation drivers."""

    per_op = 1  # operations one loop step sends

    def __init__(self, dep: Deployment, traffic: Dict[str, Any], seed: int) -> None:
        self.dep = dep
        self.tr = traffic
        self.seed = int(seed)
        self.rng = np.random.default_rng([self.seed, 0x6F7073])

    def op_seed(self) -> int:
        return int(self.rng.integers(0, OP_SEED_BOUND))

    def warm(self) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def run(self) -> Record:  # pragma: no cover - interface
        raise NotImplementedError

    def check(self, records: List[Record], control: bool) -> Dict[str, float]:  # pragma: no cover
        raise NotImplementedError

    def limits(self) -> Dict[str, float]:
        return {k: float(v) for k, v in self.tr["limits"].items()}

    def sample(self, n_items: int, k: int) -> List[int]:
        """``k`` indices of ``n_items`` drawn from the seed (all when fewer)."""
        rng = np.random.default_rng([self.seed, 0x636B])
        if n_items <= k:
            return list(range(n_items))
        return sorted(int(i) for i in rng.choice(n_items, size=k, replace=False))

    def ref_mean(self, y: np.ndarray, bw: np.ndarray, draws, control: bool) -> Tuple[float, float]:
        """(reference mean makespan in float64, the control's in float32)."""
        ref = self.dep.reference_mean(y, bw, draws, np.float64)
        low = self.dep.reference_mean(y, bw, draws, np.float32) if control else float("nan")
        return ref, low

    def warm_widths(self, widths, n_iters: int, cluster: Any = None, migrations=None) -> None:
        """One ``simulate_batch`` call per width, so that each engine shape
        the operations meet is compiled (or loaded) before the window."""
        import repro.core as core

        cl = self.dep.cluster if cluster is None else cluster
        vol, ex = self.dep.job.realize(0, n_iters)
        r = core.Realization(volumes=vol, exec_times=ex)
        p = core.Placement(np.arange(self.dep.job.J, dtype=np.int64) % cl.M)
        for w in widths:
            core.simulate_batch(
                self.dep.workload, cl, [p] * w, [r] * w, policy="oes",
                migrations=None if migrations is None else migrations * (w // len(migrations)),
            )


class PlanOps(Ops):
    """``plan()`` with the ETP search on the engine, then the numpy commit."""


    def __init__(self, dep: Deployment, traffic: Dict[str, Any], seed: int) -> None:
        super().__init__(dep, traffic, seed)
        import repro.core as core

        # one job draw for every seed: the plans' seeds differ, the job does
        # not, so the committed makespans of two runs compare
        self.vol, self.ex = dep.job.realize(traffic["realization_seed"], dep.n_iters)
        self.realization = core.Realization(volumes=self.vol, exec_times=self.ex)

    def warm(self) -> None:
        self.warm_widths(self.tr["warm_widths"], self.tr["sim_iters"])

    def run(self) -> Record:
        import repro.core as core

        s = self.op_seed()
        out = core.plan(
            self.dep.workload, self.dep.cluster, realization=self.realization,
            budget=self.tr["budget"], sim_iters=self.tr["sim_iters"], seed=s,
            policy=self.tr["policy"],
        )
        win = min(out.etp.chain_stats, key=lambda c: c["best_makespan"])
        return Record(ops=1, units=1, data=dict(
            y=out.placement.y.copy(),
            makespan=float(out.schedule.makespan),
            starts=out.schedule.task_start_matrix(self.dep.job.J, self.vol.shape[1]),
            cert=bool(out.certificate.holds),
            search_cost=float(out.etp.best_makespan),
            win_seed=int(win["seed"]),
        ))

    def check(self, records: List[Record], control: bool) -> Dict[str, float]:
        job, bw = self.dep.job, self.dep.bw
        search, commit = 0.0, 0.0
        for i in self.sample(len(records), self.tr["check_plans"]):
            d = records[i].data
            draws = job.draws(d["win_seed"], self.tr["sim_iters"], 1)
            ref, low = self.ref_mean(d["y"], bw, draws, control)
            search = max(search, rel_err(low if control else d["search_cost"], ref))
            full = self.dep.reference_schedule(d["y"], bw, self.vol, self.ex, np.float64)
            if control:
                full32 = self.dep.reference_schedule(d["y"], bw, self.vol, self.ex, np.float32)
                got_m, got_s = full32.makespan, full32.starts
            else:
                got_m, got_s = d["makespan"], d["starts"]
            if not np.array_equal(np.isnan(got_s), np.isnan(full.starts)):
                gap = float("inf")
            else:
                gap = float(np.nanmax(np.abs(got_s - full.starts))) / full.makespan
            commit = max(commit, rel_err(got_m, full.makespan), gap)
        return {
            "search_cost_rel_err": search,
            "commit_rel_err": commit,
            "certificate_failures": float(sum(not r.data["cert"] for r in records)),
        }


def single_moves(
    y: np.ndarray, movable: List[int], demand: np.ndarray, cap: np.ndarray,
    rng: np.random.Generator, n: int,
) -> List[np.ndarray]:
    """Up to ``n`` placements one capacity-feasible single-task move away
    from ``y``: a movable task drawn uniformly, then a machine drawn
    uniformly among those with room for it, as ETP proposes a move."""
    usage = np.zeros_like(cap)
    np.add.at(usage, y, demand)
    out: List[np.ndarray] = []
    for _ in range(20 * n):
        if len(out) == n:
            break
        j = int(rng.choice(movable))
        room = [
            m for m in range(len(cap))
            if m != y[j] and np.all(usage[m] + demand[j] <= cap[m] + 1e-9)
        ]
        if not room:
            continue
        z = y.copy()
        z[j] = int(rng.choice(room))
        out.append(z)
    return out


class ScoreOps(Ops):
    """``expected_makespan_many`` over a batch of candidate placements."""

    def __init__(self, dep: Deployment, traffic: Dict[str, Any], seed: int) -> None:
        super().__init__(dep, traffic, seed)
        import repro.core as core

        pool: List[np.ndarray] = []
        for _ in range(traffic["pool_ifs"]):
            y = core.ifs_placement(dep.workload, dep.cluster, seed=self.op_seed()).y
            pool.append(y)
            pool += single_moves(y, dep.movable, dep.demand, dep.capacity, self.rng, traffic["pool_moves"])
        self.pool = pool
        if len(pool) < traffic["candidates"]:
            raise RuntimeError(f"candidate pool holds {len(pool)} placements")

    def warm(self) -> None:
        self.warm_widths([self.tr["candidates"] * self.tr["draws"]], self.tr["n_iters"])

    def run(self) -> Record:
        import repro.core as core

        pick = self.rng.choice(len(self.pool), size=self.tr["candidates"], replace=False)
        ys = [self.pool[int(i)] for i in pick]
        s = self.op_seed()
        means = core.expected_makespan_many(
            self.dep.workload, self.dep.cluster, [core.Placement(y.copy()) for y in ys],
            policy=self.tr["policy"], n_iters=self.tr["n_iters"],
            n_draws=self.tr["draws"], seed=s,
        )
        return Record(ops=1, units=len(ys) * self.tr["draws"], data=dict(ys=ys, seed=s, means=list(means)))

    def check(self, records: List[Record], control: bool) -> Dict[str, float]:
        flat = [(r, c) for r in range(len(records)) for c in range(len(records[r].data["ys"]))]
        worst = 0.0
        for i in self.sample(len(flat), self.tr["check_candidates"]):
            r, c = flat[i]
            d = records[r].data
            draws = self.dep.job.draws(d["seed"], self.tr["n_iters"], self.tr["draws"])
            ref, low = self.ref_mean(d["ys"][c], self.dep.bw, draws, control)
            worst = max(worst, rel_err(low if control else d["means"][c], ref))
        return {"score_mean_rel_err": worst}


def drifted_bandwidth(
    bw: np.ndarray, seed: int, n_segments: int, scale_range: Tuple[float, float],
    drift_prob: float, threshold: float,
) -> np.ndarray:
    """NIC speeds at the most drifted segment of a sustained-drift trace:
    per segment each machine keeps its scale or, with ``drift_prob``,
    redraws it from ``scale_range``.  Trace seeds from ``seed`` up are tried
    until the drift (largest relative NIC change) passes ``threshold``."""
    for s in range(seed, seed + 1000):
        rng = np.random.default_rng(s)
        M = len(bw)
        cur = np.ones(M)
        best, best_d = cur, 0.0
        for k in range(n_segments):
            if k > 0:
                redraw = rng.random(M) < drift_prob
                draws = rng.uniform(*scale_range, size=M)
                cur = np.where(redraw, draws, cur)
            rng.random(M)  # the segment's straggler draw (unused: bandwidth only)
            d = float(np.max(1.0 - cur))
            if d > best_d:
                best, best_d = cur.copy(), d
        if best_d > threshold:
            return bw * best
    raise RuntimeError("no drift trace passes the threshold")


class ReplanOps(Ops):
    """A warm ``Replanner``: a drift re-plan, then a machine leave."""

    per_op = 2  # observe() and on_leave() each re-plan

    def warm(self) -> None:
        import repro.core as core

        # the clean (width 1) and migration-loaded (width 2) scoring shapes,
        # on the full cluster and on one that has lost a machine
        n = self.tr["sim_iters"]
        last = len(self.dep.bw) - 1
        for cl in (self.dep.cluster, self.dep.without(last, self.dep.bw)[0]):
            self.warm_widths([1], n, cl)
            for g in self.tr["warm_migration_flows"]:
                flows = [core.MigrationFlow(src=0, dst=1, gb=0.1)] * g
                self.warm_widths([2], n, cl, migrations=[None, flows])

    def run(self) -> Record:
        import repro.core as core
        import repro.dynamics as dyn

        dep, tr = self.dep, self.tr
        s = self.op_seed()
        p0 = core.ifs_placement(dep.workload, dep.cluster, seed=s)
        bw = drifted_bandwidth(
            dep.bw, s, tr["n_segments"], tuple(tr["bw_scale_range"]),
            tr["drift_prob"], tr["drift_threshold"],
        )
        leave = int(s % len(dep.bw))
        cfg = dyn.ReplanConfig(
            budget=tr["budget"], sim_iters=tr["sim_iters"],
            drift_threshold=tr["drift_threshold"], seed=s,
        )
        rp = dyn.Replanner(dep.workload, dep.cluster, p0, config=cfg)
        drift = rp.observe(bw, bw, trigger=tr["trigger"])
        y_drift = rp.placement.y.copy()
        gone = rp.on_leave(leave)
        return Record(ops=2, units=2, data=dict(
            seed=s, bw=bw, leave=leave,
            replanned=[drift.replanned, gone.replanned],
            makespans=[float(drift.makespan), float(gone.makespan)],
            ys=[y_drift, rp.placement.y.copy()],
        ))

    def check(self, records: List[Record], control: bool) -> Dict[str, float]:
        worst = 0.0
        for i in self.sample(len(records), self.tr["check_ops"]):
            d = records[i].data
            draws = self.dep.job.draws(d["seed"], self.tr["sim_iters"], 1)
            bws = [d["bw"], self.dep.without(d["leave"], d["bw"])[1]]
            for k in range(2):
                ref, low = self.ref_mean(d["ys"][k], bws[k], draws, control)
                worst = max(worst, rel_err(low if control else d["makespans"][k], ref))
        declined = sum(not all(r.data["replanned"]) for r in records)
        return {"replan_makespan_rel_err": worst, "replans_declined": float(declined)}


DRIVERS: Dict[str, Callable[[Deployment, Dict[str, Any], int], Ops]] = {
    "plan": PlanOps, "score": ScoreOps, "replan": ReplanOps,
}


def make_ops(dep: Deployment, traffic: Dict[str, Any], seed: int) -> Ops:
    op = traffic.get("op")
    if op not in DRIVERS:
        raise ValueError(f"traffic op {op!r} is none of {sorted(DRIVERS)}")
    return DRIVERS[op](dep, traffic, seed)
