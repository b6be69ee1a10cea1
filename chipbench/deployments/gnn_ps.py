"""The paper's parameter-server GNN job on a cluster of per-machine NICs.

The job (paper Sec. III): graph stores, samplers, workers and parameter
servers.  Per iteration: store -> sampler (lag 0, the sampler's graph data
split evenly over the stores), sampler -> its worker (lag 0), worker -> PS
(lag 0), PS -> worker (lag 1).  Each machine has one NIC, ingress = egress,
and flows share NICs by OES (``reference.py``).  Stores stay where they
are; every other task may move.

The generators (the DAG layout, the volume derivation and the cluster draw)
are copies kept with the benchmark, so that a change to the program cannot
move the yardstick; ``check_program_job`` holds the program's job to them.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import reference
from chipbench.deploy import Deployment, Job

RESOURCES = ("cpu", "gpu", "mem")


def sampler_volume_gb(job: Dict[str, Any]) -> float:
    """Graph data one sampler receives per iteration: seeds x sampled
    nodes per seed (L-hop fan-out, deduplicated) x 4-byte features."""
    total, width = 1.0, 1.0
    for f in job["fanout"]:
        width *= f
        total += width
    nodes_per_seed = total * job["unique_factor"]
    seeds = job["batch_size"] // job["samplers_per_worker"]
    return seeds * nodes_per_seed * job["feature_len"] * 4 / 2**30


def build_job(cfg: Dict[str, Any]) -> Job:
    j = cfg["job"]
    kinds: List[str] = []
    stores = [len(kinds) + i for i in range(j["n_stores"])]
    kinds += ["store"] * j["n_stores"]
    workers = [len(kinds) + i for i in range(j["n_workers"])]
    kinds += ["worker"] * j["n_workers"]
    sampler_of_worker: Dict[int, List[int]] = {}
    for w in workers:
        sampler_of_worker[w] = [len(kinds) + i for i in range(j["samplers_per_worker"])]
        kinds += ["sampler"] * j["samplers_per_worker"]
    samplers = [s for w in workers for s in sampler_of_worker[w]]
    pss = [len(kinds) + i for i in range(j["n_ps"])]
    kinds += ["ps"] * j["n_ps"]

    vol_s = sampler_volume_gb(j)
    edges: List[Tuple[int, int, int, float, bool]] = []
    for s in samplers:
        for g in stores:
            edges.append((g, s, 0, vol_s / len(stores), True))
    for w in workers:
        for s in sampler_of_worker[w]:
            edges.append((s, w, 0, vol_s, True))
    for w in workers:
        for p in pss:
            edges.append((w, p, 0, j["grad_gb"] / len(pss), False))
    for p in pss:
        for w in workers:
            edges.append((p, w, 1, j["grad_gb"] / len(pss), False))
    return job_of(cfg, kinds, edges)


def job_of(cfg: Dict[str, Any], kinds: List[str], edges: Sequence[Tuple[int, int, int, float, bool]]) -> Job:
    """The :class:`Job` of tasks ``kinds`` and edges ``(src, dst, lag, mean
    GB, fluctuating)``, with the exec means and draws of ``cfg["job"]``."""
    j = cfg["job"]
    exec_of = {
        "store": j["store_exec_s"], "sampler": j["sampler_exec_s"],
        "worker": j["worker_exec_s"], "ps": j["ps_exec_s"],
    }
    return Job(
        kinds=kinds,
        src=np.array([e[0] for e in edges], dtype=np.int64),
        dst=np.array([e[1] for e in edges], dtype=np.int64),
        lag=np.array([e[2] for e in edges], dtype=np.int64),
        mean_volume=np.array([e[3] for e in edges], dtype=np.float64),
        mean_exec=np.array([exec_of[k] for k in kinds], dtype=np.float64),
        fluctuating=np.array([e[4] for e in edges], dtype=bool),
        pmr=float(j["pmr"]),
        exec_jitter=float(j["exec_jitter"]),
    )


def heterogeneous_machines(
    m: int, seed: int, mem_range: Sequence[int], cpu_range: Sequence[int],
    gpu_range: Sequence[int], bw_choices_GBps: Sequence[float],
) -> List[Dict[str, float]]:
    """The paper's Sec. VI-B random cluster: per machine a NIC speed drawn
    from ``bw_choices`` and integer memory, cores and GPUs drawn uniformly
    from their ranges (inclusive)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(m):
        bw = float(rng.choice(np.asarray(bw_choices_GBps)))
        out.append({
            "mem": float(rng.integers(int(mem_range[0]), int(mem_range[1]) + 1)),
            "cpu": float(rng.integers(cpu_range[0], cpu_range[1] + 1)),
            "gpu": float(rng.integers(gpu_range[0], gpu_range[1] + 1)),
            "bw_GBps": bw,
        })
    return out


def bandwidths(machines: Sequence[Dict[str, float]]) -> np.ndarray:
    """[M] NIC speed of each machine, GB/s (ingress = egress)."""
    return np.array([m["bw_GBps"] for m in machines], dtype=np.float64)


def program_cluster(machines: Sequence[Dict[str, float]], bw: Optional[np.ndarray] = None) -> Any:
    from repro.core import ClusterSpec, Machine

    bw = bandwidths(machines) if bw is None else bw
    return ClusterSpec(machines=[
        Machine(
            name=f"m{i}",
            resources={r: float(m[r]) for r in ("mem", "cpu", "gpu")},
            bw_in=float(b), bw_out=float(b),
        )
        for i, (m, b) in enumerate(zip(machines, bw))
    ])


def program_workload(cfg: Dict[str, Any], sync: str = "ps") -> Any:
    """The program's workload of ``cfg["job"]``, through its public
    constructor."""
    from repro.core import build_gnn_workload

    j = cfg["job"]
    vol_s = sampler_volume_gb(j)
    return build_gnn_workload(
        n_stores=j["n_stores"], n_workers=j["n_workers"],
        samplers_per_worker=j["samplers_per_worker"], n_ps=j["n_ps"],
        n_iters=j["n_iters"], store_to_sampler_gb=vol_s,
        sampler_to_worker_gb=vol_s, grad_gb=j["grad_gb"],
        store_exec_s=j["store_exec_s"], sampler_exec_s=j["sampler_exec_s"],
        worker_exec_s=j["worker_exec_s"], ps_exec_s=j["ps_exec_s"],
        pmr=j["pmr"], demands=j["demands"], sync=sync,
    )


def check_program_job(name: str, job: Job, wl: Any) -> None:
    """Refuse a program workload that is not ``job``: same tasks, edges,
    lags, mean volumes and exec times."""
    same = (
        wl.J == job.J and wl.E == job.E
        and np.array_equal(wl.edge_src, job.src)
        and np.array_equal(wl.edge_dst, job.dst)
        and np.array_equal(wl.edge_lag, job.lag)
        and np.allclose(wl.traffic.mean_volume, job.mean_volume, rtol=1e-15, atol=0)
        and np.allclose(wl.traffic.mean_exec, job.mean_exec, rtol=1e-15, atol=0)
        and np.array_equal(wl.traffic.fluctuating, job.fluctuating)
        and [t.kind for t in wl.tasks] == job.kinds
        and wl.traffic.exec_jitter == job.exec_jitter
    )
    if not same:
        raise RuntimeError(f"{name}: the program's workload is not the configured job")


def deployment(name: str, cfg: Dict[str, Any], job: Job, wl: Any) -> Deployment:
    """The deployment of ``job`` (the program's ``wl``) on the configured
    cluster of per-machine NICs, with the OES event-simulation reference."""
    check_program_job(name, job, wl)
    machines = cfg["cluster"]["machines"]
    demands = cfg["job"]["demands"]
    bw = bandwidths(machines)

    def without(m: int, bw_all: np.ndarray) -> Tuple[Any, np.ndarray]:
        rest = np.delete(bw_all, m)
        return program_cluster(machines[:m] + machines[m + 1:], rest), rest

    return Deployment(
        name=name, job=job, bw=bw, workload=wl, cluster=program_cluster(machines),
        n_iters=int(cfg["job"]["n_iters"]),
        demand=np.array([[demands[k].get(r, 0.0) for r in RESOURCES] for k in job.kinds]),
        capacity=np.array([[m[r] for r in RESOURCES] for m in machines]),
        movable=[t for t, k in enumerate(job.kinds) if k != "store"],
        reference_mean=partial(reference.mean_makespan, job),
        reference_schedule=partial(reference.simulate, job.src, job.dst, job.lag, job.J),
        without=without,
    )


def load(name: str, cfg: Dict[str, Any]) -> Deployment:
    return deployment(name, cfg, build_job(cfg), program_workload(cfg))
