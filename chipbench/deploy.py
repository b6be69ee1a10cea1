"""Deployments: a cluster and a training job, built from a configuration file.

A configuration (``chipbench/configs/<name>.json``) holds the numbers of one
deployment from the paper: the machines, the dataset statistics the job's
traffic is derived from, and the job's task counts.  This module turns them
into

* a :class:`Job`, the benchmark's own description of the task/flow DAG and
  its traffic model, which the reference simulator (``reference.py``) runs;
* the program's ``Workload`` and ``ClusterSpec``, built through the public
  constructors of ``repro.core`` and checked against the :class:`Job` edge by
  edge, so that the reference and the program run the same job.

The generators (the DAG layout, the volume derivation, the per-iteration
draws and the cluster draw) are copies kept with the benchmark, so that a
change to the program cannot move the yardstick.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

@dataclass
class Job:
    """Task/flow DAG and traffic model of one job (paper Sec. III).

    Per iteration: store -> sampler (lag 0), sampler -> its worker (lag 0),
    worker -> PS (lag 0), PS -> worker (lag 1).  Instance ``n`` of an edge
    carries ``(src, n)``'s output to ``(dst, n + lag)``."""

    kinds: List[str]  # [J]
    src: np.ndarray  # [E] int
    dst: np.ndarray  # [E] int
    lag: np.ndarray  # [E] int
    mean_volume: np.ndarray  # [E] GB
    mean_exec: np.ndarray  # [J] s
    fluctuating: np.ndarray  # [E] bool
    pmr: float
    exec_jitter: float
    demands: Dict[str, Dict[str, float]]
    sampler_of_worker: Dict[int, List[int]]

    @property
    def J(self) -> int:
        return len(self.kinds)

    @property
    def E(self) -> int:
        return len(self.src)

    def realize(self, seed: int, n_iters: int) -> Tuple[np.ndarray, np.ndarray]:
        """(volumes [E, N], exec times [J, N]) of one draw: graph-data edges
        scale by uniform factors in [2 - pmr, pmr], exec times by
        [1 - jitter, 1 + jitter]; tensor flows are fixed."""
        rng = np.random.default_rng(seed)
        vol = np.tile(self.mean_volume[:, None], (1, n_iters))
        if self.pmr > 1.0 and self.fluctuating.any():
            lo = max(0.0, 2.0 - self.pmr)
            f = rng.uniform(lo, self.pmr, size=(int(self.fluctuating.sum()), n_iters))
            vol[self.fluctuating] *= f
        ex = np.tile(self.mean_exec[:, None], (1, n_iters))
        if self.exec_jitter > 0:
            ex *= rng.uniform(
                1 - self.exec_jitter, 1 + self.exec_jitter, size=(self.J, n_iters)
            )
        return vol, ex

    def draws(self, seed: int, n_iters: int, n_draws: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        """The Monte-Carlo draw set of a cost estimate: draw ``d`` at
        ``seed + 1000 * d``."""
        return [self.realize(seed + 1000 * d, n_iters) for d in range(n_draws)]


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
        demands=j["demands"],
        sampler_of_worker=sampler_of_worker,
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


def bandwidths(cfg: Dict[str, Any]) -> np.ndarray:
    """[M] NIC speed of each machine, GB/s (ingress = egress)."""
    return np.array([m["bw_GBps"] for m in cfg["cluster"]["machines"]], dtype=np.float64)


@dataclass
class Deployment:
    """One configuration, as the benchmark and as the program see it."""

    name: str
    cfg: Dict[str, Any]
    job: Job
    bw: np.ndarray  # [M] GB/s
    workload: Any  # repro.core.Workload
    cluster: Any  # repro.core.ClusterSpec


def program_cluster(machines: Sequence[Dict[str, float]], bw: Optional[np.ndarray] = None) -> Any:
    from repro.core import ClusterSpec, Machine

    bw = bandwidths({"cluster": {"machines": machines}}) if bw is None else bw
    return ClusterSpec(machines=[
        Machine(
            name=f"m{i}",
            resources={r: float(m[r]) for r in ("mem", "cpu", "gpu")},
            bw_in=float(b), bw_out=float(b),
        )
        for i, (m, b) in enumerate(zip(machines, bw))
    ])


def load_deployment(name: str, path: Path) -> Deployment:
    """Build configuration ``name`` from its file ``path`` and check that
    the program's job is the benchmark's: same tasks, edges, lags, mean
    volumes and exec times."""
    from repro.core import build_gnn_workload

    cfg = json.loads(path.read_text())
    job = build_job(cfg)
    j = cfg["job"]
    vol_s = sampler_volume_gb(j)
    wl = build_gnn_workload(
        n_stores=j["n_stores"], n_workers=j["n_workers"],
        samplers_per_worker=j["samplers_per_worker"], n_ps=j["n_ps"],
        n_iters=j["n_iters"], store_to_sampler_gb=vol_s,
        sampler_to_worker_gb=vol_s, grad_gb=j["grad_gb"],
        store_exec_s=j["store_exec_s"], sampler_exec_s=j["sampler_exec_s"],
        worker_exec_s=j["worker_exec_s"], ps_exec_s=j["ps_exec_s"],
        pmr=j["pmr"], demands=j["demands"],
    )
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
    machines = cfg["cluster"]["machines"]
    return Deployment(
        name=name, cfg=cfg, job=job, bw=bandwidths(cfg), workload=wl,
        cluster=program_cluster(machines),
    )
