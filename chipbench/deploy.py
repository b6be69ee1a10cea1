"""Deployments: a cluster and a training job, built from a configuration file.

A configuration (``chipbench/configs/<name>.json``) holds the numbers of one
deployment: the machines, the statistics the job's traffic is derived from,
and the job's task counts.  Its optional ``"kind"`` (default ``gnn_ps``)
names the module ``chipbench/deployments/<kind>.py`` that builds it: the
job layout, the cluster and the plain reference are the kind's.  A kind's
``load(name, cfg)`` returns a :class:`Deployment`:

* a :class:`Job`, the benchmark's own description of the task/flow DAG and
  its traffic model;
* the program's ``Workload`` and ``ClusterSpec``, built through the public
  constructors of ``repro.core`` and checked against the :class:`Job`, so
  that the reference and the program run the same job;
* everything else the operations (``ops.py``) need of the deployment,
  so that no operation reads the configuration or names a reference
  itself.

A new kind of deployment joins the benchmark through a new kind module, a
configuration that names it, and traffic files: no file of the harness
changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, List, Sequence, Tuple

import numpy as np

DEFAULT_KIND = "gnn_ps"

Draws = Sequence[Tuple[np.ndarray, np.ndarray]]  # [(volumes [E, N], exec times [J, N])]


@dataclass
class Job:
    """Task/flow DAG and traffic model of one job (paper Sec. III).

    Instance ``n`` of an edge carries ``(src, n)``'s output to
    ``(dst, n + lag)``."""

    kinds: List[str]  # [J]
    src: np.ndarray  # [E] int
    dst: np.ndarray  # [E] int
    lag: np.ndarray  # [E] int
    mean_volume: np.ndarray  # [E] GB
    mean_exec: np.ndarray  # [J] s
    fluctuating: np.ndarray  # [E] bool
    pmr: float
    exec_jitter: float

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


@dataclass
class Deployment:
    """One configuration, as the benchmark and as the program see it."""

    name: str
    job: Job
    bw: np.ndarray  # [M] GB/s, each machine's NIC speed
    workload: Any  # repro.core.Workload
    cluster: Any  # repro.core.ClusterSpec
    n_iters: int  # iterations of one whole job draw
    demand: np.ndarray  # [J, R] each task's resource demand
    capacity: np.ndarray  # [M, R] each machine's capacity, same resources
    movable: List[int]  # tasks a placement search may move
    # reference_mean(y, bw, draws, dtype) -> mean makespan of placement y
    # over draws, summed in draw order, computed in dtype
    reference_mean: Callable[[np.ndarray, np.ndarray, Draws, type], float]
    # reference_schedule(y, bw, vol, ex, dtype) -> the reference's schedule
    # (makespan, starts [J, N]) of one draw
    reference_schedule: Callable[[np.ndarray, np.ndarray, np.ndarray, np.ndarray, type], Any]
    # without(m, bw) -> (the program's cluster, NIC speeds) once machine m
    # has left, from NIC speeds bw of the whole cluster
    without: Callable[[int, np.ndarray], Tuple[Any, np.ndarray]]


def load_module(path: Path, name: str) -> ModuleType:
    """Import the Python file ``path`` as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None or not path.is_file():
        raise FileNotFoundError(f"no module file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_deployment(name: str, path: Path, root: Path) -> Deployment:
    """Build configuration ``name`` from its file ``path`` through its kind,
    ``<root>/chipbench/deployments/<kind>.py``."""
    cfg = json.loads(path.read_text())
    kind = cfg.get("kind", DEFAULT_KIND)
    if not re.fullmatch(r"[A-Za-z0-9_]+", str(kind)):
        raise ValueError(f"{name}: kind {kind!r} is not a module name")
    mod = load_module(root / "chipbench" / "deployments" / f"{kind}.py", f"chipbench_kind_{kind}")
    return mod.load(name, cfg)
