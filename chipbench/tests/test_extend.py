"""A configuration, a traffic mix, a per-layer metric and a kind of
deployment are added by new files and new entries in BENCHMARK.json alone:
no file of the harness changes.  The ``tiny`` configuration and the
``tiny_*`` mixes of ``conftest.tiny_root`` are such additions; these tests
add a metric and two kinds.  The pins hold the refactors of the harness to
the deployments and draws it made before them."""
import copy
import hashlib
import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import deploy, ops as ops_mod
from chipbench.tests.conftest import LIMITS, ROOT, TINY_CONFIG, TINY_TRAFFIC
from chipbench.tests.test_traffic import run_tiny

READER = '''
def read(ctx):
    return float(ctx.lockstep_iters) if ctx.lockstep_iters else None
'''
SILENT = '''
def read(ctx):
    return None
'''


def test_new_metric_needs_only_new_files(tiny_root, jax_ready, tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(tiny_root, root)
    (root / "chipbench" / "metrics" / "lockstep_total.py").write_text(READER)
    (root / "chipbench" / "metrics" / "silent.py").write_text(SILENT)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("lockstep_total.score", "silent.score"):
        bench["per_layer"].append({
            "name": name, "unit": "1", "better": "lower", "source": "program_counter",
            "layer": "device program", "moves": "score_sims_per_s",
            "workloads": ["tiny.score"],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_tiny(root, "tiny.score", trace=True)
    assert res["metrics"]["lockstep_total.score"]["value"] > 0
    assert "silent.score" not in res["metrics"]  # a reader that finds nothing is left out


def test_refuses_a_platform_that_is_not_a_tpu(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "testbed-products.plan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and p.stdout.strip() == ""


def test_needs_the_program_beside_it(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "testbed-products.plan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""


# a GNN job with no parameter server whose workers synchronise over a ring;
# the benchmark side builds its own edge list, the program side is
# build_gnn_workload(sync="allreduce")
TINY_RING = '''
from chipbench.deployments import gnn_ps


def load(name, cfg):
    j = cfg["job"]
    stores = list(range(j["n_stores"]))
    workers = [len(stores) + i for i in range(j["n_workers"])]
    sampler_of_worker = {w: [len(stores) + len(workers) + i] for i, w in enumerate(workers)}
    kinds = ["store"] * len(stores) + ["worker"] * len(workers) + ["sampler"] * len(workers)
    vol = gnn_ps.sampler_volume_gb(j)
    edges = [(g, s, 0, vol / len(stores), True) for w in workers for s in sampler_of_worker[w]
             for g in stores]
    edges += [(s, w, 0, vol, True) for w in workers for s in sampler_of_worker[w]]
    n = len(workers)  # ring all-reduce: each worker sends 2 (n - 1) / n of the gradient
    edges += [(w, workers[(i + 1) % n], 1, (n - 1) / n * j["grad_gb"], False)
              for i, w in enumerate(workers)]
    job = gnn_ps.job_of(cfg, kinds, edges)
    return gnn_ps.deployment(name, cfg, job, gnn_ps.program_workload(cfg, sync="allreduce"))
'''

# the paper's job, with a reference that reads one part in a million high
OFF_REFERENCE = '''
import dataclasses

from chipbench.deployments import gnn_ps


def load(name, cfg):
    dep = gnn_ps.load(name, cfg)
    exact = dep.reference_mean
    return dataclasses.replace(
        dep, reference_mean=lambda y, bw, draws, dtype: exact(y, bw, draws, dtype) * (1 + 1e-6))
'''


def files_of(root):
    """{path: sha256} of every file under ``root`` but caches."""
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts and ".chipbench_cache" not in p.parts
    }


def add_kind(root, kind, source, job_changes):
    """Add kind ``kind`` (module ``source``), a config ``kind`` that names it
    and one cell ``<kind>.<op>`` per operation, as new files and new
    entries in BENCHMARK.json alone."""
    (root / "chipbench" / "deployments" / f"{kind}.py").write_text(source)
    cfg = copy.deepcopy(TINY_CONFIG)
    cfg.update(name=kind, kind=kind)
    cfg["job"].update(job_changes)
    (root / "chipbench" / "configs" / f"{kind}.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": kind, "source": "test", "file": f"chipbench/configs/{kind}.json",
                             "reduced": [], "why": "test"})
    for op in ("plan", "score", "replan"):
        (root / "chipbench" / "traffic" / f"{kind}_{op}.json").write_text(
            json.dumps(TINY_TRAFFIC[f"tiny_{op}"]))
        bench["workloads"].append({"name": f"{kind}.{op}", "config": kind,
                                   "traffic": f"{kind}_{op}", "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if f"tiny.{op}" in m.get("workloads", []):
                m["workloads"].append(f"{kind}.{op}")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def ring_root(tiny_root, tmp_path_factory):
    """A copy of ``tiny_root`` with the kind ``tiny_ring`` added, and the
    digests of the harness's files before the addition."""
    root = tmp_path_factory.mktemp("ring") / "bench"
    shutil.copytree(tiny_root, root)
    harness = files_of(root / "chipbench")
    add_kind(root, "tiny_ring", TINY_RING, {"n_ps": 0, "n_workers": 3})
    return root, harness


@pytest.mark.parametrize("op", ["plan", "score", "replan"])
def test_new_kind_needs_only_new_files(ring_root, jax_ready, op):
    root, harness = ring_root
    dep = deploy.load_deployment("tiny_ring", root / "chipbench/configs/tiny_ring.json", root)
    assert (dep.job.J, dep.job.E) == (8, 12) and "ps" not in dep.job.kinds
    res = run_tiny(root, f"tiny_ring.{op}")
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and "setup_s" in res["metrics"]
    after = files_of(root / "chipbench")
    assert {k: after.get(k) for k in harness} == harness


def test_check_uses_the_kinds_reference(tiny_root, jax_ready, tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(tiny_root, root)
    add_kind(root, "off_reference", OFF_REFERENCE, {})
    res = run_tiny(root, "off_reference.score")
    assert res["correct"] is False
    check = res["checks"]["score_mean_rel_err"]
    assert check["value"] > check["limit"] == LIMITS["score"]["score_mean_rel_err"]


def digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# taken on the harness before deployments had kinds
PINS = {
    "testbed-products": {"job": "92204b5637562e87", "bw": "c4de15c04ce514c3", "program": "92204b5637562e87"},
    "papers100m-16m": {"job": "564c25a50ce61aaf", "bw": "bec058ac7e714129", "program": "564c25a50ce61aaf"},
}
TINY_SCORE_PINS = {"pool": "8935fdd1ce59a288", "picks": "e0fd3c0427e5bd19",
                   "seeds": [463742804, 1249984900, 1278679745]}


@pytest.mark.parametrize("name", sorted(PINS))
def test_paper_deployments_are_pinned(name):
    dep = deploy.load_deployment(name, ROOT / f"chipbench/configs/{name}.json", ROOT)
    j, wl = dep.job, dep.workload
    assert {
        "job": digest(j.src, j.dst, j.lag, j.mean_volume, j.mean_exec, j.fluctuating),
        "bw": digest(dep.bw),
        "program": digest(wl.edge_src, wl.edge_dst, wl.edge_lag, wl.traffic.mean_volume,
                          wl.traffic.mean_exec, wl.traffic.fluctuating),
    } == PINS[name]


def test_score_pool_and_draws_are_pinned(tiny_root, monkeypatch):
    import repro.core as core

    calls = []

    def record(workload, cluster, placements, **kw):
        calls.append(([p.y for p in placements], kw["seed"]))
        return [0.0] * len(placements)

    monkeypatch.setattr(core, "expected_makespan_many", record)
    dep = deploy.load_deployment("tiny", tiny_root / "chipbench/configs/tiny.json", tiny_root)
    ops = ops_mod.ScoreOps(dep, TINY_TRAFFIC["tiny_score"], 2**31 + 11)
    for _ in range(3):
        ops.run()
    assert {
        "pool": digest(*ops.pool),
        "picks": digest(*[y for ys, _ in calls for y in ys]),
        "seeds": [s for _, s in calls],
    } == TINY_SCORE_PINS
