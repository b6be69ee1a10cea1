"""Shared fixtures of the benchmark's own tests (run them by path:
``python -m pytest chipbench/tests``; the repository's ``testpaths`` do not
collect them).  Everything here runs on the CPU at tiny sizes."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

TINY_CONFIG = {
    "name": "tiny",
    "source": "a 3-machine toy cluster with a 2-worker job, for tests only",
    "cluster": {"machines": [
        {"mem": 48.0, "cpu": 16.0, "gpu": 2.0, "bw_GBps": 6.25},
        {"mem": 48.0, "cpu": 16.0, "gpu": 2.0, "bw_GBps": 1.25},
        {"mem": 48.0, "cpu": 16.0, "gpu": 2.0, "bw_GBps": 2.5}]},
    "job": {
        "dataset": "toy", "feature_len": 100, "fanout": [5, 10], "unique_factor": 0.8,
        "pmr": 1.16, "exec_jitter": 0.05, "store_exec_s": 0.04, "sampler_exec_s": 0.08,
        "worker_exec_s": 0.15, "ps_exec_s": 0.015, "grad_gb": 0.0013, "batch_size": 2000,
        "n_stores": 2, "n_workers": 2, "samplers_per_worker": 1, "n_ps": 1, "n_iters": 6,
        "demands": {"store": {"mem": 8.0, "cpu": 1.0}, "sampler": {"mem": 7.0, "cpu": 2.0},
                    "worker": {"mem": 3.0, "cpu": 1.0, "gpu": 1.0}, "ps": {"mem": 5.0, "cpu": 1.0}}},
    "assumed": [], "reduced": [],
}
LIMITS = {
    "plan": {"search_cost_rel_err": 1e-9, "commit_rel_err": 1e-9, "certificate_failures": 0},
    "score": {"score_mean_rel_err": 1e-9},
    "replan": {"replan_makespan_rel_err": 1e-9, "replans_declined": 0},
}
TINY_TRAFFIC = {
    "tiny_plan": {"op": "plan", "budget": 8, "sim_iters": 3, "policy": "oes", "realization_seed": 0,
                  "warm_widths": [1, 2, 4], "trace_ops": 1, "check_plans": 2,
                  "limits": LIMITS["plan"]},
    "tiny_score": {"op": "score", "candidates": 4, "draws": 2, "n_iters": 3, "policy": "oes",
                   "pool_ifs": 2, "pool_moves": 4, "trace_ops": 1, "check_candidates": 3,
                   "limits": LIMITS["score"]},
    "tiny_replan": {"op": "replan", "budget": 4, "sim_iters": 3, "drift_threshold": 0.2,
                    "n_segments": 4, "bw_scale_range": [0.25, 1.0], "drift_prob": 0.6,
                    "trigger": "epoch", "warm_migration_flows": [1, 2, 3], "trace_ops": 1,
                    "check_ops": 2, "limits": LIMITS["replan"]},
}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory: the benchmark's code, a BENCHMARK.json
    with one tiny cell per operation, and their config and traffic files."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    for name, t in TINY_TRAFFIC.items():
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for op in ("plan", "score", "replan"):
        bench["workloads"].append({"name": f"tiny.{op}", "config": "tiny",
                                   "traffic": f"tiny_{op}", "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if any(w.split(".")[-1] == op for w in m.get("workloads", [])):
                m["workloads"].append(f"tiny.{op}")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def jax_ready():
    """The benchmark process's JAX settings, with the engine on jax."""
    jax = pytest.importorskip("jax")
    import os
    os.environ["REPRO_ENGINE_BACKEND"] = "jax"
    jax.config.update("jax_enable_compilation_cache", False)
    return jax
