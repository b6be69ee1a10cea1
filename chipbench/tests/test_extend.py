"""A configuration, a traffic mix and a per-layer metric are added by new
files and new entries in BENCHMARK.json alone: no file of the harness
changes.  The ``tiny`` configuration and the ``tiny_*`` mixes of
``conftest.tiny_root`` are such additions; this test adds a metric."""
import json
import shutil
import subprocess
import sys

from chipbench.tests.conftest import ROOT
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
