"""Every configuration loads, names its source, and is the program's job."""
import json

import numpy as np
import pytest

from chipbench import deploy
from chipbench.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads_and_names_its_source(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert "2204.11224" in cfg["source"] and "2204.11224" in entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    dep = deploy.load_deployment(entry["name"], ROOT / entry["file"])
    assert dep.workload.J == dep.job.J and dep.cluster.M == len(dep.bw)


def test_papers100m_cluster_is_the_generator_draw():
    cfg = json.loads((ROOT / "chipbench/configs/papers100m-16m.json").read_text())
    d = cfg["cluster"]["draw"]
    drawn = deploy.heterogeneous_machines(
        d["m"], d["seed"], d["mem_range"], d["cpu_range"], d["gpu_range"], d["bw_choices_GBps"]
    )
    assert drawn == cfg["cluster"]["machines"]


def test_job_sizes_are_the_papers():
    tb = deploy.build_job(json.loads((ROOT / "chipbench/configs/testbed-products.json").read_text()))
    pm = deploy.build_job(json.loads((ROOT / "chipbench/configs/papers100m-16m.json").read_text()))
    assert (tb.J, tb.E) == (23, 72) and (pm.J, pm.E) == (117, 1400)


def test_draws_are_fixed_by_the_seed():
    job = deploy.build_job(json.loads((ROOT / "chipbench/configs/testbed-products.json").read_text()))
    a = job.draws(2**31 + 7, 5, 2)
    b = job.draws(2**31 + 7, 5, 2)
    assert all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][0], a[1][0])
