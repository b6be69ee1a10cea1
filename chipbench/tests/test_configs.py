"""Every configuration loads through its kind, names its public source and
what it assumed and reduced, and is the program's job."""
import json
import re

import numpy as np
import pytest

from chipbench import deploy
from chipbench.deployments import gnn_ps
from chipbench.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SOURCE = re.compile(r"https?://\S+|arXiv:\s*\d{4}\.\d{4,5}")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads_and_names_its_source(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    for source in (cfg["source"], entry["source"]):
        assert len(source) <= 200 and SOURCE.search(source), source
    assert isinstance(cfg["assumed"], list)
    assert cfg["reduced"] == entry["reduced"]
    dep = deploy.load_deployment(entry["name"], ROOT / entry["file"], ROOT)
    assert dep.workload.J == dep.job.J and dep.cluster.M == len(dep.bw)
    assert dep.demand.shape[0] == dep.job.J and dep.capacity.shape == (len(dep.bw), dep.demand.shape[1])


def test_papers100m_cluster_is_the_generator_draw():
    cfg = json.loads((ROOT / "chipbench/configs/papers100m-16m.json").read_text())
    d = cfg["cluster"]["draw"]
    drawn = gnn_ps.heterogeneous_machines(
        d["m"], d["seed"], d["mem_range"], d["cpu_range"], d["gpu_range"], d["bw_choices_GBps"]
    )
    assert drawn == cfg["cluster"]["machines"]


def test_job_sizes_are_the_papers():
    tb = gnn_ps.build_job(json.loads((ROOT / "chipbench/configs/testbed-products.json").read_text()))
    pm = gnn_ps.build_job(json.loads((ROOT / "chipbench/configs/papers100m-16m.json").read_text()))
    assert (tb.J, tb.E) == (23, 72) and (pm.J, pm.E) == (117, 1400)


def test_draws_are_fixed_by_the_seed():
    job = gnn_ps.build_job(json.loads((ROOT / "chipbench/configs/testbed-products.json").read_text()))
    a = job.draws(2**31 + 7, 5, 2)
    b = job.draws(2**31 + 7, 5, 2)
    assert all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][0], a[1][0])
