"""Compile rehearsal: every cell's engine shapes compile for a TPU v5e.

The TPU compiler is installed where no chip is attached and compiles for a
described ``v5e:2x2`` topology.  Each cell's ``warm`` drives the program's
``simulate_batch`` exactly as set-up does on the chip, with the engine's
runner patched so that its first call lowers on shapes bound to one
described chip and compiles instead of running.  Nothing runs, so no time
and no result is checked here.  The topology is described inside a fixture
(one process at a time may load the TPU library); keep these tests in this
one file.
"""
import json

import numpy as np
import pytest

from chipbench import deploy, ops as ops_mod
from chipbench.tests.conftest import ROOT

jax = pytest.importorskip("jax")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
HBM_BYTES = 16 * 1024**3


class _Compiled(Exception):
    pass


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compile_for_chip(one_chip, jax_ready, monkeypatch):
    import repro.core as core
    from jax.experimental.compilation_cache import compilation_cache
    from repro.core import engine_jax

    compiled = []
    build = engine_jax._build_runner

    def build_for_chip(**kw):
        fn = build(**kw)

        def lower_and_compile(*args):
            shapes = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=one_chip)
                      for a in args]
            compiled.append(fn.lower(*shapes).compile())
            raise _Compiled

        return lower_and_compile

    orig = core.simulate_batch

    def tolerant(*a, **k):
        try:
            return orig(*a, **k)
        except _Compiled:
            return None

    monkeypatch.setattr(engine_jax, "_build_runner", build_for_chip)
    monkeypatch.setattr(engine_jax, "_RUNNERS", {})
    monkeypatch.setattr(core, "simulate_batch", tolerant)
    compilation_cache.reset_cache()
    yield compiled
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_shapes_compile_for_v5e(cell, compile_for_chip):
    cfg = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    dep = deploy.load_deployment(cell["config"], ROOT / cfg["file"])
    traffic = json.loads((ROOT / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    if traffic["op"] == "replan":  # the fewest and the most migration columns
        traffic["warm_migration_flows"] = [min(traffic["warm_migration_flows"]),
                                           max(traffic["warm_migration_flows"])]
    expected = {"plan": len(traffic.get("warm_widths", [])), "score": 1, "replan": 6}[traffic["op"]]
    ops = ops_mod.make_ops(dep, traffic, seed=1)
    ops.warm()
    assert len(compile_for_chip) == expected
    for c in compile_for_chip:
        assert "tpu_custom_call" not in c.as_text()
        mem = c.memory_analysis()
        if mem is not None:
            used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            assert used < HBM_BYTES
