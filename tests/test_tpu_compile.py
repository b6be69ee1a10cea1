"""Compile rehearsal: the jitted event engine compiles for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described ``v5e:2x2`` topology.  Each test drives
``simulate_batch_jax`` on the same inputs ``chip_smoke.py`` runs on the
chip, with ``engine_jax._build_runner`` patched so that the runner's
first call lowers on ``ShapeDtypeStruct``s bound to one described chip
and compiles, instead of running.  A compile error here is what the chip
would raise.  Nothing runs, so results and times are not checked.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  Keep these tests in this one file for the same reason.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import chip_smoke
from repro.core import engine_jax
from repro.core.engine_jax import _INSTRUCTION, JAX_POLICIES, simulate_batch_jax

HBM_BYTES = 16 * 1024**3  # one TPU v5e chip


class _Compiled(Exception):
    """Raised by the patched runner once its program has compiled."""


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside the tree
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here: nothing to rehearse
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compile_for_chip(one_chip, monkeypatch):
    """Make every new engine runner compile for the described chip on its
    first call (then raise ``_Compiled``); yields the compiled programs.
    The persistent cache is off meanwhile: an entry compiled for a chip
    that is not attached cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    compiled = []
    build = engine_jax._build_runner

    def build_for_chip(**kw):
        fn = build(**kw)

        def lower_and_compile(*args):
            shapes = [
                jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                     sharding=one_chip)
                for a in args
            ]
            compiled.append(fn.lower(*shapes).compile())
            raise _Compiled

        return lower_and_compile

    monkeypatch.setattr(engine_jax, "_build_runner", build_for_chip)
    monkeypatch.setattr(engine_jax, "_RUNNERS", {})
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield compiled
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _check(compiled):
    (exe,) = compiled
    mem = exe.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES
    # the engine is float64 end to end: Mosaic takes no float64 operands,
    # so no Pallas kernel may sit in its program
    assert "tpu_custom_call" not in exe.as_text()


@pytest.fixture(scope="module")
def testbed():
    return chip_smoke.testbed_inputs()


@pytest.mark.parametrize("case", range(len(JAX_POLICIES) + 1),
                         ids=list(JAX_POLICIES) + ["oes-deadline-trace-migrations"])
def test_testbed_runner_compiles_for_v5e(testbed, compile_for_chip, case):
    """Paper testbed job, width 64: the five policies unshaped, then the
    deadline-shaped drift-trace + migration case."""
    wl, cluster, placements, reals, cases = testbed
    policy, kw = cases[case]
    with pytest.raises(_Compiled):
        simulate_batch_jax(wl, cluster, placements, reals, policy=policy,
                           record=True, **kw)
    _check(compile_for_chip)


def test_testbed_commit_runner_compiles_for_v5e(compile_for_chip):
    """plan()'s commit on the testbed: one placement over 60 iterations,
    width 1, with task and flow spans recorded."""
    from repro.core import ifs_placement, simulate, testbed_cluster
    from repro.core.profiles import OGBN_PRODUCTS

    wl = chip_smoke.testbed_job(OGBN_PRODUCTS, n_iters=60)
    cluster = testbed_cluster()
    with pytest.raises(_Compiled):
        simulate(wl, cluster, ifs_placement(wl, cluster, seed=0),
                 wl.realize(seed=0), policy="oes", record=True, backend="jax")
    _check(compile_for_chip)


def test_papers100m_wide_runner_compiles_for_v5e(compile_for_chip):
    """§VI-B papers100M job (16 machines, J=117, E=1400), oes, width 1024.
    Its program holds no per-element gather (slice sizes all 1): on a
    v5e two of them took 93% of this job's device time."""
    wl, cluster, placements, reals = chip_smoke.papers100m_inputs()
    assert len(placements) == 1024
    with pytest.raises(_Compiled):
        simulate_batch_jax(wl, cluster, placements, reals, policy="oes",
                           record=True)
    _check(compile_for_chip)
    (exe,) = compile_for_chip
    assert not re.search(r"slice_sizes=\{1(,1)*\}", exe.as_text())
    # the oes filling rounds run on [B, M, M] NIC-pair counts: no
    # instruction of the filling loop's body reads or writes a per-flow
    # array (a dimension of E = 1400 flows; no other axis is that long)
    E = wl.E
    assert E not in (len(placements), cluster.M, wl.J, reals[0].n_iters)
    body = [
        line for name, line in _INSTRUCTION.findall(exe.as_text())
        if "/rate_solve/while/body/" in engine_jax._op_names(f"{name} = {line}")[name]
    ]
    assert body
    for line in body:
        dims = [int(d) for shape in re.findall(r"\w\[([\d,]+)\]", line.split(", metadata=")[0])
                for d in shape.split(",")]
        assert E not in dims, line
