"""The per-layer readers: pinned values on the recorded v5e trace, and the
rate-solve share on synthetic traces."""
import lzma
from pathlib import Path

import pytest

from chipbench import tracefile
from chipbench.ops import Record
from chipbench.run import MetricContext, load_reader
from chipbench.tracefile import Trace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

# every reader that existed before the program had spans, on the recorded
# trace (two engine calls inside op spans), as each operation kind
PINNED = {
    "plan": {"commit_ms": 39.72173, "device_idle_share": 14.031749172200957,
             "device_us_per_iter": 37.94766111111111, "engine_host_ms": 5.5172655,
             "etp_host_ms": 0.0, "plan_makespan_s": 29.5, "replan_host_ms": None},
    "score": {"commit_ms": None, "device_idle_share": 14.031749172200957,
              "device_us_per_iter": 37.94766111111111, "engine_host_ms": 5.5172655,
              "etp_host_ms": None, "plan_makespan_s": None, "replan_host_ms": None},
    "replan": {"commit_ms": None, "device_idle_share": 14.031749172200957,
               "device_us_per_iter": 37.94766111111111, "engine_host_ms": 5.5172655,
               "etp_host_ms": None, "plan_makespan_s": None, "replan_host_ms": 0.0515695},
}


def ctx_of(trace, op="plan", ops=2):
    return MetricContext(op=op, ops=ops, records=[Record(ops=1, units=1, data={"makespan": 29.5})] * 2,
                         trace=trace, lockstep_iters=1800)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    pytest.importorskip("jax")
    path = tmp_path_factory.mktemp("trace") / "v5e_engine.xplane.pb"
    path.write_bytes(lzma.decompress((DATA / "v5e_engine.xplane.pb.xz").read_bytes()))
    return tracefile.load(path)


@pytest.mark.parametrize("op", sorted(PINNED))
def test_existing_readers_pinned_on_recorded_trace(recorded, op):
    got = {name: load_reader(name, ROOT)(ctx_of(recorded, op)) for name in PINNED[op]}
    assert got == PINNED[op]


@pytest.fixture()
def rate_solve_share(monkeypatch):
    """The reader, with the program's instruction map replaced by ``scopes``."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    def use(scopes):
        monkeypatch.setattr(engine_jax, "runner_scopes", lambda: scopes, raising=False)
        return load_reader("rate_solve_share.plan", ROOT)

    return use


def test_rate_solve_share_counts_innermost_time_only(rate_solve_share):
    # loop [0,100) encloses settle [10,30) and the rate solve's while [40,90),
    # which encloses its body op [50,60); a rate-solve op at [120,130) lies
    # outside every engine span
    tr = Trace(
        ops=[("%while.1", 0, 100), ("%fusion.2", 10, 20), ("%while.3", 40, 50),
             ("%fusion.4", 50, 10), ("%fusion.4", 120, 10)],
        spans={"op": [(0, 200)], "engine": [(0, 110)]},
    )
    read = rate_solve_share({"r1": {
        "%while.1": "jit(run)/while",
        "%fusion.2": "jit(run)/while/body/settle/add",
        "%while.3": "jit(run)/while/body/advance/rate_solve/while",
        "%fusion.4": "jit(run)/while/body/advance/rate_solve/while/body/mul",
    }})
    assert read(ctx_of(tr)) == pytest.approx(50.0)  # (40 + 10) of 100 busy


def test_rate_solve_share_names_disputed_by_runners_count_for_none(rate_solve_share):
    tr = Trace(ops=[("%fusion.4", 0, 10), ("%fusion.5", 10, 10)],
               spans={"op": [(0, 20)], "engine": [(0, 20)]})
    solve = "jit(run)/while/body/advance/rate_solve/x"
    read = rate_solve_share({
        "r1": {"%fusion.4": solve, "%fusion.5": solve},
        "r2": {"%fusion.4": "jit(run)/while/body/settle/x", "%fusion.5": solve},
    })
    assert read(ctx_of(tr)) == pytest.approx(50.0)


def test_rate_solve_share_reads_each_call_against_its_own_runner(rate_solve_share):
    # two runners reuse %fusion.4 for different phases; each call's other
    # op names say which runner it ran
    tr = Trace(
        ops=[("%fusion.4", 0, 40), ("%fusion.9", 40, 60),
             ("%fusion.4", 200, 60), ("%fusion.8", 260, 40)],
        spans={"op": [(0, 300)], "engine": [(0, 100), (200, 300)]},
    )
    solve, settle = "jit(run)/while/body/advance/rate_solve/x", "jit(run)/while/body/settle/x"
    read = rate_solve_share({
        "r1": {"%fusion.4": solve, "%fusion.9": settle},
        "r2": {"%fusion.4": settle, "%fusion.8": solve},
    })
    assert read(ctx_of(tr)) == pytest.approx(40.0)  # (40 + 40) of 200 busy


def test_rate_solve_share_reads_nothing_without_the_program_map(monkeypatch):
    pytest.importorskip("jax")
    from repro.core import engine_jax

    monkeypatch.delattr(engine_jax, "runner_scopes")
    tr = Trace(ops=[("%fusion.4", 0, 10)], spans={"op": [(0, 20)], "engine": [(0, 20)]})
    assert load_reader("rate_solve_share.plan", ROOT)(ctx_of(tr)) is None
