"""The settle share on synthetic traces: innermost time only, names that
the runners dispute, and nothing without the program's map."""
import pytest

from chipbench.run import load_reader
from chipbench.tests.conftest import ROOT
from chipbench.tests.test_readers import ctx_of
from chipbench.tracefile import Trace

SETTLE = "jit(run)/while/body/settle/x"
SOLVE = "jit(run)/while/body/advance/rate_solve/x"


@pytest.fixture()
def settle_share(monkeypatch):
    """The reader, with the program's instruction map replaced by ``scopes``."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    def use(scopes):
        monkeypatch.setattr(engine_jax, "runner_scopes", lambda: scopes, raising=False)
        return load_reader("settle_share.score", ROOT)

    return use


def test_settle_share_counts_innermost_time_only(settle_share):
    # the loop [0,100) encloses settle's fusion [10,40), which encloses its
    # nested volume lookup [15,25), and the rate solve's op [50,70); a
    # settle op at [120,130) lies outside every engine span
    tr = Trace(
        ops=[("%while.1", 0, 100), ("%fusion.2", 10, 30), ("%select_reduce_fusion.7", 15, 10),
             ("%fusion.4", 50, 20), ("%fusion.2", 120, 10)],
        spans={"op": [(0, 200)], "engine": [(0, 110)]},
    )
    read = settle_share({"r1": {
        "%while.1": "jit(run)/while",
        "%fusion.2": SETTLE,
        "%select_reduce_fusion.7": "jit(run)/while/body/settle/pick_iter",
        "%fusion.4": SOLVE,
    }})
    assert read(ctx_of(tr, op="score")) == pytest.approx(30.0)  # (20 + 10) of 100 busy


def test_settle_share_names_disputed_by_runners_count_for_none(settle_share):
    tr = Trace(ops=[("%fusion.4", 0, 10), ("%fusion.5", 10, 10)],
               spans={"op": [(0, 20)], "engine": [(0, 20)]})
    read = settle_share({
        "r1": {"%fusion.4": SETTLE, "%fusion.5": SETTLE},
        "r2": {"%fusion.4": SOLVE, "%fusion.5": SETTLE},
    })
    assert read(ctx_of(tr, op="score")) == pytest.approx(50.0)


def test_settle_share_reads_nothing_without_the_program_map(monkeypatch):
    pytest.importorskip("jax")
    from repro.core import engine_jax

    monkeypatch.delattr(engine_jax, "runner_scopes")
    tr = Trace(ops=[("%fusion.4", 0, 10)], spans={"op": [(0, 20)], "engine": [(0, 20)]})
    assert load_reader("settle_share.score", ROOT)(ctx_of(tr, op="score")) is None
