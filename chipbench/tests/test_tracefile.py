"""The reduction from a profiler trace to busy time, spans and gaps."""
import lzma
from pathlib import Path

import pytest

from chipbench import tracefile
from chipbench.tracefile import Trace, gaps, intersect, union

DATA = Path(__file__).resolve().parent / "data"


def test_union_intersect_gaps():
    u = union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert intersect(u, [(2, 6), (7, 20)]) == [(2, 3), (5, 6), (7, 8)]
    assert gaps(u, (0, 10)) == [(3, 5), (8, 10)]
    assert gaps(u, (4, 6)) == [(4, 5)]


def test_busy_within_spans_and_labelled_gaps():
    # device ops: [10,20) [15,30) [50,60); spans: op [0,100), engine [5,40) [45,70)
    tr = Trace(
        ops=[("fusion.1", 10, 10), ("while.2", 15, 15), ("fusion.1", 50, 10)],
        spans={"op": [(0, 100)], "engine": [(5, 40), (45, 70)]},
    )
    assert tr.window == (0, 100)
    assert tr.busy_ns() == 30
    assert tr.busy_ns(within="engine") == 30
    assert tr.span_ns("engine") == 60
    assert tr.covered_ns("op", "engine") == 60
    assert tr.top_ops(1) == [("fusion.1", 20e-9)]
    g = tr.idle_gaps({"engine": "engine_host", "op": "commit"}, "harness", 10)
    assert g == [("commit", 40e-9), ("commit", 20e-9), ("engine_host", 10e-9)]


def test_busy_is_averaged_over_devices():
    tr = Trace(ops=[("a", 0, 10), ("b", 0, 10)], spans={"op": [(0, 10)]}, n_devices=2)
    assert tr.busy_ns() == 5


def test_recorded_chip_trace(tmp_path):
    """A trace recorded on a TPU v5e (xz-compressed): two engine calls of
    the testbed job, widths 1 and 4, 15 iterations, each inside an op
    span."""
    pytest.importorskip("jax")
    path = tmp_path / "v5e_engine.xplane.pb"
    path.write_bytes(lzma.decompress((DATA / "v5e_engine.xplane.pb.xz").read_bytes()))
    tr = tracefile.load(path)
    assert tr.n_devices == 1
    assert len(tr.spans["op"]) == 2 and len(tr.spans["engine"]) == 2
    # every device operation of the engine lies inside an engine span
    w = tr.window
    busy = tr.busy_ns()
    assert 0 < busy <= w[1] - w[0]
    assert tr.busy_ns(within="engine") == pytest.approx(busy, rel=0.02)
    assert tr.top_ops(3) and all(s > 0 for _, s in tr.top_ops(3))
    # the reduction's numbers for this trace, pinned
    assert len(tr.ops) == 158001
    assert w == (38225841, 117680511)
    assert busy == 68305790
    assert tr.span_ns("engine") == 79340321
    assert tr.top_ops(1) == [("%while.28", 0.048066788)]
    assert tr.idle_gaps({"engine": "engine_host", "op": "score_host"}, "harness", 1) == [
        ("score_host", 0.005636454)
    ]
