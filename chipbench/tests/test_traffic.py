"""Each traffic driver at a tiny size, through a whole CPU run of its cell."""
import io
import json

import pytest

from chipbench import run


def run_tiny(root, cell, trace=False, **kw):
    out, err = io.StringIO(), io.StringIO()
    res = run.run_cell(cell, 2**31 + 11, 0.5, trace, root=root, require_tpu=False,
                       out=out, err=err, **kw)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    assert "compiles in window" in out.getvalue()
    return res


@pytest.mark.parametrize("op", ["plan", "score", "replan"])
def test_driver_runs_and_is_correct(tiny_root, jax_ready, op):
    res = run_tiny(tiny_root, f"tiny.{op}")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_prints_breakdown(tiny_root, jax_ready):
    res = run_tiny(tiny_root, "tiny.plan", trace=True)
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the host spans read on the CPU too; device metrics need a device plane
    assert "commit_ms.plan" in res["metrics"] and "etp_host_ms.plan" in res["metrics"]
