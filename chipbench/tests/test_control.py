"""The comparison that decides ``correct`` fails the control and each fault.

The control is the reference computed in float32 in the program's place.
The faults break the timed path underneath a whole CPU run (the harness's
look for a chip skipped): a batch mean taken over half of the draws, and an
answer altered where it is produced: every makespan the engine returns,
by one part in a million (far below what any user would see and far above
the float64 round-off)."""
import pytest

import repro.core.dgtp as dgtp
import repro.core.engine as engine
import repro.core.engine_jax as engine_jax
from chipbench.tests.test_traffic import run_tiny


@pytest.mark.parametrize("op", ["plan", "score", "replan"])
def test_control_is_not_correct(tiny_root, jax_ready, op):
    res = run_tiny(tiny_root, f"tiny.{op}", control=True)
    assert res["correct"] is False, res["checks"]


def altered_engine(monkeypatch):
    orig = engine_jax.simulate_batch_jax

    def broken(*a, **k):
        out = orig(*a, **k)
        for r in out:
            r.makespan *= 1 + 1e-6
        return out

    monkeypatch.setattr(engine_jax, "simulate_batch_jax", broken)


@pytest.mark.parametrize("op", ["plan", "score", "replan"])
def test_answer_altered_in_the_engine_is_not_correct(tiny_root, jax_ready, monkeypatch, op):
    altered_engine(monkeypatch)
    res = run_tiny(tiny_root, f"tiny.{op}")
    assert res["correct"] is False, res["checks"]


def test_answer_altered_in_the_commit_is_not_correct(tiny_root, jax_ready, monkeypatch):
    orig = dgtp.simulate

    def broken(*a, **k):
        out = orig(*a, **k)
        out.makespan *= 1 + 1e-6
        return out

    monkeypatch.setattr(dgtp, "simulate", broken)
    res = run_tiny(tiny_root, "tiny.plan")
    assert res["correct"] is False
    assert res["checks"]["commit_rel_err"]["value"] > res["checks"]["commit_rel_err"]["limit"]


def test_half_of_the_batch_left_out_is_not_correct(tiny_root, jax_ready, monkeypatch):
    orig = engine.mean_batch_makespans

    def broken(workload, cluster, groups, *a, **k):
        half = [(p, list(r)[: max(1, len(r) // 2)]) for p, r in groups]
        return orig(workload, cluster, half, *a, **k)

    monkeypatch.setattr(engine, "mean_batch_makespans", broken)
    res = run_tiny(tiny_root, "tiny.score")
    assert res["correct"] is False
