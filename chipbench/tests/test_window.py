"""The closed loop's window and the end-to-end arithmetic."""
import pytest

from chipbench import run
from chipbench.ops import Record


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_ends_at_the_last_operation_started_before_the_deadline():
    clock = FakeClock()

    def op():
        clock.t += 0.4  # each operation takes 0.4 s
        return Record(ops=1, units=3)

    w = run.closed_loop(op, 1.0, clock=clock)
    # starts at 0, 0.4, 0.8 (< 1.0); the third completes at 1.2
    assert len(w.records) == 3 and w.attempted == 3 and w.failed == 0
    assert w.seconds == pytest.approx(1.2)
    assert w.units == 9


def test_rates_are_all_the_work_over_the_whole_window():
    w = run.Window(records=[Record(ops=1, units=64), Record(ops=1, units=64)], seconds=4.0)
    assert run.end_to_end("score_sims_per_s", "score", w, 12.5) == 32.0
    assert run.end_to_end("setup_s", "score", w, 12.5) == 12.5
    assert run.end_to_end("plan_s", "score", w, 12.5) is None
    w = run.Window(records=[Record(ops=2, units=2)] * 3, seconds=3.0)
    assert run.end_to_end("replan_s", "replan", w, 1.0) == 0.5


def test_a_failed_operation_is_counted_and_the_loop_goes_on():
    clock = FakeClock()
    calls = []

    def op():
        clock.t += 0.5
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("boom")
        return Record(ops=2, units=2)

    w = run.closed_loop(op, 1.0, per_op=2, clock=clock)
    assert w.attempted == 4 and w.failed == 2 and len(w.records) == 1
    assert w.errors == ["RuntimeError: boom"]


def test_max_ops_bounds_a_traced_window():
    clock = FakeClock()

    def op():
        clock.t += 0.1
        return Record(ops=1, units=1)

    w = run.closed_loop(op, 100.0, max_ops=2, clock=clock)
    assert len(w.records) == 2 and w.seconds == pytest.approx(0.2)
