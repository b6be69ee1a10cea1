"""ETP planning-loop benches on the CPU.

  * ``simulate_batch`` at widths 1-32 (events/sec per width); on the numpy
    backend a batch is the reference ``simulate`` run instance by
    instance, so the rows track the reference's per-event cost, and
  * end-to-end ``plan()`` wall time.

CPU timings, not speed figures: ``chipbench/`` measures the jitted engine
on the chip.

Run: ``PYTHONPATH=src python -m benchmarks.bench_etp``
"""
from __future__ import annotations

from .common import Timer, emit  # noqa: F401 (inserts src/ into sys.path)

from repro.core import plan, simulate_batch
from repro.core.cluster import testbed_cluster
from repro.core.placement import ifs_placement
from repro.core.profiles import OGBN_PRODUCTS, build_workload_from_profile


def paper_job(n_iters: int = 12):
    return build_workload_from_profile(
        OGBN_PRODUCTS, n_stores=4, n_workers=6, samplers_per_worker=2,
        n_ps=1, n_iters=n_iters,
    )


def batch_width_scaling():
    """Raw engine throughput vs batch width (events/sec per instance)."""
    wl = paper_job()
    cluster = testbed_cluster()
    reals = [wl.realize(seed=s) for s in range(32)]
    placements = [ifs_placement(wl, cluster, seed=s) for s in range(32)]
    simulate_batch(wl, cluster, placements[:2], reals[:2])  # warm
    base = None
    for width in (1, 4, 8, 16, 32):
        with Timer() as t:
            res = simulate_batch(
                wl, cluster, placements[:width], reals[:width], policy="oes"
            )
        events = sum(r.n_events for r in res)
        eps = events / t.dt
        if width == 1:
            base = eps
        emit(
            f"simulate_batch_w{width}", t.us,
            f"events_per_s={eps:.0f} vs_w1={eps/base:.2f}x",
        )


def plan_wall_time(budget: int = 400):
    """End-to-end DGTP plan() (search + schedule + certificate)."""
    wl = paper_job(n_iters=15)
    cluster = testbed_cluster()
    with Timer() as t:
        p = plan(wl, cluster, budget=budget, sim_iters=15, seed=0)
    emit(
        "plan_end_to_end", t.us,
        f"budget={budget} wall={t.dt:.1f}s makespan={p.schedule.makespan:.2f}s "
        f"certificate_holds={p.certificate.holds}",
    )


def main():
    batch_width_scaling()
    plan_wall_time()


if __name__ == "__main__":
    main()
