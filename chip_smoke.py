"""Bring-up smoke run of the scheduling engine on one TPU chip.

Run from the repository root on a machine with a TPU::

    python chip_smoke.py

It drives the jitted event engine (``repro.core.engine_jax``) through the
entry points a user calls and checks each result against the numpy
reference engine at the pinned ``PARITY_RTOL`` / ``PARITY_ATOL``:

  (a) engine parity on the paper's §VI-A testbed job: 64 IFS placements x
      realizations under all five rate policies, plus one deadline-shaped
      case with a bandwidth-drift trace and migration flows;
  (b) ``plan(..., backend="jax")`` on the products and reddit testbed jobs
      (the ``bench_figures.fig4_testbed_end2end`` shape);
  (c) the §VI-B papers100M job on 16 machines at batch width 1024;
  (d) a warm ``Replanner`` re-plan after drift and a machine leave.

Every input is built from seeds.  Each phase prints one line with the
wall seconds of its first call (compilation included) and of its second
call (warm, ending in host arrays), and its largest relative error
against numpy.  The last line is one JSON object naming the device.  The
script refuses to run anywhere but a TPU: there is no CPU fallback.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, List, Tuple

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np

WIDTH = 64  # phase (a) batch width
WIDE = 1024  # phase (c) batch width


def _twice(fn: Callable[[], Any]) -> Tuple[Any, float, float]:
    """(second call's result, first-call seconds, second-call seconds)."""
    t0 = time.perf_counter()
    fn()
    t1 = time.perf_counter()
    out = fn()
    return out, t1 - t0, time.perf_counter() - t1


def _rel_err(ref: Any, got: Any, what: str) -> float:
    """Largest relative error of ``got`` against ``ref``; raises unless
    they agree at the engine's pinned parity tolerance (nan = task not
    recorded, which must match exactly)."""
    from repro.core.engine_jax import PARITY_ATOL, PARITY_RTOL

    ref = np.asarray(ref, dtype=np.float64)
    got = np.asarray(got, dtype=np.float64)
    nan = np.isnan(ref)
    if ref.shape != got.shape or not np.array_equal(nan, np.isnan(got)):
        raise AssertionError(f"{what}: recorded entries differ")
    r, g = ref[~nan], got[~nan]
    err = float(np.max(np.abs(g - r) / np.maximum(np.abs(r), PARITY_ATOL), initial=0.0))
    if not np.allclose(g, r, rtol=PARITY_RTOL, atol=PARITY_ATOL):
        raise AssertionError(f"{what}: parity broken, max rel err {err:.3e}")
    return err


def _schedule_err(wl: Any, n_iters: int, refs: List[Any], gots: List[Any], what: str) -> float:
    err = 0.0
    for b, (ref, got) in enumerate(zip(refs, gots)):
        err = max(
            err,
            _rel_err(ref.makespan, got.makespan, f"{what}[{b}] makespan"),
            _rel_err(
                ref.task_start_matrix(wl.J, n_iters),
                got.task_start_matrix(wl.J, n_iters),
                f"{what}[{b}] task starts",
            ),
        )
    return err


def _report(phase: str, first_s: float, run_s: float, err: float, extra: str = "") -> None:
    print(
        f"phase {phase}: first_call_s={first_s!r} run_s={run_s!r} "
        f"max_rel_err={err!r}" + (f" {extra}" if extra else ""),
        flush=True,
    )


def testbed_job(profile: Any, n_iters: int) -> Any:
    from repro.core.profiles import build_workload_from_profile

    return build_workload_from_profile(
        profile, n_stores=4, n_workers=6, samplers_per_worker=2, n_ps=1,
        n_iters=n_iters,
    )


def testbed_inputs(width: int = WIDTH) -> Tuple[Any, Any, List[Any], List[Any], List[Tuple[str, dict]]]:
    """Phase (a)'s batch: the products testbed job, ``width`` IFS placements
    x realizations, and its cases — the five policies unshaped, then oes
    deadline-shaped under a drift trace with migration flows."""
    from repro.core import MigrationFlow, ifs_placement, simulate, testbed_cluster
    from repro.core.engine_jax import JAX_POLICIES
    from repro.core.profiles import OGBN_PRODUCTS
    from repro.dynamics import drift_trace

    wl = testbed_job(OGBN_PRODUCTS, n_iters=15)
    cluster = testbed_cluster()
    placements = [ifs_placement(wl, cluster, seed=s) for s in range(width)]
    reals = [wl.realize(seed=s) for s in range(width)]
    horizon = simulate(wl, cluster, placements[0], reals[0]).makespan * 1.5
    trace = drift_trace(
        cluster, horizon_s=horizon, n_segments=6, seed=0,
        bw_scale_range=(0.25, 1.0),
    )
    y = placements[0].y
    # heterogeneous per-instance flow sets (the test suite's matrix shape):
    # gated with a tight deadline + ungated background, none, gated loose
    patterns = [
        [
            MigrationFlow(src=int((y[0] + 1) % cluster.M), dst=int(y[0]),
                          gb=1.2, task=0, deadline=0.5),
            MigrationFlow(src=0, dst=1, gb=0.5),
        ],
        None,
        [MigrationFlow(src=1, dst=0, gb=0.8, task=wl.J - 1, deadline=3.0)],
    ]
    migrations = [patterns[b % 3] for b in range(width)]
    cases: List[Tuple[str, dict]] = [(pol, {}) for pol in JAX_POLICIES]
    cases.append(("oes", dict(shaping="deadline", trace=trace, migrations=migrations)))
    return wl, cluster, placements, reals, cases


def papers100m_inputs() -> Tuple[Any, Any, List[Any], List[Any]]:
    """Phase (c)'s batch: the §VI-B papers100M job on 16 machines, WIDE
    instances (16 IFS placements x 64 realizations; one IFS placement of
    this job takes about half a second on the host)."""
    from benchmarks.common import feasible_cluster
    from repro.core import ifs_placement
    from repro.core.profiles import OGBN_PAPERS100M, build_workload_from_profile

    wl = build_workload_from_profile(
        OGBN_PAPERS100M, n_stores=16, n_workers=20, samplers_per_worker=4,
        n_ps=1, n_iters=10,
    )
    cluster = feasible_cluster(16, wl, seed0=1)
    distinct = [ifs_placement(wl, cluster, seed=s) for s in range(16)]
    placements = [distinct[b % 16] for b in range(WIDE)]
    reals = [wl.realize(seed=b // 16) for b in range(WIDE)]
    return wl, cluster, placements, reals


def phase_parity() -> None:
    """(a) 5 policies + a deadline-shaped drift/migration case, width 64."""
    from repro.core import simulate_batch
    from repro.core.engine_jax import simulate_batch_jax

    wl, cluster, placements, reals, cases = testbed_inputs()
    first = run = err = 0.0
    for pol, kw in cases:
        got, f, r = _twice(lambda: simulate_batch_jax(
            wl, cluster, placements, reals, policy=pol, record=True, **kw
        ))
        ref = simulate_batch(
            wl, cluster, placements, reals, policy=pol, record=True,
            backend="numpy", **kw,
        )
        first, run = first + f, run + r
        err = max(err, _schedule_err(wl, 15, ref, got, f"(a) {pol} {kw.get('shaping')}"))
    _report("a engine_parity", first, run, err, f"cases={len(cases)} width={WIDTH}")


def phase_plan() -> None:
    """(b) plan() on the jax backend for the products and reddit jobs."""
    from repro.core import monte_carlo_draws, plan, plan_baseline, simulate_batch, testbed_cluster
    from repro.core.profiles import OGBN_PRODUCTS, REDDIT

    cluster = testbed_cluster()
    first = run = err = 0.0
    parts = []
    for profile in (OGBN_PRODUCTS, REDDIT):
        wl = testbed_job(profile, n_iters=60)
        out, f, r = _twice(lambda: plan(
            wl, cluster, backend="jax", budget=400, sim_iters=15, seed=0,
        ))
        first, run = first + f, run + r
        if not out.certificate.holds:
            raise AssertionError(f"(b) {profile.name}: chain certificate fails")
        # re-score the chosen placement on numpy with the winning chain's
        # own draws: the search's best cost came from the device
        etp = out.etp
        win = min(etp.chain_stats, key=lambda c: c["best_makespan"])
        reals = monte_carlo_draws(wl, seed=win["seed"], n_iters=15, n_draws=1)
        ref = simulate_batch(
            wl, cluster, [etp.placement] * len(reals), reals, backend="numpy",
        )
        ref_mean = sum(x.makespan for x in ref) / len(ref)
        err = max(err, _rel_err(ref_mean, etp.best_makespan, f"(b) {profile.name} search cost"))
        base = plan_baseline(wl, cluster, baseline="distdgl")
        parts.append(
            f"{profile.name}_makespan={out.schedule.makespan!r} "
            f"{profile.name}_distdgl={base.schedule.makespan!r}"
        )
    _report("b plan", first, run, err, " ".join(parts))


def phase_papers100m() -> None:
    """(c) §VI-B papers100M on 16 machines, oes, width 1024."""
    from repro.core import simulate_batch
    from repro.core.engine_jax import simulate_batch_jax

    wl, cluster, placements, reals = papers100m_inputs()
    got, first, run = _twice(lambda: simulate_batch_jax(
        wl, cluster, placements, reals, policy="oes", record=True,
    ))
    # the numpy lock-step batch engine is bit-identical to 16 scalar
    # simulate() calls (tests/test_batch_engine.py) at a seventh of the
    # host time
    check = np.linspace(0, WIDE - 1, 16).astype(int)
    refs = simulate_batch(
        wl, cluster, [placements[b] for b in check], [reals[b] for b in check],
        policy="oes", record=True, backend="numpy",
    )
    err = _schedule_err(wl, 10, refs, [got[b] for b in check], "(c) papers100M")
    _report("c papers100m", first, run, err, f"J={wl.J} E={wl.E} width={WIDE} checked={len(check)}")


def phase_replan() -> None:
    """(d) warm Replanner re-plans (drift, then a machine leave) on jax."""
    from repro.core import ifs_placement, monte_carlo_draws, simulate, simulate_batch, testbed_cluster
    from repro.core.profiles import OGBN_PRODUCTS, build_workload_from_profile
    from repro.dynamics import ReplanConfig, Replanner, drift_trace
    from repro.dynamics.traces import relative_bw_drift

    # benchmarks/bench_dynamics.py's drift testbed: 5 intervals x 10 iters
    wl = build_workload_from_profile(
        OGBN_PRODUCTS, n_stores=4, n_workers=4, samplers_per_worker=2,
        n_ps=1, n_iters=50,
    )
    cluster = testbed_cluster()
    p0 = ifs_placement(wl, cluster, seed=0)
    undisturbed = simulate(wl, cluster, p0, wl.realize(seed=0, n_iters=50)).makespan
    trace = drift_trace(
        cluster, horizon_s=undisturbed * 1.5, n_segments=10, seed=0,
        bw_scale_range=(0.25, 1.0),
    )
    cfg = ReplanConfig(budget=60, sim_iters=10, drift_threshold=0.2, backend="jax")
    # re-plan at the trace's most drifted segment, then lose machine 3
    drifted = int(np.argmax([
        relative_bw_drift(cluster.bw_in, cluster.bw_out, bw_in, bw_out)
        for bw_in, bw_out in zip(trace.bw_in, trace.bw_out)
    ]))

    def run_once() -> Replanner:
        rp = Replanner(wl, cluster, p0.copy(), config=cfg)
        rp.observe(trace.bw_in[drifted], trace.bw_out[drifted], trigger="epoch")
        rp.on_leave(3)
        return rp

    rp, first, run = _twice(run_once)
    for rec in rp.records:
        vals = (rec.makespan, rec.objective, rec.overlap_s)
        if not np.all(np.isfinite(vals)):
            raise AssertionError(f"(d) {rec.trigger}: non-finite record {vals}")
    # the committed post-leave placement, re-scored on numpy with the
    # re-planner's own draws, must match the device's clean makespan
    leave = rp.records[-1]
    reals = monte_carlo_draws(wl, seed=cfg.seed, n_iters=cfg.sim_iters, n_draws=cfg.sim_draws)
    ref = simulate_batch(wl, rp.cluster, [rp.placement] * len(reals), reals, backend="numpy")
    err = _rel_err(sum(r.makespan for r in ref) / len(ref), leave.makespan, "(d) leave makespan")
    _report(
        "d replan", first, run, err,
        f"triggers={[r.trigger for r in rp.records]} replanned={[r.replanned for r in rp.records]} "
        f"leave_makespan={leave.makespan!r} leave_overlap_s={leave.overlap_s!r} "
        f"moved={leave.moved_tasks}",
    )


def main() -> int:
    import jax

    from repro.core.engine_jax import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    count = len(jax.devices())
    print(
        f"jax {jax.__version__} platform={dev.platform} kind={dev.device_kind} "
        f"count={count} compile_cache={cache}",
        flush=True,
    )
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); there is no CPU fallback",
            file=sys.stderr,
        )
        return 2
    phase_parity()
    phase_plan()
    phase_papers100m()
    phase_replan()
    print(json.dumps(
        {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count}}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
