"""Benchmark harness entry: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (plus the framework's own perf
benches).  Prints ``name,us_per_call,derived`` CSV rows.

  fig4            testbed end-to-end: DGTP vs DistDGL (products, reddit)
  fig6/8          products 8-machine sim: batch-size + PMR sweeps, 4 schedulers
  fig7/9          papers100M 16-machine sim: batch-size + PMR sweeps
  competitive     Theorem-1 empirical certificate table
  etp_*           ETP ablation (paper-faithful vs enhanced) + 5-min claim
  etp             batched-vs-scalar planning-loop throughput (bench_etp)
  cache           feature-cache sweeps + cache-aware ETP (bench_cache)
  dynamics        drift-trace re-planning: static vs replan vs oracle,
                  warm-vs-cold evaluations-to-quality (bench_dynamics;
                  ``--smoke`` shrinks budgets to CI size)
  arrivals        multi-tenant arrival streams: service vs EDF/SJF/RR
                  deadline compliance, rejection isolation, tenant-blame
                  conservation, incremental-merge churn (bench_arrivals)
  engine_*        event-engine throughput: numpy vs jitted jax backend
                  across batch width and workload scale (bench_engine;
                  every row asserts makespan parity first)
  obs_*           observability overhead: metrics registry off/on on the
                  engine rows (asserts the <3% off-path pin) + the full
                  record->trace->blame->perfetto pipeline cost (bench_obs)
  attn/ssd/flash  kernel-layer benches (XLA mirrors + interpret allclose)
  roofline_*      summary rows from the dry-run roofline table
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from . import (
    bench_algorithms,
    bench_arrivals,
    bench_cache,
    bench_dynamics,
    bench_engine,
    bench_etp,
    bench_figures,
    bench_kernels,
    bench_obs,
)
from .common import emit, flush_json, set_group, set_json_dir


def roofline_summary():
    try:
        from repro.roofline import full_table
    except Exception:  # pragma: no cover
        return
    cells = [c for c in full_table("pod") if c.status == "run"]
    if not cells:
        emit("roofline", 0.0, "no dry-run artifacts (run repro.launch.dryrun)")
        return
    by_dom = {}
    for c in cells:
        by_dom.setdefault(c.dominant or "n/a", []).append(c)
    emit(
        "roofline_summary",
        0.0,
        " ".join(f"{k}-bound={len(v)}" for k, v in sorted(by_dom.items()))
        + f" cells={len(cells)}",
    )
    for c in cells:
        emit(
            f"roofline_{c.arch}_{c.shape}",
            0.0,
            f"compute={c.compute_s:.3g}s memory={c.memory_s:.3g}s "
            f"collective={c.collective_s:.3g}s dom={c.dominant} "
            f"frac={c.roofline_fraction:.2f} fits={'y' if c.fits else 'N'}",
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None,
        choices=[
            None, "figures", "algorithms", "kernels", "roofline", "etp",
            "cache", "dynamics", "engine", "obs", "arrivals",
        ],
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized budgets (honoured by the dynamics, engine and obs "
        "benches)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write every emitted row to machine-readable "
        "BENCH_<group>.json files under PATH (name, us_per_call, derived, "
        "timestamp, git sha) — CI uploads these as artifacts so the perf "
        "trajectory persists across PRs",
    )
    args = ap.parse_args()
    if args.json:
        set_json_dir(args.json)
    print("name,us_per_call,derived")
    if args.only in (None, "algorithms"):
        set_group("algorithms")
        bench_algorithms.main()
    if args.only in (None, "etp"):
        set_group("etp")
        bench_etp.main()
    if args.only in (None, "engine"):
        set_group("engine")
        bench_engine.main(smoke=args.smoke)
    if args.only in (None, "cache"):
        set_group("cache")
        bench_cache.main()
    if args.only in (None, "dynamics"):
        set_group("dynamics")
        bench_dynamics.main(smoke=args.smoke)
    if args.only in (None, "arrivals"):
        set_group("arrivals")
        bench_arrivals.main(smoke=args.smoke)
    if args.only in (None, "obs"):
        set_group("obs")
        bench_obs.main(smoke=args.smoke)
    if args.only in (None, "kernels"):
        set_group("kernels")
        bench_kernels.main()
    if args.only in (None, "roofline"):
        set_group("roofline")
        roofline_summary()
    if args.only in (None, "figures"):
        set_group("figures")
        bench_figures.main()
    for p in flush_json():
        print(f"wrote {p}", file=sys.stderr)


if __name__ == "__main__":
    from repro.core.engine_jax import enable_compile_cache

    enable_compile_cache()
    main()
