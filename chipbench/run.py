"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout holding ``BENCHMARK.json``.  The cell names a
configuration (``chipbench/configs/<name>.json``) and a traffic mix
(``chipbench/traffic/<name>.json``); the configuration's kind
(``chipbench/deployments/<kind>.py``) builds the deployment.  One run:

1. selects the program's jitted engine (``REPRO_ENGINE_BACKEND=jax``) before
   ``repro`` is imported, and keeps JAX's persistent compilation cache in
   ``.chipbench_cache/jax`` inside the checkout;
2. refuses any platform but a TPU, and fewer chips than the cell asks for;
3. builds the deployment and the traffic's inputs from ``--seed`` and warms
   the engine shapes the traffic uses (all of this is ``setup_s``);
4. runs one caller's closed loop: operations back to back until
   ``--seconds`` have passed; the window ends when the last operation started
   before then completes.  With ``--trace 1`` it runs the traffic's
   ``trace_ops`` operations under the profiler instead, with the
   benchmark's spans around the program's layers;
5. compares a sample of what the window produced with the deployment's
   plain reference and prints each compared number beside its limit;
6. prints one JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``
   (the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
   metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
   ``checks``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, TextIO

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".chipbench_cache"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def prepare_process() -> None:
    """Environment and JAX settings of a benchmark process; before
    ``repro`` is imported."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    os.environ["REPRO_ENGINE_BACKEND"] = "jax"
    import jax

    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform {dev.platform!r} ({dev.device_kind})")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks, default=0))


class CompileCounter:
    """Counts the programs JAX builds (compiled or loaded from the
    persistent cache) and the persistent-cache misses while active."""

    def __init__(self) -> None:
        self.programs = 0
        self.misses = 0
        self.active = False

    def _duration(self, event: str, _secs: float, **_: Any) -> None:
        if self.active and event == COMPILE_EVENT:
            self.programs += 1

    def _event(self, event: str, **_: Any) -> None:
        if self.active and event == CACHE_MISS_EVENT:
            self.misses += 1

    def register(self) -> None:
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def unregister(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


@dataclass
class Window:
    """A closed loop's window: the operations' records and its length."""

    records: List[Any] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    seconds: float = 0.0
    errors: List[str] = field(default_factory=list)

    @property
    def ops(self) -> int:
        return sum(r.ops for r in self.records)

    @property
    def units(self) -> int:
        return sum(r.units for r in self.records)


def closed_loop(
    op: Callable[[], Any], seconds: float, max_ops: Optional[int] = None,
    per_op: int = 1, span: Optional[Callable[[], Any]] = None,
    clock: Callable[[], float] = time.perf_counter,
) -> Window:
    """Send operations back to back until ``seconds`` have passed (or
    ``max_ops`` have been sent).  The window runs from the first start to
    the completion of the last operation started before the deadline; an
    operation that raises counts ``per_op`` failed operations."""
    w = Window()
    sent = 0
    t0 = clock()
    while clock() - t0 < seconds and (max_ops is None or sent < max_ops):
        sent += 1
        w.attempted += per_op
        try:
            if span is None:
                rec = op()
            else:
                with span():
                    rec = op()
            w.records.append(rec)
        except Exception as e:  # a failed operation is counted, the loop goes on
            w.failed += per_op
            w.errors.append(f"{type(e).__name__}: {e}")
    w.seconds = clock() - t0
    return w


def end_to_end(name: str, op: str, w: Window, setup_s: float) -> Optional[float]:
    """The end-to-end metric ``name`` of a window, or None where the cell's
    operation does not produce it."""
    if name == "setup_s":
        return setup_s
    if not w.records:
        return None
    per_unit = {"plan_s": "plan", "replan_s": "replan"}
    if name in per_unit:
        return w.seconds / w.units if op == per_unit[name] else None
    if name == "score_sims_per_s":
        return w.units / w.seconds if op == "score" else None
    raise KeyError(f"no end-to-end metric named {name!r}")


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(metric: str, root: Path) -> Callable[[Any], Optional[float]]:
    """The reader of per-layer metric ``a.b``: ``chipbench/metrics/a.py``'s
    ``read(ctx)``."""
    from chipbench.deploy import load_module

    path = root / "chipbench" / "metrics" / f"{metric.split('.')[0]}.py"
    return load_module(path, f"chipbench_metric_{path.stem}").read


@dataclass
class MetricContext:
    """What a per-layer reader reads: the traced window and its counters."""

    op: str
    ops: int
    records: List[Any]
    trace: Any
    lockstep_iters: int


GAP_LABELS = {"plan": "commit", "replan": "replan_host", "score": "score_host"}


def traced_window(ops: Any, op: str, n: int, seconds: float) -> Any:
    """Run ``n`` operations under the profiler; returns (window, trace,
    spans)."""
    import jax

    from chipbench import spans as spans_mod
    from chipbench import tracefile

    out = CACHE / "trace"
    shutil.rmtree(out, ignore_errors=True)
    sp = spans_mod.Spans()
    sp.install(op)
    try:
        # no Python-call tracing: the spans are the benchmark's annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            w = closed_loop(ops.run, seconds, max_ops=n, per_op=ops.per_op, span=sp.op)
        finally:
            jax.profiler.stop_trace()
    finally:
        sp.uninstall()
    t0 = time.perf_counter()
    tr = tracefile.load(tracefile.find_xplane(out))
    shutil.rmtree(out, ignore_errors=True)
    print(f"trace: {len(tr.ops)} device ops read in {time.perf_counter() - t0!r} s", file=sys.stderr, flush=True)
    return w, tr, sp


def run_cell(
    cell: str, seed: int, seconds: float, trace: bool, *,
    root: Path = ROOT, require_tpu: bool = True, control: bool = False,
    out: TextIO = sys.stdout, err: TextIO = sys.stderr,
) -> Dict[str, Any]:
    """One run of ``cell``; prints and returns the result object.

    ``control`` puts the reference, computed in float32, in the program's
    place for the comparison (the check must then fail)."""
    from chipbench import deploy, ops as ops_mod

    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}[cell]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    device = device_info(int(wl["chips"]), require_tpu)
    dep = deploy.load_deployment(wl["config"], root / cfg_entry["file"], root)
    traffic = json.loads((root / "chipbench" / "traffic" / f"{wl['traffic']}.json").read_text())
    ops = ops_mod.make_ops(dep, traffic, seed)
    ops.warm()
    counter = CompileCounter()
    counter.register()
    setup_s = time.perf_counter() - T_START
    counter.active = True
    tr = sp = None
    if trace:
        w, tr, sp = traced_window(ops, traffic["op"], int(traffic["trace_ops"]), seconds)
    else:
        w = closed_loop(ops.run, seconds, per_op=ops.per_op)
    counter.active = False
    counter.unregister()
    print(
        f"compiles in window: programs={counter.programs} cache_misses={counter.misses} "
        f"ops={w.ops} window_s={w.seconds!r}",
        file=out, flush=True,
    )
    for e in w.errors[:5]:
        print(f"failed operation: {e}", file=err, flush=True)
    device["memory_peak_bytes"] = peak_bytes()

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, cell):
                v = end_to_end(m["name"], traffic["op"], w, setup_s)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        win = tr.window
        device["busy_s"] = tr.busy_ns() / 1e9
        device["window_s"] = (win[1] - win[0]) / 1e9 if win else 0.0
        ctx = MetricContext(op=traffic["op"], ops=w.ops, records=w.records, trace=tr,
                            lockstep_iters=sp.lockstep_iters)
        for m in bench["per_layer"]:
            if applies(m, cell):
                v = load_reader(m["name"], root)(ctx)
                if v is not None and math.isfinite(v):
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        labels = {"engine": "engine_host", "search": "etp_host", "op": GAP_LABELS[traffic["op"]]}
        breakdown = {
            "device_ops": [[n, s] for n, s in tr.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps(labels, "harness", 10)],
        }

    checks = ops.check(w.records, control=control) if w.records else {}
    limits = ops.limits()
    correct = bool(w.records) and w.failed == 0 and set(checks) == set(limits) and all(
        checks[k] <= limits[k] for k in limits
    )
    result: Dict[str, Any] = {
        "correct": correct, "attempted": w.attempted, "failed": w.failed,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # a reading that is no finite number (an answer that never came, or
    # one that is inf or nan) prints as a string: the line stays JSON
    shown = {k: checks[k] if math.isfinite(checks.get(k, math.nan)) else str(checks.get(k))
             for k in limits}
    result["checks"] = {k: {"value": shown[k], "limit": limits[k]} for k in limits}
    for k in limits:
        print(f"check {k}: {shown[k]!r} limit {limits[k]!r}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_process()
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
