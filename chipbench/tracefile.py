"""Reduction of a profiler trace to device busy time, spans and idle gaps.

The JAX profiler writes an ``.xplane.pb`` file.  From it this module takes:

* the device's operations: the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, each with its name, start and duration;
* the benchmark's spans: host events named ``chipbench.<span>`` (written by
  ``spans.Spans`` through ``TraceAnnotation``), on the trace's own clock.

Busy time is the union of the operations' intervals; busy time within a span
is that union intersected with the span's intervals.  The traced window runs
from the first ``chipbench.op`` span's start to the last one's end.  Every
quantity is in nanoseconds of the trace's clock.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that ``busy`` (sorted, disjoint) leaves idle."""
    out: List[Interval] = []
    cur = window[0]
    for s, e in busy:
        if e <= window[0] or s >= window[1]:
            continue
        if s > cur:
            out.append((cur, min(s, window[1])))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


@dataclass
class Trace:
    """What a traced window holds, on the trace's clock (ns)."""

    ops: List[Tuple[str, int, int]]  # (name, start, duration) of device ops
    spans: Dict[str, List[Interval]]  # span name -> its intervals (unioned)
    n_devices: int = 1
    busy: List[Interval] = field(default_factory=list)

    def __post_init__(self) -> None:
        self.busy = union((s, s + d) for _, s, d in self.ops)

    @property
    def window(self) -> Optional[Interval]:
        op = self.spans.get("op")
        if not op:
            return None
        return (op[0][0], op[-1][1])

    def span_ns(self, name: str) -> int:
        return total(self.spans.get(name, []))

    def covered_ns(self, outer: str, inner: str) -> int:
        """Time of ``outer`` spans that ``inner`` spans cover."""
        return total(intersect(self.spans.get(outer, []), self.spans.get(inner, [])))

    def busy_ns(self, within: Optional[str] = None) -> int:
        """Device busy time, averaged over the devices, inside the window
        or inside the spans named ``within``."""
        if within is None:
            w = self.window
            region = [w] if w else []
        else:
            region = self.spans.get(within, [])
        return total(intersect(self.busy, region)) // max(self.n_devices, 1)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` device operations that took most time, in seconds."""
        w = self.window
        acc: Dict[str, int] = {}
        for name, s, d in self.ops:
            if w is None or (s < w[1] and s + d > w[0]):
                acc[name] = acc.get(name, 0) + d
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [(name, ns / 1e9) for name, ns in ranked]

    def idle_gaps(self, labels: Dict[str, str], outside: str, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` longest idle gaps of the device in the window, each
        named by the innermost span around its midpoint: ``labels`` maps a
        span name to a gap name, innermost first; ``outside`` names a gap
        that no span encloses."""
        w = self.window
        if w is None:
            return []
        out = []
        for s, e in gaps(self.busy, w):
            mid = (s + e) // 2
            name = outside
            for span, label in labels.items():
                if any(a <= mid < b for a, b in self.spans.get(span, [])):
                    name = label
                    break
            out.append((name, (e - s) / 1e9))
        out.sort(key=lambda g: -g[1])
        return out[:k]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> Trace:
    """Read device operations and benchmark spans from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: List[Tuple[str, int, int]] = []
    spans: Dict[str, List[Interval]] = {}
    n_dev = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            n_dev += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        # an HLO op's event name is its whole instruction
                        # text; keep the instruction's name
                        name = ev.name.split(" = ", 1)[0]
                        ops.append((name, int(ev.start_ns), int(ev.duration_ns)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.setdefault(ev.name[len(SPAN_PREFIX):], []).append(
                            (s, s + int(ev.duration_ns))
                        )
    return Trace(ops=ops, spans={k: union(v) for k, v in spans.items()}, n_devices=max(n_dev, 1))
