"""Plain reference: the job's schedule under OES, one event at a time.

A straightforward event simulation of one training job (paper Sec. III-IV)
on a static cluster, written from the paper's model and independent of the
program under test:

* iteration ``n`` of task ``j`` starts once iteration ``n - 1`` of ``j`` is
  done and, for every in-edge with ``need = n - lag >= 1``, instance
  ``need`` has been delivered (or, when both ends share a machine, the
  source task has done iteration ``need``);
* instance ``n`` of a remote edge is released when its source task has done
  iteration ``n`` and instance ``n - 1`` has been delivered; at most one
  instance of an edge is in flight;
* flows in flight share the NICs by OES's work-conserving rule: max-min fair
  rates by progressive filling, where every unfrozen flow rises by the
  smallest per-NIC fair increment until a NIC saturates and its flows
  freeze;
* between events every rate is constant; the next event is the earliest task
  end or flow drain.

``dtype`` sets the precision of every time and volume.  The configuration
states float64; ``float32`` is the control: the same computation one step
lower, which the comparison in ``check.py`` must refuse.  The completion
tolerance follows the precision (1e-9 in float64, a few units in the last
place in float32), as any implementation in that precision must choose.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class RefSchedule:
    makespan: float
    starts: np.ndarray  # [J, N] start time of each task iteration
    events: int


def tolerance(dtype: np.dtype) -> float:
    return max(1e-9, 64 * float(np.finfo(dtype).eps))


def oes_rates(src_m: np.ndarray, dst_m: np.ndarray, bw: np.ndarray, dtype: np.dtype, tol: float) -> np.ndarray:
    """Max-min fair rates of flows ``src_m -> dst_m`` over NICs of speed
    ``bw`` (ingress = egress), by progressive filling."""
    M = len(bw)
    n = len(src_m)
    r = np.zeros(n, dtype=dtype)
    left_in = bw.astype(dtype).copy()
    left_out = bw.astype(dtype).copy()
    unfrozen = np.ones(n, dtype=bool)
    c_in = np.bincount(dst_m, minlength=M).astype(dtype)
    c_out = np.bincount(src_m, minlength=M).astype(dtype)
    for _ in range(4 * M + 1):
        busy_in, busy_out = c_in > 0, c_out > 0
        if not busy_in.any():
            break
        inc = dtype.type(min((left_in[busy_in] / c_in[busy_in]).min(),
                             (left_out[busy_out] / c_out[busy_out]).min()))
        r[unfrozen] += inc
        left_in = left_in - inc * c_in
        left_out = left_out - inc * c_out
        full = ((left_in <= tol) & busy_in)[dst_m] | ((left_out <= tol) & busy_out)[src_m]
        frozen = unfrozen & full
        if not frozen.any():
            break
        unfrozen &= ~frozen
        c_in -= np.bincount(dst_m[frozen], minlength=M)
        c_out -= np.bincount(src_m[frozen], minlength=M)
    return r


def simulate(
    src: np.ndarray,
    dst: np.ndarray,
    lag: np.ndarray,
    J: int,
    y: np.ndarray,
    bw: np.ndarray,
    vol: np.ndarray,
    ex: np.ndarray,
    dtype: type = np.float64,
    max_events: Optional[int] = None,
) -> RefSchedule:
    """Run the job with placement ``y`` ([J] machine per task) on NICs
    ``bw`` ([M] GB/s), volumes ``vol`` ([E, N] GB) and exec times ``ex``
    ([J, N] s) to completion."""
    dt_ = np.dtype(dtype)
    tol = tolerance(dt_)
    E, N = vol.shape
    vol = vol.astype(dt_)
    ex = ex.astype(dt_)
    src_m, dst_m = y[src], y[dst]
    local = src_m == dst_m
    last = N - lag  # last instance of each edge
    delivered = np.zeros(E, dtype=np.int64)
    active = np.zeros(E, dtype=bool)
    left = np.zeros(E, dtype=dt_)  # GB left of the instance in flight
    size = np.ones(E, dtype=dt_)  # max(1, its volume)
    done = np.zeros(J, dtype=np.int64)
    running = np.zeros(J, dtype=bool)
    ends = np.full(J, np.inf, dtype=dt_)
    starts = np.full((J, N), np.nan)
    t = dt_.type(0.0)
    events = 0
    limit = max_events if max_events is not None else 200 * (E + J) * N + 1000

    def release() -> None:
        while True:
            nxt = delivered + 1
            ok = ~local & ~active & (nxt <= last) & (done[src] >= nxt)
            if not ok.any():
                return
            v = vol[np.arange(E), np.clip(nxt - 1, 0, N - 1)]
            empty = ok & (v <= tol)
            delivered[empty] = nxt[empty]  # nothing to send: delivered at once
            arm = ok & (v > tol)
            left[arm] = v[arm]
            size[arm] = np.maximum(1.0, v[arm])
            active[arm] = True
            if not empty.any():
                return

    def start() -> None:
        n = done + 1
        need = n[dst] - lag
        met = (need <= 0) | np.where(local, done[src] >= need, delivered >= need)
        blocked = np.bincount(dst[~met], minlength=J) > 0
        go = ~running & (n <= N) & ~blocked
        for j in np.nonzero(go)[0]:
            starts[j, n[j] - 1] = float(t)
            ends[j] = t + ex[j, n[j] - 1]
            running[j] = True

    start()
    while running.any() or active.any():
        events += 1
        if events > limit:
            raise RuntimeError("reference: event limit exceeded")
        idx = np.nonzero(active)[0]
        t_next = ends.min()
        rates = None
        if len(idx):
            rates = oes_rates(src_m[idx], dst_m[idx], bw, dt_, tol)
            with np.errstate(divide="ignore"):
                drain = np.where(rates > tol, left[idx] / np.maximum(rates, tol), np.inf)
            t_next = min(t_next, t + drain.min())
        if not np.isfinite(t_next):
            raise RuntimeError("reference: no progress")
        t_next = dt_.type(t_next)
        if len(idx):
            left[idx] = left[idx] - rates * (t_next - t)
        t = t_next
        fin_t = running & (ends <= t + tol)
        done[fin_t] += 1
        running[fin_t] = False
        ends[fin_t] = np.inf
        fin_f = active & (left <= tol * size)
        delivered[fin_f] += 1
        active[fin_f] = False
        left[fin_f] = 0.0
        release()
        start()
    return RefSchedule(makespan=float(t), starts=starts, events=events)


def mean_makespan(job, y: np.ndarray, bw: np.ndarray, draws, dtype: type = np.float64) -> float:
    """Mean makespan of placement ``y`` over ``draws`` ([(vol, ex)]), summed
    in draw order."""
    total = 0.0
    for vol, ex in draws:
        total += simulate(job.src, job.dst, job.lag, job.J, y, bw, vol, ex, dtype).makespan
    return total / len(draws)
