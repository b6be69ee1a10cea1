"""Continuous-time event-driven execution/flow engine (array-based).

This is the exact (slot-width -> 0) counterpart of the paper's slotted
Alg. 1, with the *rate policy* factored out so the paper's OES rule and the
three baselines (OMCoflow / MRTF / FIFO) all run on identical dependency
semantics — the comparison then isolates the scheduling policy, exactly as
in §VI-B where baselines "start a task immediately once its dependencies
have been cleared" and differ only in flow rate control.

Dependency semantics implemented (paper constraints (5)-(14)):
  * store tasks bootstrap iteration 1 at t=0                         (5)
  * task (j,n) starts when: (j,n-1) done; every remote in-edge's
    instance for source-iteration (n - lag) delivered; every local
    in-edge's source task has finished iteration (n - lag)        (7)-(9)
  * instances of one edge transmit strictly in iteration order       (11)
  * per-machine NIC capacity is respected by every rate policy   (13)(14)

Makespan = completion time of the last task's iteration N (eq. 15). Final
PS->worker flows (which would feed iteration N+1) are not generated.

Both engines also accept a time-varying cluster (``trace=``, a
``repro.dynamics.traces.BandwidthTrace``): NIC bandwidths and per-machine
compute slowdowns are piecewise-constant in time, segment boundaries become
a third event source, and the dependency constraints (5)-(12) are untouched
while the capacity constraints (13)(14) hold pointwise against B(t) — see
``simulate``'s docstring for the exact semantics.

Flows additionally carry a TRAFFIC CLASS (training / migration / per-job
QoS): under a ``ShapedPolicy`` wrapper the rate policy serves classes in
priority order against leftover capacity (work-conserving strict
de-prioritisation), optionally with EDF deadline escalation for gated
state moves — see the traffic-class section below.  Unshaped policies
ignore classes entirely and match the pre-class engine bit-for-bit.

Implementation notes: because constraint (11) serialises a logical edge's
instances, *at most one instance per edge is ever in flight* — the active
flow set is a boolean mask over the E logical edges, and all per-event work
is vectorised numpy over that mask.

Backends: ``simulate`` / ``simulate_batch`` / ``expected_makespan`` (and
every consumer that threads the knob — placement search, re-planning, the
cache-aware and multi-job objectives) accept ``backend="numpy" | "jax"``,
defaulting to the ``REPRO_ENGINE_BACKEND`` environment variable and then
to ``"numpy"``.  The numpy engine in this module is the REFERENCE
implementation: event-exact float64 with a full ``flow_log``.
``simulate_batch`` on numpy is that reference run instance by instance.
``backend="jax"`` routes calls through ``engine_jax.simulate_batch_jax``,
the lock-step batch engine: one jitted ``lax.while_loop`` array program
per (width-bucket, topology, policy) that advances every instance of a
batch together and agrees with the reference at
``engine_jax.PARITY_RTOL`` (certified by tests/test_jax_engine.py).  The
jax backend supports the five built-in policies (custom ``RatePolicy``
callables raise a clear error) and records ``flow_log`` like this engine
when ``record=True``.
"""
from __future__ import annotations

import heapq
import os
from dataclasses import dataclass
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cluster import ClusterSpec, Placement
from .units import GB, Seconds
from .workload import Realization, Workload
from ..obs import metrics as obs_metrics

if TYPE_CHECKING:  # layering: core never imports dynamics at runtime
    from numpy.typing import ArrayLike

    from ..dynamics.traces import BandwidthTrace

EPS = 1e-9

# Selectable simulation backends (see the module docstring's backend
# section).  "numpy" is the reference event loop below; "jax" is the jitted
# array program in engine_jax.py, parity-certified at PARITY_RTOL.
ENGINE_BACKENDS = ("numpy", "jax")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve the engine backend: explicit argument > the
    ``REPRO_ENGINE_BACKEND`` environment variable > ``"numpy"``.

    Raises ``ValueError`` for unknown names and ``RuntimeError`` (with the
    original import error) when ``"jax"`` is requested but jax cannot be
    imported — a mis-set environment fails loudly at the first simulation
    instead of silently falling back to the slow path."""
    if backend is None:
        backend = os.environ.get("REPRO_ENGINE_BACKEND", "").strip() or "numpy"
    backend = backend.lower()
    if backend not in ENGINE_BACKENDS:
        raise ValueError(
            f"unknown engine backend {backend!r}; expected one of "
            f"{ENGINE_BACKENDS} (explicit backend= or REPRO_ENGINE_BACKEND)"
        )
    if backend == "jax":
        from . import engine_jax

        if not engine_jax.HAVE_JAX:
            raise RuntimeError(
                "engine backend 'jax' requested (backend= or "
                "REPRO_ENGINE_BACKEND) but jax is not importable: "
                f"{engine_jax.JAX_IMPORT_ERROR!r} — install jax or use "
                "backend='numpy'"
            )
    return backend

# Traffic-class ids (see ShapedPolicy): LOWER id = HIGHER priority.  Training
# flows default to class 0 and migration flows to class 1; merged multi-job
# workloads may assign any integer per-job QoS class (multijob.merged_edge_classes).
CLASS_TRAINING = 0
CLASS_MIGRATION = 1


# ---------------------------------------------------------------------------
# Rate policies (vectorised): given arrays describing active flows, return
# per-active-flow rates.  All respect NIC caps (13)(14).
# ---------------------------------------------------------------------------
class RatePolicy:
    name = "abstract"

    def rates(
        self,
        src_m: np.ndarray,  # [A] source machine per active flow
        dst_m: np.ndarray,  # [A]
        remaining: np.ndarray,  # [A] GB left
        release: np.ndarray,  # [A] release time (for FIFO)
        group: np.ndarray,  # [A] coflow group id (dst task instance)
        bw_in: np.ndarray,  # [M]
        bw_out: np.ndarray,  # [M]
    ) -> np.ndarray:  # pragma: no cover - interface
        raise NotImplementedError


class OESStrictRate(RatePolicy):
    """Paper Alg. 1 lines 18-21, verbatim: degree-balanced fair share.

    rate(f) = min( B_in[dst]/Delta_in[dst], B_out[src]/Delta_out[src] ).

    NOT work-conserving: when a flow's other NIC is the bottleneck, the
    residual capacity of this NIC is wasted — measurably slower than FIFO
    on high-degree jobs (papers100M: ~9 % — see EXPERIMENTS §Search).
    Kept verbatim for fidelity tests and the ablation.
    """

    name = "oes_strict"

    def rates(self, src_m, dst_m, remaining, release, group, bw_in, bw_out):
        d_out = np.bincount(src_m, minlength=len(bw_out))
        d_in = np.bincount(dst_m, minlength=len(bw_in))
        return np.minimum(bw_in[dst_m] / d_in[dst_m], bw_out[src_m] / d_out[src_m])


class OESRate(RatePolicy):
    """Work-conserving OES (beyond-paper, default for DGTP): max-min fair
    rates via progressive filling over the bipartite NIC graph.

    Every flow receives AT LEAST the paper rule's min-share (its first
    freeze level is >= min(B_in/Delta_in, B_out/Delta_out)), so Lemma 1
    and the Theorem-1 chain bound continue to hold verbatim, while
    residual capacity is redistributed instead of wasted.  Property-tested
    dominance: tests/test_oes.py::test_workconserving_dominates_strict.
    """

    name = "oes"

    def rates(self, src_m, dst_m, remaining, release, group, bw_in, bw_out):
        n = len(src_m)
        r = np.zeros(n)
        rem_in = bw_in.astype(np.float64).copy()
        rem_out = bw_out.astype(np.float64).copy()
        unfrozen = np.ones(n, dtype=bool)
        # progressive filling: raise all unfrozen flows uniformly until a
        # NIC saturates; freeze its flows; repeat (<= 2M rounds).
        for _ in range(2 * (len(bw_in) + len(bw_out))):
            if not unfrozen.any():
                break
            cnt_in = np.bincount(dst_m[unfrozen], minlength=len(bw_in))
            cnt_out = np.bincount(src_m[unfrozen], minlength=len(bw_out))
            with np.errstate(divide="ignore", invalid="ignore"):
                inc_in = np.where(cnt_in > 0, rem_in / np.maximum(cnt_in, 1), np.inf)
                inc_out = np.where(cnt_out > 0, rem_out / np.maximum(cnt_out, 1), np.inf)
            inc = min(inc_in.min(), inc_out.min())
            if not np.isfinite(inc):
                break
            r[unfrozen] += inc
            rem_in -= inc * cnt_in
            rem_out -= inc * cnt_out
            sat_in = (rem_in <= EPS) & (cnt_in > 0)
            sat_out = (rem_out <= EPS) & (cnt_out > 0)
            newly = unfrozen & (sat_in[dst_m] | sat_out[src_m])
            if not newly.any():
                break
            unfrozen &= ~newly
        return r


class _WaterfillRate(RatePolicy):
    """Greedy sequential water-fill in a priority order (FIFO/MRTF base).

    Flows are visited in priority order; each takes the min of the remaining
    ingress/egress capacity of its two NICs (head-of-line blocking emerges
    naturally for FIFO)."""

    def order(self, src_m, dst_m, remaining, release, bw_in, bw_out):
        raise NotImplementedError

    def rates(self, src_m, dst_m, remaining, release, group, bw_in, bw_out):
        # float64 coercion matters: a user-built ClusterSpec can carry
        # integer bandwidth arrays, and an int `rem` silently truncates the
        # in-place `rem -= give` arithmetic below (same coercion as OESRate)
        rem_in = bw_in.astype(np.float64)
        rem_out = bw_out.astype(np.float64)
        r = np.zeros(len(src_m))
        for i in self.order(src_m, dst_m, remaining, release, bw_in, bw_out):
            give = min(rem_in[dst_m[i]], rem_out[src_m[i]])
            if give > EPS:
                r[i] = give
                rem_in[dst_m[i]] -= give
                rem_out[src_m[i]] -= give
        return r


class FIFORate(_WaterfillRate):
    """DistDGL's system-default behaviour: FIFO queues per NIC."""

    name = "fifo"

    def order(self, src_m, dst_m, remaining, release, bw_in, bw_out):
        return np.argsort(release, kind="stable")


class MRTFRate(_WaterfillRate):
    """Minimum-remaining-time-first heuristic (§VI-B baseline (ii))."""

    name = "mrtf"

    def order(self, src_m, dst_m, remaining, release, bw_in, bw_out):
        # a dynamic-trace segment can drive a NIC's bandwidth to exactly 0;
        # an unguarded denominator makes t_rem inf/NaN and poisons the
        # argsort order — the EPS floor sorts dead-NIC flows last instead
        t_rem = remaining / np.maximum(np.minimum(bw_in[dst_m], bw_out[src_m]), EPS)
        return np.argsort(t_rem, kind="stable")


class OMCoflowRate(RatePolicy):
    """Online coflow baseline (§VI-B baseline (i), after Tan et al. [48]).

    Flows destined to the same task instance form one coflow. Within a
    coflow each flow gets weight inversely proportional to its predicted
    standalone finish time (remaining / min(B_in, B_out)), normalised so
    each coflow has unit aggregate weight ('as if it were the only coflow
    in the network'); rates are then proportional-fair scaled onto NIC
    capacities by iterative scaling.
    """

    name = "omcoflow"
    rounds = 4

    def rates(self, src_m, dst_m, remaining, release, group, bw_in, bw_out):
        # zero bandwidth (dynamic-trace dip) made ``pred`` inf, ``w`` 0 and
        # a coflow whose flows all hit dead NICs got ``gsum == 0`` — the
        # resulting NaN survived the iterative scaling and poisoned the
        # engine's ``remaining`` arithmetic; both denominators are floored
        pred = np.maximum(remaining, EPS) / np.maximum(
            np.minimum(bw_in[dst_m], bw_out[src_m]), EPS
        )
        w = 1.0 / pred
        gsum = np.zeros(group.max() + 1)
        np.add.at(gsum, group, w)
        w = w / np.maximum(gsum[group], EPS)
        r = w * min(bw_in.max(), bw_out.max())
        for _ in range(self.rounds):
            load_out = np.bincount(src_m, weights=r, minlength=len(bw_out))
            load_in = np.bincount(dst_m, weights=r, minlength=len(bw_in))
            s_out = bw_out / np.maximum(load_out, EPS)
            s_in = bw_in / np.maximum(load_in, EPS)
            r = r * np.minimum(1.0, np.minimum(s_out[src_m], s_in[dst_m]))
        return r


POLICIES: Dict[str, Callable[[], RatePolicy]] = {
    "oes": OESRate,
    "oes_strict": OESStrictRate,
    "fifo": FIFORate,
    "mrtf": MRTFRate,
    "omcoflow": OMCoflowRate,
}


# ---------------------------------------------------------------------------
# Traffic classes: every flow carries an integer class id (lower = higher
# priority).  Training edges default to CLASS_TRAINING, migration flows to
# CLASS_MIGRATION, and merged multi-job workloads may assign arbitrary
# per-job QoS classes (``multijob.merged_edge_classes``).  ``ShapedPolicy``
# is the class-aware shaping wrapper: it composes with ANY base rate policy
# by running one capacity pass per class in priority order.
# ---------------------------------------------------------------------------
SHAPING_MODES = ("strict", "deadline")


def _effective_classes(mode, cls, deadline, remaining, src_m, dst_m, bw_in, bw_out, now):
    """Class each flow is scheduled in THIS instant.

    ``strict`` keeps the declared classes.  ``deadline`` escalates a
    background flow EDF-style once its slack is consumed: when the time
    left to its deadline no longer covers the transfer time at the best
    rate its two NICs could ever give it, the flow is promoted STRICTLY
    above every class currently present (``min(classes, CLASS_TRAINING)
    - 1``), because earliest-deadline-FIRST means the urgent transfer
    must now outrank the very traffic that was starving it — promoting to
    an equal share cannot beat a work-conserving policy's fair split
    (which is what left the PR 4 restore overlap on the table), and a
    fixed promotion class would sit below user QoS classes < 0.  Earlier-
    deadline flows promote first because their slack runs out first.
    Flows without a deadline (inf) never promote, so deadline mode
    degrades to strict for them."""
    eff = np.asarray(cls, dtype=np.int64)
    if mode != "deadline":
        return eff
    lim = np.minimum(bw_in[dst_m], bw_out[src_m])
    need = remaining / np.maximum(lim, EPS)
    # + EPS: the complement of the wake rule (a wake is scheduled only
    # while deadline - need > now + EPS), so a flow woken at its own
    # escalation time escalates whatever the rounding of deadline - need
    urgent = (eff > CLASS_TRAINING) & ((deadline - now) <= need + EPS)
    if not urgent.any():
        return eff
    top = min(int(eff.min()), CLASS_TRAINING) - 1
    eff = eff.copy()
    eff[urgent] = top
    return eff


def _class_shaped_rates(
    mode, cls, deadline, remaining, src_m, dst_m, bw_in, bw_out, now,
    minlength, base_call,
):
    """The per-class leftover-capacity loop shared by
    ``ShapedPolicy.rates`` and the slotted transcription
    (``oes_slotted.py``): classes ascending, each rated by
    ``base_call(mask, rem_in, rem_out)`` against what the classes above
    left over, single class short-circuiting to a full-capacity
    pass-through (``mask=None``)."""
    eff = _effective_classes(
        mode, cls, deadline, remaining, src_m, dst_m, bw_in, bw_out, now
    )
    levels = np.unique(eff)
    if len(levels) == 1:
        return base_call(None, bw_in, bw_out)
    r = np.zeros(len(src_m))
    rem_in = bw_in.astype(np.float64)
    rem_out = bw_out.astype(np.float64)
    for i, c in enumerate(levels):
        m = eff == c
        sub = base_call(m, rem_in, rem_out)
        r[m] = sub
        if i + 1 < len(levels):
            rem_in -= np.bincount(dst_m[m], weights=sub, minlength=minlength)
            rem_out -= np.bincount(src_m[m], weights=sub, minlength=minlength)
            np.maximum(rem_in, 0.0, out=rem_in)
            np.maximum(rem_out, 0.0, out=rem_out)
    return r


class ShapedPolicy(RatePolicy):
    """Class-aware shaping wrapper composing with every base rate policy.

    Classes are served in ascending id order; each class's flows are rated
    by the BASE policy against the capacity LEFT OVER by the classes before
    it, so class 0 (training) never sees lower-class contention while lower
    classes soak up whatever training leaves idle — strict de-prioritisation
    that stays work-conserving.  ``mode="deadline"`` additionally promotes a
    background flow STRICTLY ABOVE the training pass once its deadline slack is
    consumed (see ``_effective_classes``); with no finite deadlines it is
    exactly ``strict``.

    With a single class present (e.g. a clean run without migrations) the
    wrapper is a bit-identical pass-through to the base policy, which is
    what keeps shaped clean-variant simulations comparable to unshaped ones.
    """

    def __init__(self, base: RatePolicy | str, mode: str = "strict") -> None:
        if isinstance(base, str):
            base = POLICIES[base]()
        if isinstance(base, ShapedPolicy):
            raise ValueError("ShapedPolicy cannot wrap another ShapedPolicy")
        if mode not in SHAPING_MODES:
            raise ValueError(f"unknown shaping mode {mode!r}; known: {SHAPING_MODES}")
        self.base = base
        self.mode = mode
        self.name = f"{base.name}+{mode}"

    def rates(
        self, src_m, dst_m, remaining, release, group, bw_in, bw_out,
        cls=None, deadline=None, now=0.0,
    ):
        if cls is None:  # no class info: single-class pass-through
            return self.base.rates(
                src_m, dst_m, remaining, release, group, bw_in, bw_out
            )
        if deadline is None:
            deadline = np.full(len(src_m), np.inf)

        def base_call(m, rem_in, rem_out):
            if m is None:
                return self.base.rates(
                    src_m, dst_m, remaining, release, group, rem_in, rem_out
                )
            return self.base.rates(
                src_m[m], dst_m[m], remaining[m], release[m],
                group[m] if group is not None else None,
                rem_in, rem_out,
            )

        return _class_shaped_rates(
            self.mode, cls, deadline, remaining, src_m, dst_m,
            bw_in, bw_out, now, len(bw_in), base_call,
        )


def resolve_policy(policy: "RatePolicy | str", shaping: Optional[str] = None) -> RatePolicy:
    """Resolve a policy spec (+ optional shaping mode) into a RatePolicy.

    Accepts a policy name (``"oes"``), a shaped name (``"oes+strict"``), a
    policy instance, or a ``ShapedPolicy``; ``shaping`` wraps an unshaped
    policy and must agree with an already-shaped one."""
    if isinstance(policy, str):
        if "+" in policy:
            base, _, mode = policy.partition("+")
            policy = ShapedPolicy(POLICIES[base](), mode)
        else:
            policy = POLICIES[policy]()
    if shaping is not None:
        if isinstance(policy, ShapedPolicy):
            if policy.mode != shaping:
                raise ValueError(
                    f"policy is already shaped with mode {policy.mode!r} but "
                    f"shaping={shaping!r} was requested"
                )
        else:
            policy = ShapedPolicy(policy, shaping)
    return policy


def _check_edge_classes(
    edge_classes: Optional["ArrayLike"], E: int
) -> Optional[np.ndarray]:
    if edge_classes is None:
        return None
    ec = np.asarray(edge_classes, dtype=np.int64)
    if ec.shape != (E,):
        raise ValueError(
            f"edge_classes must give one class id per logical edge "
            f"(expected shape ({E},), got {ec.shape})"
        )
    return ec


# ---------------------------------------------------------------------------
# Migration flows: one-shot state relocations scheduled WITH the training
# traffic.  The dynamics tier (repro.dynamics.replan) used to price re-plan
# migrations with a closed-form per-NIC drain bound computed OUTSIDE the
# engine; that bound can neither overlap state moves with training flows nor
# account for the contention they cause.  Promoting migration to a flow kind
# lets every rate policy arbitrate state moves against training transfers on
# the same NICs — the analytic bound survives only as a certified lower
# bound (property-tested in tests/test_dynamics_properties.py).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MigrationFlow:
    """A one-shot state-relocation flow, released at t=0.

    ``src`` / ``dst`` are MACHINE indices on the simulated cluster (a
    migration is machine-to-machine state movement, not a workload edge);
    ``gb`` is the state volume.  ``task`` optionally names the relocated
    task: that task may not start its FIRST simulated iteration until this
    flow completes (the post-replan gating rule) — ``-1`` leaves the flow
    ungated.  A flow whose ``src`` equals ``dst`` (or whose volume is ~0)
    ships nothing: it completes instantly and never gates.

    ``cls`` is the flow's traffic class (``CLASS_MIGRATION`` by default;
    only consumed when the simulation runs under a ``ShapedPolicy`` —
    unshaped policies arbitrate all classes as equals).  ``deadline`` is
    the absolute simulation time by which the flow should have completed
    so it delays nothing — under ``shaping="deadline"`` the flow is
    promoted strictly above the training class once its slack is consumed
    (EDF: the urgent transfer must outrank what starves it); ``inf``
    (the default) never promotes.  The replanner fills deadlines from the
    gated task's clean-variant start time (its slack absent migration)."""

    src: int
    dst: int
    gb: GB
    task: int = -1
    cls: int = CLASS_MIGRATION
    deadline: Seconds = float("inf")


def check_migration_flows(
    migrations: Optional[Sequence["MigrationFlow"]], M: int, J: int
) -> List["MigrationFlow"]:
    """Validate machine/task indices; returns the flows as a list.

    Raising here (rather than letting ``np.bincount`` mis-shape or — worse
    — silently misattribute bytes to the wrong NIC) is load-bearing for the
    elastic path: after a machine leave, PRE-leave machine indices must
    never meet a POST-leave cluster."""
    if not migrations:
        return []
    migs = list(migrations)
    for f in migs:
        if not (0 <= f.src < M and 0 <= f.dst < M):
            raise ValueError(
                f"migration flow {f} references a machine outside the "
                f"{M}-machine cluster — remap placements after membership "
                "changes before billing (stale pre-leave indices?)"
            )
        if f.task >= J:
            raise ValueError(
                f"migration flow {f} gates task {f.task} but the workload "
                f"has only {J} tasks"
            )
        if f.gb < 0:
            raise ValueError(f"migration flow {f} has negative volume")
        if np.isnan(f.deadline):
            raise ValueError(f"migration flow {f} has a NaN deadline")
    return migs


# ---------------------------------------------------------------------------
# Schedule recording
# ---------------------------------------------------------------------------
@dataclass
class TaskEvent:
    task: int
    iter: int
    start: Seconds
    end: Seconds


@dataclass
class ScheduleResult:
    """One simulated schedule.

    ``flow_log`` is a list of ``(edge, iter, start, end)`` tuples when the
    run was recorded (``record=True``, on either backend: the same flow
    instances, zero-volume ones left out) and ``None`` when it was NOT
    recorded (``record=False``).  ``None`` (not ``[]``) so "unrecorded"
    can never be confused with "a recorded schedule that happened to have
    no remote flows".

    ``n_events`` diverges between backends BY DESIGN: the numpy engine
    counts discrete events (task completions, flow deliveries, trace
    segments, escalations), while the jax engine counts lock-step
    ``while_loop`` iterations — one iteration may retire several
    simultaneous events, so the jax count is <= the numpy count for the
    same schedule.  Compare makespans and task-start matrices across
    backends (pinned at ``PARITY_RTOL``), never ``n_events``.

    ``aggregates``, when present, is the jax engine's in-program
    accumulator dict: per-machine NIC utilization integrals
    (``nic_in_gb``/``nic_out_gb``, GB delivered into/out of each machine),
    per-machine busy-time integrals (``busy_s``) and per-traffic-class
    delivered bytes (``class_gb``).  ``None`` unless collected.
    """

    makespan: Seconds
    task_events: List[TaskEvent]
    # (edge, iter, start, end) per delivered flow; None when unrecorded
    flow_log: Optional[List[Tuple[int, int, float, float]]]
    n_events: int
    policy: str
    aggregates: Optional[dict] = None

    def task_start_matrix(self, J: int, N: int) -> np.ndarray:
        out = np.full((J, N), np.nan)
        for ev in self.task_events:
            out[ev.task, ev.iter - 1] = ev.start
        return out


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------
def simulate(
    workload: Workload,
    cluster: ClusterSpec,
    placement: Placement,
    realization: Realization,
    policy: RatePolicy | str = "oes",
    record: bool = False,
    max_events: int = 50_000_000,
    trace: Optional["BandwidthTrace"] = None,
    migrations: Optional[Sequence[MigrationFlow]] = None,
    shaping: Optional[str] = None,
    edge_classes: Optional["ArrayLike"] = None,
    backend: Optional[str] = None,
) -> ScheduleResult:
    """Run one training job to completion under ``policy``; return schedule.

    ``backend`` selects the simulation engine (``resolve_backend``:
    explicit > ``REPRO_ENGINE_BACKEND`` > numpy).  ``"jax"`` runs the job
    as a width-1 ``engine_jax.simulate_batch_jax`` call — same event
    semantics and recorded ``flow_log`` at ``PARITY_RTOL`` (see the module
    docstring's backend section), so a jax-selected stack never silently
    mixes engines.

    ``migrations`` (a sequence of ``MigrationFlow``) injects one-shot state
    moves released at t=0 that compete for NIC bandwidth with the training
    flows under the SAME rate policy — the engine arbitrates them exactly
    like workload flows (they occupy pseudo-edge slots ``E..E+G-1``; in a
    recorded ``flow_log`` they appear with instance id 1 and start 0.0).  A
    flow that names a ``task`` gates that task's first iteration on the
    flow's completion.  An ungated flow that outlives every task extends the
    reported makespan (the run is not over until its state has landed).

    ``shaping`` (``None`` | ``"strict"`` | ``"deadline"``) wraps the policy
    in a class-aware ``ShapedPolicy``: flows are scheduled by traffic class
    (training edges class 0 unless ``edge_classes`` says otherwise,
    migration flows their ``MigrationFlow.cls``), lower ids first, each
    class rated by the base policy against the capacity left over by the
    classes above it.  ``"deadline"`` additionally promotes a background
    flow strictly above the training class once its ``deadline`` slack is
    consumed (EDF escalation).
    Equivalent to passing an already-wrapped ``ShapedPolicy`` (or a
    ``"<policy>+<mode>"`` name) as ``policy``.  ``edge_classes`` ([E] int)
    assigns per-edge QoS classes to the workload's own flows (multi-job
    merges); it is inert without a shaped policy.

    ``trace`` (a ``repro.dynamics.traces.BandwidthTrace``, duck-typed on
    ``times`` / ``bw_in`` / ``bw_out`` / ``slow``) makes the cluster
    time-varying: within segment ``s`` every NIC runs at ``trace.bw_in[s]``
    / ``trace.bw_out[s]`` and a task started in that segment executes for
    ``exec * trace.slow[s, machine]``.  Dynamic-trace semantics vs the
    paper's constraints (5)-(14): the dependency structure (5)-(12) is
    untouched — only the capacity constraints (13)(14) become
    time-indexed, ``sum of rates <= B(t)``, which every rate policy already
    satisfies pointwise because rates are recomputed from the segment's
    bandwidth at every event.  A segment boundary is simply a third event
    source next to task and flow completions: flows in flight carry their
    remaining bytes across it and continue at the new rates, and the
    engine stays exact because everything is constant between events
    (rates integrate trivially).  Tasks sample their machine's slowdown at
    START time only — a task spanning a boundary keeps its original finish
    time, mirroring how a straggling host delays the work it has already
    admitted."""
    if obs_metrics.REGISTRY.enabled:
        # one pre-aggregated increment per call, OUTSIDE the event loop —
        # the engine hot path itself carries no obs code
        obs_metrics.REGISTRY.counter("engine.simulate.calls").inc()
    if resolve_backend(backend) == "jax":
        from .engine_jax import simulate_batch_jax

        return simulate_batch_jax(
            workload, cluster, [placement], [realization], policy=policy,
            record=record, max_events=max_events, trace=trace,
            migrations=[migrations] if migrations is not None else None,
            shaping=shaping, edge_classes=edge_classes,
        )[0]
    return _simulate_reference(
        workload, cluster, placement, realization, policy, record, max_events,
        trace, migrations, shaping, edge_classes,
    )


def _simulate_reference(
    workload: Workload,
    cluster: ClusterSpec,
    placement: Placement,
    realization: Realization,
    policy: RatePolicy | str,
    record: bool,
    max_events: int,
    trace: Optional["BandwidthTrace"],
    migrations: Optional[Sequence[MigrationFlow]],
    shaping: Optional[str],
    edge_classes: Optional["ArrayLike"],
) -> ScheduleResult:
    """The numpy event loop behind ``simulate``: the reference engine,
    event-exact in float64.  ``simulate_batch`` on numpy runs it once per
    instance."""
    policy = resolve_policy(policy, shaping)
    shaped = isinstance(policy, ShapedPolicy)
    N = realization.n_iters
    J, E = workload.J, workload.E
    y = placement.y
    src_t, dst_t, lag = workload.edge_src, workload.edge_dst, workload.edge_lag
    vol = realization.volumes
    ex = realization.exec_times
    # no-copy for ClusterSpec's own float64 arrays; coerces user-supplied
    # integer bandwidth vectors before any policy arithmetic sees them
    bw_in = np.asarray(cluster.bw_in, dtype=np.float64)
    bw_out = np.asarray(cluster.bw_out, dtype=np.float64)
    seg, n_segs, seg_times = 0, 1, None
    slow_cur = None
    if trace is not None:
        if trace.bw_in.shape[1] != cluster.M:
            raise ValueError(
                f"trace covers {trace.bw_in.shape[1]} machines but the "
                f"cluster has {cluster.M} — rebuild the trace after "
                "membership changes"
            )
        seg_times = np.asarray(trace.times, dtype=np.float64)
        n_segs = len(seg_times)
        bw_in = np.asarray(trace.bw_in[0], dtype=np.float64)
        bw_out = np.asarray(trace.bw_out[0], dtype=np.float64)
        slow_cur = np.asarray(trace.slow[0], dtype=np.float64)
    src_m_all = y[src_t]
    dst_m_all = y[dst_t]

    local = src_m_all == dst_m_all  # dependency only, no flow
    last_instance = N - lag  # [E]

    # migration flows occupy pseudo-edge slots E..E+G-1 so the event loop's
    # vectorised per-flow work (rates, time stepping, completion) treats
    # them uniformly; G == 0 leaves every array exactly as before.
    migs = check_migration_flows(migrations, cluster.M, J)
    G = len(migs)
    EG = E + G
    dst_t_grp, lag_grp = dst_t, lag  # coflow-group inputs (extended below)
    if G:
        mig_src = np.array([f.src for f in migs], dtype=np.int64)
        mig_dst = np.array([f.dst for f in migs], dtype=np.int64)
        mig_gb = np.array([f.gb for f in migs], dtype=np.float64)
        src_m_all = np.concatenate([src_m_all, mig_src])
        dst_m_all = np.concatenate([dst_m_all, mig_dst])
        local = np.concatenate([local, (mig_src == mig_dst) | (mig_gb <= EPS)])
        vol = np.concatenate([vol, np.zeros((G, N))], axis=0)
        vol[E + np.arange(G), 0] = mig_gb
        # unique coflow group per migration flow, disjoint from task groups
        dst_t_grp = np.concatenate([dst_t, J + np.arange(G)])
        lag_grp = np.concatenate([lag, np.zeros(G, dtype=np.int64)])

    # traffic class + deadline per flow column (only consumed when shaped)
    flow_cls = np.zeros(EG, dtype=np.int64)
    flow_dl = np.full(EG, np.inf)
    ec = _check_edge_classes(edge_classes, E)
    if ec is not None:
        flow_cls[:E] = ec
    if G:
        flow_cls[E:] = [f.cls for f in migs]
        flow_dl[E:] = [f.deadline for f in migs]
    # all-inf deadlines make deadline mode bit-identical to strict: skip
    # the per-event escalation-wake scan entirely
    dl_events = (
        shaped and policy.mode == "deadline" and bool(np.isfinite(flow_dl).any())
    )

    # per-edge instance state (constraint (11): <=1 active instance per edge)
    delivered = np.zeros(EG, dtype=np.int64)
    sending = np.zeros(EG, dtype=np.int64)  # active instance id (0 = idle)
    remaining = np.zeros(EG, dtype=np.float64)
    release = np.zeros(EG, dtype=np.float64)
    active = np.zeros(EG, dtype=bool)

    done_iter = np.zeros(J, dtype=np.int64)
    running = np.zeros(J, dtype=bool)
    mig_left = np.zeros(J, dtype=np.int64)  # pending state flows gating a task

    in_edges = workload.in_edges
    out_edges = workload.out_edges

    task_heap: List[Tuple[float, int, int]] = []
    events: List[TaskEvent] = []
    flow_log: List[Tuple[int, int, float, float]] = []
    flow_start: Dict[Tuple[int, int], float] = {}

    def can_start(j: int, n: int) -> bool:
        if n > N or running[j] or done_iter[j] != n - 1:
            return False
        if n == 1 and mig_left[j]:
            return False  # relocated: first iteration waits for its state
        for e in in_edges[j]:
            need = n - lag[e]
            if need <= 0:
                continue
            if local[e]:
                if done_iter[src_t[e]] < need:
                    return False
            elif delivered[e] < need:
                return False
        return True

    def start_task(j: int, n: int, t: float) -> None:
        running[j] = True
        if slow_cur is None:
            end = t + ex[j, n - 1]
        else:
            end = t + ex[j, n - 1] * slow_cur[y[j]]
        heapq.heappush(task_heap, (end, j, n))
        if record:
            events.append(TaskEvent(j, n, t, end))

    def try_start_flow(e: int, t: float) -> bool:
        """Arm the next instance of edge e if released + predecessor done.
        Returns True if zero-volume instances were delivered instantly."""
        if local[e] or active[e]:
            return False
        got_zero = False
        while True:
            nxt = delivered[e] + 1
            if nxt > last_instance[e] or done_iter[src_t[e]] < nxt:
                return got_zero
            if vol[e, nxt - 1] > EPS:
                break
            delivered[e] = nxt
            got_zero = True
        sending[e] = nxt
        remaining[e] = vol[e, nxt - 1]
        release[e] = t
        active[e] = True
        if record:
            flow_start[(e, int(nxt))] = t
        return got_zero

    for g, f in enumerate(migs):
        e = E + g
        if local[e]:
            delivered[e] = 1  # nothing to ship: state already in place
            continue
        sending[e] = 1
        remaining[e] = vol[e, 0]
        active[e] = True
        if f.task >= 0:
            mig_left[f.task] += 1
        if record:
            flow_start[(e, 1)] = 0.0

    t = 0.0
    for j in range(J):
        if can_start(j, 1):
            start_task(j, 1, 0.0)

    n_events = 0
    while task_heap or active.any():
        n_events += 1
        if n_events > max_events:  # pragma: no cover
            raise RuntimeError("event limit exceeded — dependency deadlock?")
        (idx,) = np.nonzero(active)
        if len(idx):
            # coflow group id: destination task instance, encoded densely
            # (migration pseudo-edges get their own singleton groups)
            grp = dst_t_grp[idx] * (N + 2) + delivered[idx] + 1 + lag_grp[idx]
            if shaped:
                rates = policy.rates(
                    src_m_all[idx], dst_m_all[idx], remaining[idx],
                    release[idx], grp, bw_in, bw_out,
                    cls=flow_cls[idx], deadline=flow_dl[idx], now=t,
                )
            else:
                rates = policy.rates(
                    src_m_all[idx], dst_m_all[idx], remaining[idx],
                    release[idx], grp, bw_in, bw_out,
                )
            with np.errstate(divide="ignore"):
                dt = np.where(rates > EPS, remaining[idx] / np.maximum(rates, EPS), np.inf)
            dt_min = dt.min()
            t_flow = t + dt_min if np.isfinite(dt_min) else np.inf
        else:
            rates = None
            t_flow = np.inf
        t_task = task_heap[0][0] if task_heap else np.inf
        t_break = seg_times[seg + 1] if seg + 1 < n_segs else np.inf
        # deadline shaping adds a fourth event source: the earliest moment
        # a still-background flow's slack could run out.  Without it a
        # zero-rate (starved) flow contributes no flow event, and its
        # escalation would wait for an unrelated event — arbitrarily late.
        # ``remaining`` at t is an upper bound on remaining at the wake
        # time, so the estimate errs early and the wake simply re-checks.
        t_esc = np.inf
        if dl_events and len(idx):
            cand = np.isfinite(flow_dl[idx]) & (flow_cls[idx] > CLASS_TRAINING)
            if cand.any():
                sel = idx[cand]
                lim = np.minimum(bw_in[dst_m_all[sel]], bw_out[src_m_all[sel]])
                esc = flow_dl[sel] - remaining[sel] / np.maximum(lim, EPS)
                fut = esc[esc > t + EPS]
                if fut.size:
                    t_esc = float(fut.min())
        t_next = min(t_task, t_flow, t_break, t_esc)
        if not np.isfinite(t_next):  # pragma: no cover
            raise RuntimeError("no progress: flows active but zero rates")
        if len(idx):
            remaining[idx] -= rates * (t_next - t)
        t = t_next
        while seg + 1 < n_segs and seg_times[seg + 1] <= t:
            seg += 1
            bw_in = np.asarray(trace.bw_in[seg], dtype=np.float64)
            bw_out = np.asarray(trace.bw_out[seg], dtype=np.float64)
            slow_cur = np.asarray(trace.slow[seg], dtype=np.float64)

        touched: List[int] = []

        # task completions
        while task_heap and task_heap[0][0] <= t + EPS:
            _, j, n = heapq.heappop(task_heap)
            running[j] = False
            done_iter[j] = n
            touched.append(j)
            for e in out_edges[j]:
                if local[e]:
                    touched.append(int(dst_t[e]))
                elif try_start_flow(e, t):
                    touched.append(int(dst_t[e]))

        # flow completions (delivery may arm next instance; cascades handled
        # inside try_start_flow for zero-volume runs)
        if len(idx):
            fin = idx[remaining[idx] <= EPS * np.maximum(1.0, vol[idx, sending[idx] - 1])]
            for e in fin:
                n = int(sending[e])
                delivered[e] = n
                sending[e] = 0
                active[e] = False
                remaining[e] = 0.0
                if e >= E:  # one-shot migration flow: state has landed
                    if record:
                        flow_log.append((int(e), n, flow_start.pop((int(e), n)), t))
                    tsk = migs[int(e) - E].task
                    if tsk >= 0:
                        mig_left[tsk] -= 1
                        touched.append(int(tsk))
                    continue
                touched.append(int(dst_t[e]))
                if record:
                    flow_log.append((int(e), n, flow_start.pop((int(e), n)), t))
                if try_start_flow(int(e), t):
                    touched.append(int(dst_t[e]))

        # start newly-available tasks
        for j in set(touched):
            n = int(done_iter[j]) + 1
            if can_start(j, n):
                start_task(j, n, t)

    return ScheduleResult(
        makespan=float(t),
        task_events=events,
        flow_log=flow_log if record else None,
        n_events=n_events,
        policy=policy.name,
    )


def simulate_batch(
    workload: Workload,
    cluster: ClusterSpec,
    placements: Sequence[Placement],
    realizations: Sequence[Realization],
    policy: RatePolicy | str = "oes",
    record: bool = False,
    max_events: int = 50_000_000,
    trace: Optional["BandwidthTrace"] = None,
    migrations: Optional[Sequence[Optional[Sequence[MigrationFlow]]]] = None,
    shaping: Optional[str] = None,
    edge_classes: Optional["ArrayLike"] = None,
    backend: Optional[str] = None,
) -> List[ScheduleResult]:
    """Run ``B = len(placements)`` independent jobs to completion; instance
    ``b`` pairs ``placements[b]`` with ``realizations[b]``.  Returns one
    ``ScheduleResult`` per instance.

    On the numpy backend this is the reference engine run instance by
    instance: each result IS ``simulate`` on that instance alone.  On the
    jax backend (``resolve_backend``: explicit > ``REPRO_ENGINE_BACKEND`` >
    numpy) the whole batch is one call of ``engine_jax.simulate_batch_jax``,
    the lock-step engine, which agrees with the reference at
    ``engine_jax.PARITY_RTOL``.

    ``migrations`` is per-instance: ``migrations[b]`` (None or a sequence
    of ``MigrationFlow``) is injected into instance ``b`` exactly as
    ``simulate(..., migrations=...)`` would, so one batch can evaluate
    clean and migration-loaded variants side by side (the replan objective
    relies on this).  All realizations must share ``n_iters``; the cluster,
    ``trace``, ``shaping`` and ``edge_classes`` are shared by every
    instance (see ``simulate``)."""
    if obs_metrics.REGISTRY.enabled:
        obs_metrics.REGISTRY.counter("engine.simulate_batch.calls").inc()
        obs_metrics.REGISTRY.counter("engine.simulate_batch.instances").inc(
            len(placements)
        )
    if resolve_backend(backend) == "jax":
        from .engine_jax import simulate_batch_jax

        return simulate_batch_jax(
            workload, cluster, placements, realizations, policy=policy,
            record=record, max_events=max_events, trace=trace,
            migrations=migrations, shaping=shaping, edge_classes=edge_classes,
        )
    policy = resolve_policy(policy, shaping)
    B = len(placements)
    if B == 0:
        return []
    if len(realizations) != B:
        raise ValueError("placements and realizations must have equal length")
    N = realizations[0].n_iters
    if any(r.n_iters != N for r in realizations):
        raise ValueError("all realizations in a batch must share n_iters")
    if migrations is not None and len(migrations) != B:
        raise ValueError("migrations must give one (possibly None) entry per instance")
    if migrations is None:
        migrations = [None] * B
    return [
        _simulate_reference(
            workload, cluster, placements[b], realizations[b], policy, record,
            max_events, trace, migrations[b], shaping, edge_classes,
        )
        for b in range(B)
    ]


def monte_carlo_draws(
    workload: Workload, *, seed: int, n_iters: int, n_draws: int
) -> List[Realization]:
    """The canonical Monte-Carlo draw set for cost estimation: draw ``d``
    realizes at ``seed + 1000 * d``.  Every consumer of 'the draws for
    (seed, n_iters)' — expected_makespan(_many), ETP chains, the
    cache-aware objective — MUST build them here so independently-built
    draw sets for one seed are identical (apples-to-apples comparisons
    depend on it)."""
    return [
        workload.realize(seed=seed + 1000 * d, n_iters=n_iters)
        for d in range(n_draws)
    ]


def expected_makespan(
    workload: Workload,
    cluster: ClusterSpec,
    placement: Placement,
    policy: str = "oes",
    n_iters: int = 20,
    n_draws: int = 3,
    seed: int = 0,
    backend: Optional[str] = None,
) -> Seconds:
    """Monte-Carlo estimate of T'_Y (paper §V-B): simulate ``n_iters``
    iterations a few times with fresh draws from the traffic profile.

    All draws run in one ``simulate_batch`` call; ``backend`` is threaded
    to the engine (see ``resolve_backend``)."""
    reals = monte_carlo_draws(
        workload, seed=seed, n_iters=n_iters, n_draws=n_draws
    )
    return mean_batch_makespans(
        workload, cluster, [(placement, reals)], policy=policy, backend=backend
    )[0]


def mean_batch_makespans(
    workload: Workload,
    cluster: ClusterSpec,
    groups: Sequence[Tuple[Placement, Sequence[Realization]]],
    policy: RatePolicy | str = "oes",
    backend: Optional[str] = None,
) -> List[float]:
    """One ``simulate_batch`` over ``(placement, realizations)`` groups;
    returns each group's mean makespan over its realizations (summed in
    order — bit-identical to averaging per-group scalar simulations).
    This is the shared batch-expansion used by ``expected_makespan_many``,
    ETP's pooled chain evaluation and the merged-job objective."""
    batch_p: List[Placement] = []
    batch_r: List[Realization] = []
    sizes: List[int] = []
    for p, reals in groups:
        batch_p += [p] * len(reals)
        batch_r += list(reals)
        sizes.append(len(reals))
    results = simulate_batch(
        workload, cluster, batch_p, batch_r, policy=policy, backend=backend
    )
    out: List[float] = []
    k = 0
    for s in sizes:
        total = 0.0
        for r in results[k : k + s]:
            total += r.makespan
        out.append(total / s)
        k += s
    return out


def expected_makespan_many(
    workload: Workload,
    cluster: ClusterSpec,
    placements: Sequence[Placement],
    policy: str = "oes",
    n_iters: int = 20,
    n_draws: int = 3,
    seed: int = 0,
    backend: Optional[str] = None,
) -> List[float]:
    """Fused T'_Y for many candidate placements sharing one draw seed: all
    placements x draws run in ONE ``simulate_batch`` call.  Bit-identical
    to per-placement ``expected_makespan``.  (ETP's multi-chain search
    pools per-chain draws itself via ``mean_batch_makespans`` because its
    chains use distinct seeds.)"""
    if len(placements) == 0:
        return []
    reals = monte_carlo_draws(
        workload, seed=seed, n_iters=n_iters, n_draws=n_draws
    )
    return mean_batch_makespans(
        workload, cluster, [(p, reals) for p in placements], policy=policy,
        backend=backend,
    )
