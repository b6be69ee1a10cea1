"""JAX backend for the batched event engine: jitted rate solves + advancement.

This module ports ``engine.simulate_batch``'s lock-step inner loop to a
single jitted ``lax.while_loop`` array program so the planner's
placement-evaluations/sec scale with batch width instead of paying Python
per-event overhead per instance.  The event calculus is identical to the
numpy engine (the reference implementation):

  * one outer iteration = one lock-step event per still-alive instance:
    a SETTLE fixpoint (task completions -> flow completions/migration
    gating -> flow arming incl. zero-volume cascades -> task starts,
    repeated until nothing changes at the current instant) followed by an
    ADVANCE step (rate solve, next-event time over task ends / flow
    drains / dynamic-trace segment boundaries / deadline-escalation
    wakes, remaining-volume decrement, per-instance segment pointers);
  * all five built-in rate policies (oes / oes_strict / fifo / mrtf /
    omcoflow) are expressed as masked ``[B, EG]`` array programs over the
    per-instance ``[B, M]`` NIC capacity rows (the fifo/mrtf sequential
    waterfill is a ``fori_loop`` over the priority order; oes's
    progressive filling runs its rounds per NIC pair, on ``[B, M, M]``
    counts of the flows from each machine to each machine);
  * ``ShapedPolicy`` class shaping is a statically unrolled loop over the
    run's concrete class levels (plus the EDF escalation level in
    deadline mode), each level rated against the leftovers of the levels
    above it, exactly like ``engine._class_shaped_rates``.

Precision/parity contract: the backend runs in float64 (x64 is enabled at
import, an explicit and tested choice — see tests/test_jax_engine.py) and
agrees with the numpy engine on makespans and task-start schedules at
``PARITY_RTOL`` (XLA may fuse multiply-adds, so bit-equality is not
promised the way numpy batch-vs-scalar is).  Known divergence, by design:
``n_events`` counts jitted lock-step iterations (zero-duration cascades
settle in one iteration instead of several).  ``record=True`` compiles
task and flow span arrays (``[B, J, N]`` and ``[B, E + G, N]``) into the
loop and yields ``task_events`` and the numpy engine's ``flow_log``: the
same flow instances, zero-volume ones left out, migration flows as
instance 1; a runner with ``record=False`` carries neither.
Without recording, the program can carry cheap IN-PROGRAM
aggregate accumulators (``utilization=True``): per-machine NIC
utilization integrals (GB delivered into/out of each machine — the
integral of the rate solve over every advance step), per-machine
busy-time integrals (wall seconds with >= 1 task running) and
per-traffic-class delivered bytes, returned on
``ScheduleResult.aggregates``.  These add four small arrays to the loop
state and are compiled OUT (a separate jit cache entry) unless asked for.

Batch widths are padded to the next power of two (repeating instance 0)
so the jit cache sees a handful of shapes instead of one per width; the
compiled program cache is keyed on (padded width, workload topology,
policy, shaping levels, trace length, record, utilization).
"""
from __future__ import annotations

import hashlib
import os
import re
from pathlib import Path
from typing import (
    TYPE_CHECKING,
    Any,
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from .cluster import ClusterSpec, Placement
from .engine import (
    CLASS_TRAINING,
    EPS,
    MigrationFlow,
    RatePolicy,
    ScheduleResult,
    ShapedPolicy,
    TaskEvent,
    _check_edge_classes,
    check_migration_flows,
    resolve_policy,
)
from .workload import Realization, Workload
from ..obs import metrics as obs_metrics
from ..obs.spans import span

if TYPE_CHECKING:  # layering: core never imports dynamics at runtime
    from numpy.typing import ArrayLike

    from ..dynamics.traces import BandwidthTrace

try:  # pragma: no cover - exercised only when jax is absent
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    HAVE_JAX = True
    JAX_IMPORT_ERROR: Optional[BaseException] = None
except Exception as _exc:  # pragma: no cover
    HAVE_JAX = False
    JAX_IMPORT_ERROR = _exc

# Pinned jax-vs-numpy agreement tolerance (documented in ROADMAP.md):
# both engines run float64 and perform the same arithmetic, but XLA is
# free to contract multiply-adds, so schedules can drift by a few ULPs
# per event.  Certified by tests/test_jax_engine.py.
PARITY_RTOL = 1e-6
PARITY_ATOL = 1e-9

JAX_POLICIES = ("oes", "oes_strict", "fifo", "mrtf", "omcoflow")

# the runner's device phases, as jax.named_scope names in its op_name
# metadata (runner_scopes() reads them back); the lock-step loop's own
# instruction (its self time is loop control) and the set-up before it
# carry none
SCOPES = ("settle", "rate_solve", "advance")


class _Runner(NamedTuple):
    fn: Callable[..., Any]  # the jitted lock-step program
    rid: str  # short id of its cache key: repro.engine's ``runner`` argument
    args: Tuple[Any, ...]  # ShapeDtypeStructs of its first call's arguments


_RUNNERS: Dict[tuple, _Runner] = {}

# an instruction of compiled HLO text, and the op_name of its metadata
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%\S+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')

# fixed, location-derived default for JAX's persistent compilation cache
# (the directory is part of the cache key, so it must not move per run)
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    stands; otherwise the cache goes to ``<repo>/.jax_cache``.  Called
    from scripts' ``__main__``, never at import.  Returns the directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(jax.config.jax_compilation_cache_dir)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class _State(NamedTuple):
    k: object  # outer iteration counter (scalar)
    t: object  # [B] clock
    nev: object  # [B] lock-step iterations survived
    fill: object  # oes filling rounds run, over iterations and class levels
    #   (int32 scalar; the loop is batch-wide, so one count per call)
    stuck: object  # [B] zero-rate deadlock flag
    seg: object  # [B] trace segment pointer
    delivered: object  # [B, EG]
    thresh: object  # [B, EG] completion threshold EPS*max(1, vol) of the
    #   in-flight instance (an active column is always sending
    #   delivered + 1, so no separate `sending` array is carried)
    remaining: object  # [B, EG]
    release: object  # [B, EG]
    active: object  # [B, EG]
    done: object  # [B, J]
    running: object  # [B, J]
    tend: object  # [B, J]
    migleft: object  # [B, J]
    start_rec: object  # [B, J, N] (nan when not recorded)
    end_rec: object  # [B, J, N]
    fstart_rec: object  # [B, EG, N] flow instance starts (None unless record:
    #   no placeholder, so a runner that records nothing carries nothing)
    fend_rec: object  # [B, EG, N] flow instance ends
    util_in: object  # [B, M] GB delivered into each machine ((1,1) off)
    util_out: object  # [B, M] GB sent out of each machine ((1,1) off)
    busy: object  # [B, M] seconds with >=1 running task ((1,1) off)
    clsgb: object  # [B, L] GB delivered per traffic class ((1,1) off)


def _build_runner(
    *,
    B: int,
    E: int,
    Gmax: int,
    J: int,
    N: int,
    M: int,
    S: int,
    policy_name: str,
    mode: Optional[str],
    dl_events: bool,
    use_slow: bool,
    no_cascade: bool,
    levels: tuple,
    rounds: int,
    record: bool,
    max_events: int,
    collect: bool,
    agg_levels: tuple,
    src_t: np.ndarray,
    dst_t: np.ndarray,
    lag: np.ndarray,
) -> Callable[..., Any]:
    """Compile the lock-step program for one static configuration."""
    EG = E + Gmax
    top_level = min(min(levels), CLASS_TRAINING) - 1 if levels else -1

    # int32 throughout: every count here is bounded by max(J, N, M) and
    # int32 halves the bytes the integer state drags through each round
    src_t_e = jnp.asarray(src_t, dtype=jnp.int32)
    dst_t_e = jnp.asarray(dst_t, dtype=jnp.int32)
    lag_e = jnp.asarray(lag, dtype=jnp.int32)
    last_eg = jnp.asarray(
        np.concatenate([N - lag, np.zeros(Gmax, dtype=np.int64)]), dtype=jnp.int32
    )
    src_t_eg = jnp.asarray(
        np.concatenate([src_t, np.zeros(Gmax, dtype=np.int64)]), dtype=jnp.int32
    )
    dst_t_grp = jnp.asarray(
        np.concatenate([dst_t, J + np.arange(Gmax, dtype=np.int64)]),
        dtype=jnp.int32,
    )
    lag_grp = jnp.asarray(
        np.concatenate([lag, np.zeros(Gmax, dtype=np.int64)]), dtype=jnp.int32
    )
    # static in-edge incidence: in_adj[e, j] = 1 iff edge e feeds task j.
    # The per-task dependency check runs as one violation-count matmul
    # instead of a scatter-min — XLA CPU serialises scatter, and this sits
    # on the innermost event loop.  float32 is exact for counts <= E.
    in_adj_np = np.zeros((E, J), dtype=np.float32)
    in_adj_np[np.arange(E), dst_t] = 1.0
    in_adj = jnp.asarray(in_adj_np)

    def run(
        vol,  # [B, EG, N] f64
        ex,  # [B, J, N] f64
        src_mx,  # [B, EG] i64 machine per flow column
        dst_mx,  # [B, EG] i64
        armable,  # [B, EG] bool (training edge, non-local)
        local_e,  # [B, E] bool
        flow_cls,  # [B, EG] i64
        flow_dl,  # [B, EG] f64
        gate_task,  # [B, EG] i64 (-1 = ungated / not a migration column)
        y_mat,  # [B, J] i64 task machine (slowdown lookup)
        delivered0,
        thresh0,
        remaining0,
        active0,
        migleft0,
        tr_times,  # [S] f64
        tr_bw_in,  # [S, M] f64
        tr_bw_out,  # [S, M] f64
        tr_slow,  # [S, M] f64
    ):
        # Per-element lookups are select chains, not gathers: on a TPU v5e
        # two gathers with slice sizes all 1 in the oes filling loop took
        # 93% of the device time of the papers100M job (PERF.md), while M
        # (or N) selects fuse into one elementwise pass on any backend.
        # Exactly one branch matches, so the value is the same bits a
        # gather would read.
        def pick(idx, a2d):  # a2d[b, idx[b, c]] for idx in [0, a2d.shape[1])
            out = jnp.broadcast_to(a2d[:, :1], idx.shape)
            for m in range(1, a2d.shape[1]):
                out = jnp.where(idx == m, a2d[:, m : m + 1], out)
            return out

        def pick_iter(a3d, idx):  # a3d[b, c, idx[b, c]] over the iteration axis
            hit = jnp.arange(N, dtype=idx.dtype)[None, None, :] == idx[:, :, None]
            return jnp.sum(jnp.where(hit, a3d, 0.0), axis=2)

        def gather_dst(a2d):  # [B, M] -> [B, EG] by dst machine
            return pick(dst_mx, a2d)

        def gather_src(a2d):
            return pick(src_mx, a2d)

        # fixed per run: boolean NIC incidences laid out [B, M, EG] so
        # every per-machine reduction runs over the minor-most axis — XLA
        # fuses the compare, select and sum into one fast pass (a
        # middle-axis reduce lowers to a slow reduce-window on CPU, and a
        # scatter would serialise outright; both sit on the innermost
        # event loop).
        oh_dst = dst_mx[:, None, :] == jnp.arange(M, dtype=dst_mx.dtype)[None, :, None]
        oh_src = src_mx[:, None, :] == jnp.arange(M, dtype=src_mx.dtype)[None, :, None]
        if collect:
            # [B, M, J] task->machine incidence for the busy-time integral
            oh_y = (
                y_mat[:, None, :] == jnp.arange(M, dtype=y_mat.dtype)[None, :, None]
            )

        def sum_dst(vals):  # [B, EG] f64 -> [B, M]
            return jnp.sum(jnp.where(oh_dst, vals[:, None, :], 0.0), axis=2)

        def sum_src(vals):
            return jnp.sum(jnp.where(oh_src, vals[:, None, :], 0.0), axis=2)

        def cnt_dst(bools):  # [B, EG] bool -> [B, M] f64 counts
            return jnp.sum(
                oh_dst & bools[:, None, :], axis=2
            ).astype(jnp.float64)

        def cnt_src(bools):
            return jnp.sum(
                oh_src & bools[:, None, :], axis=2
            ).astype(jnp.float64)

        # ---- rate policies: masked [B, EG] programs over [B, M] caps ----
        def rates_oes_strict(mask, cap_in, cap_out, remaining, release, grp):
            d_in = cnt_dst(mask)
            d_out = cnt_src(mask)
            r = jnp.minimum(
                gather_dst(cap_in) / jnp.maximum(gather_dst(d_in), 1.0),
                gather_src(cap_out) / jnp.maximum(gather_src(d_out), 1.0),
            )
            return jnp.where(mask, r, 0.0)

        def rates_oes(mask, cap_in, cap_out, remaining, release, grp):
            # lock-step progressive filling, mirroring engine.oes_pool:
            # each instance raises its unfrozen flows by ITS OWN bottleneck
            # increment until a NIC saturates; frozen flows keep their level.
            # A flow loads exactly two NICs, its dst machine's in-NIC and
            # its src machine's out-NIC, so the flows of one (dst, src)
            # machine pair are counted, frozen and raised together: the
            # rounds run on [B, M, M] pair counts and [B, M] NIC state,
            # never on the [B, EG] flow axis.  (M * M is below EG on every
            # cluster the engine runs: 16 against 72 flows on the testbed,
            # 256 against 1400 on papers100M's 16 machines; it would pass EG
            # only on clusters of 64 machines or more.)
            # pairs[b, i, o]: masked flows into machine i from machine o.
            # One batched matmul, exact in float32 for counts up to EG: on a
            # TPU v5e an integer sum of the [B, M, M, EG] conjunction in its
            # place doubled the device time of a papers100M call.
            pairs = jnp.einsum(
                "bie,boe->bio",
                oh_dst.astype(jnp.float32),
                (oh_src & mask[:, None, :]).astype(jnp.float32),
                precision=lax.Precision.HIGHEST,
            )

            def cond(c):
                flows = c[4]
                return flows.any() & (c[5] < 4 * M)

            def body(c):
                # flows: unfrozen pairs of instances still filling (a
                # finished instance has none); lv_*: each NIC's level, the
                # sum of the increments of the rounds it had unfrozen flows
                lv_i, lv_o, rem_i, rem_o, flows, k = c
                cnt_i = jnp.sum(jnp.where(flows, pairs, 0.0), axis=2).astype(jnp.float64)
                cnt_o = jnp.sum(jnp.where(flows, pairs, 0.0), axis=1).astype(jnp.float64)
                inc_i = jnp.min(
                    jnp.where(cnt_i > 0, rem_i / jnp.maximum(cnt_i, 1.0), jnp.inf),
                    axis=1,
                )
                inc_o = jnp.min(
                    jnp.where(cnt_o > 0, rem_o / jnp.maximum(cnt_o, 1.0), jnp.inf),
                    axis=1,
                )
                inc_b = jnp.minimum(inc_i, inc_o)
                inc_f = jnp.where(jnp.isfinite(inc_b), inc_b, 0.0)
                lv_i = lv_i + jnp.where(cnt_i > 0, inc_b[:, None], 0.0)
                lv_o = lv_o + jnp.where(cnt_o > 0, inc_b[:, None], 0.0)
                rem_i = rem_i - inc_f[:, None] * cnt_i
                rem_o = rem_o - inc_f[:, None] * cnt_o
                sat_i = (rem_i <= EPS) & (cnt_i > 0)
                sat_o = (rem_o <= EPS) & (cnt_o > 0)
                # a round that saturates no NIC ends the instance's filling
                live = sat_i.any(axis=1) | sat_o.any(axis=1)
                flows = (
                    flows
                    & ~(sat_i[:, :, None] | sat_o[:, None, :])
                    & live[:, None, None]
                )
                return lv_i, lv_o, rem_i, rem_o, flows, k + 1

            init = (
                jnp.zeros((B, M)),
                jnp.zeros((B, M)),
                cap_in,
                cap_out,
                pairs > 0,
                jnp.int32(0),
            )
            lv_i, lv_o, _, _, _, rounds = lax.while_loop(cond, body, init)
            # a pair rises until the first of its two NICs stops, and levels
            # only grow, so its level (its flows' rate) is the smaller of its
            # NICs' levels: the same sums, bit for bit.  The lookup is a
            # one-hot max over the minor M axis (levels are >= 0): one fused
            # pass, where a select chain slices M columns in M passes.
            ms = jnp.arange(M, dtype=dst_mx.dtype)[None, None, :]
            r = jnp.minimum(
                jnp.max(jnp.where(dst_mx[:, :, None] == ms, lv_i[:, None, :], 0.0), axis=2),
                jnp.max(jnp.where(src_mx[:, :, None] == ms, lv_o[:, None, :], 0.0), axis=2),
            )
            return jnp.where(mask, r, 0.0), rounds

        def rates_waterfill(mask, cap_in, cap_out, remaining, release, grp):
            if policy_name == "fifo":
                key = jnp.where(mask, release, jnp.inf)
            else:  # mrtf: remaining time at the best rate the caps allow
                lim = jnp.minimum(gather_dst(cap_in), gather_src(cap_out))
                key = jnp.where(
                    mask, remaining / jnp.maximum(lim, EPS), jnp.inf
                )
            order = jnp.argsort(key, axis=1)  # stable: ties by column

            def body(kk, carry):
                r, rem_i, rem_o = carry
                i = order[:, kk]
                ohd = jnp.take_along_axis(oh_dst, i[:, None, None], axis=2)[..., 0]
                ohs = jnp.take_along_axis(oh_src, i[:, None, None], axis=2)[..., 0]
                give = jnp.minimum(
                    jnp.sum(jnp.where(ohd, rem_i, 0.0), axis=1),
                    jnp.sum(jnp.where(ohs, rem_o, 0.0), axis=1),
                )
                m_i = jnp.take_along_axis(mask, i[:, None], axis=1)[:, 0]
                give = jnp.where(m_i & (give > EPS), give, 0.0)
                sel = jnp.arange(EG)[None, :] == i[:, None]
                r = r + jnp.where(sel, give[:, None], 0.0)
                rem_i = rem_i - jnp.where(ohd, give[:, None], 0.0)
                rem_o = rem_o - jnp.where(ohs, give[:, None], 0.0)
                return r, rem_i, rem_o

            r, _, _ = lax.fori_loop(
                0, EG, body, (jnp.zeros((B, EG)), cap_in, cap_out)
            )
            return r

        def rates_omcoflow(mask, cap_in, cap_out, remaining, release, grp):
            ci = gather_dst(cap_in)
            co = gather_src(cap_out)
            pred = jnp.maximum(remaining, EPS) / jnp.maximum(
                jnp.minimum(ci, co), EPS
            )
            w = jnp.where(mask, 1.0 / pred, 0.0)
            # per-coflow weight sums: the same-group compare fuses into the
            # reduction (group ids change with `delivered`, so no static
            # one-hot; the [B, EG, EG] comparison never materialises)
            gsum = jnp.sum(
                jnp.where(
                    grp[:, :, None] == grp[:, None, :], w[:, None, :], 0.0
                ),
                axis=2,
            )
            w = w / jnp.maximum(gsum, EPS)
            ref_b = jnp.minimum(cap_in.max(axis=1), cap_out.max(axis=1))
            r = w * ref_b[:, None]

            def rnd(_, r):
                rm = jnp.where(mask, r, 0.0)
                load_out = sum_src(rm)
                load_in = sum_dst(rm)
                s_out = cap_out / jnp.maximum(load_out, EPS)
                s_in = cap_in / jnp.maximum(load_in, EPS)
                return r * jnp.minimum(
                    1.0, jnp.minimum(gather_src(s_out), gather_dst(s_in))
                )

            r = lax.fori_loop(0, rounds, rnd, r)
            return jnp.where(mask, r, 0.0)

        rates = {
            "oes": rates_oes,
            "oes_strict": rates_oes_strict,
            "fifo": rates_waterfill,
            "mrtf": rates_waterfill,
            "omcoflow": rates_omcoflow,
        }[policy_name]

        def base(*args):
            """(rates, filling rounds): only oes fills in rounds."""
            if policy_name == "oes":
                return rates(*args)
            return rates(*args), jnp.int32(0)

        def compute_rates(active, remaining, release, delivered, cap_in, cap_out, t):
            grp = None
            if policy_name == "omcoflow":
                grp = dst_t_grp[None, :] * (N + 2) + delivered + 1 + lag_grp[None, :]
            if mode is None:
                return base(active, cap_in, cap_out, remaining, release, grp)
            # class shaping: statically unrolled ascending-level passes
            # against leftovers (engine._class_shaped_rates).  Levels absent
            # from an instance leave its capacity arithmetic untouched, so
            # one unrolled program serves heterogeneous class sets exactly.
            if mode == "deadline" and dl_events:
                lim = jnp.minimum(gather_dst(cap_in), gather_src(cap_out))
                need = remaining / jnp.maximum(lim, EPS)
                # + EPS: complement of the wake rule in advance(), as in
                # engine._effective_classes
                urgent = (
                    (flow_cls > CLASS_TRAINING)
                    & ((flow_dl - t[:, None]) <= need + EPS)
                )
                eff = jnp.where(urgent, top_level, flow_cls)
                level_list = (top_level,) + tuple(levels)
            else:
                eff = flow_cls
                level_list = tuple(levels)
            if len(level_list) == 1:
                return base(active, cap_in, cap_out, remaining, release, grp)
            r = jnp.zeros((B, EG))
            rounds = jnp.int32(0)
            rem_i, rem_o = cap_in, cap_out
            for c in level_list:
                m = active & (eff == c)
                sub, n = base(m, rem_i, rem_o, remaining, release, grp)
                r = jnp.where(m, sub, r)
                rounds = rounds + n
                sm = jnp.where(m, sub, 0.0)
                rem_i = jnp.maximum(rem_i - sum_dst(sm), 0.0)
                rem_o = jnp.maximum(rem_o - sum_src(sm), 0.0)
            return r, rounds

        # the iteration axis of the recorded task and flow spans
        iters = jnp.arange(N)[None, None, :] if record else None

        # ---- settle: fixpoint of same-instant completions/arms/starts ----
        def settle_round(s: _State) -> _State:
            t = s.t
            comp = s.running & (s.tend <= t[:, None] + EPS)
            done = s.done + comp.astype(jnp.int32)
            running = s.running & ~comp
            tend = jnp.where(comp, jnp.inf, s.tend)

            fin = s.active & (s.remaining <= s.thresh)
            delivered = jnp.where(fin, s.delivered + 1, s.delivered)
            migleft = s.migleft
            if Gmax:
                # Gmax is tiny: a static loop of dense compares beats a
                # scatter on every settle round
                for g in range(Gmax):
                    col = E + g
                    dec = fin[:, col, None] & (
                        gate_task[:, col, None]
                        == jnp.arange(J, dtype=jnp.int32)[None, :]
                    )
                    migleft = migleft - dec.astype(jnp.int32)
            remaining = jnp.where(fin, 0.0, s.remaining)
            active = s.active & ~fin
            fstart_rec, fend_rec = s.fstart_rec, s.fend_rec
            if record:  # the finishing instance is delivered + 1
                fend_rec = jnp.where(
                    fin[:, :, None] & (iters == s.delivered[:, :, None]),
                    t[:, None, None], fend_rec,
                )

            nxt = delivered + 1
            src_done = done[:, src_t_eg]
            ready = (
                armable
                & ~active
                & (nxt <= last_eg[None, :])
                & (src_done >= nxt)
            )
            vn = pick_iter(vol, jnp.clip(nxt - 1, 0, N - 1))
            if no_cascade:  # statically no zero-volume instances anywhere
                zero = None
                arm = ready
            else:
                zero = ready & (vn <= EPS)
                arm = ready & (vn > EPS)
                delivered = jnp.where(zero, nxt, delivered)
            thresh = jnp.where(arm, EPS * jnp.maximum(1.0, vn), s.thresh)
            remaining = jnp.where(arm, vn, remaining)
            # only fifo's priority key ever reads release times
            release = (
                jnp.where(arm, t[:, None], s.release)
                if policy_name == "fifo"
                else s.release
            )
            active = active | arm
            if record:  # zero-volume instances are delivered, never logged
                fstart_rec = jnp.where(
                    arm[:, :, None] & (iters == (nxt - 1)[:, :, None]),
                    t[:, None, None], fstart_rec,
                )

            ncand = done + 1
            need = ncand[:, dst_t_e] - lag_e[None, :]
            ok = (need <= 0) | jnp.where(
                local_e, done[:, src_t_e] >= need, delivered[:, :E] >= need
            )
            # dep[b, j] iff no in-edge of j is violated: one matmul with the
            # static incidence instead of a scatter-min
            viol = jnp.einsum("be,ej->bj", (~ok).astype(jnp.float32), in_adj)
            dep = viol == 0.0
            can = (
                ~running
                & (ncand <= N)
                & dep
                & ~((ncand == 1) & (migleft > 0))
            )
            exn = pick_iter(ex, jnp.clip(ncand - 1, 0, N - 1))
            if use_slow:
                slow_t = pick(y_mat, tr_slow[s.seg])
                end_new = t[:, None] + exn * slow_t
            else:  # no slowdowns anywhere in the trace: ex * 1.0 == ex
                end_new = t[:, None] + exn
            tend = jnp.where(can, end_new, tend)
            running = running | can
            start_rec, end_rec = s.start_rec, s.end_rec
            if record:
                sel = can[:, :, None] & (
                    iters == jnp.clip(ncand - 1, 0, N - 1)[:, :, None]
                )
                start_rec = jnp.where(sel, t[:, None, None], start_rec)
                end_rec = jnp.where(sel, end_new[:, :, None], end_rec)

            # Everything a round changes is already visible to the later
            # steps of the SAME round (comp -> done -> arm/start, fin ->
            # delivered/migleft -> arm/start), so another round is needed
            # only for genuinely chained same-instant events: zero-volume
            # deliveries (which unlock the NEXT arming of that edge) and
            # zero-duration task starts (which complete next round).  When
            # the inputs statically rule both out, the fixpoint is one
            # round and the convergence check compiles away entirely.
            if no_cascade:
                changed = jnp.bool_(False)
            else:
                changed = zero.any() | (
                    can & (end_new <= t[:, None] + EPS)
                ).any()
            return (
                s._replace(
                    delivered=delivered,
                    thresh=thresh,
                    remaining=remaining,
                    release=release,
                    active=active,
                    done=done,
                    running=running,
                    tend=tend,
                    migleft=migleft,
                    start_rec=start_rec,
                    end_rec=end_rec,
                    fstart_rec=fstart_rec,
                    fend_rec=fend_rec,
                ),
                changed,
            )

        if no_cascade:

            def settle(s: _State) -> _State:
                return settle_round(s)[0]

        else:

            def settle(s: _State) -> _State:
                def cond(c):
                    return c[1]

                def body(c):
                    return settle_round(c[0])

                return lax.while_loop(cond, body, (s, jnp.bool_(True)))[0]

        # ---- advance: rate solve + next-event time + volume decrement ----
        def advance(s: _State) -> _State:
            if S > 1:
                cap_in = tr_bw_in[s.seg]
                cap_out = tr_bw_out[s.seg]
            else:  # static cluster: one shared capacity row
                cap_in = jnp.broadcast_to(tr_bw_in[0], (B, M))
                cap_out = jnp.broadcast_to(tr_bw_out[0], (B, M))
            # every rate rule returns 0 on inactive columns, so r > EPS
            # already implies active — no extra masking pass needed
            with jax.named_scope("rate_solve"):
                r, rounds = compute_rates(
                    s.active, s.remaining, s.release, s.delivered, cap_in,
                    cap_out, s.t,
                )
            dt = jnp.where(
                r > EPS,
                s.remaining / jnp.maximum(r, EPS),
                jnp.inf,
            )
            t_flow = s.t + jnp.min(dt, axis=1)
            # tend is inf whenever a task is not running, so no mask needed
            t_task = jnp.min(s.tend, axis=1)
            if S > 1:
                t_break = jnp.where(
                    s.seg + 1 < S,
                    tr_times[jnp.clip(s.seg + 1, 0, S - 1)],
                    jnp.inf,
                )
            else:
                t_break = jnp.full(B, jnp.inf)
            t_next = jnp.minimum(t_task, jnp.minimum(t_flow, t_break))
            if dl_events:
                # fourth event source: earliest possible EDF escalation of a
                # still-background flow (errs early; the wake re-checks)
                lim = jnp.minimum(gather_dst(cap_in), gather_src(cap_out))
                esc = flow_dl - s.remaining / jnp.maximum(lim, EPS)
                cand = (
                    s.active
                    & jnp.isfinite(flow_dl)
                    & (flow_cls > CLASS_TRAINING)
                    & (esc > s.t[:, None] + EPS)
                )
                t_esc = jnp.min(jnp.where(cand, esc, jnp.inf), axis=1)
                t_next = jnp.minimum(t_next, t_esc)
            alive = s.running.any(axis=1) | s.active.any(axis=1)
            bad = alive & ~jnp.isfinite(t_next)
            adv = alive & ~bad
            dtb = jnp.where(adv, t_next - s.t, 0.0)
            remaining = s.remaining - r * dtb[:, None]
            t = jnp.where(adv, t_next, s.t)
            agg = {}
            if collect:
                # in-program observability integrals: GB moved this step
                # per flow, folded onto the NIC / class axes
                dvol = r * dtb[:, None]
                agg["util_in"] = s.util_in + sum_dst(dvol)
                agg["util_out"] = s.util_out + sum_src(dvol)
                nrun = jnp.sum(oh_y & s.running[:, None, :], axis=2)
                agg["busy"] = s.busy + jnp.where(nrun > 0, dtb[:, None], 0.0)
                agg["clsgb"] = s.clsgb + jnp.stack(
                    [
                        jnp.sum(jnp.where(flow_cls == lvl, dvol, 0.0), axis=1)
                        for lvl in agg_levels
                    ],
                    axis=1,
                )
            seg = s.seg
            if S > 1:
                new_seg = (
                    jnp.searchsorted(tr_times, t, side="right").astype(jnp.int32)
                    - 1
                )
                seg = jnp.where(
                    adv, jnp.maximum(seg, jnp.clip(new_seg, 0, S - 1)), seg
                )
            return s._replace(
                t=t,
                nev=s.nev + adv.astype(jnp.int64),
                fill=s.fill + rounds,
                stuck=s.stuck | bad,
                seg=seg,
                remaining=remaining,
                # freeze deadlocked instances so the outer loop terminates
                active=s.active & ~bad[:, None],
                running=s.running & ~bad[:, None],
                **agg,
            )

        rec_shape = (B, J, N) if record else (1, 1, 1)
        agg_shape = (B, M) if collect else (1, 1)
        cls_shape = (B, max(1, len(agg_levels))) if collect else (1, 1)
        s = _State(
            k=jnp.int64(0),
            t=jnp.zeros(B),
            nev=jnp.zeros(B, dtype=jnp.int64),
            fill=jnp.int32(0),
            stuck=jnp.zeros(B, dtype=bool),
            seg=jnp.zeros(B, dtype=jnp.int32),
            delivered=delivered0,
            thresh=thresh0,
            remaining=remaining0,
            release=jnp.zeros((B, EG)),
            active=active0,
            done=jnp.zeros((B, J), dtype=jnp.int32),
            running=jnp.zeros((B, J), dtype=bool),
            tend=jnp.full((B, J), jnp.inf),
            migleft=migleft0,
            start_rec=jnp.full(rec_shape, jnp.nan),
            end_rec=jnp.full(rec_shape, jnp.nan),
            # migration columns armed at init start at 0 as instance 1
            fstart_rec=(
                jnp.where(active0[:, :, None] & (iters == 0), 0.0, jnp.nan)
                if record else None
            ),
            fend_rec=jnp.full((B, EG, N), jnp.nan) if record else None,
            util_in=jnp.zeros(agg_shape),
            util_out=jnp.zeros(agg_shape),
            busy=jnp.zeros(agg_shape),
            clsgb=jnp.zeros(cls_shape),
        )
        with jax.named_scope("settle"):
            s = settle(s)

        def cond(s: _State) -> Any:
            # the batch-wide form of advance's per-row `alive` test
            with jax.named_scope("advance"):
                return (s.running.any() | s.active.any()) & (s.k < max_events)

        def body(s: _State) -> _State:
            with jax.named_scope("advance"):
                s = advance(s)
            with jax.named_scope("settle"):
                s = settle(s)
            return s._replace(k=s.k + 1)

        s = lax.while_loop(cond, body, s)
        alive = s.running.any(axis=1) | s.active.any(axis=1)
        return (
            s.t, s.nev, s.stuck, alive, s.start_rec, s.end_rec,
            s.util_in, s.util_out, s.busy, s.clsgb, s.fill,
            s.fstart_rec, s.fend_rec,
        )

    return jax.jit(run)


def _runner_for(
    key: Tuple[Any, ...], build_kwargs: Dict[str, Any], args: Sequence[np.ndarray]
) -> _Runner:
    r = _RUNNERS.get(key)
    if r is None:
        r = _Runner(
            _build_runner(**build_kwargs),
            hashlib.blake2s(repr(key).encode(), digest_size=4).hexdigest(),
            tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args),
        )
        _RUNNERS[key] = r
        if obs_metrics.REGISTRY.enabled:
            # a miss traces and compiles a new program on its first call
            obs_metrics.REGISTRY.counter("engine.jax.runner_builds").inc()
    return r


def _op_names(hlo_text: str) -> Dict[str, str]:
    out = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        out[name] = m.group(1) if m else ""
    return out


def runner_scopes() -> Dict[str, Dict[str, str]]:
    """``{runner id: {HLO instruction name: op_name}}`` for every runner
    built in this process: how an operator reads a TPU profile by phase.

    A profile names each device operation by its instruction (``%while.37``,
    ``%fusion.216``); its ``op_name`` holds the runner's named scopes
    (``SCOPES``: ``.../settle/...``, ``.../advance/rate_solve/...``; ``""``
    where the compiler left an instruction none), and the ``runner``
    argument of the ``repro.engine`` span around the call names the runner.
    Runners of other widths reuse instruction names for other work, so a
    name is read against its own runner's map.  Each runner is lowered and
    compiled again on its first call's argument shapes (a persistent-cache
    hit where that cache is on), so call this after the profiled work, not
    inside it."""
    return {
        r.rid: _op_names(r.fn.lower(*r.args).compile().as_text())
        for r in _RUNNERS.values()
    }


def _assemble(
    workload: Workload,
    cluster: ClusterSpec,
    placements: Sequence[Placement],
    realizations: Sequence[Realization],
    policy: RatePolicy,
    Bp: int,
    *,
    record: bool,
    max_events: int,
    trace: Optional["BandwidthTrace"],
    migrations: Optional[Sequence[Optional[Sequence[MigrationFlow]]]],
    edge_classes: Optional["ArrayLike"],
    utilization: bool,
) -> Tuple[Tuple[np.ndarray, ...], Tuple[Any, ...], Dict[str, Any]]:
    """The runner's arguments, padded to ``Bp`` rows (repeating instance 0),
    its cache key and its build arguments."""
    shaped = isinstance(policy, ShapedPolicy)
    inner = policy.base if shaped else policy
    B = len(placements)
    N = realizations[0].n_iters
    J, E, M = workload.J, workload.E, cluster.M
    src_t, dst_t, lag = workload.edge_src, workload.edge_dst, workload.edge_lag

    vol = np.stack([r.volumes for r in realizations]).astype(np.float64)
    ex = np.stack([r.exec_times for r in realizations]).astype(np.float64)
    src_m = np.stack([p.y[src_t] for p in placements]).astype(np.int32)
    dst_m = np.stack([p.y[dst_t] for p in placements]).astype(np.int32)
    local = src_m == dst_m
    y_mat = np.stack([p.y for p in placements]).astype(np.int32)

    if migrations is not None and len(migrations) != B:
        raise ValueError(
            "migrations must give one (possibly None) entry per instance"
        )
    mig_lists = [
        check_migration_flows(m, M, J)
        for m in (migrations if migrations is not None else [None] * B)
    ]
    Gmax = max((len(m) for m in mig_lists), default=0)
    EG = E + Gmax
    flow_cls = np.zeros((B, EG), dtype=np.int32)
    flow_dl = np.full((B, EG), np.inf)
    gate_task = np.full((B, EG), -1, dtype=np.int32)
    ec = _check_edge_classes(edge_classes, E)
    if ec is not None:
        flow_cls[:, :E] = ec
    if Gmax:
        vol = np.concatenate([vol, np.zeros((B, Gmax, N))], axis=1)
        src_m = np.concatenate(
            [src_m, np.zeros((B, Gmax), dtype=np.int32)], axis=1
        )
        dst_m = np.concatenate(
            [dst_m, np.zeros((B, Gmax), dtype=np.int32)], axis=1
        )
        local = np.concatenate([local, np.ones((B, Gmax), dtype=bool)], axis=1)
        for b, ms in enumerate(mig_lists):
            for g, f in enumerate(ms):
                e = E + g
                src_m[b, e] = f.src
                dst_m[b, e] = f.dst
                vol[b, e, 0] = f.gb
                local[b, e] = (f.src == f.dst) or (f.gb <= EPS)
                flow_cls[b, e] = f.cls
                flow_dl[b, e] = f.deadline

    # initial flow state: migration columns pre-armed exactly like the
    # numpy engine (local / zero-volume flows delivered instantly)
    delivered0 = np.zeros((B, EG), dtype=np.int32)
    remaining0 = np.zeros((B, EG), dtype=np.float64)
    active0 = np.zeros((B, EG), dtype=bool)
    migleft0 = np.zeros((B, J), dtype=np.int32)
    for b, ms in enumerate(mig_lists):
        for g, f in enumerate(ms):
            e = E + g
            if local[b, e]:
                delivered0[b, e] = 1
                continue
            remaining0[b, e] = vol[b, e, 0]
            active0[b, e] = True
            if f.task >= 0:
                migleft0[b, f.task] += 1
                gate_task[b, e] = f.task
    thresh0 = np.where(active0, EPS * np.maximum(1.0, remaining0), 0.0)

    # trace arrays (S=1 static row when no trace: the same program serves
    # both, with the boundary/slowdown logic compiled out for S == 1)
    if trace is None:
        S = 1
        tr_times = np.zeros(1)
        tr_bw_in = np.asarray(cluster.bw_in, dtype=np.float64)[None, :]
        tr_bw_out = np.asarray(cluster.bw_out, dtype=np.float64)[None, :]
        tr_slow = np.ones((1, M))
    else:
        if trace.bw_in.shape[1] != M:
            raise ValueError(
                f"trace covers {trace.bw_in.shape[1]} machines but the "
                f"cluster has {M} — rebuild the trace after membership "
                "changes"
            )
        tr_times = np.asarray(trace.times, dtype=np.float64)
        S = len(tr_times)
        tr_bw_in = np.asarray(trace.bw_in, dtype=np.float64)
        tr_bw_out = np.asarray(trace.bw_out, dtype=np.float64)
        tr_slow = np.asarray(trace.slow, dtype=np.float64)

    mode = policy.mode if shaped else None
    use_slow = bool(trace is not None and not np.all(tr_slow == 1.0))
    # statically rule out same-instant cascades: every training-edge
    # instance carries real volume and no (slowdown-scaled) task runs in
    # zero time, so one settle round is always a fixpoint (migration
    # columns never re-arm: their zero-volume/local cases are resolved at
    # init and last_eg is 0 for them)
    min_slow = float(tr_slow.min()) if use_slow else 1.0
    no_cascade = bool(
        (E == 0 or vol[:, :E, :].min() > EPS)
        and float(ex.min()) * min_slow > EPS
    )
    dl_events = bool(
        shaped and policy.mode == "deadline" and np.isfinite(flow_dl).any()
    )
    levels = tuple(int(c) for c in np.unique(flow_cls)) if shaped else (0,)
    # class axis for the aggregate accumulators (independent of shaping:
    # unshaped runs still want migration-vs-training byte splits)
    agg_levels = (
        tuple(int(c) for c in np.unique(flow_cls)) if utilization else ()
    )

    # pad the batch to a power of two (repeat instance 0) so the jit cache
    # sees a handful of widths; padding rows are discarded on return
    if Bp != B:
        pad = Bp - B

        def _pad(a):
            return np.concatenate([a, np.repeat(a[:1], pad, axis=0)], axis=0)

        vol, ex, src_m, dst_m, local, flow_cls, flow_dl, gate_task = (
            _pad(a)
            for a in (
                vol, ex, src_m, dst_m, local, flow_cls, flow_dl, gate_task
            )
        )
        y_mat, delivered0, thresh0, remaining0, active0, migleft0 = (
            _pad(a)
            for a in (
                y_mat, delivered0, thresh0, remaining0, active0, migleft0
            )
        )

    key = (
        Bp, E, Gmax, J, N, M, S, inner.name, mode, dl_events, use_slow,
        no_cascade, levels,
        int(getattr(inner, "rounds", 4)), record, max_events,
        bool(utilization), agg_levels,
        src_t.tobytes(), dst_t.tobytes(), lag.tobytes(),
    )
    build = dict(
        B=Bp, E=E, Gmax=Gmax, J=J, N=N, M=M, S=S,
        policy_name=inner.name, mode=mode, dl_events=dl_events,
        use_slow=use_slow, no_cascade=no_cascade,
        levels=levels, rounds=int(getattr(inner, "rounds", 4)),
        record=record, max_events=max_events,
        collect=bool(utilization), agg_levels=agg_levels,
        src_t=src_t, dst_t=dst_t, lag=lag,
    )
    args = (
        vol, ex, src_m, dst_m,
        ~local & (np.arange(EG) < E)[None, :],  # armable
        local[:, :E], flow_cls, flow_dl, gate_task, y_mat,
        delivered0, thresh0, remaining0, active0, migleft0,
        tr_times, tr_bw_in, tr_bw_out, tr_slow,
    )
    return args, key, build


def simulate_batch_jax(
    workload: Workload,
    cluster: ClusterSpec,
    placements: Sequence[Placement],
    realizations: Sequence[Realization],
    policy: "RatePolicy | str" = "oes",
    record: bool = False,
    max_events: int = 50_000_000,
    trace: Optional["BandwidthTrace"] = None,
    migrations: Optional[Sequence[Optional[Sequence[MigrationFlow]]]] = None,
    shaping: Optional[str] = None,
    edge_classes: Optional["ArrayLike"] = None,
    utilization: bool = False,
) -> List[ScheduleResult]:
    """``engine.simulate_batch`` on the jitted JAX backend.

    Same signature and event semantics; returns one ``ScheduleResult`` per
    instance agreeing with the numpy engine at ``PARITY_RTOL`` (float64).
    ``record=True`` fills ``task_events`` and ``flow_log`` (``None``
    otherwise); ``n_events`` counts jitted lock-step iterations — see the
    module docstring for the exact contract.  ``utilization=True`` compiles
    the in-program aggregate accumulators into the loop (its own jit cache
    entry) and fills ``ScheduleResult.aggregates`` with per-machine NIC
    utilization integrals (``nic_in_gb``/``nic_out_gb``), busy-time
    integrals (``busy_s``) and per-class delivered bytes (``class_gb``):
    the totals a flow log gives, without recording one.
    """
    if not HAVE_JAX:  # pragma: no cover
        raise RuntimeError(
            "backend='jax' requested but jax is not importable: "
            f"{JAX_IMPORT_ERROR!r}"
        )
    policy = resolve_policy(policy, shaping)
    shaped = isinstance(policy, ShapedPolicy)
    inner = policy.base if shaped else policy
    if inner.name not in JAX_POLICIES:
        raise ValueError(
            f"the jax engine backend supports the built-in rate policies "
            f"{JAX_POLICIES}, got {inner.name!r} — use backend='numpy' for "
            "custom policies"
        )
    B = len(placements)
    if B == 0:
        return []
    if len(realizations) != B:
        raise ValueError("placements and realizations must have equal length")
    N = realizations[0].n_iters
    if any(r.n_iters != N for r in realizations):
        raise ValueError("all realizations in a batch must share n_iters")
    Bp = _next_pow2(B)
    with span("repro.engine", width=B, padded=Bp) as eng:
        with span("repro.engine.assemble"):
            args, key, build = _assemble(
                workload, cluster, placements, realizations, policy, Bp,
                record=record, max_events=max_events, trace=trace,
                migrations=migrations, edge_classes=edge_classes,
                utilization=utilization,
            )
            runner = _runner_for(key, build, args)
        eng.set_metadata(runner=runner.rid)
        with span("repro.engine.dispatch"):
            *outs, fill, fstart_rec, fend_rec = runner.fn(*args)
        with span("repro.engine.fetch"):
            t, nev, stuck, alive = (np.asarray(a)[:B] for a in outs[:4])
            start_rec, end_rec, util_in, util_out, busy, clsgb = outs[4:]
            if record:
                start_rec = np.asarray(start_rec)[:B]
                end_rec = np.asarray(end_rec)[:B]
                fstart_rec = np.asarray(fstart_rec)[:B]
                fend_rec = np.asarray(fend_rec)[:B]
            if utilization:
                util_in = np.asarray(util_in)[:B]
                util_out = np.asarray(util_out)[:B]
                busy = np.asarray(busy)[:B]
                clsgb = np.asarray(clsgb)[:B]
        if stuck.any():  # pragma: no cover - mirrors the numpy engine's guard
            raise RuntimeError("no progress: flows active but zero rates")
        if alive.any():  # pragma: no cover
            raise RuntimeError("event limit exceeded — dependency deadlock?")
        with span("repro.engine.unpack"):
            agg_levels = build["agg_levels"]
            out: List[ScheduleResult] = []
            n_flows = 0
            for b in range(B):
                events: List[TaskEvent] = []
                flow_log = None
                if record:
                    # task instances by (start, task, iteration); flow
                    # instances by (end, edge, iteration): the numpy
                    # engine's delivery order, ties by edge
                    j, n = np.nonzero(~np.isnan(start_rec[b]))
                    st = start_rec[b, j, n]
                    o = np.lexsort((n, j, st))
                    events = [
                        TaskEvent(*ev) for ev in zip(
                            j[o].tolist(), (n[o] + 1).tolist(),
                            st[o].tolist(), end_rec[b, j[o], n[o]].tolist(),
                        )
                    ]
                    e, n = np.nonzero(~np.isnan(fend_rec[b]))
                    en = fend_rec[b, e, n]
                    o = np.lexsort((n, e, en))
                    flow_log = list(zip(
                        e[o].tolist(), (n[o] + 1).tolist(),
                        fstart_rec[b, e[o], n[o]].tolist(), en[o].tolist(),
                    ))
                    n_flows += len(flow_log)
                agg = None
                if utilization:
                    agg = {
                        "nic_in_gb": util_in[b].copy(),
                        "nic_out_gb": util_out[b].copy(),
                        "busy_s": busy[b].copy(),
                        "class_gb": {
                            lvl: float(clsgb[b, i])
                            for i, lvl in enumerate(agg_levels)
                        },
                    }
                out.append(
                    ScheduleResult(
                        makespan=float(t[b]),
                        task_events=events,
                        flow_log=flow_log,
                        n_events=int(nev[b]),
                        policy=policy.name,
                        aggregates=agg,
                    )
                )
    if obs_metrics.REGISTRY.enabled:
        # once per call, pre-aggregated: the padded rows run on the device
        # too, and the call's lock-step loop runs as long as its longest row
        reg = obs_metrics.REGISTRY
        reg.counter("engine.jax.calls").inc()
        reg.counter("engine.jax.rows").inc(B)
        reg.counter("engine.jax.padded_rows").inc(Bp - B)
        reg.counter("engine.jax.lockstep_iters").inc(int(nev.max()))
        reg.counter("engine.jax.fill_rounds").inc(int(fill))
        if record:
            reg.counter("engine.jax.recorded_flows").inc(n_flows)
    return out
