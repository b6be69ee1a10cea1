"""JAX engine backend: parity matrix, golden tolerance, backend routing.

The jitted engine (``repro.core.engine_jax``) re-implements the numpy
reference event loop as one ``lax.while_loop`` array program; its contract
is agreement at the PINNED tolerance ``PARITY_RTOL`` / ``PARITY_ATOL``
(documented in ROADMAP.md): both engines run float64 end to end — x64 is
enabled at engine_jax import, asserted below — but XLA may contract
multiply-adds, so schedules can drift a few ULPs per event and
bit-equality is deliberately NOT the contract (the numpy engine's own
batch-vs-scalar bitwise promise is certified in test_batch_engine.py).

Covered here:
  * the full parity matrix — 5 policies x {unshaped, strict, deadline}
    x {static, dynamic-trace, migration-loaded}, batched (width 3);
  * the golden-schedule suite (every job/regime/policy cell of
    tests/golden/golden_schedules.json) at the same tolerance, width-1;
  * the zero-volume / zero-exec cascade stress that forces the general
    multi-round settle fixpoint (the fast single-round specialisation is
    compiled out of easy workloads, so nothing else exercises this path);
  * backend routing: kwarg > REPRO_ENGINE_BACKEND env > numpy default,
    loud errors for unknown backends / missing jax / custom policies;
  * the per-backend ``plan()`` chain-count defaults (re-derived from the
    measured sweep in the ROADMAP perf log);
  * a hypothesis property sweep over random jobs (skipped when hypothesis
    is not installed).

``n_events`` is NOT compared anywhere: the jax engine counts lock-step
iterations (zero-duration cascades settle inside one), a documented
divergence.  ``flow_log`` is ``None`` on the jax backend (never
recorded, distinct from numpy's recorded-but-empty ``[]``);
``task_events`` are exact and are what the start-matrix checks consume.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (
    ENGINE_BACKENDS,
    MigrationFlow,
    build_gnn_workload,
    heterogeneous_cluster,
    ifs_placement,
    resolve_backend,
    simulate,
    simulate_batch,
)
from repro.core.dgtp import DEFAULT_N_CHAINS, plan
from repro.core.engine import OESRate, RatePolicy
from repro.core import engine_jax
from repro.core.engine_jax import PARITY_ATOL, PARITY_RTOL, simulate_batch_jax
from repro.dynamics import DynamicsEvent, trace_from_events

from test_golden_schedules import GOLDEN_PATH, JOBS, REGIMES, _cases

POLICIES = ("oes", "oes_strict", "fifo", "mrtf", "omcoflow")
SHAPINGS = (None, "strict", "deadline")


def _assert_parity(wl, ref, got, n_iters):
    """Makespan + full task-start schedule agreement at the pinned tol."""
    assert np.isclose(ref.makespan, got.makespan,
                      rtol=PARITY_RTOL, atol=PARITY_ATOL)
    sm_r = ref.task_start_matrix(wl.J, n_iters)
    sm_g = got.task_start_matrix(wl.J, n_iters)
    assert np.allclose(sm_r, sm_g, rtol=PARITY_RTOL, atol=PARITY_ATOL,
                       equal_nan=True)


# ---------------------------------------------------------------------------
# the parity matrix (batched, width 3)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def matrix_case():
    wl = build_gnn_workload(
        n_stores=2, n_workers=2, samplers_per_worker=2, n_ps=1, n_iters=4,
        store_to_sampler_gb=1.0, sampler_to_worker_gb=0.5, grad_gb=0.2,
        store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2, pmr=1.3,
    )
    cluster = heterogeneous_cluster(3, seed=0)
    placements = [ifs_placement(wl, cluster, seed=s) for s in range(3)]
    reals = [wl.realize(seed=s) for s in range(3)]
    dyn = trace_from_events(cluster, [
        DynamicsEvent(t0=1.5, t1=6.0, machine=0, bw_scale=0.4),
        DynamicsEvent(t0=3.0, machine=None, bw_scale=0.75, slowdown=1.2),
    ])
    y = placements[0].y
    # per-instance heterogeneous flow sets incl. a None entry: gated with a
    # tight deadline, gated loose, ungated background
    migs = [
        [
            MigrationFlow(src=int((y[0] + 1) % cluster.M), dst=int(y[0]),
                          gb=1.2, task=0, deadline=0.5),
            MigrationFlow(src=0, dst=1, gb=0.5),
        ],
        None,
        [MigrationFlow(src=1, dst=0, gb=0.8, task=wl.J - 1, deadline=3.0)],
    ]
    return wl, cluster, placements, reals, dyn, migs


@pytest.mark.parametrize("policy", POLICIES)
def test_parity_matrix(matrix_case, policy):
    """5 policies x 3 shapings x {static, dynamic, migration} at width 3."""
    wl, cluster, placements, reals, dyn, migs = matrix_case
    for trace, migrations in ((None, None), (dyn, None), (dyn, migs)):
        for shaping in SHAPINGS:
            ref = simulate_batch(
                wl, cluster, placements, reals, policy=policy, record=True,
                trace=trace, migrations=migrations, shaping=shaping,
            )
            got = simulate_batch_jax(
                wl, cluster, placements, reals, policy=policy, record=True,
                trace=trace, migrations=migrations, shaping=shaping,
            )
            for b in range(3):
                _assert_parity(wl, ref[b], got[b], reals[0].n_iters)


@pytest.mark.parametrize("policy", POLICIES)
def test_cascade_settle_parity(policy):
    """Zero-volume edges + zero-exec tasks: instant deliveries and
    zero-duration task starts cascade INSIDE one event instant, forcing
    the jax engine's general multi-round settle fixpoint (workloads with
    all-positive volumes/exec compile the single-round specialisation, so
    the matrix above never reaches this path)."""
    for seed in (0, 1):
        wl = build_gnn_workload(
            n_stores=2, n_workers=2, samplers_per_worker=1, n_ps=1,
            n_iters=4, store_to_sampler_gb=0.6, sampler_to_worker_gb=0.0,
            grad_gb=0.3, store_exec_s=0.3, sampler_exec_s=0.0,
            worker_exec_s=0.5, ps_exec_s=0.2, pmr=1.2,
        )
        cluster = heterogeneous_cluster(3, seed=seed)
        placements = [ifs_placement(wl, cluster, seed=s) for s in range(3)]
        reals = [wl.realize(seed=s) for s in range(3)]
        ref = simulate_batch(wl, cluster, placements, reals, policy=policy,
                             record=True)
        got = simulate_batch_jax(wl, cluster, placements, reals,
                                 policy=policy, record=True)
        for b in range(3):
            _assert_parity(wl, ref[b], got[b], reals[0].n_iters)


# ---------------------------------------------------------------------------
# golden-schedule suite at the pinned tolerance (width-1 scalar routing)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def golden():
    import json

    assert GOLDEN_PATH.exists()
    return json.loads(GOLDEN_PATH.read_text())


@pytest.mark.parametrize(
    "name,regime", [(n, r) for n in JOBS for r in REGIMES]
)
def test_golden_suite_jax(golden, name, regime):
    """Every pinned golden cell, reproduced by the jax backend through the
    scalar ``simulate(..., backend="jax")`` route at PARITY_RTOL.  The
    pinned JSON is the numpy engine's exact output, so this certifies the
    backends against ONE shared history (a jax change that drifts past the
    tolerance fails here even if both engines drift together vs the pin)."""
    for (nm, rg, wl, cluster, placement, realization, trace, flows,
         shaping) in _cases():
        if (nm, rg) != (name, regime):
            continue
        for policy in POLICIES:
            pinned = golden[name][regime][policy]
            res = simulate(
                wl, cluster, placement, realization, policy=policy,
                record=True, trace=trace, migrations=flows, shaping=shaping,
                backend="jax",
            )
            assert np.isclose(res.makespan, pinned["makespan"],
                              rtol=PARITY_RTOL, atol=PARITY_ATOL)
            starts = res.task_start_matrix(wl.J, realization.n_iters)
            assert np.allclose(starts, np.array(pinned["task_start"]),
                               rtol=PARITY_RTOL, atol=PARITY_ATOL)
            assert res.flow_log is None  # documented jax-backend divergence


# ---------------------------------------------------------------------------
# backend routing + errors
# ---------------------------------------------------------------------------
@pytest.fixture()
def routing_case():
    wl = build_gnn_workload(
        n_stores=2, n_workers=1, samplers_per_worker=1, n_ps=1, n_iters=3,
        store_to_sampler_gb=0.5, sampler_to_worker_gb=0.3, grad_gb=0.2,
        store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2,
    )
    cluster = heterogeneous_cluster(3, seed=0)
    return wl, cluster, ifs_placement(wl, cluster, seed=0), wl.realize(seed=0)


def test_backend_kwarg_and_env_routing(routing_case, monkeypatch):
    wl, cluster, p, r = routing_case
    ref = simulate(wl, cluster, p, r, backend="numpy")
    via_kwarg = simulate(wl, cluster, p, r, backend="jax")
    _assert_parity(wl, ref, via_kwarg, r.n_iters)
    # env default: kwarg omitted, REPRO_ENGINE_BACKEND selects jax
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "jax")
    assert resolve_backend() == "jax"
    via_env = simulate_batch(wl, cluster, [p], [r])[0]
    assert via_env.flow_log is None  # proves the jax engine actually ran
    _assert_parity(wl, ref, via_env, r.n_iters)
    # explicit kwarg beats the env
    via_override = simulate_batch(wl, cluster, [p], [r], backend="numpy")[0]
    assert via_override.makespan == ref.makespan
    monkeypatch.delenv("REPRO_ENGINE_BACKEND")
    assert resolve_backend() == "numpy"
    assert ENGINE_BACKENDS == ("numpy", "jax")


def test_backend_errors(routing_case, monkeypatch):
    wl, cluster, p, r = routing_case
    with pytest.raises(ValueError, match="unknown engine backend"):
        simulate(wl, cluster, p, r, backend="torch")
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "not-a-backend")
    with pytest.raises(ValueError, match="REPRO_ENGINE_BACKEND"):
        resolve_backend()
    monkeypatch.delenv("REPRO_ENGINE_BACKEND")
    # jax requested while jax is unimportable: loud RuntimeError carrying
    # the original import error, not a silent numpy fallback
    monkeypatch.setattr(engine_jax, "HAVE_JAX", False)
    monkeypatch.setattr(engine_jax, "JAX_IMPORT_ERROR",
                        ImportError("no module named jax"))
    with pytest.raises(RuntimeError, match="jax is not importable"):
        resolve_backend("jax")


def test_custom_policy_rejected(routing_case):
    """Custom RatePolicy callables only exist in Python; the jitted engine
    must refuse them loudly and point at backend='numpy'."""
    wl, cluster, p, r = routing_case

    class Custom(RatePolicy):
        name = "custom"

        def rates(self, **kw):  # pragma: no cover - never called
            return OESRate().rates(**kw)

    with pytest.raises(ValueError, match="backend='numpy'"):
        simulate_batch_jax(wl, cluster, [p], [r], policy=Custom())


def test_float64_is_explicit(routing_case):
    """The backend's precision choice is x64 (enabled at engine_jax
    import): float64 end to end, matching the numpy engine's dtype — the
    parity tolerance accounts for reassociation only, not precision."""
    assert jax.config.jax_enable_x64
    import jax.numpy as jnp

    assert jnp.asarray(1.0).dtype == jnp.float64
    wl, cluster, p, r = routing_case
    res = simulate(wl, cluster, p, r, backend="jax")
    assert isinstance(res.makespan, float)


@pytest.mark.parametrize("from_env", (True, False), ids=("env", "repo-default"))
def test_compile_cache_lands_where_configured(from_env, tmp_path, monkeypatch):
    """``enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` (which JAX
    reads at start-up) stands when set; otherwise compiled programs land
    in the fixed repository directory."""
    from jax.experimental.compilation_cache import compilation_cache

    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    monkeypatch.setattr(engine_jax, "REPO_COMPILE_CACHE", repo_dir)
    saved = {
        k: getattr(jax.config, k)
        for k in ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs")
    }
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        jax.config.update("jax_compilation_cache_dir", str(env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = env_dir if from_env else repo_dir
    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compilation_cache.reset_cache()
        assert engine_jax.enable_compile_cache() == str(want)
        jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(5.0 + from_env)).block_until_ready()
        assert any(want.iterdir())
        assert not (env_dir if not from_env else repo_dir).exists()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# plan() chain-count defaults (re-derived sweep, see ROADMAP perf log)
# ---------------------------------------------------------------------------
def test_plan_n_chains_defaults(routing_case):
    """The per-backend defaults are pinned: numpy keeps the PR-1 value 8,
    jax runs 16 (the measured sweep shows ~flat wall 8->16 on the jitted
    engine with best-makespan unchanged, so the wider basin sweep is
    free; beyond 16 per-chain memoisation stops paying).  An explicit
    n_chains= always wins over the default."""
    assert DEFAULT_N_CHAINS == {"numpy": 8, "jax": 16}
    import inspect

    assert inspect.signature(plan).parameters["n_chains"].default is None
    wl, cluster, p, r = routing_case
    # the backend knob reaches plan() end to end (tiny budget: smoke only)
    out = plan(wl, cluster, realization=r, budget=8, sim_iters=3,
               n_chains=2, backend="jax")
    assert out.schedule.makespan > 0
    assert out.schedule.flow_log  # committed schedule stays on numpy


def test_plan_env_jax_keeps_numpy_commit(routing_case, monkeypatch):
    """Regression: with REPRO_ENGINE_BACKEND=jax set globally, plan()'s
    COMMITTED schedule must still run on numpy — the certificate's chain
    construction follows the recorded flow_log, which the jax engine never
    produces (an env-routed commit used to yield an empty flow_log and a
    degenerate ~0 chain lower bound)."""
    wl, cluster, p, r = routing_case
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "jax")
    out = plan(wl, cluster, realization=r, budget=8, sim_iters=3, n_chains=2)
    assert out.schedule.flow_log
    assert out.certificate.lower_bound > 0.1
    ref = plan(wl, cluster, realization=r, budget=8, sim_iters=3, n_chains=2,
               backend="jax")
    assert out.certificate.lower_bound == ref.certificate.lower_bound


# ---------------------------------------------------------------------------
# hypothesis property sweep (optional dependency)
# ---------------------------------------------------------------------------
def _parity_property(seed, policy):
    """Random small jobs/clusters/placements: jax == numpy at the pinned
    tolerance for every policy.  Bounded example count — the matrix above
    is the systematic sweep; this hunts structure the grid misses."""
    rng = np.random.default_rng(seed)
    wl = build_gnn_workload(
        n_stores=int(rng.integers(2, 4)),
        n_workers=int(rng.integers(1, 4)),
        samplers_per_worker=int(rng.integers(1, 3)),
        n_ps=1, n_iters=int(rng.integers(2, 6)),
        store_to_sampler_gb=float(rng.uniform(0.1, 2.0)),
        sampler_to_worker_gb=float(rng.uniform(0.0, 1.0)),
        grad_gb=float(rng.uniform(0.05, 0.4)),
        store_exec_s=0.3, sampler_exec_s=float(rng.uniform(0.0, 0.5)),
        worker_exec_s=0.8, ps_exec_s=0.2, pmr=1.3,
    )
    cluster = heterogeneous_cluster(3, seed=seed)
    try:
        placements = [ifs_placement(wl, cluster, seed=s) for s in range(2)]
    except ValueError:
        return  # infeasible draw: nothing to compare
    reals = [wl.realize(seed=s) for s in range(2)]
    ref = simulate_batch(wl, cluster, placements, reals, policy=policy,
                         record=True)
    got = simulate_batch_jax(wl, cluster, placements, reals, policy=policy,
                             record=True)
    for b in range(2):
        _assert_parity(wl, ref[b], got[b], reals[0].n_iters)


def test_parity_property():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    hypothesis.given(
        seed=st.integers(0, 10_000), policy=st.sampled_from(POLICIES)
    )(hypothesis.settings(max_examples=8, deadline=None)(_parity_property))()
