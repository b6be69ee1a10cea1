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
    is not installed);
  * the oes runner's own outputs pinned bit for bit against
    tests/golden/jax_oes_schedules.json (the golden jobs in every regime,
    and a testbed batch of width 16), and the ``engine.jax.fill_rounds``
    counter of its filling rounds.

``n_events`` is never compared with numpy's: the jax engine counts lock-step
iterations (zero-duration cascades settle inside one), a documented
divergence.  A recorded run's ``flow_log`` holds the numpy engine's flow
instances, compared sorted at the same tolerance (the order of flows that
end at one instant is the engine's own); an unrecorded one is ``None`` on
both backends.  ``record=False`` runners, which every search and scoring
call runs, are pinned by the text of their lowered program.
"""
import contextlib
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (
    ENGINE_BACKENDS,
    ClusterSpec,
    Machine,
    MigrationFlow,
    Placement,
    build_gnn_workload,
    heterogeneous_cluster,
    ifs_placement,
    resolve_backend,
    simulate,
    simulate_batch,
    testbed_cluster,
)
from repro.core.dgtp import DEFAULT_N_CHAINS, plan
from repro.core.engine import OESRate, RatePolicy
from repro.core import engine_jax
from repro.core.engine_jax import PARITY_ATOL, PARITY_RTOL, simulate_batch_jax
from repro.core.profiles import OGBN_PRODUCTS, build_workload_from_profile
from repro.dynamics import DynamicsEvent, trace_from_events
from repro.obs import REGISTRY

from test_golden_schedules import GOLDEN_PATH, JOBS, REGIMES, _cases

POLICIES = ("oes", "oes_strict", "fifo", "mrtf", "omcoflow")
SHAPINGS = (None, "strict", "deadline")


def _assert_parity(wl, ref, got, n_iters):
    """Makespan, full task-start schedule and flow log agreement at the
    pinned tol."""
    assert np.isclose(ref.makespan, got.makespan,
                      rtol=PARITY_RTOL, atol=PARITY_ATOL)
    sm_r = ref.task_start_matrix(wl.J, n_iters)
    sm_g = got.task_start_matrix(wl.J, n_iters)
    assert np.allclose(sm_r, sm_g, rtol=PARITY_RTOL, atol=PARITY_ATOL,
                       equal_nan=True)
    _assert_flow_log_parity(ref, got)


def _assert_flow_log_parity(ref, got):
    """The same flow instances, (edge, iteration) for (edge, iteration),
    with starts and ends at the pinned tol; unrecorded on both or neither."""
    if ref.flow_log is None:
        assert got.flow_log is None
        return
    want, have = sorted(ref.flow_log), sorted(got.flow_log)
    assert [f[:2] for f in have] == [f[:2] for f in want]
    assert np.allclose(np.array([f[2:] for f in have]).reshape(-1, 2),
                       np.array([f[2:] for f in want]).reshape(-1, 2),
                       rtol=PARITY_RTOL, atol=PARITY_ATOL)


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
            ref = simulate(
                wl, cluster, placement, realization, policy=policy,
                record=True, trace=trace, migrations=flows, shaping=shaping,
                backend="numpy",
            )
            assert res.flow_log
            _assert_flow_log_parity(ref, res)


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
    assert out.schedule.flow_log  # the jax commit records its flows


def test_plan_env_jax_commits_on_jax(routing_case, monkeypatch):
    """With REPRO_ENGINE_BACKEND=jax, plan() commits on the jax engine,
    which records the flow log the certificate's chain construction walks
    (a commit without it would give a degenerate ~0 chain lower bound):
    the certificate holds and its bound is the numpy commit's."""
    wl, cluster, p, r = routing_case
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "jax")
    with _registry_on() as reg:
        out = plan(wl, cluster, realization=r, budget=8, sim_iters=3,
                   n_chains=2)
        recorded = reg.snapshot()["engine.jax.recorded_flows"]["value"]
    assert out.schedule.flow_log
    assert recorded == len(out.schedule.flow_log)  # the commit ran on jax
    assert out.certificate.holds
    assert out.certificate.lower_bound > 0.1
    ref = plan(wl, cluster, realization=r, budget=8, sim_iters=3, n_chains=2,
               backend="numpy")
    assert np.array_equal(out.placement.y, ref.placement.y)
    assert np.isclose(out.certificate.lower_bound, ref.certificate.lower_bound,
                      rtol=PARITY_RTOL, atol=PARITY_ATOL)
    _assert_parity(wl, ref.schedule, out.schedule, r.n_iters)


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


# ---------------------------------------------------------------------------
# the oes runner pinned bit for bit (tests/golden/jax_oes_schedules.json)
# ---------------------------------------------------------------------------
# Unlike the parity checks above, these are the jax engine's OWN exact
# outputs: a change to the oes rate solve that moves one schedule by one
# ULP, one lock-step iteration or one filling round fails here.
# Regenerate (ONLY for an intended change, with the diff reviewed):
#   PYTHONPATH=src python tests/test_jax_engine.py --regen <case...>
# Cases already pinned are NEVER overwritten unless named; a bare --regen
# only fills in missing ones.
JAX_OES_PATH = GOLDEN_PATH.parent / "jax_oes_schedules.json"
TESTBED_WIDTH = 16


def _testbed_batch():
    """The paper's 4-server testbed job (products, 4 iterations) at width
    16: seeded random placements, so the batch holds many NIC-pair mixes."""
    wl = build_workload_from_profile(
        OGBN_PRODUCTS, n_stores=4, n_workers=6, samplers_per_worker=2,
        n_ps=1, n_iters=4,
    )
    cluster = testbed_cluster()
    rng = np.random.default_rng(0)
    placements = [Placement(rng.integers(0, cluster.M, size=wl.J))
                  for _ in range(TESTBED_WIDTH)]
    reals = [wl.realize(seed=s) for s in range(TESTBED_WIDTH)]
    return wl, cluster, placements, reals


def _oes_cases():
    """{case id: (workload, cluster, placements, realizations, kwargs)}:
    the golden jobs in every regime at width 1, and the testbed batch."""
    out = {}
    for (name, regime, wl, cluster, placement, realization, trace, flows,
         shaping) in _cases():
        out[f"{name}-{regime}"] = (
            wl, cluster, [placement], [realization],
            dict(trace=trace, shaping=shaping,
                 migrations=None if flows is None else [flows]),
        )
    wl, cluster, placements, reals = _testbed_batch()
    out[f"testbed-w{TESTBED_WIDTH}"] = (wl, cluster, placements, reals, {})
    return out


@contextlib.contextmanager
def _registry_on():
    was = REGISTRY.enabled
    REGISTRY.enable()
    REGISTRY.reset()
    try:
        yield REGISTRY
    finally:
        REGISTRY.enabled = was
        REGISTRY.reset()


def _run_counted(wl, cluster, placements, reals, policy="oes", **kw):
    """The call's results and its ``engine.jax.*`` counters."""
    with _registry_on() as reg:
        res = simulate_batch_jax(wl, cluster, placements, reals,
                                 policy=policy, record=True, **kw)
        snap = reg.snapshot()
    return res, {k: v["value"] for k, v in snap.items()}


def _oes_record(case):
    wl, cluster, placements, reals, kw = case
    res, counters = _run_counted(wl, cluster, placements, reals, **kw)
    n_iters = reals[0].n_iters
    return {
        "fill_rounds": int(counters["engine.jax.fill_rounds"]),
        "results": [
            {
                "makespan": r.makespan,
                "n_events": r.n_events,
                "task_start": r.task_start_matrix(wl.J, n_iters).tolist(),
            }
            for r in res
        ],
    }


def regen_jax_oes(named=(), path=JAX_OES_PATH):
    """The pinned file with missing cases (and those ``named``) simulated
    anew; every other case is kept byte for byte."""
    cases = _oes_cases()
    unknown = set(named) - set(cases)
    if unknown:
        raise ValueError(f"unknown case(s) {sorted(unknown)}; known: {sorted(cases)}")
    existing = json.loads(path.read_text()) if path.exists() else {}
    return {
        cid: (_oes_record(case) if cid in named or cid not in existing
              else existing[cid])
        for cid, case in cases.items()
    }


@pytest.fixture(scope="module")
def jax_oes_pinned():
    assert JAX_OES_PATH.exists()
    return json.loads(JAX_OES_PATH.read_text())


@pytest.fixture(scope="module")
def oes_cases():
    return _oes_cases()


OES_CASE_IDS = [f"{n}-{r}" for n in JOBS for r in REGIMES] + [f"testbed-w{TESTBED_WIDTH}"]


@pytest.mark.parametrize("case_id", OES_CASE_IDS)
def test_jax_oes_pinned_bit_for_bit(jax_oes_pinned, oes_cases, case_id):
    """Makespan, lock-step iterations, every task start and the filling
    rounds of the call equal the pinned values exactly."""
    want = jax_oes_pinned[case_id]
    got = _oes_record(oes_cases[case_id])
    assert got["fill_rounds"] == want["fill_rounds"]
    assert len(got["results"]) == len(want["results"])
    for b, (g, w) in enumerate(zip(got["results"], want["results"])):
        assert g["makespan"] == w["makespan"], (case_id, b)
        assert g["n_events"] == w["n_events"], (case_id, b)
        assert np.array_equal(np.asarray(g["task_start"]),
                              np.asarray(w["task_start"])), (case_id, b)


def test_jax_oes_pins_cover_every_case(jax_oes_pinned):
    assert sorted(jax_oes_pinned) == sorted(OES_CASE_IDS)


# ---------------------------------------------------------------------------
# engine.jax.fill_rounds: the oes filling loop's rounds, summed per call
# ---------------------------------------------------------------------------
def _two_machine_cluster(bw):
    return ClusterSpec(machines=[
        Machine(name=f"m{i}", resources={"mem": 64.0, "cpu": 16.0, "gpu": 2.0},
                bw_in=bw, bw_out=bw)
        for i in range(2)
    ])


def test_fill_rounds_one_round_per_solve_on_one_nic_pair():
    """Stores on machine 1, every other task on machine 0: every training
    flow and a long ungated migration flow run 1 -> 0, so each rate solve
    freezes all its flows in its first round, and the migration flow keeps
    a flow active in every lock-step iteration."""
    wl = build_gnn_workload(
        n_stores=2, n_workers=1, samplers_per_worker=2, n_ps=1, n_iters=3,
        store_to_sampler_gb=0.5, sampler_to_worker_gb=0.3, grad_gb=0.2,
        store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2,
    )
    cluster = _two_machine_cluster(1.25)
    y = np.array([1 if t.name.startswith("store") else 0 for t in wl.tasks])
    reals = [wl.realize(seed=s) for s in range(3)]
    flows = [MigrationFlow(src=1, dst=0, gb=50.0)]
    res, counters = _run_counted(wl, cluster, [Placement(y)] * 3, reals,
                                 migrations=[flows] * 3)
    iters = counters["engine.jax.lockstep_iters"]
    assert iters == max(r.n_events for r in res) > 3 * 3
    assert counters["engine.jax.fill_rounds"] == iters


@pytest.mark.parametrize("shaping,levels", ((None, 1), ("strict", 2)))
def test_fill_rounds_count_every_class_level(shaping, levels):
    """Every task on machine 0 and two migration flows of equal volume on
    disjoint NICs (0 -> 1 and 1 -> 0) in two traffic classes: each flow
    saturates its own pair in one round and both run to the last
    iteration.  Unshaped, one solve serves both; under class shaping each
    class level runs its own filling loop, and the counter sums them."""
    wl = build_gnn_workload(
        n_stores=1, n_workers=1, samplers_per_worker=1, n_ps=1, n_iters=2,
        store_to_sampler_gb=0.5, sampler_to_worker_gb=0.3, grad_gb=0.2,
        store_exec_s=0.3, sampler_exec_s=0.4, worker_exec_s=0.8,
        ps_exec_s=0.2,
    )
    cluster = _two_machine_cluster(2.5)
    flows = [MigrationFlow(src=0, dst=1, gb=20.0, cls=1),
             MigrationFlow(src=1, dst=0, gb=20.0, cls=2)]
    res, counters = _run_counted(
        wl, cluster, [Placement(np.zeros(wl.J, dtype=np.int64))],
        [wl.realize(seed=0)], migrations=[flows], shaping=shaping,
    )
    iters = counters["engine.jax.lockstep_iters"]
    assert iters == res[0].n_events > 2
    assert res[0].makespan == pytest.approx(20.0 / 2.5)
    assert counters["engine.jax.fill_rounds"] == levels * iters


def test_recorded_flows_count_the_flow_log_rows(matrix_case):
    """``engine.jax.recorded_flows``: the flow log rows a recorded call
    unpacks, over its instances (migration flows included); a call that
    records nothing counts none."""
    wl, cluster, placements, reals, dyn, migs = matrix_case
    res, counters = _run_counted(wl, cluster, placements, reals, trace=dyn,
                                 migrations=migs)
    assert any(f[0] >= wl.E for f in res[0].flow_log)
    assert counters["engine.jax.recorded_flows"] == sum(
        len(r.flow_log) for r in res) > 0
    with _registry_on() as reg:
        simulate_batch_jax(wl, cluster, placements, reals)
        assert "engine.jax.recorded_flows" not in reg.snapshot()


# ---------------------------------------------------------------------------
# runners that record nothing are pinned by their lowered program
# ---------------------------------------------------------------------------
# Every ETP search call and every scoring call runs ``record=False``: the
# text of those runners as jax lowers them is pinned by its sha256, so a
# change to what ``record=True`` runs cannot leak into them.  Regenerate
# (ONLY for an intended change to those programs, with the cause named):
#   PYTHONPATH=src:. python tests/test_jax_engine.py --regen-hlo
RUNNER_HLO_PATH = GOLDEN_PATH.parent / "jax_runner_hlo.json"


def _hlo_cases():
    """{case id: (workload, cluster, placements, realizations, policy,
    kwargs)}: the testbed job at the plan cell's search shapes (15
    iterations) under every policy and the deadline/trace/migration
    regime, and the papers100M job at the score cell's (width 16, 4
    iterations).  A runner's program depends on the shapes, the topology
    and the static regime, not on the placements' or draws' values."""
    import chip_smoke
    from repro.core.profiles import OGBN_PAPERS100M

    wl, cluster, placements, reals, cases = chip_smoke.testbed_inputs(width=16)
    out = {}
    for policy, kw in cases:
        regime = "-deadline-trace-migrations" if kw else ""
        out[f"testbed-w16-i15-{policy}{regime}"] = (
            wl, cluster, placements, reals, policy, kw)
    out["testbed-w1-i15-oes"] = (wl, cluster, placements[:1], reals[:1], "oes", {})
    big = build_workload_from_profile(
        OGBN_PAPERS100M, n_stores=16, n_workers=20, samplers_per_worker=4,
        n_ps=1, n_iters=4,
    )
    big_cluster = heterogeneous_cluster(16, seed=1)
    y = Placement(np.arange(big.J, dtype=np.int64) % big_cluster.M)
    out["papers100m-w16-i4-oes"] = (
        big, big_cluster, [y] * 16, [big.realize(seed=s) for s in range(16)],
        "oes", {})
    return out


class _Lowered(Exception):
    pass


def _runner_hlo_sha256(case, monkeypatch):
    """sha256 of the lowered text of the ``record=False`` runner that
    ``simulate_batch_jax`` builds for ``case`` (built, lowered, never run)."""
    import hashlib

    wl, cluster, placements, reals, policy, kw = case
    texts = []
    build = engine_jax._build_runner

    def build_and_lower(**bkw):
        fn = build(**bkw)

        def lower(*args):
            texts.append(fn.lower(*args).as_text())
            raise _Lowered

        return lower

    with monkeypatch.context() as mp:
        mp.setattr(engine_jax, "_build_runner", build_and_lower)
        mp.setattr(engine_jax, "_RUNNERS", {})
        with pytest.raises(_Lowered):
            simulate_batch_jax(wl, cluster, placements, reals, policy=policy,
                               record=False, **kw)
    (text,) = texts
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.fixture(scope="module")
def hlo_cases():
    return _hlo_cases()


HLO_CASE_IDS = (
    [f"testbed-w16-i15-{p}" for p in POLICIES]
    + ["testbed-w16-i15-oes-deadline-trace-migrations", "testbed-w1-i15-oes",
       "papers100m-w16-i4-oes"]
)


@pytest.mark.parametrize("case_id", HLO_CASE_IDS)
def test_unrecorded_runner_program_pinned(hlo_cases, case_id, monkeypatch):
    """A runner that records nothing lowers to the pinned program text."""
    pinned = json.loads(RUNNER_HLO_PATH.read_text())
    assert sorted(pinned) == sorted(HLO_CASE_IDS)
    assert _runner_hlo_sha256(hlo_cases[case_id], monkeypatch) == pinned[case_id]


if __name__ == "__main__":
    import sys

    if "--regen-hlo" in sys.argv:
        with pytest.MonkeyPatch.context() as mp:
            pinned = {cid: _runner_hlo_sha256(case, mp)
                      for cid, case in _hlo_cases().items()}
        RUNNER_HLO_PATH.write_text(json.dumps(pinned, indent=1) + "\n")
        print(f"wrote {RUNNER_HLO_PATH}: {sorted(pinned)}")
    elif "--regen" in sys.argv:
        named = [a for a in sys.argv[sys.argv.index("--regen") + 1:]
                 if not a.startswith("-")]
        pinned = regen_jax_oes(named)
        JAX_OES_PATH.write_text(json.dumps(pinned, indent=1) + "\n")
        print(f"wrote {JAX_OES_PATH}: {sorted(pinned)}")
    else:
        print(__doc__)
