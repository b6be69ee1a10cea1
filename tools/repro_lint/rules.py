"""The rule catalogue: one class per invariant, registered in ALL_RULES.

Each rule documents the bug class that motivated it (the PR that fixed
the live instances) so a finding carries its own rationale.  Rules are
deliberately approximate static passes — they key on the repo's naming
and call conventions, and every escape hatch (pragma, baseline) is
first-class.  See README "Static analysis & typing" for the catalogue
with suppression guidance.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import (
    Finding,
    LintModule,
    contains_mult,
    enclosing_functions,
    referenced_names,
    root_name,
    terminal_name,
)


class Rule:
    """Base class: subclasses set ``rule_id``/``title``/``rationale`` and
    implement ``check``.  ``applies`` gates path-scoped rules (RL004,
    RL006) — fixtures spoof ``LintModule.rel_path`` to exercise them."""

    rule_id: str = "RL000"
    title: str = ""
    rationale: str = ""

    def applies(self, module: LintModule) -> bool:
        return True

    def check(self, module: LintModule) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


# ---------------------------------------------------------------------------
# RL001 — raw seed arithmetic
# ---------------------------------------------------------------------------
def _is_seedlike(node: ast.AST) -> bool:
    name = terminal_name(node)
    return name is not None and "seed" in name.lower()


class SeedArithmeticRule(Rule):
    """``seed + k*expr`` derivations collide across derivation levels.

    PR 8 replaced the affine ``seed+1000*d`` / ``seed+7919*ji`` streams
    (which collided whenever ``1000*d == 7919*ji + k*1000`` lined up)
    with namespaced splitmix64 mixing.  Any new affine derivation
    reintroduces the collision class, so child seeds must come from
    ``core.multijob.derive_seed(base, namespace, index)``.
    """

    rule_id = "RL001"
    title = "raw seed arithmetic outside core/multijob.derive_seed"
    rationale = (
        "affine seed+k*expr streams can collide across derivation levels "
        "(PR 8); derive child seeds with derive_seed(base, namespace, index)"
    )

    #: the sanctioned implementation itself
    EXEMPT_FUNCTIONS = {"derive_seed", "_splitmix64"}

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        owner = enclosing_functions(module.tree)
        for node in ast.walk(module.tree):
            if not (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, (ast.Add, ast.Sub))
            ):
                continue
            fn = owner.get(node)
            if fn is not None and fn.name in self.EXEMPT_FUNCTIONS:
                continue
            hit = (
                (_is_seedlike(node.left) and contains_mult(node.right))
                or (_is_seedlike(node.right) and contains_mult(node.left))
            )
            if hit:
                out.append(
                    module.finding(
                        self.rule_id,
                        node,
                        "raw seed arithmetic (seed +/- k*expr): derive "
                        "child streams with core.multijob.derive_seed("
                        "base, namespace, index) — affine offsets collide "
                        "across derivation levels",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# RL002 — direct .realize() on merged workloads
# ---------------------------------------------------------------------------
class MergedRealizeRule(Rule):
    """Merged workloads need ``realize_merged`` (epsilon padding, per-job
    namespaced streams); ``Workload.realize`` refuses at runtime (PR 8) —
    this catches it at review time.

    Static approximation: a value is treated as a MergedJob when it is
    assigned from ``merge_workloads(...)`` or ``<inc>.merged(...)``, and
    as a merged workload when it is ``<mergedjob>.workload`` (directly or
    via an alias assignment) or its root identifier contains "merged".
    """

    rule_id = "RL002"
    title = ".realize() on merged-workload values outside realize_merged"
    rationale = (
        "epsilon padding and per-job pmr/jitter silently diverge when a "
        "merged workload is realized directly (PR 8); route through "
        "core.multijob.realize_merged / IncrementalMerge.realize"
    )

    MERGE_PRODUCERS = {"merge_workloads", "merged"}

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        # track assignments module-wide: the sets are per-name, and names
        # rarely collide across scopes in this codebase; a collision would
        # only ever ADD a finding a pragma can waive
        merged_jobs: Set[str] = set()
        merged_workloads: Set[str] = set()
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            if not isinstance(tgt, ast.Name):
                continue
            val = node.value
            if isinstance(val, ast.Call):
                callee = terminal_name(val.func)
                if callee in self.MERGE_PRODUCERS:
                    merged_jobs.add(tgt.id)
            elif (
                isinstance(val, ast.Attribute)
                and val.attr == "workload"
                and isinstance(val.value, ast.Name)
                and val.value.id in merged_jobs
            ):
                merged_workloads.add(tgt.id)

        for node in ast.walk(module.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "realize"
            ):
                continue
            recv = node.func.value
            hit = False
            if isinstance(recv, ast.Name) and recv.id in merged_workloads:
                hit = True
            elif (
                isinstance(recv, ast.Attribute)
                and recv.attr == "workload"
            ):
                root = root_name(recv)
                inner = recv.value
                if (isinstance(inner, ast.Name) and inner.id in merged_jobs):
                    hit = True
                elif (
                    isinstance(inner, ast.Call)
                    and terminal_name(inner.func) in self.MERGE_PRODUCERS
                ):
                    hit = True
                elif root is not None and "merged" in root.lower():
                    hit = True
            elif isinstance(recv, ast.Name) and "merged" in recv.id.lower():
                # e.g. `merged_wl.realize(...)`
                hit = True
            if hit:
                out.append(
                    module.finding(
                        self.rule_id,
                        node,
                        "direct .realize() on a merged workload: use "
                        "core.multijob.realize_merged (or "
                        "IncrementalMerge.realize) so epsilon padding and "
                        "per-job streams stay correct",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# RL003 — unrecorded results fed into per-job accounting
# ---------------------------------------------------------------------------
class UnrecordedAccountingRule(Rule):
    """``simulate(record=False)`` leaves ``task_events`` empty; feeding
    such a result into per-job accounting used to silently return 0.0
    for every job (PR 8 made it raise).  This rule catches the miswiring
    statically: within a function, a name assigned from
    ``simulate``/``simulate_batch`` without ``record=True`` must not be
    passed to ``per_job_makespans``/``per_job_iteration_ends`` or have
    its ``.task_events`` read.
    """

    rule_id = "RL003"
    title = "record=False simulation results fed into per-job accounting"
    rationale = (
        "unrecorded results carry no task_events; per-job accounting on "
        "them judged every admission feasible before PR 8 made it raise — "
        "pass record=True (numpy backend) to the producing simulate call"
    )

    PRODUCERS = {"simulate", "simulate_batch", "simulate_batch_jax"}
    SINKS = {"per_job_makespans", "per_job_iteration_ends"}

    @classmethod
    def _is_unrecorded_call(cls, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        if terminal_name(node.func) not in cls.PRODUCERS:
            return False
        for kw in node.keywords:
            if kw.arg == "record":
                return not (
                    isinstance(kw.value, ast.Constant)
                    and kw.value.value is True
                )
            if kw.arg is None:
                # **kwargs may carry record=True — give it the benefit
                # of the doubt
                return False
        return True  # record defaults to False

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        scopes: List[ast.AST] = [module.tree] + [
            n
            for n in ast.walk(module.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for scope in scopes:
            out.extend(self._check_scope(module, scope))
        return out

    def _check_scope(
        self, module: LintModule, scope: ast.AST
    ) -> List[Finding]:
        out: List[Finding] = []
        unrecorded: Set[str] = set()
        body = scope.body if hasattr(scope, "body") else []
        nodes: List[ast.AST] = []
        for stmt in body:
            # nested functions are their own scopes — analysed once each
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            nodes.extend(self._walk_no_nested_fn(stmt))
        for node in nodes:
            # 1) track assignments
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name):
                    if self._is_unrecorded_call(node.value):
                        unrecorded.add(tgt.id)
                    elif (
                        isinstance(node.value, ast.Subscript)
                        and isinstance(node.value.value, ast.Name)
                        and node.value.value.id in unrecorded
                    ):
                        unrecorded.add(tgt.id)
                    elif tgt.id in unrecorded:
                        unrecorded.discard(tgt.id)  # rebound to clean value
            # 2) sinks: accounting calls
            if isinstance(node, ast.Call) and (
                terminal_name(node.func) in self.SINKS
            ):
                for arg in list(node.args) + [
                    kw.value for kw in node.keywords
                ]:
                    if self._is_unrecorded_value(arg, unrecorded):
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                "per-job accounting on an unrecorded "
                                "result: the producing simulate call needs "
                                "record=True (numpy backend) or "
                                "task_events is empty",
                            )
                        )
                        break
            # 3) sinks: .task_events reads
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "task_events"
                and self._is_unrecorded_value(node.value, unrecorded)
            ):
                out.append(
                    module.finding(
                        self.rule_id,
                        node,
                        ".task_events on an unrecorded result is always "
                        "empty: pass record=True to the producing "
                        "simulate call",
                    )
                )
        return out

    def _walk_no_nested_fn(self, node: ast.AST) -> Iterable[ast.AST]:
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._walk_no_nested_fn(child)

    @classmethod
    def _is_unrecorded_value(
        cls, node: ast.AST, unrecorded: Set[str]
    ) -> bool:
        if isinstance(node, ast.Name):
            return node.id in unrecorded
        if isinstance(node, ast.Subscript):
            return cls._is_unrecorded_value(node.value, unrecorded)
        if cls._is_unrecorded_call(node):
            return True
        return False


# ---------------------------------------------------------------------------
# RL004 — metrics calls inside engine hot loops
# ---------------------------------------------------------------------------
class MetricsInHotLoopRule(Rule):
    """The obs contract (PR 7): call sites increment once per call with
    pre-aggregated values, never inside event loops — the <3% off-path
    overhead pin in ``benchmarks/bench_obs.py`` depends on it.  Host spans
    (``repro.obs.spans.span``, ``TraceAnnotation``) follow the same
    contract: one span per call, never one per event or instance.  Scoped
    to the engine hot-path files.
    """

    rule_id = "RL004"
    title = "REGISTRY/metrics calls or host spans inside engine hot-path loop bodies"
    rationale = (
        "the obs off-path overhead pin (<3%) holds because metrics "
        "increment and spans open once per engine call, outside event "
        "loops — hoist the call and pre-aggregate"
    )

    SPAN_NAMES = {"span", "TraceAnnotation"}

    HOT_PATH_SUFFIXES = (
        "src/repro/core/engine.py",
        "src/repro/core/engine_jax.py",
    )

    def applies(self, module: LintModule) -> bool:
        return module.rel_path.endswith(self.HOT_PATH_SUFFIXES)

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        loops = [
            n
            for n in ast.walk(module.tree)
            if isinstance(n, (ast.For, ast.While, ast.AsyncFor))
        ]
        seen: Set[int] = set()
        for loop in loops:
            for stmt in loop.body + loop.orelse:
                for node in ast.walk(stmt):
                    if id(node) in seen or not isinstance(node, ast.Call):
                        continue
                    names = referenced_names(node.func) | {
                        sub.attr
                        for sub in ast.walk(node.func)
                        if isinstance(sub, ast.Attribute)
                    }
                    if "REGISTRY" in names or "obs_metrics" in names:
                        # flag only the outermost call of a chained
                        # expression (REGISTRY.counter(...).inc())
                        for sub in ast.walk(node):
                            if isinstance(sub, ast.Call):
                                seen.add(id(sub))
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                "metrics call inside an engine hot-path "
                                "loop: hoist it out and increment once "
                                "with a pre-aggregated value (obs "
                                "overhead pin, PR 7)",
                            )
                        )
                    elif names & self.SPAN_NAMES:
                        for sub in ast.walk(node):
                            if isinstance(sub, ast.Call):
                                seen.add(id(sub))
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                "host span opened inside an engine hot-path "
                                "loop: open one span around the whole call "
                                "(obs overhead pin)",
                            )
                        )
        return out


# ---------------------------------------------------------------------------
# RL005 — jit purity
# ---------------------------------------------------------------------------
class JitPurityRule(Rule):
    """Code traced by ``jax.jit`` must stay in the array program: a
    ``float()``/``.item()`` call forces a device sync per invocation, a
    ``np.`` call silently constant-folds the traced operand, and Python
    ``if``/``while`` on a traced operand raises a TracerBoolConversion
    at best.  The rule finds functions passed to ``jit(...)`` (or
    decorated with it) and flags impurities inside them; branching is
    approximated as ``if``/``while`` whose condition references one of
    the jitted function's own parameters (closure config branching is
    static under trace and stays legal).
    """

    rule_id = "RL005"
    title = "host-side impurities inside jit-traced functions"
    rationale = (
        "float()/.item()/np. calls and Python branching on traced "
        "operands break or de-optimise the jitted engine (PR 6); keep "
        "traced code jnp/lax-only"
    )

    IMPURE_BUILTINS = {"float", "int", "bool"}
    NUMPY_ROOTS = {"np", "numpy"}

    def _jitted_functions(self, module: LintModule) -> List[ast.FunctionDef]:
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for n in ast.walk(module.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(n.name, []).append(n)
        jitted: List[ast.FunctionDef] = []
        seen: Set[int] = set()

        def add_by_name(name: str) -> None:
            for fd in defs.get(name, []):
                if id(fd) not in seen:
                    seen.add(id(fd))
                    jitted.append(fd)

        for n in ast.walk(module.tree):
            # jax.jit(fn) / jit(fn) call with a Name argument
            if (
                isinstance(n, ast.Call)
                and terminal_name(n.func) == "jit"
                and n.args
                and isinstance(n.args[0], ast.Name)
            ):
                add_by_name(n.args[0].id)
        # @jit / @jax.jit / @partial(jit, ...) decorators
        for name, fds in defs.items():
            for fd in fds:
                for dec in fd.decorator_list:
                    tn = terminal_name(dec)
                    if tn == "jit":
                        add_by_name(name)
                    elif isinstance(dec, ast.Call):
                        if terminal_name(dec.func) == "jit":
                            add_by_name(name)
                        elif terminal_name(dec.func) == "partial" and any(
                            terminal_name(a) == "jit" for a in dec.args
                        ):
                            add_by_name(name)
        return jitted

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        for fd in self._jitted_functions(module):
            params = {
                a.arg
                for a in (
                    fd.args.posonlyargs + fd.args.args + fd.args.kwonlyargs
                )
            }
            for node in ast.walk(fd):
                if node is fd:
                    continue
                if isinstance(node, ast.Call):
                    callee = node.func
                    if (
                        isinstance(callee, ast.Name)
                        and callee.id in self.IMPURE_BUILTINS
                        and node.args
                    ):
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                f"{callee.id}() inside a jit-traced "
                                "function forces a host sync (or fails "
                                "on tracers): keep the value in the "
                                "array program",
                            )
                        )
                    elif (
                        isinstance(callee, ast.Attribute)
                        and callee.attr == "item"
                    ):
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                ".item() inside a jit-traced function "
                                "forces a host sync per invocation",
                            )
                        )
                    elif (
                        isinstance(callee, ast.Attribute)
                        and root_name(callee) in self.NUMPY_ROOTS
                    ):
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                "np. call inside a jit-traced function "
                                "constant-folds (or rejects) the traced "
                                "operand: use jnp/lax",
                            )
                        )
                elif isinstance(node, (ast.If, ast.While)):
                    if referenced_names(node.test) & params:
                        out.append(
                            module.finding(
                                self.rule_id,
                                node,
                                "Python branching on a traced operand "
                                "inside a jit-traced function: use "
                                "jnp.where / lax.cond / lax.while_loop",
                            )
                        )
        return out


# ---------------------------------------------------------------------------
# RL006 — backend threading completeness
# ---------------------------------------------------------------------------
class BackendThreadingRule(Rule):
    """The backend knob must never silently drop (PR 6): a library call
    into ``simulate``/``simulate_batch`` without ``backend=`` pins the
    callee to the env-var default even when the caller was asked for a
    specific engine.  Forwarding a name (``backend=backend`` /
    ``backend=cfg.backend``) and deliberate literal pins
    (``backend="numpy"`` for committed/audit sims) both satisfy the
    rule; the finding is the *absent* kwarg.  Scoped to ``src/``
    (tests/benchmarks exercise defaults on purpose).
    """

    rule_id = "RL006"
    title = "simulate/simulate_batch call without backend= threading"
    rationale = (
        "a dropped backend kwarg silently mixes engines under "
        "REPRO_ENGINE_BACKEND (PR 6); forward backend= or pin it "
        'explicitly (backend="numpy" for committed/audit sims)'
    )

    CALLEES = {"simulate", "simulate_batch"}

    def applies(self, module: LintModule) -> bool:
        return module.rel_path.startswith("src/")

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = terminal_name(node.func)
            if callee not in self.CALLEES:
                continue
            # only direct calls to the engine entry points, not methods
            # on arbitrary objects (x.simulate(...) still counts: the
            # serve engine mirrors the API)
            kwargs = {kw.arg for kw in node.keywords}
            if "backend" in kwargs or None in kwargs:
                continue  # forwarded, pinned, or **kw may carry it
            out.append(
                module.finding(
                    self.rule_id,
                    node,
                    f"{callee}() without backend=: thread the caller's "
                    "backend through (or pin backend=\"numpy\" for a "
                    "committed/audit simulation)",
                )
            )
        return out


# ---------------------------------------------------------------------------
# RL007 — int-bandwidth/capacity arrays
# ---------------------------------------------------------------------------
class IntBandwidthArrayRule(Rule):
    """Integer bandwidth/capacity arrays silently truncate waterfill
    arithmetic (the PR 5 bug class: in-place ``//=``-style updates on an
    int array drop fractional rates).  Arrays whose name or keyword says
    bandwidth/capacity must carry an explicit float dtype when built
    from integer literals.
    """

    rule_id = "RL007"
    title = "bandwidth/capacity array from int literals without float dtype"
    rationale = (
        "int arrays truncate waterfill capacity arithmetic (PR 5); "
        "construct bw/cap arrays with an explicit float dtype"
    )

    CTORS = {"array", "asarray"}
    ROOTS = {"np", "numpy", "jnp"}
    NAME_RE = re.compile(
        r"(^|_)(bw|bandwidth|bandwidths|cap|caps|capacity|capacities|nic)"
        r"(s)?(_|$)",
        re.IGNORECASE,
    )

    @classmethod
    def _bwlike(cls, name: Optional[str]) -> bool:
        return name is not None and bool(cls.NAME_RE.search(name))

    @classmethod
    def _int_literal_array_call(cls, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        if terminal_name(node.func) not in cls.CTORS:
            return False
        if root_name(node.func) not in cls.ROOTS:
            return False
        if any(kw.arg == "dtype" for kw in node.keywords):
            return False  # explicit dtype (even int) is a stated choice
        if not node.args:
            return False
        return cls._all_int_literals(node.args[0])

    @classmethod
    def _all_int_literals(cls, node: ast.AST) -> bool:
        if isinstance(node, (ast.List, ast.Tuple)):
            return len(node.elts) > 0 and all(
                cls._all_int_literals(e) for e in node.elts
            )
        if isinstance(node, ast.Constant):
            return isinstance(node.value, int) and not isinstance(
                node.value, bool
            )
        if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)
        ):
            return cls._all_int_literals(node.operand)
        return False

    def check(self, module: LintModule) -> List[Finding]:
        out: List[Finding] = []
        flagged: Set[int] = set()

        def flag(call: ast.AST, why: str) -> None:
            if id(call) in flagged:
                return
            flagged.add(id(call))
            out.append(
                module.finding(
                    self.rule_id,
                    call,
                    f"{why} built from int literals without an explicit "
                    "float dtype: int arrays truncate capacity "
                    "arithmetic — add dtype=float (or np.float64)",
                )
            )

        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name) and self._bwlike(tgt.id):
                    if self._int_literal_array_call(node.value):
                        flag(node.value, f"'{tgt.id}' array")
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if self._bwlike(kw.arg) and self._int_literal_array_call(
                        kw.value
                    ):
                        flag(kw.value, f"'{kw.arg}=' array")
        return out


ALL_RULES: List[Rule] = [
    SeedArithmeticRule(),
    MergedRealizeRule(),
    UnrecordedAccountingRule(),
    MetricsInHotLoopRule(),
    JitPurityRule(),
    BackendThreadingRule(),
    IntBandwidthArrayRule(),
]


def get_rules(select: Optional[Sequence[str]] = None) -> List[Rule]:
    """The registered rules, optionally filtered to ``select`` ids."""
    if not select:
        return list(ALL_RULES)
    wanted = {s.strip().upper() for s in select}
    unknown = wanted - {r.rule_id for r in ALL_RULES}
    if unknown:
        raise ValueError(
            f"unknown rule id(s): {', '.join(sorted(unknown))} "
            f"(have {', '.join(r.rule_id for r in ALL_RULES)})"
        )
    return [r for r in ALL_RULES if r.rule_id in wanted]
