"""The benchmark's own spans around the program's layers, for a traced run.

``Spans.install`` wraps module attributes of the program in this process:
each call runs inside a ``jax.profiler.TraceAnnotation`` named
``chipbench.<span>``, so that the profiler's trace holds the span on the same
clock as the device's operations.  Engine calls also count their lock-step
iterations (the largest ``n_events`` among the call's results).  A target
that is missing raises: a span never silently reads as nothing.
"""
from __future__ import annotations

import functools
import importlib
from typing import Any, Callable, Dict, List, Tuple

PREFIX = "chipbench."

# span name -> (module, attribute) it wraps, per operation kind
TARGETS: Dict[str, List[Tuple[str, str, str]]] = {
    "plan": [
        ("search", "repro.core.dgtp", "etp_multichain"),
        ("engine", "repro.core.engine_jax", "simulate_batch_jax"),
    ],
    "score": [("engine", "repro.core.engine_jax", "simulate_batch_jax")],
    "replan": [("engine", "repro.core.engine_jax", "simulate_batch_jax")],
}


class Spans:
    def __init__(self) -> None:
        self.lockstep_iters = 0
        self._undo: List[Tuple[Any, str, Any]] = []

    def wrap(self, name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
        import jax

        @functools.wraps(fn)
        def spanned(*args: Any, **kwargs: Any) -> Any:
            with jax.profiler.TraceAnnotation(PREFIX + name):
                out = fn(*args, **kwargs)
            if name == "engine":
                self.lockstep_iters += max((r.n_events for r in out), default=0)
            return out

        return spanned

    def install(self, op: str) -> None:
        for name, mod_name, attr in TARGETS[op]:
            mod = importlib.import_module(mod_name)
            if not hasattr(mod, attr):
                raise AttributeError(f"span {name!r}: {mod_name}.{attr} does not exist")
            orig = getattr(mod, attr)
            self._undo.append((mod, attr, orig))
            setattr(mod, attr, self.wrap(name, orig))

    def uninstall(self) -> None:
        while self._undo:
            mod, attr, orig = self._undo.pop()
            setattr(mod, attr, orig)

    def op(self) -> Any:
        """The span of one whole operation (entered by the loop)."""
        import jax

        return jax.profiler.TraceAnnotation(PREFIX + "op")
