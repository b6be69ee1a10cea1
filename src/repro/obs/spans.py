"""Host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session is active (``jax.profiler.start_trace`` / ``trace``) it
records one host event named ``name`` with ``args`` as its stats, on the
same clock as the device's operations; otherwise it records nothing and
costs about half a microsecond (0.7 us with one argument, CPU).  Where jax
is not imported no profiler session can exist, and ``span`` hands back a
shared null span.  There is no switch of its own.

Names start with ``repro.``; nesting on the host thread gives a span its
parent.  ``set_metadata(**args)`` on the entered span adds arguments known
only later (``repro.engine``'s ``runner``).  A span is opened once per
call of the code it times, never per event or per instance:
repro-lint RL004 holds the engine files to that.

The program's spans (an xprof trace of a slow request shows which phase
holds the device idle):

* ``repro.plan`` (``seq``, ``budget``) around ``core.dgtp.plan``, with
  ``repro.plan.search`` (ETP, or IFS without search),
  ``repro.plan.commit.simulate`` (the recorded numpy schedule) and
  ``repro.plan.commit.audit`` (chain certificate, Delta, traffic summary);
* ``repro.engine`` (``width``, ``padded``, ``runner``) around
  ``core.engine_jax.simulate_batch_jax``, with ``repro.engine.assemble``
  (numpy inputs, padding, runner lookup), ``repro.engine.dispatch``
  (argument transfer and launch, plus tracing and compiling on a new
  runner), ``repro.engine.fetch`` (the wait for the results and their
  transfer) and ``repro.engine.unpack`` (building the results);
* ``repro.replan`` (``seq``) around ``dynamics.replan.Replanner``'s
  re-plan, with ``repro.replan.remap`` (machine leave only),
  ``repro.replan.search`` and ``repro.replan.price``.

``seq`` is a process-wide request number (``next_seq``): every span of one
request shares it.  The device side carries ``jax.named_scope``s
(``settle``, ``rate_solve``, ``advance``) in the runner's ``op_name``
metadata; ``engine_jax.runner_scopes`` maps a profile's instruction names
to them.
"""
from __future__ import annotations

import itertools
import sys
from typing import Any

_SEQ = itertools.count(1)


class _NoSpan:
    """Stands in for a ``TraceAnnotation`` where jax is not imported."""

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def set_metadata(self, **args: Any) -> None:
        pass


NO_SPAN = _NoSpan()


def span(name: str, **args: Any) -> Any:
    """A context manager that records host span ``name`` (with ``args``)
    while a profiler session is active."""
    jax = sys.modules.get("jax")
    if jax is None:  # no jax in this process: no profiler session either
        return NO_SPAN
    return jax.profiler.TraceAnnotation(name, **args)


def next_seq() -> int:
    """The next process-wide request number (``seq`` of a request span)."""
    return next(_SEQ)
