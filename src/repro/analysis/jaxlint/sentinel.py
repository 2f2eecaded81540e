"""Dynamic zero-retrace sentinel: count XLA traces around a test.

The static rules (``rules.py``) catch contract violations they can see
in the AST; this sentinel catches the ones they can't — any code path
that traces a *new* XLA program at runtime (e.g. a jit keyed on a
value, a shape that silently varies across a sweep).  It is the
per-test generalization of the two hand-rolled witnesses
(``tests/test_fleet.py::*zero_retrace*`` and
``composition.retraces_second_half``).

Mechanism: while active, the sentinel listens to JAX's public
monitoring events (``jax.monitoring``) and counts every
``/jax/core/compile/jaxpr_trace_duration`` event — JAX records one per
tracing-cache miss, exactly the event the zero-retrace contract forbids
after warmup.  Traces made while JAX dispatches a single primitive
eagerly (op-by-op, outside any jit) are not counted: JAX keeps those
one-primitive programs in a bounded LRU, so a long test session evicts
and re-traces them with no fault of the code under test.  It also
snapshots the repo's own :func:`repro.core.controller.fleet_trace_counts`
so failures name which fleet program retraced.

Usage (see ``pytest_plugin.py`` for the pytest marker wiring)::

    s = RetraceSentinel()
    s.start()
    warmup()          # compiles are allowed here
    s.arm()           # baseline: everything after this must not trace
    sweep()
    s.stop()
    assert not s.tripped(), s.report()
"""

from __future__ import annotations

import sys
from typing import Dict

import jax

#: monitoring event JAX records once per new jaxpr trace
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _in_eager_dispatch() -> bool:
    """True inside JAX's op-by-op dispatch of one primitive
    (``jax._src.dispatch.apply_primitive`` is on the stack)."""
    frame = sys._getframe(2)
    while frame is not None:
        code = frame.f_code
        if code.co_name == "apply_primitive" and "jax" in code.co_filename:
            return True
        frame = frame.f_back
    return False


def _fleet_counts() -> Dict[str, int]:
    """Current fleet-program trace counters (empty if controller is
    not importable — the sentinel must not force heavy imports)."""
    try:
        from repro.core import controller
        return controller.fleet_trace_counts()
    except Exception:  # jaxlint: disable=JL008
        # optional signal only: the trace-event counter is the witness
        return {}


class RetraceSentinel:
    """Counts new XLA program traces between :meth:`arm` and
    :meth:`stop` (``arm`` defaults to ``start`` time)."""

    def __init__(self) -> None:
        self._count = 0
        self._active = False
        self._baseline = 0
        self._baseline_fleet: Dict[str, int] = {}
        self._armed_explicitly = False

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "RetraceSentinel":
        if self._active:
            raise RuntimeError("sentinel already started")
        self._active = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.arm()
        self._armed_explicitly = False
        return self

    def arm(self) -> None:
        """Snapshot the baseline: traces after this point are failures.
        Call after warmup compiles; without an explicit call the
        baseline is :meth:`start` time (strict mode).

        Construct test inputs *before* arming: the counter sees every
        program trace, including first-time internal ``jnp`` helpers
        (``jnp.full`` and friends are themselves jitted), so building a
        fresh device array after ``arm()`` can trip the sentinel even
        though the swept program never retraced."""
        if not self._active:
            raise RuntimeError("sentinel not started")
        self._baseline = self._count
        self._baseline_fleet = _fleet_counts()
        self._armed_explicitly = True

    def stop(self) -> None:
        if self._active:
            jax.monitoring.unregister_event_duration_listener(self._on_event)
        self._active = False

    # -- results ------------------------------------------------------

    def delta(self) -> int:
        """New traces since the last :meth:`arm`."""
        return self._count - self._baseline

    def fleet_delta(self) -> Dict[str, int]:
        now = _fleet_counts()
        return {k: now[k] - v for k, v in self._baseline_fleet.items()
                if now.get(k, v) != v}

    def tripped(self) -> bool:
        return self.delta() > 0 or bool(self.fleet_delta())

    def report(self) -> str:
        mode = ("armed after warmup" if self._armed_explicitly
                else "strict (armed at start — use the `zero_retrace` "
                     "fixture's .arm() after warmup compiles)")
        parts = [f"zero-retrace sentinel tripped: {self.delta()} new "
                 f"XLA trace(s) after baseline [{mode}]"]
        fleet = self.fleet_delta()
        if fleet:
            parts.append(f"fleet programs retraced: {fleet}")
        return "; ".join(parts)

    # -- listener -----------------------------------------------------

    def _on_event(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == _TRACE_EVENT and not _in_eager_dispatch():
            self._count += 1
