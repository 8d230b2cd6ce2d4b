"""Host spans of the Palgol program.

:func:`span` marks one phase of host work in two places at once:

* the profiler trace, as a ``jax.profiler.TraceAnnotation`` named
  ``palgol/<name>``, on the clock the device trace shares, so that a
  reduction of the trace can say what the host did in a device idle gap;
* ``jax.monitoring``, as one duration event ``/palgol/<name>`` in seconds,
  for a listener registered with
  ``jax.monitoring.register_event_duration_secs_listener`` (the mechanism
  JAX's own compile-time events use).

:func:`count` records a plain ``jax.monitoring`` event ``/palgol/<name>``
where the program makes a choice at trace time (which path an edge
reduction takes); :func:`counted` tallies such events over a ``with``
body. :func:`tally` records a scalar event ``/palgol/<name>`` where the
program computes a size at trace time (the bytes a collective carries);
:func:`summed` adds such amounts up over a ``with`` body.

Spans wrap host phases only (``compile_program``'s front end,
``CompiledProgram.run``'s phases, the staged walk's dispatches). Device
work inside a jitted program is named with ``jax.named_scope`` instead,
by :mod:`repro.core.codegen`; a span cannot see it.
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax

#: prefix of every monitoring event a span or a count records
EVENT_PREFIX = "/palgol/"


@contextlib.contextmanager
def span(name: str):
    """Time the ``with`` body as the span ``name`` (see the module doc).
    The event is recorded when the body ends, whether or not it raised."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"palgol/{name}"):
        try:
            yield
        finally:
            jax.monitoring.record_event_duration_secs(
                EVENT_PREFIX + name, time.perf_counter() - t0
            )


def count(name: str) -> None:
    """Record one ``jax.monitoring`` event ``/palgol/<name>``."""
    jax.monitoring.record_event(EVENT_PREFIX + name)


@contextlib.contextmanager
def counted(prefix: str):
    """Tally the events ``/palgol/<prefix><rest>`` recorded in the ``with``
    body, in this process, into the yielded ``Counter`` keyed by
    ``<rest>``."""
    tally = collections.Counter()
    head = EVENT_PREFIX + prefix

    def listen(event: str, **_):
        if event.startswith(head):
            tally[event[len(head):]] += 1

    jax.monitoring.register_event_listener(listen)
    try:
        yield tally
    finally:
        jax.monitoring.unregister_event_listener(listen)


def tally(name: str, amount: float) -> None:
    """Record ``amount`` under the ``jax.monitoring`` scalar event
    ``/palgol/<name>`` (a size the program computes at trace time, such as
    the bytes a collective's operands carry)."""
    jax.monitoring.record_scalar(EVENT_PREFIX + name, amount)


@contextlib.contextmanager
def summed(prefix: str):
    """Sum the amounts of the scalar events ``/palgol/<prefix><rest>``
    recorded in the ``with`` body, in this process, into the yielded
    ``Counter`` keyed by ``<rest>``."""
    totals = collections.Counter()
    head = EVENT_PREFIX + prefix

    def listen(event: str, value, **_):
        if event.startswith(head):
            totals[event[len(head):]] += value

    jax.monitoring.register_scalar_listener(listen)
    try:
        yield totals
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
