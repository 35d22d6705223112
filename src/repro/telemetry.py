"""Host spans, device→host reads and dispatch counts of the partitioner.

Spans are ``jax.profiler.TraceAnnotation``s and always on: with no
profiler running each costs well under a microsecond; under one they land
on the host plane of the trace, on the device planes' clock, so a device
idle gap can be named by the span open at its middle.

Every scalar the host reads back from the device goes through
:func:`host_read`, which opens the span ``host_read/<site>`` around the
conversion (where the host waits for the device) and counts the read.
Every dispatch of a named compiled program goes through
:func:`count_dispatch`.  Counts go to each recorder open in the current
context (:func:`record`), so a call's counts are its own even while
another thread partitions (``PartitionService`` runs its jobs on a thread
of its own, which starts with no recorder open).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax

span = jax.profiler.TraceAnnotation

_OPEN: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_telemetry_recorders", default=())


@dataclasses.dataclass
class Counters:
    host_reads: dict = dataclasses.field(default_factory=dict)
    dispatches: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return {"host_reads": dict(self.host_reads),
                "dispatches": dict(self.dispatches)}


@contextlib.contextmanager
def record():
    """Count the host reads and dispatches made inside the block, in this
    context."""
    rec = Counters()
    token = _OPEN.set(_OPEN.get() + (rec,))
    try:
        yield rec
    finally:
        _OPEN.reset(token)


def _bump(field: str, key: str) -> None:
    for rec in _OPEN.get():
        d = getattr(rec, field)
        d[key] = d.get(key, 0) + 1


def count_dispatch(program: str) -> None:
    _bump("dispatches", program)


def host_read(site: str, x, cast=int):
    """``cast(x)`` of a device scalar, inside the span ``host_read/<site>``,
    counted under ``site``."""
    with span(f"host_read/{site}"):
        v = cast(x)
    _bump("host_reads", site)
    return v
