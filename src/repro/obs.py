"""Program spans on the profiler's clock.

``span(name, **attrs)`` marks a stretch of the program's host work as
the event ``pimdb.<name>`` for the JAX profiler::

    with obs.span("readback", rel=rel.name) as sp:
        host = jax.device_get(raw)
        sp.set_metadata(bytes=...)

While no profiler session is active a span records nothing and costs
about a microsecond; the session (``jax.profiler.start_trace``) is the
only switch. Under a session the spans land in the trace's
``.xplane.pb`` on the host plane, one line per thread, on the same
clock as the device's operations, with their attributes as the event's
stats. Spans on one thread nest, so each has its parent.

Attributes are counters the code already holds (ints, short strings):
a span never makes a pass over data to fill one. ``set_metadata`` adds
those known only at the span's end.

``query(q)`` names the queries the work in progress serves (one name, or
a ``+``-joined list for a batch); every span opened inside it, in the
same thread or task, carries it as ``q`` unless it names its own.
Attribute values hold no ``,``, ``=`` or ``#``: the profiler encodes
the attributes in the event's name with those characters and would cut
the value there.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

PREFIX = "pimdb."

#: The queries the current work serves; each thread starts with none.
current_query: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("pimdb_query", default=None)


def span(name: str, **attrs) -> TraceAnnotation:
    """The profiler event ``pimdb.<name>`` with ``attrs`` as its stats."""
    if "q" not in attrs:
        q = current_query.get()
        if q is not None:
            attrs["q"] = q
    return TraceAnnotation(PREFIX + name, **attrs)


@contextlib.contextmanager
def query(q: str) -> Iterator[None]:
    """Spans opened inside, in this thread or task, carry ``q``."""
    token = current_query.set(q)
    try:
        yield
    finally:
        current_query.reset(token)
