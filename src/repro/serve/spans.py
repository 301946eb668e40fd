"""Host spans of the serving path, recorded by the JAX profiler.

Each span is a ``jax.profiler.TraceAnnotation``, so a trace taken with
``jax.profiler.start_trace`` holds it on the host's clock beside the
device's programs.  With no trace being recorded a span costs about a
microsecond, so the spans are always on.

Names are ``<layer>.<what>``: ``server.*`` for the calls of
``ReachabilityServer``, ``engine.*`` for the phases of ``QueryEngine``
and ``index.*`` for a rebuild's steps.  A span whose name holds
``.wait_`` covers work that blocks on the device: a call that waits for a
device result, or a rebuild step (``index.rebuild.wait_*``), which reads
device results back between its own host steps.  Every other span's own
time is host work that the device does not hold up.
"""
from __future__ import annotations

import functools

import jax

#: ``with span("engine.pool"): ...``
span = jax.profiler.TraceAnnotation


def traced(name: str):
    """Method decorator: every call runs inside the span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)
