"""Profiler traces + range annotations: the one path from the program to
the profiler's trace.

Parity surface: the reference's NVTX ranges (``deepspeed/utils/nvtx.py``,
used throughout ZeRO-3) and ``accelerator.range_push/range_pop``. TPU-native form: the
XLA profiler. :func:`annotate` is the ONLY way the package writes a host
span: a ``jax.profiler.TraceAnnotation`` (a TraceMe) with plain-int
attributes, returned unconditionally. A TraceMe is inert unless a profiler
session is active, so nothing decides it but the session itself: no flag,
no config field, no environment variable; the path reads no clock, takes
no lock and never touches the device. Device-side names come from
``jax.named_scope`` in the jitted code (metadata only).

How an operator gets the spans and scopes (catalogue:
docs/observability.md "Program spans and device scopes"): any profiler
session does -- ``with profiling.trace.trace(logdir):`` around the work,
``jax.profiler.start_trace``, or ``jax.profiler.start_server(port)`` and
a capture from TensorBoard. Then open the ``.xplane.pb`` under
``<logdir>/plugins/profile/`` in TensorBoard's profile plugin, or reduce
it with ``python benchmarks/trace_reduce.py <file>``. Host spans sit on
the profiler's clock beside the device's operations; their attributes are
the event's stats. Scoped spans of the request tracer
(``telemetry/tracing.py`` ``Tracer.span``) go through :func:`annotate`
too while that tracer is enabled, under any session.

This module stays import-safe without jax: the handle is resolved once,
and every entry point degrades to a no-op when jax cannot be imported.
"""

from __future__ import annotations

import contextlib
from typing import Iterator


_JAX = None          # the jax module, False once an import has failed


def _jax():
    """The jax handle, resolved once; None when jax is not installed
    (stripped environments: annotations degrade to no-ops, with one
    warning so a requested capture never fails silently). A jax that is
    installed but BROKEN still raises loudly: only a clean ImportError
    is the degrade path."""
    global _JAX
    if _JAX is None:
        try:
            import jax

            _JAX = jax
        except ImportError:
            _JAX = False
            import logging

            logging.getLogger(__name__).warning(
                "jax unavailable: profiler traces/annotations are no-ops")
    return _JAX or None


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture an XLA profiler trace into ``logdir`` (view with
    TensorBoard's profile plugin)."""
    jax = _jax()
    if jax is None:
        yield
        return
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **attrs: int):
    """Named range on the profiler's host track (the range_push/range_pop
    analog), with plain-int attributes that become the event's stats.
    A context manager; attributes known only at the end are added with
    ``set_metadata(**attrs)`` on the object ``with`` yields. Inert without
    a profiler session; a no-op context when jax is unavailable."""
    jax = _jax()
    if jax is None:
        return _NoAnnotation()
    return jax.profiler.TraceAnnotation(name, **attrs)


class _NoAnnotation(contextlib.nullcontext):
    """``annotate``'s stand-in without jax: same surface, does nothing."""

    def __enter__(self):
        return self

    def set_metadata(self, **attrs: int) -> None:
        pass
