"""Host spans on the profiler's clock, for the one process that owns a device.

``span(name, **args)`` is a context manager around a SYNCHRONOUS section.
Until a factory is installed it returns one shared no-op, so ``spi.py`` and
everything a replica or a client imports stay off JAX.  The verifier service
calls ``install(jax.profiler.TraceAnnotation)`` once it holds its device; from
then on a span is an event on the calling thread's line of a ``jax.profiler``
trace (outside a capture, a flag test), beside the device's own events.

Spans nest per thread, so one must never cross an ``await``: interleaved
coroutines on the loop thread would close each other's spans.  Names are
constants and arguments are numbers already in hand (the ``span-lazy-label``
rule: no formatting at the call site).
"""

from __future__ import annotations

from typing import Callable, Optional


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NO_SPAN = _NoSpan()
_factory: Optional[Callable] = None


def install(factory: Optional[Callable]) -> None:
    """``factory(name, **args)`` returns a context manager; None uninstalls."""
    global _factory
    _factory = factory


def installed() -> bool:
    return _factory is not None


def span(name: str, **args):
    if _factory is None:
        return _NO_SPAN
    return _factory(name, **args)
