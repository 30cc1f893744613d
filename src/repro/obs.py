"""Program spans on the profiler's clock: the one span recorder of the
program.

``span(name, **args)`` marks a step of the serving or build path.  Off
(the default) it costs one check of a module flag and returns a shared
null context: no annotation is built and no argument is formatted.  On
(``enable(True)``) it enters a ``jax.profiler.TraceAnnotation``, so the
step lands in a profiler trace on the same clock as the device's
operations, and a reader of the trace can charge each device-idle gap to
the step the host thread was in.  Outside a profiler trace an enabled
span records nothing.

Every name starts with ``repro.``.  ``set_metadata(**args)`` on what a
span yields adds arguments known only once the step has run (a flush's
real rows); on the null context it does nothing.

Counters are not spans: they stay with the object that counts
(``serve.loop.LoopStats``, ``RetrievalServer.closure_builds``).
"""

from __future__ import annotations

import jax

__all__ = ["enable", "enabled", "span"]

PREFIX = "repro."


class _Null:
    """The shared context of a span while spans are off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_NULL = _Null()
_on = False


def enable(on: bool = True) -> None:
    """Turn program spans on or off for the whole process."""
    global _on
    _on = bool(on)


def enabled() -> bool:
    return _on


def span(name: str, **args):
    """A context marking one program step named ``name`` (``repro.*``)
    with ``args`` on its trace event; the shared null context when off."""
    if not _on:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **args)
