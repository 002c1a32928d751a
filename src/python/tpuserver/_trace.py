"""Host spans on the profiler's clock: the one way this package writes them.

``span(name)`` is ``jax.profiler.TraceAnnotation``: a span on the same
clock as the device trace, inert (a flag test in native code, ~0.4 us)
unless a profiler session is running in this process
(``jax.profiler.start_trace``; docs/observability.md "Tracing").  A
process that never imported jax — a ``simple``-only replica, the router
— cannot hold a session, so there a span is a no-op and jax is not
imported for its sake.
"""

import contextlib
import sys

_NO_SESSION_POSSIBLE = contextlib.nullcontext()


def span(name, **stats):
    """A context manager: the host span ``name`` (``stats`` ride along as
    the event's metadata) while a profiler session runs, else nothing."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return _NO_SESSION_POSSIBLE
    return profiler.TraceAnnotation(name, **stats)
