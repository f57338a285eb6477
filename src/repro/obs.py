"""Names the program gives its work on a profiler trace.

``span(name)`` opens a ``jax.profiler.TraceAnnotation("repro.<name>")``
around a block of host set-up, or around each call of a function it
decorates, so a profiler trace shows that step on the host clock beside
the device ops.  The spans are for operators reading a trace: no metric
reads them, and nothing is recorded or written to disk.

``scoped(name)`` is the device side: it runs a function under
``jax.named_scope(name)``, which reaches the compiled program as op
metadata only (no op of its own).  Each phase of the BFS level loop is
scoped like its CommStats phase (``bfs/column``, ``bfs/expand/push``,
...), so a profiler names each device op's phase.

jax is imported per call: host-only modules (``core/csr.py``) use
``span`` without importing jax themselves.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager

PREFIX = "repro."


@contextmanager
def span(name: str):
    import jax

    with jax.profiler.TraceAnnotation(PREFIX + name):
        yield


def scoped(name: str):
    """Decorator: each call runs under the device scope ``name``.  The
    scope is made per call: a ``named_scope`` object keeps its caller's
    name stack, so one shared by threads or nested calls would mix them."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return run

    return wrap
