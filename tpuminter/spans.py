"""Named host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler session
records (``worker --profile``, or any ``jax.profiler.start_trace`` in the
miner's process), each span lands in the same trace as the device's ops,
on the same clock, so a gap on the device can be put down to what the
miner's host loop was doing in it. With no session a span costs well under
a microsecond, so spans are always on.

:func:`span` never imports JAX: in a process that has not imported it (the
worker's own, see ``miner_proc``) every span is a no-op.

The vocabulary, fixed so that trace readers and tests share it:

- :data:`AWAIT_CHUNK`: the miner child waits for its next command and holds
  no work (``miner_proc``).
- :data:`DISPATCH`: one sweep handed to the device, non-blocking
  (``search``).
- :data:`RESOLVE`: the host waits on one sweep's result (``search`` and the
  pipelined miner loops).
- :data:`WINNER`: a chunk sends the Result that answers its job; args
  ``job``, ``chunk`` (``miner_proc``).
- :data:`CANCEL`: the miner child abandons a chunk that is no longer
  wanted; args ``job``, ``chunk`` (``miner_proc``).
- :data:`ROLL`: one extranonce roll of a rolled sweep, handed to the
  device; the first of a chunk also builds the template's words, so it
  holds the cost of a new template; args ``job``, ``chunk`` and
  ``nonces``, the indices the sweep it feeds covers (``rolled``).
"""

from __future__ import annotations

import contextlib
import sys

__all__ = [
    "AWAIT_CHUNK", "CANCEL", "DISPATCH", "RESOLVE", "ROLL", "WINNER", "span",
]

AWAIT_CHUNK = "tpuminter.await_chunk"
DISPATCH = "tpuminter.dispatch"
RESOLVE = "tpuminter.resolve"
WINNER = "tpuminter.winner"
CANCEL = "tpuminter.cancel"
ROLL = "tpuminter.roll"

_OFF = contextlib.nullcontext()


def span(name: str, **ids):
    """A context manager that records ``name`` (with ``ids`` as its args)
    in the process's profiler trace, or does nothing where JAX is not
    imported."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **ids)
