"""Host spans on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: when a
``jax.profiler`` trace is running, the span lands in the same trace, on the
same clock, as the operations the device ran, so a stretch of device idle
time can be put down to what the host was doing. The profiler is the only
switch. With none running a span is a check of a fraction of a
microsecond; still, spans go on per-batch paths and never per request.

The program's spans:

  * serving worker (``repro.serve``): ``serve.poll``, ``serve.file``,
    ``serve.assemble``, ``serve.compute`` (around ``serve.dispatch`` and
    ``serve.readback``), ``serve.scatter``; see ``docs/serving.md``;
  * input pipeline (``repro.data.prefetch``): ``data.draw`` and
    ``data.place`` on the producer thread, ``data.wait`` on the consumer;
    see ``docs/data.md``.

Device operations carry ``jax.named_scope`` names instead (``egnn/embed``,
``layer{i}/message``, ``layer{i}/node_update``, ``heads``, ``loss``,
``optimizer``), which exist only in the compiled program's metadata.
"""
from __future__ import annotations

import contextlib

import jax

_OFF = contextlib.nullcontext()


def span(name: str):
    """A host span named ``name`` (a context manager). With no profiler
    running it is one shared no-op, so a span costs the check alone."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name)
    return _OFF
