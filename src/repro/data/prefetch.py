"""Async double-buffered input pipeline.

``GroupBatcher``/``SingleBatcher`` assemble batches on the host (NumPy
indexing + stacking) and the training loop then pays ``shard_batch`` /
``device_put`` before every step — all serialized with the running step, so
the accelerator idles between steps. ``Prefetcher`` moves that whole chain
onto a background thread with a bounded queue (default depth 2 — classic
double buffering: one batch in flight to the device while the step consumes
the previous one). JAX dispatch is thread-safe and ``device_put`` is async,
so the H2D copy overlaps the running step's compute.

This is the generic, batcher-agnostic layer of the DDStore latency-hiding
role (``repro.data.store.PrefetchingBatcher`` is the shard-store-specific
sibling that prefetches filesystem reads).

Determinism: the producer thread is the only caller of
``batcher.next_batch``, so the batch stream is byte-identical to the
synchronous path (tests/test_prefetch.py asserts this) — prefetching changes
when batches are built, never which. One caveat: ``close()`` discards the
(up to ``depth``+1) batches the producer has already drawn, advancing the
wrapped batcher past what the consumer saw — so hold ONE Prefetcher for the
batcher's whole lifetime instead of re-wrapping per loop (``Session`` keeps
its prefetcher across ``run()`` calls for exactly this reason; queued
batches are simply consumed by the next run).

Checkpointing closes exactly that gap: when the wrapped batcher is
checkpointable (``state()``/``restore()``), the producer snapshots the
batcher state AFTER drawing each batch and ships it through the queue with
the batch, and ``Prefetcher.state()`` returns the snapshot of the last batch
the CONSUMER actually received — never crediting read-ahead the training
loop hasn't seen. ``restore(state)`` halts the producer, discards its
read-ahead, rewinds the batcher, and restarts — so a resumed run replays the
stream from the first unconsumed batch, byte-identically
(tests/test_datapipe_checkpoint.py).
"""
from __future__ import annotations

import queue
import threading

from repro.profiling import span


class Prefetcher:
    """Wrap any batcher (the ``next_batch()`` contract) with a depth-``depth``
    background producer.

    transform: optional callable applied to each batch ON THE PRODUCER
    THREAD — pass ``plan.shard_batch`` (or ``jax.device_put``) so host->
    device transfer overlaps the running step.

    Host spans (``repro.profiling``): ``data.draw`` (the batcher) and
    ``data.place`` (the transform) on the producer thread, ``data.wait``
    (blocked for a batch) on the consumer's.

    Exceptions in the producer (including inside ``transform``) are captured
    and re-raised from ``next_batch()``. Use as a context manager or call
    ``close()`` to stop the producer; extra batches already in the queue are
    discarded."""

    _DONE = object()   # queued after a producer exception

    def __init__(self, batcher, *, transform=None, depth: int = 2):
        assert depth >= 1, f"prefetch depth must be >= 1, got {depth}"
        self.batcher = batcher
        self.transform = transform
        self.depth = depth
        # consumer-visible stream position: state as of the last batch
        # handed out by next_batch() (initially: before any batch).
        # Trackability is probed by CALLING state(), not hasattr — a
        # delegating wrapper (e.g. BucketingBatcher) always has the method
        # but raises when its inner batcher is not checkpointable
        try:
            self._consumed_state = batcher.state()
            self._trackable = True
        except (AttributeError, TypeError):
            self._consumed_state = None
            self._trackable = False
        self._err: BaseException | None = None
        self._fault: BaseException | None = None
        self._closed = False
        self._start()

    def _start(self):
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        # instrumentation seam (repro.analysis.tsan tests): producer
        # generations are SEQUENTIAL — a restore halts generation N before
        # generation N+1 draws, which is why the single-producer contract
        # is overlap-based, not thread-identity-based
        self.generation = getattr(self, "generation", 0) + 1
        self._thread = threading.Thread(
            target=self._produce, name=f"prefetcher-{self.generation}",
            daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Blocking put that stays responsive to close(); False if stopped."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            while not self._stop.is_set():
                if self._fault is not None:
                    exc, self._fault = self._fault, None
                    raise exc
                with span("data.draw"):
                    b = self.batcher.next_batch()
                # snapshot BEFORE transform (transform is placement, not
                # stream position) and after the draw: restoring to this
                # snapshot replays the stream from the NEXT batch
                st = self.batcher.state() if self._trackable else None
                if self.transform is not None:
                    with span("data.place"):
                        b = self.transform(b)
                self._put((b, st))
        except BaseException as e:  # propagate to the consumer
            if isinstance(e, StopIteration):
                # next_batch() is also __next__: re-raising a producer's
                # bare StopIteration there would SILENTLY end any for-loop
                # over the Prefetcher instead of surfacing the failure —
                # wrap it, keeping the original as __cause__ (traceback
                # included)
                wrapped = RuntimeError(
                    "prefetch producer raised StopIteration "
                    "(exhausted/broken source?)")
                wrapped.__cause__ = e
                e = wrapped
            self._err = e
            self._put((self._DONE, None))

    def inject_producer_fault(self, exc: BaseException):
        """Chaos hook (repro.resilience.faults): the producer raises ``exc``
        before its next draw, exactly as if it had crashed — the consumer
        sees it from ``next_batch()`` after draining already-queued batches,
        and ``restore(state())`` recovers the stream in place."""
        self._fault = exc

    def next_batch(self):
        if self._err is not None and self._q.empty():
            raise self._err          # producer already died; don't block
        if self._stop.is_set():      # closed: drain or raise, never hang
            try:
                item, st = self._q.get_nowait()
            except queue.Empty:
                raise RuntimeError("Prefetcher is closed") from self._err
        else:
            with span("data.wait"):
                item, st = self._q.get()
        if item is self._DONE:
            self._stop.set()
            raise self._err
        if st is not None:
            self._consumed_state = st
        return item

    # iterator protocol, so a Prefetcher drops into train_loop(batches=...)
    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()

    # -- checkpointing ------------------------------------------------------

    def state(self) -> dict:
        """Wrapped-batcher state as of the last batch the consumer received
        (producer read-ahead is NOT credited — it will be re-drawn after a
        restore)."""
        if not self._trackable:
            raise TypeError(
                f"{type(self.batcher).__name__} has no state()/restore(); "
                "wrap a checkpointable batcher to checkpoint the pipeline")
        return self._consumed_state

    def restore(self, state: dict):
        """Rewind the pipeline to a ``state()`` snapshot: halt the producer,
        discard its read-ahead, restore the batcher, restart. Also revives a
        closed Prefetcher."""
        if not self._trackable:
            raise TypeError(
                f"{type(self.batcher).__name__} has no state()/restore()")
        self._halt()
        if self._thread.is_alive():
            # a producer stuck past _halt's join timeout would race the new
            # producer on the same batcher and corrupt the rewound stream
            raise RuntimeError(
                "prefetch producer did not stop within the join timeout; "
                "cannot restore safely while it may still draw batches")
        self.batcher.restore(state)
        self._consumed_state = self.batcher.state()
        self._err = None
        self._closed = False
        self._start()

    # -- shutdown -----------------------------------------------------------

    def _halt(self):
        """Stop the producer and discard queued batches."""
        self._stop.set()
        # unblock a producer stuck in _put, then drain — twice: the first
        # drain can free a slot that the producer's in-flight put fills
        # before it observes _stop, so drain again after the join
        for _ in range(2):
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5.0)

    def close(self):
        """Stop the producer and discard queued batches. Repeated shutdown
        is a strict no-op: the second ``close()`` (or a ``close()`` followed
        by context-manager ``__exit__``) returns immediately without
        re-draining or re-joining — a producer stuck past the join timeout
        previously made every extra ``close()`` block for the full timeout
        again. (``restore()`` revives a closed Prefetcher and re-arms
        ``close()``; ``next_batch()`` on a closed one raises.)"""
        if self._closed:
            return
        self._closed = True
        self._halt()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
