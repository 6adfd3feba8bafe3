"""Background batch prefetching — overlap host ingest with device compute.

The reference's loop strictly alternates poll → process (src/kafka.rs:92-135,
single thread).  Here device dispatch is already asynchronous, so the
remaining serialization is host-side batch production (fetch/decode/pack);
a small bounded queue filled by a worker thread overlaps it with the device
step (SURVEY.md §7 M5 'double-buffered host→device pipeline').  The native
generator and socket IO release the GIL, so the overlap is real.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, TypeVar

from kafka_topic_analyzer_tpu.obs import trace as obs_trace

T = TypeVar("T")

_SENTINEL = object()


class _Error:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class PrefetchIterator:
    """Wraps an iterator, producing items from a worker thread.

    Exceptions raised by the source are re-raised at the consuming side, at
    the position they occurred; the worker stops on first error.
    """

    def __init__(self, it: Iterator[T], depth: int = 2):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._q: "queue.Queue[object]" = queue.Queue(maxsize=depth)
        self._it = it
        self._cancel = threading.Event()
        self._source_closed = False
        # The worker runs in a copy of the caller's context, so the spans
        # of the iterator it drains carry the caller's scan id.
        self._thread = threading.Thread(
            target=obs_trace.in_context(self._fill), daemon=True
        )
        self._thread.start()

    def _put(self, item: object) -> bool:
        """Bounded put that gives up when the consumer cancelled."""
        while not self._cancel.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _close_source(self) -> None:
        """Throw GeneratorExit into the wrapped iterator (idempotent) so
        its finally blocks run — sources hold real resources (the wire
        client's per-stream broker connections).  Only called while no
        thread is executing the generator: from the worker after its loop
        exits, or from ``close()`` after the worker thread is gone."""
        if self._source_closed:
            return
        self._source_closed = True
        if hasattr(self._it, "close"):
            try:
                self._it.close()
            except Exception:
                pass  # a dying source must not mask the scan's real error

    def _fill(self) -> None:
        try:
            for item in self._it:
                if not self._put(item):
                    break
        except BaseException as e:  # propagate to the consumer
            self._put(_Error(e))
            return
        finally:
            if self._cancel.is_set():
                self._close_source()  # close the abandoned generator
        self._put(_SENTINEL)

    def close(self) -> None:
        """Stop the worker and release the wrapped iterator.  Safe to call
        multiple times; the engine calls it from a finally so early exits
        (errors, interrupts) never leak the thread, the underlying
        generator, or its connections."""
        self._cancel.set()
        # Drain so a blocked worker can observe the cancel promptly.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        if not self._thread.is_alive():
            # The worker can exit without taking its cancel-path close: it
            # already finished (exhaustion, error) before close() was
            # called, or it lost the cancel race right after its loop.
            # Either way the generator is quiescent now — close it HERE so
            # an early consumer exit always unwinds the source's finally
            # blocks, not just the worker thread.
            self._close_source()

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self) -> T:
        item = self._q.get()
        if item is _SENTINEL:
            raise StopIteration
        if isinstance(item, _Error):
            raise item.exc
        return item


def prefetch(it: Iterator[T], depth: int = 2) -> Iterator[T]:
    """0/negative depth disables prefetching (pass-through)."""
    if depth <= 0:
        return it
    return PrefetchIterator(it, depth)
