"""Host-side spans: one primitive, written to the profiler and to JSON.

Every span enters a ``jax.profiler.TraceAnnotation`` named ``kta.<name>``
(the prefix marks the program's spans among the runtime's own), so a
``--profile-dir`` trace — or any profiler session in the process — shows
the host stages (fetch, decode and pack, dispatch, snapshot, finalize) on
the same clock as the device's operations.  The annotation is taken only
where JAX is already imported: the broker child and CPU-only tools stay
JAX-free, and with no profiler running an annotation costs about a
microsecond.

With a ``SpanTracer`` attached (``--trace-json``) the same spans are also
recorded as Chrome trace-event JSON under their unprefixed names, stamped
with the tracer's own ``perf_counter`` offset: a separate clock, for
viewing the host side alone.  Spans are complete events (``ph: "X"``)
appended under a lock — prefetch workers and fetch-pool threads record
concurrently and the per-thread ``tid`` keeps their tracks separate.
``ScanProfile`` mirrors its stage windows into the active tracer with the
*same* measured duration, so the trace's per-stage totals agree with
``--stats`` by construction (tests/test_telemetry.py holds them within
5%).

Spans carry ``scan=<id>`` inside ``scan_span`` (the context is copied
into the scan's ingest threads), and spans of one batch carry
``batch=<seq>``: ``next_batch`` numbers batches within a scan, so a
batch's decode-and-pack links to the dispatch that folds it.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import json
import os
import sys
import threading
import time
from typing import Callable, Iterator, List, Optional

#: Prefix of every profiler annotation the program writes.
PREFIX = "kta."


class _Scan:
    __slots__ = ("id", "batches")

    def __init__(self, scan_id: int):
        self.id = scan_id
        self.batches = itertools.count()


_scan_ids = itertools.count(1)
_scan: "contextvars.ContextVar[Optional[_Scan]]" = contextvars.ContextVar(
    "kta_scan", default=None
)
_unscoped_batches = itertools.count()


def _annotation(name: str, args: dict):
    """The profiler half of a span, or a no-op where JAX is not loaded."""
    if "jax" not in sys.modules:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PREFIX + name, **args)


def _scoped(args: dict) -> dict:
    s = _scan.get()
    return args if s is None else {"scan": s.id, **args}


def annotate(name: str, **args):
    """Context manager: ``kta.<name>`` on the profiler's timeline, with
    the current scan's id and ``args`` as its stats."""
    return _annotation(name, _scoped(args))


def next_batch() -> int:
    """The next batch sequence number of the current scan (of the process
    outside any scan).  Thread-safe: ``itertools.count`` advances
    atomically."""
    s = _scan.get()
    return next(s.batches if s is not None else _unscoped_batches)


class SpanTracer:
    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._events: List[dict] = []

    def add_complete(
        self,
        name: str,
        start_s: float,
        dur_s: float,
        cat: str = "span",
        args: "Optional[dict]" = None,
    ) -> None:
        """Record one complete span; ``start_s`` is in this tracer's clock
        domain (the same clock used by the caller's measurement, so the
        recorded duration is exactly the measured one)."""
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": (start_s - self._t0) * 1e6,
            "dur": dur_s * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def add_counter(
        self, name: str, values: "dict", t_s: "Optional[float]" = None
    ) -> None:
        """Record one counter-track sample (``ph: "C"``): the flight
        recorder's occupancy gauges render as stacked numeric lanes under
        the stage spans in chrome://tracing / Perfetto.  ``t_s`` is in
        this tracer's clock domain, like ``add_complete``; None stamps
        the sample "now" (callers on a different clock — the flight
        recorder's injectable monotonic — must not translate domains).
        ``values`` maps series name -> number (one lane per key)."""
        ev = {
            "name": name,
            "cat": "flight",
            "ph": "C",
            "ts": ((self._clock() if t_s is None else t_s) - self._t0) * 1e6,
            "pid": os.getpid(),
            "tid": 0,  # counter tracks live on one lane, not per-thread
            "args": {k: float(v) for k, v in values.items()},
        }
        with self._lock:
            self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "span", **args) -> Iterator[None]:
        args = _scoped(args)
        with _annotation(name, args):
            t0 = self._clock()
            try:
                yield
            finally:
                self.add_complete(
                    name, t0, self._clock() - t0, cat, args or None
                )

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.chrome_trace(), fh)


_active: "Optional[SpanTracer]" = None


def set_active(tracer: "Optional[SpanTracer]") -> None:
    global _active
    _active = tracer


def active() -> "Optional[SpanTracer]":
    return _active


def maybe_span(name: str, cat: str = "span", **args):
    """Span on the active tracer, or the profiler annotation alone when no
    tracer is attached — what library modules (io/kafka_wire.py) wrap their
    fetch/decode work in without threading a tracer through every call."""
    tr = _active
    if tr is None:
        return annotate(name, **args)
    return tr.span(name, cat, **args)


@contextlib.contextmanager
def scan_span(tracer: "Optional[SpanTracer]" = None) -> Iterator[int]:
    """The ``scan`` span around one whole scan: gives the scan its id,
    which every span inside it (and in threads that copy the context)
    carries.  Yields the id."""
    s = _Scan(next(_scan_ids))
    token = _scan.set(s)
    try:
        with (tracer.span("scan") if tracer is not None
              else annotate("scan")):
            yield s.id
    finally:
        _scan.reset(token)


@contextlib.contextmanager
def device_scope(name: str) -> Iterator[None]:
    """The device-side twin of a span: ``jax.named_scope("kta.<name>")``,
    so the device ops traced inside carry the scope in their ``op_name``
    metadata.  Metadata only: the compiled program does not change.  Also
    a decorator, entering a fresh scope on every call (safe when two
    threads trace at once)."""
    import jax

    with jax.named_scope(PREFIX + name):
        yield


def in_context(fn: Callable) -> Callable:
    """``fn`` bound to a copy of the calling thread's context: the target
    of an ingest thread, so that its spans carry the scan's id.  A copy
    runs on one thread at a time, so bind once per thread or task."""
    return functools.partial(contextvars.copy_context().run, fn)
