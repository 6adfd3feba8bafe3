"""Backend protocol + factory + the superbatch dispatch queue."""

from __future__ import annotations

import abc
import collections
import functools
import time

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.records import RecordBatch
from kafka_topic_analyzer_tpu.results import TopicMetrics


class DispatchQueue:
    """Bounded in-flight superbatch dispatch tracking (``--dispatch-depth``).

    Device dispatch is asynchronous: without a bound, a fast ingest side
    could stack arbitrarily many staged superbatches (host staging rows +
    device input buffers) behind a slow device.  This queue caps the
    in-flight count at ``depth`` using per-dispatch completion tokens —
    small non-donated step outputs that become ready exactly when their
    superbatch's fold (and therefore its host→device transfer) completed.

    Contract, enforced by tools/lint.sh rule 4: all in-flight bookkeeping
    lives HERE (no other module touches an inflight container), and every
    dispatch site calls ``throttle()`` before launching + ``launched()``
    after — so no drive loop can ever hold more than ``depth`` staged
    superbatches.  Blocking inside ``throttle`` is the backpressure that
    propagates into the ingest fan-in (the engine thread stops draining
    the worker queues, which fill, which stalls the workers).
    """

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError("dispatch depth must be >= 1")
        self.depth = depth
        self._inflight: "collections.deque" = collections.deque()

    def __len__(self) -> int:
        return len(self._inflight)

    def throttle(self) -> None:
        """Block until fewer than ``depth`` dispatches are in flight —
        call BEFORE staging the device transfer of the next superbatch.

        Time spent blocked here is booked on
        ``kta_dispatch_throttle_seconds_total`` UNCONDITIONALLY (flight
        recorder on or off): this wait is the backpressure at the launch
        site, and the one signal that directly decides dispatch-bound vs
        ingest-bound (obs/doctor.py) — an unbooked stall here made every
        manual bench ledger reconstruct it from residuals."""
        self._reap()
        if len(self._inflight) < self.depth:
            return
        t0 = time.perf_counter()
        try:
            while len(self._inflight) >= self.depth:
                self._retire(block=True)
        finally:
            obs_metrics.DISPATCH_THROTTLE_SECONDS.inc(
                time.perf_counter() - t0
            )

    def launched(self, token, batches: int) -> None:
        """Record a dispatch just launched.  ``token`` must be a device
        value that completes with the dispatch and is never donated to a
        later dispatch (backends/step.py::superbatch_fold returns one)."""
        self._inflight.append((token, time.perf_counter(), batches))
        obs_metrics.DISPATCH_INFLIGHT.set(len(self._inflight))
        obs_metrics.SUPERBATCH_SIZE.observe(batches)

    def drain(self) -> None:
        """Retire every in-flight dispatch (finalize / block_until_ready)."""
        while self._inflight:
            self._retire(block=True)

    def _reap(self) -> None:
        """Opportunistically retire already-completed dispatches so the
        latency histogram and in-flight gauge stay fresh without blocking."""
        while self._inflight:
            ready = getattr(self._inflight[0][0], "is_ready", None)
            if ready is None or not ready():
                return
            self._retire(block=False)

    def _retire(self, block: bool) -> None:
        import jax

        token, t0, _batches = self._inflight[0]
        if block:
            jax.block_until_ready(token)
        self._inflight.popleft()
        obs_metrics.DISPATCH_SECONDS.observe(time.perf_counter() - t0)
        obs_metrics.DISPATCH_INFLIGHT.set(len(self._inflight))


class MetricBackend(abc.ABC):
    """Batched replacement for the reference's ``MetricHandler`` seam
    (src/kafka.rs:18-20): updates fold whole record batches, results are read
    once at the end.

    Contract:
    - `update` must be called with batches whose per-partition record order
      matches offset order (records.py ordering contract);
    - `update` may be asynchronous (device dispatch); `finalize` synchronizes
      and returns host-side results.
    """

    def __init__(self, config: AnalyzerConfig):
        self.config = config

    @abc.abstractmethod
    def update(self, batch: RecordBatch) -> None:
        ...

    @abc.abstractmethod
    def finalize(self) -> TopicMetrics:
        ...


def _timed(fn, hist):
    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        with hist.time():
            return fn(self, *args, **kwargs)
    return wrapped


def instrument_steps(cls):
    """Class decorator for concrete backends: record finalize latency into
    the obs histogram.  Dispatch time is the engine's ``dispatch`` stage;
    device time lives in the profiler trace (``--profile-dir``)."""
    setattr(cls, "finalize", _timed(
        cls.__dict__["finalize"], obs_metrics.BACKEND_FINALIZE_SECONDS))
    return cls


def make_backend(
    name: str, config: AnalyzerConfig, dispatch=None
) -> MetricBackend:
    """Factory for ``--backend {cpu,tpu}`` (default cpu per BASELINE.json).
    ``dispatch`` (config.DispatchConfig) sizes the tpu backend's superbatch
    layer; the cpu oracle has no device dispatch to amortize, so callers
    must not pass a K>1 dispatch config with it (cli.resolve_dispatch)."""
    if name == "cpu":
        from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend

        return CpuExactBackend(config)
    if name == "tpu":
        from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend

        return TpuBackend(config, dispatch=dispatch)
    raise ValueError(f"unknown backend {name!r} (expected 'cpu' or 'tpu')")
