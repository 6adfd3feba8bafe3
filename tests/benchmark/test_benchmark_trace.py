"""Trace reduction: busy time is the union of device-op intervals, idle
gaps are the holes between them.  The traced window: at least ``TRACE_S``,
and on until the scan has dispatched two steps, the audits end or the cap
is reached."""

import gzip
import json
import os
import threading
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.trace_reduce import Event
from benchkit import REPO, run_tiny

SAMPLE = os.path.join(REPO, "benchmark", "trace_sample", "events.json.gz")


def test_union_and_gaps_worked_out_by_hand():
    d = "/device:TPU:0"
    evs = [Event(d, "fusion.1", 0, 10), Event(d, "copy", 5, 10),
           Event(d, "fusion.1", 30, 5), Event(d, "reduce", 50, 20),
           Event(d, "tiny", 55, 1)]
    s = trace_reduce.reduce(evs)
    assert s.devices == 1
    assert s.busy_s == pytest.approx((15 + 5 + 20) / 1e9)
    assert [n for n, _ in s.idle_gaps] == ["copy", "fusion.1"]
    assert [t for _, t in s.idle_gaps] == pytest.approx([15e-9, 15e-9])
    assert [n for n, _ in s.top_ops] == ["reduce", "fusion.1", "copy",
                                          "tiny"]
    assert [t for _, t in s.top_ops] == pytest.approx(
        [20e-9, 15e-9, 10e-9, 1e-9])


def test_busy_time_is_averaged_over_devices():
    evs = [Event("/device:TPU:0", "a", 0, 100),
           Event("/device:TPU:1", "a", 0, 50)]
    assert trace_reduce.reduce(evs).busy_s == pytest.approx(75 / 1e9)


def test_nothing_on_a_device_reads_nothing():
    assert trace_reduce.reduce([]) is None


def test_a_cpu_trace_has_no_device_events(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.xplane_path(str(tmp_path))
    assert path is not None
    assert trace_reduce.device_events(path) == []


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded trace")
def test_recorded_chip_trace():
    with gzip.open(SAMPLE, "rt") as f:
        doc = json.load(f)
    evs = [Event(*e) for e in doc["events"]]
    s = trace_reduce.reduce(evs)
    # the union, by a plain sweep over nanosecond intervals
    spans = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in evs)
    busy, end = 0.0, float("-inf")
    for a, b in spans:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-12)
    assert 0 < s.busy_s <= doc["window_s"]
    assert s.busy_s == pytest.approx(doc["busy_s"], rel=1e-9)
    assert len(s.top_ops) == 10 and len(s.idle_gaps) == 10


DELAY, SPAN, POLL, CAP = 0.05, 0.3, 0.1, 1.5
RECORDS_PER_BATCH = 1000


class FakeScan:
    """The profiler and the scan's counters, stood in for: from the moment
    the trace starts, a step of ``RECORDS_PER_BATCH`` records every
    ``period`` seconds (none where it is None), and ``tracer.stop()``
    called ``stop_at`` seconds in (never where it is None)."""

    def __init__(self, tracer, period, stop_at):
        self.tracer, self.period, self.stop_at = tracer, period, stop_at
        self.started = None
        self.calls = []
        self.timer = None

    def start_trace(self, log_dir, **_):
        self.calls.append(("start", log_dir))
        self.started = time.monotonic()
        if self.stop_at is not None:
            self.timer = threading.Timer(self.stop_at, self.tracer.stop)
            self.timer.start()

    def stop_trace(self):
        self.calls.append(("stop",))

    def counters(self):
        batches = 0
        if self.started is not None and self.period is not None:
            batches = int((time.monotonic() - self.started) // self.period)
        return {"kta_scan_batches_total": {(): float(batches)},
                "kta_scan_records_total": {
                    (("partition", "0"),): float(batches * RECORDS_PER_BATCH)}}


@pytest.fixture
def short_trace(monkeypatch):
    """A Tracer on short constants whose profiler and counters are a
    ``FakeScan``; yields a maker ``(period, stop_at) -> (tracer, scan)``."""
    import jax

    for name, value in [("TRACE_DELAY_S", DELAY), ("TRACE_S", SPAN),
                        ("TRACE_POLL_S", POLL), ("TRACE_MAX_S", CAP)]:
        monkeypatch.setattr(harness, name, value)
    monkeypatch.setattr(trace_reduce, "xplane_path", lambda d: d)
    monkeypatch.setattr(trace_reduce, "device_events", lambda path: [])
    scans = []

    def make(period, stop_at):
        tracer = harness.Tracer()
        scan = FakeScan(tracer, period, stop_at)
        monkeypatch.setattr(jax.profiler, "start_trace", scan.start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", scan.stop_trace)
        monkeypatch.setattr(harness, "counters", scan.counters)
        scans.append(scan)
        return tracer, scan

    yield make
    for scan in scans:
        if scan.timer is not None:
            scan.timer.cancel()


@pytest.mark.parametrize("period,stop_at,end,batches", [
    # steps every few ms: the condition holds at TRACE_S, no poll is added
    (0.004, None, SPAN, None),
    # a step every 1.5 TRACE_S: the window ends at the second
    (1.5 * SPAN, None, 3 * SPAN, 2),
    # no step, and the audits end: the window ends at the stop
    (None, 2 * SPAN, 2 * SPAN, 0),
    # no step and no stop: the window ends at the cap
    (None, None, CAP, 0),
], ids=["frequent", "sparse", "stopped", "capped"])
def test_trace_window(short_trace, period, stop_at, end, batches):
    tracer, scan = short_trace(period, stop_at)
    tracer.start()
    assert tracer.finish() is None  # no device op in a fake trace
    assert scan.calls == [("start", tracer.dir), ("stop",)]
    assert end <= tracer.seconds < end + POLL
    if batches is None:
        assert tracer.batches >= harness.TRACE_MIN_BATCHES
    else:
        assert tracer.batches == batches
    assert tracer.records == tracer.batches * RECORDS_PER_BATCH


def test_finish_after_stop_does_not_wait_out_the_cap(short_trace):
    tracer, _ = short_trace(None, None)
    t0 = time.monotonic()
    tracer.start()
    tracer.stop()
    tracer.finish()
    assert SPAN <= tracer.seconds < SPAN + POLL
    assert time.monotonic() - t0 < DELAY + SPAN + POLL < CAP


def test_a_traced_run_with_no_device_op_still_fails(tmp_path, monkeypatch,
                                                    capsys):
    """The CPU has no device plane: the trace holds no device op, and the
    run is refused as before, once the audits have ended."""
    monkeypatch.setattr(harness, "TRACE_DELAY_S", 0.1)
    monkeypatch.setattr(harness, "TRACE_S", 0.3)
    with pytest.raises(harness.BenchError,
                       match="^no operation ran on the device while traced$"):
        run_tiny(tmp_path, trace=True)
    assert "bench: traced " in capsys.readouterr().err
