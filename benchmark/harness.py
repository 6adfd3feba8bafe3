"""One benchmark run: back-to-back whole audits through ``cli.main``.

A run starts the broker child (generator.py, broker.py), starts JAX, runs
one short warm-up audit of the cell's own partitions and batch shapes, then
starts audits back to back until ``seconds`` have passed since the first
began; the run ends when the audit in progress completes.  Each audit reads
a topic name no earlier audit of the run has read.  After the window the
device's peak memory is read, the broker stops, and every audit's report is
compared with the plain reference (reference.py).  With ``trace`` a
profiler trace inside the window, of a few seconds and at least until the
scan has put work on the device (``Tracer``), is reduced
(trace_reduce.py) and the cell's per-layer metrics are reported; without
it, its end-to-end metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import io
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

from benchmark import manifest, reference, trace_reduce
from benchmark.broker import WARMUP_PREFIX, BrokerProcess

#: JAX books this for every program it builds, a persistent-cache hit
#: included; a hit also books CACHE_HIT_EVENT.  Compilations are the
#: difference.  (Each audit builds its jitted steps anew, as each CLI
#: process does, so every audit reads them from the cache.)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: Where a traced run starts its trace, after the window's start, and for
#: how long it traces at least.
TRACE_DELAY_S = 2.0
TRACE_S = 3.0
#: The trace then goes on until the scan has dispatched this many steps
#: since it began (the first step's device work has then surely run), the
#: audits have ended, or TRACE_MAX_S has passed; it checks every
#: TRACE_POLL_S.  A trace that holds no device op reads nothing, and a scan
#: that folds slowly may dispatch no step for most of a minute: each of its
#: streams dispatches when it has packed a whole device batch.
TRACE_MIN_BATCHES = 2
TRACE_MAX_S = 120.0
TRACE_POLL_S = 0.05
#: A reading that could not be taken (a missing line) prints as this.
MISSING = 1e9


class BenchError(Exception):
    """A run that cannot produce a result: it exits non-zero with this
    message and prints no result line."""


def process_start() -> float:
    """``time.monotonic()`` at this process's start, from /proc."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


@dataclasses.dataclass
class Audit:
    topic: str
    start: float
    end: float
    rc: int
    text: str


@dataclasses.dataclass
class Run:
    """What the metric readers read (benchmark/metrics/*.py)."""

    audits: List[Audit]
    records_per_audit: int
    setup_s: float
    peak_bytes: int
    deltas: Dict[str, List[dict]]
    trace: Optional[trace_reduce.Summary] = None
    trace_s: float = 0.0
    trace_records: float = 0.0

    @property
    def window_s(self) -> float:
        return self.audits[-1].end - self.audits[0].start

    @property
    def audit_s(self) -> float:
        return sum(a.end - a.start for a in self.audits)

    @property
    def records(self) -> int:
        return self.records_per_audit * sum(a.rc == 0 for a in self.audits)

    def counter(self, name: str, **labels) -> Optional[float]:
        """Sum over the window of a registry counter's samples whose labels
        include ``labels``; None when it booked nothing."""
        hits = [s["value"] for s in self.deltas.get(name, [])
                if all(s["labels"].get(k) == v for k, v in labels.items())]
        return sum(hits) if hits else None


def counters() -> Dict[str, Dict[tuple, float]]:
    from kafka_topic_analyzer_tpu.obs.registry import default_registry

    out: Dict[str, Dict[tuple, float]] = {}
    for name, doc in default_registry().snapshot().items():
        if doc["type"] == "counter":
            out[name] = {tuple(sorted(s["labels"].items())): s["value"]
                         for s in doc["samples"]}
    return out


def delta(before, after) -> Dict[str, List[dict]]:
    out: Dict[str, List[dict]] = {}
    for name, samples in after.items():
        old = before.get(name, {})
        out[name] = [{"labels": dict(k), "value": v - old.get(k, 0.0)}
                     for k, v in samples.items()]
    return out


def require_chip(chips: int, peaks: Dict[str, dict]):
    """The first device, where JAX found a TPU with ``chips`` chips whose
    kind the peaks table knows."""
    import jax

    devices = jax.devices()
    found = devices[0].platform
    if found != "tpu":
        raise BenchError(f"found platform {found!r}, need 'tpu'")
    if len(devices) < chips:
        raise BenchError(f"found {len(devices)} tpu devices, need {chips}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device_kind {kind!r} is not in "
                         f"benchmark/peaks.json")
    return devices


def audit(topic: str, port: int, flags: List[str]) -> Audit:
    from kafka_topic_analyzer_tpu import cli

    out = io.StringIO()
    argv = ["-t", topic, "-b", f"127.0.0.1:{port}", "--backend", "tpu",
            "--quiet", *flags]
    start = time.monotonic()
    with contextlib.redirect_stdout(out):
        rc = cli.main(argv)
    return Audit(topic, start, time.monotonic(), rc, out.getvalue())


class Tracer:
    """Traces on a thread, from ``TRACE_DELAY_S`` after ``start()``, for
    ``TRACE_S`` and on until the scan has dispatched ``TRACE_MIN_BATCHES``
    steps, ``stop()`` is called or ``TRACE_MAX_S`` has passed; counts the
    records folded and the steps dispatched meanwhile."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.seconds = 0.0
        self.records = 0.0
        self.batches = 0.0
        self.error: Optional[BaseException] = None
        self._stopped = threading.Event()
        self._thread = threading.Thread(target=self._trace, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        """The audits have ended: nothing more will be dispatched."""
        self._stopped.set()

    @staticmethod
    def _counts() -> tuple:
        """The records folded and the steps dispatched so far."""
        c = counters()
        return tuple(sum(c.get(name, {}).values()) for name in
                     ("kta_scan_records_total", "kta_scan_batches_total"))

    def _trace(self) -> None:
        import jax

        try:
            time.sleep(TRACE_DELAY_S)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # device ops and annotations only
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            (n0, b0), t0 = self._counts(), time.monotonic()
            time.sleep(TRACE_S)
            while True:
                (n1, b1), t1 = self._counts(), time.monotonic()
                if (b1 - b0 >= TRACE_MIN_BATCHES or t1 - t0 >= TRACE_MAX_S
                        or self._stopped.is_set()):
                    break
                self._stopped.wait(TRACE_POLL_S)
            jax.profiler.stop_trace()
            self.seconds, self.records = t1 - t0, n1 - n0
            self.batches = b1 - b0
        except BaseException as e:  # reported by finish()
            self.error = e

    def finish(self) -> Optional[trace_reduce.Summary]:
        self._thread.join()
        try:
            if self.error is not None:
                raise BenchError(f"tracing failed: {self.error!r}")
            path = trace_reduce.xplane_path(self.dir)
            if path is None:
                raise BenchError("the profiler wrote no trace")
            return trace_reduce.reduce(trace_reduce.device_events(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def limits(root: str) -> Dict[str, float]:
    with open(os.path.join(root, "benchmark", "limits.json")) as f:
        return json.load(f)["limits"]


def check(cell: manifest.Cell, seed: int, audits: List[Audit],
          lims: Dict[str, float]):
    """Compare every audit's report with the reference.  Returns the
    worst readings (summed for counts), each beside its limit, and the
    number of audits that failed (exited non-zero or read over a limit)."""
    from benchmark import generator

    topic = generator.generate(cell.config, seed)
    flags = cell.traffic["flags"]
    exp = reference.expected(topic, cell.config, flags)
    del topic
    worst: Dict[str, float] = {}
    failed = 0
    for a in audits:
        r = reference.compare(exp, a.topic, a.text, a.end - a.start)
        failed += a.rc != 0 or any(v > lims[k] for k, v in r.items())
        for k, v in r.items():
            if k == "report_lines_wrong":
                worst[k] = worst.get(k, 0.0) + v
            else:
                worst[k] = max(worst.get(k, 0.0), v)
    return {k: (v, lims[k]) for k, v in worst.items()}, failed


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        chip_check: bool = True, t_start: Optional[float] = None) -> dict:
    t_start = process_start() if t_start is None else t_start
    cell = manifest.load_cell(root, workload)
    lims = limits(root)
    try:
        from kafka_topic_analyzer_tpu.io.native import native_status
    except ImportError as e:
        raise BenchError(f"the analyzer is not in this checkout: {e}")
    ok, why = native_status()
    if not ok:
        raise BenchError(f"native shim not loaded ({why})")
    flags = list(cell.traffic["flags"])
    broker = BrokerProcess(cell.config, seed,
                           int(cell.traffic["warmup_records"]))
    try:
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devices = (require_chip(cell.chips, manifest.peaks(root))
                   if chip_check else jax.devices())
        t_chip = time.monotonic()
        port = broker.port()
        t_broker = time.monotonic()
        warm = audit(f"{WARMUP_PREFIX}-0", port, flags)
        if warm.rc != 0:
            raise BenchError(f"warm-up audit exited {warm.rc}")
        print(f"bench: set-up: chip found at {t_chip - t_start:.2f}s, "
              f"broker serving at {t_broker - t_start:.2f}s, warm-up audit "
              f"{warm.end - warm.start:.2f}s", file=sys.stderr)
        builds: List[str] = []

        def on_event(event, *_, **__):
            if event in (COMPILE_EVENT, CACHE_HIT_EVENT):
                builds.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        jax.monitoring.register_event_listener(on_event)
        tracer = Tracer() if trace else None
        before = counters()
        audits: List[Audit] = []
        window_start = time.monotonic()
        if tracer:
            tracer.start()
        try:
            while not audits or time.monotonic() - window_start < seconds:
                audits.append(audit(f"audit-{len(audits):04d}", port, flags))
        finally:
            if tracer:
                tracer.stop()
            jax.monitoring.unregister_event_duration_listener(on_event)
            jax.monitoring.unregister_event_listener(on_event)
        deltas = delta(before, counters())
        summary = tracer.finish() if tracer else None
        if tracer:
            print(f"bench: traced {tracer.seconds:.2f}s, "
                  f"{tracer.batches:.0f} scan batches, "
                  f"{tracer.records:.0f} records", file=sys.stderr)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:cell.chips])
        hits = builds.count(CACHE_HIT_EVENT)
        compiles = builds.count(COMPILE_EVENT) - hits
        print(f"bench: {compiles} backend compilations inside the window "
              f"({hits} programs read from the compilation cache)",
              file=sys.stderr)
        if compiles:
            raise BenchError(f"{compiles} compilations inside the window: "
                             "the warm-up missed a shape")
    finally:
        broker.stop()
    gc.collect()
    run_ = Run(audits, int(cell.config["records"]),
               window_start - t_start, peak, deltas, summary,
               tracer.seconds if tracer else 0.0,
               tracer.records if tracer else 0.0)
    t_check = time.monotonic()
    checks, failed = check(cell, seed, audits, lims)
    print(f"bench: {len(audits)} audits in a {run_.window_s:.2f}s window "
          f"({', '.join(f'{a.end - a.start:.2f}s' for a in audits)}); "
          f"reference and comparison {time.monotonic() - t_check:.2f}s",
          file=sys.stderr)
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics_of(kind):
        value = m.read(run_)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(audits),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        if summary is None:
            raise BenchError("no operation ran on the device while traced")
        device["busy_s"] = summary.busy_s
        device["window_s"] = run_.trace_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[f"after {n}", s] for n, s in summary.idle_gaps],
        }
    for name, (value, lim) in checks.items():
        print(f"check {name}: {_num(value)} (limit {lim})", file=sys.stderr)
    result["checks"] = {name: {"value": _num(value), "limit": lim}
                        for name, (value, lim) in checks.items()}
    return result


def _num(v: float) -> float:
    return v if math.isfinite(v) else MISSING
