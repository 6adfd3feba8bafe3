"""Per-stage throughput counters + optional JAX profiler traces.

The reference's only observability is wall-clock + a derived msgs/s
(src/main.rs:129-130, SURVEY.md §5.1).  Since msgs/s *is* the north-star
metric here, the engine keeps per-stage (ingest / dispatch / finalize)
wall-time and record counters, and can wrap the scan in a JAX profiler trace
(``--profile-dir``) for XLA-level analysis on TPU.

Every stage window is a ``kta.<stage>`` profiler annotation
(obs/trace.py), so ``--profile-dir`` shows the stages on the device's
clock.  With a span tracer attached (``--trace-json``) each window is also
mirrored into the Chrome trace with the *same* measured duration, so the
host trace's per-stage totals agree with ``--stats`` exactly.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, Optional

from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.obs import trace as obs_trace

#: Canonical drive-loop stage order: pipeline position, not insertion
#: order (insertion order varies with which stage fires first — e.g. a
#: resumed scan snapshots before its first dispatch).  THE one list —
#: the --stats stage digest (results.StageDigest), the flight recorder's
#: stage tracks (obs/flight.py), and the scan doctor's occupancy model
#: (obs/doctor.py) all import it, so adding a stage here propagates to
#: every surface instead of silently dropping out of one.
STAGE_ORDER = ("ingest", "dispatch", "snapshot", "finalize")


@dataclasses.dataclass
class StageStats:
    seconds: float = 0.0
    items: int = 0
    bytes: int = 0

    @property
    def items_per_sec(self) -> float:
        return self.items / self.seconds if self.seconds > 0 else 0.0

    @property
    def mb_per_sec(self) -> float:
        return (
            self.bytes / self.seconds / 1e6 if self.seconds > 0 else 0.0
        )


class ScanProfile:
    def __init__(self, tracer=None) -> None:
        self.stages: Dict[str, StageStats] = {}
        self.wall_start = time.monotonic()
        #: Optional obs.trace.SpanTracer — stage windows mirror into it.
        self.tracer = tracer

    @contextlib.contextmanager
    def stage(
        self, name: str, items: int = 0, nbytes: int = 0, **args
    ) -> Iterator[None]:
        """One window of stage ``name``; ``args`` (a batch's ``batch=``)
        label its profiler annotation."""
        st = self.stages.setdefault(name, StageStats())
        with obs_trace.annotate(name, **args):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                st.seconds += dt
                st.items += items
                st.bytes += nbytes
                if self.tracer is not None:
                    # Same t0/dt as the stat above: the trace and --stats
                    # can never drift apart.
                    self.tracer.add_complete(name, t0, dt, cat="stage")
                # Book the SAME measurement into the live stage counters
                # at every window exit (not once at scan end): the flight
                # recorder samples these mid-scan for per-stage occupancy,
                # and the --stats stage digest renders from the registry
                # snapshot — one measurement, every surface (DESIGN.md
                # §17).
                obs_metrics.STAGE_SECONDS.labels(stage=name).inc(dt)
                if items:
                    obs_metrics.STAGE_RECORDS.labels(stage=name).inc(items)
                if nbytes:
                    obs_metrics.STAGE_BYTES.labels(stage=name).inc(nbytes)

    @property
    def wall_seconds(self) -> float:
        return time.monotonic() - self.wall_start

    def ordered_stages(self) -> "list[tuple[str, StageStats]]":
        """Stages in canonical pipeline order, then alphabetical for any
        stage outside the canon — deterministic across runs."""
        rank = {name: i for i, name in enumerate(STAGE_ORDER)}
        return sorted(
            self.stages.items(),
            key=lambda kv: (rank.get(kv[0], len(STAGE_ORDER)), kv[0]),
        )

    def summary(self) -> str:
        lines = []
        for name, st in self.ordered_stages():
            line = f"  {name}: {st.seconds:.3f}s, {st.items} records"
            if st.items:
                line += f" ({st.items_per_sec:,.0f}/s)"
            if st.bytes:
                line += (
                    f", {st.bytes / 1e6:,.1f} MB ({st.mb_per_sec:,.1f} MB/s)"
                )
            lines.append(line)
        return "\n".join(lines)


@contextlib.contextmanager
def maybe_jax_trace(profile_dir: "Optional[str]") -> Iterator[None]:
    if not profile_dir:
        yield
        return
    import jax

    with jax.profiler.trace(profile_dir):
        yield
