"""Analyzer configuration.

The reference has no config system beyond four CLI flags and a ``--librdkafka``
key=value escape hatch (``src/main.rs:32-67``, SURVEY.md §5.6).  The TPU build
needs a few more knobs (batch shape, sketch precisions, mesh layout); they all
live here as one frozen dataclass so every layer — CLI, backends, parallel —
shares a single source of truth and jitted functions can treat it as static.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class TransportRetryConfig:
    """Transport-fault tolerance knobs for the live Kafka scan.

    Deliberately NOT part of `AnalyzerConfig`: retry pacing changes neither
    state shapes nor fold semantics, and folding it into the analyzer config
    would churn the checkpoint fingerprint (checkpoint.py) for a setting
    that has no effect on the numbers.  Mapped from the librdkafka-style
    ``--librdkafka`` overrides table in io/kafka_wire.py.
    """

    #: First delay after a transport failure (librdkafka ``retry.backoff.ms``;
    #: ``reconnect.backoff.ms`` raises it too when set higher).  Doubles per
    #: consecutive failure.
    backoff_ms: int = 100
    #: Backoff ceiling (librdkafka ``reconnect.backoff.max.ms``).
    backoff_max_ms: int = 10_000
    #: Consecutive transport failures a partition survives before it is
    #: marked *degraded* (scan continues without it) instead of retrying
    #: forever.  Non-librdkafka knob: ``transport.retry.budget``.
    retry_budget: int = 8
    #: Fractional jitter applied to every delay (librdkafka applies ±20%):
    #: a delay d is drawn uniformly from [d·(1-j), d·(1+j)].
    jitter: float = 0.2

    def __post_init__(self) -> None:
        if self.backoff_ms < 1:
            raise ValueError("retry.backoff.ms must be >= 1")
        if self.backoff_max_ms < self.backoff_ms:
            raise ValueError(
                "reconnect.backoff.max.ms must be >= retry.backoff.ms"
            )
        if self.retry_budget < 1:
            raise ValueError("transport.retry.budget must be >= 1")
        if not (0.0 <= self.jitter < 1.0):
            raise ValueError("retry jitter must be in [0, 1)")

    @classmethod
    def from_overrides(cls, overrides: dict) -> "TransportRetryConfig":
        """Pop the retry-related librdkafka-style properties out of an
        overrides dict (mutating it, like the other knob parsers in
        io/kafka_wire.py) and build the config."""
        base = int(overrides.pop("retry.backoff.ms", 100))
        # librdkafka paces reconnect attempts separately; this client runs
        # one schedule, so an explicitly higher reconnect floor wins.
        base = max(base, int(overrides.pop("reconnect.backoff.ms", base)))
        return cls(
            backoff_ms=base,
            backoff_max_ms=max(
                base, int(overrides.pop("reconnect.backoff.max.ms", 10_000))
            ),
            retry_budget=int(overrides.pop("transport.retry.budget", 8)),
        )


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Single-scan parallel-ingest sizing (``--ingest-workers``).

    Like `TransportRetryConfig`, deliberately NOT part of `AnalyzerConfig`:
    how many host threads feed the device changes neither state shapes nor
    fold semantics (the fan-in merge is exact — DESIGN.md §11), so it must
    not churn the checkpoint fingerprint.  A snapshot taken by an N-worker
    scan resumes under any other worker count.
    """

    #: ``1`` = the sequential path (today's default), ``N`` = that many
    #: partition-sharded ingest workers, ``"auto"`` = size from the host:
    #: min(cores - 1, partitions), keeping one core for the merge loop +
    #: device dispatch.  On a sharded mesh the count resolves PER
    #: CONTROLLER: ``resolve`` is called with that controller's shard
    #: partition count, and the result splits across its data rows
    #: (parallel/ingest.py::allocate_row_workers) — so the same CLI line
    #: sizes every host of a heterogeneous fleet correctly (DESIGN.md §14).
    workers: "int | str" = 1

    def __post_init__(self) -> None:
        if isinstance(self.workers, str):
            if self.workers != "auto":
                raise ValueError(
                    f"ingest workers {self.workers!r} invalid "
                    "(a positive integer, or 'auto')"
                )
        elif self.workers < 1:
            raise ValueError("ingest workers must be >= 1")

    @classmethod
    def parse(cls, text: str) -> "IngestConfig":
        """CLI spelling: a positive integer or ``auto``."""
        if text.strip().lower() == "auto":
            return cls(workers="auto")
        try:
            n = int(text)
        except ValueError:
            raise ValueError(
                f"bad --ingest-workers {text!r}: expected a positive "
                "integer or 'auto'"
            ) from None
        return cls(workers=n)

    def resolve(self, num_partitions: int) -> int:
        """Concrete worker count for a topic with ``num_partitions``
        partitions (workers beyond the partition count would sit idle —
        each partition lives in exactly one worker)."""
        import os

        if self.workers == "auto":
            # Cores this process may actually RUN on: in a cgroup/affinity
            # -limited container os.cpu_count() reports the host's cores,
            # and sizing from it would oversubscribe badly.
            try:
                cores = len(os.sched_getaffinity(0))
            except AttributeError:  # non-Linux
                cores = os.cpu_count() or 1
            want = max(1, cores - 1)
        else:
            want = int(self.workers)
        return max(1, min(want, num_partitions))


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Superbatch device-dispatch sizing (``--superbatch``/``--dispatch-depth``).

    Like `IngestConfig`, deliberately NOT part of `AnalyzerConfig`: how many
    packed batches ride one jitted dispatch (and how many superbatches may
    be in flight) changes neither state shapes nor fold semantics — the
    scan-folded superstep applies the K batches in exactly the order the
    sequential path would (backends/step.py::superbatch_fold), so results
    stay byte-identical and it must not churn the checkpoint fingerprint.
    A snapshot taken by a K-superbatch scan resumes under any other K or D
    (snapshots land only at superbatch boundaries — engine.py).
    """

    #: Packed batches stacked into one ``uint8[K, N]`` host array and
    #: folded by a single jitted ``lax.scan`` dispatch (state donated once
    #: per superbatch).  ``1`` = today's one-dispatch-per-batch path;
    #: ``"auto"`` restores the proven-good 2^20 records per dispatch:
    #: max(1, min(16, 2^20 // batch_size)).
    superbatch: "int | str" = 1
    #: Bound on superbatches staged/transferring while the device folds
    #: (the in-flight dispatch queue, backends/base.py::DispatchQueue).
    #: 2 = transfer of superbatch i+1 overlaps the fold of i; higher
    #: values deepen the pipeline at the cost of host+device memory for
    #: the extra staged buffers.
    depth: int = 2
    #: Guardrail on ``auto``'s fold size: at most this many records per
    #: scanned dispatch.  BENCH round 7 measured the failure mode auto must
    #: avoid — K=16 × B=2^16 (2^20 records ≈ a multi-hundred-ms synchronous
    #: fold on a host-CPU jit) regressed e2e to 0.63× because the drive
    #: thread disappears into one fold long enough to starve the ingest
    #: overlap, while K=4 at the same B measured 1.02×.  2^18 records caps
    #: the estimated fold wall at ~30-130 ms across measured rigs (~0.12
    #: µs/record host-CPU fold, ~0.04 device) — long enough to amortize
    #: dispatch overhead, short enough that backpressure stays responsive.
    #: Explicit K is never capped: an operator who asks for 16 gets 16.
    auto_fold_cap_records: int = 1 << 18

    def __post_init__(self) -> None:
        if isinstance(self.superbatch, str):
            if self.superbatch != "auto":
                raise ValueError(
                    f"superbatch {self.superbatch!r} invalid "
                    "(a positive integer, or 'auto')"
                )
        elif self.superbatch < 1:
            raise ValueError("superbatch must be >= 1")
        if self.depth < 1:
            raise ValueError("dispatch depth must be >= 1")
        if self.auto_fold_cap_records < 1:
            raise ValueError("auto fold cap must be >= 1 record")

    @classmethod
    def parse(cls, superbatch: str, depth: int = 2) -> "DispatchConfig":
        """CLI spelling: ``--superbatch K|auto`` + ``--dispatch-depth D``."""
        text = superbatch.strip().lower()
        if text == "auto":
            return cls(superbatch="auto", depth=depth)
        try:
            k = int(text)
        except ValueError:
            raise ValueError(
                f"bad --superbatch {superbatch!r}: expected a positive "
                "integer or 'auto'"
            ) from None
        return cls(superbatch=k, depth=depth)

    def resolve(self, batch_size: int) -> int:
        """Concrete K for a given batch size.  ``auto`` targets the
        proven-good 2^20 records per device dispatch (round 2 established
        2^20 as the default batch; at a smaller B auto wins
        the per-dispatch amortization back without touching the per-batch
        packed layout),
        capped at 16 stacked buffers of host staging AND at
        ``auto_fold_cap_records`` per dispatch — the round-7 guardrail
        against pushing a multi-hundred-ms synchronous fold onto the drive
        thread (K=16 at B=2^16 regressed e2e to 0.63×; DESIGN.md §12)."""
        if self.superbatch == "auto":
            k = max(1, min(16, (1 << 20) // max(1, batch_size)))
            fold_cap = max(1, self.auto_fold_cap_records // max(1, batch_size))
            return min(k, fold_cap)
        return int(self.superbatch)


@dataclasses.dataclass(frozen=True)
class FollowConfig:
    """Follow-mode service knobs (``--follow`` and friends; serve/follow.py).

    Like `IngestConfig`, deliberately NOT part of `AnalyzerConfig`: how
    often the service re-polls watermarks, checkpoints, or rotates report
    windows changes neither state shapes nor fold semantics — a follow
    run's cumulative metrics are byte-identical to a batch scan stopped at
    the same offsets (DESIGN.md §18) — so none of it may churn the
    checkpoint fingerprint.  A snapshot taken by a batch scan resumes
    under ``--follow`` and vice versa.
    """

    #: Watermark re-poll cadence at the head (seconds).  Also the FLOOR of
    #: the idle backoff schedule: consecutive empty polls back off
    #: exponentially from here up to ``idle_backoff_max_s`` (reusing
    #: io/retry.Backoff), so a quiet topic costs metadata queries, not
    #: fetch spin.
    poll_interval_s: float = 1.0
    #: Idle backoff ceiling (seconds) — the longest the service sleeps
    #: between polls of a quiet topic.  Any new data resets the schedule
    #: to ``poll_interval_s``.
    idle_backoff_max_s: float = 10.0
    #: Checkpoint cadence (seconds, ``--checkpoint-interval``).  Commits
    #: happen only at superbatch boundaries (the engine's long-standing
    #: fold-consistency rule), so this is a floor, not an exact period.
    checkpoint_every_s: float = 60.0
    #: Exit cleanly after this long at the head with no new records
    #: (``--follow-idle-exit``); None = follow forever.  The "drain and
    #: stop" mode: catch up, wait out the idle window, report, exit 0.
    idle_exit_s: "float | None" = None
    #: Width of one report window (seconds) for the time-windowed folds
    #: served at /report.json (serve/windows.py).
    window_secs: float = 60.0
    #: Number of window states kept in the ring (0 disables windowed
    #: folds).  "What changed in the last 5 minutes" is the associative
    #: merge of the last ceil(300/window_secs) states.
    window_count: int = 8
    #: HLL precision for the per-window per-partition cardinality fold
    #: (2^p one-byte registers per partition per window — deliberately
    #: smaller than the scan's cumulative sketch: window memory is
    #: P * 2^p * window_count bytes).
    window_hll_p: int = 10

    def __post_init__(self) -> None:
        if self.poll_interval_s <= 0:
            raise ValueError("--poll-interval must be > 0 seconds")
        if self.idle_backoff_max_s < self.poll_interval_s:
            raise ValueError(
                "idle backoff ceiling must be >= the poll interval"
            )
        if self.checkpoint_every_s < 0:
            raise ValueError("--checkpoint-interval must be >= 0 seconds")
        if self.idle_exit_s is not None and self.idle_exit_s < 0:
            raise ValueError("--follow-idle-exit must be >= 0 seconds")
        if self.window_secs <= 0:
            raise ValueError("--window-secs must be > 0 seconds")
        if self.window_count < 0:
            raise ValueError("--window-count must be >= 0")
        if not (4 <= self.window_hll_p <= 16):
            raise ValueError("window hll precision must be in [4, 16]")


#: Valid --lease-store selections: ``auto`` derives the store from the
#: run (the object store when --segment-store is remote, else lease
#: files in the checkpoint dir), the other two pin it.
LEASE_STORES = ("auto", "file", "object")


@dataclasses.dataclass(frozen=True)
class LeaseConfig:
    """Multi-instance fleet lease knobs (``--instance-id``/``--lease-ttl``;
    fleet/lease.py, DESIGN.md §23).

    Like `FollowConfig`, deliberately NOT part of `AnalyzerConfig`: who
    owns a topic (and for how long before failover) changes neither
    state shapes nor fold semantics — a fleet of N instances produces
    per-topic reports byte-identical to one instance scanning the same
    offsets — so none of it may churn the checkpoint fingerprint.  The
    lease EPOCH does ride snapshot metadata, but as a fencing stamp
    outside the fingerprint: any instance resumes any topic's snapshot,
    provided its own epoch is current.
    """

    #: This analyzer's identity on every lease record, booked metric,
    #: and published document.  Empty = leases disabled (the solo
    #: single-owner fleet, exactly the PR-13 behavior).
    instance_id: str = ""
    #: Lease lifetime in seconds: the failover bound (a crashed owner's
    #: topics are up for grabs this long after its last renewal) AND the
    #: zombie window the epoch fence must cover.  Renewals ride every
    #: poll boundary, so this must comfortably exceed the poll interval.
    ttl_s: float = 30.0
    #: Where lease records live (``LEASE_STORES``).
    store: str = "auto"

    def __post_init__(self) -> None:
        if self.ttl_s <= 0:
            raise ValueError("--lease-ttl must be > 0 seconds")
        if self.store not in LEASE_STORES:
            raise ValueError(
                f"lease store {self.store!r} invalid "
                f"({', '.join(LEASE_STORES)})"
            )

    @property
    def enabled(self) -> bool:
        return bool(self.instance_id)


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Alert-engine knobs (obs/health.py; DESIGN.md §22).

    Like `FollowConfig`, deliberately NOT part of `AnalyzerConfig`: how
    often health is evaluated (and what thresholds page) changes neither
    state shapes nor fold semantics — the engine only READS registry
    snapshots and observed windows — so none of it may churn the
    checkpoint fingerprint, and a scan is byte-identical with alerting
    on or off (tests/test_health.py pins it).
    """

    #: Floor between evaluations on the rate-limited ``maybe_evaluate``
    #: path (the engine heartbeat hook).  Poll-boundary evaluations from
    #: the follow/fleet services are not limited by it — a poll boundary
    #: IS an evaluation point, which is what makes the /healthz flip
    #: land within one interval of the fault (the acceptance bar).
    eval_interval_s: float = 5.0
    #: Default for-duration: a rule's condition must hold this long
    #: before the alert fires (blip suppression).
    for_s: float = 10.0
    #: Default resolve hysteresis: the condition must stay clear this
    #: long before a firing alert resolves (flap suppression).
    resolve_s: float = 15.0
    #: Lag-growth window: lag must exceed its value this far back (by at
    #: least ``lag_min_growth`` records) to count as diverging.
    lag_window_s: float = 30.0
    lag_min_growth: int = 1
    #: Corruption-storm window and the frames-per-window threshold.
    storm_window_s: float = 60.0
    corrupt_frames_threshold: float = 1.0
    #: Watermark-refresh-outage window (any budget-exhausted re-poll
    #: inside it keeps the condition true).
    outage_window_s: float = 60.0
    #: Throughput regression: recent window vs the trailing baseline
    #: window; fires when recent < drop_fraction x baseline while lag
    #: remains, and never below ``min_baseline_rate`` records/s (an
    #: idle or tiny scan has no baseline worth defending).
    throughput_window_s: float = 30.0
    throughput_baseline_s: float = 180.0
    throughput_drop_fraction: float = 0.5
    min_baseline_rate: float = 1.0
    #: Observed-series retention (must cover the longest rule window).
    retention_s: float = 900.0

    def __post_init__(self) -> None:
        if self.eval_interval_s <= 0:
            raise ValueError("health eval interval must be > 0 seconds")
        if self.for_s < 0 or self.resolve_s < 0:
            raise ValueError("for/resolve durations must be >= 0")
        if self.lag_window_s <= 0 or self.storm_window_s <= 0:
            raise ValueError("rule windows must be > 0 seconds")
        if self.outage_window_s <= 0 or self.throughput_window_s <= 0:
            raise ValueError("rule windows must be > 0 seconds")
        if self.throughput_baseline_s <= self.throughput_window_s:
            raise ValueError(
                "throughput baseline window must exceed the recent window"
            )
        if not (0.0 < self.throughput_drop_fraction < 1.0):
            raise ValueError("throughput drop fraction must be in (0, 1)")
        if self.retention_s < max(
            self.lag_window_s,
            self.storm_window_s,
            self.outage_window_s,
            self.throughput_baseline_s,
        ):
            raise ValueError(
                "health retention must cover the longest rule window"
            )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Read-path knobs (serve/state.py + serve/push.py; DESIGN.md §26).

    Like `HealthConfig`, deliberately NOT part of `AnalyzerConfig`: how
    reports are encoded and pushed changes neither state shapes nor fold
    semantics — the serving plane only READS published snapshots — so
    none of it may churn the checkpoint fingerprint, and a scan is
    byte-identical with the serving stack on or off
    (tests/test_serve_plane.py pins it).
    """

    #: Compress /report.json bodies once at publish time (``gzip_level``
    #: below); ``--no-serve-gzip`` clears it.
    gzip: bool = True
    #: Gzip level for publish-time encoding: 6 is the classic wire
    #: default — ~10× on report JSON, a low-single-digit-ms cost paid
    #: once per poll boundary, never per request.
    gzip_level: int = 6
    #: Bodies smaller than this are not worth a gzip member's overhead;
    #: the publish stores no gzip variant and every client gets identity.
    gzip_min_bytes: int = 256
    #: Per-subscriber SSE frame queue bound — a subscriber this far
    #: behind the publish rate is evicted, never blocked on
    #: (kta_serve_sse_dropped_total{reason="slow-client"}).
    sse_queue_len: int = 64
    #: SSE keepalive-comment cadence while no publish arrives, keeping
    #: intermediaries from idling out the stream.
    sse_keepalive_s: float = 15.0

    def __post_init__(self) -> None:
        if not (1 <= self.gzip_level <= 9):
            raise ValueError("serve gzip level must be in 1..9")
        if self.gzip_min_bytes < 0:
            raise ValueError("serve gzip floor must be >= 0 bytes")
        if self.sse_queue_len < 1:
            raise ValueError("SSE queue length must be >= 1")
        if self.sse_keepalive_s <= 0:
            raise ValueError("SSE keepalive must be > 0 seconds")


#: The one shared default — the serve modules read their constants from
#: here so a knob has exactly one home.
DEFAULT_SERVE = ServeConfig()


@dataclasses.dataclass(frozen=True)
class SegmentFetchConfig:
    """Remote-segment-tier knobs (``--segment-readahead``/``--segment-cache``;
    io/objstore.py + io/segstore.py, DESIGN.md §21).

    Like `IngestConfig`, deliberately NOT part of `AnalyzerConfig`: how a
    chunk's bytes ARRIVE (read ahead over the network, served from a local
    cache, or memory-mapped) changes neither state shapes nor fold
    semantics — a remote scan is byte-identical to the local-directory
    scan of the same chunks — so none of it may churn the checkpoint
    fingerprint.  A snapshot taken against one store resumes against any
    other store holding the same segments (cross-store resume).
    """

    #: Chunks kept in flight ahead of each consuming ingest stream (the
    #: per-stream WINDOW; the process-wide fetch scheduler in
    #: io/fetchsched.py supplies the workers).  In-flight chunk memory
    #: stays bounded by streams × (readahead + 1) chunks.  ``"auto"``
    #: resolves per store: 0 for local directories (the memmap faults
    #: pages in for free) and 4 for remote stores (enough speculation in
    #: flight to hide tens of ms of per-GET latency behind the fused
    #: decode→pack pass).  0 disables speculation: every chunk is a
    #: demand fetch at first touch — still admitted through the
    #: scheduler.
    readahead: "int | str" = "auto"
    #: Worker count of the ONE process-wide fetch scheduler
    #: (``--fetch-concurrency N|auto``) — the single admission point for
    #: every remote byte.  Sized once per process, NOT per stream: total
    #: connection count no longer multiplies with ingest workers.
    #: ``"auto"`` sizes from the host (min(16, max(4, cpu_count))) and
    #: grows with the resolved ingest-stream count; an explicit N pins it.
    fetch_concurrency: "int | str" = "auto"
    #: Local chunk-cache directory (``--segment-cache``); None disables.
    #: Remote stores only — caching a local directory would just copy it.
    cache_dir: "str | None" = None
    #: Cache size bound in bytes (``--segment-cache-bytes``): inserts
    #: evict least-recently-used entries past it.
    cache_max_bytes: int = 1 << 30
    #: Per-request socket timeout (connect and read) in seconds.  A stall
    #: past it is a transient transport failure: backoff, retry, budget.
    timeout_s: float = 30.0
    #: Transport retry pacing + per-partition budget — the SAME recovery
    #: substrate the live wire scan runs (io/retry.py): a partition whose
    #: chunks stay unreachable past the budget is degraded, not fatal.
    retry: TransportRetryConfig = dataclasses.field(
        default_factory=TransportRetryConfig
    )

    def __post_init__(self) -> None:
        if isinstance(self.readahead, str):
            if self.readahead != "auto":
                raise ValueError(
                    f"segment readahead {self.readahead!r} invalid "
                    "(an integer >= 0, or 'auto')"
                )
        elif self.readahead < 0:
            raise ValueError("segment readahead must be >= 0")
        if isinstance(self.fetch_concurrency, str):
            if self.fetch_concurrency != "auto":
                raise ValueError(
                    f"fetch concurrency {self.fetch_concurrency!r} invalid "
                    "(an integer >= 1, or 'auto')"
                )
        elif self.fetch_concurrency < 1:
            raise ValueError("fetch concurrency must be >= 1")
        if self.cache_max_bytes < 1:
            raise ValueError("--segment-cache-bytes must be >= 1")
        if self.timeout_s <= 0:
            raise ValueError("segment fetch timeout must be > 0 seconds")

    @classmethod
    def parse(
        cls,
        readahead: str = "auto",
        cache_dir: "str | None" = None,
        cache_max_bytes: int = 1 << 30,
        fetch_concurrency: str = "auto",
    ) -> "SegmentFetchConfig":
        """CLI spelling: ``--segment-readahead N|auto``,
        ``--fetch-concurrency N|auto``, + cache flags."""
        text = str(readahead).strip().lower()
        if text == "auto":
            ra: "int | str" = "auto"
        else:
            try:
                ra = int(text)
            except ValueError:
                raise ValueError(
                    f"bad --segment-readahead {readahead!r}: expected an "
                    "integer >= 0 or 'auto'"
                ) from None
        fc_text = str(fetch_concurrency).strip().lower()
        if fc_text == "auto":
            fc: "int | str" = "auto"
        else:
            try:
                fc = int(fc_text)
            except ValueError:
                raise ValueError(
                    f"bad --fetch-concurrency {fetch_concurrency!r}: "
                    "expected an integer >= 1 or 'auto'"
                ) from None
        return cls(
            readahead=ra, cache_dir=cache_dir, cache_max_bytes=cache_max_bytes,
            fetch_concurrency=fc,
        )

    def resolve_readahead(self, remote: bool) -> int:
        """Concrete per-stream read-ahead depth: ``auto`` = 4 for remote
        stores (hide per-GET wire latency behind the running decode→pack
        pass), 0 for local directories (nothing to hide — page faults)."""
        if self.readahead == "auto":
            return 4 if remote else 0
        return int(self.readahead)

    def resolve_concurrency(self) -> "int | None":
        """Concrete scheduler pool size, or None for ``auto`` (the
        scheduler sizes itself from the host and the engine's resolved
        ingest-stream count — io/fetchsched.py)."""
        if self.fetch_concurrency == "auto":
            return None
        return int(self.fetch_concurrency)


#: Valid --on-corruption policies, in escalation order.
CORRUPTION_POLICIES = ("fail", "skip", "quarantine")


@dataclasses.dataclass(frozen=True)
class CorruptionConfig:
    """Poison-frame policy for the live Kafka scan (io/kafka_wire.py).

    Like `TransportRetryConfig`, deliberately NOT part of `AnalyzerConfig`:
    how the scan reacts to corrupt frames changes neither state shapes nor
    fold semantics, so it must not churn the checkpoint fingerprint.

    Policies (applied only after a re-fetch reproduced the identical
    failure — a one-shot in-flight bit flip is retried, not classified):

    - ``fail``: abort the scan with the classified error (the default —
      exactly the pre-corruption-layer behavior);
    - ``skip``: skip exactly the poisoned frame, account for it
      per-partition, finish the scan, exit `cli.EXIT_CORRUPT`;
    - ``quarantine``: like skip, plus the raw frame bytes are spooled to
      ``quarantine_dir`` with a JSON sidecar (io/quarantine.py) so the
      evidence survives for offline analysis.
    """

    policy: str = "fail"
    quarantine_dir: "str | None" = None

    def __post_init__(self) -> None:
        if self.policy not in CORRUPTION_POLICIES:
            raise ValueError(
                f"on-corruption policy {self.policy!r} invalid "
                f"({', '.join(CORRUPTION_POLICIES)})"
            )
        if self.policy == "quarantine" and not self.quarantine_dir:
            raise ValueError(
                "--on-corruption=quarantine requires --quarantine-dir"
            )
        if self.quarantine_dir and self.policy != "quarantine":
            raise ValueError(
                "--quarantine-dir only applies with --on-corruption=quarantine"
            )


#: Valid --on-data-loss policies, in escalation order.
DATA_LOSS_POLICIES = ("fail", "report", "ignore")


@dataclasses.dataclass(frozen=True)
class DataLossConfig:
    """Log-mutation policy for the live Kafka scan (io/kafka_wire.py):
    what to do when the log MOVES under the scanner and records in
    ``[cursor, new_log_start)`` are unreachable (retention race), or a
    leader-epoch divergence proves the log was truncated below the
    cursor (unclean election).

    Like `CorruptionConfig`, deliberately NOT part of `AnalyzerConfig`:
    the reaction policy changes neither state shapes nor fold semantics,
    so it must not churn the checkpoint fingerprint.  Whatever the
    policy, every lost record is BOOKED (kta_log_lost_*) and spanned —
    the policy only governs whether the scan continues and how the exit
    code reflects the loss:

    - ``fail``: abort the scan with the classified error; the engine's
      failure path still writes a fold-consistent checkpoint, so a
      resume continues from committed state;
    - ``report``: keep scanning the surviving records, surface the loss
      as a DATA-LOSS report block / ``data_loss`` JSON map, exit
      `cli.EXIT_DATA_LOSS` (the default — a long-running follow service
      must not die to ordinary retention);
    - ``ignore``: keep scanning and exit 0 — for logs where retention
      churn is expected; the metrics and report blocks still name the
      loss (never-silent is not policy-dependent).
    """

    policy: str = "report"

    def __post_init__(self) -> None:
        if self.policy not in DATA_LOSS_POLICIES:
            raise ValueError(
                f"on-data-loss policy {self.policy!r} invalid "
                f"({', '.join(DATA_LOSS_POLICIES)})"
            )


@dataclasses.dataclass(frozen=True)
class AnalyzerConfig:
    """Static configuration for one analysis run.

    Anything that changes the compiled XLA program (shapes, enabled sketches,
    precisions) belongs here; runtime data (offsets, records) does not.
    """

    # --- topology -----------------------------------------------------------
    #: Number of Kafka partitions in the topic (P).  Static: it fixes the
    #: shape of the per-partition counter matrix (reference keeps HashMaps
    #: keyed by partition id instead, src/metric.rs:12-19).
    num_partitions: int = 1
    #: Records per device step (B).  The last batch is padded with
    #: ``valid=False`` records (XLA static shapes; SURVEY.md §7 hard part (b)).
    batch_size: int = 1 << 16

    # --- feature toggles (each adds state + kernels to the jitted update) ---
    #: Reference-compatible alive-key bitmap (``-c`` flag; src/metric.rs:262-305).
    count_alive_keys: bool = False
    #: log2 of the bitmap slot space.  The reference's fnv32 hash gives 2^32
    #: slots (512 MiB of packed bits); smaller values trade memory for extra
    #: collisions.  Hashes are masked to this width.
    alive_bitmap_bits: int = 32
    #: HyperLogLog distinct-key sketch (new capability; replaces the bitmap's
    #: O(2^bits) memory with O(2^hll_p) at ~1.04/sqrt(2^hll_p) rel. error).
    enable_hll: bool = False
    #: HLL precision p (m = 2^p registers). p=16 → 0.41% standard error,
    #: holding BASELINE.md's ≤1% budget at >2σ (p=14's 0.81% rode the edge:
    #: r3 recorded a 1.6% draw on config 3).  Capped at 16, the widest p
    #: whose bucket indices (0..2^p-1) fit the packed transfer's u16
    #: section; inactive records ship idx 0 with rho 0 (a scatter-max
    #: no-op), so no out-of-range sentinel index is needed.
    hll_p: int = 16
    #: One register file per partition instead of a single global one
    #: (implies enable_hll).  The global estimate stays exact HLL semantics:
    #: rows union by elementwise max.
    distinct_keys_per_partition: bool = False
    #: DDSketch message-size quantiles (new capability).
    enable_quantiles: bool = False
    #: Track one sketch row per partition instead of a single global one
    #: (BASELINE.json config 2: per-partition size histograms).  Global
    #: quantiles remain exact — DDSketch rows merge by addition.
    quantiles_per_partition: bool = False
    #: DDSketch relative accuracy alpha (gamma = (1+a)/(1-a)).
    quantile_alpha: float = 0.005
    #: Number of log-gamma buckets (covers sizes up to gamma^nbuckets).
    quantile_buckets: int = 2560

    #: Use the Pallas MXU one-hot-matmul kernel for the per-partition counter
    #: reduction (ops/pallas_counters.py) instead of the XLA scatter-add.
    #: Requires batch_size a multiple of 1024 (validated in __post_init__)
    #: and value lengths < 16 MiB (pack time); partitions beyond 128 tile
    #: the kernel grid.  Off by default until benchmarked faster on the
    #: target hardware.  (Under wire v5 the counter fold arrives as a
    #: pre-reduced table and this flag routes the merge through
    #: ops/pallas_counters.pallas_counters_merge instead.)
    use_pallas_counters: bool = False
    #: Run those Pallas kernels through the interpreter.  Not a user
    #: option: the backends set it from the platform of their own device
    #: (True off the TPU, where no Mosaic kernel can compile).
    pallas_interpret: bool = False

    #: Host-side alive-pair compaction (``--alive-compaction``; DESIGN §19):
    #: ``auto`` (the default) compacts the last-writer-wins (slot, alive)
    #: pairs out of the per-batch wire rows into ONE bounded per-dispatch
    #: pair table — per-batch at K=1, per-SUPERBATCH at --superbatch K>1 —
    #: that the device merges once per dispatch instead of running the
    #: O(B) pair scatter (and its O(W) mask apply) inside every scan step.
    #: LWW compaction is itself LWW-associative, so results are
    #: byte-identical to the uncompacted fold.  Resolves ON only under the
    #: v5 combiner format with the alive bitmap enabled; ``off`` (or the
    #: ``KTA_DISABLE_COMPACTION`` env kill switch) keeps the v5 per-row
    #: pair sections — the bypass is booked on
    #: ``kta_alive_compaction_off_total{reason}``, never silent.  Pure
    #: execution strategy: byte-identical results, outside the checkpoint
    #: fingerprint (checkpoint.py), snapshots resume across the setting.
    alive_compaction: str = "auto"

    #: Packed host→device wire format (packing.py): ``0`` = auto (resolved
    #: at construction — v5 unless the ``KTA_WIRE_V4`` kill switch is set),
    #: ``4`` = per-record columns + host-pre-reduced extreme/alive/HLL
    #: sections, ``5`` = the combiner format: the remaining per-record
    #: columns are replaced by per-partition partial-fold tables (counter
    #: deltas, DDSketch bucket counts), so the device merges O(P·H) table
    #: rows instead of scattering O(B) records.  Results are byte-identical
    #: across formats (every fold is an associative integer reduction —
    #: DESIGN.md §2/§16), so this is pure execution strategy: it is
    #: excluded from the checkpoint fingerprint and snapshots resume across
    #: formats (checkpoint.py).
    wire_format: int = 0

    # --- host→device transfer ----------------------------------------------
    #: Pre-reduce bitmap updates on the host: last-writer-wins dedupe of
    #: (slot, alive) pairs per batch (C++ shim or numpy), so the device does
    #: two scatter-adds instead of a 1M-element sort.  The device-sort path
    #: remains available for reference (packing always dedupes on host; the
    #: sort kernel is exercised by its own unit tests).
    # --- parallelism --------------------------------------------------------
    #: Device mesh shape (data, space).  'data' shards record batches by
    #: partition; 'space' shards BOTH the alive-bitmap slot space and the
    #: record stream: each data row's batch is split into space_shards
    #: contiguous chunks (one per space shard, batch_size/space_shards
    #: records each), so host→device bytes and per-device reduction work
    #: scale down with the space axis; bitmap updates are redistributed
    #: on-device over ICI (all_gather + in-source-order application —
    #: backends/step.py).  (1, 1) runs single-device.
    #: See kafka_topic_analyzer_tpu/parallel/.
    mesh_shape: Tuple[int, int] = (1, 1)

    def __post_init__(self) -> None:
        if self.quantiles_per_partition and not self.enable_quantiles:
            # Per-partition sketches imply the feature (frozen dataclass, so
            # normalize via object.__setattr__).
            object.__setattr__(self, "enable_quantiles", True)
        if self.distinct_keys_per_partition and not self.enable_hll:
            object.__setattr__(self, "enable_hll", True)
        if self.num_partitions < 1:
            raise ValueError("num_partitions must be >= 1")
        if self.batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if not (0 < self.alive_bitmap_bits <= 32):
            raise ValueError("alive_bitmap_bits must be in (0, 32]")
        if not (4 <= self.hll_p <= 16):
            raise ValueError("hll_p must be in [4, 16]")
        if self.quantile_buckets < 8:
            raise ValueError("quantile_buckets must be >= 8")
        if self.wire_format == 0:
            import os

            # Resolved (and the reason recorded) ONCE, here: the booking
            # property below must describe how this config actually chose
            # v4, not whatever the env says when the engine reads it.
            forced = bool(os.environ.get("KTA_WIRE_V4"))
            object.__setattr__(self, "wire_format", 4 if forced else 5)
            object.__setattr__(
                self, "_wire_v4_reason", "env-kill-switch" if forced else None
            )
        elif self.wire_format in (4, 5):
            object.__setattr__(
                self,
                "_wire_v4_reason",
                "explicit" if self.wire_format == 4 else None,
            )
        else:
            raise ValueError(
                f"wire_format {self.wire_format!r} invalid (0=auto, 4, or 5)"
            )
        if self.alive_compaction not in ("auto", "off"):
            raise ValueError(
                f"alive_compaction {self.alive_compaction!r} invalid "
                "(auto or off)"
            )
        # Resolve alive-pair compaction ONCE, here, with the reason it is
        # off recorded at resolution time (same discipline as the wire-v4
        # reason above: the engine's fallback booking must describe the
        # decision actually taken, not whatever the env says later).
        compact = False
        off_reason = None
        if self.count_alive_keys:
            import os

            if self.alive_compaction == "off":
                off_reason = "explicit"
            elif os.environ.get("KTA_DISABLE_COMPACTION"):
                off_reason = "env-kill-switch"
            elif self.wire_format != 5:
                # The compacted pair table is a v5 combiner section; the
                # v4 layout keeps its per-row pairs.
                off_reason = "wire-v4"
            else:
                compact = True
        object.__setattr__(self, "_compact_alive", compact)
        object.__setattr__(self, "_alive_compaction_off_reason", off_reason)
        if (
            self.use_pallas_counters
            and self.wire_format == 4
            and self.batch_size % 1024
        ):
            # A constraint of the v4 MXU one-hot-matmul kernel's 1024-record
            # blocks only: under wire v5 the counter fold arrives as a
            # pre-reduced table and pallas_counters_merge pads any shape.
            raise ValueError(
                "use_pallas_counters requires batch_size % 1024 == 0"
            )

    @property
    def hll_m(self) -> int:
        return 1 << self.hll_p

    @property
    def wire_v4_reason(self) -> "str | None":
        """Why this config runs wire v4 (None when it runs v5):
        ``env-kill-switch`` (KTA_WIRE_V4 forced the fallback at
        construction) or ``explicit`` (the caller pinned v4).  Recorded
        AT RESOLUTION TIME in ``__post_init__`` — not re-read from the
        env — so the engine's ``kta_wire_v4_fallback_total`` booking
        describes the decision actually taken (a bypassed combiner format
        is never silent, same discipline as ``kta_fused_fallback_total``;
        a ``dataclasses.replace`` of an env-forced config re-labels as
        ``explicit``, which is what the copy's pinned field now is)."""
        return self._wire_v4_reason

    @property
    def compact_alive(self) -> bool:
        """True when this config ships alive pairs as a compacted
        per-dispatch pair table instead of per-row sections (resolved in
        ``__post_init__`` — see ``alive_compaction``)."""
        return self._compact_alive

    @property
    def alive_compaction_off_reason(self) -> "str | None":
        """Why an alive-key scan runs WITHOUT pair compaction (None when
        compaction is on, or when the config has no alive bitmap to
        compact): ``explicit`` (--alive-compaction off),
        ``env-kill-switch`` (KTA_DISABLE_COMPACTION), or ``wire-v4``.
        Recorded at resolution time like ``wire_v4_reason`` so the
        ``kta_alive_compaction_off_total`` booking can never drift from
        the decision taken."""
        return self._alive_compaction_off_reason

    @property
    def quantile_gamma(self) -> float:
        a = self.quantile_alpha
        return (1.0 + a) / (1.0 - a)

    @property
    def data_shards(self) -> int:
        return self.mesh_shape[0]

    @property
    def space_shards(self) -> int:
        return self.mesh_shape[1]

    @property
    def chunk_size(self) -> int:
        """Records per (data, space) device per step: each data row's batch
        is split contiguously across the space axis."""
        return self.batch_size // self.space_shards
