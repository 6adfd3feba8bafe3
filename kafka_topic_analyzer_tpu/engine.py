"""Scan orchestration — the analog of ``main``'s wiring + read loop.

Replaces src/main.rs:69-121 (build analyzer → snapshot offsets → empty guard
→ register handlers → scan → report) with: build source → snapshot
watermarks → empty guard → build backend → batched scan → finalize → report.

Partition ids need not be dense (the reference keeps HashMaps keyed by id);
the engine remaps true ids to dense row indices before batches reach the
backend and maps them back in the result.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import numpy as np

from kafka_topic_analyzer_tpu.backends.base import MetricBackend
from kafka_topic_analyzer_tpu.config import IngestConfig
from kafka_topic_analyzer_tpu.io.source import RecordSource
from kafka_topic_analyzer_tpu.obs import events as obs_events
from kafka_topic_analyzer_tpu.obs import health as obs_health
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.obs import trace as obs_trace
from kafka_topic_analyzer_tpu.obs.registry import (
    default_registry,
    merge_snapshots,
)
from kafka_topic_analyzer_tpu.records import RecordBatch
from kafka_topic_analyzer_tpu.results import TopicMetrics
from kafka_topic_analyzer_tpu.utils.profiling import ScanProfile
from kafka_topic_analyzer_tpu.utils.progress import Spinner
from kafka_topic_analyzer_tpu.utils.timefmt import format_utc_seconds


class PartitionIndex:
    """Bidirectional map between true partition ids and dense row indices."""

    def __init__(self, partition_ids: "list[int]"):
        self.ids = sorted(partition_ids)
        self._sorted = np.array(self.ids, dtype=np.int64)
        #: Fixed at construction: dense ids make remapping a no-op.
        self.is_dense = self.ids == list(range(len(self.ids)))

    def __len__(self) -> int:
        return len(self.ids)

    def to_dense(self, partition: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._sorted, partition).astype(np.int32)

    def remap_batch(self, batch: RecordBatch) -> RecordBatch:
        if self.is_dense:
            return batch
        batch.partition = self.to_dense(batch.partition)
        return batch


@dataclasses.dataclass
class ScanResult:
    metrics: TopicMetrics
    duration_secs: int
    profile: ScanProfile
    start_offsets: "dict[int, int]"
    end_offsets: "dict[int, int]"
    #: partition -> reason, for partitions the source dropped after
    #: exhausting their retry budget (graceful degradation).  Non-empty
    #: means the metrics UNDERCOUNT those partitions' tails: the report
    #: flags them and the CLI exits non-zero (cli.EXIT_DEGRADED).
    degraded_partitions: "dict[int, str]" = dataclasses.field(
        default_factory=dict
    )
    #: partition -> {"frames", "records", "bytes", "quarantined", "kinds",
    #: "spans"} for poisoned frames the source skipped or quarantined under
    #: --on-corruption (cumulative across a --resume chain: the snapshot
    #: carries the spans and the source is re-seeded with them).  Non-empty
    #: means the metrics exclude exactly those frames' records: the report
    #: renders a CORRUPT block and the CLI exits EXIT_CORRUPT.
    corrupt_partitions: "dict[int, dict]" = dataclasses.field(
        default_factory=dict
    )
    #: partition -> {"records", "ranges", "reasons", "authoritative",
    #: "spans"} for offset ranges the log mutated out from under the scan
    #: (retention races, truncation after unclean election,
    #: resume-below-log-start; KafkaWireSource.loss_stats format,
    #: cumulative across a --resume chain like corrupt_partitions).
    #: Non-empty means the metrics describe the SURVIVING records only:
    #: the report renders a DATA-LOSS block, and ``authoritative: False``
    #: (truncation) additionally means already-folded records were
    #: replaced under the scan.
    lost_partitions: "dict[int, dict]" = dataclasses.field(
        default_factory=dict
    )
    #: Registry snapshot taken at scan end (obs.registry format) — under
    #: multi-controller, the cluster-wide merge of every process's
    #: registry, so the report process can render fleet totals
    #: (``--stats``) and ``--json`` can embed them (``telemetry`` block).
    telemetry: "Optional[dict]" = None
    #: Parallel-ingest worker threads THIS process's scan actually ran
    #: (after clamping to the partition count); 1 = the sequential path.
    #: The ``--stats`` digest and ``--json`` report surface it so a
    #: recorded throughput number always carries its parallelism.
    ingest_workers: int = 1
    #: Resolved worker counts per controller, index = process id — under
    #: multi-controller each process resolves ``--ingest-workers`` against
    #: ITS shard's partition count, so a single scalar cannot describe the
    #: fleet.  Collected over the same gather_telemetry collective as the
    #: registry merge (per-process snapshots carry the
    #: kta_ingest_resolved_workers gauge).  Single-controller scans hold
    #: one entry equal to ``ingest_workers``.
    ingest_workers_per_controller: "list[int]" = dataclasses.field(
        default_factory=list
    )
    #: Superbatch size the device backend actually ran (resolved
    #: ``--superbatch``): packed batches folded per jitted dispatch.
    #: 1 = the classic one-dispatch-per-batch path.  Reported alongside
    #: ingest_workers for the same reason — dispatch amortization is part
    #: of any recorded throughput number's configuration.
    superbatch_k: int = 1
    #: Bound on in-flight superbatch dispatches (``--dispatch-depth``).
    dispatch_depth: int = 1
    #: Packed wire-format accounting (results.WireStats): format,
    #: per-record vs fold-table byte split, and the scan's actual wire
    #: bytes — None for backends without a packed transfer (cpu oracle).
    wire: "Optional[object]" = None
    #: Per-partition next-unread offsets at scan end (the progress
    #: tracker's final positions).  For a clean batch scan these equal
    #: ``end_offsets``; degraded partitions stop early.  Follow mode
    #: (serve/follow.py) chains passes on this cursor: pass N+1 starts
    #: exactly where pass N's fold committed.
    next_offsets: "dict[int, int]" = dataclasses.field(default_factory=dict)


class _ProgressTracker:
    """Per-partition next-offset tracking for snapshot resume.

    Gapless sources are tracked by counting records; sources that attach
    per-record offsets (compacted Kafka topics) are tracked exactly.
    """

    def __init__(self, start_offsets: "dict[int, int]"):
        self.next_offsets = dict(start_offsets)

    def observe(self, batch: RecordBatch, true_partition: np.ndarray) -> None:
        valid = batch.valid
        if batch.offsets is not None:
            parts = true_partition[valid]
            offs = batch.offsets[valid]
            for p in np.unique(parts):
                self.next_offsets[int(p)] = max(
                    self.next_offsets.get(int(p), 0),
                    int(offs[parts == p].max()) + 1,
                )
        else:
            parts, counts = np.unique(true_partition[valid], return_counts=True)
            for p, c in zip(parts.tolist(), counts.tolist()):
                self.next_offsets[p] = self.next_offsets.get(p, 0) + int(c)

    def observe_packed(self, row) -> None:
        """Fused-path twin of ``observe``: a packing.PackedRow carries the
        per-partition bookkeeping pre-aggregated (offset-exact partitions
        in ``next_offsets``, offset-less ones as ``counts``) instead of
        per-record columns."""
        for p, o in row.next_offsets.items():
            self.next_offsets[p] = max(self.next_offsets.get(p, 0), o)
        for p, c in row.counts.items():
            self.next_offsets[p] = self.next_offsets.get(p, 0) + c


def _note_streams(source, workers: int) -> None:
    """Book the ingest streams this scan just resolved (the resolved-
    workers gauge and the once-per-scan streams counter), and tell the
    process-wide fetch scheduler: under ``--fetch-concurrency auto`` the
    shared pool grows so every stream can keep a demand fetch plus some
    speculation in flight (an explicit size is never overridden).  Only
    remote segment sources feed the scheduler."""
    obs_metrics.INGEST_RESOLVED_WORKERS.set(workers)
    obs_metrics.INGEST_STREAMS.inc(workers)
    if bool(getattr(getattr(source, "store", None), "is_remote", False)):
        from kafka_topic_analyzer_tpu.io import fetchsched

        fetchsched.note_streams(workers)


def _in_scan_span(fn):
    """Run ``fn`` (run_scan) inside its ``scan`` span: the scan's id,
    which every span of the scan carries (obs/trace.py)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        tracer = kwargs.get("tracer") or obs_trace.active()
        with obs_trace.scan_span(tracer):
            return fn(*args, **kwargs)

    return wrapped


@_in_scan_span
def run_scan(
    topic: str,
    source: RecordSource,
    backend: MetricBackend,
    batch_size: int,
    spinner: Optional[Spinner] = None,
    snapshot_dir: Optional[str] = None,
    snapshot_every_s: float = 60.0,
    resume: bool = False,
    prefetch_depth: int = 2,
    start_at: "Optional[dict[int, int]]" = None,
    tracer=None,
    heartbeat_every_s: float = 10.0,
    ingest_workers: "int | str | IngestConfig" = 1,
    initial_seq: int = 0,
    heartbeat: "Optional[obs_events.Heartbeat]" = None,
    emit_lifecycle: bool = True,
    book_once: bool = True,
    final_snapshot: bool = False,
    lease_epoch: "Optional[int]" = None,
) -> ScanResult:
    """Full earliest→latest scan of the topic through the backend.

    With ``snapshot_dir`` set, the analyzer state + per-partition progress
    are saved atomically every ``snapshot_every_s`` seconds; with ``resume``
    a compatible snapshot restarts the scan where it left off
    (checkpoint.py; requires a backend with get_state/set_state, i.e. the
    TPU backends).

    ``tracer`` (obs.trace.SpanTracer) mirrors every profile stage into a
    Chrome trace; scan metrics/events flow to the default obs registry and
    event bus unconditionally (both are no-ops until a sink/exporter
    attaches), with per-partition lag/ETA gauges refreshed at the
    ``heartbeat_every_s`` cadence.

    ``ingest_workers`` (an int, ``"auto"``, or a config.IngestConfig)
    shards the partition set over that many private fetch→decode→pack
    worker streams feeding the backend through deterministic round-robin
    fan-ins (parallel/ingest.py) — results stay byte-identical to the
    sequential scan (DESIGN.md §11).  On sharded backends the count
    resolves PER CONTROLLER against this process's shard partition count
    and splits across its data rows, composing host-parallel ingest with
    the device-parallel collective scan (DESIGN.md §14); single-device
    backends clamp to the topic's partition count as before, and place
    each stream's rows on the device at their dispatch.  The
    default here stays 1, the sequential scan a library caller gets
    unless it asks: the CLI and ``IngestConfig`` default to ``"auto"``.

    Follow-mode pass hooks (serve/follow.py — the follow service reruns
    this function per poll on the SAME backend, so state accumulates):
    ``initial_seq`` seeds the record sequence so pass N+1's spinner/
    heartbeat/snapshot counts continue pass N's; ``heartbeat`` shares one
    rate limiter across passes (a fresh limiter per pass would fire on
    every poll at the head — event flood); ``emit_lifecycle=False``
    suppresses the per-pass scan_start/scan_end events and the per-pass
    spinner finish line — the service emits ONE lifecycle pair for its
    whole run; ``book_once=False`` suppresses the once-per-scan fallback
    bookings (fused reasons) on every follow pass after the
    first, so the counters record one scan, not one per poll;
    ``final_snapshot`` forces
    a snapshot after the stream drains (at a superbatch boundary, by
    construction) — the follow service's checkpoint-interval and
    clean-shutdown commits.

    ``lease_epoch`` (fleet/lease.py, DESIGN §23): the caller's topic-
    ownership epoch, stamped on every snapshot this pass saves and
    checked against every snapshot it loads — a pass running under a
    lost lease is fenced with `checkpoint.StaleLeaseEpochError` instead
    of clobbering (or resuming over) its successor's checkpoint."""
    ingest_cfg = (
        ingest_workers
        if isinstance(ingest_workers, IngestConfig)
        else IngestConfig(workers=ingest_workers)
    )
    pindex = PartitionIndex(source.partitions())
    start_offsets, end_offsets = source.watermarks()
    if tracer is None:
        # CLI wiring: telemetry_session installs the --trace-json tracer
        # as the process-wide active one instead of threading it here.
        tracer = obs_trace.active()
    profile = ScanProfile(tracer=tracer)
    spinner = spinner or Spinner(enabled=False)
    t0 = time.monotonic()
    seq = initial_seq
    if emit_lifecycle:
        obs_events.emit(
            "scan_start",
            topic=topic,
            partitions=len(pindex),
            batch_size=batch_size,
        )
    if heartbeat is None:
        heartbeat = obs_events.Heartbeat(heartbeat_every_s)
    # Partitions THIS process feeds — the sharded branch narrows this to
    # its local rows' partitions, so that under multi-controller each
    # process's lag/ETA gauges carry a disjoint label set (the merge
    # algebra's gauge-union assumption; a process must not report full
    # lag for partitions it never observes).
    fed_partitions = list(end_offsets)

    def maybe_heartbeat() -> None:
        """Rate-limited telemetry refresh: per-partition lag/ETA gauges
        from the tracker + one heartbeat event.  O(P) work at most once
        per interval — never per batch."""
        if not heartbeat.ready():
            return
        elapsed = time.monotonic() - t0
        # Rate over THIS run only: a --resume restores seq to the
        # snapshot's cumulative count, which elapsed knows nothing about.
        rate = (seq - seq_base) / elapsed if elapsed > 0 else 0.0
        lag_total = 0
        for p in fed_partitions:
            end = end_offsets[p]
            lag = max(0, end - tracker.next_offsets.get(p, start_offsets[p]))
            lag_total += lag
            obs_metrics.PARTITION_LAG.labels(partition=p).set(lag)
            obs_metrics.PARTITION_ETA_SECONDS.labels(partition=p).set(
                lag / rate if rate > 0 else -1.0
            )
        obs_events.emit(
            "heartbeat",
            seq=seq,
            records_per_sec=round(rate, 1),
            lag_total=lag_total,
        )
        # Health evaluation rides the heartbeat boundary so a plain
        # batch scan gets a live /healthz too; the engine rate-limits
        # itself (HealthConfig.eval_interval_s) and only READS registry
        # snapshots — the scan stays byte-identical with it on or off
        # (tests/test_health.py).  Follow/fleet services additionally
        # evaluate at every poll boundary.
        health = obs_health.active()
        if health is not None:
            health.maybe_evaluate()

    # Caller-provided start offsets (e.g. --from-timestamp lookup); a
    # resumed snapshot's offsets take precedence below.
    tracker = _ProgressTracker(start_offsets)
    if start_at:
        tracker.next_offsets.update(start_at)
    can_snapshot = (
        snapshot_dir is not None
        and hasattr(backend, "get_state")
        and getattr(backend, "snapshot_capable", True)
    )
    if (
        snapshot_dir is not None
        and hasattr(backend, "get_state")
        and not getattr(backend, "snapshot_capable", True)
    ):
        import logging

        logging.getLogger(__name__).warning(
            "this backend/mesh cannot snapshot (non-contiguous per-process "
            "data rows); continuing without snapshots"
        )
    # Multi-controller runs snapshot per process (checkpoint._snapshot_path):
    # the backend exposes its scope and process-local state accessors.
    snap_scope = getattr(backend, "snapshot_scope", None)
    snap_get = (
        backend.get_state_local if snap_scope is not None else
        (backend.get_state if hasattr(backend, "get_state") else None)
    )
    snap_set = (
        backend.set_state_local if snap_scope is not None else
        (backend.set_state if hasattr(backend, "set_state") else None)
    )
    if snapshot_dir is not None and not hasattr(backend, "get_state"):
        import logging

        logging.getLogger(__name__).warning(
            "backend %s does not support snapshots; continuing without",
            type(backend).__name__,
        )
    if resume and can_snapshot:
        from kafka_topic_analyzer_tpu.checkpoint import load_snapshot

        snap = load_snapshot(
            snapshot_dir,
            topic,
            backend.config,
            template=snap_get(),
            scope=snap_scope,
            lease_epoch=lease_epoch,
        )
        if snap is not None:
            state, offsets, records_seen, init_now_s = snap
            snap_set(state)
            backend.init_now_s = init_now_s
            tracker.next_offsets.update(offsets)
            start_at = offsets
            seq = records_seen
            if hasattr(source, "seed_corrupt_spans"):
                from kafka_topic_analyzer_tpu.checkpoint import (
                    load_corrupt_spans,
                )

                # Spans a previous run already skipped/quarantined: seed
                # the source so re-walking one (corruption skips leave no
                # records for the offset tracker to advance past) neither
                # re-counts nor double-quarantines it.
                spans = load_corrupt_spans(snapshot_dir, scope=snap_scope)
                if spans:
                    source.seed_corrupt_spans(spans)
            if hasattr(source, "seed_lost_spans"):
                from kafka_topic_analyzer_tpu.checkpoint import (
                    load_lost_spans,
                    load_partition_meta,
                )

                # Loss a previous run already booked: seed the source so
                # the logical scan's final report names it without
                # re-booking (metrics counted it when it happened).
                lspans = load_lost_spans(snapshot_dir, scope=snap_scope)
                if lspans:
                    source.seed_lost_spans(lspans)
                if hasattr(source, "validate_resume"):
                    # Durable fencing: check each saved cursor against
                    # the LIVE log before fetch #1 — a cursor below the
                    # log start is a named retention loss (offsets
                    # re-anchor in place), and an epoch that moved since
                    # the save runs the divergence check.
                    source.validate_resume(
                        offsets,
                        load_partition_meta(snapshot_dir, scope=snap_scope),
                    )
                    tracker.next_offsets.update(offsets)
    seq_base = seq  # resumed records predate t0; rate math excludes them
    last_snap = time.monotonic()

    # Offsets/seq as of the last COMPLETED fold.  The tracker observes a
    # batch during ingest, before backend.update folds it, so on a mid-round
    # failure `tracker.next_offsets` can be ahead of the backend state; the
    # failure-path snapshot must use these instead or a resume would skip
    # the observed-but-never-folded records.
    committed_offsets = dict(tracker.next_offsets)
    committed_seq = seq

    def maybe_snapshot(
        force: bool = False,
        offsets: "Optional[dict[int, int]]" = None,
        records_seen: Optional[int] = None,
    ) -> None:
        nonlocal last_snap
        if not can_snapshot:
            return
        now = time.monotonic()
        if not force and now - last_snap < snapshot_every_s:
            return
        from kafka_topic_analyzer_tpu.checkpoint import save_snapshot

        with profile.stage("snapshot"):
            save_snapshot(
                snapshot_dir,
                topic,
                backend.config,
                snap_get(),
                tracker.next_offsets if offsets is None else offsets,
                seq if records_seen is None else records_seen,
                backend.init_now_s,
                scope=snap_scope,
                degraded=(
                    source.degraded_partitions()
                    if hasattr(source, "degraded_partitions")
                    else None
                ),
                corrupt=(
                    source.corruption_spans()
                    if hasattr(source, "corruption_spans")
                    else None
                ),
                lease_epoch=lease_epoch,
                lost=(
                    source.lost_spans()
                    if hasattr(source, "lost_spans")
                    else None
                ),
                partition_meta=(
                    source.partition_meta()
                    if hasattr(source, "partition_meta")
                    else None
                ),
            )
        obs_metrics.SNAPSHOTS_SAVED.inc()
        obs_events.emit(
            "snapshot_saved",
            records_seen=seq if records_seen is None else records_seen,
        )
        last_snap = time.monotonic()

    # Prefetch iterators run worker threads; close them on ANY exit so an
    # error mid-scan doesn't leak threads or the source's connections.
    open_iters: "list" = []

    def _closing(it):
        open_iters.append(it)
        return it

    from kafka_topic_analyzer_tpu.utils.prefetch import prefetch

    def _dense_copy(b: RecordBatch) -> RecordBatch:
        """Dense-partition view for packing on a prefetch worker.  A COPY
        when ids are non-dense: remap_batch mutates in place, and the main
        loop must keep true partition ids for progress/snapshot keys."""
        if pindex.is_dense:
            return b  # nothing to rewrite; safe to alias
        return dataclasses.replace(b, partition=pindex.to_dense(b.partition))

    # Fused ingest (DESIGN.md §15): when the backend can stage packed rows
    # (make_fused_sink), the source can feed a FusedPackSink
    # (supports_fused_sink), and the native shim is up, each ingest stream
    # gets a PRIVATE sink and yields packing.PackedRow items — wire bytes
    # decoded→packed (and backend-staged) in one GIL-released native pass
    # on the producing thread, no decoded-column intermediate.  Any closed
    # gate falls back to the decoded-batch chain and is booked on
    # kta_fused_fallback_total — a bypass is never silent.
    from kafka_topic_analyzer_tpu.packing import (
        PackedRow,
        fused_ingest_enabled,
    )

    _make_sink = getattr(backend, "make_fused_sink", None)
    # The attribute declares intent; the signature check confirms no
    # wrapper in between (TeeSource, test shims that __getattr__-forward
    # to a fused-capable inner source) dropped the ``sink=`` parameter
    # from its own batches() override.
    import inspect

    try:
        _accepts_sink = "sink" in inspect.signature(source.batches).parameters
    except (TypeError, ValueError):
        _accepts_sink = False
    _declares_fused = getattr(source, "supports_fused_sink", False)
    _fusable_source = _declares_fused and _accepts_sink
    fused = (
        _make_sink is not None
        and _fusable_source
        and getattr(backend, "use_native", True)
        and fused_ingest_enabled()
    )
    if _make_sink is not None and _declares_fused and not fused and book_once:
        # Book every closed gate — a bypass is never silent, including a
        # wrapper that forwards the capability flag but dropped sink=.
        # (book_once: follow runs book on their FIRST pass only.)
        if not _accepts_sink:
            reason = "source-unfusable"
        elif not getattr(backend, "use_native", True):
            reason = "native-off"
        else:
            from kafka_topic_analyzer_tpu.io.native import native_status

            ok, why = native_status()
            reason = "fused-disabled" if ok else f"native-{why}"
        obs_metrics.FUSED_FALLBACK.labels(reason=reason).inc()
    _dense_map = {p: i for i, p in enumerate(pindex.ids)}

    def make_sink():
        """A fresh per-stream sink (sinks are single-threaded state)."""
        return _make_sink(_dense_map.__getitem__)

    # Wire-format accounting.  Only packed backends have a wire; the cpu
    # oracle folds decoded batches directly.
    wire_stats = None
    wire_bytes0 = 0.0
    if _make_sink is not None or hasattr(backend, "update_shards"):
        from kafka_topic_analyzer_tpu.packing import (
            WIRE_FORMAT,
            section_byte_split,
        )
        from kafka_topic_analyzer_tpu.results import WireStats

        wire_cfg = backend.config
        # Sharded backends pack per-chunk buffers; the split is the same
        # layout rule at that granularity (packing._sections).
        wire_b = (
            wire_cfg.chunk_size
            if hasattr(backend, "update_shards")
            else wire_cfg.batch_size
        )
        per_rec, table = section_byte_split(wire_cfg, wire_b)
        wire_stats = WireStats(
            format=WIRE_FORMAT,
            batch_size=wire_b,
            per_record_bytes=per_rec,
            table_bytes=table,
            alive_compaction="on" if wire_cfg.count_alive_keys else "n/a",
        )
        wire_bytes0 = obs_metrics.WIRE_BYTES.value
        pairs_raw0 = obs_metrics.ALIVE_PAIRS_RAW.value
        pairs_emitted0 = obs_metrics.ALIVE_PAIRS_EMITTED.value

    used_workers = 1
    # Superbatch dispatch (config.DispatchConfig, resolved by the backend):
    # accumulate K staged batches and fold them in ONE scanned device
    # dispatch.  Fold-consistency rule: progress commits (and therefore
    # snapshots) happen ONLY at superbatch boundaries — between them the
    # tracker runs ahead of the device state by the pending tail, and a
    # snapshot there would skip those records on resume.  On stop/fault/
    # corruption the pending tail is flushed as a partial superbatch
    # (identity-padded to K by the backend) so PRs 1-3 semantics — every
    # observed batch folded and committed before the failure snapshot —
    # are unchanged.  `fault_flush` is that best-effort hook; it stays
    # None when flushing from a failure path would itself be a collective
    # (multi-controller sharded runs: peers may not reach the flush, and
    # a one-sided collective deadlocks — resume simply re-scans the tail).
    super_k = int(getattr(backend, "superbatch_k", 1) or 1)
    fault_flush = None

    def make_superbatch(dispatch_fn):
        """(add, flush) pair for one drive loop's superbatch accumulation.

        ONE implementation for both the sharded and single-device branches
        so the commit/snapshot semantics can never diverge between them.
        ``add`` records the tracker offsets AT APPEND TIME: the tracker
        observes a batch slightly before it is staged into the pending
        tail, so a fault landing in that window must not let ``flush``
        commit offsets for a batch it never folded — the flush commits the
        last appended batch's snapshot, not the live tracker.
        """
        pend = {"items": [], "valid": 0, "nbytes": 0,
                "offsets": None, "seq": 0}

        def add(item, nvalid: int, nbytes: int) -> None:
            nonlocal seq
            pend["items"].append(item)
            pend["valid"] += nvalid
            pend["nbytes"] += nbytes
            seq += nvalid
            pend["offsets"] = dict(tracker.next_offsets)
            pend["seq"] = seq
            # Staging fill level (0..K) — the flight recorder's stager
            # track: how far the next superbatch has accumulated.
            obs_metrics.SUPERBATCH_FILL.set(len(pend["items"]))
            if len(pend["items"]) == super_k:
                flush()

        def flush() -> None:
            """Dispatch the accumulated (possibly partial) superbatch and
            commit fold progress — the only point the superbatch path
            snapshots.  Under multi-controller the dispatch is collective:
            every process reaches each flush at the same round count (the
            accumulation length is driven by the per-round lockstep
            agreement), and the fault path never calls this there."""
            nonlocal committed_offsets, committed_seq
            if not pend["items"]:
                return
            with profile.stage(
                "dispatch", items=pend["valid"], nbytes=pend["nbytes"],
            ):
                dispatch_fn(pend["items"])
            pend["items"] = []
            pend["valid"] = 0
            pend["nbytes"] = 0
            obs_metrics.SUPERBATCH_FILL.set(0)
            committed_offsets = pend["offsets"]
            committed_seq = pend["seq"]
            maybe_snapshot(
                offsets=committed_offsets, records_seen=committed_seq
            )

        return add, flush

    try:
        if hasattr(backend, "update_shards"):
            # Sharded scan: one batch stream PIPELINE per data shard, each
            # restricted to its own partitions (records.py ordering
            # contract), zipped so every device step carries one full batch
            # per shard.  Under multi-controller (jax.distributed), this
            # process feeds only the data rows it hosts
            # (backend.local_rows) — the turnkey multi-host contract: run
            # the same CLI on every host.
            #
            # Composed parallelism (DESIGN.md §14): each fed row's pipeline
            # is either the classic single staged prefetch stream (1
            # worker — byte-for-byte the pre-composition path) or an
            # N-worker ParallelIngest fan-in over that row's partitions,
            # so host-parallel fetch→decode→pack multiplies with the
            # device-parallel collective fold and the superbatch dispatch
            # layer below.  The round structure — and with it every
            # lockstep collective — is untouched: fan-ins only change
            # where a row's next batch comes from, never when the row
            # participates in a round.
            from kafka_topic_analyzer_tpu.parallel.ingest import (
                ParallelIngest,
                allocate_row_workers,
                shard_partitions,
            )
            from kafka_topic_analyzer_tpu.parallel.mesh import assign_partitions

            d = backend.config.data_shards
            shard_parts = assign_partitions(pindex.ids, d)
            feed_rows = list(getattr(backend, "local_rows", range(d)))
            fed_partitions = [p for r in feed_rows for p in shard_parts[r]]
            # Collective steps must stay in lockstep across processes, so
            # per-round continuation is a global agreement, not a local one.
            lockstep = getattr(backend, "global_any", None)
            multiproc = lockstep is not None and len(feed_rows) < d
            # Stage the S-way chunk packing on each row's ingest worker
            # (same contract as the single-device path below: pack a dense
            # COPY, keep the decoded batch for true-id bookkeeping).
            prepare_shard = getattr(backend, "prepare_shard", None)

            def _stage_row(it):
                for b in it:
                    if isinstance(b, PackedRow):
                        yield b, b.staged  # fused: packed AND staged already
                    elif prepare_shard is None:
                        yield b, None
                    else:
                        yield b, prepare_shard(_dense_copy(b))

            stage_shard = (
                (lambda b: prepare_shard(_dense_copy(b)))
                if prepare_shard is not None
                else None
            )
            # Per-controller resolution: the worker budget comes from THIS
            # process's shard partition count (auto = min(cores-1, local
            # partitions)) and splits deterministically across its rows.
            row_workers = allocate_row_workers(
                ingest_cfg.resolve(max(1, len(fed_partitions))),
                {r: len(shard_parts[r]) for r in feed_rows},
            )
            used_workers = max(1, sum(row_workers.values()))
            # Recorded per process so the gather below can report the
            # RESOLVED per-controller counts, not just a global scalar.
            _note_streams(source, used_workers)
            # Cold sources (segment catalogs) know per-partition record
            # counts: balance each row's worker groups by records
            # (greedy-LPT), exactly like the single-device path below.
            # Only consulted when some row actually runs a fan-in.
            weights = None
            if any(nw > 1 for nw in row_workers.values()):
                weigher = getattr(source, "partition_record_counts", None)
                weights = weigher() if weigher is not None else None
            # Worker telemetry labels must be disjoint across this
            # controller's per-row pools AND across controllers (the
            # gather_telemetry merge unions label sets).
            label_prefix = (
                f"c{backend.controller_index}."
                if multiproc and hasattr(backend, "controller_index")
                else ""
            )
            iters = {}
            wid_base = 0
            for r in feed_rows:
                nw = row_workers.get(r, 0)
                if not shard_parts[r]:
                    iters[r] = iter(())
                elif nw > 1:
                    iters[r] = _closing(
                        ParallelIngest(
                            source,
                            batch_size,
                            shard_partitions(
                                shard_parts[r], nw, weights=weights
                            ),
                            start_at=start_at,
                            stage=stage_shard,
                            depth=max(prefetch_depth, 1),
                            wid_base=wid_base,
                            label_prefix=label_prefix,
                            sink_factory=make_sink if fused else None,
                        )
                    )
                else:
                    iters[r] = _closing(
                        prefetch(
                            _stage_row(
                                source.batches(
                                    batch_size,
                                    partitions=shard_parts[r],
                                    start_at=start_at,
                                    **({"sink": make_sink()} if fused else {}),
                                )
                            ),
                            prefetch_depth,
                        )
                    )
                wid_base += nw
            dispatch_rounds = (
                backend.update_shards_superbatch
                if super_k > 1 and hasattr(backend, "update_shards_superbatch")
                else None
            )
            if dispatch_rounds is None:
                super_k = 1  # report the EFFECTIVE superbatch size
                add_round = flush_rounds = None
            else:
                add_round, flush_rounds = make_superbatch(dispatch_rounds)
                if not multiproc:
                    fault_flush = flush_rounds
            alive = {r: True for r in feed_rows}
            row_records = {
                r: obs_metrics.MESH_ROW_RECORDS.labels(row=r)
                for r in feed_rows
            }
            while True:
                shard_batches: "list" = [None] * d
                step_valid = 0
                step_bytes = 0
                with profile.stage("ingest"):
                    for r in feed_rows:
                        item = next(iters[r], None) if alive[r] else None
                        if item is None:
                            alive[r] = False
                            continue
                        b, staged = item
                        step_valid += b.num_valid
                        step_bytes += b.nbytes
                        row_records[r].inc(b.num_valid)
                        if isinstance(b, PackedRow):
                            tracker.observe_packed(b)
                            shard_batches[r] = staged
                        else:
                            tracker.observe(b, b.partition)
                            shard_batches[r] = (
                                staged if staged is not None
                                else pindex.remap_batch(b)
                            )
                have_data = step_valid > 0
                if multiproc:
                    have_data = lockstep(have_data)
                if not have_data:
                    break
                if add_round is not None:
                    add_round(shard_batches, step_valid, step_bytes)
                else:
                    with profile.stage(
                        "dispatch", items=step_valid, nbytes=step_bytes,
                    ):
                        backend.update_shards(shard_batches)
                    seq += step_valid
                    committed_offsets = dict(tracker.next_offsets)
                    committed_seq = seq
                    maybe_snapshot()
                obs_metrics.SCAN_RECORDS.inc(step_valid)
                obs_metrics.SCAN_BATCHES.inc()
                obs_metrics.SCAN_BYTES.inc(step_bytes)
                obs_metrics.BATCH_RECORDS.observe(step_valid)
                maybe_heartbeat()
                spinner.set_message(f"[Sq: {seq} | T: {topic} | shards: {d}]")
            if flush_rounds is not None:
                # Stream drained on every process (lockstep agreement):
                # flush the partial superbatch tail collectively.
                flush_rounds()
        else:
            # Backends with a `prepare` method (the packed single-device
            # path) stage INSIDE the prefetch worker: remap + pack (native,
            # GIL-released) + the async host→device transfer all overlap
            # the device's current step, so the main thread only does
            # bookkeeping and step dispatch.  The decoded batch travels
            # alongside for progress/snapshot bookkeeping and MUST keep its
            # true partition ids (remap_batch mutates in place; the tracker
            # keys snapshots by true id), so the worker packs a shallow
            # copy carrying the dense ids instead.  Prefetch depth bounds
            # the in-flight device buffers.
            prepare = getattr(backend, "prepare", None)
            stage = (
                (lambda b: prepare(_dense_copy(b)))
                if prepare is not None
                else None
            )
            used_workers = ingest_cfg.resolve(len(pindex))
            _note_streams(source, used_workers)
            if used_workers > 1:
                # Partition-sharded parallel ingest (--ingest-workers): N
                # private fetch→decode→pack streams, merged through a
                # deterministic round-robin fan-in.  Yields the same
                # (batch, staged) items as the prefetch path below, so the
                # bookkeeping loop is shared — and the fold order is a pure
                # function of the inputs, keeping results byte-identical to
                # the sequential scan (DESIGN.md §11).
                from kafka_topic_analyzer_tpu.parallel.ingest import (
                    ParallelIngest,
                    shard_partitions,
                )

                # Workers pack on the host; the backend places each row
                # at its dispatch, under the dispatch depth, so the device
                # holds no more staged rows than with one stream, whatever
                # N is.
                worker_sink = make_sink
                if hasattr(backend, "place"):
                    stage = (
                        lambda b: prepare(_dense_copy(b), place=False)
                    )
                    worker_sink = functools.partial(
                        _make_sink, _dense_map.__getitem__, place=False
                    )
                # Cold sources (segment catalogs) know per-partition record
                # counts up front: balance workers by records, not partition
                # count.  Byte-identity is grouping-independent (DESIGN §11),
                # so the wire scan's round-robin rule and this weighted rule
                # fold to the same result.
                weigher = getattr(source, "partition_record_counts", None)
                batches = _closing(
                    ParallelIngest(
                        source,
                        batch_size,
                        shard_partitions(
                            pindex.ids,
                            used_workers,
                            weights=weigher() if weigher is not None else None,
                        ),
                        start_at=start_at,
                        stage=stage,
                        depth=max(prefetch_depth, 1),
                        sink_factory=worker_sink if fused else None,
                    )
                )
            else:
                from kafka_topic_analyzer_tpu.parallel.ingest import (
                    iter_staged,
                )

                batches = _closing(
                    prefetch(
                        iter_staged(
                            source.batches(
                                batch_size,
                                start_at=start_at,
                                **({"sink": make_sink()} if fused else {}),
                            ),
                            stage,
                        ),
                        prefetch_depth,
                    )
                )
            dispatch_super = (
                backend.update_superbatch
                if super_k > 1 and hasattr(backend, "update_superbatch")
                else None
            )
            if dispatch_super is None:
                super_k = 1  # report the EFFECTIVE superbatch size
                add_batch = flush_pending = None
            else:
                add_batch, flush_pending = make_superbatch(dispatch_super)
                fault_flush = flush_pending
            while True:
                with profile.stage("ingest"):
                    item = next(batches, None)
                if item is None:
                    break
                batch, staged = item
                nvalid = batch.num_valid
                if isinstance(batch, PackedRow):
                    last_partition = batch.last_partition
                    last_offset = (
                        str(batch.last_offset)
                        if batch.last_offset >= 0 else "~"
                    )
                    last_ts = batch.last_ts_s
                    tracker.observe_packed(batch)
                    if staged is None:
                        staged = batch.staged
                    # The dispatch span's batch= links it to the row's
                    # decode-and-pack and stage spans (obs/trace.py).
                    span_args = {"batch": batch.seq}
                else:
                    span_args = {}
                    last = len(batch) - 1
                    last_partition = int(batch.partition[last])  # true id, pre-remap
                    last_offset = (
                        str(int(batch.offsets[last]))
                        if batch.offsets is not None
                        else "~"  # gapless sources don't carry offsets
                    )
                    last_ts = int(batch.ts_s[last])
                    tracker.observe(batch, batch.partition)
                    if staged is None:
                        staged = pindex.remap_batch(batch)
                if add_batch is not None:
                    add_batch(staged, nvalid, batch.nbytes)
                else:
                    # nbytes is always the DECODED batch size (remap doesn't
                    # change it) so the stat stays comparable across backends.
                    with profile.stage(
                        "dispatch", items=nvalid, nbytes=batch.nbytes,
                        **span_args,
                    ):
                        backend.update(staged)
                    seq += nvalid
                    committed_offsets = dict(tracker.next_offsets)
                    committed_seq = seq
                    maybe_snapshot()
                obs_metrics.SCAN_RECORDS.inc(nvalid)
                obs_metrics.SCAN_BATCHES.inc()
                obs_metrics.SCAN_BYTES.inc(batch.nbytes)
                obs_metrics.BATCH_RECORDS.observe(nvalid)
                maybe_heartbeat()
                # indicatif-template message like src/kafka.rs:111-113.
                spinner.set_message(
                    f"[Sq: {seq} | T: {topic} | P: {last_partition} | "
                    f"O: {last_offset} | Ts: {format_utc_seconds(last_ts)}]"
                )
            if flush_pending is not None:
                flush_pending()  # partial superbatch tail at stream end
    except BaseException:
        # Irrecoverable mid-scan failure (or interrupt): flush the pending
        # superbatch tail (so every observed batch is folded — the same
        # invariant the per-batch path holds at failure time), then persist
        # the progress as a final snapshot so a rerun with --resume
        # continues where this one died instead of rescanning from
        # earliest.  Best effort — the original failure is what must
        # surface; an unflushable tail just means resume re-scans it.
        if fault_flush is not None:
            try:
                fault_flush()
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "pending superbatch tail could not be flushed; the "
                    "failure snapshot falls back to the last committed "
                    "superbatch boundary"
                )
        # Retire in-flight superbatch dispatches before snapshotting.
        # Lockstep-safe even on a one-sided stop: drain_dispatch blocks
        # only on collectives every controller already launched at a
        # lockstep-agreed round — it never initiates one (unlike the tail
        # flush above, which is why THAT stays None under multiproc).
        drain = getattr(backend, "drain_dispatch", None)
        if drain is not None:
            try:
                drain()
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "in-flight dispatches could not be drained before the "
                    "failure snapshot"
                )
        try:
            maybe_snapshot(
                force=True,
                offsets=committed_offsets,
                records_seen=committed_seq,
            )
        except Exception:
            import logging

            logging.getLogger(__name__).exception(
                "final failure snapshot could not be written"
            )
        raise
    finally:
        for it in open_iters:
            if hasattr(it, "close"):
                it.close()

    degraded = (
        dict(source.degraded_partitions())
        if hasattr(source, "degraded_partitions")
        else {}
    )
    corrupt = (
        source.corruption_stats()
        if hasattr(source, "corruption_stats")
        else {}
    )
    lost = (
        source.loss_stats()
        if hasattr(source, "loss_stats")
        else {}
    )
    # Multi-controller: each process feeds (and can only degrade or observe
    # corruption on) its own rows, but process 0 renders the report and
    # orchestrators read every process's exit code — so "did the scan hit
    # this issue" must be a global agreement, like the per-round
    # continuation above.  One lockstep call per issue, same order on every
    # process.
    lockstep = getattr(backend, "global_any", None)
    multiproc = lockstep is not None and len(
        list(getattr(backend, "local_rows", range(backend.config.data_shards)))
    ) < backend.config.data_shards

    def issue_elsewhere(local_flag: bool) -> bool:
        """True when another process saw the issue and this one did not
        (the collective still runs when local_flag is True — every process
        must participate in every lockstep call)."""
        return multiproc and lockstep(local_flag) and not local_flag

    if issue_elsewhere(bool(degraded)):
        degraded = {
            -1: "partition(s) degraded on another process (see its log)"
        }
    if issue_elsewhere(bool(corrupt)):
        corrupt = {
            -1: {
                "frames": 0, "records": 0, "bytes": 0, "quarantined": 0,
                "kinds": {}, "spans": [],
                "note": "corrupt frame(s) on another process (see its log)",
            }
        }
    if issue_elsewhere(bool(lost)):
        lost = {
            -1: {
                "records": 0, "ranges": 0, "reasons": {},
                "authoritative": True, "spans": [],
                "note": "data loss on another process (see its log)",
            }
        }
    if degraded or corrupt or lost or final_snapshot:
        # Degraded partitions carry an unscanned tail; corrupt ones carry
        # skipped spans the offset tracker never saw; lost ones carry
        # booked spans a resume must inherit.  Snapshot so a rerun
        # resumes correctly (and, for corruption/loss, re-seeds the span
        # lists).
        # ``final_snapshot`` forces the same commit for a clean drain —
        # the follow service's checkpoint/shutdown boundary.
        maybe_snapshot(force=True)

    with profile.stage("finalize"):
        metrics = backend.finalize()
    metrics.partitions = pindex.ids
    if emit_lifecycle:
        spinner.finish_with_message("done")
    duration_secs = int(time.monotonic() - t0)
    # Final telemetry: drained partitions report zero lag, the stage
    # profile folds into the registry, and the lifecycle closes.  Follow
    # passes skip the force: the service refreshes lag gauges against the
    # MOVING head every poll, and a forced heartbeat per pass would flood
    # the event log at exactly the cadence the limiter exists to bound.
    if emit_lifecycle:
        heartbeat.force()  # the closing gauge refresh always lands
    maybe_heartbeat()
    # Locally-degraded partitions only: the -1 cross-process sentinel is
    # another process's partition, and THAT process books it — counting
    # it here would double it under the gauge's merge="sum" policy.
    local_degraded = sum(1 for p in degraded if p >= 0)
    obs_metrics.DEGRADED_PARTITIONS.set(local_degraded)
    # (Stage seconds/records/bytes are already in the registry: the
    # profile books them live at every stage window exit, so the flight
    # recorder and the gather below see the same totals — no end-of-scan
    # record_profile fold.)
    if emit_lifecycle:
        obs_events.emit(
            "scan_end",
            topic=topic,
            records=seq,
            duration_secs=duration_secs,
            degraded=local_degraded,
            corrupt_frames=sum(
                d.get("frames", 0) for p, d in corrupt.items() if p >= 0
            ),
            lost_records=sum(
                d.get("records", 0) for p, d in lost.items() if p >= 0
            ),
        )
    # Close out the wire accounting before the registry gathers, so the
    # bytes/record gauge lands in every snapshot the merge sees.
    if wire_stats is not None:
        wire_stats.bytes_total = int(
            obs_metrics.WIRE_BYTES.value - wire_bytes0
        )
        wire_stats.records = seq - seq_base
        wire_stats.pairs_raw = int(
            obs_metrics.ALIVE_PAIRS_RAW.value - pairs_raw0
        )
        wire_stats.pairs_emitted = int(
            obs_metrics.ALIVE_PAIRS_EMITTED.value - pairs_emitted0
        )
        obs_metrics.WIRE_BYTES_PER_RECORD.set(
            round(wire_stats.bytes_per_record, 2)
        )
    # Cluster-wide registry view.  gather_telemetry is a lockstep
    # collective, so it runs here — a point every process reaches — never
    # from the report-only branch of the CLI.
    gather = getattr(backend, "gather_telemetry", None)
    snaps = gather() if gather is not None else [default_registry().snapshot()]
    telemetry = merge_snapshots(snaps)
    # Per-controller resolved worker counts, read from the UN-merged
    # per-process snapshots (gather returns them pid-sorted): each process
    # stamped its kta_ingest_resolved_workers gauge before the gather.
    workers_per_controller = []
    for s in snaps:
        m = s.get("kta_ingest_resolved_workers")
        v = m["samples"][0]["value"] if m and m.get("samples") else 0
        workers_per_controller.append(max(1, int(v)))
    return ScanResult(
        metrics=metrics,
        duration_secs=duration_secs,
        profile=profile,
        start_offsets=start_offsets,
        end_offsets=end_offsets,
        degraded_partitions=degraded,
        corrupt_partitions=corrupt,
        lost_partitions=lost,
        telemetry=telemetry,
        ingest_workers=used_workers,
        ingest_workers_per_controller=workers_per_controller,
        superbatch_k=super_k,
        dispatch_depth=int(getattr(backend, "dispatch_depth", 1) or 1),
        wire=wire_stats,
        next_offsets=dict(tracker.next_offsets),
    )
