"""CLI — the reference's flag surface plus TPU-build extensions.

Reference-compatible flags (src/main.rs:32-67): ``-t/--topic`` (required),
``-b/--bootstrap-server`` (comma separated), ``--librdkafka`` (comma-separated
``k=v`` passthrough into the consumer config), ``-c/--count-alive-keys``.
Extensions: ``--backend {cpu,tpu}`` (default cpu per BASELINE.json),
``--source``, sketch/batch/mesh knobs.  Exit code -2 on an empty topic
(src/main.rs:98-101).
"""

from __future__ import annotations

import argparse
import contextlib
import sys
from typing import Dict, Optional

from kafka_topic_analyzer_tpu.config import AnalyzerConfig


#: Exit code when the scan finished but one or more partitions were dropped
#: after exhausting their transport retry budget: the report renders (with
#: the degraded block) yet the numbers undercount, so automation must see a
#: failure.  Distinct from 1 (hard error) and -2 (empty topic).
EXIT_DEGRADED = 3

#: Exit code when the scan COMPLETED but skipped (or quarantined) one or
#: more deterministically corrupt frames (--on-corruption=skip/quarantine):
#: the metrics exclude exactly those frames' records, which automation must
#: distinguish from both a clean run (0) and a degraded one (3 — an
#: unbounded undercount; degradation therefore takes precedence when both
#: occur).
EXIT_CORRUPT = 4

#: Exit code when a scan ABORTED because the log mutated out from under it
#: (retention race, truncation after an unclean election, resume below
#: log-start) under ``--on-data-loss=fail``: the loss is fully booked and a
#: fold-consistent checkpoint is written before the abort, so a --resume
#: continues past the named gap.  Under the default ``report`` policy the
#: scan finishes with exit 0 and the DATA-LOSS block names the loss;
#: ``ignore`` finishes with exit 0 and no block (metrics/JSON still carry
#: it) — loss is always accounted, the policy only picks the reaction.
EXIT_DATA_LOSS = 5


def _scan_issue_exit(result, doc=None, render=False,
                     data_loss_policy: str = "report") -> int:
    """Shared tail of every report path: surface corrupt, degraded, and
    lost partitions — into ``doc`` as str-keyed maps (``--json``; the one
    block builder report.attach_issue_blocks) and/or as the post-table
    warning blocks (``render``) — and pick the exit code."""
    rc = 0
    corrupt = getattr(result, "corrupt_partitions", None) or {}
    if doc is not None:
        from kafka_topic_analyzer_tpu.report import attach_issue_blocks

        attach_issue_blocks(doc, result)
    lost = getattr(result, "lost_partitions", None) or {}
    if lost and render and data_loss_policy != "ignore":
        from kafka_topic_analyzer_tpu.report import render_data_loss_block

        sys.stdout.write(render_data_loss_block(lost))
    if corrupt:
        if render:
            from kafka_topic_analyzer_tpu.report import render_corrupt_block

            sys.stdout.write(render_corrupt_block(corrupt))
        rc = EXIT_CORRUPT
    if result.degraded_partitions:
        if render:
            from kafka_topic_analyzer_tpu.report import render_degraded_block

            sys.stdout.write(
                render_degraded_block(result.degraded_partitions)
            )
        rc = EXIT_DEGRADED
    return rc


class UserInputError(ValueError):
    """A bad flag/spec value (setup phase) — reported as one clean line.
    Internal ValueErrors deliberately do NOT inherit this, so they keep
    their tracebacks."""


@contextlib.contextmanager
def user_input_phase():
    """Re-brand setup-phase ValueErrors as user input errors."""
    try:
        yield
    except UserInputError:
        raise
    except ValueError as e:
        raise UserInputError(e) from e


def parse_kv_pairs(text: Optional[str]) -> Dict[str, str]:
    """Parse ``"a=b,c=d"`` exactly like src/main.rs:84-92."""
    if not text:
        return {}
    out: Dict[str, str] = {}
    for pair in text.split(","):
        k, _, v = pair.partition("=")
        out[k] = v
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="kafka-topic-analyzer",
        description="An analyzer for getting metrics about the contents of an "
        "Apache Kafka topic (TPU-native rebuild)",
    )
    # --- reference-compatible surface (src/main.rs:32-67) -------------------
    from kafka_topic_analyzer_tpu import __version__

    # (The reference's -V banner self-reports a stale 0.4.1 — a quirk
    # SURVEY.md §0 says not to replicate.)
    p.add_argument("-V", "--version", action="version",
                   version=f"kafka-topic-analyzer-tpu {__version__}")
    p.add_argument("-t", "--topic", required=True, metavar="TOPIC",
                   help="The topic to analyze")
    p.add_argument("-b", "--bootstrap-server", metavar="BOOTSTRAP_SERVER",
                   help="Bootstrap server(s) to work with, comma separated")
    p.add_argument("--librdkafka", metavar="LIBRDKAFKA",
                   help="Options to pass into the underlying consumer, comma "
                        "separated key=value pairs")
    p.add_argument("-c", "--count-alive-keys", action="store_true",
                   help="Counts the effective number of alive keys in a log "
                        "compacted topic. A key is 'alive' when it is present "
                        "and has a non-null value in its latest-offset version")
    # --- TPU-build extensions ----------------------------------------------
    p.add_argument("--backend", choices=["cpu", "tpu"], default="cpu",
                   help="Metric backend: numpy exact oracle (cpu) or JAX "
                        "streaming reducers (tpu). Default: cpu")
    p.add_argument("--source", choices=["kafka", "synthetic", "segfile"],
                   default="kafka",
                   help="Record source. 'kafka' reads the real topic via the "
                        "wire protocol; 'synthetic'/'segfile' are cluster-free")
    p.add_argument("--synthetic", metavar="SPEC",
                   help="Synthetic workload spec, comma separated k=v: "
                        "partitions,messages,keys,key_null,tombstones,vmin,"
                        "vmax,seed")
    p.add_argument("--segment-dir", metavar="DIR|URL",
                   help="Segment store of .ktaseg dumps (--source segfile): "
                        "a local directory (or file://DIR), or a remote "
                        "object store — http(s)://host[:port]/bucket"
                        "[/prefix] for any S3-compatible endpoint "
                        "(path-style), s3://bucket[/prefix] through "
                        "KTA_S3_ENDPOINT. Composes with --ingest-workers "
                        "(partitions shard across parallel decode+pack "
                        "workers, balanced by the catalog's record "
                        "counts), --superbatch, --segment-readahead and "
                        "--segment-cache")
    p.add_argument("--segment-readahead", default="auto", metavar="N|auto",
                   help="Remote chunks kept in flight ahead of each ingest "
                        "stream (the per-stream window over the shared "
                        "fetch scheduler), so per-GET wire latency "
                        "overlaps the running decode→pack pass instead "
                        "of serializing with it. 'auto' = 4 for remote "
                        "stores, 0 (demand-only) for local directories. "
                        "Results are byte-identical at any depth. "
                        "Default: auto")
    p.add_argument("--fetch-concurrency", default="auto", metavar="N|auto",
                   help="Worker count of the ONE process-wide fetch "
                        "scheduler every remote segment byte is admitted "
                        "through (catalog header probes, demand fetches, "
                        "read-ahead) — sized once per process, not per "
                        "stream, so connection count stays fixed while "
                        "--ingest-workers scales. Demand requests outrank "
                        "speculative read-ahead; streams share the pool "
                        "fairly. 'auto' sizes from the host and grows "
                        "with the resolved stream count. Default: auto")
    p.add_argument("--segment-cache", metavar="DIR",
                   help="Local chunk cache for remote segment stores: "
                        "fetched chunks land here (atomic rename-in, "
                        "sha256 sidecar) and repeated audits of the same "
                        "archive run at local-disk speed. Entries are "
                        "sha256-verified at first touch each process "
                        "(then latched as trusted and served as "
                        "zero-copy mmap views) — a flipped byte is "
                        "detected, booked and re-fetched, never served")
    p.add_argument("--segment-cache-bytes", type=int, default=1 << 30,
                   metavar="BYTES",
                   help="Size bound of --segment-cache: inserts evict "
                        "least-recently-used entries past it. "
                        "Default: 1 GiB")
    p.add_argument("--batch-size", type=int, default=1 << 18,
                   help="Records per device step")
    p.add_argument("--alive-bitmap-bits", type=int, default=32,
                   help="log2 of alive-key bitmap slots (32 = reference-exact)")
    p.add_argument("--distinct-keys", action="store_true",
                   help="Also estimate distinct keys with a HyperLogLog sketch")
    p.add_argument("--distinct-keys-per-partition", action="store_true",
                   help="Track one HLL register file per partition "
                        "(implies --distinct-keys)")
    p.add_argument("--quantiles", action="store_true",
                   help="Also compute message-size quantiles (DDSketch)")
    p.add_argument("--quantiles-per-partition", action="store_true",
                   help="Track one size-quantile sketch per partition "
                        "(implies --quantiles)")
    p.add_argument("--mesh", metavar="DATA[,SPACE]", default="1",
                   help="Device mesh shape: data shards[, space shards]")
    p.add_argument("--ingest-workers", default="1", metavar="N|auto",
                   help="Parallel partition-sharded ingest for one scan: "
                        "shard the partition set over N private "
                        "fetch+decode+pack worker threads feeding the "
                        "backend through deterministic fan-ins — results "
                        "stay byte-identical to the sequential scan. "
                        "'auto' sizes from the host (min(cores-1, "
                        "partitions)). Composes with --mesh: each "
                        "controller resolves the count against ITS data "
                        "shard's partitions and fans in per data row "
                        "(host x device x dispatch parallelism in one "
                        "scan). Default: 1")
    p.add_argument("--superbatch", default="1", metavar="K|auto",
                   help="Superbatch dispatch: stack K packed batches into "
                        "one uint8[K, N] host array and fold them in a "
                        "single jitted lax.scan dispatch (state donated "
                        "once per superbatch, one large host->device "
                        "transfer) — K x fewer dispatches with "
                        "byte-identical results. 'auto' targets 2^20 "
                        "records per dispatch (min 1, max 16), capped at "
                        "2^18 records per fold so a long synchronous fold "
                        "cannot starve ingest overlap (DESIGN.md §12); an "
                        "explicit K is never capped. Default: 1. "
                        "Requires --backend tpu")
    p.add_argument("--dispatch-depth", type=int, default=2, metavar="D",
                   help="Superbatches allowed in flight (staged/"
                        "transferring) while the device folds; the drive "
                        "loop blocks — backpressuring ingest — beyond it. "
                        "2 overlaps the next transfer with the current "
                        "fold. Default: 2")
    p.add_argument("--pallas", action="store_true",
                   help="Use the Pallas MXU counter kernel for the "
                        "per-partition counters (tpu backend; requires "
                        "batch-size %% 1024 == 0)")
    p.add_argument("--distributed", metavar="COORD:PORT,PID,NPROCS",
                   help="Multi-host mode: initialize jax.distributed with the "
                        "given coordinator address, process id and process "
                        "count before building the mesh (collectives then "
                        "span hosts over DCN)")
    p.add_argument("--wire-format", choices=["auto", "v4", "v5"],
                   default="auto", metavar="auto|v4|v5",
                   help="Packed host→device wire format: v5 (combiner rows "
                        "— host pre-reduced per-partition fold tables, the "
                        "default) or v4 (per-record columns). 'auto' "
                        "resolves to v5 unless KTA_WIRE_V4 is set. Results "
                        "are byte-identical either way; snapshots resume "
                        "across formats")
    p.add_argument("--alive-compaction", choices=["auto", "off"],
                   default="auto", metavar="auto|off",
                   help="Host-side LWW compaction of the alive-key pairs "
                        "into one bounded per-dispatch table (wire v5 "
                        "only; DESIGN §19). 'auto' (default) compacts "
                        "whenever -c runs under v5; 'off' keeps the "
                        "per-row pair sections. Results are byte-identical "
                        "either way; KTA_DISABLE_COMPACTION is the env "
                        "kill switch, and a bypass is booked on "
                        "kta_alive_compaction_off_total")
    p.add_argument("--native", choices=["auto", "on", "off"], default="auto",
                   help="Use the native C++ ingest shim when available")
    p.add_argument("--profile-dir", metavar="DIR",
                   help="Write a JAX profiler trace of the scan")
    p.add_argument("--snapshot-dir", metavar="DIR",
                   help="Periodically save resumable scan snapshots here")
    p.add_argument("--snapshot-every", type=float, default=60.0,
                   metavar="SECONDS", help="Snapshot interval (default 60s)")
    p.add_argument("--resume", action="store_true",
                   help="Resume from a snapshot in --snapshot-dir if present")
    p.add_argument("--from-timestamp", metavar="ISO8601|EPOCH_MS",
                   help="Scan only records at or after this time (kafka "
                        "source: broker-side ListOffsets timestamp lookup). "
                        "Accepts epoch milliseconds or ISO-8601, e.g. "
                        "2026-01-01T00:00:00")
    p.add_argument("--dump-segments", metavar="DIR",
                   help="While scanning, dump record metadata into .ktaseg "
                        "chunks so the topic can be re-analyzed from disk "
                        "(not combined with --resume)")
    p.add_argument("--json", action="store_true",
                   help="Emit the report as JSON on stdout instead of the "
                        "terminal tables")
    p.add_argument("--extremes-table", action="store_true",
                   help="Also print a per-partition first/last-timestamp and "
                        "min/max-size table (new capability)")
    p.add_argument("--stats", action="store_true",
                   help="Print per-stage throughput stats and the telemetry "
                        "counter digest to stderr")
    p.add_argument("--metrics-port", type=int, metavar="PORT",
                   help="Serve Prometheus metrics on "
                        "http://127.0.0.1:PORT/metrics while the scan runs "
                        "(0 binds an ephemeral port)")
    p.add_argument("--sse", action="store_true",
                   help="Push a Server-Sent-Events stream of report "
                        "publishes at /events on --metrics-port: each "
                        "frame carries the new snapshot's seq and a "
                        "compact delta summary, so dashboards re-fetch "
                        "/report.json only when it actually changed "
                        "(requires --metrics-port)")
    p.add_argument("--no-serve-gzip", action="store_true",
                   help="Disable publish-time gzip of /report.json "
                        "bodies (the default compresses once per "
                        "publish and serves the cached encoding to "
                        "Accept-Encoding: gzip readers)")
    p.add_argument("--events-jsonl", metavar="FILE",
                   help="Append structured scan lifecycle + transport-fault "
                        "events to FILE as JSON lines")
    p.add_argument("--trace-json", metavar="FILE",
                   help="Write a Chrome trace-event JSON of host-side scan "
                        "spans (fetch/decode/stages) to FILE; combine with "
                        "--profile-dir for the XLA timeline")
    p.add_argument("--flight-record", action="store_true",
                   help="Run the pipeline flight recorder: a low-overhead "
                        "sampler records per-stage occupancy time series "
                        "(ingest/dispatch/snapshot occupancy, worker "
                        "stalls, queue depths, throttle waits) while the "
                        "scan runs. Adds windowed verdicts to the --stats "
                        "BOTTLENECK digest, counter tracks to --trace-json, "
                        "and serves the ring-buffered series at /flight on "
                        "--metrics-port. The bottleneck verdict itself is "
                        "always computed — the recorder adds the timeline")
    p.add_argument("--history-bytes", type=int, default=0, metavar="BYTES",
                   help="Persist the flight recorder's telemetry series "
                        "to a crash-safe, multi-resolution on-disk store "
                        "bounded by BYTES (RRD-style: recent history at "
                        "full resolution, older history progressively "
                        "halved), living next to the checkpoints "
                        "(requires --snapshot-dir) so a restarted "
                        "service resumes its series. Serves windowed "
                        "queries at /history on --metrics-port, feeds "
                        "the trend doctor's TRENDS digest on --stats, "
                        "and implies --flight-record. 0 disables "
                        "(default)")
    p.add_argument("--fleet", action="store_true",
                   help="Cluster-wide topic discovery + scan: ask the "
                        "cluster for ALL topics (one all-topics Metadata "
                        "request), filter them (-t becomes a comma-"
                        "separated include-glob list, default '*'; "
                        "--fleet-exclude subtracts; internal "
                        "__consumer_offsets-style topics are excluded "
                        "unless --fleet-internal), then scan every match "
                        "— up to --fleet-concurrency topics at once, "
                        "sharing the global --ingest-workers and "
                        "--dispatch-depth budgets across the concurrent "
                        "scans.  Per-topic results are byte-identical to "
                        "solo scans; one topic's failure never kills the "
                        "fleet (it becomes a status row).  Composes with "
                        "--follow (the whole cluster tailed as one "
                        "service), --json (cluster rollup + per-topic "
                        "documents), --snapshot-dir (one subdirectory "
                        "per topic) and /report.json?topic= on "
                        "--metrics-port")
    p.add_argument("--fleet-exclude", metavar="GLOBS",
                   help="Comma-separated topic-name globs to exclude "
                        "from --fleet discovery (applied after the -t "
                        "include globs)")
    p.add_argument("--fleet-internal", action="store_true",
                   help="Include broker-internal topics "
                        "(__consumer_offsets-style; metadata-flagged or "
                        "__-prefixed) in --fleet discovery")
    p.add_argument("--fleet-concurrency", default="auto", metavar="N|auto",
                   help="Per-topic scans admitted concurrently under "
                        "--fleet ('auto' sizes from the worker budget "
                        "and topic count). The admission scheduler "
                        "defers the rest until budget returns. "
                        "Default: auto")
    p.add_argument("--instance-id", metavar="ID", default=None,
                   nargs="?", const="auto",
                   help="This analyzer's identity in a multi-instance "
                        "fleet (DESIGN §23): turns on per-topic ownership "
                        "leases so N analyzers pointed at one cluster "
                        "split the topics instead of double-scanning "
                        "them, with crash failover by lease expiry. "
                        "Stamped on lease records, kta_lease_*/kta_fleet_* "
                        "metrics, and published report documents. "
                        "Requires --fleet and a lease store "
                        "(--snapshot-dir for file leases, or a remote "
                        "--segment-dir spec with --lease-store object). "
                        "Bare --instance-id derives HOSTNAME-PID. "
                        "Omit the flag to run solo (the default)")
    p.add_argument("--lease-ttl", type=float, default=30.0,
                   metavar="SECONDS",
                   help="Topic-lease lifetime under --instance-id: the "
                        "failover bound (a crashed instance's topics are "
                        "reacquirable this long after its last renewal). "
                        "Renewals ride every poll boundary. Default: 30")
    p.add_argument("--lease-store", default="auto",
                   metavar="auto|file|object",
                   help="Where lease records live: 'file' = atomic-rename "
                        "JSON records under SNAPSHOT_DIR/_kta_leases/, "
                        "'object' = ETag-fenced conditional writes to the "
                        "--segment-store bucket, 'auto' (default) picks "
                        "'object' when the segment store is remote, else "
                        "'file'")
    p.add_argument("--follow", action="store_true",
                   help="Run as a long-lived analyzer service: after the "
                        "initial earliest→latest pass, keep re-polling "
                        "watermarks and fold new records incrementally "
                        "(superbatch/parallel-ingest/mesh composition "
                        "unchanged), serving the evolving report at "
                        "/report.json on --metrics-port. SIGINT/SIGTERM "
                        "stop at the next poll boundary: final "
                        "checkpoint, final report, clean exit. Resumes "
                        "from any --snapshot-dir snapshot, including one "
                        "a batch scan wrote")
    p.add_argument("--poll-interval", type=float, default=1.0,
                   metavar="SECONDS",
                   help="Follow-mode watermark poll cadence; consecutive "
                        "empty polls back off exponentially from here to "
                        "10s. Default: 1.0")
    p.add_argument("--checkpoint-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="Follow-mode checkpoint cadence (committed only "
                        "at superbatch boundaries; requires "
                        "--snapshot-dir). Defaults to --snapshot-every")
    p.add_argument("--follow-idle-exit", type=float, default=None,
                   metavar="SECONDS",
                   help="Exit the follow service cleanly after this long "
                        "at the head with no new records (drain mode); "
                        "default: follow forever")
    p.add_argument("--window-secs", type=float, default=60.0,
                   metavar="SECONDS",
                   help="Width of one follow-mode report window (the "
                        "time-windowed per-partition rate/cardinality/"
                        "size folds served in /report.json). Default: 60")
    p.add_argument("--window-count", type=int, default=8, metavar="N",
                   help="Window states kept in the follow-mode ring "
                        "(merged associatively for the whole-ring view); "
                        "0 disables windowed folds. Default: 8")
    p.add_argument("--check-crcs", action="store_true",
                   help="Verify record-batch checksums (CRC32-C) while "
                        "decoding, like librdkafka's check.crcs. Without it, "
                        "corruption detection only catches structural "
                        "damage; payload bit rot decodes as garbage values")
    p.add_argument("--on-corruption", choices=["fail", "skip", "quarantine"],
                   default="fail", metavar="POLICY",
                   help="What to do with a deterministically corrupt record "
                        "frame (one that fails decode identically on a "
                        "re-fetch): 'fail' aborts the scan (default), 'skip' "
                        "skips exactly that frame and finishes with exit "
                        f"code {EXIT_CORRUPT}, 'quarantine' additionally "
                        "spools the raw frame + JSON sidecar to "
                        "--quarantine-dir")
    p.add_argument("--quarantine-dir", metavar="DIR",
                   help="Directory for quarantined corrupt frames "
                        "(requires --on-corruption=quarantine)")
    p.add_argument("--on-data-loss", choices=["fail", "report", "ignore"],
                   default="report", metavar="POLICY",
                   help="What to do when the log mutates out from under "
                        "the scan (retention races past the cursor, "
                        "truncation after an unclean leader election, "
                        "resume below the live log start): 'fail' aborts "
                        "with a fold-consistent checkpoint and exit code "
                        f"{EXIT_DATA_LOSS}, 'report' (default) finishes "
                        "with exit 0 and a DATA-LOSS block naming every "
                        "lost range, 'ignore' finishes with exit 0 and no "
                        "block. The loss is ALWAYS booked to metrics and "
                        "the --json data_loss map regardless of policy")
    p.add_argument("--quiet", action="store_true", help="No progress spinner")
    return p


def parse_timestamp_ms(text: str) -> int:
    """Epoch milliseconds, or ISO-8601 (naive strings are taken as UTC).
    Negative values are rejected — they collide with Kafka's ListOffsets
    sentinels (-1 latest, -2 earliest) and would silently change scan
    semantics."""
    ms: "int | None" = None
    try:
        ms = int(text)
    except ValueError:
        import datetime

        try:
            dt = datetime.datetime.fromisoformat(text)
        except ValueError as e:
            raise ValueError(
                f"bad --from-timestamp {text!r}: expected epoch ms or ISO-8601"
            ) from e
        if dt.tzinfo is None:
            dt = dt.replace(tzinfo=datetime.timezone.utc)
        ms = int(dt.timestamp() * 1000)
    if ms < 0:
        raise ValueError(
            f"bad --from-timestamp {text!r}: must not be before the epoch"
        )
    return ms


def parse_mesh(text: str) -> "tuple[int, int]":
    try:
        parts = [int(x) for x in text.split(",") if x]
    except ValueError:
        raise ValueError(
            f"bad --mesh {text!r}: expected DATA or DATA,SPACE device "
            "counts (integers, e.g. '4' or '4,2')"
        ) from None
    if len(parts) not in (1, 2):
        raise ValueError(
            f"bad --mesh {text!r}: expected 1 or 2 comma-separated device "
            f"counts, got {len(parts)}"
        )
    if any(p < 1 for p in parts):
        raise ValueError(
            f"bad --mesh {text!r}: device counts must be positive"
        )
    return (parts[0], parts[1] if len(parts) == 2 else 1)


def make_source(args, topic: "str | None" = None, seed_salt: int = 0) -> "object":
    topic = topic if topic is not None else args.topic
    if args.source != "kafka" and (
        getattr(args, "on_corruption", "fail") != "fail"
        or getattr(args, "quarantine_dir", None)
    ):
        raise ValueError(
            "--on-corruption/--quarantine-dir require --source kafka "
            "(only the wire scan can classify and re-fetch frames)"
        )
    if args.source != "segfile" and getattr(args, "segment_cache", None):
        raise ValueError(
            "--segment-cache requires --source segfile (it caches chunks "
            "fetched from a remote segment store)"
        )
    if args.source == "synthetic":
        from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

        spec = SyntheticSpec.from_kv(parse_kv_pairs(args.synthetic), seed_salt)
        use_native = args.native in ("auto", "on")
        if use_native:
            try:
                from kafka_topic_analyzer_tpu.io.native import NativeSyntheticSource

                return NativeSyntheticSource(spec)
            except Exception:
                if args.native == "on":
                    raise
        return SyntheticSource(spec)
    if args.source == "segfile":
        if not args.segment_dir:
            raise SystemExit(
                "--source segfile requires --segment-dir (a local "
                "directory of .ktaseg dumps, or a remote store spec like "
                "http(s)://host:port/bucket or s3://bucket/prefix)"
            )
        import dataclasses

        from kafka_topic_analyzer_tpu.config import (
            SegmentFetchConfig,
            TransportRetryConfig,
        )
        from kafka_topic_analyzer_tpu.io.segfile import SegmentFileSource

        fetch = SegmentFetchConfig.parse(
            readahead=getattr(args, "segment_readahead", "auto"),
            cache_dir=getattr(args, "segment_cache", None),
            cache_max_bytes=getattr(args, "segment_cache_bytes", 1 << 30),
            fetch_concurrency=getattr(args, "fetch_concurrency", "auto"),
        )
        # The remote tier runs the SAME retry substrate as the wire scan,
        # so the same --librdkafka knobs tune it (retry.backoff.ms,
        # reconnect.backoff.max.ms, transport.retry.budget).
        retry_overrides = parse_kv_pairs(args.librdkafka)
        if retry_overrides:
            fetch = dataclasses.replace(
                fetch,
                retry=TransportRetryConfig.from_overrides(retry_overrides),
            )
        return SegmentFileSource(args.segment_dir, topic=topic, fetch=fetch)
    # kafka
    if not args.bootstrap_server:
        raise SystemExit("--source kafka requires -b/--bootstrap-server")
    from kafka_topic_analyzer_tpu.config import (
        CorruptionConfig,
        DataLossConfig,
    )
    from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource

    overrides = parse_kv_pairs(args.librdkafka)
    if getattr(args, "check_crcs", False):
        # First-class flag for the knob that upgrades corruption detection
        # from structural damage to full payload checksums; the explicit
        # flag wins over a --librdkafka check.crcs override.
        overrides["check.crcs"] = "true"
    corruption = None
    if (
        getattr(args, "on_corruption", "fail") != "fail"
        or getattr(args, "quarantine_dir", None)
    ):
        corruption = CorruptionConfig(
            policy=getattr(args, "on_corruption", "fail"),
            quarantine_dir=getattr(args, "quarantine_dir", None),
        )
    data_loss = None
    if getattr(args, "on_data_loss", "report") != "report":
        data_loss = DataLossConfig(policy=args.on_data_loss)
    return KafkaWireSource(
        bootstrap_servers=args.bootstrap_server,
        topic=topic,
        overrides=overrides,
        use_native_hashing=args.native != "off",
        # None lets an --librdkafka on.corruption/quarantine.dir (or
        # on.data.loss) override apply; explicit flags win.
        corruption=corruption,
        data_loss=data_loss,
    )


def wrap_with_dump(args, topic: str, source):
    """Attach a segment-dump tee to a source when --dump-segments is set
    (shared by the single- and multi-topic paths)."""
    if not args.dump_segments:
        return source
    if args.resume:
        raise UserInputError(
            "--dump-segments cannot be combined with --resume (the dump "
            "would miss already-scanned records); drop --resume — or "
            "delete the snapshot — so the dump scan covers the topic "
            "from its earliest offset"
        )
    from kafka_topic_analyzer_tpu.io.segfile import SegmentDumpWriter, TeeSource

    return TeeSource(source, SegmentDumpWriter(args.dump_segments, topic))



def resolve_ingest_workers(args, mesh_shape, num_partitions):
    """Parse --ingest-workers (shared by the single- and multi-topic
    paths).  For the single-device scan, returns the concrete worker
    count after 'auto'/partition-count resolution.  For a sharded mesh,
    returns the parsed IngestConfig unresolved: the engine resolves it
    PER CONTROLLER — auto = min(cores-1, that controller's shard
    partition count), explicit N clamped the same way — because under
    multi-controller neither the global partition count nor this
    process's core count describes the other hosts (DESIGN.md §14)."""
    from kafka_topic_analyzer_tpu.config import IngestConfig

    cfg = IngestConfig.parse(args.ingest_workers)
    if mesh_shape != (1, 1):
        return cfg
    return cfg.resolve(num_partitions)


def resolve_dispatch(args):
    """Parse + validate --superbatch/--dispatch-depth against the backend
    (shared by the single- and multi-topic paths).  Returns the
    DispatchConfig for the device backends, or None for the cpu oracle —
    which has no device dispatch to amortize, so an EXPLICIT K>1 request
    there is a contradiction (reject rather than silently underdeliver;
    'auto' means "size appropriately" and resolves to no superbatching)."""
    from kafka_topic_analyzer_tpu.config import DispatchConfig

    cfg = DispatchConfig.parse(args.superbatch, args.dispatch_depth)
    if args.backend != "tpu":
        if cfg.superbatch != "auto" and int(cfg.superbatch) > 1:
            raise ValueError(
                "--superbatch requires --backend tpu (the cpu oracle has "
                "no device dispatch to amortize)"
            )
        return None
    return cfg


def resolve_wire_format(args) -> int:
    """--wire-format → AnalyzerConfig.wire_format (shared by the single-
    and multi-topic paths): 'auto' = 0 (config resolves to v5 unless the
    KTA_WIRE_V4 kill switch is set), 'v4'/'v5' pin the format.  Results
    are byte-identical either way (DESIGN.md §16) and the format is
    outside the checkpoint fingerprint, so snapshots resume across it."""
    return {"auto": 0, "v4": 4, "v5": 5}[getattr(args, "wire_format", "auto")]


def _diagnose(result):
    """Scan-doctor attribution for a finished scan: computed from the
    SAME merged snapshot ``--json`` embeds (fleet-wide under
    multi-controller), plus the flight recorder's series when one ran.
    Shared with the follow service's /report.json publisher
    (obs/doctor.diagnose_scan) so every surface attributes identically."""
    from kafka_topic_analyzer_tpu.obs.doctor import diagnose_scan

    return diagnose_scan(result)


def _print_stats(args, result, diagnosis=None) -> None:
    """--stats stderr dump: per-stage digest + telemetry counters + the
    doctor's BOTTLENECK attribution (cluster-wide under multi-controller).
    Stage timings render ONCE, from the registry snapshot — the same
    source the doctor attributes from — not from the in-process profile
    (which under multi-controller only knew this process's stages)."""
    if not args.stats:
        return
    from kafka_topic_analyzer_tpu.report import (
        render_bottleneck,
        render_stage_stats,
        render_telemetry_stats,
    )

    sys.stderr.write(render_stage_stats(result.telemetry))
    sys.stderr.write(
        render_telemetry_stats(
            result.telemetry,
            ingest_workers=result.ingest_workers,
            ingest_workers_per_controller=(
                result.ingest_workers_per_controller
            ),
            superbatch_k=result.superbatch_k,
            dispatch_depth=result.dispatch_depth,
            wire=result.wire,
        )
    )
    sys.stderr.write(
        render_bottleneck(
            diagnosis if diagnosis is not None else _diagnose(result)
        )
    )
    _print_health_stats()


def _print_health_stats() -> None:
    """--stats HEALTH + TRENDS digests (shared by the solo and fleet
    stats paths): the alert engine's latest document, and the trend
    doctor's findings over the history window when --history-bytes ran."""
    from kafka_topic_analyzer_tpu.obs import health as obs_health
    from kafka_topic_analyzer_tpu.obs import history as obs_history
    from kafka_topic_analyzer_tpu.report import render_health, render_trends

    engine = obs_health.active()
    if engine is not None:
        if engine.doc() is None:
            # Sub-interval scans never hit a heartbeat boundary; the
            # digest must still report from one real evaluation.
            engine.evaluate()
        sys.stderr.write(render_health(engine.doc()))
    store = obs_history.active()
    if store is not None:
        from kafka_topic_analyzer_tpu.obs.doctor import diagnose_trends

        sys.stderr.write(render_trends(diagnose_trends(store.window())))


def _not_report_process(args) -> bool:
    """Multi-host runs produce ONE report: every process scans and takes
    part in the collective finalize, but only process 0 renders output."""
    if not args.distributed:
        return False
    import jax

    return jax.process_index() != 0


def _make_cli_backend(args, config: AnalyzerConfig, mesh_shape, dispatch=None):
    """cpu oracle, single-device tpu, or sharded mesh backend per flags."""
    if args.backend == "tpu" and mesh_shape != (1, 1):
        from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

        return ShardedTpuBackend(config, dispatch=dispatch)
    from kafka_topic_analyzer_tpu.backends.base import make_backend

    return make_backend(args.backend, config, dispatch=dispatch)


def parse_from_timestamp_flag(args) -> "int | None":
    """Validate --from-timestamp's flag combination and parse it to ms
    (shared by the single- and multi-topic paths)."""
    if not args.from_timestamp:
        return None
    if args.source != "kafka":
        raise ValueError(
            "--from-timestamp requires --source kafka (broker-side "
            "timestamp index lookup)"
        )
    if args.resume:
        raise ValueError(
            "--from-timestamp cannot be combined with --resume (the "
            "snapshot's offsets already fix where the scan continues); "
            "drop --resume to seek to the timestamp, or drop "
            "--from-timestamp to resume the snapshot"
        )
    return parse_timestamp_ms(args.from_timestamp)


def resolve_start_offsets(source, from_ts_ms, label):
    """(start_at, exhausted): per-partition first offsets at/after the
    cutoff via the broker timestamp index; exhausted=True (with the
    message already printed) when nothing remains at or after it."""
    if from_ts_ms is None:
        return None, False
    start_at = source.offsets_for_timestamp(from_ts_ms)
    _, end = source.watermarks()
    if all(start_at.get(p, 0) >= end[p] for p in end):
        print(
            f"No records at or after {label} — nothing to analyze.",
            file=sys.stderr,
        )
        return None, True
    return start_at, False


def run_multi_topic(args, topics: "list[str]") -> int:
    """Fan-in scan of several topics through one backend: per-topic reports
    from row slices, plus a cross-topic union block whose sketch lines come
    from the associatively merged state (io/multi.py)."""
    from kafka_topic_analyzer_tpu.engine import run_scan
    from kafka_topic_analyzer_tpu.io.multi import MultiTopicSource
    from kafka_topic_analyzer_tpu.report import render_report
    from kafka_topic_analyzer_tpu.results import slice_rows
    from kafka_topic_analyzer_tpu.utils.profiling import maybe_jax_trace
    from kafka_topic_analyzer_tpu.utils.progress import Spinner
    from kafka_topic_analyzer_tpu.utils.timefmt import format_utc_seconds

    with user_input_phase():
        from_ts_ms = parse_from_timestamp_flag(args)
        # Dump tees attach per topic, before fan-in remaps partition ids.
        topic_sources = [
            (t, wrap_with_dump(args, t, make_source(args, topic=t, seed_salt=i)))
            for i, t in enumerate(topics)
        ]
        multi = MultiTopicSource(topic_sources)
    if multi.is_empty():
        print(
            "Given topic has no content, no analysis possible. Exiting.",
            file=sys.stderr,
        )
        sys.exit(-2)
    start_at, exhausted = resolve_start_offsets(
        multi, from_ts_ms, args.from_timestamp
    )
    if exhausted:
        return 0

    with user_input_phase():
        mesh_shape = parse_mesh(args.mesh)
        config = AnalyzerConfig(
            num_partitions=len(multi.partitions()),
            batch_size=args.batch_size,
            count_alive_keys=args.count_alive_keys,
            alive_bitmap_bits=args.alive_bitmap_bits,
            enable_hll=args.distinct_keys,
            distinct_keys_per_partition=args.distinct_keys_per_partition,
            enable_quantiles=args.quantiles,
            quantiles_per_partition=args.quantiles_per_partition,
            mesh_shape=mesh_shape,
            use_pallas_counters=args.pallas,
            wire_format=resolve_wire_format(args),
            alive_compaction=getattr(args, "alive_compaction", "auto"),
        )
        ingest_workers = resolve_ingest_workers(
            args, mesh_shape, len(multi.partitions())
        )
        dispatch = resolve_dispatch(args)
    backend = _make_cli_backend(args, config, mesh_shape, dispatch=dispatch)

    banner_out = sys.stderr if args.json else sys.stdout
    print(f"Subscribing to {', '.join(topics)} ({len(topics)}-topic fan-in)",
          file=banner_out)
    print("Starting message consumption...", file=banner_out)
    with maybe_jax_trace(args.profile_dir):
        result = run_scan(
            args.topic,
            multi,
            backend,
            batch_size=args.batch_size,
            spinner=Spinner(enabled=not args.quiet),
            snapshot_dir=args.snapshot_dir,
            snapshot_every_s=args.snapshot_every,
            resume=args.resume,
            start_at=start_at,
            ingest_workers=ingest_workers,
        )
    # Only the --stats digest and the --json flight block consume the
    # diagnosis; the plain report path skips the doctor pass entirely.
    diagnosis = _diagnose(result) if (args.stats or args.json) else None
    _print_stats(args, result, diagnosis)
    multi.close()  # flush per-topic segment dumps, release connections
    if _not_report_process(args):
        return _scan_issue_exit(result)  # multi-host: one report, from process 0

    union = result.metrics
    # Per-topic projections, computed once for both output formats.
    slices = []
    for topic in topics:
        rows = multi.rows_for(topic)
        ids = [multi.true_partition(r) for r in rows]
        sliced = slice_rows(union, rows, ids)
        start = {multi.true_partition(r): result.start_offsets[r] for r in rows}
        end = {multi.true_partition(r): result.end_offsets[r] for r in rows}
        slices.append((topic, sliced, start, end))

    if args.json:
        import json

        doc: dict = {
            "topics": {},
            "duration_secs": result.duration_secs,
            "ingest_workers": result.ingest_workers,
            "ingest_workers_per_controller": (
                result.ingest_workers_per_controller
            ),
            "superbatch_k": result.superbatch_k,
            "dispatch_depth": result.dispatch_depth,
        }
        for topic, sliced, start, end in slices:
            doc["topics"][topic] = sliced.to_dict(start, end)
        union_doc = {
            "count": union.overall_count,
            "size_bytes": union.overall_size,
            "earliest_ts": union.earliest_ts_s,
            "latest_ts": union.latest_ts_s,
        }
        if union.alive_keys is not None:
            union_doc["alive_keys_sum_over_topics"] = union.alive_keys
        if union.distinct_keys_hll is not None:
            union_doc["distinct_keys_hll"] = union.distinct_keys_hll
        if union.distinct_keys_exact is not None:
            union_doc["distinct_keys_exact"] = union.distinct_keys_exact
        if union.quantiles is not None:
            union_doc["size_quantiles"] = union.quantiles.as_dict()
        doc["union"] = union_doc
        doc["telemetry"] = result.telemetry
        from kafka_topic_analyzer_tpu.report import attach_scan_digests

        attach_scan_digests(doc, result, diagnosis)
        # Degraded keys are dense fan-in rows; reasons carry topic/partition.
        rc = _scan_issue_exit(result, doc=doc)
        print(json.dumps(doc))
        return rc
    # Per-topic reports from the shared projections.
    for topic, sliced, start, end in slices:
        # Extensions render only the per-row lines a slice can carry (e.g.
        # per-partition quantiles); merged union-only sketches are None here.
        sys.stdout.write(
            render_report(
                topic, sliced, start, end, result.duration_secs,
                show_alive_keys=False, show_extensions=True,
            )
        )
        if args.extremes_table:
            from kafka_topic_analyzer_tpu.report import render_extremes_table

            sys.stdout.write(render_extremes_table(sliced))

    # Union block: totals + merged sketches (not sliceable per topic).
    eq = "=" * 120
    print(eq)
    print(f"FAN-IN UNION of {len(topics)} topics: {', '.join(topics)}")
    print(f"Messages: {union.overall_count}")
    print(f"Bytes: {union.overall_size}")
    print(f"Earliest Message: {format_utc_seconds(union.earliest_ts_s)}")
    print(f"Latest Message: {format_utc_seconds(union.latest_ts_s)}")
    if args.count_alive_keys and union.alive_keys is not None:
        # Sum of per-topic alive keys (slots are salted per topic so the
        # count is mesh- and interleaving-independent; io/multi.py).
        print(f"Alive keys (sum over topics): {union.alive_keys}")
    if union.distinct_keys_hll is not None:
        print(f"Distinct keys (HLL est., union): {round(union.distinct_keys_hll)}")
    if union.distinct_keys_exact is not None:
        print(f"Distinct keys (exact, union): {union.distinct_keys_exact}")
    if union.quantiles is not None:
        qs = " ".join(
            f"p{int(p * 100)}={v:.0f}B"
            for p, v in zip(union.quantiles.probs, union.quantiles.values)
        )
        print(f"Message size quantiles (union): {qs}")
    print(eq)
    return _scan_issue_exit(
        result, render=True,
        data_loss_policy=getattr(args, "on_data_loss", "report"),
    )


def _fleet_exit(fleet_result) -> int:
    """Fleet exit precedence mirrors the solo scan's (degraded outranks
    corrupt — PR 3's contract) with one rung above both: a topic whose
    scan hard-failed (isolation caught it; its numbers are partial)."""
    if fleet_result.any_failed:
        return 1
    if fleet_result.any_degraded:
        return EXIT_DEGRADED
    if fleet_result.any_corrupt:
        return EXIT_CORRUPT
    if getattr(fleet_result, "any_data_loss", False):
        return EXIT_DATA_LOSS
    return 0


def make_lease_manager(cfg, snapshot_dir=None, store_spec=None):
    """Resolve a ``LeaseConfig`` + the run's stores into a live
    `fleet.lease.LeaseManager` (DESIGN §23): ``object`` leases ride the
    remote segment store's ETag-fenced conditional writes; ``file``
    leases ride atomic renames under the checkpoint dir; ``auto`` picks
    object exactly when the segment spec is remote."""
    import re as _re

    from kafka_topic_analyzer_tpu.config import (
        SegmentFetchConfig,
        TransportRetryConfig,
    )
    from kafka_topic_analyzer_tpu.fleet.lease import (
        FileLeaseStore,
        LeaseManager,
        ObjectLeaseStore,
    )
    from kafka_topic_analyzer_tpu.io.retry import Backoff

    remote = bool(
        store_spec and _re.match(r"^(https?|s3)://", str(store_spec))
    )
    choice = cfg.store
    if choice == "auto":
        choice = "object" if remote else "file"
    if choice == "object":
        if not remote:
            raise ValueError(
                "--lease-store object needs a remote --segment-dir spec "
                "(http://, https://, s3://) to host the lease records"
            )
        from kafka_topic_analyzer_tpu.io.objstore import RetryingHttp

        store = ObjectLeaseStore(
            RetryingHttp(str(store_spec), SegmentFetchConfig())
        )
    else:
        if not snapshot_dir:
            raise ValueError(
                "--instance-id needs a lease store: pass --snapshot-dir "
                "(file leases live in SNAPSHOT_DIR/_kta_leases/) or a "
                "remote --segment-dir spec with --lease-store object"
            )
        store = FileLeaseStore(snapshot_dir)
    return LeaseManager(
        store,
        instance=cfg.instance_id,
        ttl_s=cfg.ttl_s,
        backoff=Backoff(TransportRetryConfig()),
    )


def run_fleet(args, topics: "list[str] | None" = None) -> int:
    """Cluster-wide scan (--fleet), or an explicit multi-topic follow
    (``-t a,b --follow`` — each topic keeps its solo pass chain; the
    fleet scheduler shares the budgets).  ``topics`` pins the list and
    skips discovery."""
    from kafka_topic_analyzer_tpu.config import IngestConfig
    from kafka_topic_analyzer_tpu.fleet.discovery import (
        discover_topics,
        parse_globs,
    )
    from kafka_topic_analyzer_tpu.fleet.scheduler import (
        FleetScheduler,
        TopicSeed,
    )
    from kafka_topic_analyzer_tpu.fleet.service import FleetService

    with user_input_phase():
        if args.source != "kafka":
            raise ValueError(
                "--fleet requires --source kafka (discovery reads cluster "
                "metadata); a segment store is one topic's immutable "
                "archive with no topic list or moving head — scan it solo "
                "with --source segfile (synthetic sources scan solo too)"
            )
        if not args.bootstrap_server:
            raise SystemExit("--fleet requires -b/--bootstrap-server")
        mesh_shape = parse_mesh(args.mesh)
        if mesh_shape != (1, 1):
            raise ValueError(
                "--fleet does not support --mesh yet (fleet scans run "
                "per-topic single-device backends); drop --mesh, or scan "
                "one topic solo to use a device mesh"
            )
        if args.distributed:
            raise ValueError(
                "--fleet does not support --distributed (per-poll "
                "admission would need fleet-wide lockstep agreement); "
                "run the fleet single-controller"
            )
        if args.dump_segments:
            raise ValueError(
                "--fleet does not support --dump-segments (the dump tee "
                "is single-topic); run a solo scan with --dump-segments "
                "per topic instead"
            )
        if args.from_timestamp:
            raise ValueError(
                "--fleet does not support --from-timestamp yet (the "
                "cutoff would need a per-topic seek); scan the topic "
                "solo with --from-timestamp instead"
            )
        dispatch = resolve_dispatch(args)
        ingest_cfg = IngestConfig.parse(args.ingest_workers)
        text = str(args.fleet_concurrency).strip().lower()
        explicit_concurrency = None
        if text != "auto":
            try:
                explicit_concurrency = int(text)
            except ValueError:
                raise ValueError(
                    f"bad --fleet-concurrency {args.fleet_concurrency!r}: "
                    "expected a positive integer or 'auto'"
                ) from None
            if explicit_concurrency < 1:
                raise ValueError("--fleet-concurrency must be >= 1")

    banner_out = sys.stderr if args.json else sys.stdout
    rediscover = None
    if topics is None:
        include = parse_globs(args.topic) or ["*"]
        exclude = parse_globs(args.fleet_exclude)

        def discover() -> "list[TopicSeed]":
            return [
                TopicSeed(name=d.name, partitions=d.partitions)
                for d in discover_topics(
                    args.bootstrap_server, include, exclude,
                    args.fleet_internal,
                )
            ]

        seeds = discover()
        if args.follow:
            rediscover = discover
        print(
            f"Fleet discovery: {len(seeds)} topic(s) matched "
            f"{','.join(include)}"
            + (f" minus {','.join(exclude)}" if exclude else ""),
            file=banner_out,
        )
    else:
        # Explicit list (multi-topic --follow): real partition counts
        # come from one all-topics metadata round trip — the worker
        # budget below is resolved against them, and a placeholder of 1
        # would silently cap the whole fleet at len(topics) workers.  An
        # unreachable cluster keeps the placeholders; every scan then
        # fails in isolation and the service exits, like solo.
        parts_by_name: "dict[str, int]" = {}
        try:
            wanted = set(topics)
            for d in discover_topics(
                args.bootstrap_server, include_internal=True
            ):
                if d.name in wanted:
                    parts_by_name[d.name] = d.partitions
        except Exception as e:
            print(
                f"warning: could not size the fleet from cluster "
                f"metadata ({e}); worker budget assumes 1 partition "
                "per topic",
                file=sys.stderr,
            )
        seeds = [
            TopicSeed(name=t, partitions=parts_by_name.get(t, 1))
            for t in topics
        ]
    if not seeds:
        print(
            "No topics matched the fleet filters, no analysis possible. "
            "Exiting.",
            file=sys.stderr,
        )
        sys.exit(-2)

    total_parts = sum(max(1, s.partitions) for s in seeds)
    worker_budget = ingest_cfg.resolve(max(1, total_parts))
    max_concurrent = (
        explicit_concurrency
        if explicit_concurrency is not None
        else max(1, min(4, len(seeds), worker_budget))
    )
    # Under --fleet, --dispatch-depth is the GLOBAL in-flight budget the
    # concurrent device scans share (each admitted scan holds >= 1
    # token).  The cpu oracle has no dispatch queue, so its token budget
    # just matches the concurrency.
    dispatch_budget = (
        max(1, args.dispatch_depth)
        if args.backend == "tpu"
        else max_concurrent
    )

    def source_factory(topic: str):
        return make_source(args, topic=topic)

    def backend_factory(topic: str, num_partitions: int, grant):
        with user_input_phase():
            config = AnalyzerConfig(
                num_partitions=num_partitions,
                batch_size=args.batch_size,
                count_alive_keys=args.count_alive_keys,
                alive_bitmap_bits=args.alive_bitmap_bits,
                enable_hll=args.distinct_keys,
                distinct_keys_per_partition=args.distinct_keys_per_partition,
                enable_quantiles=args.quantiles,
                quantiles_per_partition=args.quantiles_per_partition,
                mesh_shape=(1, 1),
                use_pallas_counters=args.pallas,
                wire_format=resolve_wire_format(args),
                alive_compaction=getattr(args, "alive_compaction", "auto"),
            )
        topic_dispatch = None
        if dispatch is not None:
            from kafka_topic_analyzer_tpu.config import DispatchConfig

            topic_dispatch = DispatchConfig(
                superbatch=dispatch.superbatch,
                depth=grant.dispatch_depth,
            )
        return _make_cli_backend(args, config, (1, 1), dispatch=topic_dispatch)

    follow_cfg = None
    if args.follow:
        with user_input_phase():
            from kafka_topic_analyzer_tpu.config import FollowConfig

            follow_cfg = FollowConfig(
                poll_interval_s=args.poll_interval,
                checkpoint_every_s=(
                    args.checkpoint_interval
                    if args.checkpoint_interval is not None
                    else args.snapshot_every
                ),
                idle_exit_s=args.follow_idle_exit,
            )

    lease_mgr = None
    instance = "solo"
    if getattr(args, "instance_id", None):
        with user_input_phase():
            from kafka_topic_analyzer_tpu.config import LeaseConfig

            instance = args.instance_id
            if instance == "auto":
                # Bare --instance-id: HOSTNAME-PID is unique per process
                # on a shared cluster, which is all a lease owner needs.
                import os
                import socket

                instance = f"{socket.gethostname()}-{os.getpid()}"
            lease_cfg = LeaseConfig(
                instance_id=instance,
                ttl_s=args.lease_ttl,
                store=args.lease_store,
            )
            lease_mgr = make_lease_manager(
                lease_cfg,
                snapshot_dir=args.snapshot_dir,
                store_spec=getattr(args, "segment_dir", None),
            )

    scheduler = FleetScheduler(
        worker_budget, dispatch_budget, max_concurrent, instance=instance
    )
    from kafka_topic_analyzer_tpu.utils.progress import Spinner

    svc = FleetService(
        seeds,
        source_factory,
        backend_factory,
        args.batch_size,
        scheduler,
        follow=follow_cfg,
        snapshot_dir=args.snapshot_dir,
        resume=args.resume,
        # /report.json assembly is pure waste when no HTTP server exists
        # to serve it (same rule as the solo follow service).
        publish_reports=args.metrics_port is not None,
        serve_gzip=not args.no_serve_gzip,
        spinner=Spinner(enabled=not args.quiet),
        rediscover=rediscover,
        leases=lease_mgr,
        instance=instance,
    )
    print(
        f"Fleet scan of {len(seeds)} topic(s): "
        f"{worker_budget} worker(s), dispatch budget {dispatch_budget}, "
        f"concurrency {max_concurrent}"
        + (" (follow)" if args.follow else "")
        + (
            f" [instance {instance}, lease TTL {args.lease_ttl:g}s]"
            if lease_mgr is not None else ""
        ),
        file=banner_out,
    )
    if args.follow:
        restore = svc.install_signal_handlers()
        try:
            fleet_result = svc.run_follow()
        finally:
            restore()
    else:
        fleet_result = svc.run_batch()

    if args.stats:
        from kafka_topic_analyzer_tpu.obs.registry import default_registry
        from kafka_topic_analyzer_tpu.report import (
            render_fleet_status,
            render_telemetry_stats,
        )

        sys.stderr.write(render_fleet_status(fleet_result.rollup))
        sys.stderr.write(
            render_telemetry_stats(default_registry().snapshot())
        )
        _print_health_stats()
    if args.json:
        import json

        from kafka_topic_analyzer_tpu.report import build_json_doc

        doc = dict(fleet_result.rollup)
        doc["topics"] = {
            t: build_json_doc(
                t,
                result,
                diagnosis=_diagnose(result),
                fleet=fleet_result.statuses[t].as_dict(),
            )
            for t, result in sorted(fleet_result.results.items())
        }
        rc = _fleet_exit(fleet_result)
        print(json.dumps(doc))
        return rc
    from kafka_topic_analyzer_tpu.report import (
        render_fleet_status,
        render_report,
    )

    for t, result in sorted(fleet_result.results.items()):
        sys.stdout.write(
            render_report(
                t,
                result.metrics,
                result.start_offsets,
                result.end_offsets,
                result.duration_secs,
                show_alive_keys=args.count_alive_keys,
            )
        )
    sys.stdout.write(render_fleet_status(fleet_result.rollup))
    return _fleet_exit(fleet_result)


def main(argv: "list[str] | None" = None) -> int:
    from kafka_topic_analyzer_tpu.utils.log import init_logging

    init_logging()  # env_logger parity: RUST_LOG / KTA_LOG (src/main.rs:30)
    args = build_parser().parse_args(argv)
    from kafka_topic_analyzer_tpu.io.kafka_codec import KafkaProtocolError
    from kafka_topic_analyzer_tpu.obs import telemetry_session

    history_dir = None
    if args.history_bytes:
        if args.history_bytes < 4096:
            print("error: --history-bytes must be >= 4096", file=sys.stderr)
            return 1
        if not args.snapshot_dir:
            print(
                "error: --history-bytes requires --snapshot-dir (the "
                "telemetry history lives next to the checkpoints so a "
                "restarted service resumes both from one place)",
                file=sys.stderr,
            )
            return 1
        from kafka_topic_analyzer_tpu.checkpoint import (
            history_dir as _history_dir,
        )

        history_dir = _history_dir(args.snapshot_dir)
    try:
        with telemetry_session(
            metrics_port=args.metrics_port,
            events_jsonl=args.events_jsonl,
            trace_json=args.trace_json,
            flight_record=args.flight_record,
            history_dir=history_dir,
            history_bytes=args.history_bytes,
            sse=args.sse,
        ):
            return _run(args)
    except (OSError, KafkaProtocolError) as e:
        # Environment/user-facing failures get one clean line, not a
        # traceback (the reference panics here; we can do better).  Other
        # exception types — including internal ValueErrors — keep their
        # tracebacks so bugs stay diagnosable.
        from kafka_topic_analyzer_tpu.io.kafka_wire import DataLossError

        if isinstance(e, DataLossError):
            # --on-data-loss=fail abort: the loss is booked and a
            # fold-consistent checkpoint was written on the way out, so
            # the distinct exit code tells automation a --resume will
            # continue past the NAMED gap (not a hard failure).
            print(f"error: DATA-LOSS: {e}", file=sys.stderr)
            return EXIT_DATA_LOSS
        print(f"error: {e}", file=sys.stderr)
        return 1
    except UserInputError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


def _run(args) -> int:
    if args.distributed:
        from kafka_topic_analyzer_tpu.parallel.mesh import initialize_distributed

        with user_input_phase():
            initialize_distributed(args.distributed)
    if args.fleet:
        return run_fleet(args)
    if getattr(args, "instance_id", None) and not (
        "," in args.topic and args.follow
    ):
        with user_input_phase():
            raise ValueError(
                "--instance-id requires the fleet scheduler (topic "
                "ownership leases arbitrate WHOLE topics between "
                "analyzer instances; a solo or batch fan-in scan has "
                "no admission to arbitrate) — add --fleet, or --follow "
                "with a multi-topic list"
            )
    # Kafka topic names cannot contain commas, so "-t a,b,c" unambiguously
    # selects multi-topic fan-in (new capability; BASELINE.json config 5).
    if "," in args.topic:
        if args.follow:
            # Lifted (fleet mode): an explicit topic list under --follow
            # runs through the fleet scheduler — each topic keeps its
            # solo pass chain (NOT the fan-in's merged state), budgets
            # are shared, and /report.json?topic= serves each document —
            # so --instance-id leases compose here too.
            return run_fleet(
                args, topics=[t for t in args.topic.split(",") if t]
            )
        return run_multi_topic(args, [t for t in args.topic.split(",") if t])
    with user_input_phase():
        # Cheap flag validation first — before any broker handshake or dump
        # directory creation.
        if args.follow and args.source == "segfile":
            raise ValueError(
                "--follow cannot tail --source segfile (a segment store "
                "is immutable — there is no moving head to poll); run "
                "the batch scan of the store, or --follow the live "
                "topic with --source kafka (add --dump-segments to keep "
                "the archive fresh)"
            )
        from_ts_ms = parse_from_timestamp_flag(args)
        source = wrap_with_dump(args, args.topic, make_source(args))
        start_at, exhausted = resolve_start_offsets(
            source, from_ts_ms, args.from_timestamp
        )
        if exhausted:
            return 0

    # Empty-topic guard: exit(-2) like src/main.rs:98-101.  A follow
    # service deliberately skips it — sitting on a still-empty topic and
    # waiting for the first record IS the job.
    if source.is_empty() and not args.follow:
        print(
            "Given topic has no content, no analysis possible. Exiting.",
            file=sys.stderr,
        )
        sys.exit(-2)

    with user_input_phase():
        mesh_shape = parse_mesh(args.mesh)
        config = AnalyzerConfig(
            num_partitions=len(source.partitions()),
            batch_size=args.batch_size,
            count_alive_keys=args.count_alive_keys,
            alive_bitmap_bits=args.alive_bitmap_bits,
            enable_hll=args.distinct_keys,
            distinct_keys_per_partition=args.distinct_keys_per_partition,
            enable_quantiles=args.quantiles,
            quantiles_per_partition=args.quantiles_per_partition,
            mesh_shape=mesh_shape,
            use_pallas_counters=args.pallas,
            wire_format=resolve_wire_format(args),
            alive_compaction=getattr(args, "alive_compaction", "auto"),
        )
        ingest_workers = resolve_ingest_workers(
            args, mesh_shape, len(source.partitions())
        )
        dispatch = resolve_dispatch(args)

    from kafka_topic_analyzer_tpu.engine import run_scan
    from kafka_topic_analyzer_tpu.report import render_report
    from kafka_topic_analyzer_tpu.utils.profiling import maybe_jax_trace
    from kafka_topic_analyzer_tpu.utils.progress import Spinner

    backend = _make_cli_backend(args, config, mesh_shape, dispatch=dispatch)

    banner_out = sys.stderr if args.json else sys.stdout
    print(f"Subscribing to {args.topic}", file=banner_out)
    print("Starting message consumption...", file=banner_out)
    follow_service = None
    with maybe_jax_trace(args.profile_dir):
        if args.follow:
            from kafka_topic_analyzer_tpu.config import FollowConfig
            from kafka_topic_analyzer_tpu.serve.follow import FollowService

            with user_input_phase():
                follow_cfg = FollowConfig(
                    poll_interval_s=args.poll_interval,
                    checkpoint_every_s=(
                        args.checkpoint_interval
                        if args.checkpoint_interval is not None
                        else args.snapshot_every
                    ),
                    idle_exit_s=args.follow_idle_exit,
                    window_secs=args.window_secs,
                    window_count=args.window_count,
                )
            with user_input_phase():
                follow_service = FollowService(
                    args.topic,
                    source,
                    backend,
                    batch_size=args.batch_size,
                    follow=follow_cfg,
                    spinner=Spinner(enabled=not args.quiet),
                    snapshot_dir=args.snapshot_dir,
                    resume=args.resume,
                    start_at=start_at,
                    ingest_workers=ingest_workers,
                    # /report.json assembly is pure waste when no HTTP
                    # server exists to serve it.
                    publish_reports=args.metrics_port is not None,
                    serve_gzip=not args.no_serve_gzip,
                )
            restore_signals = follow_service.install_signal_handlers()
            try:
                result = follow_service.run()
            finally:
                restore_signals()
        else:
            result = run_scan(
                args.topic,
                source,
                backend,
                batch_size=args.batch_size,
                spinner=Spinner(enabled=not args.quiet),
                snapshot_dir=args.snapshot_dir,
                snapshot_every_s=args.snapshot_every,
                resume=args.resume,
                start_at=start_at,
                ingest_workers=ingest_workers,
            )
    # Only the --stats digest and the --json flight block consume the
    # diagnosis; the plain report path skips the doctor pass entirely.
    diagnosis = _diagnose(result) if (args.stats or args.json) else None
    _print_stats(args, result, diagnosis)
    if hasattr(source, "close"):
        source.close()  # flush segment dumps, release broker connections
    if _not_report_process(args):
        # Multi-host: one report, from process 0 — but every process must
        # agree on the degraded exit code for orchestrators (run_scan
        # reduces the degraded flag across processes).
        return _scan_issue_exit(result)

    if args.json:
        import json

        from kafka_topic_analyzer_tpu.obs import health as obs_health
        from kafka_topic_analyzer_tpu.report import build_json_doc

        health_engine = obs_health.active()
        if health_engine is not None and health_engine.doc() is None:
            # Sub-interval scans never hit a heartbeat boundary; the
            # document must still carry one real evaluation — a missing
            # health key would be indistinguishable from "alerting
            # never ran" (same rule as the --stats digest).
            health_engine.evaluate()
        doc = build_json_doc(
            args.topic,
            result,
            diagnosis=diagnosis,
            follow=(
                follow_service.follow_block()
                if follow_service is not None
                else None
            ),
            windows=(
                follow_service.windows_report()
                if follow_service is not None
                else None
            ),
            health=(
                health_engine.alerts_block()
                if health_engine is not None
                else None
            ),
        )
        rc = _scan_issue_exit(result)
        print(json.dumps(doc))
        return rc
    sys.stdout.write(
        render_report(
            args.topic,
            result.metrics,
            result.start_offsets,
            result.end_offsets,
            result.duration_secs,
            show_alive_keys=args.count_alive_keys,
        )
    )
    if args.extremes_table:
        from kafka_topic_analyzer_tpu.report import render_extremes_table

        sys.stdout.write(render_extremes_table(result.metrics))
    return _scan_issue_exit(
        result, render=True,
        data_loss_policy=getattr(args, "on_data_loss", "report"),
    )


if __name__ == "__main__":
    sys.exit(main())
