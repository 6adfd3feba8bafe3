"""End-to-end pipeline benchmark: ONE measured msgs/s figure covering
broker → wire client → record decode → pack → device → reduce.

This is the apples-to-apples comparison to the reference's published
590,221 msgs/s (demo_output.png; formula src/main.rs:130 =
overall_count / max(secs, 1)): the reference's number times the whole
consume pipeline, whereas ``bench.py`` times the device path with
pre-materialized batches.  Here the records cross a real loopback TCP
socket as Kafka Fetch v4 responses and the scan runs through the real
engine (`engine.run_scan`) — the same code path as ``kta --source kafka``.

The serving side must be far faster than the client under test, so the
broker never encodes per record at fetch time.  It pre-encodes a small
set of **template RecordBatches** (base_offset 0) and serves every offset
window as a template copy with the base_offset header patched in place.
That is valid Kafka wire data: the v2 batch CRC32-C covers attributes
onward and explicitly EXCLUDES base_offset/batch_length/leader_epoch/
magic/crc (io/kafka_codec.py:encode_record_batch), and record offset
deltas are relative to base_offset — so an 8-byte patch retargets a batch
to any window at memcpy speed.  Distinct templates carry distinct key
sets, so HLL/alive-key paths still see `templates × records_per_batch`
unique keys cycling through the topic.  With at least as many templates
as partitions, each partition cycles through its own template range, so
a key lives in one partition, as Kafka's key partitioner places it.
"""

from __future__ import annotations

import argparse
import json
import socket
import struct
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from kafka_topic_analyzer_tpu.io import kafka_codec as kc

BASELINE_MSGS_PER_SEC = 590_221.0


def build_templates(
    records_per_batch: int,
    n_templates: int,
    vmin: int,
    vmax: int,
    seed: int = 7,
    compression: int = kc.COMPRESSION_NONE,
    tombstone_every: int = 0,
) -> List[bytes]:
    """Encode ``n_templates`` RecordBatches (base_offset 0) with disjoint
    key sets and seeded value sizes in [vmin, vmax]."""
    rng = np.random.default_rng(seed)
    base_ts = 1_767_225_600_000  # 2026-01-01T00:00:00Z, ms
    out = []
    for t in range(n_templates):
        sizes = rng.integers(vmin, vmax + 1, size=records_per_batch)
        recs: List[kc.OffsetRecord] = []
        for i in range(records_per_batch):
            key = b"k%04d-%08d" % (t, i)
            if tombstone_every and i % tombstone_every == (t % tombstone_every):
                value = None
            else:
                value = bytes(int(sizes[i]))
            recs.append((i, base_ts + i, key, value))
        out.append(kc.encode_record_batch(recs, compression=compression))
    return out


def _sendmsg_all(conn: socket.socket, bufs: "list") -> None:
    """sendall semantics for a scatter-gather buffer list (sendmsg may
    send partially; resume from the exact byte)."""
    views = [memoryview(b) for b in bufs]
    i = 0
    while i < len(views):
        sent = conn.sendmsg(views[i : i + 512])
        while i < len(views) and sent >= len(views[i]):
            sent -= len(views[i])
            i += 1
        if sent:
            views[i] = views[i][sent:]


class TemplateBroker:
    """Loopback Kafka broker serving base_offset-patched template batches.

    Speaks exactly the APIs the wire client negotiates (ApiVersions v0,
    Metadata v1–v5, ListOffsets v1, Fetch v4) and honors both byte budgets
    of a Fetch request — partition_max_bytes per partition and the KIP-74
    request-level max_bytes (first batch always served whole).
    """

    def __init__(
        self,
        topic: str,
        partitions: int,
        windows_per_partition: int,
        templates: List[bytes],
        records_per_batch: int,
        brokers: int = 1,
    ):
        self.topic = topic
        self.partitions = list(range(partitions))
        self.partition_set = set(self.partitions)
        #: Template split for scatter-gather serving: the first 8 bytes of
        #: a v2 frame are base_offset (not CRC-covered), so a response is
        #: [8-byte patched header][shared template tail] pairs — the tails
        #: are served zero-copy straight from these views by sendmsg.
        self.tmpl_tails = [memoryview(t)[8:] for t in templates]
        self.windows = windows_per_partition
        self.templates = templates
        #: Templates each partition cycles through (disjoint ranges).
        self.per_partition = max(1, len(templates) // partitions)
        self.R = records_per_batch
        self.end_offset = windows_per_partition * records_per_batch
        #: N listener sockets = N advertised broker nodes (partition p is
        #: led by node p % N) — exercises the wire client's
        #: leader-parallel fetch the way a real multi-broker cluster does.
        self._socks: List[socket.socket] = []
        self.ports: List[int] = []
        for _ in range(max(1, brokers)):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            s.listen(16)
            self._socks.append(s)
            self.ports.append(s.getsockname()[1])
        self.port = self.ports[0]
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "TemplateBroker":
        for s in self._socks:
            t = threading.Thread(
                target=self._accept_loop, args=(s,), daemon=True
            )
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        for s in self._socks:
            try:
                s.close()
            except OSError:
                pass

    def __enter__(self) -> "TemplateBroker":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _accept_loop(self, sock: socket.socket) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = sock.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(target=self._serve, args=(conn,), daemon=True)
            t.start()
            self._threads.append(t)

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> Optional[bytes]:
        buf = bytearray()
        while len(buf) < n:
            try:
                chunk = conn.recv(n - len(buf))
            except OSError:
                return None
            if not chunk:
                return None
            buf += chunk
        return bytes(buf)

    # -- serving -------------------------------------------------------------

    def _serve(self, conn: socket.socket) -> None:
        with conn:
            while not self._stop.is_set():
                head = self._recv_exact(conn, 4)
                if head is None:
                    return
                (length,) = struct.unpack(">i", head)
                payload = self._recv_exact(conn, length)
                if payload is None:
                    return
                api_key, api_version, corr, _client, r = (
                    kc.decode_request_header(payload)
                )
                body = self._dispatch(api_key, api_version, r)
                # Fetch responses are iovec lists served scatter-gather:
                # sendmsg lets the kernel read the shared template tails
                # directly — zero Python-side assembly of the ~64 MB body.
                if isinstance(body, list):
                    total = sum(len(b) for b in body)
                    _sendmsg_all(
                        conn,
                        [struct.pack(">ii", 4 + total, corr)] + body,
                    )
                else:
                    conn.sendall(struct.pack(">ii", 4 + len(body), corr))
                    conn.sendall(body)

    def _fetch_response(self, parts, max_bytes: int) -> "list":
        """Build the Fetch v4 response as an iovec list: small packed
        header chunks interleaved with the SHARED template tails (the
        first 8 bytes of each frame — base_offset, not CRC-covered — are
        per-window header chunks).  sendmsg serves the tails zero-copy, so
        the serving side never assembles the multi-MB body at all and
        stays far faster than the client under test.

        Budgets mirror a real broker: per-partition ``partition_max_bytes``
        and the KIP-74 request-level ``max_bytes``, with the first
        non-empty partition always granted one whole batch (minOneMessage),
        which the wire client's starvation logic relies on."""
        plan = []  # (pid, err, first_window, n_windows, rs_bytes)
        budget = max_bytes
        served_any = False
        for pid, fetch_offset, pmax, _epoch in parts:
            if pid not in self.partition_set:
                plan.append((pid, kc.ERR_UNKNOWN_TOPIC_OR_PARTITION, 0, 0, 0))
                continue
            w0 = fetch_offset // self.R  # align down; clients skip below
            lim = min(pmax, budget)
            n = 0
            size = 0
            while w0 + n < self.windows and (
                size < lim or (n == 0 and not served_any)
            ):
                size += len(self.templates[self._template(pid, w0 + n)])
                n += 1
            if n:
                served_any = True
            budget = max(0, budget - size)
            plan.append((pid, 0, w0, n, size))

        topic_b = self.topic.encode()
        head = struct.pack(
            ">iiH", 0, 1, len(topic_b)
        ) + topic_b + struct.pack(">i", len(plan))
        iov = [head]
        for pid, err, w0, n, size in plan:
            iov.append(
                struct.pack(
                    ">ihqqii", pid, err, self.end_offset,
                    self.end_offset, 0, size,
                )
            )
            for i in range(n):
                w = w0 + i
                iov.append(struct.pack(">q", w * self.R))
                iov.append(self.tmpl_tails[self._template(pid, w)])
        return iov

    def _template(self, pid: int, window: int) -> int:
        """Index of the template served as ``window`` of partition ``pid``."""
        k = self.per_partition
        return (pid * k + window % k) % len(self.templates)

    def _dispatch(self, api_key: int, api_version: int, r: kc.ByteReader) -> bytes:
        if api_key == kc.API_VERSIONS:
            return kc.encode_api_versions_response(
                [
                    (kc.API_FETCH, 0, 4),
                    (kc.API_LIST_OFFSETS, 0, 1),
                    (kc.API_METADATA, 0, 5),
                ]
            )
        if api_key == kc.API_METADATA:
            requested = []
            n = r.i32()
            for _ in range(max(n, 0)):
                requested.append(r.string())
            nb = len(self.ports)
            topics = [
                kc.TopicMetadata(
                    0,
                    self.topic,
                    [
                        kc.PartitionMetadata(0, p, p % nb)
                        for p in self.partitions
                    ],
                )
                if name == self.topic
                else kc.TopicMetadata(
                    kc.ERR_UNKNOWN_TOPIC_OR_PARTITION, name or "", []
                )
                for name in (requested if requested else [self.topic])
            ]
            return kc.encode_metadata_response(
                kc.MetadataResponse(
                    {i: ("127.0.0.1", port) for i, port in enumerate(self.ports)},
                    0,
                    topics,
                ),
                version=api_version,
            )
        if api_key == kc.API_LIST_OFFSETS:
            _topic, parts = kc.decode_list_offsets_request(r)
            results = []
            for pid, ts in parts:
                if pid not in self.partitions:
                    results.append(
                        (pid, kc.ERR_UNKNOWN_TOPIC_OR_PARTITION, -1, -1)
                    )
                elif ts == kc.EARLIEST_TIMESTAMP:
                    results.append((pid, 0, -1, 0))
                elif ts == kc.LATEST_TIMESTAMP:
                    results.append((pid, 0, -1, self.end_offset))
                else:
                    results.append((pid, 0, ts, 0))
            return kc.encode_list_offsets_response(self.topic, results)
        if api_key == kc.API_FETCH:
            _topic, parts, _mw, _mb, max_bytes = kc.decode_fetch_request(r)
            return self._fetch_response(parts, max_bytes)
        raise AssertionError(f"bench broker: unsupported api {api_key}")


def _broker_child(pipe, topic, partitions, windows, R, n_templates,
                  vmin, vmax, compression, tombstone_every,
                  brokers) -> None:
    """Subprocess entry: build templates, serve, report the port, block.

    The broker must live in its own process — in-process serving steals
    GIL time from the client under test and the measurement stops being
    a client-side number."""
    templates = build_templates(
        R, n_templates, vmin, vmax,
        compression=compression, tombstone_every=tombstone_every,
    )
    broker = TemplateBroker(
        topic, partitions, windows, templates, R, brokers=brokers
    )
    broker.start()
    pipe.send(broker.port)
    pipe.recv()  # parent says stop (or EOFError on parent death)


class BrokerProcess:
    """TemplateBroker in a child process; context manager yields the port."""

    def __init__(self, **kw):
        self._kw = kw

    def __enter__(self) -> int:
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self._parent, child = ctx.Pipe()
        self._proc = ctx.Process(
            target=_broker_child,
            args=(
                child,
                self._kw["topic"], self._kw["partitions"], self._kw["windows"],
                self._kw["R"], self._kw["n_templates"], self._kw["vmin"],
                self._kw["vmax"], self._kw["compression"],
                self._kw.get("tombstone_every", 0),
                self._kw.get("brokers", 1),
            ),
            daemon=True,
        )
        self._proc.start()
        deadline = time.monotonic() + 120
        while not self._parent.poll(0.5):
            # The parent still holds the child's pipe end, so a child that
            # dies never shows as EOF: watch the process itself.
            if not self._proc.is_alive():
                raise RuntimeError(
                    f"bench broker exited with code {self._proc.exitcode} "
                    "before serving"
                )
            if time.monotonic() > deadline:
                self._proc.terminate()
                raise RuntimeError("bench broker failed to start within 120s")
        return self._parent.recv()

    def __exit__(self, *exc) -> None:
        try:
            self._parent.send("stop")
        except OSError:
            pass
        self._proc.join(5)
        if self._proc.is_alive():
            self._proc.terminate()


def run(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--partitions", type=int, default=16)
    ap.add_argument("--records", type=int, default=50_000_000,
                    help="total logical records served across partitions")
    ap.add_argument("--batch-size", type=int, default=1 << 20)
    ap.add_argument("--records-per-batch", type=int, default=4096,
                    help="records per wire RecordBatch (template size)")
    ap.add_argument("--templates", type=int, default=16,
                    help="distinct templates (keys = templates x "
                         "records-per-batch)")
    ap.add_argument("--features", default="counters",
                    help="comma set: counters,alive,hll,quantiles "
                         "(default matches the reference's headline scan)")
    ap.add_argument("--backend", default="tpu", choices=["cpu", "tpu"])
    ap.add_argument("--vmin", type=int, default=100)
    ap.add_argument("--vmax", type=int, default=420)
    ap.add_argument("--compression", default="none",
                    choices=["none", "gzip", "snappy", "lz4", "zstd"])
    ap.add_argument("--tombstone-every", type=int, default=0,
                    help="make every Nth template record a tombstone "
                         "(0 = none)")
    ap.add_argument("--brokers", type=int, default=1,
                    help="advertised broker nodes (partition p led by "
                         "p %% N) — exercises leader-parallel fetching")
    ap.add_argument("--alive-bits", type=int, default=26)
    ap.add_argument("--superbatch", default="1", metavar="K|auto",
                    help="stack K packed batches per jitted scan dispatch "
                         "(tpu backend; 'auto' targets 2^20 records per "
                         "dispatch)")
    ap.add_argument("--dispatch-depth", type=int, default=2,
                    help="superbatches allowed in flight while the device "
                         "folds (default 2)")
    ap.add_argument("--ingest-workers", default="1", metavar="N|auto",
                    help="partition-sharded parallel ingest workers for "
                         "the scan (engine --ingest-workers; composes "
                         "with --mesh: per-controller fan-in per data row)")
    ap.add_argument("--mesh", default="1", metavar="DATA[,SPACE]",
                    help="device mesh for the sharded backend (tpu only). "
                         "On a CPU-platform bench this forces the needed "
                         "virtual device count when jax is not yet "
                         "imported — the mesh x workers sweep referee")
    ap.add_argument("--flight-record", action="store_true",
                    help="run the pipeline flight recorder during the "
                         "scan and print the doctor's BOTTLENECK verdict "
                         "— the shipped replacement for the manual "
                         "bench-ledger procedure. Also the overhead "
                         "referee: an A/B against a run without this flag "
                         "must stay within 2%% (DESIGN.md §17)")
    ap.add_argument("--service-obs", metavar="DIR",
                    help="run the FULL service-observability stack during "
                         "the scan: flight recorder + the disk-backed "
                         "telemetry history persisted under DIR + "
                         "alert-engine evaluation at heartbeat cadence "
                         "(DESIGN.md §22). The BENCH round 15 overhead "
                         "referee: an A/B against a plain run must stay "
                         "within the same 2%% bar as --flight-record "
                         "alone")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    from kafka_topic_analyzer_tpu.cli import parse_mesh

    mesh_shape = parse_mesh(args.mesh)
    if mesh_shape != (1, 1):
        if args.backend != "tpu":
            ap.error("--mesh requires --backend tpu")
        # Virtual-device bring-up must precede the first jax import; when
        # the bench runner already imported jax this is a no-op and the
        # mesh constructor will reject a too-small device count itself.
        import os as _os

        need = mesh_shape[0] * mesh_shape[1]
        flags = _os.environ.get("XLA_FLAGS", "")
        if (
            "jax" not in sys.modules
            and "xla_force_host_platform_device_count" not in flags
            and _os.environ.get("JAX_PLATFORMS", "") == "cpu"
        ):
            _os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={need}"
            ).strip()

    feats = {f.strip() for f in args.features.split(",") if f.strip()}
    R = args.records_per_batch
    windows = max(1, args.records // (args.partitions * R))
    total = windows * R * args.partitions

    comp = {
        "none": kc.COMPRESSION_NONE,
        "gzip": kc.COMPRESSION_GZIP,
        "snappy": kc.COMPRESSION_SNAPPY,
        "lz4": kc.COMPRESSION_LZ4,
        "zstd": kc.COMPRESSION_ZSTD,
    }[args.compression]

    from kafka_topic_analyzer_tpu.backends.base import make_backend
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig
    from kafka_topic_analyzer_tpu.engine import run_scan
    from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
    from kafka_topic_analyzer_tpu.utils.progress import Spinner

    config = AnalyzerConfig(
        num_partitions=args.partitions,
        batch_size=args.batch_size,
        count_alive_keys="alive" in feats,
        alive_bitmap_bits=args.alive_bits,
        enable_hll="hll" in feats,
        enable_quantiles="quantiles" in feats,
        mesh_shape=mesh_shape,
    )
    # Same validation as the CLI (cli.resolve_dispatch): an explicit
    # --superbatch K>1 on the cpu backend is rejected, never silently
    # dropped — a published bench number must not claim a dispatch
    # configuration that never ran.
    from kafka_topic_analyzer_tpu.cli import (
        resolve_dispatch,
        resolve_ingest_workers,
    )

    try:
        dispatch = resolve_dispatch(args)
        ingest_workers = resolve_ingest_workers(
            args, mesh_shape, args.partitions
        )
    except ValueError as e:
        ap.error(str(e))
    if mesh_shape != (1, 1):
        from kafka_topic_analyzer_tpu.parallel.sharded import (
            ShardedTpuBackend,
        )

        backend = ShardedTpuBackend(config, dispatch=dispatch)
    else:
        backend = make_backend(args.backend, config, dispatch=dispatch)

    with BrokerProcess(
        topic="bench-e2e", partitions=args.partitions, windows=windows,
        R=R, n_templates=args.templates, vmin=args.vmin, vmax=args.vmax,
        compression=comp, tombstone_every=args.tombstone_every,
        brokers=args.brokers,
    ) as port:
        source = KafkaWireSource(f"127.0.0.1:{port}", "bench-e2e")
        recorder = None
        store = None
        if args.flight_record or args.service_obs:
            from kafka_topic_analyzer_tpu.obs import flight as obs_flight

            recorder = obs_flight.FlightRecorder()
            if args.service_obs:
                from kafka_topic_analyzer_tpu.obs import (
                    health as obs_health,
                    history as obs_history,
                )

                store = obs_history.HistoryStore(args.service_obs)
                recorder.attach_history(store)
                obs_history.set_active(store)
                obs_health.set_active(obs_health.HealthEngine())
            obs_flight.set_active(recorder)
            recorder.start()
        try:
            t0 = time.perf_counter()
            result = run_scan(
                "bench-e2e",
                source,
                backend,
                batch_size=args.batch_size,
                spinner=Spinner(enabled=False),
                ingest_workers=ingest_workers,
            )
            if hasattr(backend, "block_until_ready"):
                backend.block_until_ready()
            elapsed = time.perf_counter() - t0
        finally:
            # A failing scan (or the count-mismatch early return below)
            # must not leak a live sampler thread as the process-wide
            # active recorder; the stopped series stays readable.
            if recorder is not None:
                recorder.stop()
                obs_flight.set_active(None)
            if store is not None:
                from kafka_topic_analyzer_tpu.obs import (
                    health as obs_health,
                    history as obs_history,
                )

                store.close()
                obs_history.set_active(None)
                obs_health.set_active(None)
        source.close()

    got = int(result.metrics.overall_count)
    if got != total:
        print(
            f"bench-e2e: scanned {got} records, expected {total}",
            file=sys.stderr,
        )
        return 1
    value = total / elapsed
    diagnosis = None
    if recorder is not None:
        from kafka_topic_analyzer_tpu.obs import doctor

        diagnosis = doctor.diagnose(
            result.telemetry,
            controllers=max(1, len(result.ingest_workers_per_controller)),
            dispatch_depth=result.dispatch_depth,
            flight=recorder.series(),
        )
    if not args.quiet:
        print(
            f"# e2e: {total} records, {args.partitions} partitions, "
            f"{elapsed:.2f}s, backend={args.backend}, "
            f"features={sorted(feats)}, compression={args.compression}",
            file=sys.stderr,
        )
        print(result.profile.summary(), file=sys.stderr)
        if diagnosis is not None:
            from kafka_topic_analyzer_tpu.report import render_bottleneck

            sys.stderr.write(render_bottleneck(diagnosis))
    doc = {
        "metric": "e2e_msgs_per_sec",
        "value": round(value),
        "unit": "msgs/s",
        "vs_baseline": round(value / BASELINE_MSGS_PER_SEC, 2),
        "superbatch_k": result.superbatch_k,
        "dispatch_depth": result.dispatch_depth,
        "ingest_workers": result.ingest_workers,
        "ingest_workers_per_controller": result.ingest_workers_per_controller,
        "mesh": list(mesh_shape),
        "batch_size": args.batch_size,
    }
    if diagnosis is not None:
        doc["flight"] = {
            "verdict": diagnosis.verdict,
            "stages": {
                k: round(v, 4) for k, v in diagnosis.stages.items()
            },
            "window_share": {
                k: round(v, 4)
                for k, v in diagnosis.window_share.items()
            },
        }
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(run())
