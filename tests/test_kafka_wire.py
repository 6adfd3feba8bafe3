"""Kafka wire-protocol client against the in-process fake broker.

Covers: codec roundtrips (varints, record batches, CRC32-C, gzip), the
topology handshake, the full fetch loop with multi-fetch pagination,
compaction gaps, null keys/values, missing timestamps, and end-to-end
metric parity with a direct scan of the same records.
"""

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io import kafka_codec as kc
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource, parse_bootstrap
from kafka_topic_analyzer_tpu.records import RecordBatch

from fake_broker import FakeBroker, FakeCluster


# ---------------------------------------------------------------------------
# codec units


def test_varint_roundtrip():
    w = kc.ByteWriter()
    values = [0, 1, -1, 2, -2, 127, 128, -300, 10**12, -(10**12)]
    for v in values:
        w.varint(v)
    r = kc.ByteReader(w.done())
    assert [r.varint() for _ in values] == values


@pytest.mark.parametrize("compression", [kc.COMPRESSION_NONE, kc.COMPRESSION_GZIP])
def test_record_batch_roundtrip(compression):
    records = [
        (100, 1_600_000_000_000, b"k1", b"v1"),
        (101, 1_600_000_000_123, None, b"v2"),       # null key
        (105, 1_600_000_001_000, b"k3", None),       # tombstone, offset gap
        (106, -1, b"", b""),                          # empty (not null) k/v
    ]
    buf = kc.encode_record_batch(records, compression)
    got = [(off, ts, k, v) for off, (ts, k, v) in kc.decode_record_batches(buf, verify_crc=True)]
    assert got == records


def test_record_batch_crc_detects_corruption():
    buf = bytearray(kc.encode_record_batch([(0, 0, b"k", b"v")]))
    buf[-1] ^= 0xFF
    with pytest.raises(kc.KafkaProtocolError, match="CRC"):
        list(kc.decode_record_batches(bytes(buf), verify_crc=True))


def test_partial_trailing_batch_tolerated():
    full = kc.encode_record_batch([(0, 0, b"k", b"v"), (1, 0, b"k2", b"v2")])
    truncated = full + full[: len(full) // 2]
    assert len(list(kc.decode_record_batches(truncated))) == 2


def test_from_timestamp_scan():
    """Scan from a point in time via the broker's timestamp index."""
    # Partition 0: ts 1.6e12 + i*1000 ms at offsets 0..99.
    rows = [(i, 1_600_000_000_000 + i * 1000, f"k{i}".encode(), bytes(10))
            for i in range(100)]
    with FakeBroker("ts.topic", {0: rows}) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "ts.topic")
        # Cutoff mid-stream: first record with ts >= cutoff is offset 40.
        offs = src.offsets_for_timestamp(1_600_000_000_000 + 39_500)
        assert offs == {0: 40}
        cfg = AnalyzerConfig(num_partitions=1, batch_size=32)
        be = CpuExactBackend(cfg, init_now_s=10**10)
        m = run_scan("ts.topic", src, be, 32, start_at=offs).metrics
        assert m.overall_count == 60  # offsets 40..99
        assert m.earliest_ts_s == (1_600_000_000_000 + 40_000) // 1000
        # Cutoff beyond every record: nothing scanned.
        offs2 = src.offsets_for_timestamp(2_000_000_000_000)
        assert offs2 == {0: 100}  # end watermark
        src.close()


def test_cli_from_timestamp_flags():
    from kafka_topic_analyzer_tpu.cli import parse_timestamp_ms

    assert parse_timestamp_ms("1600000000000") == 1_600_000_000_000
    assert parse_timestamp_ms("2020-09-13T12:26:40") == 1_600_000_000_000
    assert parse_timestamp_ms("2020-09-13T12:26:40+00:00") == 1_600_000_000_000
    with pytest.raises(ValueError, match="from-timestamp"):
        parse_timestamp_ms("not-a-time")


def test_crc32c_native_matches_python():
    import ctypes
    import os

    from kafka_topic_analyzer_tpu.io.kafka_codec import _crc32c_py
    from kafka_topic_analyzer_tpu.io.native import load_library, native_available

    if not native_available():
        pytest.skip("native shim unavailable")  # fallback would self-compare
    lib = load_library()
    for data in (b"", b"a", b"123456789", os.urandom(100_001)):
        native = int(lib.kta_crc32c(data, ctypes.c_int64(len(data))))
        assert native == _crc32c_py(data)
    # Known CRC32-C vector: "123456789" -> 0xE3069283.
    assert _crc32c_py(b"123456789") == 0xE3069283


def test_parse_bootstrap():
    assert parse_bootstrap("a:9092,b") == [("a", 9092), ("b", 9092)]


def test_parse_bootstrap_ipv6():
    # Bracketed with and without port, and bare IPv6 literals (which contain
    # multiple colons and must not be split at the last one).
    assert parse_bootstrap("[::1]:9093") == [("::1", 9093)]
    assert parse_bootstrap("[2001:db8::1]") == [("2001:db8::1", 9092)]
    assert parse_bootstrap("::1") == [("::1", 9092)]
    assert parse_bootstrap("[::1]:9093,plain:9094,2001:db8::2") == [
        ("::1", 9093), ("plain", 9094), ("2001:db8::2", 9092),
    ]


def test_librdkafka_passthrough_knobs(caplog):
    """The broadened --librdkafka surface: socket/fetch knobs map onto the
    client, reference-style properties (src/kafka.rs:24-44) are accepted
    silently, and only truly unknown names warn."""
    import logging

    records = {0: _mk_records(0, 30)}
    with FakeBroker("wire.topic", records) as broker:
        with caplog.at_level(logging.DEBUG, "kafka_topic_analyzer_tpu.io.kafka_wire"):
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", "wire.topic",
                overrides={
                    "socket.timeout.ms": "5000",
                    "socket.connection.setup.timeout.ms": "4000",
                    "fetch.error.backoff.ms": "250",
                    "receive.message.max.bytes": "1000000",
                    "broker.address.family": "v4",
                    "socket.keepalive.enable": "true",
                    "socket.send.buffer.bytes": "65536",
                    "socket.receive.buffer.bytes": "65536",
                    # reference defaults (src/kafka.rs:24-44): no warnings
                    "auto.offset.reset": "earliest",
                    "enable.auto.commit": "false",
                    "enable.partition.eof": "false",
                    "enable.auto.offset.store": "false",
                    "queue.buffering.max.ms": "1000",
                    "group.id": "topic-analyzer--x",
                    # genuinely unknown: one warning
                    "definitely.not.a.property": "1",
                },
            )
        assert src.timeout_s == 5.0
        assert src.error_backoff_ms == 250
        assert src.max_bytes == 1_000_000
        assert src._sock_opts.connect_timeout_s == 4.0
        assert src._sock_opts.keepalive and src._sock_opts.rcvbuf == 65536
        assert sum(len(b) for b in src.batches(64)) == 30  # v4 pin works
        src.close()
    warned = [r.message for r in caplog.records if r.levelno >= logging.WARNING]
    assert any("definitely.not.a.property" in m for m in warned)
    assert not any("queue.buffering" in m or "group.id" in m for m in warned)


def test_invalid_address_family_rejected():
    with pytest.raises(ValueError, match="broker.address.family"):
        KafkaWireSource(
            "127.0.0.1:1", "x", overrides={"broker.address.family": "ipv9"}
        )


# ---------------------------------------------------------------------------
# end-to-end against the fake broker


def _mk_records(partition, n, start=0, key_every=1, tombstone_every=7, ts0=1_600_000_000_000):
    out = []
    for i in range(n):
        off = start + i
        key = f"p{partition}-key-{i % 50}".encode() if i % key_every == 0 else None
        value = None if (key is not None and i % tombstone_every == 3) else bytes(10 + i % 40)
        out.append((off, ts0 + i * 1000, key, value))
    return out


def _scan_via_wire(broker, topic="wire.topic", batch_size=333, overrides=None):
    src = KafkaWireSource(f"127.0.0.1:{broker.port}", topic, overrides=overrides)
    cfg = AnalyzerConfig(
        num_partitions=len(src.partitions()), batch_size=batch_size,
        count_alive_keys=True, alive_bitmap_bits=20,
    )
    be = CpuExactBackend(cfg, init_now_s=10**10)
    result = run_scan(topic, src, be, batch_size)
    src.close()
    return result


def _scan_direct(partition_records, partitions):
    cfg = AnalyzerConfig(
        num_partitions=len(partitions), batch_size=1024,
        count_alive_keys=True, alive_bitmap_bits=20,
    )
    be = CpuExactBackend(cfg, init_now_s=10**10)
    from kafka_topic_analyzer_tpu.io.kafka_wire import records_to_batch

    for pidx, p in enumerate(sorted(partitions)):
        rows = [(pidx, ts, k, v) for (_, ts, k, v) in partition_records[p]]
        if rows:
            be.update(records_to_batch(rows))
    return be.finalize()


def test_wire_scan_matches_direct_scan():
    records = {0: _mk_records(0, 400), 1: _mk_records(1, 250), 2: []}
    with FakeBroker("wire.topic", records, max_records_per_fetch=97) as broker:
        result = _scan_via_wire(broker)
    direct = _scan_direct(records, [0, 1, 2])
    m = result.metrics
    assert np.array_equal(m.per_partition, direct.per_partition)
    assert m.alive_keys == direct.alive_keys
    assert m.overall_count == 650
    assert m.earliest_ts_s == direct.earliest_ts_s
    assert m.latest_ts_s == direct.latest_ts_s
    assert m.smallest_message == direct.smallest_message
    assert m.largest_message == direct.largest_message
    # Pagination actually happened (400 records / 97 per fetch).
    assert broker.fetch_count > 4


def test_wire_scan_gzip():
    records = {0: _mk_records(0, 120)}
    with FakeBroker("wire.topic", records, compression=kc.COMPRESSION_GZIP) as broker:
        result = _scan_via_wire(broker, overrides={"check.crcs": "true"})
    assert result.metrics.overall_count == 120


def test_wire_scan_compaction_gaps():
    # Only every third offset retained; start offset nonzero.
    rows = [r for r in _mk_records(0, 300, start=50) if r[0] % 3 == 0]
    with FakeBroker("wire.topic", {0: rows}) as broker:
        result = _scan_via_wire(broker)
    assert result.metrics.overall_count == len(rows)
    # Watermarks reflect the retained range, like fetch_watermarks.
    assert result.start_offsets == {0: 51}
    assert result.end_offsets == {0: 349}  # last retained offset 348 + 1


def test_wire_missing_timestamps_map_to_epoch():
    rows = [(0, -1, b"k", b"v"), (1, -1, b"k2", b"v2")]
    with FakeBroker("wire.topic", {0: rows}) as broker:
        result = _scan_via_wire(broker)
    assert result.metrics.earliest_ts_s == 0  # unwrap_or(0) semantics


def test_version_negotiation_modern_broker():
    """Default fake broker advertises Metadata up to v5 (Kafka 4.0 floor,
    KIP-896) — the whole default suite runs over negotiated v5.  This test
    pins the negotiation result explicitly."""
    with FakeBroker("wire.topic", {0: _mk_records(0, 20)}) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic")
        conn = src._any_conn()
        assert src._version(conn, kc.API_METADATA) == 5
        assert src._version(conn, kc.API_FETCH) == 4
        assert src.partitions() == [0]
        src.close()


def test_version_negotiation_legacy_and_ancient_brokers():
    records = {0: _mk_records(0, 30)}
    legacy_ranges = {
        kc.API_FETCH: (0, 4), kc.API_LIST_OFFSETS: (0, 1),
        kc.API_METADATA: (0, 1),
    }
    for kwargs in ({"api_ranges": legacy_ranges}, {"no_api_versions": True}):
        with FakeBroker("wire.topic", records, **kwargs) as broker:
            src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic")
            conn = src._any_conn()
            assert src._version(conn, kc.API_METADATA) == 1
            m = _scan_via_wire(broker)
            assert m.metrics.overall_count == 30


def test_version_negotiation_incompatible_broker():
    ranges = {
        # Too new: both our Fetch encodings (v12 flexible, v4 classic)
        # removed by a hypothetical future KIP-896-style floor raise.
        kc.API_FETCH: (13, 17),
        kc.API_LIST_OFFSETS: (0, 9),
        kc.API_METADATA: (0, 13),
    }
    with FakeBroker("wire.topic", {0: _mk_records(0, 5)}, api_ranges=ranges) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic")
        with pytest.raises(kc.KafkaProtocolError, match="Fetch versions"):
            src._version(src._any_conn(), kc.API_FETCH)
        src.close()


def test_metadata_v5_roundtrip():
    md = kc.MetadataResponse(
        {0: ("h", 1), 2: ("i", 3)}, 0,
        [kc.TopicMetadata(0, "t", [kc.PartitionMetadata(0, 7, 2)])],
    )
    for v in (1, 2, 3, 5):
        buf = kc.encode_metadata_response(md, version=v)
        got = kc.decode_metadata_response(kc.ByteReader(buf), version=v)
        assert got.brokers == md.brokers
        assert got.topics[0].partitions[0].partition == 7
        assert got.topics[0].partitions[0].leader == 2


def test_native_and_python_decode_paths_agree():
    """The C++ frame decoder and the Python per-record generator must yield
    byte-identical RecordBatch streams (fields, hashes, offsets) across
    nulls, tombstones, gaps, headers-free records and gzip compression."""
    rows = [r for r in _mk_records(0, 700, start=13) if r[0] % 4 != 1]
    for compression in (kc.COMPRESSION_NONE, kc.COMPRESSION_GZIP):
        with FakeBroker(
            "wire.topic", {0: rows}, compression=compression,
            max_records_per_fetch=123,
        ) as broker:
            batches = {}
            for native in (True, False):
                src = KafkaWireSource(
                    f"127.0.0.1:{broker.port}", "wire.topic",
                    use_native_hashing=native,
                )
                batches[native] = RecordBatch.concat(list(src.batches(97)))
                src.close()
        a, b = batches[True], batches[False]
        assert len(a) == len(b) == len(rows)
        for name, _ in RecordBatch.FIELDS:
            assert np.array_equal(getattr(a, name), getattr(b, name)), name
        assert np.array_equal(a.offsets, b.offsets)


def test_multi_broker_cluster_scan():
    """Partitions led by different nodes: the client must group fetches by
    leader and pull each partition from the right broker."""
    records = {p: _mk_records(p, 150 + 37 * p) for p in range(5)}
    with FakeCluster("wire.topic", records, n_nodes=3, max_records_per_fetch=60) as cluster:
        src = KafkaWireSource(cluster.bootstrap, "wire.topic")
        cfg = AnalyzerConfig(
            num_partitions=5, batch_size=128, count_alive_keys=True,
            alive_bitmap_bits=20,
        )
        be = CpuExactBackend(cfg, init_now_s=10**10)
        result = run_scan("wire.topic", src, be, 128)
        src.close()
        # Every node served fetch traffic (each leads at least one partition).
        assert all(node.fetch_count > 0 for node in cluster.nodes)
    m = result.metrics
    direct = _scan_direct(records, list(records))
    assert np.array_equal(m.per_partition, direct.per_partition)
    assert m.alive_keys == direct.alive_keys
    assert m.overall_count == sum(len(r) for r in records.values())


def test_multi_broker_bootstrap_via_single_node():
    """Bootstrapping from ONE node must still discover and use the others."""
    records = {p: _mk_records(p, 80) for p in range(4)}
    with FakeCluster("wire.topic", records, n_nodes=2) as cluster:
        one = f"127.0.0.1:{cluster.nodes[0].port}"
        src = KafkaWireSource(one, "wire.topic")
        cfg = AnalyzerConfig(num_partitions=4, batch_size=64)
        be = CpuExactBackend(cfg, init_now_s=10**10)
        m = run_scan("wire.topic", src, be, 64).metrics
        src.close()
        assert cluster.nodes[1].fetch_count > 0  # discovered via metadata
    assert m.overall_count == 4 * 80


def test_wire_all_records_beyond_watermark_terminates():
    # Snapshot end=15, but compaction removed 10..14 and retained records
    # continue at 20: the fetch at offset 10 returns a non-empty batch whose
    # offsets are all >= end.  The scan must skip to the watermark and
    # terminate with only the 10 in-window records.
    rows = _mk_records(0, 10) + [
        (20 + i, 1_600_000_100_000 + i, b"late", b"v") for i in range(10)
    ]
    with FakeBroker(
        "wire.topic", {0: rows}, end_offsets={0: 15}
    ) as broker:
        result = _scan_via_wire(broker)
    assert result.metrics.overall_count == 10


def test_wire_compacted_batch_before_truncated_batch_not_skipped():
    """Regression: a fetch response whose first batch retains only records
    BELOW the fetch position (its last_offset_delta covers compacted-away
    offsets) while the next batch is truncated by partition_max_bytes must
    advance to the covered batch end and refetch — not conclude the
    partition is exhausted and skip to the watermark."""
    batch_a = _mk_records(0, 10)                       # offsets 0..9
    batch_b = [(15 + i, 1_600_000_100_000 + i, b"late", bytes(20))
               for i in range(5)]                      # offsets 15..19
    with FakeBroker(
        "wire.topic", {0: batch_a + batch_b},
        max_records_per_fetch=10,  # chunk 1 = batch_a, chunk 2 = batch_b
        honor_partition_max_bytes=True,
        # Batch A's on-disk range covers compacted-away 10..14, so a fetch
        # at offset 10 serves batch A again.
        coverage_overrides={0: {0: 14}},
    ) as broker:
        a_len = len(broker._chunks[0][0][2])
        # First fetch returns A + a truncated sliver of B.
        result = _scan_via_wire(
            broker,
            overrides={"max.partition.fetch.bytes": str(a_len + 10)},
        )
    assert result.metrics.overall_count == 15  # 10 from A, 5 from B


def test_wire_last_retained_batch_before_fetch_position_terminates():
    """The dual of the refetch regression above: when the compacted batch
    preceding the fetch position is the LAST data in the partition, its
    covered end (base + last_offset_delta + 1) reaches the watermark, so
    the scan must terminate — not grow the fetch size forever."""
    batch_a = _mk_records(0, 10)  # offsets 0..9; watermark snapshot says 15
    with FakeBroker(
        "wire.topic", {0: batch_a}, end_offsets={0: 15},
        honor_partition_max_bytes=True,
        coverage_overrides={0: {0: 14}},  # batch covers 10..14 on disk
    ) as broker:
        result = _scan_via_wire(broker)
    assert result.metrics.overall_count == 10


def test_wire_response_budget_starvation_not_mistaken_for_end():
    """KIP-74: when the request-level fetch.max.bytes budget is spent on
    earlier partitions, later ones come back EMPTY despite having data.
    The client must rotate the fetch order and keep going — not conclude
    the starved partitions are compacted away."""
    records = {p: _mk_records(p, 50) for p in range(3)}
    with FakeBroker(
        "wire.topic", records, max_records_per_fetch=10,
        honor_partition_max_bytes=True, honor_max_bytes=True,
    ) as broker:
        one_chunk = len(broker._chunks[0][0][2])
        # Budget fits ~one chunk per response: every round starves two of
        # the three partitions.
        result = _scan_via_wire(
            broker, overrides={"fetch.max.bytes": str(one_chunk + 10)}
        )
    assert result.metrics.overall_count == 150


def test_wire_oversized_batch_grows_fetch_size():
    """A single batch larger than max.partition.fetch.bytes comes back
    truncated (no complete frame): the client must double the limit until
    the batch fits."""
    rows = [(i, 1_600_000_000_000 + i, b"k%d" % i, bytes(200))
            for i in range(20)]
    with FakeBroker(
        "wire.topic", {0: rows}, honor_partition_max_bytes=True,
    ) as broker:
        result = _scan_via_wire(
            broker, overrides={"max.partition.fetch.bytes": "64"}
        )
    assert result.metrics.overall_count == 20


def test_gzip_uses_real_gzip_framing():
    # Kafka's gzip codec is RFC-1952; the encoded payload must carry the
    # gzip magic so real brokers/clients interoperate.
    buf = kc.encode_record_batch([(0, 0, b"k", b"v")], kc.COMPRESSION_GZIP)
    # header: offset(8) + len(4) + epoch(4) + magic(1) + crc(4) + attrs..count(45 total to payload)
    assert b"\x1f\x8b" in buf  # gzip magic somewhere in the batch payload


def test_topic_not_found_exits():
    with FakeBroker("other.topic", {0: []}) as broker:
        with pytest.raises(SystemExit, match="Topic not found!"):
            KafkaWireSource(f"127.0.0.1:{broker.port}", "missing.topic")


def test_empty_topic_is_empty():
    with FakeBroker("wire.topic", {0: [], 1: []}) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic")
        assert src.is_empty()
        src.close()


# ---------------------------------------------------------------------------
# flexible (KIP-482) protocol versions: Metadata v12 / ListOffsets v7 /
# Fetch v12 / ApiVersions v3


def test_flexible_primitives_roundtrip():
    w = kc.ByteWriter()
    for v in (0, 1, 127, 128, 300, 1 << 31):
        w.uvarint(v)
    w.compact_string(None).compact_string("").compact_string("héllo")
    w.compact_bytes(None).compact_bytes(b"").compact_bytes(b"\x00\xff")
    w.compact_array_len(None).compact_array_len(0).compact_array_len(3)
    w.tags()
    r = kc.ByteReader(w.done())
    assert [r.uvarint() for _ in range(6)] == [0, 1, 127, 128, 300, 1 << 31]
    assert [r.compact_string() for _ in range(3)] == [None, "", "héllo"]
    assert [r.compact_bytes() for _ in range(3)] == [None, b"", b"\x00\xff"]
    assert [r.compact_array_len() for _ in range(3)] == [0, 0, 3]
    r.skip_tags()
    assert r.remaining() == 0


def test_skip_tags_skips_unknown_tagged_fields():
    # Forward compatibility: a response carrying tagged fields this client
    # does not know must decode as if they were absent.
    w = kc.ByteWriter()
    w.uvarint(2)  # two tagged fields
    w.uvarint(0).uvarint(3).raw(b"abc")
    w.uvarint(7).uvarint(1).raw(b"z")
    w.i32(42)
    r = kc.ByteReader(w.done())
    r.skip_tags()
    assert r.i32() == 42


@pytest.mark.parametrize("version", [9, 12])
def test_metadata_flexible_roundtrip(version):
    topics = [
        kc.TopicMetadata(
            0, "t", [kc.PartitionMetadata(0, 0, 1), kc.PartitionMetadata(0, 1, 2)]
        )
    ]
    resp = kc.MetadataResponse({1: ("h1", 9092), 2: ("h2", 9093)}, 1, topics)
    out = kc.decode_metadata_response(
        kc.ByteReader(kc.encode_metadata_response(resp, version)), version
    )
    assert out.brokers == resp.brokers
    assert out.controller_id == resp.controller_id
    assert [(t.error, t.name) for t in out.topics] == [(0, "t")]
    assert [(p.partition, p.leader) for p in out.topics[0].partitions] == [
        (0, 1), (1, 2),
    ]
    req = kc.encode_metadata_request(["a", "b"], version)
    assert kc.decode_metadata_request(kc.ByteReader(req), version) == ["a", "b"]


def test_list_offsets_v7_roundtrip():
    req = kc.encode_list_offsets_request("t", [(0, -2), (3, -1)], 7)
    topic, parts = kc.decode_list_offsets_request(kc.ByteReader(req), 7)
    assert (topic, parts) == ("t", [(0, -2), (3, -1)])
    resp = kc.encode_list_offsets_response(
        "t", [(0, 0, -1, 17), (3, 0, -1, 99)], 7
    )
    out = kc.decode_list_offsets_response(kc.ByteReader(resp), 7)
    assert out == {0: (0, 17, -1), 3: (0, 99, -1)}
    resp = kc.encode_list_offsets_response(
        "t", [(0, 0, -1, 17, 4), (3, 0, -1, 99, 7)], 7
    )
    out = kc.decode_list_offsets_response(kc.ByteReader(resp), 7)
    assert out == {0: (0, 17, 4), 3: (0, 99, 7)}


def test_fetch_v12_roundtrip():
    req = kc.encode_fetch_request("t", [(0, 5), (2, 11)], 100, 1, 1 << 20,
                                  1 << 16, 12)
    topic, parts, mw, mb, xb = kc.decode_fetch_request(kc.ByteReader(req), 12)
    assert (topic, mw, mb, xb) == ("t", 100, 1, 1 << 20)
    assert parts == [(0, 5, 1 << 16, -1), (2, 11, 1 << 16, -1)]
    req = kc.encode_fetch_request("t", [(0, 5, 3)], 100, 1, 1 << 20,
                                  1 << 16, 12)
    _t, parts, _mw, _mb, _xb = kc.decode_fetch_request(kc.ByteReader(req), 12)
    assert parts == [(0, 5, 1 << 16, 3)]
    records = kc.encode_record_batch([(5, 1000, b"k", b"v")])
    resp = kc.encode_fetch_response("t", [(0, 0, 6, records)], 12)
    fps = kc.decode_fetch_response(kc.ByteReader(resp), 12)
    assert len(fps) == 1
    assert (fps[0].partition, fps[0].error, fps[0].high_watermark) == (0, 0, 6)
    assert bytes(fps[0].records) == records


def test_api_versions_v3_roundtrip():
    apis = [(1, 4, 12), (3, 1, 12), (18, 0, 3)]
    out = kc.decode_api_versions_response(
        kc.ByteReader(kc.encode_api_versions_response(apis, 3)), 3
    )
    assert out == {1: (4, 12), 3: (1, 12), 18: (0, 3)}
    # The v3 request body is compact strings + tags; decodable as written.
    r = kc.ByteReader(kc.encode_api_versions_request(3))
    assert r.compact_string() == "kafka-topic-analyzer-tpu"
    assert r.compact_string() == "2"
    r.skip_tags()
    assert r.remaining() == 0


def test_version_negotiation_flexible_broker():
    """A broker advertising current ranges drives the client onto the
    flexible versions (and the whole request/response cycle survives the
    tagged headers)."""
    with FakeBroker("wire.topic", {0: _mk_records(0, 20)}, modern=True) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic")
        conn = src._any_conn()
        assert src._version(conn, kc.API_METADATA) == 12
        assert src._version(conn, kc.API_LIST_OFFSETS) == 7
        assert src._version(conn, kc.API_FETCH) == 12
        assert src.partitions() == [0]
        src.close()


def test_wire_scan_flexible_broker_matches_direct():
    records = {0: _mk_records(0, 400), 1: _mk_records(1, 250), 2: []}
    with FakeBroker(
        "wire.topic", records, max_records_per_fetch=97, modern=True
    ) as broker:
        result = _scan_via_wire(broker)
    direct = _scan_direct(records, [0, 1, 2])
    m = result.metrics
    assert np.array_equal(m.per_partition, direct.per_partition)
    assert m.alive_keys == direct.alive_keys
    assert m.overall_count == 650


def test_wire_scan_flexible_broker_compressed_and_paginated():
    rows = [r for r in _mk_records(0, 300, start=50) if r[0] % 3 == 0]
    with FakeBroker(
        "wire.topic", {0: rows}, compression=kc.COMPRESSION_LZ4, modern=True
    ) as broker:
        result = _scan_via_wire(broker, overrides={"check.crcs": "true"})
    assert result.metrics.overall_count == len(rows)
    assert result.end_offsets == {0: 349}


def test_api_versions_downgrade_dance():
    """The client offers ApiVersions v3 first (KIP-511); a classic broker
    rejects it with error 35 in v0 format and the client retries at v0 —
    same negotiation result, no eviction, same connection."""
    with FakeBroker("wire.topic", {0: _mk_records(0, 20)}) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic")
        conn = src._any_conn()
        assert src._version(conn, kc.API_METADATA) == 5  # classic fallback
        assert conn.api_versions  # handshake completed despite the 35
        src.close()


# ---------------------------------------------------------------------------
# receive-ahead and the receive-buffer ring


def _outcomes(name):
    from kafka_topic_analyzer_tpu.obs.registry import default_registry

    doc = default_registry().snapshot().get(name)
    out = {}
    for s in doc["samples"] if doc else []:
        key = s["labels"]["outcome"]
        out[key] = out.get(key, 0.0) + s["value"]
    return out


class _Booked:
    """Deltas of a labelled counter over a ``with`` block, by outcome."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._before = _outcomes(self.name)
        return self

    def __exit__(self, *exc):
        after = _outcomes(self.name)
        self.delta = {k: v - self._before.get(k, 0.0) for k, v in after.items()
                      if v != self._before.get(k, 0.0)}

    def __getitem__(self, outcome):
        return self.delta.get(outcome, 0.0)


def _starved_broker(records, **kwargs):
    """The budget-starvation shape: every response holds one whole batch
    of the partition it leads with, and its siblings come back empty."""
    return FakeBroker(
        "wire.topic", records, max_records_per_fetch=10,
        honor_partition_max_bytes=True, honor_max_bytes=True, **kwargs,
    )


#: Below any batch: a response is the first batch alone (KIP-74).
ONE_BATCH = {"fetch.max.bytes": "1"}
FAST_RETRY = {"retry.backoff.ms": "5", "reconnect.backoff.max.ms": "40"}


def _assert_same_scan(result, records):
    direct = _scan_direct(records, sorted(records))
    m = result.metrics
    assert np.array_equal(m.per_partition, direct.per_partition)
    assert m.alive_keys == direct.alive_keys
    assert m.overall_count == sum(len(r) for r in records.values())


def test_receive_ahead_engages_when_siblings_starve():
    """Responses whose other partitions came back empty still send the
    next fetch ahead, and the receiver takes it off the socket."""
    records = {p: _mk_records(p, 50) for p in range(3)}
    with _starved_broker(records) as broker:
        with _Booked("kta_fetch_ahead_total") as ahead, \
                _Booked("kta_fetch_buffers_total") as bufs:
            result = _scan_via_wire(broker, overrides=ONE_BATCH)
    _assert_same_scan(result, records)
    assert ahead["ahead"] >= 10 and ahead["inline"] >= 1
    assert ahead["discarded"] == 0
    # Two buffers fill the ring; every later response reuses one.
    assert bufs["fresh"] <= 2 and bufs["reused"] >= 10


def _poisoned_chunk(records, chunk):
    """A CorruptionInjector whose damage the header walk cannot see: the
    first record's length of ``chunk`` turns negative."""
    from fake_broker import CorruptionInjector

    clean = FakeBroker("wire.topic", records, max_records_per_fetch=10)
    byte = clean._chunks[0][chunk][2][61]  # the first record's length
    clean.stop()
    return CorruptionInjector().flip_byte(0, chunk=chunk, offset=61,
                                          xor=byte ^ 0x7F)


def test_speculation_miss_on_a_corrupt_frame_is_discarded():
    """Without check.crcs a record-body corruption passes the header walk,
    so the next fetch goes ahead; processing stops at the frame for its
    re-fetch, the speculation is drained, and the scan is the clean one
    minus the poisoned frame."""
    from kafka_topic_analyzer_tpu.config import CorruptionConfig

    records = {0: _mk_records(0, 60)}
    with FakeBroker(
        "wire.topic", records, max_records_per_fetch=10,
        corruption=_poisoned_chunk(records, 2),
    ) as broker:
        src = KafkaWireSource(
            f"127.0.0.1:{broker.port}", "wire.topic", overrides=FAST_RETRY,
            corruption=CorruptionConfig(policy="skip"),
        )
        cfg = AnalyzerConfig(num_partitions=1, batch_size=64,
                             count_alive_keys=True, alive_bitmap_bits=20)
        with _Booked("kta_fetch_ahead_total") as ahead:
            result = run_scan("wire.topic", src,
                              CpuExactBackend(cfg, init_now_s=10**10), 64)
        src.close()
    assert ahead["discarded"] >= 1 and ahead["ahead"] >= 1
    assert result.corrupt_partitions[0]["frames"] == 1
    _assert_same_scan(result, {0: records[0][:20] + records[0][30:]})


def test_compressed_tail_is_read_inline():
    """A gzip frame ends the native prefix, so its response is not clean:
    no fetch goes ahead of it, the next one is read on the stream, and the
    scan is unchanged."""
    rows = _mk_records(0, 80)
    with FakeBroker("wire.topic", {0: rows[:50]},
                    max_records_per_fetch=10) as broker:
        broker.compression = kc.COMPRESSION_GZIP
        for i in range(50, 80, 10):
            broker.produce(0, rows[i:i + 10])
        with _Booked("kta_fetch_ahead_total") as ahead:
            result = _scan_via_wire(broker)
    _assert_same_scan(result, {0: rows})
    assert ahead["ahead"] >= 4
    assert ahead["inline"] >= 3  # the first, and after each gzip response
    assert ahead["discarded"] == 0


def test_connection_cut_mid_receive_is_a_transport_failure():
    """The broker cuts the response the receiver is reading: the failure
    comes back through the receive's future, is booked like any transport
    failure, and the reconnected scan equals the fault-free one."""
    from fake_broker import ChaosTrigger, FaultInjector

    from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics

    records = {0: _mk_records(0, 200)}
    with FakeBroker(
        "wire.topic", records, max_records_per_fetch=20,
        # Every fetch answer waits, so the cut lands on one the receiver
        # is already waiting for.
        response_delay=lambda api, _node: 0.02 if api == kc.API_FETCH else 0,
    ) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", "wire.topic",
                              overrides=FAST_RETRY)

        def cut():
            broker.faults = FaultInjector().drop_connection(after_bytes=40)

        cfg = AnalyzerConfig(num_partitions=1, batch_size=16,
                             count_alive_keys=True, alive_bitmap_bits=20)
        failures = obs_metrics.TRANSPORT_FAILURES.value
        with _Booked("kta_fetch_ahead_total") as ahead:
            result = run_scan("wire.topic", ChaosTrigger(src, 3, cut),
                              CpuExactBackend(cfg, init_now_s=10**10), 16)
        src.close()
        assert broker.faults.exhausted()
    assert obs_metrics.TRANSPORT_FAILURES.value - failures == 1
    assert ahead["ahead"] >= 3
    assert not result.degraded_partitions
    _assert_same_scan(result, records)


def _ring_conn(broker, **ring):
    from kafka_topic_analyzer_tpu.io.kafka_wire import (
        BrokerConnection,
        RecvBufferRing,
    )

    conn = BrokerConnection("127.0.0.1", broker.port)
    conn.ring = RecvBufferRing(**ring)
    return conn


def _fetch(conn, parts):
    body = kc.encode_fetch_request("wire.topic", parts, 100, 1, 1 << 20,
                                   1 << 20, 4)
    return kc.decode_fetch_response(conn.request(kc.API_FETCH, 4, body), 4)


def test_ring_reuses_a_buffer_nothing_holds():
    with FakeBroker("wire.topic", {0: _mk_records(0, 40)},
                    max_records_per_fetch=10) as broker:
        conn = _ring_conn(broker, slots=1, min_bytes=1 << 16)
        with _Booked("kta_fetch_buffers_total") as bufs:
            fps = _fetch(conn, [(0, 0)])
            first = bytes(fps[0].records)
            del fps
            fps = _fetch(conn, [(0, 10)])
            assert bytes(fps[0].records) != first
        conn.close()
    assert bufs.delta == {"fresh": 1.0, "reused": 1.0}


def test_ring_never_overwrites_a_held_view():
    """A view kept into the previous response keeps its buffer: the next
    receive lands in a fresh one, and the view's bytes stay as they were."""
    with FakeBroker("wire.topic", {0: _mk_records(0, 40)},
                    max_records_per_fetch=10) as broker:
        conn = _ring_conn(broker, slots=1, min_bytes=1 << 16)
        with _Booked("kta_fetch_buffers_total") as bufs:
            held = _fetch(conn, [(0, 0)])[0].records
            before = bytes(held)
            later = _fetch(conn, [(0, 10)])[0].records
            assert bytes(held) == before and bytes(later) != before
        conn.close()
    assert bufs.delta == {"fresh": 2.0}


def test_ring_grows_for_a_larger_response():
    records = {p: _mk_records(p, 20) for p in range(2)}
    with FakeBroker("wire.topic", records, max_records_per_fetch=10) as broker:
        conn = _ring_conn(broker, slots=1)  # buffers sized to a response
        with _Booked("kta_fetch_buffers_total") as bufs:
            one = _fetch(conn, [(0, 0)])
            del one
            both = _fetch(conn, [(0, 0), (1, 0)])  # two batches: larger
            assert [len(fp.records) > 0 for fp in both] == [True, True]
            del both
            _fetch(conn, [(1, 10)])  # fits the grown buffer
        conn.close()
    assert bufs.delta == {"fresh": 2.0, "reused": 1.0}
