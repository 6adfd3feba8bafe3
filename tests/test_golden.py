"""Golden-bytes conformance tier.

Every Kafka wire frame in this file is authored BYTE BY BYTE from the
public protocol specification (kafka.apache.org/protocol + KIP-98 record
batch layout, RFC 1952 gzip, the Snappy format description, the LZ4 frame
format spec, RFC 8878 zstd) using this file's OWN primitive writers —
``kafka_codec``'s encoders are never called to produce test inputs, so the
decode paths are checked against bytes that do not share authorship with
the codec under test.  Compressed variants use stdlib zlib/gzip (an
independent implementation) and hand-laid-out snappy/LZ4/zstd store-mode
streams.

Tiers:
1. Primitive cross-checks: in-file CRC32-C (Castagnoli) and xxHash32
   against published test vectors, then against the codec's CRC.
2. Decoder-level golden bodies: RecordBatch v2 (plain + each codec),
   Metadata v1/v12, ListOffsets v1/v7, Fetch v4/v12, ApiVersions v0/v3.
3. A golden BROKER: a socket server replaying only canned hand-authored
   responses (including the KIP-511 ApiVersions downgrade dance) drives
   the full client + CLI end to end.

Reference behaviors exercised: watermark-snapshot termination
(src/kafka.rs:60-72,119-121), per-message metric semantics
(src/metric.rs:207-252), alive-key tracking (src/metric.rs:288-305).
"""

import gzip
import socket
import struct
import threading

import pytest

from kafka_topic_analyzer_tpu.io import kafka_codec as kc

# ---------------------------------------------------------------------------
# Primitive writers (big-endian, per the Kafka protocol "Protocol Primitive
# Types" table).  Deliberately minimal and local to this file.


def i8(v):
    return struct.pack(">b", v)


def i16(v):
    return struct.pack(">h", v)


def i32(v):
    return struct.pack(">i", v)


def i64(v):
    return struct.pack(">q", v)


def u32(v):
    return struct.pack(">I", v)


def uvarint(v):
    """Unsigned LEB128 (Kafka UNSIGNED_VARINT)."""
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def zigzag(v):
    """Kafka VARINT/VARLONG: zigzag then LEB128."""
    return uvarint((v << 1) ^ (v >> 63))


def string(s):
    """Classic STRING: i16 length (-1 = null) + utf8."""
    if s is None:
        return i16(-1)
    b = s.encode()
    return i16(len(b)) + b


def compact_string(s):
    """Flexible COMPACT_STRING: uvarint(len+1), 0 = null."""
    if s is None:
        return uvarint(0)
    b = s.encode()
    return uvarint(len(b) + 1) + b


def carr(n):
    """COMPACT_ARRAY length prefix: uvarint(n+1)."""
    return uvarint(n + 1)


def tags():
    """Empty tagged-field section."""
    return uvarint(0)


# ---------------------------------------------------------------------------
# CRC32-C (Castagnoli): reflected polynomial 0x82F63B78, init/final
# xor 0xFFFFFFFF — written from the definition, table-driven.

_CRC32C_TABLE = []
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (_c >> 1) ^ 0x82F63B78 if _c & 1 else _c >> 1
    _CRC32C_TABLE.append(_c)


def crc32c(data):
    c = 0xFFFFFFFF
    for b in bytes(data):
        c = (c >> 8) ^ _CRC32C_TABLE[(c ^ b) & 0xFF]
    return c ^ 0xFFFFFFFF


# xxHash32 (for the LZ4 frame header checksum), from the published spec.

_XXP1, _XXP2, _XXP3, _XXP4, _XXP5 = (
    2654435761, 2246822519, 3266489917, 668265263, 374761393,
)
_M = 0xFFFFFFFF


def _rotl(x, r):
    return ((x << r) | (x >> (32 - r))) & _M


def xxh32(data, seed=0):
    data = bytes(data)
    n = len(data)
    if n >= 16:
        v1 = (seed + _XXP1 + _XXP2) & _M
        v2 = (seed + _XXP2) & _M
        v3 = seed
        v4 = (seed - _XXP1) & _M
        i = 0
        while i <= n - 16:
            for vi in range(4):
                (lane,) = struct.unpack_from("<I", data, i + 4 * vi)
                v = (v1, v2, v3, v4)[vi]
                v = (v + lane * _XXP2) & _M
                v = (_rotl(v, 13) * _XXP1) & _M
                if vi == 0:
                    v1 = v
                elif vi == 1:
                    v2 = v
                elif vi == 2:
                    v3 = v
                else:
                    v4 = v
            i += 16
        h = (_rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12) + _rotl(v4, 18)) & _M
    else:
        h = (seed + _XXP5) & _M
        i = 0
    h = (h + n) & _M
    while i <= n - 4:
        (lane,) = struct.unpack_from("<I", data, i)
        h = (h + lane * _XXP3) & _M
        h = (_rotl(h, 17) * _XXP4) & _M
        i += 4
    while i < n:
        h = (h + data[i] * _XXP5) & _M
        h = (_rotl(h, 11) * _XXP1) & _M
        i += 1
    h ^= h >> 15
    h = (h * _XXP2) & _M
    h ^= h >> 13
    h = (h * _XXP3) & _M
    h ^= h >> 16
    return h


# ---------------------------------------------------------------------------
# Independent store-mode compressors (each from its format spec).


def snappy_raw(data):
    """Snappy block format, literal elements only: uvarint uncompressed
    length preamble, then 00-tag literals of at most 60 bytes."""
    out = bytearray(uvarint(len(data)))
    for i in range(0, len(data), 60):
        chunk = data[i : i + 60]
        out.append((len(chunk) - 1) << 2)  # tag 00 = literal
        out += chunk
    return bytes(out)


def snappy_xerial(data):
    """xerial framing: magic, version 1, compat 1, then i32-length-prefixed
    raw-snappy blocks (the Kafka java client's snappy container)."""
    block = snappy_raw(data)
    return b"\x82SNAPPY\x00" + i32(1) + i32(1) + i32(len(block)) + block


def lz4_frame(data):
    """LZ4 Frame: magic, FLG (version 01, block-independent, no checksums,
    no content size), BD (64 KB max block), header checksum =
    (xxh32(FLG+BD) >> 8) & 0xFF, one compressed block holding a single
    literal-only sequence (spec: the last sequence is literals only), then
    the 0 EndMark."""
    flg, bd = 0x60, 0x40
    hc = (xxh32(bytes([flg, bd])) >> 8) & 0xFF
    n = len(data)
    token = min(n, 15) << 4
    ext = bytearray()
    if n >= 15:
        rem = n - 15
        while rem >= 255:
            ext.append(255)
            rem -= 255
        ext.append(rem)
    block = bytes([token]) + bytes(ext) + data
    assert len(block) < (1 << 31)
    return (
        struct.pack("<I", 0x184D2204)
        + bytes([flg, bd, hc])
        + struct.pack("<I", len(block))
        + block
        + struct.pack("<I", 0)  # EndMark
    )


def zstd_frame_raw(data):
    """RFC 8878 zstd frame: magic, single-segment frame header with a
    1-byte frame content size, one Raw (store) block marked last."""
    assert len(data) <= 255, "1-byte FCS golden frame"
    fhd = 0x20  # single_segment=1, FCS code 0 -> 1-byte FCS
    block_header = struct.pack("<I", (len(data) << 3) | (0 << 1) | 1)[:3]
    return (
        struct.pack("<I", 0xFD2FB528)
        + bytes([fhd, len(data)])
        + block_header
        + data
    )


# ---------------------------------------------------------------------------
# The golden topic: 3 records at offsets 0..2 (KIP-98 RecordBatch v2).

T0_MS = 1_600_000_000_000  # 2020-09-13T12:26:40Z
GOLDEN_RECORDS = [
    (0, T0_MS + 0, b"alpha", b"v-zero"),
    (1, T0_MS + 1, b"beta", None),  # tombstone
    (2, T0_MS + 2, None, b"anonymous"),  # unkeyed
]


def encode_record(offset_delta, ts_delta, key, value):
    body = bytearray()
    body += i8(0)  # record attributes
    body += zigzag(ts_delta)
    body += zigzag(offset_delta)
    if key is None:
        body += zigzag(-1)
    else:
        body += zigzag(len(key)) + key
    if value is None:
        body += zigzag(-1)
    else:
        body += zigzag(len(value)) + value
    body += zigzag(0)  # headers
    return zigzag(len(body)) + bytes(body)


def golden_records_section():
    out = bytearray()
    for off, ts, k, v in GOLDEN_RECORDS:
        out += encode_record(off, ts - T0_MS, k, v)
    return bytes(out)


def golden_batch(codec=0):
    """One RecordBatch v2 frame: 61-byte header + records section
    (compressed per ``codec``).  The CRC (CRC32-C) covers attributes
    through the end and EXCLUDES base_offset/batch_length/
    partition_leader_epoch/magic/crc."""
    section = golden_records_section()
    if codec == kc.COMPRESSION_GZIP:
        section = gzip.compress(section)
    elif codec == kc.COMPRESSION_SNAPPY:
        section = snappy_raw(section)
    elif codec == kc.COMPRESSION_LZ4:
        section = lz4_frame(section)
    elif codec == kc.COMPRESSION_ZSTD:
        section = zstd_frame_raw(section)
    crc_part = (
        i16(codec)          # attributes: low 3 bits = codec
        + i32(2)            # last_offset_delta
        + i64(T0_MS)        # first_timestamp
        + i64(T0_MS + 2)    # max_timestamp
        + i64(-1)           # producer_id
        + i16(-1)           # producer_epoch
        + i32(-1)           # base_sequence
        + i32(3)            # record count
        + section
    )
    after_length = i32(0) + i8(2) + u32(crc32c(crc_part)) + crc_part
    #              ^partition_leader_epoch  ^magic=2
    return i64(0) + i32(len(after_length)) + after_length


# ---------------------------------------------------------------------------
# Tier 1: primitive cross-checks.


def test_crc32c_known_vectors_and_codec_parity():
    # Published CRC-32C check value ("123456789" -> 0xE3069283).
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0
    # iSCSI CRC32C test vector: 32 bytes of zeros -> 0x8A9136AA (RFC 3720).
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    for payload in (b"", b"a", b"hello kafka", bytes(range(256)) * 3):
        assert kc._crc32c(payload) == crc32c(payload)


def test_xxh32_known_vectors():
    # Published xxHash32 sanity vectors.
    assert xxh32(b"") == 0x02CC5D05
    assert xxh32(b"Hello World") == 0xB1FD16EE


# ---------------------------------------------------------------------------
# Tier 2: decoder-level golden bodies.


def _expect_records(frame_iter):
    got = []
    for frame in frame_iter:
        for off, (ts_ms, key, value) in kc.decode_frame_records(frame):
            got.append((off, ts_ms, key, value))
    assert got == GOLDEN_RECORDS


def test_golden_record_batch_plain_python_decode():
    buf = golden_batch()
    _expect_records(kc.iter_batch_frames(buf, verify_crc=True))


def test_golden_record_batch_native_decode():
    from kafka_topic_analyzer_tpu.io.native import (
        decode_record_set_native,
        native_available,
        scan_record_set_native,
    )

    if not native_available():
        pytest.skip("native shim unavailable")
    buf = golden_batch()
    n, used, covered = scan_record_set_native(buf, verify_crc=True)
    assert (n, used, covered) == (3, len(buf), 3)
    soa, used, covered = decode_record_set_native(buf, verify_crc=True)
    assert used == len(buf) and covered == 3
    assert list(soa["offsets"]) == [0, 1, 2]
    assert list(soa["ts_ms"]) == [T0_MS, T0_MS + 1, T0_MS + 2]
    assert list(soa["key_len"]) == [5, 4, 0]
    assert list(soa["value_len"]) == [6, 0, 9]
    assert list(soa["key_null"]) == [0, 0, 1]
    assert list(soa["value_null"]) == [0, 1, 0]


@pytest.mark.parametrize(
    "codec",
    [
        kc.COMPRESSION_GZIP,
        kc.COMPRESSION_SNAPPY,
        kc.COMPRESSION_LZ4,
        kc.COMPRESSION_ZSTD,
    ],
)
def test_golden_record_batch_compressed(codec):
    buf = golden_batch(codec)
    _expect_records(kc.iter_batch_frames(buf, verify_crc=True))


def test_golden_snappy_xerial_framing():
    """The Kafka java client wraps snappy in xerial framing; decoders must
    accept both.  Exercised at the decompressor level (batch attributes
    carry only 'snappy', the framing is sniffed)."""
    from kafka_topic_analyzer_tpu.io.compression import snappy_decompress

    section = golden_records_section()
    assert snappy_decompress(snappy_xerial(section)) == section
    assert snappy_decompress(snappy_raw(section)) == section


GOLDEN_TOPIC = "golden.topic"


def metadata_v1_body(port, host="127.0.0.1"):
    return (
        i32(1)  # brokers
        + i32(1) + string(host) + i32(port) + string(None)  # rack null
        + i32(1)  # controller_id
        + i32(1)  # topics
        + i16(0) + string(GOLDEN_TOPIC) + i8(0)  # error, name, is_internal
        + i32(1)  # partitions
        + i16(0) + i32(0) + i32(1)  # error, partition 0, leader 1
        + i32(1) + i32(1)  # replicas [1]
        + i32(1) + i32(1)  # isr [1]
    )


def metadata_v12_body(port, host="127.0.0.1"):
    return (
        i32(0)  # throttle
        + carr(1)
        + i32(1) + compact_string(host) + i32(port)
        + compact_string(None) + tags()  # rack
        + compact_string(None)  # cluster_id
        + i32(1)  # controller_id
        + carr(1)
        + i16(0) + compact_string(GOLDEN_TOPIC)
        + b"\x00" * 16  # topic_id (v10+)
        + i8(0)  # is_internal
        + carr(1)
        + i16(0) + i32(0) + i32(1)  # error, partition, leader
        + i32(0)  # leader_epoch
        + carr(1) + i32(1)  # replicas
        + carr(1) + i32(1)  # isr
        + carr(0)  # offline_replicas
        + tags()
        + i32(-2147483648)  # topic_authorized_operations (v8+)
        + tags()
        + tags()
    )


def list_offsets_v1_body(offset):
    return (
        i32(1) + string(GOLDEN_TOPIC)
        + i32(1)
        + i32(0) + i16(0) + i64(-1) + i64(offset)  # pid, err, ts, offset
    )


def list_offsets_v7_body(offset):
    return (
        i32(0)  # throttle
        + carr(1) + compact_string(GOLDEN_TOPIC)
        + carr(1)
        + i32(0) + i16(0) + i64(-1) + i64(offset) + i32(0)  # +leader_epoch
        + tags() + tags() + tags()
    )


def fetch_v4_body(records):
    return (
        i32(0)  # throttle
        + i32(1) + string(GOLDEN_TOPIC)
        + i32(1)
        + i32(0) + i16(0)  # partition 0, error
        + i64(3)  # high watermark
        + i64(3)  # last_stable_offset
        + i32(0)  # aborted_transactions: empty
        + i32(len(records)) + records
    )


def fetch_v12_body(records):
    return (
        i32(0)  # throttle
        + i16(0)  # top-level error
        + i32(0)  # session_id
        + carr(1) + compact_string(GOLDEN_TOPIC)
        + carr(1)
        + i32(0) + i16(0)  # partition 0, error
        + i64(3) + i64(3) + i64(0)  # hw, last_stable, log_start
        + carr(0)  # aborted
        + i32(-1)  # preferred_read_replica
        + uvarint(len(records) + 1) + records  # COMPACT_BYTES
        + tags() + tags() + tags()
    )


APIS_V0 = [(kc.API_FETCH, 0, 4), (kc.API_LIST_OFFSETS, 0, 1),
           (kc.API_METADATA, 0, 1), (kc.API_VERSIONS, 0, 0)]


def api_versions_v0_body(error=0):
    out = i16(error) + i32(len(APIS_V0))
    for key, lo, hi in APIS_V0:
        out += i16(key) + i16(lo) + i16(hi)
    return out


def api_versions_v3_body():
    out = i16(0) + carr(len(APIS_V0))
    for key, lo, hi in APIS_V0:
        out += i16(key) + i16(lo) + i16(hi) + tags()
    return out + i32(0) + tags()


def test_golden_metadata_bodies_decode():
    for version, body in ((1, metadata_v1_body(9092)),
                          (12, metadata_v12_body(9092))):
        md = kc.decode_metadata_response(kc.ByteReader(body), version)
        assert md.brokers == {1: ("127.0.0.1", 9092)}
        assert md.controller_id == 1
        assert len(md.topics) == 1
        t = md.topics[0]
        assert (t.error, t.name) == (0, GOLDEN_TOPIC)
        assert [(p.error, p.partition, p.leader) for p in t.partitions] == [
            (0, 0, 1)
        ]


def test_golden_list_offsets_bodies_decode():
    assert kc.decode_list_offsets_response(
        kc.ByteReader(list_offsets_v1_body(3)), 1
    ) == {0: (0, 3, -1)}
    assert kc.decode_list_offsets_response(
        kc.ByteReader(list_offsets_v7_body(3)), 7
    ) == {0: (0, 3, 0)}


def test_golden_fetch_bodies_decode():
    records = golden_batch()
    for version, body in ((4, fetch_v4_body(records)),
                          (12, fetch_v12_body(records))):
        fps = kc.decode_fetch_response(kc.ByteReader(body), version)
        assert len(fps) == 1
        fp = fps[0]
        assert (fp.partition, fp.error, fp.high_watermark) == (0, 0, 3)
        assert bytes(fp.records) == records
        _expect_records(kc.iter_batch_frames(bytes(fp.records),
                                             verify_crc=True))


def test_golden_api_versions_bodies_decode():
    ranges = kc.decode_api_versions_response(
        kc.ByteReader(api_versions_v0_body()), 0
    )
    assert ranges[kc.API_FETCH] == (0, 4)
    assert ranges[kc.API_METADATA] == (0, 1)
    ranges3 = kc.decode_api_versions_response(
        kc.ByteReader(api_versions_v3_body()), 3
    )
    assert ranges3 == ranges
    with pytest.raises(kc.UnsupportedVersionError):
        kc.decode_api_versions_response(
            kc.ByteReader(api_versions_v0_body(error=35)), 3
        )


# ---------------------------------------------------------------------------
# Tier 3: the golden broker — canned hand-authored responses only.


class GoldenBroker:
    """Replays canned golden responses over real TCP.  Request handling
    reads only the universal header prefix (api_key, api_version,
    correlation_id — identical at every header version) and, for
    ListOffsets v1, the trailing (partition, timestamp) fields; request
    bodies are otherwise ignored.  Responses are the hand-authored bodies
    above behind a correlation-id echo — no kafka_codec encoder runs."""

    def __init__(self, codec=0):
        self.codec = codec
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = threading.Event()
        self._threads = []
        self._accept_thread = threading.Thread(target=self._accept,
                                               daemon=True)
        self._accept_thread.start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _serve(self, conn):
        try:
            while True:
                head = self._recv_exact(conn, 4)
                if head is None:
                    return
                (size,) = struct.unpack(">i", head)
                frame = self._recv_exact(conn, size)
                if frame is None:
                    return
                api_key, api_version, corr = struct.unpack(">hhi", frame[:8])
                body = self._respond(api_key, api_version, frame)
                conn.sendall(i32(len(body) + 4) + i32(corr) + body)
        except OSError:
            pass
        finally:
            conn.close()

    def _respond(self, api_key, api_version, frame):
        if api_key == kc.API_VERSIONS:
            if api_version >= 3:
                # KIP-511: a broker that does not speak v3 answers
                # UNSUPPORTED_VERSION in the v0 body format.
                return api_versions_v0_body(error=35)
            return api_versions_v0_body()
        if api_key == kc.API_METADATA:
            assert api_version == 1, f"unexpected Metadata v{api_version}"
            return metadata_v1_body(self.port)
        if api_key == kc.API_LIST_OFFSETS:
            assert api_version == 1
            (ts,) = struct.unpack(">q", frame[-8:])
            return list_offsets_v1_body(0 if ts == -2 else 3)
        if api_key == kc.API_FETCH:
            assert api_version == 4, f"unexpected Fetch v{api_version}"
            return fetch_v4_body(golden_batch(self.codec))
        raise AssertionError(f"golden broker got api_key {api_key}")

    @staticmethod
    def _recv_exact(conn, n):
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self.sock.close()


def _scan_golden_topic(capsys, codec=0, extra=()):
    from kafka_topic_analyzer_tpu.cli import main

    with GoldenBroker(codec) as broker:
        rc = main([
            "-t", GOLDEN_TOPIC,
            "-b", f"127.0.0.1:{broker.port}",
            "--librdkafka", "check.crcs=true",
            "-c", "--alive-bitmap-bits", "20",
            "--quiet",
        ] + list(extra))
    assert rc == 0
    return capsys.readouterr().out


def _assert_golden_report(out):
    # src/metric.rs semantics on the golden records: 3 total, 2 alive
    # (non-null values), 1 tombstone, 1 null key; sizes K=9 V=15;
    # averages divide by alive (=2); min/max message size exclude the
    # tombstone (r0=11, r2=9); alive keys: alpha in, beta tombstoned,
    # unkeyed ignored -> 1.
    assert f"Topic {GOLDEN_TOPIC}" in out
    assert "Topic Size: 24" in out
    assert "Largest Message: 11" in out
    assert "Smallest Message: 9" in out
    assert "Alive keys: 1" in out
    # 2020-09-13T12:26:40Z at second granularity, both ts in one second.
    assert "Earliest Message: 2020-09-13 12:26:40" in out
    assert "Latest Message: 2020-09-13 12:26:40" in out
    row = next(l for l in out.splitlines() if l.startswith("| 0 |"))
    cells = [c.strip() for c in row.strip("|").split("|")]
    # P, <OS, >OS, Total, Alive, Tmb, DR, K Null, K !Null, P-Bytes,
    # K-Bytes, V-Bytes, A K-Sz, A V-Sz, A M-Sz  (src/main.rs:150)
    assert cells == ["0", "0", "3", "3", "2", "1", "33.3333", "1", "2",
                     "24", "9", "15", "4", "7", "12"]


def test_golden_broker_end_to_end_cpu(capsys):
    _assert_golden_report(_scan_golden_topic(capsys, extra=["--backend", "cpu"]))


def test_golden_broker_end_to_end_tpu(capsys):
    _assert_golden_report(_scan_golden_topic(capsys, extra=["--backend", "tpu"]))


@pytest.mark.parametrize(
    "codec",
    [
        kc.COMPRESSION_GZIP,
        kc.COMPRESSION_SNAPPY,
        kc.COMPRESSION_LZ4,
        kc.COMPRESSION_ZSTD,
    ],
)
def test_golden_broker_compressed_end_to_end(capsys, codec):
    _assert_golden_report(_scan_golden_topic(capsys, codec))
