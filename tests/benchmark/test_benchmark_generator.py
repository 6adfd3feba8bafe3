"""The benchmark's generator: seeded, the same work for every seed, and
valid Kafka v2 record batches, zstd-compressed as Kafka's Java producer
writes them where the configuration asks."""

import hashlib
import json
import os
import struct
import sys

import numpy as np
import pytest

from benchmark import broker, generator
from benchkit import REPO, TINY_CONFIG

OMB_LIKE = dict(TINY_CONFIG, key_bytes=0, key_ids=0, partitioner="sticky",
                tombstone_share=0, message_bytes_min=1024,
                message_bytes_mean=1024, message_bytes_max=1024,
                records_per_batch=50, produce_rate_per_s=50000)
OMB_ZSTD = dict(OMB_LIKE, compression="zstd")
KEYED_ZSTD = dict(TINY_CONFIG, compression="zstd")
CASES = {"keyed": TINY_CONFIG, "keyless": OMB_LIKE,
         "keyless-zstd": OMB_ZSTD, "keyed-zstd": KEYED_ZSTD}
#: SHA-256 over the partitions' encoded logs, in partition order, of each
#: configuration with ``records`` cut to 2^15 and every other key kept, at
#: seed 2^31 + 29: taken with ``generator.generate`` and
#: ``generator.encode(topic, records_per_batch, broker.crc32c_native)`` at
#: commit ad4de52, before configurations could name a codec.  The mesh
#: configuration audits the same topic.
PARENT_DIGESTS = {
    "changelog-ref":
        "b1f4b2e3c0f1b3a39c63c1f4372d3d4097cf5dc53c111aa55270bb83f4a3113b",
    "omb-1topic-16p-1kb":
        "4acc0bb4727a96ab0e2804c3e1a41b0ecae5191ee1cc76a1ba75958356a86ec8",
    "changelog-ref-mesh4":
        "b1f4b2e3c0f1b3a39c63c1f4372d3d4097cf5dc53c111aa55270bb83f4a3113b",
}


def _fields(t):
    return (t.partition, t.key_id, t.value_len, t.ts_ms)


@pytest.mark.parametrize("cfg", [TINY_CONFIG, OMB_LIKE, OMB_ZSTD],
                         ids=["keyed", "keyless", "keyless-zstd"])
def test_same_seed_same_topic_and_seeds_differ(cfg):
    _needs_codec(cfg)
    (a, la), (b, lb), (c, lc) = (_encode(cfg, s) for s in (7, 7, 2**31 + 5))
    for x, y in zip(_fields(a), _fields(b)):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x.buf, y.buf)
    if cfg["key_bytes"]:
        assert not np.array_equal(a.key_id, c.key_id)
        assert not np.array_equal(a.partition, c.partition)
    else:
        # fixed-size keyless records: nothing is left for a seed to place
        assert all(np.array_equal(x, y)
                   for x, y in zip(_fields(a), _fields(c)))
        # but the seed draws a compressed topic's payloads
        assert any(not np.array_equal(x.buf, z.buf) for x, z in
                   zip(la, lc)) == (cfg.get("compression") == "zstd")


def test_every_seed_gets_the_same_work():
    a = generator.generate(TINY_CONFIG, 1)
    b = generator.generate(TINY_CONFIG, 2**33 + 1)
    ka = np.sort(np.unique(a.key_id, return_counts=True)[1])
    kb = np.sort(np.unique(b.key_id, return_counts=True)[1])
    np.testing.assert_array_equal(ka, kb)
    parts = TINY_CONFIG["partitions"]
    for t in (a, b):
        assert np.all(np.diff(t.partition) >= 0)  # partition-major
        sized = t.value_len[t.value_len >= 0] + TINY_CONFIG["key_bytes"]
        assert sized.min() >= TINY_CONFIG["message_bytes_min"]
        assert sized.max() <= TINY_CONFIG["message_bytes_max"]
        assert abs(sized.mean() - TINY_CONFIG["message_bytes_mean"]) < 3
        assert abs((t.value_len < 0).sum() - 0.1 * t.n) <= parts
    assert abs(int(a.value_len.clip(0).sum()) - int(b.value_len.clip(0).sum())
               ) < 1e-3 * a.value_len.clip(0).sum()


def test_murmur2_matches_kafka():
    # Kafka's Utils.murmur2 on b"21" (the partitioner's own unit test
    # vector, Utils.murmur2 == -973932308).
    got = generator.murmur2_partition(
        np.frombuffer(b"21", np.uint8)[None, :], 2**31 - 1)
    assert int(got[0]) == (-973932308 & 0x7FFFFFFF) % (2**31 - 1)


def _needs_codec(cfg):
    """Skip a compressed case where the benchmark's zstd binding is
    missing."""
    if cfg.get("compression", "none") != "none":
        pytest.importorskip("zstandard")


def _encode(cfg, seed):
    topic = generator.generate(cfg, seed)
    return topic, generator.encode(topic, cfg["records_per_batch"],
                                   broker.crc32c_native, 8,
                                   generator.codec(cfg))


def _varint(buf, pos):
    shift = value = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return (value >> 1) ^ -(value & 1), pos


def _varbytes(buf, pos):
    n, pos = _varint(buf, pos)
    return (None, pos) if n < 0 else (bytes(buf[pos:pos + n]), pos + n)


def _batches(buf):
    """Each batch's header fields and its records section, read without
    the program."""
    pos = 0
    while pos < len(buf):
        head = dict(zip(generator._HEADER.names, struct.unpack_from(
            ">qiibIhiqqqhii", buf, pos)))
        end = pos + 12 + head["batch_length"]
        yield head, buf[pos + generator.BATCH_HEADER_BYTES:end]
        pos = end


def _zstd_records(buf):
    """(offset, (ts, key, value)) of every record, read without the
    program: each batch's records section through the ``zstandard``
    module's decoder, then field by field."""
    zstandard = pytest.importorskip("zstandard")
    for head, section in _batches(buf):
        assert head["attributes"] == generator.ATTR_ZSTD
        body = zstandard.ZstdDecompressor().decompressobj().decompress(
            section)
        pos = 0
        for _ in range(head["count"]):
            length, pos = _varint(body, pos)
            end = pos + length
            ts, pos = _varint(body, pos + 1)  # after the attributes
            off, pos = _varint(body, pos)
            key, pos = _varbytes(body, pos)
            value, pos = _varbytes(body, pos)
            headers, pos = _varint(body, pos)
            assert headers == 0 and pos == end
            yield head["base_offset"] + off, (head["first_ts"] + ts, key,
                                              value)
        assert pos == len(body)


@pytest.mark.parametrize("cfg", list(CASES.values()), ids=list(CASES))
def test_batches_decode_to_the_records(cfg, monkeypatch):
    from kafka_topic_analyzer_tpu.io import kafka_codec as kc

    _needs_codec(cfg)
    # a compressed partition in pieces of two batches, joined
    monkeypatch.setattr(generator, "PIECE_RECORDS", 128)
    topic, logs = _encode(cfg, 3)
    order = topic.in_partition_order()
    first = 0
    for p, log in enumerate(logs):
        frames = list(kc.iter_batch_frames(bytes(log.buf), verify_crc=True))
        assert [f.base_offset for f in frames] == list(log.base_offsets)
        if cfg.get("compression") == "zstd":
            recs = list(_zstd_records(bytes(log.buf)))
        else:
            recs = [r for f in frames for r in kc.decode_frame_records(f)]
        assert len(recs) == log.end_offset
        want = order[first:first + len(recs)]
        first += len(recs)
        if cfg.get("compression") == "zstd":
            pool = generator.payload_pool(topic.payload_seed, int(
                generator.random_bytes(topic.value_len).max()))
            choice = generator.payload_choice(topic.payload_seed, topic.n)
        for j, ((off, (ts, key, value)), i) in enumerate(zip(recs, want)):
            assert off == j and ts == topic.ts_ms[i]
            assert (key is None) == (topic.key_id[i] < 0)
            if key is not None:
                assert key == b"k%08d" % topic.key_id[i]
            assert (len(value) if value is not None else -1) == \
                topic.value_len[i]
            if value and cfg.get("compression") == "zstd":
                # OMB's randomized payload: its random bytes, then zeros
                rand = int(len(value) * generator.RANDOM_BYTES_RATIO)
                assert value == (pool[choice[i], :rand].tobytes()
                                 + bytes(len(value) - rand))
            elif value:
                assert value == bytes(len(value))
    assert first == topic.n


# Values 30 % random bytes compress about three to one: 50 records of
# 1 KiB a batch 3.3 to one, the keyed topic's shorter values, with their
# keys and record headers, 3.0 to one.
@pytest.mark.parametrize("cfg", [OMB_ZSTD, KEYED_ZSTD],
                         ids=["keyless", "keyed"])
def test_zstd_batches_are_framed_as_the_java_producer_writes_them(cfg):
    _needs_codec(cfg)
    topic, logs = _encode(cfg, 2**31 + 3)
    plain = generator.encode(topic, cfg["records_per_batch"],
                             broker.crc32c_native)
    raw = packed = 0
    for log, ref in zip(logs, plain):
        np.testing.assert_array_equal(log.base_offsets, ref.base_offsets)
        for (head, section), (want, records) in zip(
                _batches(bytes(log.buf)), _batches(bytes(ref.buf))):
            assert head["attributes"] == generator.ATTR_ZSTD
            assert {k: v for k, v in head.items() if k not in
                    ("batch_length", "crc", "attributes")} == \
                {k: v for k, v in want.items() if k not in
                 ("batch_length", "crc", "attributes")}
            assert head["batch_length"] == 49 + len(section)
            # zstd's magic, then a frame header descriptor with no content
            # size, no single segment and no checksum: a streaming frame
            assert section[:4] == b"\x28\xb5\x2f\xfd"
            assert section[4] & 0b1110_0100 == 0
            raw += len(records)
            packed += len(section)
    # part random, not zeros: zeros compress some thousand to one
    assert 2.5 <= raw / packed <= 4.5, raw / packed


@pytest.mark.parametrize("name", PARENT_DIGESTS)
def test_existing_configs_encode_to_the_parent_bytes(name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{name}.json")) as f:
        cfg = dict(json.load(f), records=1 << 15)
    topic = generator.generate(cfg, 2**31 + 29)
    logs = generator.encode(topic, int(cfg["records_per_batch"]),
                            broker.crc32c_native, 8, generator.codec(cfg))
    h = hashlib.sha256()
    for log in logs:
        h.update(log.buf.tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[name]


def test_unknown_codec_is_an_error():
    with pytest.raises(ValueError, match="compression"):
        generator.codec(dict(OMB_LIKE, compression="lz5"))
    topic = generator.generate(OMB_LIKE, 1)
    with pytest.raises(ValueError, match="compression"):
        generator.encode(topic, 50, broker.crc32c_native, 1, "lz5")


def test_zstd_without_its_binding_is_a_clear_error(monkeypatch):
    monkeypatch.setitem(sys.modules, "zstandard", None)
    with pytest.raises(RuntimeError, match="zstandard"):
        generator.codec(OMB_ZSTD)
    assert generator.codec(OMB_LIKE) == "none"
