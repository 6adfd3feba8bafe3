"""A zstd-compressed cell, added to a copy of the benchmark by files
alone, runs through the harness on the CPU and comes out correct; its
broker serves batches whose attributes say zstd."""

import os
import socket
import struct

import pytest

pytest.importorskip("zstandard", reason="a zstd topic needs zstandard")
from benchmark import broker, generator, harness  # noqa: E402
from benchkit import (REPO, compile_cache,  # noqa: E402
                      copy_with_tiny_zstd_cell)


@pytest.fixture(scope="module")
def tiny_zstd(tmp_path_factory):
    root = copy_with_tiny_zstd_cell(str(tmp_path_factory.mktemp("zstd")))
    with compile_cache(str(tmp_path_factory.mktemp("jax_cache"))):
        yield root


def _fetch(port: int, topic: str, partitions: int) -> dict:
    """Each partition's record set from offset 0, by one Fetch v4 request
    in the benchmark broker's own framing."""
    w = broker._Writer().i16(broker.API_FETCH).i16(4).i32(1).string("probe")
    w.i32(-1).i32(0).i32(0).i32(1 << 30).i8(0).i32(1).string(topic)
    w.i32(partitions)
    for p in range(partitions):
        w.i32(p).i64(0).i32(1 << 30)
    req = w.done()
    with socket.create_connection(("127.0.0.1", port)) as conn:
        conn.sendall(struct.pack(">i", len(req)) + req)
        size = struct.unpack(">i", broker._recv_exact(conn, 4))[0]
        r = broker._Reader(broker._recv_exact(conn, size))
    r.i32(), r.i32(), r.i32()  # correlation id, throttle, topics
    r.string()
    out = {}
    for _ in range(r.i32()):
        p, err = r.i32(), r.i16()
        r.i64(), r.i64(), r.i32()  # watermark, stable offset, aborted
        n = r.i32()
        assert err == 0
        out[p] = r.buf[r.pos:r.pos + n]
        r.pos += n
    return out


def _attributes(records: bytes) -> list:
    attrs, pos = [], 0
    while pos < len(records):
        length, = struct.unpack_from(">i", records, pos + 8)
        attrs.append(struct.unpack_from(">h", records, pos + 21)[0])
        pos += 12 + length
    return attrs


@pytest.mark.parametrize("seed", [2**31 + 11, 2**33 + 5])
def test_tiny_zstd_cell_matches_the_reference(tiny_zstd, monkeypatch, seed):
    for name in os.listdir(os.path.join(REPO, "benchmark")):
        if name.endswith(".py"):
            with open(os.path.join(REPO, "benchmark", name), "rb") as a, \
                    open(os.path.join(tiny_zstd, "benchmark", name),
                         "rb") as b:
                assert a.read() == b.read()
    served = {}
    audit = harness.audit

    def probe_first(topic, port, flags):
        if not served:
            served.update(_fetch(port, "probe", 16))
        return audit(topic, port, flags)

    monkeypatch.setattr(harness, "audit", probe_first)
    result = harness.run(tiny_zstd, "tiny-zstd", seed, 0.5, False,
                         chip_check=False)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert {k: v["value"] for k, v in result["checks"].items()} == {
        "report_lines_wrong": 0.0, "quantile_rel_gap": 0.0}
    attrs = [a for p in range(16) for a in _attributes(served[p])]
    assert len(attrs) == 16 * 6  # every batch of the 4,096 records
    assert set(attrs) == {generator.ATTR_ZSTD}
