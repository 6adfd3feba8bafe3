"""Segment-dump roundtrip: write from a synthetic topic, re-scan, same report.

Plus the cold-path surface: the catalog/store layer, zero-copy reads,
corrupt-segment classification, and the parallel segment scan's
byte-identity against the sequential wire scan of the same data
(``--ingest-workers N`` x ``--superbatch K`` sweep).
"""

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.segfile import (
    CorruptSegmentError,
    MalformedSegmentError,
    SegmentFile,
    SegmentFileSource,
    TruncatedSegmentError,
    write_segment_from_batches,
)
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

pytestmark = pytest.mark.segfile

SPEC = SyntheticSpec(
    num_partitions=3,
    messages_per_partition=2_500,
    keys_per_partition=100,
    tombstone_permille=120,
    seed=5,
)


@pytest.fixture()
def seg_dir(tmp_path):
    src = SyntheticSource(SPEC)
    for p in src.partitions():
        write_segment_from_batches(
            str(tmp_path), "t", p, list(src.batches(1000, partitions=[p]))
        )
    return str(tmp_path)


def test_roundtrip_header_and_watermarks(seg_dir):
    src = SegmentFileSource(seg_dir, "t")
    assert src.partitions() == [0, 1, 2]
    start, end = src.watermarks()
    assert start == {0: 0, 1: 0, 2: 0}
    assert end == {0: 2500, 1: 2500, 2: 2500}
    seg = SegmentFile(f"{seg_dir}/t-0.ktaseg")
    assert seg.count == 2500 and seg.partition == 0


def test_segfile_scan_matches_synthetic_scan(seg_dir):
    cfg = AnalyzerConfig(num_partitions=3, batch_size=777, count_alive_keys=True,
                         alive_bitmap_bits=20)
    m_synth = run_scan(
        "t", SyntheticSource(SPEC), CpuExactBackend(cfg, init_now_s=10**10), 777
    ).metrics
    m_seg = run_scan(
        "t", SegmentFileSource(seg_dir, "t"), CpuExactBackend(cfg, init_now_s=10**10), 777
    ).metrics
    assert np.array_equal(m_synth.per_partition, m_seg.per_partition)
    assert m_synth.alive_keys == m_seg.alive_keys
    assert m_synth.earliest_ts_s == m_seg.earliest_ts_s
    assert m_synth.latest_ts_s == m_seg.latest_ts_s
    assert m_synth.smallest_message == m_seg.smallest_message
    assert m_synth.largest_message == m_seg.largest_message


def test_topic_name_prefix_not_confused(seg_dir):
    # A topic whose name extends the requested one must not be swallowed
    # by filename matching.
    src0 = SyntheticSource(SPEC)
    write_segment_from_batches(
        seg_dir, "t-extra", 0, list(src0.batches(1000, partitions=[0]))
    )
    src = SegmentFileSource(seg_dir, "t")
    assert src.partitions() == [0, 1, 2]
    _, end = src.watermarks()
    assert end[0] == 2500  # not the t-extra file's data


def test_dump_writer_roundtrip_with_gappy_offsets(tmp_path):
    """Dump a gappy (compacted) stream in rolled chunks, re-read it, and
    get identical metrics plus offset-exact watermarks."""
    from kafka_topic_analyzer_tpu.io.segfile import SegmentDumpWriter, TeeSource
    from kafka_topic_analyzer_tpu.io.kafka_wire import records_to_batch

    rows = []
    for off in range(0, 600, 3):  # offsets with gaps
        rows.append((0, 1_600_000_000_000 + off, f"k{off % 13}".encode(),
                     None if off % 7 == 0 else bytes(10 + off % 40)))
    batch = records_to_batch(rows)
    batch.offsets = np.arange(0, 600, 3, dtype=np.int64)

    # Append in 50-record batches; chunks roll once >= 64 records buffered
    # (rolling is batch-granular).
    writer = SegmentDumpWriter(str(tmp_path), "gap", records_per_chunk=64)
    for lo in range(0, 200, 50):
        writer.append(batch.take(np.arange(lo, lo + 50)))
    writer.close()

    src = SegmentFileSource(str(tmp_path), "gap")
    start, end = src.watermarks()
    assert start == {0: 0}
    assert end == {0: 598}  # last retained offset 597 + 1
    from kafka_topic_analyzer_tpu.records import RecordBatch

    full = RecordBatch.concat(list(src.batches(50)))
    assert len(full) == 200
    assert np.array_equal(full.offsets, batch.offsets)
    assert np.array_equal(full.key_len, batch.key_len)
    assert np.array_equal(full.value_null, batch.value_null)
    # Chunked files actually rolled.
    import os

    chunks = [f for f in os.listdir(tmp_path) if f.startswith("gap-0.c")]
    assert len(chunks) == 2  # rolled at 100 records (2 x 50-record appends)

    # Offset-exact resume mid-chunk.
    resumed = RecordBatch.concat(list(src.batches(50, start_at={0: 301})))
    assert int(resumed.offsets[0]) == 303  # first retained offset >= 301


def test_dump_preserves_nonzero_start_of_gapless_source(tmp_path):
    """Re-dumping an offset-less source that starts above 0 (retention) must
    keep the true start offset, not rebase to 0."""
    from kafka_topic_analyzer_tpu.io.segfile import SegmentDumpWriter, TeeSource

    src_dir = tmp_path / "src"
    dst_dir = tmp_path / "dst"
    src_dir.mkdir()
    src = SyntheticSource(SPEC)
    write_segment_from_batches(
        str(src_dir), "t", 0, list(src.batches(1000, partitions=[0])),
        start_offset=1000,
    )
    reader = SegmentFileSource(str(src_dir), "t")
    assert reader.watermarks()[0] == {0: 1000}
    tee = TeeSource(reader, SegmentDumpWriter(str(dst_dir), "t"))
    for _ in tee.batches(700):
        pass
    tee.close()
    redump = SegmentFileSource(str(dst_dir), "t")
    start, end = redump.watermarks()
    assert start == {0: 1000}
    assert end == {0: 1000 + SPEC.messages_per_partition}


def test_corrupt_magic_rejected(seg_dir, tmp_path):
    bad = tmp_path / "t-9.ktaseg"
    data = bytearray(open(f"{seg_dir}/t-0.ktaseg", "rb").read())
    data[:8] = b"NOTASEG!"
    bad.write_bytes(bytes(data))
    # Classified (CorruptFrameError taxonomy) AND still a ValueError for
    # pre-classification callers.
    with pytest.raises(MalformedSegmentError, match="bad magic") as e:
        SegmentFile(str(bad))
    assert isinstance(e.value, ValueError)
    assert e.value.kind == "malformed-header"
    assert e.value.path == str(bad)
    assert e.value.span == (0, 8)


# ---------------------------------------------------------------------------
# corrupt-segment classification (decode-surface rule: tools/lint.sh)


def test_truncated_header_classified(tmp_path):
    bad = tmp_path / "t-0.ktaseg"
    bad.write_bytes(b"KTASEG01\x00\x00")  # 10 of 28 header bytes
    with pytest.raises(TruncatedSegmentError, match="truncated header") as e:
        SegmentFile(str(bad))
    assert e.value.kind == "truncated"
    assert e.value.path == str(bad)
    from kafka_topic_analyzer_tpu.io.kafka_codec import CorruptFrameError

    assert isinstance(e.value, CorruptFrameError)


def test_truncated_payload_classified(seg_dir, tmp_path):
    data = open(f"{seg_dir}/t-0.ktaseg", "rb").read()
    bad = tmp_path / "t-0.ktaseg"
    bad.write_bytes(data[:-100])  # column payload cut short
    with pytest.raises(TruncatedSegmentError, match="size") as e:
        SegmentFile(str(bad))
    assert e.value.kind == "truncated"
    assert e.value.partition == 0
    assert e.value.num_records == 2500
    # Trailing garbage is malformed, not truncated.
    bad.write_bytes(data + b"xx")
    with pytest.raises(MalformedSegmentError, match="size"):
        SegmentFile(str(bad))


def test_impossible_header_classified(tmp_path):
    import struct

    from kafka_topic_analyzer_tpu.io.segfile import _HEADER

    bad = tmp_path / "t-0.ktaseg"
    bad.write_bytes(_HEADER.pack(b"KTASEG01", 0, 0, 0, -5))
    with pytest.raises(MalformedSegmentError, match="impossible header"):
        SegmentFile(str(bad))


def test_filename_header_mismatch_classified(seg_dir, tmp_path):
    import shutil

    shutil.copy(f"{seg_dir}/t-0.ktaseg", tmp_path / "t-7.ktaseg")
    with pytest.raises(MalformedSegmentError, match="does not match filename"):
        SegmentFileSource(str(tmp_path), "t")


def test_overlapping_chunks_classified(seg_dir, tmp_path):
    import shutil

    # Two copies of the same chunk under rolled-chunk names: identical
    # [0, 2500) offset ranges overlap.
    shutil.copy(f"{seg_dir}/t-0.ktaseg", tmp_path / "t-0.c0.ktaseg")
    shutil.copy(f"{seg_dir}/t-0.ktaseg", tmp_path / "t-0.c1.ktaseg")
    with pytest.raises(MalformedSegmentError, match="overlapping"):
        SegmentFileSource(str(tmp_path), "t")


# ---------------------------------------------------------------------------
# catalog/store layer (io/segstore.py)


def test_open_segment_store_and_catalog(seg_dir):
    from kafka_topic_analyzer_tpu.io.segstore import (
        DirectorySegmentStore,
        SegmentCatalog,
        open_segment_store,
    )

    store = open_segment_store(seg_dir)
    assert isinstance(store, DirectorySegmentStore)
    refs = store.list_refs("t")
    assert [r.partition for r in refs] == [0, 1, 2]
    assert all(r.size > 0 for r in refs)
    catalog = SegmentCatalog(store, "t")
    assert catalog.num_files == 3
    assert catalog.total_bytes == sum(r.size for r in refs)
    assert catalog.record_counts() == {0: 2500, 1: 2500, 2: 2500}
    # The source built from a plain path routes through the same store.
    src = SegmentFileSource(seg_dir, "t")
    assert src.partition_record_counts() == {0: 2500, 1: 2500, 2: 2500}


def test_open_segment_store_rejects_unknown_scheme(tmp_path):
    from kafka_topic_analyzer_tpu.io.segstore import open_segment_store

    # Unknown schemes list what IS supported (s3://-style specs route to
    # the remote tier now — tests/test_objstore.py).
    with pytest.raises(ValueError, match="scheme 'gs' is not supported"):
        open_segment_store("gs://bucket/prefix")
    with pytest.raises(ValueError, match="not a directory"):
        open_segment_store(str(tmp_path / "missing"))
    # file:// is the explicit spelling of the local store.
    store = open_segment_store(f"file://{tmp_path}")
    assert store.list_refs("t") == []


def test_segment_telemetry_counters(seg_dir):
    from kafka_topic_analyzer_tpu.obs.registry import default_registry
    from kafka_topic_analyzer_tpu.results import SegmentStats

    before = SegmentStats.from_telemetry(default_registry().snapshot())
    src = SegmentFileSource(seg_dir, "t")
    n = sum(len(b) for b in src.batches(1000))
    after = SegmentStats.from_telemetry(default_registry().snapshot())
    assert after.files - before.files == 3
    assert after.records - before.records == n == 7500
    assert after.batches - before.batches == 9  # ceil(2500/1000) x 3
    assert after.bytes_mapped - before.bytes_mapped == src.catalog.total_bytes
    assert after.as_dict()["files"] == after.files


# ---------------------------------------------------------------------------
# zero-copy read + pack


def test_read_batch_is_zero_copy_and_matches_copy(seg_dir):
    seg = SegmentFile(f"{seg_dir}/t-0.ktaseg")
    view = seg.read_batch(100, 400)
    deep = seg.read_batch(100, 400, copy=True)
    for name, _ in view.FIELDS:
        assert np.array_equal(getattr(view, name), getattr(deep, name)), name
    # The int/hash columns and null flags alias the file mapping...
    for name in ("key_len", "value_len", "key_null", "value_null",
                 "key_hash32", "key_hash64"):
        assert np.shares_memory(getattr(view, name), seg._mm), name
        assert not getattr(view, name).flags.writeable, name
    # ...partition/valid alias the per-file constants (one allocation per
    # file, not per batch), and the copy path detaches everything.
    assert np.shares_memory(view.partition, seg._const_partition)
    assert np.shares_memory(view.valid, seg._const_valid)
    for name, _ in deep.FIELDS:
        assert not np.shares_memory(getattr(deep, name), seg._mm), name


def test_pack_from_memmap_views_matches_copy_pack(seg_dir):
    """Packed rows built straight from mapped columns (the cold path's
    pack) must be byte-identical to packing detached copies, with and
    without the native shim, including the pack_batch(out=) row path."""
    from kafka_topic_analyzer_tpu.packing import pack_batch, packed_nbytes

    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=16, enable_hll=True, hll_p=8,
        enable_quantiles=True,
    )
    seg = SegmentFile(f"{seg_dir}/t-1.ktaseg")
    view = seg.read_batch(0, 512)
    view.partition = np.full(512, 1, dtype=np.int32)  # dense remap rebinding
    deep = view.copy()
    for use_native in (False, True):
        a = pack_batch(view, cfg, use_native=use_native)
        b = pack_batch(deep, cfg, use_native=use_native)
        assert np.array_equal(a, b)
        row = np.empty(packed_nbytes(cfg, 512), dtype=np.uint8)
        assert pack_batch(view, cfg, use_native=use_native, out=row) is row
        assert np.array_equal(row, a)


# ---------------------------------------------------------------------------
# parallel cold scan


def test_shard_partitions_weighted_balances_and_stays_disjoint():
    from kafka_topic_analyzer_tpu.parallel.ingest import shard_partitions

    weights = {0: 1000, 1: 10, 2: 10, 3: 10}
    groups = shard_partitions([0, 1, 2, 3], 2, weights=weights)
    assert groups == [[0], [1, 2, 3]]  # greedy LPT: hot partition isolated
    # Disjoint cover, deterministic, empty groups dropped.
    flat = sorted(p for g in groups for p in g)
    assert flat == [0, 1, 2, 3]
    assert shard_partitions([0, 1, 2, 3], 2, weights=weights) == groups
    assert shard_partitions([5], 4, weights={5: 9}) == [[5]]
    # No weights: unchanged mesh round-robin rule.
    assert shard_partitions([0, 1, 2, 3], 2) == [[0, 2], [1, 3]]


def test_parallel_segfile_scan_matches_sequential(seg_dir):
    cfg = AnalyzerConfig(num_partitions=3, batch_size=777,
                         count_alive_keys=True, alive_bitmap_bits=20)

    def scan(workers):
        return run_scan(
            "t", SegmentFileSource(seg_dir, "t"),
            CpuExactBackend(cfg, init_now_s=10**10), 777,
            ingest_workers=workers,
        )

    ref = scan(1)
    for n in (2, 3):
        got = scan(n)
        assert got.ingest_workers == n
        assert np.array_equal(
            ref.metrics.per_partition, got.metrics.per_partition
        )
        assert ref.metrics.to_dict() == got.metrics.to_dict()
        assert got.start_offsets == ref.start_offsets
        assert got.end_offsets == ref.end_offsets


def test_wire_dump_rescan_byte_identity_workers_x_superbatch(tmp_path):
    """The cold-path acceptance bar: produce → wire scan with a
    --dump-segments tee → re-scan the dump from disk, swept across ingest
    workers N∈{1,2,4} × superbatch K∈{1,4} — every cold scan's report doc
    must be byte-identical to the sequential wire scan's (same metrics,
    same watermarks), with a deliberately skewed partition layout so the
    weighted worker sharding is exercised."""
    from fake_broker import FakeBroker

    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
    from kafka_topic_analyzer_tpu.config import DispatchConfig
    from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
    from kafka_topic_analyzer_tpu.io.segfile import SegmentDumpWriter, TeeSource

    def mk(partition, n):
        return [
            (
                i,
                1_600_000_000_000 + i * 1000,
                f"k{partition}-{i % 23}".encode() if i % 5 else None,
                bytes(20 + (i % 13)) if i % 7 else None,
            )
            for i in range(n)
        ]

    records = {0: mk(0, 240), 1: mk(1, 120), 2: mk(2, 60)}
    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=64, count_alive_keys=True,
        alive_bitmap_bits=16, enable_hll=True, hll_p=8,
        enable_quantiles=True,
    )
    seg_dir = str(tmp_path / "dump")

    def doc(result):
        d = result.metrics.to_dict(result.start_offsets, result.end_offsets)
        d["start"] = result.start_offsets
        d["end"] = result.end_offsets
        return d

    with FakeBroker("cold.topic", records, max_records_per_fetch=50) as broker:
        src = TeeSource(
            KafkaWireSource(f"127.0.0.1:{broker.port}", "cold.topic"),
            SegmentDumpWriter(seg_dir, "cold.topic", records_per_chunk=100),
        )
        ref = doc(run_scan(
            "cold.topic", src, TpuBackend(cfg, init_now_s=10**10), 64
        ))
        src.close()

    for workers in (1, 2, 4):
        for k in (1, 4):
            backend = TpuBackend(
                cfg, init_now_s=10**10,
                dispatch=DispatchConfig(superbatch=k),
            )
            result = run_scan(
                "cold.topic", SegmentFileSource(seg_dir, "cold.topic"),
                backend, 64, ingest_workers=workers,
            )
            assert result.ingest_workers == min(workers, 3)
            assert result.superbatch_k == k
            assert doc(result) == ref, (workers, k)


@pytest.mark.parametrize("workers", ["1", "4"])
def test_cli_segfile_parallel_scan_with_digest(seg_dir, capsys, workers):
    """End-to-end cold path through the CLI: --source segfile with parallel
    ingest workers, the --json segments digest, and the telemetry block's
    kta_segment_* counters."""
    import json

    from kafka_topic_analyzer_tpu.cli import main
    from kafka_topic_analyzer_tpu.obs.registry import default_registry
    from kafka_topic_analyzer_tpu.results import SegmentStats

    # The default registry is process-global and cumulative, so under
    # pytest (many scans, one process) the digest carries prior tests'
    # counters too — assert the delta this scan added.  A real CLI process
    # starts from zero.
    before = SegmentStats.from_telemetry(default_registry().snapshot())
    assert main([
        "-t", "t", "--source", "segfile", "--segment-dir", seg_dir,
        "--backend", "cpu", "-c", "--alive-bitmap-bits", "20",
        "--ingest-workers", workers, "--batch-size", "1024",
        "--json", "--quiet", "--native", "off",
    ]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["overall"]["count"] == 7500
    assert doc["ingest_workers"] == min(int(workers), 3)
    assert doc["segments"]["files"] - before.files == 3
    assert doc["segments"]["records"] - before.records == 7500
    assert doc["segments"]["bytes_mapped"] > before.bytes_mapped
    assert "kta_segment_files_opened_total" in doc["telemetry"]


def test_make_segments_cli_roundtrip_and_flag_hint(tmp_path, capsys):
    """tools/make_segments: works with the --synthetic kv spec, and a user
    who tries per-key flags gets pointed at the spec form (r3 weak #6)."""
    from kafka_topic_analyzer_tpu.tools.make_segments import main as ms_main

    out = str(tmp_path / "segs")
    rc = ms_main(["--out", out, "--topic", "demo", "--native", "off",
                  "--synthetic", "partitions=2,messages=300,keys=40"])
    assert rc == 0
    import os
    assert sorted(os.listdir(out)) == ["demo-0.ktaseg", "demo-1.ktaseg"]

    with pytest.raises(SystemExit) as e:
        ms_main(["--out", out, "--topic", "demo",
                 "--partitions", "4", "--messages", "5000"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "--synthetic" in err and "partitions=" in err
    # Bad kv values still come back as one clean named-key line, rc 1.
    rc = ms_main(["--out", out, "--topic", "demo", "--native", "off",
                  "--synthetic", "nope=1"])
    assert rc == 1
    assert "unknown --synthetic key 'nope'" in capsys.readouterr().err
