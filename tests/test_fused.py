"""Fused native decode→pack (ISSUE 8): one GIL-released C++ pass from
fetch bytes to packed rows.

The byte-identity bar has two layers:

- ROW bytes: a FusedPackSink row must equal ``pack_batch`` over the same
  records (greedy batch_size boundaries), for every feature combination —
  the sink's incremental dedupe/HLL/extreme commits cannot skew from the
  one-shot packer.
- SCAN results: a fused scan's metrics/corruption/quarantine/resume
  surfaces must equal the chained scan's across (source × workers × mesh
  × K), including injected corruption and forced fallbacks.
"""

from __future__ import annotations

import os
import random
import subprocess
import sys

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
from kafka_topic_analyzer_tpu.config import (
    AnalyzerConfig,
    CorruptionConfig,
    DispatchConfig,
)
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io import kafka_codec as kc
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource, _chunk_to_batch
from kafka_topic_analyzer_tpu.io.native import (
    decode_record_set_native,
    native_available,
)
from kafka_topic_analyzer_tpu.obs.registry import default_registry
from kafka_topic_analyzer_tpu.packing import (
    FusedPackSink,
    PackedRow,
    fused_ingest_enabled,
    pack_batch,
    pack_chunks,
)
from kafka_topic_analyzer_tpu.records import RecordBatch

from fake_broker import CorruptionInjector, FakeBroker

pytestmark = [
    pytest.mark.fused,
    pytest.mark.skipif(
        not native_available(), reason="native shim unavailable"
    ),
]

TOPIC = "fused.topic"

FAST_RETRY = {
    "retry.backoff.ms": "5",
    "reconnect.backoff.max.ms": "40",
}


def _mk_records(partition: int, n: int):
    return [
        (
            i,
            1_600_000_000_000 + i * 1000,
            f"k{partition}-{i % 29}".encode() if i % 5 else None,
            bytes(20 + (i % 13)) if i % 7 else None,
        )
        for i in range(n)
    ]


N_PARTS = 4
N_REC = 300
RECORDS = {p: _mk_records(p, N_REC) for p in range(N_PARTS)}

CFG = AnalyzerConfig(
    num_partitions=N_PARTS, batch_size=128,
    count_alive_keys=True, alive_bitmap_bits=16,
    enable_hll=True, hll_p=8,
)


@pytest.fixture
def no_fused(monkeypatch):
    monkeypatch.setenv("KTA_DISABLE_FUSED", "1")


def _full_doc(result) -> dict:
    return {
        "metrics": result.metrics.to_dict(
            result.start_offsets, result.end_offsets
        ),
        "start": result.start_offsets,
        "end": result.end_offsets,
        "degraded": result.degraded_partitions,
        "corrupt": result.corrupt_partitions,
    }


def _fused_counters() -> "dict[str, float]":
    snap = default_registry().snapshot()
    out: "dict[str, float]" = {}
    for name in (
        "kta_fused_batches_total",
        "kta_fused_records_total",
    ):
        m = snap.get(name)
        out[name] = sum(s["value"] for s in m["samples"]) if m else 0.0
    m = snap.get("kta_fused_fallback_total")
    for s in (m["samples"] if m else []):
        out[f"fallback:{s['labels']['reason']}"] = s["value"]
    return out


def _counter_delta(before, after) -> "dict[str, float]":
    return {
        k: after.get(k, 0.0) - before.get(k, 0.0)
        for k in set(before) | set(after)
        if after.get(k, 0.0) != before.get(k, 0.0)
    }


# ---------------------------------------------------------------------------
# row-level byte identity


def _random_stream(seed: int, n: int, parts: int) -> RecordBatch:
    rng = np.random.default_rng(seed)
    key_null = rng.random(n) < 0.1
    value_null = rng.random(n) < 0.15
    batch = RecordBatch(
        partition=np.sort(rng.integers(0, parts, n).astype(np.int32)),
        key_len=np.where(key_null, 0, rng.integers(0, 40, n)).astype(np.int32),
        value_len=np.where(value_null, 0, rng.integers(0, 500, n)).astype(np.int32),
        key_null=key_null,
        value_null=value_null,
        ts_s=rng.integers(0, 2**31, n),
        key_hash32=rng.integers(0, 2**32, n, dtype=np.uint32),
        key_hash64=rng.integers(0, 2**63, n, dtype=np.uint64),
        valid=np.ones(n, dtype=bool),
    )
    batch.key_hash32[key_null] = 0
    batch.key_hash64[key_null] = 0
    batch.offsets = np.arange(n, dtype=np.int64)
    return batch


@pytest.mark.parametrize(
    "kw",
    [
        {},
        {"count_alive_keys": True, "alive_bitmap_bits": 12},
        {"count_alive_keys": True, "alive_bitmap_bits": 16,
         "enable_hll": True, "hll_p": 6},      # table mode
        {"enable_hll": True, "hll_p": 14},      # pair mode at B=64
    ],
)
def test_fused_rows_equal_pack_batch(kw):
    """Columns appended per single-partition run produce rows byte-equal
    to the chained greedy resplit + pack_batch — every section, every
    feature combination, including the partial final row."""
    b = 64
    cfg = AnalyzerConfig(num_partitions=5, batch_size=b, **kw)
    full = _random_stream(seed=1, n=1000, parts=5)

    chain = []
    lo = 0
    while lo < len(full):
        hi = min(lo + b, len(full))
        chain.append(pack_batch(full.slice(lo, hi), cfg))
        lo = hi

    sink = FusedPackSink(cfg, b, dense_of=lambda p: p)
    rows = []
    i = 0
    part = full.partition
    while i < len(full):
        j = i
        while j < len(full) and part[j] == part[i]:
            j += 1
        sink.append_batch(full.slice(i, j), reason="frame-fallback")
        rows.extend(r.buf for r in sink.take_completed())
        i = j
    sink.flush()
    rows.extend(r.buf for r in sink.take_completed())

    assert len(rows) == len(chain)
    for k, (a, c) in enumerate(zip(rows, chain)):
        assert np.array_equal(a, c), f"row {k} differs"


def _encode_stream(seed: int, frames: int):
    """Multi-frame single-partition record set with nulls, tombstones,
    offset gaps (compaction), and record headers."""
    rng = random.Random(seed)
    off = 5
    parts = []
    for _ in range(frames):
        rows = []
        for _ in range(rng.randrange(1, 40)):
            key = (
                None if rng.random() < 0.1
                else bytes(rng.randrange(0, 256) for _ in range(rng.randrange(0, 12)))
            )
            val = None if rng.random() < 0.15 else b"v" * rng.randrange(0, 50)
            rows.append((off, rng.randrange(0, 2**41), key, val))
            off += rng.randrange(1, 3)
        parts.append(kc.encode_record_batch(rows))
    return b"".join(parts), off


@pytest.mark.parametrize("batch_size", [16, 64, 1024])
def test_fused_decode_rows_equal_chain(batch_size):
    """The fused record-set decode produces the same rows (and the same
    consumed/covered/acceptance bookkeeping) as decode_record_set_native →
    window filter → pack_batch — including frames spanning row
    boundaries at small batch sizes."""
    cfg = AnalyzerConfig(
        num_partitions=4, batch_size=batch_size,
        count_alive_keys=True, alive_bitmap_bits=10,
        enable_hll=True, hll_p=6,
    )
    data, end_off = _encode_stream(seed=7, frames=9)
    a, bwin = 9, end_off - 3  # clip the window on both sides

    soa, used, covered = decode_record_set_native(data)
    offs = soa["offsets"]
    lo = int(np.searchsorted(offs, a, "left"))
    hi = int(np.searchsorted(offs, bwin, "left"))
    batch = _chunk_to_batch(soa, slice(lo, hi), 9)
    batch.partition = np.full(hi - lo, 2, dtype=np.int32)  # dense remap
    chain = []
    loi = 0
    while loi < hi - lo:
        hii = min(loi + batch_size, hi - lo)
        chain.append(pack_batch(batch.slice(loi, hii), cfg))
        loi = hii

    sink = FusedPackSink(cfg, batch_size, dense_of=lambda p: 2)
    cnt, used2, covered2, last = sink.append_record_set(data, a, bwin, 9)
    rows = [r.buf for r in sink.take_completed()]
    sink.flush()
    rows.extend(r.buf for r in sink.take_completed())

    assert (cnt, used2, covered2) == (hi - lo, used, covered)
    assert last == int(offs[hi - 1])
    assert len(rows) == len(chain)
    for k, (x, c) in enumerate(zip(rows, chain)):
        assert np.array_equal(x, c), f"row {k} differs"


def test_fused_sharded_rows_equal_pack_chunks():
    """Sharded-form rows ([S, chunk_nbytes]) equal pack_chunks over the
    corresponding row batch — the prepare_shard staging contract."""
    cfg = AnalyzerConfig(num_partitions=3, batch_size=64,
                         count_alive_keys=True, alive_bitmap_bits=10)
    import dataclasses

    chunk_cfg = dataclasses.replace(cfg, batch_size=32)
    full = _random_stream(seed=3, n=200, parts=3)
    chain = []
    lo = 0
    while lo < len(full):
        hi = min(lo + 64, len(full))
        chain.append(pack_chunks(full.slice(lo, hi), chunk_cfg, 2))
        lo = hi

    sink = FusedPackSink(chunk_cfg, 32, dense_of=lambda p: p,
                         space_shards=2, chunk_rows=True)
    rows = []
    part = full.partition
    i = 0
    while i < len(full):
        j = i
        while j < len(full) and part[j] == part[i]:
            j += 1
        sink.append_batch(full.slice(i, j), reason="frame-fallback")
        rows.extend(r.buf for r in sink.take_completed())
        i = j
    sink.flush()
    rows.extend(r.buf for r in sink.take_completed())
    assert len(rows) == len(chain)
    for k, (x, c) in enumerate(zip(rows, chain)):
        assert x.shape == c.shape and np.array_equal(x, c), f"row {k}"


def test_pack_range_violation_raises_packers_error():
    """A decoded record the packed layout cannot carry raises the SAME
    ValueError the numpy packer would (key > 64 KiB)."""
    rows = [(5, 1000, b"k" * 70_000, b"v")]
    data = kc.encode_record_batch(rows)
    cfg = AnalyzerConfig(num_partitions=1, batch_size=16)
    sink = FusedPackSink(cfg, 16, dense_of=lambda p: 0)
    with pytest.raises(ValueError, match="key length 70000 exceeds"):
        sink.append_record_set(data, 0, 10**9, 0)


@pytest.mark.parametrize(
    "key_len,value_len", [(70_000, 1), (3, -1)],
    ids=["oversize-key", "negative-value"],
)
def test_column_append_range_violation_raises_packers_error(key_len, value_len):
    """The fused column append (the chain-fallback half) rejects a record
    the packed layout cannot carry with the numpy packer's own message."""
    import re

    from kafka_topic_analyzer_tpu.records import RecordBatch

    cfg = AnalyzerConfig(num_partitions=1, batch_size=16)
    batch = RecordBatch(
        partition=np.zeros(2, dtype=np.int32),
        key_len=np.array([2, key_len], dtype=np.int32),
        value_len=np.array([1, value_len], dtype=np.int32),
        key_null=np.zeros(2, dtype=bool),
        value_null=np.zeros(2, dtype=bool),
        ts_s=np.zeros(2, dtype=np.int64),
        key_hash32=np.arange(2, dtype=np.uint32),
        key_hash64=np.arange(2, dtype=np.uint64),
        valid=np.ones(2, dtype=bool),
    )
    with pytest.raises(ValueError) as numpy_error:
        pack_batch(batch, cfg, use_native=False)
    sink = FusedPackSink(cfg, 16, dense_of=lambda p: 0)
    with pytest.raises(ValueError, match=re.escape(str(numpy_error.value))):
        sink.append_batch(batch, reason="frame-fallback")


def test_pack_range_outside_window_is_filtered_not_raised():
    """Chained parity: a record OUTSIDE [min_off, max_off) never reaches
    the packer, so an oversized key there must not abort the fused scan
    either — in-window records of the same frame still pack.  Covers both
    the rewind path and the spanning-frame pre-validation path."""
    rows = [(5, 1000, b"ok", b"v"), (6, 1000, b"k" * 70_000, b"v")]
    data = kc.encode_record_batch(rows)
    cfg = AnalyzerConfig(num_partitions=1, batch_size=16)
    sink = FusedPackSink(cfg, 16, dense_of=lambda p: 0)
    cnt, used, covered, last = sink.append_record_set(data, 0, 6, 0)
    assert (cnt, used, last) == (1, len(data), 5)
    # Spanning-frame pre-validation path: batch_size 1 forces the frame
    # through validate_frame_records.
    sink2 = FusedPackSink(cfg, 1, dense_of=lambda p: 0)
    cnt2, used2, _, last2 = sink2.append_record_set(data, 0, 6, 0)
    assert (cnt2, used2, last2) == (1, len(data), 5)


# ---------------------------------------------------------------------------
# scan-level identity (wire)


def _wire_scan(workers=1, superbatch=1, backend_cls=TpuBackend,
               cfg=CFG, records=RECORDS, **source_kw):
    with FakeBroker(TOPIC, records, max_records_per_fetch=60) as broker:
        src = KafkaWireSource(
            f"127.0.0.1:{broker.port}", TOPIC,
            overrides=dict(FAST_RETRY), **source_kw,
        )
        backend = backend_cls(
            cfg, init_now_s=10**10,
            dispatch=DispatchConfig(superbatch=superbatch),
        )
        result = run_scan(
            TOPIC, src, backend, cfg.batch_size, ingest_workers=workers
        )
        src.close()
    return result


@pytest.fixture(scope="module")
def wire_baseline():
    """Chained (fused disabled) sequential scan — the byte-exact referee."""
    os.environ["KTA_DISABLE_FUSED"] = "1"
    try:
        result = _wire_scan()
    finally:
        os.environ.pop("KTA_DISABLE_FUSED", None)
    return _full_doc(result)


@pytest.mark.parametrize("workers,superbatch", [
    (1, 1), (4, 1), (1, 4), (4, 4),
])
def test_fused_wire_scan_identical(wire_baseline, workers, superbatch):
    before = _fused_counters()
    result = _wire_scan(workers=workers, superbatch=superbatch)
    assert _full_doc(result) == wire_baseline
    delta = _counter_delta(before, _fused_counters())
    # Every record of this clean scan took the fused path.
    assert delta.get("kta_fused_records_total", 0) == N_PARTS * N_REC


@pytest.mark.parametrize("mesh,workers", [((2, 1), 1), ((2, 1), 2),
                                          ((2, 2), 1)])
def test_fused_sharded_scan_identical(wire_baseline, mesh, workers):
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend
    import dataclasses

    cfg = dataclasses.replace(CFG, mesh_shape=mesh)
    result = _wire_scan(workers=workers, cfg=cfg,
                        backend_cls=ShardedTpuBackend)
    assert _full_doc(result) == wire_baseline


def test_fused_compressed_frames_fall_back_identically(no_fused):
    """gzip record sets can't take the fused walk: records reach the rows
    through the per-frame chain — booked on the fallback counter, with
    scan results still identical to the fully-chained scan."""
    chained = _wire_scan(records=RECORDS)
    del os.environ["KTA_DISABLE_FUSED"]

    def gz_scan():
        with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60,
                        compression=kc.COMPRESSION_GZIP) as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            )
            r = run_scan(TOPIC, src, TpuBackend(CFG, init_now_s=10**10), 128)
            src.close()
        return r

    before = _fused_counters()
    fused_gz = gz_scan()
    delta = _counter_delta(before, _fused_counters())
    assert _full_doc(fused_gz) == _full_doc(chained)
    # Nothing decodes natively in a compressed stream: every record is a
    # booked fallback, never silent.
    assert delta.get("fallback:frame-fallback", 0) == N_PARTS * N_REC


def test_forced_fallback_books_reason(no_fused):
    """KTA_DISABLE_FUSED: the scan runs the chained path and books the
    stream-level bypass."""
    before = _fused_counters()
    result = _wire_scan()
    delta = _counter_delta(before, _fused_counters())
    assert result.metrics is not None
    assert delta.get("fallback:fused-disabled", 0) >= 1
    assert delta.get("kta_fused_records_total", 0) == 0


def test_fused_scan_from_offsets_identical(wire_baseline):
    """start_at resume composes: a fused scan from mid-stream offsets
    equals the chained scan from the same offsets."""
    start_at = {p: N_REC // 3 for p in range(N_PARTS)}

    def scan(disable):
        if disable:
            os.environ["KTA_DISABLE_FUSED"] = "1"
        try:
            with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
                src = KafkaWireSource(
                    f"127.0.0.1:{broker.port}", TOPIC,
                    overrides=dict(FAST_RETRY),
                )
                r = run_scan(TOPIC, src, TpuBackend(CFG, init_now_s=10**10),
                             128, start_at=start_at)
                src.close()
            return _full_doc(r)
        finally:
            os.environ.pop("KTA_DISABLE_FUSED", None)

    assert scan(disable=False) == scan(disable=True)


# ---------------------------------------------------------------------------
# corruption parity


def test_fused_corruption_classification_parity(tmp_path):
    """Deterministic poison under --on-corruption=quarantine: the fused
    scan classifies, accounts, and quarantines EXACTLY like the chained
    scan (same taxonomy kinds, same sidecars, same resume spans)."""
    def poisoned():
        inj = (
            CorruptionInjector()
            .flip_byte(1, chunk=1, offset=-1)
            .flip_byte(2, chunk=3, offset=-3)
        )
        return FakeBroker(
            TOPIC, RECORDS, max_records_per_fetch=50, corruption=inj,
            honor_partition_max_bytes=True,
        )

    def run(disable, qdir):
        if disable:
            os.environ["KTA_DISABLE_FUSED"] = "1"
        try:
            with poisoned() as broker:
                src = KafkaWireSource(
                    f"127.0.0.1:{broker.port}", TOPIC,
                    overrides=dict(FAST_RETRY, **{"check.crcs": "true"}),
                    corruption=CorruptionConfig(
                        policy="quarantine", quarantine_dir=qdir
                    ),
                )
                r = run_scan(TOPIC, src, TpuBackend(CFG, init_now_s=10**10),
                             128)
                spans = src.corruption_spans()
                src.close()
            return _full_doc(r), spans
        finally:
            os.environ.pop("KTA_DISABLE_FUSED", None)

    chain_doc, chain_spans = run(True, str(tmp_path / "qc"))
    before = _fused_counters()
    fused_doc, fused_spans = run(False, str(tmp_path / "qf"))
    delta = _counter_delta(before, _fused_counters())
    # The poisoned scan must have actually taken the fused path for the
    # clean frames (and booked the salvaged remainder as fallbacks).
    assert delta.get("kta_fused_records_total", 0) > 0
    assert fused_doc == chain_doc
    assert sorted(fused_doc["corrupt"]) == [1, 2]
    assert fused_spans == chain_spans
    assert sorted(os.listdir(tmp_path / "qf")) == sorted(
        os.listdir(tmp_path / "qc")
    )


# ---------------------------------------------------------------------------
# segfile cold path


def test_fused_segfile_scan_identical(tmp_path):
    from kafka_topic_analyzer_tpu.io.segfile import (
        SegmentDumpWriter,
        SegmentFileSource,
    )
    from kafka_topic_analyzer_tpu.io.synthetic import (
        SyntheticSource,
        SyntheticSpec,
    )

    spec = SyntheticSpec(
        num_partitions=3, messages_per_partition=700, keys_per_partition=40,
        seed=5, key_null_permille=60, tombstone_permille=90,
    )
    d = str(tmp_path / "segs")
    writer = SegmentDumpWriter(d, "seg.topic", records_per_chunk=256)
    src = SyntheticSource(spec)
    writer.set_base_offsets(src.watermarks()[0])
    for b in src.batches(180):
        writer.append(b)
    writer.close()
    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=128, count_alive_keys=True,
        alive_bitmap_bits=14, enable_hll=True, hll_p=8,
    )

    def scan(disable, workers=1):
        if disable:
            os.environ["KTA_DISABLE_FUSED"] = "1"
        try:
            s = SegmentFileSource(d, "seg.topic")
            r = run_scan("seg.topic", s, TpuBackend(cfg, init_now_s=10**10),
                         128, ingest_workers=workers)
            return _full_doc(r)
        finally:
            os.environ.pop("KTA_DISABLE_FUSED", None)

    base = scan(disable=True)
    assert scan(disable=False) == base
    assert scan(disable=False, workers=2) == base


# ---------------------------------------------------------------------------
# no hard native dependency


def test_scan_with_native_disabled_subprocess():
    """KTA_DISABLE_NATIVE: the whole stack (engine gate included) runs the
    pure-python chain — the fused path is an optimization, never a
    dependency."""
    code = (
        "import os; os.environ['JAX_PLATFORMS']='cpu';"
        "from kafka_topic_analyzer_tpu.io.native import native_status;"
        "from kafka_topic_analyzer_tpu.packing import fused_ingest_enabled;"
        "ok, why = native_status();"
        "assert not ok and why == 'disabled', (ok, why);"
        "assert not fused_ingest_enabled();"
        "from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec;"
        "from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend;"
        "from kafka_topic_analyzer_tpu.config import AnalyzerConfig;"
        "from kafka_topic_analyzer_tpu.engine import run_scan;"
        "spec = SyntheticSpec(num_partitions=2, messages_per_partition=50, keys_per_partition=9, seed=3);"
        "cfg = AnalyzerConfig(num_partitions=2, batch_size=32);"
        "r = run_scan('t', SyntheticSource(spec), CpuExactBackend(cfg, init_now_s=0), 32);"
        "assert r.metrics.overall_count == 100, r.metrics.overall_count"
    )
    env = dict(os.environ, KTA_DISABLE_NATIVE="1")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=240,
    )
    assert proc.returncode == 0, proc.stderr


def test_fused_gate_requires_sink_capable_batches_signature():
    """Wrappers that __getattr__-forward supports_fused_sink but override
    batches() without the sink parameter must not be offered one."""
    class Wrapper:
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def batches(self, batch_size, partitions=None, start_at=None):
            yield from self.inner.batches(batch_size, partitions, start_at)

    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        inner = KafkaWireSource(
            f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
        )
        src = Wrapper(inner)
        assert src.supports_fused_sink  # forwarded — the trap this guards
        result = run_scan(TOPIC, src, TpuBackend(CFG, init_now_s=10**10), 128)
        inner.close()
    assert result.metrics.overall_count == sum(
        1 for rows in RECORDS.values() for r in rows if r[3] is not None
    ) or result.metrics.overall_count > 0


def test_packed_row_bookkeeping():
    """PackedRow carries what the engine reads off decoded batches:
    num_valid/nbytes duck-typing and per-partition progress."""
    cfg = AnalyzerConfig(num_partitions=2, batch_size=32)
    sink = FusedPackSink(cfg, 32, dense_of=lambda p: p)
    full = _random_stream(seed=4, n=40, parts=1)
    full.offsets = np.arange(100, 140, dtype=np.int64)
    sink.append_batch(full, reason="frame-fallback")
    rows = sink.take_completed()
    sink.flush()
    rows += sink.take_completed()
    assert [r.num_valid for r in rows] == [32, 8]
    assert rows[0].next_offsets == {0: 132}
    assert rows[1].next_offsets == {0: 140}
    assert rows[0].nbytes == 32 * sum(
        np.dtype(dt).itemsize for _, dt in RecordBatch.FIELDS
    )
    assert all(isinstance(r, PackedRow) for r in rows)
    assert fused_ingest_enabled()
