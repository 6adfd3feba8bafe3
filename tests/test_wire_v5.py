"""Combiner rows: host pre-reduced per-partition fold tables are the
packed layout (packing.py module docstring).

The bar has two layers:

- TABLE bytes: the combiner tables a packer emits (counter deltas,
  DDSketch buckets, extremes) must equal a straight numpy reference
  reduction over the same records — native and numpy packers alike
  (the hypothesis property test mirrors the PR-8 row-bytes parity suite).
- SCAN results: a device scan's full document must equal the CPU
  oracle's (tests/oracle.py) across (wire, segfile) × workers × K × mesh,
  including corruption/quarantine parity.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
from kafka_topic_analyzer_tpu.config import (
    AnalyzerConfig,
    CorruptionConfig,
    DispatchConfig,
)
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.obs.registry import default_registry
from kafka_topic_analyzer_tpu.packing import (
    batch_alive_pairs,
    pack_batch,
    packed_nbytes,
    section_byte_split,
    unpack_numpy,
)
from kafka_topic_analyzer_tpu.records import RecordBatch

from fake_broker import CorruptionInjector, FakeBroker
from oracle import sketched

pytestmark = pytest.mark.wirev5

TOPIC = "wirev5.topic"

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


def _cfg(**kw) -> AnalyzerConfig:
    base = dict(
        num_partitions=N_PARTS,
        batch_size=128,
        count_alive_keys=True,
        alive_bitmap_bits=16,
        enable_hll=True,
        hll_p=8,
        enable_quantiles=True,
        quantiles_per_partition=True,
    )
    base.update(kw)
    return AnalyzerConfig(**base)


def _full_doc(result, metrics=None) -> dict:
    return {
        "metrics": (metrics or result.metrics).to_dict(
            result.start_offsets, result.end_offsets
        ),
        "start": result.start_offsets,
        "end": result.end_offsets,
        "degraded": result.degraded_partitions,
        "corrupt": result.corrupt_partitions,
    }


def _backend(cfg, backend_cls, superbatch=1):
    if backend_cls is CpuExactBackend:
        return CpuExactBackend(cfg, init_now_s=10**10)
    return backend_cls(
        cfg, init_now_s=10**10, dispatch=DispatchConfig(superbatch=superbatch)
    )


def _doc(result, backend) -> dict:
    """The scan's document; the oracle's with its sketches taken."""
    if isinstance(backend, CpuExactBackend):
        return _full_doc(result, sketched(backend, result.metrics))
    return _full_doc(result)


def _wire_scan(workers=1, superbatch=1, backend_cls=TpuBackend,
               mesh=None, **cfg_kw):
    """(result, document) of one scan of the fake broker's topic."""
    cfg = _cfg(**cfg_kw)
    if mesh is not None:
        cfg = dataclasses.replace(cfg, mesh_shape=mesh)
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        src = KafkaWireSource(
            f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
        )
        backend = _backend(cfg, backend_cls, superbatch)
        result = run_scan(
            TOPIC, src, backend, cfg.batch_size, ingest_workers=workers
        )
        src.close()
    return result, _doc(result, backend)


@pytest.fixture(scope="module")
def wire_oracle():
    """The CPU oracle's document — the referee for every device scan."""
    return _wire_scan(backend_cls=CpuExactBackend)[1]


# ---------------------------------------------------------------------------
# scan-level identity: (wire) × workers × K × mesh


@pytest.mark.parametrize("workers,superbatch", [
    (1, 1), (4, 1), (1, 4), (4, 4),
])
def test_v5_wire_scan_identical(wire_oracle, workers, superbatch):
    result, doc = _wire_scan(workers=workers, superbatch=superbatch)
    assert doc == wire_oracle
    assert result.wire is not None and result.wire.format == 5


@pytest.mark.parametrize("mesh,superbatch", [((2, 1), 1), ((2, 1), 4)])
def test_v5_sharded_scan_identical(wire_oracle, mesh, superbatch):
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    _, doc = _wire_scan(mesh=mesh, superbatch=superbatch,
                        backend_cls=ShardedTpuBackend)
    assert doc == wire_oracle


def test_v5_flat_hll_pair_mode_scan_identical(wire_oracle):
    """Per-partition HLL in PAIR mode (idx32 carries the register row).
    hll_p=14 at B=128 forces pair mode; referee is the oracle of the same
    config."""
    kw = dict(distinct_keys_per_partition=True, hll_p=14)
    a = _wire_scan(backend_cls=CpuExactBackend, **kw)[1]
    b = _wire_scan(**kw)[1]
    assert a == b
    assert a != wire_oracle  # the per-partition rows actually differ


# ---------------------------------------------------------------------------
# segfile cold path


def test_v5_segfile_scan_identical(tmp_path):
    from kafka_topic_analyzer_tpu.io.segfile import (
        SegmentDumpWriter,
        SegmentFileSource,
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

    def scan(backend_cls=TpuBackend, workers=1):
        cfg = AnalyzerConfig(
            num_partitions=3, batch_size=128, count_alive_keys=True,
            alive_bitmap_bits=14, enable_hll=True, hll_p=8,
            enable_quantiles=True,
        )
        s = SegmentFileSource(d, "seg.topic")
        backend = _backend(cfg, backend_cls)
        r = run_scan("seg.topic", s, backend, 128, ingest_workers=workers)
        return _doc(r, backend)

    base = scan(CpuExactBackend)
    assert scan() == base
    assert scan(workers=2) == base


# ---------------------------------------------------------------------------
# corruption parity


def test_v5_corruption_quarantine_parity(tmp_path):
    """Deterministic poison under --on-corruption=quarantine: the device
    scan classifies, accounts, and quarantines EXACTLY like the oracle."""
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

    def run(backend_cls, qdir):
        cfg = _cfg()
        with poisoned() as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC,
                overrides=dict(FAST_RETRY, **{"check.crcs": "true"}),
                corruption=CorruptionConfig(
                    policy="quarantine", quarantine_dir=qdir
                ),
            )
            backend = _backend(cfg, backend_cls)
            r = run_scan(TOPIC, src, backend, 128)
            spans = src.corruption_spans()
            src.close()
        return _doc(r, backend), spans

    doc_cpu, spans_cpu = run(CpuExactBackend, str(tmp_path / "qcpu"))
    doc_tpu, spans_tpu = run(TpuBackend, str(tmp_path / "qtpu"))
    assert doc_tpu == doc_cpu
    assert sorted(doc_tpu["corrupt"]) == [1, 2]
    assert spans_tpu == spans_cpu
    assert sorted(os.listdir(tmp_path / "qtpu")) == sorted(
        os.listdir(tmp_path / "qcpu")
    )


# ---------------------------------------------------------------------------
# combiner tables vs reference reduction (hypothesis property test)


def _reference_tables(batch: RecordBatch, cfg: AnalyzerConfig):
    """Straight numpy reference reduction of a batch's combiner tables —
    written against the metric DEFINITIONS (counter channels, tombstone
    exclusion, the shared edge table), independently of pack_batch."""
    from kafka_topic_analyzer_tpu.ops.ddsketch import (
        ddsketch_bucket_numpy,
        ddsketch_num_buckets,
    )

    nv = batch.num_valid
    p = np.asarray(batch.partition[:nv])
    kn = ~np.asarray(batch.key_null[:nv])
    vn = ~np.asarray(batch.value_null[:nv])
    kb = np.where(kn, batch.key_len[:nv], 0).astype(np.int64)
    vb = np.where(vn, batch.value_len[:nv], 0).astype(np.int64)
    counts = np.zeros((cfg.num_partitions, 7), np.int64)
    for i in range(nv):
        row = counts[p[i]]
        row[0] += 1
        row[1] += 0 if vn[i] else 1
        row[2] += 1 if vn[i] else 0
        row[3] += 0 if kn[i] else 1
        row[4] += 1 if kn[i] else 0
        row[5] += kb[i]
        row[6] += vb[i]
    nb = ddsketch_num_buckets(cfg.quantile_buckets)
    q_rows = cfg.num_partitions if cfg.quantiles_per_partition else 1
    qt = np.zeros((q_rows, nb), np.int64)
    sizes = kb + vb
    for i in range(nv):
        if not vn[i]:
            continue  # tombstones excluded, like the size extremes
        idx = int(ddsketch_bucket_numpy(
            np.array([sizes[i]]), cfg.quantile_gamma, cfg.quantile_buckets
        )[0])
        qt[p[i] if q_rows > 1 else 0, idx] += 1
    return counts, qt


def _hyp_batch(draw):
    from hypothesis import strategies as st

    n = draw(st.integers(min_value=0, max_value=96))
    parts = draw(st.integers(min_value=1, max_value=5))
    # Histogram-edge sizes: include exact gamma-power boundaries so a
    # searchsorted off-by-one fails here, plus 0/1 and u16-max keys.
    key_len = np.array(
        [draw(st.sampled_from([0, 1, 7, 64, 65535])) for _ in range(n)],
        dtype=np.int32,
    )
    value_len = np.array(
        [draw(st.sampled_from([0, 1, 2, 100, 101, 4096, 1 << 20]))
         for _ in range(n)],
        dtype=np.int32,
    )
    key_null = np.array(
        [draw(st.booleans()) for _ in range(n)], dtype=bool
    )
    value_null = np.array(
        [draw(st.booleans()) for _ in range(n)], dtype=bool
    )
    batch = RecordBatch(
        partition=np.array(
            [draw(st.integers(0, parts - 1)) for _ in range(n)],
            dtype=np.int32,
        ),
        key_len=np.where(key_null, 0, key_len).astype(np.int32),
        value_len=np.where(value_null, 0, value_len).astype(np.int32),
        key_null=key_null,
        value_null=value_null,
        ts_s=np.array(
            [draw(st.integers(0, 2**31)) for _ in range(n)], dtype=np.int64
        ),
        key_hash32=np.array(
            [draw(st.integers(0, 2**32 - 1)) for _ in range(n)],
            dtype=np.uint32,
        ),
        key_hash64=np.array(
            [draw(st.integers(0, 2**63)) for _ in range(n)],
            dtype=np.uint64,
        ),
        valid=np.ones(n, dtype=bool),
    )
    return batch, parts


def test_combiner_tables_match_reference_reduction():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    use_native = native.native_available()

    @settings(max_examples=60, deadline=None)
    @given(st.data())
    def run(data):
        batch, parts = _hyp_batch(data.draw)
        cfg = AnalyzerConfig(
            num_partitions=parts, batch_size=96, enable_quantiles=True,
            quantiles_per_partition=data.draw(st.booleans()),
        )
        ref_counts, ref_qt = _reference_tables(batch, cfg)
        for nat in ([False, True] if use_native else [False]):
            got = unpack_numpy(
                pack_batch(batch, cfg, use_native=nat).copy(), cfg
            )
            assert np.array_equal(np.asarray(got["counts"]), ref_counts), nat
            assert np.array_equal(np.asarray(got["qcounts"]), ref_qt), nat

    run()


# ---------------------------------------------------------------------------
# packer units


def _rand_batch(seed: int, n: int, parts: int) -> RecordBatch:
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
    return batch


def test_v5_native_rows_equal_numpy_rows():
    """Native and numpy packers agree byte-for-byte on every row; the
    alive pairs (outside the row) agree up to their documented ordering
    difference (compared as maps, counts exact)."""
    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    if not native.native_available():
        pytest.skip("native shim unavailable")
    batch = _rand_batch(2, 500, 4)
    for kw in ({}, {"enable_hll": True, "hll_p": 8},
               {"distinct_keys_per_partition": True, "hll_p": 14},
               {"enable_quantiles": True, "quantiles_per_partition": True},
               {"count_alive_keys": True, "alive_bitmap_bits": 14}):
        cfg = AnalyzerConfig(num_partitions=4, batch_size=500, **kw)
        a = pack_batch(batch, cfg, use_native=False)
        b = pack_batch(batch, cfg, use_native=True)
        assert np.array_equal(a, b), kw
    # alive pairs: pair order differs (sorted vs first-touch).
    sa, fa = batch_alive_pairs(batch, cfg, use_native=False)
    sb, fb = batch_alive_pairs(batch, cfg, use_native=True)
    assert len(sa) == len(sb) > 0
    assert dict(zip(sa.tolist(), fa.tolist())) == dict(
        zip(sb.tolist(), fb.tolist())
    )


def test_v5_empty_batch_is_identity_pad():
    """A packed empty v5 batch is the superbatch identity pad: zero
    counter/quantile tables, identity-filled extremes."""
    cfg = _cfg()
    buf = pack_batch(RecordBatch.empty(0), cfg, use_native=False)
    got = unpack_numpy(buf, cfg)
    assert int(got["n_valid"]) == 0
    assert not np.asarray(got["counts"]).any()
    assert not np.asarray(got["qcounts"]).any()
    assert (np.asarray(got["ts_min"]) == np.iinfo(np.int64).max).all()
    assert (np.asarray(got["sz_max"]) == 0).all()


def test_section_byte_split_sums_to_packed_nbytes():
    for kw in ({}, {"count_alive_keys": True},
               {"enable_quantiles": True, "quantiles_per_partition": True},
               {"distinct_keys_per_partition": True, "hll_p": 14}):
        cfg = AnalyzerConfig(num_partitions=7, batch_size=256, **kw)
        per_rec, table = section_byte_split(cfg, 256)
        assert per_rec + table == packed_nbytes(cfg, 256), kw
    # Without HLL pairs a row ships NO per-record bytes at all.
    cfg = AnalyzerConfig(num_partitions=7, batch_size=256,
                         count_alive_keys=True, enable_quantiles=True)
    per_rec, table = section_byte_split(cfg, 256)
    assert per_rec == 0 and table == packed_nbytes(cfg, 256)


def test_ddsketch_edges_match_device_buckets():
    """The native packers' quantile tables — the whole-batch packer and
    the fused sink's column append — bucket every size exactly like the
    numpy edge-table rule, at exact edge values, edge+1, and 0."""
    from kafka_topic_analyzer_tpu.ops.ddsketch import (
        ddsketch_bucket_numpy,
        ddsketch_edges,
        ddsketch_num_buckets,
    )
    from kafka_topic_analyzer_tpu.packing import FusedPackSink

    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    if not native.native_available():
        pytest.skip("native shim unavailable")
    gamma, nbuckets = (1.0 + 0.005) / (1.0 - 0.005), 2560
    edges = ddsketch_edges(gamma, nbuckets)
    reachable = edges[edges < 2**31 - 1]  # a value length is an int32
    probe = np.unique(np.concatenate([
        np.array([0, 1, 2, 3], dtype=np.int64),
        edges[:200], edges[:200] + 1,
        reachable[-3:], reachable[-3:] + 1,
    ]))
    n = len(probe)
    batch = RecordBatch(
        partition=np.zeros(n, dtype=np.int32),
        key_len=np.zeros(n, dtype=np.int32),
        value_len=probe.astype(np.int32),
        key_null=np.ones(n, dtype=bool),
        value_null=np.zeros(n, dtype=bool),
        ts_s=np.zeros(n, dtype=np.int64),
        key_hash32=np.zeros(n, dtype=np.uint32),
        key_hash64=np.zeros(n, dtype=np.uint64),
        valid=np.ones(n, dtype=bool),
    )
    cfg = AnalyzerConfig(num_partitions=1, batch_size=n,
                         enable_quantiles=True)
    ref = np.zeros((1, ddsketch_num_buckets(nbuckets)), dtype=np.int64)
    np.add.at(ref[0], ddsketch_bucket_numpy(probe, gamma, nbuckets), 1)
    whole = unpack_numpy(pack_batch(batch, cfg, use_native=True), cfg)
    assert np.array_equal(whole["qcounts"], ref)
    sink = FusedPackSink(cfg, n, dense_of=lambda p: p)
    sink.append_batch(batch, reason="frame-fallback")
    sink.flush()
    (row,) = sink.take_completed()
    assert np.array_equal(unpack_numpy(row.buf, cfg)["qcounts"], ref)


# ---------------------------------------------------------------------------
# gating, telemetry, stats


def _metric_total(name: str) -> float:
    m = default_registry().snapshot().get(name)
    return sum(s["value"] for s in m["samples"]) if m else 0.0


def test_v4_fallback_booked_and_wire_bytes_counted():
    before_bytes = _metric_total("kta_wire_bytes_total")
    result, _ = _wire_scan()
    grew = _metric_total("kta_wire_bytes_total") - before_bytes
    assert grew > 0
    assert result.wire is not None
    assert result.wire.format == 5
    assert result.wire.bytes_total == int(grew)
    assert result.wire.records == N_PARTS * N_REC
    assert result.wire.bytes_per_record > 0


def test_stats_wire_line_renders():
    from kafka_topic_analyzer_tpu.report import render_telemetry_stats

    result, _ = _wire_scan()
    text = render_telemetry_stats(
        result.telemetry, wire=result.wire,
    )
    assert "wire-format: v5" in text
    assert "fold-table" in text
    # This config's rows are all fold tables: the alive pairs travel in
    # the dispatch's table and HLL ships as a register table.
    assert result.wire.table_bytes > 0 and result.wire.per_record_bytes == 0


def test_scan_v5_with_native_disabled_subprocess():
    """KTA_DISABLE_NATIVE: the v5 scan runs the pure-python packers end to
    end — wire v5 is a layout, not a native-shim dependency."""
    code = (
        "import os; os.environ['JAX_PLATFORMS']='cpu';"
        "from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec;"
        "from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend;"
        "from kafka_topic_analyzer_tpu.config import AnalyzerConfig;"
        "from kafka_topic_analyzer_tpu.engine import run_scan;"
        "spec = SyntheticSpec(num_partitions=2, messages_per_partition=50, keys_per_partition=9, seed=3);"
        "cfg = AnalyzerConfig(num_partitions=2, batch_size=32, enable_quantiles=True);"
        "r = run_scan('t', SyntheticSource(spec), TpuBackend(cfg, init_now_s=0, use_native=False), 32);"
        "assert r.metrics.overall_count == 100, r.metrics.overall_count;"
        "assert r.wire.format == 5"
    )
    env = dict(os.environ, KTA_DISABLE_NATIVE="1")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr
