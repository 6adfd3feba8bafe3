"""Wire-v5 combiner rows (ISSUE 9): host pre-reduced per-partition fold
tables replace the last per-record columns.

The byte-identity bar has two layers:

- TABLE bytes: the combiner tables a packer emits (counter deltas,
  DDSketch buckets, extremes) must equal a straight numpy reference
  reduction over the same records — native and numpy packers alike
  (the hypothesis property test mirrors the PR-8 row-bytes parity suite).
- SCAN results: a v5 scan's full document must equal the v4 scan's across
  (wire, segfile) × workers × K × mesh, including corruption/quarantine
  parity and v4↔v5 cross-format resume.
"""

from __future__ import annotations

import dataclasses
import os
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
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.obs.registry import default_registry
from kafka_topic_analyzer_tpu.packing import (
    pack_batch,
    packed_nbytes,
    section_byte_split,
    unpack_numpy,
)
from kafka_topic_analyzer_tpu.records import RecordBatch

from fake_broker import CorruptionInjector, FakeBroker

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


def _cfg(wire_format: int, **kw) -> AnalyzerConfig:
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
    return AnalyzerConfig(wire_format=wire_format, **base)


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


def _wire_scan(wire_format, workers=1, superbatch=1, backend_cls=TpuBackend,
               mesh=None, **cfg_kw):
    cfg = _cfg(wire_format, **cfg_kw)
    if mesh is not None:
        cfg = dataclasses.replace(cfg, mesh_shape=mesh)
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        src = KafkaWireSource(
            f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
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
def wire_v4_baseline():
    """The v4 scan — the byte-exact referee for every v5 configuration."""
    return _full_doc(_wire_scan(4))


# ---------------------------------------------------------------------------
# scan-level identity: (wire) × workers × K × mesh


@pytest.mark.parametrize("workers,superbatch", [
    (1, 1), (4, 1), (1, 4), (4, 4),
])
def test_v5_wire_scan_identical(wire_v4_baseline, workers, superbatch):
    result = _wire_scan(5, workers=workers, superbatch=superbatch)
    assert _full_doc(result) == wire_v4_baseline
    assert result.wire is not None and result.wire.format == 5


@pytest.mark.parametrize("mesh,superbatch", [((2, 1), 1), ((2, 1), 4)])
def test_v5_sharded_scan_identical(wire_v4_baseline, mesh, superbatch):
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    for wf in (4, 5):
        result = _wire_scan(wf, mesh=mesh, superbatch=superbatch,
                            backend_cls=ShardedTpuBackend)
        assert _full_doc(result) == wire_v4_baseline, wf


def test_v5_flat_hll_pair_mode_scan_identical(wire_v4_baseline):
    """Per-partition HLL in PAIR mode (the one v5 section that cannot ride
    unchanged — idx32 carries the register row).  hll_p=14 at B=128 forces
    pair mode; referee is the v4 scan of the same config."""
    a = _full_doc(_wire_scan(4, distinct_keys_per_partition=True, hll_p=14))
    b = _full_doc(_wire_scan(5, distinct_keys_per_partition=True, hll_p=14))
    assert a == b
    assert a != wire_v4_baseline  # the per-partition rows actually differ


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

    def scan(wf, workers=1):
        cfg = AnalyzerConfig(
            num_partitions=3, batch_size=128, count_alive_keys=True,
            alive_bitmap_bits=14, enable_hll=True, hll_p=8,
            enable_quantiles=True, wire_format=wf,
        )
        s = SegmentFileSource(d, "seg.topic")
        r = run_scan("seg.topic", s, TpuBackend(cfg, init_now_s=10**10),
                     128, ingest_workers=workers)
        return _full_doc(r)

    base = scan(4)
    assert scan(5) == base
    assert scan(5, workers=2) == base
    assert scan(4, workers=2) == base


# ---------------------------------------------------------------------------
# corruption parity


def test_v5_corruption_quarantine_parity(tmp_path):
    """Deterministic poison under --on-corruption=quarantine: the v5 scan
    classifies, accounts, and quarantines EXACTLY like the v4 scan."""
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

    def run(wf, qdir):
        cfg = _cfg(wf)
        with poisoned() as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC,
                overrides=dict(FAST_RETRY, **{"check.crcs": "true"}),
                corruption=CorruptionConfig(
                    policy="quarantine", quarantine_dir=qdir
                ),
            )
            r = run_scan(TOPIC, src, TpuBackend(cfg, init_now_s=10**10), 128)
            spans = src.corruption_spans()
            src.close()
        return _full_doc(r), spans

    doc4, spans4 = run(4, str(tmp_path / "q4"))
    doc5, spans5 = run(5, str(tmp_path / "q5"))
    assert doc5 == doc4
    assert sorted(doc5["corrupt"]) == [1, 2]
    assert spans5 == spans4
    assert sorted(os.listdir(tmp_path / "q5")) == sorted(
        os.listdir(tmp_path / "q4")
    )


# ---------------------------------------------------------------------------
# cross-format resume


class _Interrupt(Exception):
    pass


class _InterruptingSource(SyntheticSource):
    def __init__(self, spec, limit):
        super().__init__(spec)
        self.limit = limit

    def batches(self, batch_size, partitions=None, start_at=None):
        it = super().batches(batch_size, partitions, start_at)
        for i, b in enumerate(it):
            if start_at is None and i >= self.limit:
                raise _Interrupt()
            yield b


RESUME_SPEC = SyntheticSpec(
    num_partitions=3, messages_per_partition=2_000, keys_per_partition=80,
    tombstone_permille=150, seed=31,
)


@pytest.mark.parametrize("wf_first,wf_second", [(4, 5), (5, 4)])
def test_cross_format_resume(tmp_path, wf_first, wf_second):
    """A snapshot taken mid-scan under one wire format resumes under the
    other, reproducing the uninterrupted scan exactly — the format is
    execution strategy, outside the checkpoint fingerprint."""
    cfg_first = AnalyzerConfig(
        num_partitions=3, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=18, enable_hll=True, hll_p=10,
        enable_quantiles=True, wire_format=wf_first,
    )
    cfg_second = dataclasses.replace(cfg_first, wire_format=wf_second)
    full = run_scan(
        "t", SyntheticSource(RESUME_SPEC),
        TpuBackend(cfg_second, init_now_s=10**10), 512,
    ).metrics.to_dict(None, None)

    with pytest.raises(_Interrupt):
        run_scan(
            "t", _InterruptingSource(RESUME_SPEC, limit=5),
            TpuBackend(cfg_first, init_now_s=10**10), 512,
            snapshot_dir=str(tmp_path), snapshot_every_s=0.0,
        )
    resumed = run_scan(
        "t", SyntheticSource(RESUME_SPEC),
        TpuBackend(cfg_second, init_now_s=0), 512,
        snapshot_dir=str(tmp_path), resume=True,
    )
    assert resumed.metrics.to_dict(None, None) == full


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
            wire_format=5,
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
    """Native and numpy v5 packers agree byte-for-byte on every section
    except the alive pairs' documented ordering difference (compared as
    sets, counts exact)."""
    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    if not native.native_available():
        pytest.skip("native shim unavailable")
    batch = _rand_batch(2, 500, 4)
    for kw in ({}, {"enable_hll": True, "hll_p": 8},
               {"distinct_keys_per_partition": True, "hll_p": 14},
               {"enable_quantiles": True, "quantiles_per_partition": True}):
        cfg = AnalyzerConfig(
            num_partitions=4, batch_size=500, wire_format=5, **kw
        )
        a = pack_batch(batch, cfg, use_native=False)
        b = pack_batch(batch, cfg, use_native=True)
        assert np.array_equal(a, b), kw
    # alive combo: pair order differs (sorted vs first-touch).  Pinned to
    # --alive-compaction off: this asserts the UNCOMPACTED v5 pair-section
    # layout (the compacted rows — no pair sections at all — are covered
    # by tests/test_alive_compaction.py).
    cfg = AnalyzerConfig(num_partitions=4, batch_size=500, wire_format=5,
                         count_alive_keys=True, alive_bitmap_bits=14,
                         alive_compaction="off")
    ua = unpack_numpy(pack_batch(batch, cfg, use_native=False).copy(), cfg)
    ub = unpack_numpy(pack_batch(batch, cfg, use_native=True).copy(), cfg)
    np_pairs = int(ua["n_pairs"])
    assert np_pairs == int(ub["n_pairs"])
    assert dict(zip(ua["alive_slot"][:np_pairs].tolist(),
                    ua["alive_flag"][:np_pairs].tolist())) == dict(
        zip(ub["alive_slot"][:np_pairs].tolist(),
            ub["alive_flag"][:np_pairs].tolist()))
    assert np.array_equal(np.asarray(ua["counts"]), np.asarray(ub["counts"]))


def test_v5_empty_batch_is_identity_pad():
    """A packed empty v5 batch is the superbatch identity pad: zero
    counter/quantile tables, identity-filled extremes."""
    cfg = _cfg(5)
    buf = pack_batch(RecordBatch.empty(0), cfg, use_native=False)
    got = unpack_numpy(buf, cfg)
    assert int(got["n_valid"]) == 0
    assert not np.asarray(got["counts"]).any()
    assert not np.asarray(got["qcounts"]).any()
    assert (np.asarray(got["ts_min"]) == np.iinfo(np.int64).max).all()
    assert (np.asarray(got["sz_max"]) == 0).all()


def test_section_byte_split_sums_to_packed_nbytes():
    for wf in (4, 5):
        for kw in ({}, {"count_alive_keys": True},
                   {"enable_quantiles": True, "quantiles_per_partition": True}):
            cfg = AnalyzerConfig(num_partitions=7, batch_size=256,
                                 wire_format=wf, **kw)
            per_rec, table = section_byte_split(cfg, 256)
            assert per_rec + table == packed_nbytes(cfg, 256), (wf, kw)
    # v5 without the alive pairs ships NO per-record bytes at all.
    cfg = AnalyzerConfig(num_partitions=7, batch_size=256, wire_format=5)
    per_rec, table = section_byte_split(cfg, 256)
    assert per_rec == 0 and table == packed_nbytes(cfg, 256)


def test_pallas_counters_merge_exact():
    """The v5 pallas table-merge (u32 digit planes + carry) is exact for
    adversarial i64 values — carries across the 2^32 boundary, negative
    sentinels, INT64 extremes."""
    from kafka_topic_analyzer_tpu.ops.pallas_counters import (
        pallas_counters_merge,
    )

    rng = np.random.default_rng(9)
    a = rng.integers(-2**62, 2**62, size=(37, 7), dtype=np.int64)
    b = rng.integers(-2**62, 2**62, size=(37, 7), dtype=np.int64)
    a[0, 0] = (1 << 32) - 1
    b[0, 0] = 1  # lo-word carry
    a[0, 1] = -1
    b[0, 1] = 1
    a[0, 2] = np.iinfo(np.int64).max
    b[0, 2] = np.iinfo(np.int64).min
    got = np.asarray(pallas_counters_merge(a, b, interpret=True))
    assert np.array_equal(got, a + b)


def test_ddsketch_edges_match_device_buckets():
    """The integer edge table and the device update agree on every bucket
    — including exact edge values, edge+1, and 0."""
    import jax

    from kafka_topic_analyzer_tpu.jax_support import jnp
    from kafka_topic_analyzer_tpu.ops.ddsketch import (
        ddsketch_bucket_numpy,
        ddsketch_edges,
        ddsketch_update,
    )

    gamma, nbuckets = (1.0 + 0.005) / (1.0 - 0.005), 2560
    edges = ddsketch_edges(gamma, nbuckets)
    probe = np.unique(np.concatenate([
        np.array([0, 1, 2, 3], dtype=np.int64),
        edges[:200], edges[:200] + 1,
        np.array([int(edges[-1]), int(edges[-1]) + 1], dtype=np.int64),
    ]))
    host = ddsketch_bucket_numpy(probe, gamma, nbuckets)
    counts = jnp.zeros((1, nbuckets + 2), dtype=jnp.int64)
    dev = np.asarray(jax.jit(
        lambda c, s: ddsketch_update(
            c, s, jnp.ones(len(probe), dtype=bool), gamma, nbuckets
        )
    )(counts, jnp.asarray(probe)))[0]
    ref = np.zeros(nbuckets + 2, dtype=np.int64)
    np.add.at(ref, host, 1)
    assert np.array_equal(dev, ref)


# ---------------------------------------------------------------------------
# gating, telemetry, stats


def test_env_kill_switch_forces_v4(monkeypatch):
    monkeypatch.setenv("KTA_WIRE_V4", "1")
    cfg = AnalyzerConfig(num_partitions=2, batch_size=64)
    assert cfg.wire_format == 4
    assert cfg.wire_v4_reason == "env-kill-switch"
    monkeypatch.delenv("KTA_WIRE_V4")
    assert AnalyzerConfig(num_partitions=2, batch_size=64).wire_format == 5
    explicit = AnalyzerConfig(num_partitions=2, batch_size=64, wire_format=4)
    assert explicit.wire_v4_reason == "explicit"
    with pytest.raises(ValueError, match="wire_format"):
        AnalyzerConfig(num_partitions=2, batch_size=64, wire_format=3)


def _metric_total(name: str) -> float:
    m = default_registry().snapshot().get(name)
    return sum(s["value"] for s in m["samples"]) if m else 0.0


def test_v4_fallback_booked_and_wire_bytes_counted():
    before_fb = _metric_total("kta_wire_v4_fallback_total")
    before_bytes = _metric_total("kta_wire_bytes_total")
    result = _wire_scan(4)
    assert _metric_total("kta_wire_v4_fallback_total") == before_fb + 1
    grew = _metric_total("kta_wire_bytes_total") - before_bytes
    assert grew > 0
    assert result.wire is not None
    assert result.wire.format == 4
    assert result.wire.bytes_total == int(grew)
    assert result.wire.records == N_PARTS * N_REC
    assert result.wire.bytes_per_record > 0


def test_stats_wire_line_renders():
    from kafka_topic_analyzer_tpu.report import render_telemetry_stats

    result = _wire_scan(5)
    text = render_telemetry_stats(
        result.telemetry, wire=result.wire,
    )
    assert "wire-format: v5" in text
    assert "fold-table" in text
    # v5's fold tables dominate this config's buffers (only the alive
    # pairs remain per-record).
    assert result.wire.table_bytes > 0


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
        "assert cfg.wire_format == 5;"
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
