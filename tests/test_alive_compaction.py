"""Host-side LWW alive-pair compaction (DESIGN §19): alive pairs leave
the rows and ship as ONE LWW-merged per-dispatch table applied after the
scan.

The identity bar: a scan's full document must equal the CPU oracle's
(tests/oracle.py) across (wire, segfile) × workers × K × mesh, under
corruption/quarantine rewind, and across follow passes.  The algebra bar:
host compaction ∘ device merge must equal the per-record fold over
generated update streams — duplicate slots within and across frames,
tombstone↔set flips, arbitrary batch and superbatch splits — for BOTH the
native and numpy packers (the hypothesis property test).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
from kafka_topic_analyzer_tpu.config import (
    AnalyzerConfig,
    CorruptionConfig,
    DispatchConfig,
    FollowConfig,
)
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.obs.registry import default_registry
from kafka_topic_analyzer_tpu.packing import (
    batch_alive_pairs,
    pack_pair_table,
    packed_nbytes,
    pair_table_capacity,
    pair_table_nbytes,
    unpack_pair_table_numpy,
)
from kafka_topic_analyzer_tpu.records import RecordBatch

from fake_broker import CorruptionInjector, FakeBroker
from oracle import sketched

pytestmark = pytest.mark.alivecompact

TOPIC = "compact.topic"

FAST_RETRY = {
    "retry.backoff.ms": "5",
    "reconnect.backoff.max.ms": "40",
}


def _mk_records(partition: int, n: int):
    # Dense key reuse + frequent tombstones: the LWW order-sensitivity
    # this feature must preserve, and real cross-batch duplication for
    # the compaction ratio to bite on.
    return [
        (
            i,
            1_600_000_000_000 + i * 1000,
            f"k{partition}-{i % 17}".encode() if i % 5 else None,
            bytes(20 + (i % 13)) if i % 3 else None,
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
def alive_oracle():
    """The CPU oracle's document — the referee for every device scan."""
    return _wire_scan(backend_cls=CpuExactBackend)[1]


# ---------------------------------------------------------------------------
# scan-level identity: (wire) × workers × K × mesh


@pytest.mark.parametrize("workers,superbatch", [
    (1, 1), (4, 1), (1, 4), (4, 4),
])
def test_compacted_wire_scan_identical(alive_oracle, workers, superbatch):
    result, doc = _wire_scan(workers=workers, superbatch=superbatch)
    assert doc == alive_oracle
    assert result.wire is not None
    assert result.wire.alive_compaction == "on"
    assert result.wire.pairs_emitted > 0
    assert result.wire.pairs_raw >= result.wire.pairs_emitted
    if superbatch > 1 and workers == 1:
        # Cross-batch dedupe only exists at K>1, and only when one
        # dispatch sees the same partition more than once (the 4-worker
        # fan-in gives each superbatch one batch per partition — disjoint
        # key spaces, honestly ratio 1.0).  Sequential ingest repeats the
        # 17-key cycle within a superbatch, so the ratio must bite here.
        assert result.wire.compaction_ratio < 1.0


@pytest.mark.parametrize("mesh,superbatch", [
    ((2, 1), 1), ((2, 1), 4), ((2, 2), 1), ((2, 2), 4),
])
def test_compacted_sharded_scan_identical(alive_oracle, mesh, superbatch):
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    _, doc = _wire_scan(mesh=mesh, superbatch=superbatch,
                        backend_cls=ShardedTpuBackend)
    assert doc == alive_oracle, mesh


def _lww_map(batch, bits: int) -> dict:
    """Per-slot last writer of one batch, replayed record by record."""
    out = {}
    for i in range(batch.num_valid):
        if not batch.key_null[i]:
            slot = int(batch.key_hash32[i]) & ((1 << bits) - 1)
            out[slot] = 0 if batch.value_null[i] else 1
    return out


def test_compacted_rows_drop_pair_sections():
    """The rows carry NO alive sections — counting alive keys adds no row
    bytes — and the per-dispatch table is the only place pairs travel,
    holding exactly the batch's per-slot last writers."""
    from kafka_topic_analyzer_tpu.packing import _sections

    cfg_on = _cfg()
    names_on = {n for n, _, _ in _sections(cfg_on, 128)}
    assert not {n for n in names_on if n.startswith("alive")}
    assert packed_nbytes(cfg_on, 128) == packed_nbytes(
        _cfg(count_alive_keys=False), 128
    )

    spec = SyntheticSpec(
        num_partitions=2, messages_per_partition=200,
        keys_per_partition=11, tombstone_permille=300, seed=9,
    )
    batch = next(SyntheticSource(spec).batches(128))
    want = _lww_map(batch, cfg_on.alive_bitmap_bits)
    for use_native in (False, True):
        if use_native:
            native = pytest.importorskip(
                "kafka_topic_analyzer_tpu.io.native"
            )
            if not native.native_available():
                pytest.skip("native shim unavailable")
        cap = pair_table_capacity(cfg_on, 128, 1)
        tbl, raw, emitted = pack_pair_table(
            [batch_alive_pairs(batch, cfg_on, use_native)],
            cfg_on, cap, use_native=use_native,
        )
        assert tbl.nbytes == pair_table_nbytes(cfg_on, cap)
        ut = unpack_pair_table_numpy(tbl, cfg_on, cap)
        assert int(ut["n_pairs"]) == len(want) == emitted
        # bits=16 picks the MASK form: reconstruct the per-slot LWW map
        # from the set/clear words and compare against the replay.
        assert "alive_set" in ut
        got = {}
        for w, (sw, cw) in enumerate(zip(
            np.asarray(ut["alive_set"]).tolist(),
            np.asarray(ut["alive_clear"]).tolist(),
        )):
            for bit in range(32):
                if sw & (1 << bit):
                    got[w * 32 + bit] = 1
                elif cw & (1 << bit):
                    got[w * 32 + bit] = 0
        assert got == want


# ---------------------------------------------------------------------------
# segfile cold path


def test_compacted_segfile_scan_identical(tmp_path):
    from kafka_topic_analyzer_tpu.io.segfile import (
        SegmentDumpWriter,
        SegmentFileSource,
    )

    spec = SyntheticSpec(
        num_partitions=3, messages_per_partition=700, keys_per_partition=40,
        seed=5, key_null_permille=60, tombstone_permille=200,
    )
    d = str(tmp_path / "segs")
    writer = SegmentDumpWriter(d, "seg.topic", records_per_chunk=256)
    src = SyntheticSource(spec)
    writer.set_base_offsets(src.watermarks()[0])
    for b in src.batches(180):
        writer.append(b)
    writer.close()

    def scan(backend_cls=TpuBackend, workers=1, superbatch=1):
        cfg = AnalyzerConfig(
            num_partitions=3, batch_size=128, count_alive_keys=True,
            alive_bitmap_bits=14, enable_hll=True, hll_p=8,
        )
        s = SegmentFileSource(d, "seg.topic")
        backend = _backend(cfg, backend_cls, superbatch)
        r = run_scan("seg.topic", s, backend, 128, ingest_workers=workers)
        return _doc(r, backend)

    base = scan(CpuExactBackend)
    assert scan() == base
    assert scan(workers=2) == base
    assert scan(superbatch=4) == base


# ---------------------------------------------------------------------------
# corruption / quarantine rewind parity


def test_compacted_corruption_quarantine_parity(tmp_path):
    """Deterministic poison under --on-corruption=quarantine: the device
    scan classifies, accounts, and quarantines EXACTLY like the oracle —
    frame rewind must leave the pair emission region as atomic as the row
    sections (pairs only emit after a frame validates)."""
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

    def run(backend_cls, qdir, superbatch=1):
        cfg = _cfg()
        with poisoned() as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC,
                overrides=dict(FAST_RETRY, **{"check.crcs": "true"}),
                corruption=CorruptionConfig(
                    policy="quarantine", quarantine_dir=qdir
                ),
            )
            backend = _backend(cfg, backend_cls, superbatch)
            r = run_scan(TOPIC, src, backend, 128)
            spans = src.corruption_spans()
            src.close()
        return _doc(r, backend), spans

    doc_ref, spans_ref = run(CpuExactBackend, str(tmp_path / "qref"))
    doc_on, spans_on = run(TpuBackend, str(tmp_path / "qon"))
    doc_on_k, spans_on_k = run(TpuBackend, str(tmp_path / "qonk"),
                               superbatch=4)
    assert doc_on == doc_ref
    assert doc_on_k == doc_ref
    assert sorted(doc_on["corrupt"]) == [1, 2]
    assert spans_on == spans_ref == spans_on_k
    assert sorted(os.listdir(tmp_path / "qon")) == sorted(
        os.listdir(tmp_path / "qref")
    )


# ---------------------------------------------------------------------------
# follow mode: pass-chained folds == batch scan


def _wait_for(predicate, timeout_s=30.0, interval_s=0.01, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(interval_s)
    raise AssertionError(f"timed out waiting for {what}")


def test_follow_compacted_matches_batch():
    from kafka_topic_analyzer_tpu.serve.follow import FollowService

    phase1 = {p: RECORDS[p][:200] for p in range(N_PARTS)}
    phase2 = {p: RECORDS[p][200:] for p in range(N_PARTS)}
    total = N_PARTS * N_REC

    def followed():
        cfg = _cfg(batch_size=64)
        follow = FollowConfig(
            poll_interval_s=0.02, idle_backoff_max_s=0.05, window_count=0
        )
        with FakeBroker(TOPIC, phase1, max_records_per_fetch=48) as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            )
            svc = FollowService(
                TOPIC, src,
                TpuBackend(cfg, init_now_s=10**10,
                           dispatch=DispatchConfig(superbatch=4)),
                64, follow,
            )
            errors = []

            def folded():
                doc = svc.state.snapshot()
                return doc["overall"]["count"] if doc else -1

            def driver():
                try:
                    _wait_for(
                        lambda: folded() >= N_PARTS * 200,
                        what="phase-1 fold",
                    )
                    for p in range(N_PARTS):
                        broker.produce(p, phase2[p])
                    _wait_for(
                        lambda: folded() >= total, what="phase-2 fold"
                    )
                except BaseException as e:
                    errors.append(e)
                finally:
                    svc.request_stop("test")

            t = threading.Thread(target=driver)
            t.start()
            result = svc.run()
            t.join()
            src.close()
            if errors:
                raise errors[0]
        return result.metrics.to_dict(None, None)

    want = _wire_scan(backend_cls=CpuExactBackend)[1]["metrics"]
    for row in want["partitions"].values():
        del row["start_offset"], row["end_offset"]
    assert followed() == want


# ---------------------------------------------------------------------------
# compaction algebra: host compaction ∘ device merge ≡ per-record fold
# (hypothesis property test, both packers)


def _reference_alive_count(stream, bits):
    """Pure-python per-record LWW replay: the metric's DEFINITION."""
    alive = {}
    mask = (1 << bits) - 1
    for batch in stream:
        nv = batch.num_valid
        for i in range(nv):
            if batch.key_null[i]:
                continue
            alive[int(batch.key_hash32[i]) & mask] = not batch.value_null[i]
    return sum(1 for v in alive.values() if v)


def _bitmap_words(table_groups, cfg):
    """Apply per-dispatch compacted tables in order through the DEVICE
    merge — pair-scatter or elementwise-mask form, exactly as
    backends.step.apply_pair_table dispatches on the section names."""
    from kafka_topic_analyzer_tpu.jax_support import jnp
    from kafka_topic_analyzer_tpu.ops.bitmap import (
        bitmap_apply_masks,
        bitmap_apply_pairs,
        bitmap_num_words,
        bitmap_popcount,
    )

    words = jnp.zeros(
        (bitmap_num_words(cfg.alive_bitmap_bits),), dtype=jnp.uint32
    )
    for ut in table_groups:
        if "alive_set" in ut:
            words = bitmap_apply_masks(
                words,
                jnp.asarray(np.asarray(ut["alive_set"])),
                jnp.asarray(np.asarray(ut["alive_clear"])),
                bits=cfg.alive_bitmap_bits,
            )
        else:
            words = bitmap_apply_pairs(
                words,
                jnp.asarray(np.asarray(ut["alive_slot"])),
                jnp.asarray(np.asarray(ut["alive_flag"])),
                jnp.int32(int(ut["n_pairs"])),
                bits=cfg.alive_bitmap_bits,
            )
    return int(bitmap_popcount(words))


def _stream_batch(parts, records):
    n = len(records)
    key_null = np.array([r[0] is None for r in records], dtype=bool)
    value_null = np.array([r[1] for r in records], dtype=bool)
    h32 = np.array([0 if r[0] is None else r[0] for r in records],
                   dtype=np.uint32)
    return RecordBatch(
        partition=np.zeros(n, dtype=np.int32),
        key_len=np.where(key_null, 0, 3).astype(np.int32),
        value_len=np.where(value_null, 0, 5).astype(np.int32),
        key_null=key_null,
        value_null=value_null,
        ts_s=np.arange(n, dtype=np.int64),
        key_hash32=h32,
        key_hash64=h32.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15),
        valid=np.ones(n, dtype=bool),
    )


def test_compaction_algebra_matches_per_record_fold():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    use_native = native.native_available()

    @settings(max_examples=50, deadline=None)
    @given(st.data())
    def run(data):
        bits = data.draw(st.integers(min_value=3, max_value=8))
        # A stream of (key-hash-or-None, tombstone?) updates over a TINY
        # slot space: duplicates within and across batches, tombstone↔set
        # flips, guaranteed.
        n = data.draw(st.integers(min_value=0, max_value=120))
        updates = [
            (
                None
                if data.draw(st.booleans()) and data.draw(st.booleans())
                else data.draw(st.integers(0, 2**32 - 1)),
                data.draw(st.booleans()),
            )
            for _ in range(n)
        ]
        # Arbitrary batch split, then arbitrary superbatch (dispatch)
        # grouping of those batches.
        cuts = sorted(data.draw(
            st.lists(st.integers(0, n), max_size=6)
        )) + [n]
        batches, lo = [], 0
        for hi in cuts:
            if hi > lo:
                batches.append(_stream_batch(1, updates[lo:hi]))
                lo = hi
        k = data.draw(st.integers(min_value=1, max_value=4))
        # bits 26 forces the bounded PAIR form at this tiny capacity;
        # small bits take the mask form — both kernels must agree.
        if data.draw(st.booleans()) and data.draw(st.booleans()):
            bits = 26
        cfg = AnalyzerConfig(
            num_partitions=1, batch_size=128, count_alive_keys=True,
            alive_bitmap_bits=bits,
        )
        ref = _reference_alive_count(batches, bits)
        for nat in ([False, True] if use_native else [False]):
            groups = []
            for g in range(0, len(batches), k):
                cap = pair_table_capacity(cfg, 128, k)
                tbl, _, _ = pack_pair_table(
                    [
                        batch_alive_pairs(b, cfg, use_native=nat)
                        for b in batches[g : g + k]
                    ],
                    cfg, cap, use_native=nat,
                )
                groups.append(unpack_pair_table_numpy(tbl, cfg, cap))
            assert _bitmap_words(groups, cfg) == ref, (nat, bits, k)

    run()


def test_compaction_algebra_seeded_sweep():
    """Seeded twin of the hypothesis property above — the same
    compaction ∘ merge ≡ per-record-fold check runs even where the
    hypothesis package is absent (tier-1 containers)."""
    try:
        from kafka_topic_analyzer_tpu.io.native import native_available

        use_native = native_available()
    except ImportError:
        use_native = False
    rng = np.random.default_rng(0xC0FFEE)
    for trial in range(40):
        bits = int(rng.integers(3, 9))
        n = int(rng.integers(0, 121))
        updates = [
            (
                None if rng.random() < 0.2 else int(rng.integers(0, 2**32)),
                bool(rng.random() < 0.4),
            )
            for _ in range(n)
        ]
        cuts = sorted(rng.integers(0, n + 1, size=int(rng.integers(0, 6))).tolist()) + [n]
        batches, lo = [], 0
        for hi in cuts:
            if hi > lo:
                batches.append(_stream_batch(1, updates[lo:hi]))
                lo = hi
        k = int(rng.integers(1, 5))
        if trial % 8 == 7:
            bits = 26  # the bounded PAIR form (masks past the trade cap)
        cfg = AnalyzerConfig(
            num_partitions=1, batch_size=128, count_alive_keys=True,
            alive_bitmap_bits=bits,
        )
        from kafka_topic_analyzer_tpu.packing import alive_table_mode
        assert alive_table_mode(cfg, pair_table_capacity(cfg, 128, k)) == (
            1 if bits == 26 else 2
        )
        ref = _reference_alive_count(batches, bits)
        for nat in ([False, True] if use_native else [False]):
            groups = []
            for g in range(0, len(batches), k):
                cap = pair_table_capacity(cfg, 128, k)
                tbl, _, _ = pack_pair_table(
                    [
                        batch_alive_pairs(b, cfg, use_native=nat)
                        for b in batches[g : g + k]
                    ],
                    cfg, cap, use_native=nat,
                )
                groups.append(unpack_pair_table_numpy(tbl, cfg, cap))
            assert _bitmap_words(groups, cfg) == ref, (trial, nat, bits, k)


# ---------------------------------------------------------------------------
# gating, kill switches, accounting


def _metric_total(name: str) -> float:
    m = default_registry().snapshot().get(name)
    return sum(s["value"] for s in m["samples"]) if m else 0.0


def test_compaction_resolution_and_kill_switches(monkeypatch):
    """Compaction runs iff the scan counts alive keys (-c): no option or
    env variable selects anything else."""
    def scan(**kw):
        spec = SyntheticSpec(num_partitions=2, messages_per_partition=100,
                             keys_per_partition=9, seed=4)
        cfg = AnalyzerConfig(num_partitions=2, batch_size=64, **kw)
        return run_scan("t", SyntheticSource(spec),
                        TpuBackend(cfg, init_now_s=10**10), 64)

    monkeypatch.setenv("KTA_DISABLE_COMPACTION", "1")  # ignored
    assert scan(count_alive_keys=True).wire.alive_compaction == "on"
    assert scan().wire.alive_compaction == "n/a"
    with pytest.raises(TypeError):
        AnalyzerConfig(num_partitions=2, alive_compaction="off")


def test_pair_counters_and_fallback_booked():
    before_raw = _metric_total("kta_alive_pairs_raw_total")
    before_em = _metric_total("kta_alive_pairs_emitted_total")

    result, _ = _wire_scan(superbatch=4)
    raw = _metric_total("kta_alive_pairs_raw_total") - before_raw
    em = _metric_total("kta_alive_pairs_emitted_total") - before_em
    assert raw > 0 and 0 < em <= raw
    assert result.wire.pairs_raw == int(raw)
    assert result.wire.pairs_emitted == int(em)
    assert "kta_alive_compaction_off_total" not in (
        default_registry().snapshot()
    )


def test_stats_compaction_line_renders():
    from kafka_topic_analyzer_tpu.report import render_telemetry_stats

    result, _ = _wire_scan(superbatch=4)
    text = render_telemetry_stats(result.telemetry, wire=result.wire)
    assert "alive-compaction: on" in text
    assert "ratio" in text
    doc = result.wire.as_dict()
    assert doc["alive_pairs_raw"] == result.wire.pairs_raw
    assert 0 < doc["alive_compaction_ratio"] <= 1

    no_alive, _ = _wire_scan(count_alive_keys=False)
    text_off = render_telemetry_stats(no_alive.telemetry, wire=no_alive.wire)
    assert "alive-compaction" not in text_off
    assert no_alive.wire.as_dict()["alive_compaction"] == "n/a"


def test_worst_case_all_unique_ratio_is_one():
    """All-unique keys: compaction cannot dedupe anything — the ratio is
    honestly 1.0 and results still match the oracle."""
    spec = SyntheticSpec(
        num_partitions=2, messages_per_partition=1500,
        keys_per_partition=1_000_000, tombstone_permille=100, seed=13,
    )

    def scan(backend_cls):
        cfg = AnalyzerConfig(
            num_partitions=2, batch_size=256, count_alive_keys=True,
            alive_bitmap_bits=24,
        )
        return run_scan(
            "t", SyntheticSource(spec), _backend(cfg, backend_cls, 4), 256,
        )

    on = scan(TpuBackend)
    ref = scan(CpuExactBackend)
    assert on.metrics.to_dict(None, None) == ref.metrics.to_dict(None, None)
    # Not exactly 1.0 only if the 1M-key draw collides; allow a hair.
    assert on.wire.compaction_ratio > 0.99


# ---------------------------------------------------------------------------
# mesh-pinned alive resume rejection names the feature + allowed configs


def test_mesh_pinned_resume_error_names_feature(tmp_path):
    from kafka_topic_analyzer_tpu.checkpoint import (
        load_snapshot,
        save_snapshot,
    )
    from kafka_topic_analyzer_tpu.models.state import AnalyzerState

    cfg21 = AnalyzerConfig(
        num_partitions=4, batch_size=128, count_alive_keys=True,
        alive_bitmap_bits=12, mesh_shape=(2, 1),
    )
    save_snapshot(
        str(tmp_path), "t", cfg21, AnalyzerState.init(cfg21),
        {0: 5}, 5, 0,
    )
    cfg11 = dataclasses.replace(cfg21, mesh_shape=(1, 1))
    with pytest.raises(ValueError) as ei:
        load_snapshot(str(tmp_path), "t", cfg11)
    msg = str(ei.value)
    assert "MESH-PINNED" in msg
    assert "count-alive-keys" in msg
    assert "--mesh 2,1" in msg          # the config that may resume it
    assert "--mesh 1,1" in msg          # what a rescan would run
    # A genuinely different config (other topic) stays a generic mismatch
    # but still names the mesh-pinning rule for alive scans.
    with pytest.raises(ValueError, match="alive keys"):
        load_snapshot(str(tmp_path), "other-topic".replace("-", "_"),
                      dataclasses.replace(cfg11, num_partitions=5))


# ---------------------------------------------------------------------------
# the pair form's device apply: exact, and O(pairs) per dispatch

#: (bits, table capacity): a 2^10-slot bitmap of 32 words with a 96-pair
#: table, so several pairs share a word; and a 2^17-slot bitmap of 4,096
#: words (four of the chip's (8, 128) tiles) with a 2,500-pair table.
_APPLY_SMALL = (10, 96)
_APPLY_TILED = (17, 2500)


def _three_pass_reference(words, slot, flag, n_pairs, bits, space_index,
                          space_shards):
    """The pair form's LWW apply as a formula on numpy arrays: scatter the
    live in-shard set and clear bits into two zeroed word arrays, then
    ``(words & ~clear) | set``."""
    w = (1 << (bits - 5)) // space_shards
    base = w * 32 * space_index
    local = slot[:n_pairs].astype(np.int64) - base
    keep = (local >= 0) & (local < w * 32)
    local, alive = local[keep], flag[:n_pairs][keep].astype(bool)
    bit = np.left_shift(np.uint32(1), (local & 31).astype(np.uint32))
    set_words = np.zeros(w, dtype=np.uint32)
    clear_words = np.zeros(w, dtype=np.uint32)
    np.add.at(set_words, local[alive] >> 5, bit[alive])
    np.add.at(clear_words, local[~alive] >> 5, bit[~alive])
    return (words & ~clear_words) | set_words


def _pair_apply_case(bits, cap, n_live, seed=2147400032):
    """A deduped pair table and a non-zero starting bitmap of the whole
    2^bits-slot space.  In every space shard's first word the table sets
    a clear bit 0, clears a set bit 31, sets an already-set bit, clears an
    already-clear bit, and puts four slots in one word; random unique
    slots fill the rest.  Entries past ``n_live`` hold in-range slots, so
    an apply that read them would change the bitmap."""
    rng = np.random.default_rng(seed)
    total_words = 1 << (bits - 5)
    start = rng.integers(0, 1 << 32, size=total_words,
                         dtype=np.uint64).astype(np.uint32)
    forced = {}   # slot -> (alive flag, starting bit)
    for shard_base in range(0, 1 << bits, (1 << bits) // 4):
        forced.update({
            shard_base + 0: (1, 0),    # bit 0: set a clear bit
            shard_base + 31: (0, 1),   # bit 31: clear a set bit
            shard_base + 5: (1, 1),    # set an already-set bit
            shard_base + 6: (0, 0),    # clear an already-clear bit
        })
    for s, (_, was) in forced.items():
        w, b = s >> 5, np.uint32(1 << (s & 31))
        start[w] = (start[w] | b) if was else (start[w] & ~b)
    others = rng.permutation(
        np.setdiff1d(np.arange(1 << bits), list(forced))
    )[: cap - len(forced)]
    slot = np.concatenate([list(forced), others]).astype(np.uint32)
    flag = np.concatenate([
        [f for f, _ in forced.values()],
        rng.integers(0, 2, size=others.size),
    ]).astype(np.uint8)
    order = rng.permutation(cap)
    slot, flag = slot[order], flag[order]
    dead = np.arange(n_live, cap)
    slot[dead] = rng.integers(0, 1 << bits, size=dead.size)
    flag[dead] = rng.integers(0, 2, size=dead.size)
    return start, slot, flag


def _pair_apply_params():
    """Every space shard of the small table at no, few, part and all live
    pairs; the tiled table at one shard of each split."""
    small = [
        (_APPLY_SMALL, shards, index, n_live)
        for shards in (1, 2, 4) for index in range(shards)
        for n_live in (0, 5, _APPLY_SMALL[1] // 2 + 5, _APPLY_SMALL[1])
    ]
    tiled = [
        (_APPLY_TILED, shards, shards - 1, n_live)
        for shards in (1, 2, 4) for n_live in (0, 5, 1300, _APPLY_TILED[1])
    ]
    return [
        pytest.param(*p, id=f"bits{p[0][0]}-space{p[1]}.{p[2]}-live{p[3]}")
        for p in small + tiled
    ]


@pytest.mark.parametrize("geometry,space_shards,space_index,n_live",
                         _pair_apply_params())
def test_pair_apply_matches_three_pass_reference(geometry, space_shards,
                                                 space_index, n_live):
    """``bitmap_apply_pairs`` (gather, per-bit delta, scatter-add) equals
    the three-pass LWW formula bit for bit, on each shard's slot range."""
    import jax

    from kafka_topic_analyzer_tpu.jax_support import jnp
    from kafka_topic_analyzer_tpu.ops.bitmap import (
        bitmap_apply_pairs,
        bitmap_num_words,
    )

    bits, cap = geometry
    start, slot, flag = _pair_apply_case(bits, cap, n_live)
    w = bitmap_num_words(bits, space_shards)
    shard = start[space_index * w:(space_index + 1) * w].copy()
    apply = jax.jit(
        lambda words, s, f, n, i: bitmap_apply_pairs(
            words, s, f, n, bits=bits, space_index=i,
            space_shards=space_shards,
        )
    )
    got = np.asarray(apply(
        jnp.asarray(shard), jnp.asarray(slot), jnp.asarray(flag),
        jnp.int32(n_live), jnp.int32(space_index),
    ))
    want = _three_pass_reference(shard, slot, flag, n_live, bits,
                                 space_index, space_shards)
    np.testing.assert_array_equal(got, want)
    if n_live == 0:
        np.testing.assert_array_equal(got, shard)


def _jaxpr_values(jaxpr):
    """Every value an equation of ``jaxpr`` (or of a jaxpr nested in its
    parameters) defines, with the primitive that defines it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, v
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _jaxpr_values(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _jaxpr_values(sub)


@pytest.mark.parametrize("space_shards", [1, 4], ids=["one-chip", "space-4"])
def test_pair_apply_builds_nothing_of_bitmap_size(space_shards):
    """At a 2^28-bit bitmap (2^23 words a shard at one space shard) and a
    2^18-pair table, the apply's only value of W or W+1 elements is the
    scatter-add into the state: no zeroed scratch, no elementwise pass, no
    copy, so a dispatch costs its pairs and not the bitmap."""
    import jax

    from kafka_topic_analyzer_tpu.jax_support import jnp
    from kafka_topic_analyzer_tpu.ops.bitmap import (
        bitmap_apply_pairs,
        bitmap_num_words,
    )

    bits, cap = 28, 1 << 18
    w = bitmap_num_words(bits, space_shards)
    closed = jax.make_jaxpr(
        lambda words, s, f, n, i: bitmap_apply_pairs(
            words, s, f, n, bits=bits, space_index=i,
            space_shards=space_shards,
        )
    )(
        jax.ShapeDtypeStruct((w,), jnp.uint32),
        jax.ShapeDtypeStruct((cap,), jnp.uint32),
        jax.ShapeDtypeStruct((cap,), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    big = [
        (prim, v) for prim, v in _jaxpr_values(closed.jaxpr)
        if getattr(v.aval, "size", 0) in (w, w + 1)
    ]
    assert [prim for prim, _ in big] == ["scatter-add"], big
    assert big[0][1] is closed.jaxpr.outvars[0]
