"""Sharded (mesh) backend parity vs the CPU oracle on the virtual 8-device
CPU platform — SURVEY.md §4 'multi-core without a cluster'."""

import dataclasses

import jax
import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

SPEC = SyntheticSpec(
    num_partitions=7,  # deliberately not divisible by the shard count
    messages_per_partition=3_000,
    keys_per_partition=300,
    key_null_permille=60,
    tombstone_permille=180,
    value_len_min=20,
    value_len_max=220,
    seed=99,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def run_cpu(config):
    be = CpuExactBackend(config, init_now_s=10**10)
    src = SyntheticSource(SPEC)
    return run_scan("t", src, be, config.batch_size).metrics


def run_sharded(config):
    be = ShardedTpuBackend(config, init_now_s=10**10)
    src = SyntheticSource(SPEC)
    return run_scan("t", src, be, config.batch_size).metrics


@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4)])
def test_sharded_parity(mesh_shape):
    cfg = AnalyzerConfig(
        num_partitions=7,
        batch_size=1024,
        count_alive_keys=True,
        alive_bitmap_bits=20,
        enable_hll=True,
        enable_quantiles=True,
        mesh_shape=mesh_shape,
    )
    m_cpu = run_cpu(cfg)
    m_tpu = run_sharded(cfg)
    assert np.array_equal(m_cpu.per_partition, m_tpu.per_partition)
    assert m_cpu.earliest_ts_s == m_tpu.earliest_ts_s
    assert m_cpu.latest_ts_s == m_tpu.latest_ts_s
    assert m_cpu.smallest_message == m_tpu.smallest_message
    assert m_cpu.largest_message == m_tpu.largest_message
    assert m_cpu.overall_size == m_tpu.overall_size
    assert m_cpu.overall_count == m_tpu.overall_count
    assert m_cpu.alive_keys == m_tpu.alive_keys
    # Sketches merged across shards stay inside their error budget.
    assert m_tpu.distinct_keys_hll == pytest.approx(
        m_cpu.distinct_keys_exact, rel=0.05
    )
    for q_exact, q_sketch in zip(m_cpu.quantiles.values, m_tpu.quantiles.values):
        assert q_sketch == pytest.approx(q_exact, rel=0.011)


def test_mixed_batch_update_splits_by_partition():
    cfg = AnalyzerConfig(num_partitions=7, batch_size=512, mesh_shape=(4, 1))
    be = ShardedTpuBackend(cfg, init_now_s=10**10)
    src = SyntheticSource(SPEC)
    for batch in src.batches(512):
        be.update(batch)  # mixed-partition path
    m = be.finalize()
    assert int(m.overall_count) == 7 * 3_000


def test_cross_chunk_last_writer_wins():
    """A key whose alive-bitmap updates straddle the space-chunk boundary
    must resolve by RECORD order, not by which space shard saw it: the
    device-side ordered application (backends/step.py) is what makes the
    chunked input sharding exact."""
    from kafka_topic_analyzer_tpu.records import RecordBatch

    def batch_of(rows):
        b = RecordBatch.empty(len(rows))
        for i, (h32, value_len) in enumerate(rows):
            b.partition[i] = 0
            b.key_len[i] = 4
            b.value_null[i] = value_len is None
            b.value_len[i] = 0 if value_len is None else value_len
            b.ts_s[i] = 100 + i
            b.key_hash32[i] = h32
            b.key_hash64[i] = h32
            b.valid[i] = True
        return b

    # batch_size 8 over (1, 2) → chunks of 4.  Key A: alive in chunk 0,
    # tombstoned in chunk 1 → dead.  Key B: tombstoned in chunk 0, alive
    # in chunk 1 → alive.  Key C alive twice in chunk 0 → alive.  Key D
    # only in chunk 1, alive → alive.
    rows = [
        (0xA, 10), (0xB, None), (0xC, 5), (0xC, 6),   # chunk 0
        (0xA, None), (0xB, 7), (0xD, 8), (0xD, 9),    # chunk 1
    ]
    cfg = AnalyzerConfig(
        num_partitions=1,
        batch_size=8,
        mesh_shape=(1, 2),
        count_alive_keys=True,
        alive_bitmap_bits=8,
    )
    be = ShardedTpuBackend(cfg, init_now_s=10**10)
    be.update_shards([batch_of(rows)])
    m = be.finalize()
    assert int(m.alive_keys) == 3  # B, C, D alive; A dead

    # Same records through the CPU oracle (sequential replay).
    oracle = CpuExactBackend(
        AnalyzerConfig(
            num_partitions=1, batch_size=8,
            count_alive_keys=True, alive_bitmap_bits=8,
        ),
        init_now_s=10**10,
    )
    oracle.update(batch_of(rows))
    assert int(oracle.finalize().alive_keys) == 3


def test_prepare_shard_staged_step_matches_direct():
    """update_shards fed PackedShards (the engine's prefetch-worker
    staging) must be byte-identical to feeding decoded batches."""
    import numpy as np

    from kafka_topic_analyzer_tpu.config import AnalyzerConfig
    from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg = AnalyzerConfig(
        num_partitions=4, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=16, enable_hll=True, hll_p=10,
        enable_quantiles=True, mesh_shape=(2, 2),
    )
    spec = SyntheticSpec(
        num_partitions=4, messages_per_partition=900,
        keys_per_partition=70, tombstone_permille=90, seed=31,
    )
    batches = list(SyntheticSource(spec).batches(cfg.batch_size))
    halves = [batches[i::2] for i in range(2)]  # row r gets every 2nd batch
    direct = ShardedTpuBackend(cfg, init_now_s=0)
    staged = ShardedTpuBackend(cfg, init_now_s=0)
    rounds = max(len(h) for h in halves)
    for i in range(rounds):
        row = [h[i] if i < len(h) else None for h in halves]
        direct.update_shards(list(row))
        staged.update_shards([
            staged.prepare_shard(b) if b is not None else None for b in row
        ])
    md, ms = direct.finalize(), staged.finalize()
    assert np.array_equal(md.per_partition, ms.per_partition)
    assert np.array_equal(md.per_partition_extremes, ms.per_partition_extremes)
    assert md.overall_count == ms.overall_count
    assert md.alive_keys == ms.alive_keys
    assert md.distinct_keys_hll == ms.distinct_keys_hll
    assert list(md.quantiles.values) == list(ms.quantiles.values)


def test_non_dense_partitions_sharded_engine_scan(tmp_path):
    """Sharded engine scan over true partition ids {5,7,9}: the staged
    packing must use dense rows while snapshots keep true ids (same
    regression class as the single-device staging)."""
    import numpy as np

    from fake_broker import FakeBroker

    from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
    from kafka_topic_analyzer_tpu.checkpoint import load_snapshot
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig
    from kafka_topic_analyzer_tpu.engine import run_scan
    from kafka_topic_analyzer_tpu.io.kafka_wire import (
        KafkaWireSource,
        records_to_batch,
    )
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    ids = (5, 7, 9)
    records = {
        p: [
            (off, 1_600_000_000_000 + off * 400,
             f"p{p}-k{off % 23}".encode() if off % 6 else None,
             None if off % 11 == 4 else bytes(8 + (off * 5 + p) % 50))
            for off in range(500)
        ]
        for p in ids
    }
    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=256, count_alive_keys=True,
        alive_bitmap_bits=16, mesh_shape=(2, 2),
    )
    with FakeBroker("gap.sharded", records) as b:
        src = KafkaWireSource(f"127.0.0.1:{b.port}", "gap.sharded")
        try:
            result = run_scan(
                "gap.sharded", src, ShardedTpuBackend(cfg, init_now_s=0),
                256, snapshot_dir=str(tmp_path), snapshot_every_s=0.0,
            )
        finally:
            src.close()
    snap = load_snapshot(
        str(tmp_path), "gap.sharded", cfg,
        template=ShardedTpuBackend(cfg, init_now_s=0).get_state(),
    )
    assert snap is not None
    _, next_offsets, records_seen, _ = snap
    assert next_offsets == {5: 500, 7: 500, 9: 500}
    assert records_seen == 1500

    m = result.metrics
    assert m.partitions == [5, 7, 9]
    oracle = CpuExactBackend(cfg, init_now_s=0)
    rows = [
        (dense, ts, k, v)
        for dense, p in enumerate(ids)
        for (_off, ts, k, v) in records[p]
    ]
    for lo in range(0, len(rows), 256):
        oracle.update(records_to_batch(rows[lo:lo + 256]))
    want = oracle.finalize()
    assert np.array_equal(m.per_partition, want.per_partition)
    assert m.overall_count == want.overall_count
    assert m.alive_keys == want.alive_keys


def test_a_dropped_backend_frees_its_state_without_the_cycle_collector():
    """No jitted step holds the backend: its device state (the alive
    bitmap, 512 MiB a chip at the CLI default) goes with its last
    reference, not at some later cyclic collection."""
    import gc
    import weakref

    cfg = AnalyzerConfig(
        num_partitions=7, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=16, mesh_shape=(4, 1),
    )
    be = ShardedTpuBackend(cfg, init_now_s=10**10)
    run_scan("t", SyntheticSource(SPEC), be, cfg.batch_size)
    ref = weakref.ref(be)
    gc.disable()
    try:
        del be
        assert ref() is None
    finally:
        gc.enable()


def test_back_to_back_scans_compile_the_merge_once():
    """Each scan builds its own backend; the second of two with one config
    and mesh merges with the program the first compiled, so that its
    ``finalize.merge`` times the collective and no build."""
    from kafka_topic_analyzer_tpu.parallel import sharded

    cfg = AnalyzerConfig(
        num_partitions=7, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=20, enable_hll=True, enable_quantiles=True,
        mesh_shape=(4, 1),
    )
    first = run_sharded(cfg)
    (program,) = sharded._MERGE_PROGRAM.values()
    second = ShardedTpuBackend(cfg, init_now_s=10**10)
    assert second._merge_program() is program
    again = run_scan("t", SyntheticSource(SPEC), second, cfg.batch_size)
    assert np.array_equal(again.metrics.per_partition, first.per_partition)
    assert again.metrics.alive_keys == first.alive_keys
    assert again.metrics.alive_keys == run_cpu(cfg).alive_keys

    other = dataclasses.replace(cfg, mesh_shape=(2, 1))
    third = ShardedTpuBackend(other, init_now_s=10**10)
    run_scan("t", SyntheticSource(SPEC), third, other.batch_size)
    assert list(sharded._MERGE_PROGRAM) == [(other, third.mesh)]
