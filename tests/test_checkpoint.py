"""Snapshot/resume: an interrupted scan resumed from a snapshot must produce
exactly the same report as an uninterrupted scan (SURVEY.md §5.4)."""

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
from kafka_topic_analyzer_tpu.checkpoint import load_snapshot, save_snapshot
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

SPEC = SyntheticSpec(
    num_partitions=3,
    messages_per_partition=4_000,
    keys_per_partition=200,
    tombstone_permille=150,
    seed=31,
)
CFG = AnalyzerConfig(
    num_partitions=3,
    batch_size=512,
    count_alive_keys=True,
    alive_bitmap_bits=20,
    enable_hll=True,
    hll_p=10,
    enable_quantiles=True,
)


def _metrics_equal(a, b):
    assert np.array_equal(a.per_partition, b.per_partition)
    assert a.alive_keys == b.alive_keys
    assert a.earliest_ts_s == b.earliest_ts_s
    assert a.latest_ts_s == b.latest_ts_s
    assert a.smallest_message == b.smallest_message
    assert a.largest_message == b.largest_message
    assert a.overall_count == b.overall_count
    assert a.distinct_keys_hll == b.distinct_keys_hll
    assert a.quantiles.values == b.quantiles.values


class _Interrupt(Exception):
    pass


class _InterruptingSource(SyntheticSource):
    """Raises after yielding `limit` batches — simulates a crash mid-scan."""

    def __init__(self, spec, limit):
        super().__init__(spec)
        self.limit = limit

    def batches(self, batch_size, partitions=None, start_at=None):
        it = super().batches(batch_size, partitions, start_at)
        for i, b in enumerate(it):
            if start_at is None and i >= self.limit:
                raise _Interrupt()
            yield b


def test_resume_matches_uninterrupted(tmp_path):
    # Uninterrupted run.
    full = run_scan(
        "t", SyntheticSource(SPEC), TpuBackend(CFG, init_now_s=10**10), 512
    ).metrics

    # Interrupted run: snapshot every batch, crash after 7 batches.
    be1 = TpuBackend(CFG, init_now_s=10**10)
    src = _InterruptingSource(SPEC, limit=7)
    with pytest.raises(_Interrupt):
        run_scan(
            "t", src, be1, 512,
            snapshot_dir=str(tmp_path), snapshot_every_s=0.0,
        )

    # Resume with a fresh backend (fresh process semantics).
    be2 = TpuBackend(CFG, init_now_s=0)  # init time restored from snapshot
    result = run_scan(
        "t", SyntheticSource(SPEC), be2, 512,
        snapshot_dir=str(tmp_path), snapshot_every_s=3600.0, resume=True,
    )
    _metrics_equal(full, result.metrics)
    assert be2.init_now_s == 10**10  # restored, not re-stamped


def test_kafka_resume_with_compaction_gaps(tmp_path):
    """Offset-exact resume on a gappy (compacted) offset space."""
    import sys

    sys.path.insert(0, "tests")
    from fake_broker import FakeBroker
    from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource

    rows = [
        (off, 1_600_000_000_000 + off, f"k{off % 37}".encode(),
         None if off % 11 == 7 else bytes(20 + off % 64))
        for off in range(0, 900, 3)  # offsets 0,3,6,... (gaps)
    ]
    cfg = AnalyzerConfig(
        num_partitions=1, batch_size=128, count_alive_keys=True,
        alive_bitmap_bits=16,
    )
    with FakeBroker("snap.topic", {0: rows}) as broker:
        bootstrap = f"127.0.0.1:{broker.port}"
        full = run_scan(
            "snap.topic", KafkaWireSource(bootstrap, "snap.topic"),
            TpuBackend(cfg, init_now_s=10**10), 128,
        ).metrics

        # First half: consume 2 batches then stop (limit via islice wrapper).
        src1 = KafkaWireSource(bootstrap, "snap.topic")
        be1 = TpuBackend(cfg, init_now_s=10**10)

        class Half:
            def __getattr__(self, name):
                return getattr(src1, name)

            def batches(self, batch_size, partitions=None, start_at=None):
                it = src1.batches(batch_size, partitions, start_at)
                for i, b in enumerate(it):
                    if i >= 2:
                        raise _Interrupt()
                    yield b

        with pytest.raises(_Interrupt):
            run_scan(
                "snap.topic", Half(), be1, 128,
                snapshot_dir=str(tmp_path), snapshot_every_s=0.0,
            )

        snap = load_snapshot(str(tmp_path), "snap.topic", cfg)
        assert snap is not None
        _, offsets, records_seen, _ = snap
        assert records_seen == 256
        # Offsets have gaps: next offset reflects true positions, not counts.
        assert offsets[0] == rows[255][0] + 1

        be2 = TpuBackend(cfg, init_now_s=0)
        result = run_scan(
            "snap.topic", KafkaWireSource(bootstrap, "snap.topic"), be2, 128,
            snapshot_dir=str(tmp_path), resume=True,
        )
    assert np.array_equal(full.per_partition, result.metrics.per_partition)
    assert full.alive_keys == result.metrics.alive_keys


def test_sharded_resume_matches_uninterrupted(tmp_path):
    """Snapshot/resume through the mesh backend (stacked state leaves)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg = AnalyzerConfig(
        num_partitions=3,
        batch_size=512,
        count_alive_keys=True,
        alive_bitmap_bits=18,
        enable_hll=True,
        hll_p=10,
        mesh_shape=(2, 2),
    )
    full = run_scan(
        "t", SyntheticSource(SPEC), ShardedTpuBackend(cfg, init_now_s=10**10), 512
    ).metrics

    be1 = ShardedTpuBackend(cfg, init_now_s=10**10)
    with pytest.raises(_Interrupt):
        run_scan(
            "t", _InterruptingSource(SPEC, limit=5), be1, 512,
            snapshot_dir=str(tmp_path), snapshot_every_s=0.0,
        )
    be2 = ShardedTpuBackend(cfg, init_now_s=0)
    result = run_scan(
        "t", SyntheticSource(SPEC), be2, 512,
        snapshot_dir=str(tmp_path), resume=True,
    )
    assert np.array_equal(full.per_partition, result.metrics.per_partition)
    assert full.alive_keys == result.metrics.alive_keys
    assert full.distinct_keys_hll == result.metrics.distinct_keys_hll
    assert full.overall_count == result.metrics.overall_count


MESHFREE_SPEC = SyntheticSpec(
    num_partitions=5, messages_per_partition=1500,
    keys_per_partition=31, tombstone_permille=120, seed=3,
)
MESHFREE_BASE = dict(
    num_partitions=5, batch_size=256,
    enable_hll=True, hll_p=10, enable_quantiles=True,
)


def test_cross_mesh_cross_config_resume(tmp_path):
    """Any-config↔any-config resume (DESIGN.md §14): a snapshot taken
    under (mesh 2, workers 2, K 2) resumes under (mesh 4, workers 3, K 4)
    AND under the plain single device, reproducing the uninterrupted
    metrics exactly.  Works because v4 snapshots store the canonical
    mesh-free layout (checkpoint._canonicalize) and redistribute as
    (canonical, identity, ...) rows on load — every fold associative and
    commutative across device rows."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from kafka_topic_analyzer_tpu.config import DispatchConfig
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    full = run_scan(
        "t", SyntheticSource(MESHFREE_SPEC),
        TpuBackend(AnalyzerConfig(**MESHFREE_BASE), init_now_s=10**10), 256,
    ).metrics

    def interrupted(snap_dir):
        be = ShardedTpuBackend(
            AnalyzerConfig(**MESHFREE_BASE, mesh_shape=(2, 1)),
            init_now_s=10**10,
            dispatch=DispatchConfig(superbatch=2, depth=2),
        )
        with pytest.raises(_Interrupt):
            run_scan(
                "t", _InterruptingSource(MESHFREE_SPEC, limit=7), be, 256,
                snapshot_dir=str(snap_dir), snapshot_every_s=0.0,
                ingest_workers=2,
            )

    d1 = tmp_path / "to_mesh4"
    interrupted(d1)
    be2 = ShardedTpuBackend(
        AnalyzerConfig(**MESHFREE_BASE, mesh_shape=(4, 1)),
        init_now_s=0,
        dispatch=DispatchConfig(superbatch=4, depth=1),
    )
    r = run_scan(
        "t", SyntheticSource(MESHFREE_SPEC), be2, 256,
        snapshot_dir=str(d1), resume=True, ingest_workers=3,
    )
    assert r.metrics.to_dict(r.start_offsets, r.end_offsets) == full.to_dict(
        r.start_offsets, r.end_offsets
    )
    assert be2.init_now_s == 10**10  # restored across the mesh change

    d2 = tmp_path / "to_single"
    interrupted(d2)
    be3 = TpuBackend(AnalyzerConfig(**MESHFREE_BASE), init_now_s=0)
    r = run_scan(
        "t", SyntheticSource(MESHFREE_SPEC), be3, 256,
        snapshot_dir=str(d2), resume=True,
    )
    assert r.metrics.to_dict(r.start_offsets, r.end_offsets) == full.to_dict(
        r.start_offsets, r.end_offsets
    )


def test_alive_bitmap_snapshots_stay_mesh_pinned(tmp_path):
    """Alive-key scans keep the mesh in the fingerprint: last-writer-wins
    bit CLEARS only resolve against the row that set the bit, and the
    partition→row assignment changes with the mesh — resuming under a
    different mesh must be a clean error, never a silent miscount."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg2 = AnalyzerConfig(
        num_partitions=3, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=18, mesh_shape=(2, 1),
    )
    be = ShardedTpuBackend(cfg2, init_now_s=5)
    save_snapshot(str(tmp_path), "t", cfg2, be.get_state(), {0: 1}, 1, 5)
    cfg4 = AnalyzerConfig(
        num_partitions=3, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=18, mesh_shape=(4, 1),
    )
    be4 = ShardedTpuBackend(cfg4, init_now_s=5)
    # The rejection names the offending feature and the mesh that may
    # resume the snapshot (PR 12's diagnosable-error satellite).
    with pytest.raises(ValueError, match="MESH-PINNED") as ei:
        load_snapshot(str(tmp_path), "t", cfg4, template=be4.get_state())
    assert "--mesh 2,1" in str(ei.value)
    assert "count-alive-keys" in str(ei.value)


def test_scoped_mesh_free_snapshot_canonicalizes_and_distributes(tmp_path):
    """Multi-controller mesh-free snapshots take the same canonical path:
    a PROCESS-LOCAL (scope'd) stacked state folds down at save and
    redistributes into the local stacked template at load — row 0 of this
    process's rows carries exactly its canonical fold, the other rows the
    merge identities.  (The default path for every non-alive multi-host
    resume; exercised here by slicing a single-process mesh state into
    the rows 'process 0' would own.)"""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg = AnalyzerConfig(**MESHFREE_BASE, mesh_shape=(4, 1))
    be = ShardedTpuBackend(cfg, init_now_s=5)
    run_scan("t", SyntheticSource(MESHFREE_SPEC), be, 256)  # non-trivial fold
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x)), be.get_state()
    )
    local = jax.tree.map(lambda x: x[:2].copy(), host)  # "process 0" rows
    scope = (0, 2, [0, 1])
    save_snapshot(str(tmp_path), "t", cfg, local, {0: 1}, 1, 5, scope=scope)
    fresh_local = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x))[:2].copy(),
        ShardedTpuBackend(cfg, init_now_s=5).get_state(),
    )
    snap = load_snapshot(
        str(tmp_path), "t", cfg, template=fresh_local, scope=scope
    )
    assert snap is not None
    state = snap[0]
    m = state.metrics
    # Row 0 = the canonical fold of THIS process's saved rows...
    assert np.array_equal(
        m.per_partition[0], host.metrics.per_partition[:2].sum(axis=0)
    )
    assert np.array_equal(
        m.earliest_s[0], host.metrics.earliest_s[:2].min(axis=0)
    )
    # ...and row 1 the merge identities (a fresh state's values).
    assert np.array_equal(m.per_partition[1], np.zeros_like(m.per_partition[1]))
    assert np.array_equal(
        m.earliest_s[1],
        np.full_like(m.earliest_s[1], np.iinfo(np.int64).max),
    )
    assert np.array_equal(
        state.hll.regs[0], host.hll.regs[:2].max(axis=0)
    )
    assert not state.hll.regs[1].any()


def test_mesh_free_snapshot_is_canonical_on_disk(tmp_path):
    """v4 snapshots store the single-device layout regardless of the mesh
    that wrote them — that is WHY any mesh can adopt them."""
    import json as _json

    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from kafka_topic_analyzer_tpu.checkpoint import (
        SNAPSHOT_NAME,
        config_fingerprint,
    )
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg = AnalyzerConfig(**MESHFREE_BASE, mesh_shape=(2, 1))
    be = ShardedTpuBackend(cfg, init_now_s=5)
    save_snapshot(str(tmp_path), "t", cfg, be.get_state(), {0: 1}, 1, 5)
    with np.load(str(tmp_path / SNAPSHOT_NAME), allow_pickle=False) as z:
        meta = _json.loads(str(z["__meta__"]))
        per_part = z["state.metrics.per_partition"]
        overall = z["state.metrics.overall_count"]
    assert per_part.shape == (5, 7)  # canonical, not [dev, 5, 7]
    assert overall.shape == ()
    # Mesh-free stamp: the single-device config produces the SAME key.
    assert meta["fingerprint"] == config_fingerprint(
        AnalyzerConfig(**MESHFREE_BASE), "t"
    )


def test_pack_rejects_out_of_range_partition():
    from kafka_topic_analyzer_tpu.packing import pack_batch
    from kafka_topic_analyzer_tpu.records import RecordBatch

    cfg = AnalyzerConfig(num_partitions=1, batch_size=8)
    b = RecordBatch.empty(4)
    b.valid[:] = True
    b.partition[0] = 40_000
    with pytest.raises(ValueError, match="partition index"):
        pack_batch(b, cfg, use_native=False)


def test_incompatible_snapshot_rejected(tmp_path):
    be = TpuBackend(CFG, init_now_s=5)
    save_snapshot(str(tmp_path), "t", CFG, be.get_state(), {0: 1}, 1, 5)
    other = AnalyzerConfig(num_partitions=4, batch_size=512)
    with pytest.raises(ValueError, match="fingerprint"):
        load_snapshot(str(tmp_path), "t", other)
    with pytest.raises(ValueError, match="fingerprint"):
        load_snapshot(str(tmp_path), "other-topic", CFG)


def test_v3_stamped_single_shard_snapshot_still_loads(tmp_path):
    """r2/r3 stamped EVERY config's fingerprint with state_version=3; S=1
    layouts were identical under v2 and v3, so a v3-stamped S=1 snapshot
    must keep loading after the v2 re-labeling (code-review r4)."""
    import json

    from kafka_topic_analyzer_tpu.checkpoint import (
        SNAPSHOT_NAME,
        _fingerprint_at,
    )

    be = TpuBackend(CFG, init_now_s=5)
    save_snapshot(str(tmp_path), "t", CFG, be.get_state(), {0: 1}, 1, 5)
    # Rewrite the stamp to what the r2/r3 code would have produced.
    path = str(tmp_path / SNAPSHOT_NAME)
    with np.load(path, allow_pickle=False) as z:
        data = {k: z[k] for k in z.files}
    meta = json.loads(str(data["__meta__"]))
    assert CFG.space_shards == 1
    meta["fingerprint"] = _fingerprint_at(CFG, "t", 3)
    data["__meta__"] = np.array(json.dumps(meta))
    np.savez(path.removesuffix(".npz"), **data)
    loaded = load_snapshot(str(tmp_path), "t", CFG)
    assert loaded is not None


#: Fingerprints that snapshots already on disk carry, computed by the code
#: that still had the wire-format, compaction and Pallas config fields
#: (excluded from the fingerprint there).  Topic "demo", 10 partitions,
#: batch 2^16.
GOLDEN_FINGERPRINTS = {
    "counters": ({}, "28ef60cdbb4fe30a"),
    "alive": ({"count_alive_keys": True}, "748bac2e49ba9ae0"),
    "alive-distinct-quantiles": (
        {"count_alive_keys": True, "enable_hll": True,
         "enable_quantiles": True},
        "295e9ec98342a8e0",
    ),
    "mesh21-per-partition-hll-quantiles": (
        {"distinct_keys_per_partition": True,
         "quantiles_per_partition": True, "mesh_shape": (2, 1)},
        "cfdfced32207c555",
    ),
}


@pytest.mark.parametrize("name", sorted(GOLDEN_FINGERPRINTS))
def test_fingerprint_unchanged_from_parent(name):
    """A snapshot written before those fields went still resumes: every
    config's fingerprint string is the one it was stamped with."""
    from kafka_topic_analyzer_tpu.checkpoint import config_fingerprint

    kw, want = GOLDEN_FINGERPRINTS[name]
    cfg = AnalyzerConfig(num_partitions=10, batch_size=1 << 16, **kw)
    assert config_fingerprint(cfg, "demo") == want
