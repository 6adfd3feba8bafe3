"""TPU backend parity vs the CPU-exact oracle (runs on the virtual CPU
platform in tests; same code path runs on real TPU).

Counters must match bit-for-bit; sketches within their error budgets
(SURVEY.md §4 backend-contract tests).
"""

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

SPEC = SyntheticSpec(
    num_partitions=3,
    messages_per_partition=5_000,
    keys_per_partition=400,
    key_null_permille=80,
    tombstone_permille=150,
    value_len_min=50,
    value_len_max=350,
    seed=7,
)


def run_both(config: AnalyzerConfig, spec: SyntheticSpec = SPEC):
    cpu = CpuExactBackend(config, init_now_s=10**10)
    tpu = TpuBackend(config, init_now_s=10**10)
    src = SyntheticSource(spec)
    for batch in src.batches(config.batch_size):
        cpu.update(batch)
        tpu.update(batch)
    return cpu.finalize(), tpu.finalize()


def test_exact_counters_parity():
    cfg = AnalyzerConfig(num_partitions=3, batch_size=2048)
    m_cpu, m_tpu = run_both(cfg)
    assert np.array_equal(m_cpu.per_partition, m_tpu.per_partition)
    assert m_cpu.earliest_ts_s == m_tpu.earliest_ts_s
    assert m_cpu.latest_ts_s == m_tpu.latest_ts_s
    assert m_cpu.smallest_message == m_tpu.smallest_message
    assert m_cpu.largest_message == m_tpu.largest_message
    assert m_cpu.overall_size == m_tpu.overall_size
    assert m_cpu.overall_count == m_tpu.overall_count


def test_alive_bitmap_parity():
    cfg = AnalyzerConfig(
        num_partitions=3,
        batch_size=1024,
        count_alive_keys=True,
        alive_bitmap_bits=22,
    )
    m_cpu, m_tpu = run_both(cfg)
    assert m_cpu.alive_keys == m_tpu.alive_keys
    # With a roomy bitmap and few keys, the bitmap count equals the true
    # number of alive keys from a sequential dict replay.
    replay = {}
    for batch in SyntheticSource(SPEC).batches(4096):
        keyed = ~batch.key_null
        for h, dead in zip(
            batch.key_hash64[keyed].tolist(), batch.value_null[keyed].tolist()
        ):
            replay[h] = not dead
        # (offset order within partitions is preserved by the source)
    assert m_cpu.alive_keys == sum(replay.values())


def test_alive_count_on_the_device_matches_the_host_popcount():
    from kafka_topic_analyzer_tpu.backends import tpu

    rng = np.random.default_rng(28)
    words = rng.integers(0, 1 << 32, size=1 << 14, dtype=np.uint32)
    words[rng.integers(0, words.size, size=500)] = 0xFFFFFFFF
    words[rng.integers(0, words.size, size=500)] = 0
    count = tpu._alive_count(words)
    assert count.dtype == np.int64
    assert int(count) == int(np.bitwise_count(words).sum())


def test_alive_count_is_built_once_a_process():
    from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics

    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=256, count_alive_keys=True,
        alive_bitmap_bits=18,
    )
    TpuBackend(cfg, init_now_s=10**10).finalize()
    second = TpuBackend(cfg, init_now_s=10**10)
    built = obs_metrics.PROGRAM_BUILD_SECONDS.value
    assert second.finalize().alive_keys == 0
    assert obs_metrics.PROGRAM_BUILD_SECONDS.value == built


def test_hll_within_error_budget():
    cfg = AnalyzerConfig(num_partitions=3, batch_size=2048, enable_hll=True, hll_p=14)
    m_cpu, m_tpu = run_both(cfg)
    exact = m_cpu.distinct_keys_exact
    assert exact == 3 * 400
    est = m_tpu.distinct_keys_hll
    assert est == pytest.approx(exact, rel=0.05)  # p=14 → ~0.8% σ; 5% is 6σ


def test_hll_estimator_accurate_across_range():
    """Ertl's improved estimator at the default p=16: accurate across the
    full range, INCLUDING the classic estimator's weak band around the old
    linear-counting crossover (2.5m = 163,840) where r3's config-3 budget
    breach lived.  1.7% bound = 4σ at p=16's 0.41% standard error."""
    import numpy as np

    from kafka_topic_analyzer_tpu.ops.hll import hll_estimate
    from kafka_topic_analyzer_tpu.packing import hll_idx_rho_numpy

    p, m = 16, 1 << 16
    rng = np.random.default_rng(11)
    for n in (1_000, 100_000, 163_840, 327_680, 2_000_000):
        for _ in range(3):
            h64 = rng.integers(0, 2**63, size=n, dtype=np.uint64)
            idx, rho = hll_idx_rho_numpy(h64, np.ones(n, dtype=bool), p)
            regs = np.zeros(m, dtype=np.int64)
            np.maximum.at(regs, idx.astype(np.int64), rho.astype(np.int64))
            est = hll_estimate(regs)
            assert est == pytest.approx(n, rel=0.017), n


def test_hll_default_precision_handles_small_cardinalities():
    """The default config (hll_p now 16) on a small topic: Ertl's sigma
    term takes over where linear counting used to — estimates must stay
    tight when almost every register is zero."""
    cfg = AnalyzerConfig(num_partitions=3, batch_size=2048, enable_hll=True)
    assert cfg.hll_p == 16
    m_cpu, m_tpu = run_both(cfg)
    assert m_cpu.distinct_keys_exact == 3 * 400
    assert m_tpu.distinct_keys_hll == pytest.approx(1200, rel=0.02)


def test_ddsketch_within_alpha():
    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=2048, enable_quantiles=True, quantile_alpha=0.005
    )
    m_cpu, m_tpu = run_both(cfg)
    assert m_cpu.quantiles is not None and m_tpu.quantiles is not None
    for q_exact, q_sketch in zip(m_cpu.quantiles.values, m_tpu.quantiles.values):
        assert q_sketch == pytest.approx(q_exact, rel=0.011)  # 2*alpha + rank slack


def test_per_partition_hll_within_budget():
    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=2048,
        distinct_keys_per_partition=True, hll_p=12,
    )
    m_cpu, m_tpu = run_both(cfg)
    assert m_cpu.distinct_keys_exact_per_partition == [400, 400, 400]
    assert len(m_tpu.distinct_keys_hll_per_partition) == 3
    for exact, est in zip(
        m_cpu.distinct_keys_exact_per_partition,
        m_tpu.distinct_keys_hll_per_partition,
    ):
        assert est == pytest.approx(exact, rel=0.1)  # p=12 → ~1.6% σ
    # Global line = union of rows (partition-disjoint keys → 1200).
    assert m_tpu.distinct_keys_hll == pytest.approx(1200, rel=0.1)


def test_per_partition_quantiles_within_alpha():
    cfg = AnalyzerConfig(
        num_partitions=3, batch_size=2048, enable_quantiles=True,
        quantiles_per_partition=True, quantile_alpha=0.005,
    )
    m_cpu, m_tpu = run_both(cfg)
    assert len(m_cpu.quantiles_per_partition) == 3
    assert len(m_tpu.quantiles_per_partition) == 3
    for exact, sketch in zip(m_cpu.quantiles_per_partition, m_tpu.quantiles_per_partition):
        for q_exact, q_sketch in zip(exact.values, sketch.values):
            assert q_sketch == pytest.approx(q_exact, rel=0.011)
    # Global line still matches the single-sketch path.
    for q_exact, q_sketch in zip(m_cpu.quantiles.values, m_tpu.quantiles.values):
        assert q_sketch == pytest.approx(q_exact, rel=0.011)


def test_batch_padding_is_inert():
    cfg = AnalyzerConfig(num_partitions=3, batch_size=4096)
    # 15000 records into 4096-sized padded steps exercises padding heavily.
    m_cpu, m_tpu = run_both(cfg)
    assert m_tpu.overall_count == 15_000


def test_prepare_staged_updates_match_direct_updates():
    """prepare()+update(StagedBatch) must be byte-identical to direct
    update(RecordBatch) — the engine stages on prefetch workers, so any
    divergence would corrupt scans only in the threaded path."""
    import numpy as np

    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig
    from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

    cfg = AnalyzerConfig(
        num_partitions=5, batch_size=512, count_alive_keys=True,
        alive_bitmap_bits=16, enable_hll=True, hll_p=10,
        enable_quantiles=True,
    )
    spec = SyntheticSpec(
        num_partitions=5, messages_per_partition=700,
        keys_per_partition=90, tombstone_permille=120, seed=77,
    )
    batches = [
        b.pad_to(cfg.batch_size)
        for b in SyntheticSource(spec).batches(cfg.batch_size)
    ]
    direct = TpuBackend(cfg, init_now_s=0)
    staged = TpuBackend(cfg, init_now_s=0)
    for b in batches:
        direct.update(b)
        staged.update(staged.prepare(b))
    md, ms = direct.finalize(), staged.finalize()
    assert np.array_equal(md.per_partition, ms.per_partition)
    assert np.array_equal(md.per_partition_extremes, ms.per_partition_extremes)
    assert md.overall_count == ms.overall_count
    assert md.overall_size == ms.overall_size
    assert md.alive_keys == ms.alive_keys
    assert md.distinct_keys_hll == ms.distinct_keys_hll
    assert list(md.quantiles.values) == list(ms.quantiles.values)
