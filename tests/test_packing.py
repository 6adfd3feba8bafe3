"""Packed transfer (the layout contract is packing.py's module docstring):
layout roundtrip, host pre-reductions, and the packers' agreement."""

import jax
import numpy as np
import pytest

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.packing import (
    batch_alive_pairs,
    dedupe_slots_numpy,
    hll_idx_rho_numpy,
    pack_batch,
    packed_nbytes,
    unpack_device,
    unpack_numpy,
)
from kafka_topic_analyzer_tpu.results import COUNTER_CHANNELS

SPEC = SyntheticSpec(
    num_partitions=5,
    messages_per_partition=300,
    keys_per_partition=40,
    key_null_permille=100,
    tombstone_permille=200,
    seed=21,
)

CFG = AnalyzerConfig(
    num_partitions=5,
    batch_size=512,
    count_alive_keys=True,
    alive_bitmap_bits=18,
    enable_hll=True,
    hll_p=10,
)


def _batch():
    return next(SyntheticSource(SPEC).batches(400)).pad_to(512)


def _reference_counts(batch, n: int, parts: int) -> np.ndarray:
    """The [P, 7] counter table written from the channel DEFINITIONS,
    record by record (independently of the packers' vectorized fold)."""
    want = np.zeros((parts, len(COUNTER_CHANNELS)), dtype=np.int64)
    for i in range(n):
        row = want[int(batch.partition[i])]
        kn, vn = not batch.key_null[i], not batch.value_null[i]
        row[0] += 1
        row[1] += not vn
        row[2] += vn
        row[3] += not kn
        row[4] += kn
        row[5] += int(batch.key_len[i]) if kn else 0
        row[6] += int(batch.value_len[i]) if vn else 0
    return want


def test_pack_unpack_numpy_roundtrip():
    batch = _batch()
    buf = pack_batch(batch, CFG, use_native=False)
    assert buf.nbytes == packed_nbytes(CFG, 512)
    got = unpack_numpy(buf, CFG)
    assert int(got["n_valid"]) == 400
    assert set(got) == {
        "n_valid", "counts", "ts_min", "ts_max", "sz_min", "sz_max",
        "hll_regs",
    }
    assert np.array_equal(got["counts"], _reference_counts(batch, 400, 5))
    # ts and size extremes ship as host-reduced per-partition min/max
    # tables (sizes tombstone-excluded, key bytes only when the key is
    # non-null; identities I64_MAX / I64_MIN and I64_MAX / 0).
    sizes = (
        np.where(batch.key_null[:400], 0, batch.key_len[:400]).astype(np.int64)
        + batch.value_len[:400]
    )
    for p in range(CFG.num_partitions):
        sel = batch.partition[:400] == p
        if sel.any():
            assert got["ts_min"][p] == batch.ts_s[:400][sel].min()
            assert got["ts_max"][p] == batch.ts_s[:400][sel].max()
        else:
            assert got["ts_min"][p] == np.iinfo(np.int64).max
            assert got["ts_max"][p] == np.iinfo(np.int64).min
        sized = sel & ~batch.value_null[:400]
        if sized.any():
            assert got["sz_min"][p] == sizes[sized].min()
            assert got["sz_max"][p] == sizes[sized].max()
        else:
            assert got["sz_min"][p] == np.iinfo(np.int64).max
            assert got["sz_max"][p] == 0


def test_device_unpack_matches_numpy_unpack():
    batch = _batch()
    buf = pack_batch(batch, CFG, use_native=False)
    expected = unpack_numpy(buf, CFG)
    got = jax.jit(lambda b: unpack_device(b, CFG))(buf)
    for name, exp in expected.items():
        assert np.array_equal(np.asarray(got[name]), np.asarray(exp)), name


def test_dedupe_numpy_last_writer_wins():
    h32 = np.array([5, 5, 6, 6, 7, 9], dtype=np.uint32)
    active = np.array([1, 1, 1, 1, 1, 0], dtype=bool)
    alive = np.array([1, 0, 0, 1, 1, 1], dtype=bool)
    slots, flags = dedupe_slots_numpy(h32, active, alive, bits=16)
    result = dict(zip(slots.tolist(), flags.tolist()))
    assert result == {5: 0, 6: 1, 7: 1}  # inactive slot 9 ignored


#: HARD-CODED expected HLL wire mode per (per_partition, hll_p) at
#: b=512/P=5 — independent of hll_table_rows, so a threshold bug in the
#: size rule fails here instead of shifting expectations silently.
HLL_MODE = {
    (False, 8): "table",   # 1*256  <= 1536
    (False, 10): "table",  # 1*1024 <= 1536
    (False, 16): "pairs",  # 1*65536 > 1536
    (True, 8): "table",    # 5*256  <= 1536 — the R>1 row-indexed path
    (True, 10): "pairs",   # 5*1024 > 1536
    (True, 16): "pairs",
}


def test_hll_table_rows_size_rule():
    """The one decision function every packer derives the mode from."""
    import dataclasses

    from kafka_topic_analyzer_tpu.packing import hll_table_rows

    for (pp, p), mode in HLL_MODE.items():
        cfg = dataclasses.replace(
            CFG, hll_p=p, distinct_keys_per_partition=pp
        )
        rows = hll_table_rows(cfg, 512)
        assert bool(rows) == (mode == "table"), (pp, p)
        if rows:
            assert rows == (5 if pp else 1)
    # Boundary (global p=8, table = 256 B): 3*86 = 258 >= 256 -> table;
    # 3*85 = 255 < 256 -> pairs.
    cfg = dataclasses.replace(CFG, hll_p=8, distinct_keys_per_partition=False)
    assert hll_table_rows(cfg, 86) == 1
    assert hll_table_rows(cfg, 85) == 0


@pytest.mark.parametrize("hll_p", [8, 10, 16])
@pytest.mark.parametrize("per_partition", [False, True])
def test_native_pack_semantics_match_numpy(hll_p, per_partition):
    import dataclasses

    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    if not native.native_available():
        pytest.skip("native shim unavailable")
    cfg = dataclasses.replace(
        CFG, hll_p=hll_p, distinct_keys_per_partition=per_partition
    )
    batch = _batch()
    a = pack_batch(batch, cfg, use_native=False)
    b = pack_batch(batch, cfg, use_native=True)
    ua, ub = unpack_numpy(a, cfg), unpack_numpy(b, cfg)
    nv = int(ua["n_valid"])
    assert nv == int(ub["n_valid"])
    mode = HLL_MODE[(per_partition, hll_p)]
    hll_names = (
        ("hll_regs",) if mode == "table"
        else ("hll_idx32", "hll_rho") if per_partition
        else ("hll_idx", "hll_rho")
    )
    assert set(ua) == set(ub) == {
        "n_valid", "counts", "ts_min", "ts_max", "sz_min", "sz_max",
    } | set(hll_names)
    for name in ua:
        assert np.array_equal(ua[name], ub[name]), name
    assert np.array_equal(a, b)  # header and padding bytes too
    # Alive pairs leave the row: dedupe pair ORDER differs between the
    # packers (sorted vs first-touch); counts must match exactly (dict
    # comparison alone would mask duplicate emissions), then as dicts.
    sa, fa = batch_alive_pairs(batch, cfg, use_native=False)
    sb, fb = batch_alive_pairs(batch, cfg, use_native=True)
    assert len(sa) == len(sb)
    assert dict(zip(sa.tolist(), fa.tolist())) == dict(
        zip(sb.tolist(), fb.tolist())
    )


def test_native_pack_odd_batch_size_and_empty():
    """Alignment safety (batch_size not a multiple of 8) and empty batches
    must stay on the native path, not silently fall back or crash."""
    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    if not native.native_available():
        pytest.skip("native shim unavailable")
    import dataclasses

    odd_cfg = dataclasses.replace(CFG, batch_size=517)
    batch = next(SyntheticSource(SPEC).batches(400)).pad_to(517)
    a = pack_batch(batch, odd_cfg, use_native=False)
    b = native.pack_batch_native(batch, odd_cfg)
    assert b is not None
    ua, ub = unpack_numpy(a, odd_cfg), unpack_numpy(b, odd_cfg)
    for name in ("counts", "ts_min", "ts_max", "sz_min", "sz_max"):
        assert np.array_equal(ua[name], ub[name]), name
    from kafka_topic_analyzer_tpu.records import RecordBatch

    empty = native.pack_batch_native(RecordBatch.empty(0), odd_cfg)
    assert empty is not None
    ue = unpack_numpy(empty, odd_cfg)
    assert int(ue["n_valid"]) == 0 and not ue["counts"].any()


def test_pack_rejects_negative_lengths():
    batch = _batch()
    batch.value_len[2] = -5
    with pytest.raises(ValueError, match="negative"):
        pack_batch(batch, CFG, use_native=False)


def test_dedupe_native_matches_numpy():
    native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")
    if not native.native_available():
        pytest.skip("native shim unavailable")
    rng = np.random.default_rng(3)
    n = 5000
    h32 = rng.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
    active = rng.random(n) > 0.1
    alive = rng.random(n) > 0.3
    for bits in (8, 16, 32):
        s_np, f_np = dedupe_slots_numpy(h32, active, alive, bits)
        s_nat, f_nat = native.dedupe_slots_native(h32, active, alive, bits)
        assert dict(zip(s_np.tolist(), f_np.tolist())) == dict(
            zip(s_nat.tolist(), f_nat.tolist())
        ), bits


@pytest.mark.parametrize("p", [10, 16])
def test_hll_idx_rho_matches_reference(p):
    """p=16 is the default AND the u16 edge: max idx 65535 must survive the
    packed section round trip (the old sentinel-bucket design would have
    overflowed here)."""
    from kafka_topic_analyzer_tpu.ops.fnv import splitmix64

    rng = np.random.default_rng(4)
    h64 = rng.integers(0, 2**63, size=1000, dtype=np.uint64)
    # make some values produce long rho runs
    h64[:4] = [0, 1, 1 << 50, (1 << 64) - 1]
    active = np.ones(1000, dtype=bool)
    idx, rho = hll_idx_rho_numpy(h64, active, p)
    for i in range(64):
        h = splitmix64(int(h64[i]))
        exp_idx = h >> (64 - p)
        rest = (h << p) & ((1 << 64) - 1)
        exp_rho = (64 - p + 1) if rest == 0 else (64 - rest.bit_length() + 1)
        assert int(idx[i]) == exp_idx, i
        assert int(rho[i]) == exp_rho, i


def test_pack_rejects_oversize_keys():
    batch = _batch()
    batch.key_len[0] = 1 << 17
    with pytest.raises(ValueError, match="key length"):
        pack_batch(batch, CFG, use_native=False)


def test_pack_rejects_non_prefix_valid():
    batch = _batch()
    batch.valid[10] = False
    with pytest.raises(ValueError, match="prefix-valid"):
        pack_batch(batch, CFG, use_native=False)


def _random_batch(rng, n: int, parts: int, vmin: int, vmax: int):
    from kafka_topic_analyzer_tpu.records import RecordBatch

    key_null = rng.random(n) < 0.1
    value_null = rng.random(n) < 0.15
    h32 = rng.integers(0, 2**32, n, dtype=np.uint32)
    return RecordBatch(
        partition=rng.integers(0, parts, n).astype(np.int32),
        key_len=np.where(key_null, 0, rng.integers(0, 1 << 16, n)).astype(
            np.int32
        ),
        value_len=np.where(value_null, 0, rng.integers(vmin, vmax, n)).astype(
            np.int32
        ),
        key_null=key_null,
        value_null=value_null,
        ts_s=rng.integers(0, 2**31, n),
        key_hash32=np.where(key_null, 0, h32).astype(np.uint32),
        key_hash64=np.where(
            key_null, 0, rng.integers(0, 2**63, n, dtype=np.uint64)
        ).astype(np.uint64),
        valid=np.ones(n, dtype=bool),
    )


def _packers():
    from kafka_topic_analyzer_tpu.io.native import native_available

    return [False, True] if native_available() else [False]


@pytest.mark.parametrize("parts", [1, 3, 16, 64, 200, 300])
def test_counter_table_matches_oracle(parts):
    """Every packer's [P, 7] counter table, and the device's fold of two
    of them, equal the CPU oracle's counters — at a batch size that is no
    multiple of 1024 and partition counts past 128."""
    from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend

    b = 1000
    rng = np.random.default_rng(parts)
    cfg = AnalyzerConfig(num_partitions=parts, batch_size=b)
    batches = [_random_batch(rng, b, parts, 0, 5000) for _ in range(2)]
    cpu = CpuExactBackend(cfg, init_now_s=0)
    tpu = TpuBackend(cfg, init_now_s=0)
    for batch in batches:
        one = CpuExactBackend(cfg, init_now_s=0)
        one.update(batch)
        want = one.finalize().per_partition
        for native in _packers():
            got = unpack_numpy(pack_batch(batch, cfg, use_native=native), cfg)
            assert np.array_equal(got["counts"], want), native
        cpu.update(batch)
        tpu.update(batch)
    assert np.array_equal(
        tpu.finalize().per_partition, cpu.finalize().per_partition
    )


@pytest.mark.parametrize("native", [False, True], ids=["numpy", "native"])
def test_large_values_exact_against_oracle(native):
    """Values of 2^24 bytes and more, whose byte sums pass 2^32 within a
    single batch and partition, fold exactly: the whole report equals the
    oracle's (sketch lines through tests/oracle.py)."""
    from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend

    from oracle import sketched

    if native and len(_packers()) == 1:
        pytest.skip("native shim unavailable")
    rng = np.random.default_rng(24)
    cfg = AnalyzerConfig(
        num_partitions=2, batch_size=800, count_alive_keys=True,
        alive_bitmap_bits=16, enable_hll=True, hll_p=10,
        enable_quantiles=True, quantiles_per_partition=True,
    )
    batches = [
        _random_batch(rng, 800, 2, 1 << 24, (1 << 24) + (1 << 22))
        for _ in range(2)
    ]
    one = unpack_numpy(pack_batch(batches[0], cfg, use_native=native), cfg)
    assert int(one["counts"][:, 6].max()) > 1 << 32
    cpu = CpuExactBackend(cfg, init_now_s=0)
    tpu = TpuBackend(cfg, init_now_s=0, use_native=native)
    for batch in batches:
        cpu.update(batch)
        tpu.update(batch)
    assert tpu.finalize().to_dict() == sketched(cpu, cpu.finalize()).to_dict()
