"""Pallas TPU kernel: fused per-partition counter reduction on the MXU.

The counter update (ops/counters.py) is a segment-sum of 7 channels by
partition id — XLA lowers it as a scatter-add.  This kernel instead maps it
onto the MXU as a one-hot matmul, the TPU-native formulation of a segment
sum (guide: /opt/skills/guides/pallas_guide.md):

    contrib[16, N] · one_hot[N, P] → [16, P]    (per 1024-record block)

Each input streams as a (1, 1024) lane row of a (1, B) view, so every
operand inside the kernel stays 2-D.

**Exactness.**  The MXU accumulates in f32, which is exact only below 2^24.
Counts are 0/1 so they are safe, but byte lengths are not — so the two byte
channels are decomposed into 12-bit digits (lo = v & 0xFFF, hi = v >> 12):
every matmul partial is ≤ 4095·1024 < 2^24, the per-block result converts
losslessly to i32, blocks accumulate in an i32 VMEM scratch (safe for
≤ 2^18 records per call), and the digits recombine in i64 outside.  Value
lengths are capped at 2^24-1 (16 MiB, enforced by packing.py) so two digits
suffice.

Channel plane layout (rows of the [16, P] accumulator):
    0..6  COUNTER_CHANNELS lo digits (counts have no hi digit)
    7     key_size_sum   hi digit
    8     value_size_sum hi digit
    9..15 zero padding (MXU-friendly row count)

Partition counts beyond 128 tile the grid's leading dimension (one
accumulator pass per 128-partition tile), and the kernel runs under
`shard_map` meshes (parallel/sharded.py relaxes the vma check for the
interpreter only).
Enabled by ``AnalyzerConfig.use_pallas_counters``; the lax scatter path
remains the default until the kernel is benchmarked faster on real hardware.

``interpret`` is the caller's choice: the backends set it from the platform
of their own device (``AnalyzerConfig.pallas_interpret``), so a kernel
compiled for a TPU is never silently swapped for the interpreter.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kafka_topic_analyzer_tpu.jax_support import jnp

#: Records per grid step: one (1, 1024) int32 lane row per input.
BLOCK = 1024
#: Max records per pallas_call: keeps i32 scratch sums < 2^31
#: (2^18 · 4095 ≈ 1.07e9).
MAX_CALL = 1 << 18
PLANES = 16
#: Partitions per 128-lane output tile; wider topics tile the grid's
#: leading dimension (one accumulator pass per tile).
PART_TILE = 128


def _kernel(part_ref, klen_ref, vlen_ref, kn_ref, vn_ref, valid_ref, out_ref, acc_ref):
    j = pl.program_id(0)  # partition tile
    i = pl.program_id(1)  # record block

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Every operand stays 2-D: a (1, BLOCK) lane row per input (Mosaic
    # cannot relayout an (8, 128) tile into a flat vector).
    part = part_ref[:]                      # [1, BLOCK] i32
    klen = klen_ref[:]
    vlen = vlen_ref[:]
    kn = kn_ref[:]                          # i32 0/1: valid & key non-null
    vn = vn_ref[:]                          # i32 0/1: valid & value non-null
    valid = valid_ref[:]                    # i32 0/1

    tomb = valid - vn                       # valid & value_null
    knull = valid - kn
    k_bytes = klen * kn
    v_bytes = vlen * vn

    planes = [
        valid,                               # total
        tomb,                                # tombstones
        vn,                                  # alive
        knull,                               # key_null
        kn,                                  # key_non_null
        k_bytes & 0xFFF,                     # key_size_sum lo
        v_bytes & 0xFFF,                     # value_size_sum lo
        k_bytes >> 12,                       # key_size_sum hi
        v_bytes >> 12,                       # value_size_sum hi
    ]
    # Plane c lands on sublane c by select (rows 9..15 stay zero).
    row = jax.lax.broadcasted_iota(jnp.int32, (PLANES, BLOCK), 0)
    contrib = jnp.zeros((PLANES, BLOCK), jnp.int32)
    for c, plane in enumerate(planes):
        contrib = jnp.where(row == c, plane, contrib)
    contrib = contrib.astype(jnp.float32)                   # [16, BLOCK]

    # Transposed one-hot over this tile's partition range
    # [j·128, (j+1)·128); invalid records carry partition 0 but all their
    # contribution planes are 0, so they add nothing.
    iota = jax.lax.broadcasted_iota(jnp.int32, (PART_TILE, BLOCK), 0)
    iota = iota + j * PART_TILE
    one_hot_t = (part == iota).astype(jnp.float32)         # [128, BLOCK]

    # precision=HIGHEST: without it the MXU may run f32 operands through
    # bf16 passes, whose 8-bit mantissa cannot represent the 12-bit digit
    # planes — preferred_element_type alone only fixes the accumulator.
    block_out = jax.lax.dot_general(
        contrib,
        one_hot_t,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                                       # [16, 128] tile j
    acc_ref[:] += block_out.astype(jnp.int32)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[:]


#: Block-index zero as i32: under jax_enable_x64 a bare ``0`` in an index
#: map traces as i64, which Mosaic cannot return next to the i32 grid ids.
_ZERO = np.int32(0)


def _call(part, klen, vlen, kn, vn, valid, p_pad: int, interpret: bool):
    n = part.shape[0]
    assert n % BLOCK == 0 and n <= MAX_CALL
    assert p_pad % PART_TILE == 0

    def row(x):
        return x.reshape(1, n)

    # Under a check_vma shard_map the output aval must declare its
    # varying mesh axes; the reduction preserves the inputs' variance
    # (per-device records → per-device counts), so propagate their vma.
    vma = jax.typeof(part).vma or None
    out_aval = (
        jax.ShapeDtypeStruct((PLANES, p_pad), jnp.int32, vma=vma)
        if vma
        else jax.ShapeDtypeStruct((PLANES, p_pad), jnp.int32)
    )
    # Partition tiles lead the grid so each tile streams all record
    # blocks through its own accumulator pass (i innermost).
    in_spec = pl.BlockSpec((1, BLOCK), lambda j, i: (_ZERO, i))
    out = pl.pallas_call(
        _kernel,
        grid=(p_pad // PART_TILE, n // BLOCK),
        in_specs=[in_spec] * 6,
        out_specs=pl.BlockSpec((PLANES, PART_TILE), lambda j, i: (_ZERO, j)),
        out_shape=out_aval,
        scratch_shapes=[pltpu.VMEM((PLANES, PART_TILE), jnp.int32)],
        interpret=interpret,
    )(row(part), row(klen), row(vlen), row(kn), row(vn), row(valid))
    return out


def pallas_counters_update(
    per_partition,  # int64[P, 7]
    partition,      # int32[B]
    key_len,
    value_len,
    key_null,
    value_null,
    valid,
    num_partitions: int,
    interpret: bool = False,
):
    """Drop-in replacement for ops.counters.counters_update via the MXU
    kernel.  Requires B % 1024 == 0 (config batch sizes are powers of two)."""
    b = partition.shape[0]
    if b % BLOCK != 0:
        raise ValueError(f"batch size {b} must be a multiple of {BLOCK}")
    kn = (valid & ~key_null).astype(jnp.int32)
    vn = (valid & ~value_null).astype(jnp.int32)
    v32 = valid.astype(jnp.int32)
    part = partition.astype(jnp.int32)
    klen = key_len.astype(jnp.int32)
    vlen = value_len.astype(jnp.int32)

    p_pad = -(-num_partitions // PART_TILE) * PART_TILE
    total = jnp.zeros((PLANES, p_pad), dtype=jnp.int64)
    # Under a check_vma shard_map the kernel output varies over the mesh
    # axes its inputs vary over; the zeros accumulator starts replicated
    # and must be explicitly cast to match before the add.
    axes = tuple(sorted(jax.typeof(partition).vma))
    if axes:
        total = jax.lax.pvary(total, axes)
    for lo in range(0, b, MAX_CALL):
        hi = min(lo + MAX_CALL, b)
        sl = slice(lo, hi)
        total = total + _call(
            part[sl], klen[sl], vlen[sl], kn[sl], vn[sl], v32[sl],
            p_pad, interpret,
        ).astype(jnp.int64)

    p = num_partitions
    counts = total[:5, :p]                                # [5, P]
    k_sum = total[5, :p] + (total[7, :p] << 12)
    v_sum = total[6, :p] + (total[8, :p] << 12)
    delta = jnp.concatenate(
        [counts, k_sum[None, :], v_sum[None, :]], axis=0
    ).T                                                    # [P, 7]
    return per_partition + delta


# ---------------------------------------------------------------------------
# Wire-v5 table merge
#
# Under the v5 combiner format (packing.py) the per-partition counter fold
# arrives as a pre-reduced i64[P, 7] delta table: there is no scatter left
# for the one-hot matmul above to replace — the whole fold is an elementwise
# i64 add.  This kernel keeps the pallas path compiled against the v5 table
# inputs (untimed on the chip): the add runs on the VPU as two u32 digit planes with an
# explicit carry, the same exactness discipline as the matmul kernel's
# 12-bit digits (TPU pallas has no native i64 lanes).

#: Rows per merge grid step: an (8, 128) u32 tile.
_MERGE_ROWS = 8


def _merge_kernel(alo_ref, ahi_ref, blo_ref, bhi_ref, lo_ref, hi_ref):
    alo = alo_ref[:]
    lo = alo + blo_ref[:]                     # u32 add wraps mod 2^32
    carry = (lo < alo).astype(jnp.int32)      # unsigned overflow detect
    lo_ref[:] = lo
    hi_ref[:] = ahi_ref[:] + bhi_ref[:] + carry


def pallas_counters_merge(per_partition, delta, interpret: bool = False):
    """Elementwise ``per_partition + delta`` for wire-v5 ``i64[P, 7]``
    counter tables via a pallas VPU kernel — exact for every i64 value
    (lo/hi u32 digits with carry).  Drop-in for the plain jnp add the
    default v5 path uses; selected by ``use_pallas_counters``."""
    shape = per_partition.shape
    n = 1
    for d in shape:
        n *= d
    pad = -n % (_MERGE_ROWS * 128)

    def planes(v):
        flat = v.reshape(-1)
        if pad:
            zeros = jnp.zeros((pad,), dtype=flat.dtype)
            axes = tuple(sorted(jax.typeof(v).vma))
            if axes:
                # Under a check_vma shard_map the pad constant starts
                # replicated and must match the data's variance to concat.
                zeros = jax.lax.pvary(zeros, axes)
            flat = jnp.concatenate([flat, zeros])
        # Arithmetic split instead of a bitcast: truncation and arithmetic
        # shift-right are endianness-free, so lo/hi identification cannot
        # depend on platform byte order.
        lo = (flat & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (flat >> jnp.int64(32)).astype(jnp.int32)
        return lo.reshape(-1, 128), hi.reshape(-1, 128)

    alo, ahi = planes(per_partition)
    blo, bhi = planes(delta.astype(jnp.int64))
    rows = alo.shape[0]
    vma = jax.typeof(per_partition).vma | jax.typeof(delta).vma
    vma = vma or None

    def out_aval(dtype):
        if vma:
            return jax.ShapeDtypeStruct((rows, 128), dtype, vma=vma)
        return jax.ShapeDtypeStruct((rows, 128), dtype)

    spec = pl.BlockSpec((_MERGE_ROWS, 128), lambda i: (i, _ZERO))
    lo, hi = pl.pallas_call(
        _merge_kernel,
        grid=(rows // _MERGE_ROWS,),
        in_specs=[spec] * 4,
        out_specs=(spec, spec),
        out_shape=(out_aval(jnp.uint32), out_aval(jnp.int32)),
        interpret=interpret,
    )(alo, ahi, blo, bhi)
    merged = (hi.astype(jnp.int64).reshape(-1) << jnp.int64(32)) | lo.astype(
        jnp.int64
    ).reshape(-1)
    if pad:
        merged = merged[:n]
    return merged.reshape(shape)
