"""Packed host→device batch transfer (wire format v5).

The expected bottleneck of the streaming path is host→device bandwidth
(SURVEY.md §7 hard part (a)).  Every metric is an associative
per-partition fold, so the host ships the fold's partial RESULT instead
of the records it was computed from — the MapReduce-combiner move — and
the device merges tables elementwise: O(P·H) work per dispatch instead of
an O(B) scatter.  A batch crosses as ONE contiguous ``uint8[N]`` buffer
(one transfer), sections in order (B = static batch size, P =
num_partitions):

    header    u8[16]      n_valid i32 | n_pairs i32 (0 in rows) | reserved
    counts    i64[7P]     per-partition counter deltas, row-major [P, 7]
                          in results.COUNTER_CHANNELS order (total,
                          tombstones, alive, key_null, key_non_null,
                          key_size_sum, value_size_sum)
    ts_minmax i64[2P]     per-partition ts min then max, identity-filled
                          (I64_MAX / I64_MIN)
    sz_minmax i64[2P]     per-partition message-size min then max
                          (tombstone-excluded, identities I64_MAX / 0)
    [hll]     regs u8[R << p]  host-reduced register table (R = 1 global,
              P per-partition) WHEN R·2^p ≤ 3·B; else pairs — idx u16[B]
              + rho u8[B] globally, idx32 u32[B] (= partition << p |
              bucket) + rho u8[B] per-partition.  One size rule,
              ``hll_wire_mode``, decides for the packers and (via section
              names) the device step
    [quant]   i64[R·(nbuckets+2)]  iff enable_quantiles: per-row DDSketch
              bucket-count deltas (R = P per-partition else 1), buckets
              from the shared integer edge table (ops/ddsketch.py)

Every fold above is an integer sum or min/max (associative and
commutative), and the DDSketch bucket rule is one integer edge table on
every packer, so the device state equals the CPU oracle's fold exactly.

**The alive table.**  With ``count_alive_keys`` the rows carry no alive
sections: each device DISPATCH ships one more buffer, the last-writer-wins
merge — in stream order — of the per-batch deduped (slot, aliveness)
pairs of every batch it folds (DESIGN.md §19), applied once after the
dispatch's scan.  Header as above (n_valid 0 | n_pairs), then one of two
forms, picked by ``alive_table_mode`` from the slot-space size:

    pairs     slot u32[T] | flag u8[T]     T = pair_table_capacity
    masks     set u32[W] | clear u32[W]    W = 2^bits / 32 bitmap words

Device-side unpacking is pure ``lax.bitcast_convert_type`` on reshaped slices
(both host and TPU are little-endian; the TPU backend runs a one-time
pack→unpack self-check at init to guarantee it).
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.obs import trace as obs_trace
from kafka_topic_analyzer_tpu.records import RecordBatch

HEADER_BYTES = 16
MAX_KEY_LEN = 0xFFFF
#: Dense partition indices fit 15 bits: the flat HLL pair index packs
#: ``partition << p`` (p ≤ 16) into a u32.
MAX_PARTITIONS = 0x7FFF
#: The layout version this module packs (the ``--stats`` wire line and the
#: ``--json`` ``wire`` block report it).
WIRE_FORMAT = 5


def _sections(config: AnalyzerConfig, batch_size: int,
              pair_table: bool = False):
    """(name, dtype, count) section list, in buffer order — the one layout
    source (module docstring): the packers, the unpackers and the device
    step all derive from it, so they cannot skew (lint rule 7).

    ``pair_table=True`` returns the layout of ONE alive TABLE buffer
    instead (DESIGN §19): the per-dispatch LWW-merged table the device
    applies once per dispatch, with ``batch_size`` then meaning the table
    CAPACITY (``pair_table_capacity``); the pair packers
    (`pack_pair_table`) and unpackers (`unpack_pair_table_*`) derive from
    this list too.

    Known trade-off: a sharded scan packs each of its S space chunks with
    tables of its own, so table bytes per step grow S-fold — a cost only
    when the tables approach the chunk's record bytes, which no realistic
    config (P ≤ thousands, B ≥ 2^17) reaches.
    """
    b = batch_size
    p = config.num_partitions
    if pair_table:
        if alive_table_mode(config, b) == 2:
            w = _alive_mask_words(config)
            return [
                ("alive_set", np.uint32, w),
                ("alive_clear", np.uint32, w),
            ]
        return [
            ("alive_slot", np.uint32, b),
            ("alive_flag", np.uint8, b),
        ]
    sec = [
        ("counts", np.int64, 7 * p),
        ("ts_minmax", np.int64, 2 * p),
        ("sz_minmax", np.int64, 2 * p),
    ]
    mode = hll_wire_mode(config, b)
    if mode == 2:
        # Register max is fully commutative, so the host pre-reduces the
        # whole batch to a u8[R, 2^p] register table and the device
        # merges it ELEMENTWISE — no scatter on the hot path.
        sec.append(
            ("hll_regs", np.uint8, hll_table_rows(config, b) << config.hll_p)
        )
    elif mode == 3:
        # Per-partition pairs: the register ROW rides inside the index
        # (partition << p | bucket — 15 + 16 bits fit u32).
        sec.append(("hll_idx32", np.uint32, b))
        sec.append(("hll_rho", np.uint8, b))
    elif mode == 1:
        # Global pairs: per-record (register index, rho) — cheaper on the
        # wire than a table whenever 2^p > 3·B.
        sec.append(("hll_idx", np.uint16, b))
        sec.append(("hll_rho", np.uint8, b))
    if config.enable_quantiles:
        from kafka_topic_analyzer_tpu.ops.ddsketch import ddsketch_num_buckets

        q_rows = p if config.quantiles_per_partition else 1
        sec.append(
            ("qcounts", np.int64,
             q_rows * ddsketch_num_buckets(config.quantile_buckets))
        )
    return sec


#: Sections whose byte count scales with the batch size — the per-record
#: share of a packed buffer (the HLL pair forms).  Everything
#: else (header included) is a fold-table constant per batch.  Drives
#: ``section_byte_split`` and the ``--stats`` wire line.
PER_RECORD_SECTIONS = frozenset({"hll_idx", "hll_idx32", "hll_rho"})


def section_byte_split(
    config: AnalyzerConfig, batch_size: int
) -> "Tuple[int, int]":
    """(per_record_bytes, fold_table_bytes) of one packed buffer — the
    fold-table share includes the header.  Derived from ``_sections`` (the
    single layout source, lint rule 7), summing to ``packed_nbytes``."""
    per_record = 0
    table = HEADER_BYTES
    for name, dtype, count in _sections(config, batch_size):
        nbytes = np.dtype(dtype).itemsize * count
        if name in PER_RECORD_SECTIONS:
            per_record += nbytes
        else:
            table += nbytes
    return per_record, table


def hll_table_rows(config: AnalyzerConfig, batch_size: int) -> int:
    """Rows of the host-reduced HLL register table, or 0 for pair mode.

    The table costs ``R << hll_p`` bytes per batch vs 3 B/record of
    pairs: ship whichever is smaller.  Pack, unpack, and the device step
    all derive the mode from this one function (the step via the
    presence of the ``hll_regs`` array), so the decision cannot skew."""
    rows = (
        config.num_partitions if config.distinct_keys_per_partition else 1
    )
    return rows if (rows << config.hll_p) <= 3 * batch_size else 0


def hll_wire_mode(config: AnalyzerConfig, batch_size: int) -> int:
    """The HLL section mode every packer and the layout derive from — ONE
    function so the numpy path, the native calls, and ``_sections`` can
    never disagree (the same discipline as ``hll_table_rows``, which
    decides the table half of this rule):

    - ``0`` — HLL off;
    - ``1`` — global u16 (bucket, rho) pairs;
    - ``2`` — host-reduced register table (``hll_table_rows`` rows);
    - ``3`` — per-partition flat u32 pairs (``partition << p | bucket``).
    """
    if not config.enable_hll:
        return 0
    if hll_table_rows(config, batch_size):
        return 2
    if config.distinct_keys_per_partition:
        return 3
    return 1


def packed_nbytes(config: AnalyzerConfig, batch_size: int) -> int:
    return HEADER_BYTES + sum(
        np.dtype(dt).itemsize * n for _, dt, n in _sections(config, batch_size)
    )


# ---------------------------------------------------------------------------
# host-side pre-reductions


def dedupe_slots_numpy(
    h32: np.ndarray, active: np.ndarray, alive: np.ndarray, bits: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Last-writer-wins (slot, aliveness) pairs for one batch (numpy).

    Equivalent to replaying insert/remove in record order
    (src/metric.rs:273-280): only each slot's last record survives.
    """
    slot = (h32.astype(np.uint64) & np.uint64((1 << bits) - 1)).astype(np.uint32)
    slot = slot[active]
    alive = alive[active]
    if len(slot) == 0:
        return slot, alive.astype(np.uint8)
    uniq, first_rev = np.unique(slot[::-1], return_index=True)
    return uniq.astype(np.uint32), alive[::-1][first_rev].astype(np.uint8)


def hll_idx_rho_numpy(
    h64: np.ndarray, active: np.ndarray, p: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pre-split HLL updates: (bucket index, rho).  Inactive records get
    bucket 0 with rho 0 — a no-op under scatter-max, so indices use the
    full u16 range (p up to 16 inclusive) with no sentinel bucket."""
    from kafka_topic_analyzer_tpu.ops.fnv import splitmix64_np

    h = splitmix64_np(h64.astype(np.uint64))
    idx = (h >> np.uint64(64 - p)).astype(np.uint16)
    rest = (h << np.uint64(p)) & np.uint64((1 << 64) - 1)
    # rho = clz(rest) + 1, capped at 64 - p + 1 when rest == 0.
    # numpy >= 2.0: bit_count unavailable for clz; use float trick on the
    # top bits via log2 of rest (exact for leading-zero counting).
    rho = np.full(h.shape, 64 - p + 1, dtype=np.uint8)
    nz = rest != 0
    # floor(log2(x)) is exact for uint64 -> float64 only up to 2^53 of
    # mantissa; compute clz via hi/lo split to stay exact.
    hi = (rest >> np.uint64(32)).astype(np.uint32)
    lo = (rest & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    clz_hi = 31 - np.floor(np.log2(np.maximum(hi, 1).astype(np.float64))).astype(np.int32)
    clz_lo = 63 - np.floor(np.log2(np.maximum(lo, 1).astype(np.float64))).astype(np.int32)
    clz = np.where(hi != 0, clz_hi, np.where(lo != 0, clz_lo, 64)).astype(np.int32)
    rho[nz] = (clz[nz] + 1).astype(np.uint8)
    # Inactive records: rho 0 is a no-op under scatter-max (registers are
    # never negative), so no index sentinel is needed.
    idx = np.where(active, idx, np.uint16(0))
    rho = np.where(active, rho, np.uint8(0))
    return idx.astype(np.uint16), rho


def _dedupe_slots(h32, active, alive, bits, use_native=True):
    if use_native:
        try:
            from kafka_topic_analyzer_tpu.io.native import dedupe_slots_native, native_available

            if native_available():
                return dedupe_slots_native(h32, active, alive, bits)
        except ImportError:
            pass
    return dedupe_slots_numpy(h32, active, alive, bits)


# ---------------------------------------------------------------------------
# the alive table (DESIGN.md §19)
#
# Rows carry no alive sections: every device DISPATCH carries ONE table
# buffer, the LWW merge — in stream order — of the per-batch deduped pairs
# of all K batches the dispatch folds.  LWW compaction is LWW-associative
# (compact(a,b) then merge with compact(c,d) in order equals the per-record
# replay), so applying the merged table once AFTER the superbatch scan
# equals the per-record fold — and the O(W) bitmap apply is paid once per
# dispatch instead of K times.


def pair_table_capacity(config: AnalyzerConfig, batch_size: int,
                        k: int = 1) -> int:
    """Static capacity of one dispatch's compacted pair table — the
    bounded-table growth rule: a dispatch folds at most ``k * batch_size``
    records, and distinct slots can never exceed the bitmap's slot space,
    so ``min(k·B, 2^bits)`` bounds the merge with NO overflow path (the
    compacted wire shape never needs a mid-scan fallback)."""
    return min(int(k) * int(batch_size), 1 << config.alive_bitmap_bits)


#: Mask-form cap: the set/clear word masks may grow to at most this many
#: bytes per dispatch (the other half of the bounded-table growth rule);
#: past it the compacted PAIR list is the bounded form.  64 MiB covers
#: ``alive_bitmap_bits <= 28``; the reference-exact 2^32 slot space stays
#: on pairs.
ALIVE_MASK_CAP_BYTES = 64 << 20

#: Mask-vs-pairs trade factor: masks may cost up to this many times the
#: pair list's wire bytes.  Measured rationale (BENCH round 13): the
#: device applies elementwise mask words ~80-180x cheaper per byte than
#: scatter elements (0.7 ms per 16 MB of masks vs ~21-60 ms per 2.6 MB
#: of pair scatter at B=2^16 on the host-CPU jit), so trading up to 32x
#: the bytes for the elementwise merge wins on the host-CPU jit; a bitmap
#: past the caps keeps the pair form.
ALIVE_MASK_TRADE_FACTOR = 32


def _alive_mask_words(config: AnalyzerConfig) -> int:
    return 1 << max(config.alive_bitmap_bits - 5, 0)


def alive_table_mode(config: AnalyzerConfig, capacity: int) -> int:
    """The compacted table's form — ONE rule (like ``hll_wire_mode``) so
    the packers, the layout, and (via section names) the device apply can
    never disagree:

    - ``1`` — bounded pair list ``slot u32[T] | flag u8[T]``, applied by
      a device scatter (the only form that stays bounded for huge slot
      spaces);
    - ``2`` — set/clear word masks ``u32[W] | u32[W]``: the host resolves
      LWW straight into bitmask form and the device merges ELEMENTWISE
      (``(words & ~clear) | set``) like any other fold table — no
      scatter at all.
    """
    mask_nbytes = 2 * _alive_mask_words(config) * 4
    if mask_nbytes <= min(
        ALIVE_MASK_TRADE_FACTOR * 5 * capacity, ALIVE_MASK_CAP_BYTES
    ):
        return 2
    return 1


def pair_table_nbytes(config: AnalyzerConfig, capacity: int) -> int:
    return HEADER_BYTES + sum(
        np.dtype(dt).itemsize * n
        for _, dt, n in _sections(config, capacity, pair_table=True)
    )


def batch_alive_pairs(
    batch: RecordBatch, config: AnalyzerConfig, use_native: bool = True
) -> Tuple[np.ndarray, np.ndarray]:
    """One batch's LWW-deduped (slot, alive) pairs, returned host-side so
    the dispatch can merge them across its batches (`pack_pair_table`)."""
    active = batch.valid & ~batch.key_null
    alive = batch.valid & ~batch.value_null
    return _dedupe_slots(
        batch.key_hash32, active, alive, config.alive_bitmap_bits, use_native
    )


def _pairs_to_masks_numpy(
    slots: np.ndarray, flags: np.ndarray, bits: int
) -> "tuple[np.ndarray, np.ndarray]":
    """Set/clear word masks from DEDUPED (unique-slot) pairs — the numpy
    half of the mask-form build (`alive_table_mode` 2).  Sorted grouping
    + ``bitwise_or.reduceat`` keeps it vectorized; uniqueness means the
    set/clear interplay is already resolved, so plain ORs suffice."""
    w_words = 1 << max(bits - 5, 0)
    set_w = np.zeros(w_words, dtype=np.uint32)
    clear_w = np.zeros(w_words, dtype=np.uint32)
    if len(slots):
        order = np.argsort(slots, kind="stable")
        s = slots[order]
        f = flags[order].astype(bool)
        for subset, mask_out in ((f, set_w), (~f, clear_w)):
            ss = s[subset]
            if not len(ss):
                continue
            w = (ss >> np.uint32(5)).astype(np.int64)
            b = np.uint32(1) << (ss & np.uint32(31))
            starts = np.flatnonzero(np.r_[True, w[1:] != w[:-1]])
            mask_out[w[starts]] = np.bitwise_or.reduceat(b, starts)
    return set_w, clear_w


def pack_pair_table(
    pair_lists,
    config: AnalyzerConfig,
    capacity: int,
    use_native: bool = True,
    out: "np.ndarray | None" = None,
) -> "tuple[np.ndarray, int, int]":
    """LWW-merge per-batch pair lists — IN STREAM ORDER — into one packed
    compacted-alive-table buffer: ``header u8[16]`` (n_pairs at the same
    header slot the rows use) + the ``pair_table`` sections of
    ``_sections``, in the form `alive_table_mode` picks — the bounded
    pair list (mode 1) or set/clear word masks (mode 2).

    Returns ``(buffer, raw_pairs, emitted_pairs)`` — the raw→emitted
    split feeds ``kta_alive_pairs_{raw,emitted}_total`` (in mask form
    "emitted" counts distinct touched slots).  The merge is the same
    per-slot last-writer rule as the per-batch dedupe (later list wins,
    later entry within a list wins); pair ORDER in a mode-1 buffer is
    implementation-defined exactly like `_dedupe_slots` — the device
    result is order-free because slots are unique."""
    parts = [
        (np.ascontiguousarray(s, dtype=np.uint32),
         np.ascontiguousarray(f, dtype=np.uint8))
        for s, f in pair_lists
        if len(s)
    ]
    if parts:
        slots = (
            parts[0][0] if len(parts) == 1
            else np.concatenate([p[0] for p in parts])
        )
        flags = (
            parts[0][1] if len(parts) == 1
            else np.concatenate([p[1] for p in parts])
        )
        raw = len(slots)
    else:
        raw = 0
        slots = np.empty(0, dtype=np.uint32)
        flags = np.empty(0, dtype=np.uint8)
    mode = alive_table_mode(config, capacity)
    nbytes = pair_table_nbytes(config, capacity)
    if out is None:
        out = np.empty(nbytes, dtype=np.uint8)
    elif out.shape != (nbytes,) or out.dtype != np.uint8:
        raise ValueError("pack_pair_table out= must be uint8[nbytes]")
    header = np.zeros(4, dtype=np.int32)
    pos = HEADER_BYTES
    secs = {}
    for name, dtype, count in _sections(config, capacity, pair_table=True):
        nb = np.dtype(dtype).itemsize * count
        secs[name] = out[pos : pos + nb].view(dtype)
        pos += nb

    if mode == 2:
        # Mask form: resolve LWW straight into bitmask monoid values —
        # one native pass over the RAW stream (no merge table at all), or
        # dedupe-then-OR on the numpy path.
        n = None
        if use_native and raw:
            try:
                from kafka_topic_analyzer_tpu.io.native import (
                    native_available,
                    pairs_to_masks_native,
                )

                if native_available():
                    n = pairs_to_masks_native(
                        slots, flags, config.alive_bitmap_bits,
                        secs["alive_set"], secs["alive_clear"],
                    )
            except ImportError:
                pass
        if n is None:
            merged_slots, merged_flags = (
                _dedupe_slots(
                    slots, np.ones(raw, dtype=bool), flags,
                    config.alive_bitmap_bits, use_native,
                )
                if raw
                else (slots, flags)
            )
            set_w, clear_w = _pairs_to_masks_numpy(
                merged_slots, merged_flags, config.alive_bitmap_bits
            )
            secs["alive_set"][:] = set_w
            secs["alive_clear"][:] = clear_w
            n = len(merged_slots)
        header[1] = n
        out[:HEADER_BYTES] = header.view(np.uint8)
        return out, raw, n

    if raw:
        merged_slots, merged_flags = _dedupe_slots(
            slots, np.ones(raw, dtype=bool), flags,
            config.alive_bitmap_bits, use_native,
        )
    else:
        merged_slots, merged_flags = slots, flags
    n = len(merged_slots)
    if n > capacity:
        # Impossible by the capacity rule (pair_table_capacity); a breach
        # means a caller merged more batches than the capacity was sized
        # for — corrupting the table silently would be worse than dying.
        raise AssertionError(
            f"pair-table overflow: {n} merged pairs > capacity {capacity}"
        )
    header[1] = n
    out[:HEADER_BYTES] = header.view(np.uint8)
    for name in ("alive_slot", "alive_flag"):
        sec = secs[name]
        src = merged_slots if name == "alive_slot" else merged_flags
        sec[:n] = src
        sec[n:] = 0
    return out, raw, n


def unpack_pair_table_numpy(
    buf: np.ndarray, config: AnalyzerConfig, capacity: int
) -> Dict[str, np.ndarray]:
    """Host-side reference unpack of a pair-table buffer (tests)."""
    out: Dict[str, np.ndarray] = {
        "n_pairs": buf[:HEADER_BYTES].view(np.int32)[1]
    }
    pos = HEADER_BYTES
    for name, dtype, count in _sections(config, capacity, pair_table=True):
        nb = np.dtype(dtype).itemsize * count
        out[name] = buf[pos : pos + nb].view(dtype)
        pos += nb
    return out


@obs_trace.device_scope("unpack")
def unpack_pair_table_device(buf, config: AnalyzerConfig, capacity: int):
    """uint8[pair_table_nbytes] → typed device arrays (runs under jit) —
    the pair-table twin of `unpack_device`, same bitcast rules."""
    from kafka_topic_analyzer_tpu.jax_support import jnp, lax

    header = lax.bitcast_convert_type(
        buf[:HEADER_BYTES].reshape(4, 4), jnp.int32
    )
    out = {"n_pairs": header[1]}
    pos = HEADER_BYTES
    for name, dtype, count in _sections(config, capacity, pair_table=True):
        nb = np.dtype(dtype).itemsize * count
        sec = buf[pos : pos + nb]
        itemsize = np.dtype(dtype).itemsize
        out[name] = (
            sec
            if itemsize == 1
            else lax.bitcast_convert_type(
                sec.reshape(-1, itemsize), jnp.dtype(dtype)
            )
        )
        pos += nb
    return out


# ---------------------------------------------------------------------------
# pack (host)


I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min


def ts_minmax_table(partition: np.ndarray, ts_s: np.ndarray,
                    num_partitions: int) -> np.ndarray:
    """Host-side per-partition ts reduction: ``[2P]`` int64, mins then
    maxes, identity-filled for partitions absent from this batch.  Inputs
    are the VALID prefix only (callers slice by n_valid)."""
    table = np.empty(2 * num_partitions, dtype=np.int64)
    table[:num_partitions] = I64_MAX
    table[num_partitions:] = I64_MIN
    if len(partition):
        np.minimum.at(table[:num_partitions], partition, ts_s)
        np.maximum.at(table[num_partitions:], partition, ts_s)
    return table


def sz_minmax_table(batch: RecordBatch, n_valid: int,
                    num_partitions: int) -> np.ndarray:
    """Host-side per-partition message-size extremes: ``[2P]`` int64, mins
    then maxes.  Size = key bytes (when the key is non-null) + value
    bytes; tombstones are EXCLUDED entirely (src/metric.rs:249-251).
    Identities are I64_MAX / 0 — matching the reference's ``largest``
    starting at 0 (src/metric.rs:34)."""
    table = np.empty(2 * num_partitions, dtype=np.int64)
    table[:num_partitions] = I64_MAX
    table[num_partitions:] = 0
    sized = ~batch.value_null[:n_valid]
    if sized.any():
        part = batch.partition[:n_valid][sized]
        size = (
            np.where(batch.key_null[:n_valid], 0,
                     batch.key_len[:n_valid]).astype(np.int64)
            + batch.value_len[:n_valid].astype(np.int64)
        )[sized]
        np.minimum.at(table[:num_partitions], part, size)
        np.maximum.at(table[num_partitions:], part, size)
    return table


def pack_batch(
    batch: RecordBatch,
    config: AnalyzerConfig,
    use_native: bool = True,
    out: "np.ndarray | None" = None,
) -> np.ndarray:
    """RecordBatch → one contiguous uint8 buffer (the module docstring is
    the layout's single source of truth).  Alive pairs are not part of the
    row: the dispatch packs them (`batch_alive_pairs`, `pack_pair_table`).

    The batch's valid records must be a prefix (all sources produce
    prefix-valid batches; padding lives at the tail).

    ``out`` packs into a caller-provided ``uint8[packed_nbytes]`` view —
    superbatch staging (SuperbatchStager) hands out rows of its stacked
    host array so the numpy path writes the row directly instead of
    allocating a buffer that would be copied into the stack anyway.
    Every byte of ``out`` is overwritten (header + the full section list
    cover the buffer exactly), so rows need no re-zeroing between uses.
    """
    b = config.batch_size
    n = len(batch)
    if n > b:
        raise ValueError(f"batch of {n} exceeds batch_size {b}")
    n_valid = batch.num_valid
    if n_valid and not bool(batch.valid[:n_valid].all()):
        raise ValueError("packed transfer requires prefix-valid batches")
    if batch.key_len.max(initial=0) > MAX_KEY_LEN:
        raise ValueError(
            f"key length {int(batch.key_len.max())} exceeds the packed "
            f"transfer limit of {MAX_KEY_LEN} bytes"
        )
    if n and (
        batch.partition.max(initial=0) > MAX_PARTITIONS or batch.partition.min() < 0
    ):
        raise ValueError(
            f"partition index out of packed-transfer range [0, {MAX_PARTITIONS}]"
        )
    if n_valid and batch.partition[:n_valid].max() >= config.num_partitions:
        # The tables are [P]-indexed; a stray dense index past P would
        # scatter out of bounds (the reducers would mis-bucket it anyway).
        raise ValueError(
            f"partition index {int(batch.partition[:n_valid].max())} >= "
            f"num_partitions {config.num_partitions}"
        )
    if n and (batch.value_len.min() < 0 or batch.key_len.min() < 0):
        # A negative length would corrupt the byte sums silently.
        raise ValueError("negative key/value length in record batch")

    if use_native:
        # Fused C++ pack (tables + HLL split in one pass), writing straight
        # into ``out`` when given (superbatch rows take the packed bytes
        # with no intermediate buffer).  A None return means the shim
        # rejected the batch; the numpy path below re-derives the
        # descriptive error.
        try:
            from kafka_topic_analyzer_tpu.io.native import (
                native_available,
                pack_batch_native,
            )

            if native_available():
                packed = pack_batch_native(batch, config, out=out)
                if packed is not None:
                    return packed
        except ImportError:
            pass

    if out is None:
        out = np.empty(packed_nbytes(config, b), dtype=np.uint8)
    elif out.shape != (packed_nbytes(config, b),) or out.dtype != np.uint8:
        raise ValueError("pack_batch out= must be uint8[packed_nbytes]")
    header = np.zeros(4, dtype=np.int32)
    header[0] = n_valid

    # The combiner reduction: the per-partition delta tables the device
    # merges — the exact contrib stack of the CPU oracle, pre-added by
    # partition on the host.
    part = batch.partition[:n_valid]
    kn = ~batch.key_null[:n_valid]
    vn = ~batch.value_null[:n_valid]
    k_bytes = np.where(kn, batch.key_len[:n_valid], 0).astype(np.int64)
    v_bytes = np.where(vn, batch.value_len[:n_valid], 0).astype(np.int64)
    counts = np.zeros((config.num_partitions, 7), dtype=np.int64)
    if n_valid:
        contrib = np.stack(
            [
                np.ones(n_valid, dtype=np.int64),
                (~vn).astype(np.int64),  # tombstones
                vn.astype(np.int64),     # alive
                (~kn).astype(np.int64),  # key_null
                kn.astype(np.int64),     # key_non_null
                k_bytes,
                v_bytes,
            ],
            axis=1,
        )
        np.add.at(counts, part, contrib)
    fields: Dict[str, np.ndarray] = {
        "counts": counts.reshape(-1),
        "ts_minmax": ts_minmax_table(
            part, batch.ts_s[:n_valid], config.num_partitions
        ),
        "sz_minmax": sz_minmax_table(batch, n_valid, config.num_partitions),
    }
    if config.enable_quantiles:
        from kafka_topic_analyzer_tpu.ops.ddsketch import (
            ddsketch_bucket_numpy,
            ddsketch_num_buckets,
        )

        nb = ddsketch_num_buckets(config.quantile_buckets)
        q_rows = config.num_partitions if config.quantiles_per_partition else 1
        qtable = np.zeros(q_rows * nb, dtype=np.int64)
        if n_valid and vn.any():
            # Quantiles run over sized (non-tombstone) messages, like the
            # size extremes; buckets come from the shared integer edge
            # table so every packer agrees.
            sizes = (k_bytes + v_bytes)[vn]
            idx = ddsketch_bucket_numpy(
                sizes, config.quantile_gamma, config.quantile_buckets
            )
            if q_rows > 1:
                idx = part[vn].astype(np.int64) * nb + idx
            np.add.at(qtable, idx, 1)
        fields["qcounts"] = qtable
    if config.enable_hll:
        active = batch.valid & ~batch.key_null
        idx, rho = hll_idx_rho_numpy(batch.key_hash64, active, config.hll_p)
        mode = hll_wire_mode(config, b)
        if mode == 2:
            rows = hll_table_rows(config, b)
            table = np.zeros(rows << config.hll_p, dtype=np.uint8)
            if n_valid:
                # rho is 0 for masked/null-key records — a no-op under max.
                flat = idx[:n_valid].astype(np.int64)
                if rows > 1:
                    flat = flat + (part.astype(np.int64) << config.hll_p)
                np.maximum.at(table, flat, rho[:n_valid])
            fields["hll_regs"] = table
        elif mode == 3:
            # Flat pairs: the register row travels inside the index
            # (partition << p | bucket).  Inactive records stay (0, 0) — a
            # no-op under the flat scatter-max.
            idx32 = np.where(
                active,
                (batch.partition.astype(np.int64) << config.hll_p)
                | idx.astype(np.int64),
                0,
            ).astype(np.uint32)
            fields["hll_idx32"] = idx32
            fields["hll_rho"] = rho
        else:
            fields["hll_idx"] = idx
            fields["hll_rho"] = rho

    out[:HEADER_BYTES] = header.view(np.uint8)
    pos = HEADER_BYTES
    for name, dtype, count in _sections(config, b):
        # Write each section directly through a typed view of the output
        # buffer — no staging array, so the bytes move source→buffer once.
        nbytes = np.dtype(dtype).itemsize * count
        src = fields[name]
        sec = out[pos : pos + nbytes].view(dtype)
        sec[: len(src)] = src
        sec[len(src):] = 0  # tail padding past the batch's rows
        pos += nbytes
    return out


# ---------------------------------------------------------------------------
# fused decode→pack sink (DESIGN.md §15)
#
# The seam between the wire/segment readers and the packed device backends:
# a sink accepts raw record-set bytes (fused native decode→pack, no SoA
# intermediate) or already-decoded columns (the python-chain fallback for
# compressed/legacy/salvaged frames), fills packed rows incrementally, and
# hands completed rows — staged for the backend — back to the stream.


#: Decoded bytes per record (the RecordBatch column widths) — PackedRow
#: reports the same per-record nbytes as the decoded batch it replaces so
#: throughput stats stay comparable across the fused and chained paths.
_RECORD_NBYTES = sum(np.dtype(dt).itemsize for _, dt in RecordBatch.FIELDS)


def fused_ingest_enabled() -> bool:
    """Master gate for the fused ingest path: the native shim must load
    and ``KTA_DISABLE_FUSED`` must be unset.  Callers that get False keep
    the python decode→RecordBatch→pack chain — the fused path is an
    optimization with a reachable fallback everywhere (lint rule 6)."""
    import os

    if os.environ.get("KTA_DISABLE_FUSED"):
        return False
    from kafka_topic_analyzer_tpu.io.native import native_available

    return native_available()


class PackedRow:
    """One completed packed row from the fused ingest path, plus the
    bookkeeping the engine would otherwise read off the decoded batch:
    per-partition progress (offsets or counts), the last record's
    identity for the spinner, and the decoded-equivalent byte size for
    throughput stats.  ``staged`` carries the backend-staged form
    (StagedBatch / PackedShard) when the sink was given a stage callback —
    it runs on the producing (worker) thread, exactly like
    ``backend.prepare`` does on the chained path.  ``seq`` is the row's
    batch sequence number (obs/trace.py::next_batch), the ``batch=`` of
    the spans that decoded, staged and dispatched it."""

    __slots__ = (
        "buf", "staged", "n_valid", "next_offsets", "counts",
        "last_partition", "last_offset", "last_ts_s", "pairs", "seq",
    )

    def __init__(self, buf, staged, n_valid, next_offsets, counts,
                 last_partition, last_offset, last_ts_s, pairs=None,
                 seq=None):
        self.buf = buf
        self.staged = staged
        self.seq = seq
        #: Alive pairs of THIS row — (slot u32[n], flag u8[n]) host
        #: arrays in row stream order, None when the scan counts no alive
        #: keys (the staged form carries the same pairs for the backends'
        #: dispatch merge).
        self.pairs = pairs
        self.n_valid = n_valid
        #: true partition id -> one past the last appended offset (sources
        #: that carry offsets); exact-resume bookkeeping.
        self.next_offsets = next_offsets
        #: true partition id -> records appended (offset-less sources).
        self.counts = counts
        self.last_partition = last_partition
        self.last_offset = last_offset
        self.last_ts_s = last_ts_s

    @property
    def num_valid(self) -> int:
        return self.n_valid

    @property
    def nbytes(self) -> int:
        return self.n_valid * _RECORD_NBYTES


class FusedPackSink:
    """Incremental packed-row assembly for one ingest stream.

    Single-device form (``space_shards=1``): rows are flat
    ``uint8[packed_nbytes]`` buffers of ``chunk_records`` records — the
    same greedy ``batch_size`` boundaries the wire layer's pend/resplit
    chain produces, so a fused row is byte-identical to
    ``pack_batch`` over the corresponding chained batch.

    Sharded form (``space_shards=S`` with the backend's chunk config):
    rows are ``uint8[S, chunk_nbytes]`` — records fill chunk 0..S-1
    sequentially at ``chunk_records`` each, the exact ``pack_chunks``
    rule, so a fused row is what ``prepare_shard`` would have staged.

    NOT thread-safe; each ingest stream owns a private sink (parallel
    ingest builds one per worker, the sharded engine one per fed row).
    Appends must preserve per-partition record order — the stream
    contract all byte-identity arguments rest on (DESIGN.md §11).
    """

    def __init__(
        self,
        pack_config: AnalyzerConfig,
        chunk_records: int,
        dense_of,
        stage=None,
        space_shards: int = 1,
        chunk_rows: "bool | None" = None,
    ):
        from kafka_topic_analyzer_tpu.io import native as _native

        self._native = _native
        self.pack_config = pack_config
        self.chunk_records = int(chunk_records)
        self.space_shards = int(space_shards)
        #: Sharded backends consume ``[S, chunk_nbytes]`` rows even at
        #: S=1 (PackedShard's shape contract); single-device rows are
        #: flat.
        self._chunked = (
            self.space_shards > 1 if chunk_rows is None else chunk_rows
        )
        self.capacity = self.chunk_records * self.space_shards
        self._dense_of = dense_of
        self._stage = stage
        self._nbytes = packed_nbytes(pack_config, self.chunk_records)
        self._scratch = np.zeros(
            _native.pack_scratch_len(pack_config, self.chunk_records),
            dtype=np.int64,
        )
        self._row: "np.ndarray | None" = None
        self._chunk = 0
        self._count = 0
        self._next_offsets: "dict[int, int]" = {}
        self._counts: "dict[int, int]" = {}
        self._last = (-1, -1, 0)
        self._done: "list[PackedRow]" = []
        #: Alive-key scans: the native pass emits each chunk's LWW pairs
        #: to the scratch emission region; they are harvested — copied
        #: out — before every scratch re-init and accumulate here until
        #: the row completes.
        self._alive = pack_config.count_alive_keys
        self._row_pairs: "list[tuple[np.ndarray, np.ndarray]]" = []
        #: Batch sequence number of the row being filled, or of the next
        #: row where none is open (obs/trace.py::next_batch).
        self.batch = obs_trace.next_batch()

    # -- row lifecycle -------------------------------------------------------

    def _out_chunk(self) -> np.ndarray:
        return self._row[self._chunk] if self._chunked else self._row

    def _ensure_row(self) -> None:
        if self._row is None:
            self._row = np.empty(
                (self.space_shards, self._nbytes)
                if self._chunked
                else self._nbytes,
                dtype=np.uint8,
            )
            self._chunk = 0
            self._count = 0
            self._next_offsets = {}
            self._counts = {}
            self._native.pack_row_init(
                self._out_chunk(), self._scratch, self.pack_config,
                self.chunk_records,
            )

    def _harvest_pairs(self) -> None:
        """Copy the current chunk's alive pairs out of the scratch
        emission region — MUST run before any ``pack_row_init`` resets the
        scratch (chunk rotation, row completion, flush padding)."""
        if self._alive and int(self._scratch[1]):
            self._row_pairs.append(
                self._native.pack_take_pairs(self._scratch, self.chunk_records)
            )

    def _take_row_pairs(self) -> "tuple[np.ndarray, np.ndarray] | None":
        if not self._alive:
            return None
        pairs = self._row_pairs
        self._row_pairs = []
        if not pairs:
            return (np.empty(0, dtype=np.uint32), np.empty(0, dtype=np.uint8))
        if len(pairs) == 1:
            return pairs[0]
        return (
            np.concatenate([p[0] for p in pairs]),
            np.concatenate([p[1] for p in pairs]),
        )

    def _advance_full_chunks(self) -> None:
        """Eagerly rotate past filled chunks: completing the row when the
        last chunk fills (full rows emit as soon as they exist — the same
        moment the chained flush would yield the corresponding batch)."""
        while self._row is not None and int(self._scratch[0]) == self.chunk_records:
            self._harvest_pairs()
            self._chunk += 1
            if self._chunk >= self.space_shards:
                self._complete_row()
                return  # next append re-allocates lazily
            self._native.pack_row_init(
                self._out_chunk(), self._scratch, self.pack_config,
                self.chunk_records,
            )

    def _complete_row(self) -> None:
        row = self._row
        self._row = None
        from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics

        obs_metrics.FUSED_BATCHES.inc()
        obs_metrics.FUSED_RECORDS.inc(self._count)
        pairs = self._take_row_pairs()
        seq, self.batch = self.batch, obs_trace.next_batch()
        if self._stage is None:
            staged = None
        else:
            with obs_trace.maybe_span("stage", cat="io", batch=seq):
                # The stage callback carries the row's alive pairs into
                # the staged form for the backend's dispatch merge.
                staged = self._stage(row, pairs)
        self._done.append(
            PackedRow(
                row,
                staged,
                self._count,
                self._next_offsets,
                self._counts,
                *self._last,
                pairs=pairs,
                seq=seq,
            )
        )

    def _note(self, partition: int, count: int, last_off: "int | None",
              last_ts: int) -> None:
        if last_off is not None and last_off >= 0:
            self._next_offsets[partition] = last_off + 1
            self._last = (partition, last_off, last_ts)
        else:
            self._counts[partition] = self._counts.get(partition, 0) + count
            self._last = (partition, -1, last_ts)

    # -- appends -------------------------------------------------------------

    def append_record_set(
        self,
        data,
        min_off: int,
        max_off: int,
        partition: int,
        verify_crc: bool = False,
        prescan: "tuple[int, int, int] | None" = None,
    ) -> "tuple[int, int, int, int]":
        """Fused decode→pack of a record set's native-decodable prefix:
        records of ``partition`` with ``min_off <= offset < max_off``
        append straight into the current row (rows rotate as they fill).
        Returns ``(accepted, consumed_bytes, covered_end, last_offset)``
        — the same contract the chained whole-set decode + accept_records
        pair implements.  Raises the packer's ValueError on records the
        packed layout cannot carry; a malformed frame just ends the
        prefix (the caller's per-frame chain classifies it)."""
        buf = np.frombuffer(data, dtype=np.uint8)
        dense = self._dense_of(partition)
        # A prescan only waives CRC verification when it provably covered
        # the ENTIRE buffer (consumed == len): the walk below is not
        # bounded by the prescan, so a partial prescan (possible from a
        # future caller; the wire layer today only stores full-set scans)
        # must not let unverified frames past the checksummed prefix
        # decode — re-verifying the prefix is wasted CPU, never a hole.
        verify = verify_crc and (prescan is None or prescan[1] != len(buf))
        pos = 0
        skip = 0
        total = 0
        covered = -1
        last_off_all = -1
        while True:
            self._ensure_row()
            (appended, pos, cov, last_off, last_ts, full, skip) = (
                self._native.decode_pack_record_set_native(
                    buf, self._out_chunk(), self._scratch,
                    self.pack_config, self.chunk_records, dense,
                    min_off, max_off, verify, start_pos=pos, skip=skip,
                )
            )
            if appended:
                total += appended
                self._count += appended
                last_off_all = last_off
                self._note(partition, appended, last_off, last_ts)
            if cov > covered:
                covered = cov
            self._advance_full_chunks()
            if not full:
                break
        return total, pos, covered, last_off_all

    def append_columns(
        self,
        partition: int,
        key_len,
        value_len,
        key_null,
        value_null,
        ts,
        key_hash32,
        key_hash64,
        n: int,
        ts_mode: int = 0,
        offsets=None,
        reason: "str | None" = None,
    ) -> int:
        """Chain-fallback append: ``n`` already-decoded single-partition
        records enter the row through the same native incremental core,
        so rows mixing fused and fallback records stay byte-identical to
        the chained pack.  ``reason`` books the fallback (never silent)."""
        if n == 0:
            return 0
        if reason is not None:
            from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics

            obs_metrics.FUSED_FALLBACK.labels(reason=reason).inc(n)
        dense = self._dense_of(partition)
        start = 0
        while start < n:
            self._ensure_row()
            took = self._native.pack_append_columns_native(
                self._out_chunk(), self._scratch, self.pack_config,
                self.chunk_records, dense, key_len, value_len, key_null,
                value_null, ts, key_hash32, key_hash64, start, n,
                ts_mode=ts_mode,
            )
            if took:
                self._count += took
                start += took
                last_off = (
                    int(offsets[start - 1]) if offsets is not None else None
                )
                last_ts = int(ts[start - 1])
                if ts_mode == 1:
                    last_ts //= 1000
                elif ts_mode == 2:
                    last_ts = max(last_ts, 0) // 1000
                self._note(partition, took, last_off, last_ts)
            self._advance_full_chunks()
            if not took and int(self._scratch[0]) < self.chunk_records:
                raise RuntimeError("fused append made no progress")
        return n

    def append_batch(self, batch: RecordBatch, reason: str) -> int:
        """RecordBatch form of the fallback append (salvaged frames,
        python-decoded rows).  Single-partition by the stream contract —
        every caller hands per-frame / per-partition chunks."""
        n = len(batch)
        if n == 0:
            return 0
        p = int(batch.partition[0])
        if n > 1 and not bool((batch.partition == p).all()):
            raise ValueError(
                "fused sink chunks must be single-partition"
            )
        return self.append_columns(
            p, batch.key_len, batch.value_len, batch.key_null,
            batch.value_null, batch.ts_s, batch.key_hash32,
            batch.key_hash64, n, ts_mode=0, offsets=batch.offsets,
            reason=reason,
        )

    # -- draining ------------------------------------------------------------

    def pending_records(self) -> int:
        """Records staged in the (incomplete) current row."""
        return self._count if self._row is not None else 0

    def flush(self) -> None:
        """Complete the partial row (stream end).  Chunks never reached
        stay as initialized — an initialized chunk IS a packed empty
        batch, the superbatch identity pad — so a sharded partial row is
        exactly what ``pack_chunks`` does with a short tail batch."""
        if self._row is None:
            return
        if self._count == 0:
            self._row = None  # nothing appended: emit nothing (chain parity)
            self._row_pairs = []
            return
        self._harvest_pairs()  # before the pad inits reset the scratch
        for s in range(self._chunk + 1, self.space_shards):
            self._native.pack_row_init(
                self._row[s], self._scratch, self.pack_config,
                self.chunk_records,
            )
        self._complete_row()

    def take_completed(self) -> "list[PackedRow]":
        done, self._done = self._done, []
        return done


class SuperbatchStager:
    """Reusable host staging for stacked superbatch dispatch.

    A superbatch crosses the host→device boundary as ONE contiguous
    ``uint8[K, N]`` array (one large ``device_put`` instead of K small
    ones).  This stager owns a ring of ``depth + 1`` such arrays so
    assembling superbatch ``i`` never allocates and never overwrites
    memory an in-flight transfer may still be reading: the slot being
    reused was last dispatched as superbatch ``i - depth - 1``, and the
    dispatch queue (backends/base.py::DispatchQueue) guarantees that
    dispatch retired — its device step consumed the transfer — before
    dispatch ``i`` may launch.  Safe under either PJRT host-buffer
    semantics (immediate copy or zero-copy-until-transfer-completes).

    Callers either pack straight into a row (``pack_batch(..., out=row)``
    — no intermediate buffer at all) or ``np.copyto`` a worker-staged
    buffer into it (parallel ingest packs on worker threads before the
    fan-in order — and hence the row index — is known).

    ``row_shape`` is one batch's staged shape: ``(nbytes,)`` for the
    single-device backend, ``(local_rows, S, chunk_nbytes)`` for one
    collective round of the sharded backend — the ring arrays are
    ``uint8[(k,) + row_shape]`` either way.
    """

    def __init__(self, row_shape: "tuple[int, ...]", k: int, depth: int):
        if k < 1 or depth < 1:
            raise ValueError("superbatch k and dispatch depth must be >= 1")
        self.k = k
        self.row_shape = tuple(row_shape)
        self._ring = [
            np.empty((k,) + self.row_shape, dtype=np.uint8)
            for _ in range(depth + 1)
        ]
        self._next = 0

    def next_slot(self) -> np.ndarray:
        """The ``uint8[(K,) + row_shape]`` host array to assemble the next
        superbatch into.  Rotates the ring; see the class docstring for
        why the returned memory is quiescent."""
        from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics

        slot = self._ring[self._next]
        self._next = (self._next + 1) % len(self._ring)
        # Ring-activity booking for the flight recorder: slots in use at
        # any instant = kta_dispatch_inflight + 1 (this one), and the
        # slot hand-out rate is the superbatch assembly rate.
        obs_metrics.STAGER_SLOTS.inc()
        return slot


def pack_chunks(
    batch: RecordBatch,
    chunk_config: AnalyzerConfig,
    space_shards: int,
    use_native: bool = True,
    out: "np.ndarray | None" = None,
) -> np.ndarray:
    """One data row's batch packed into its ``space_shards`` contiguous
    record chunks: ``uint8[S, chunk_nbytes]``, chunk ``s`` holding records
    ``[s*C, (s+1)*C)`` of the row's batch (``C = chunk_config.batch_size``).

    Contiguity is what makes the sharded backend's device-side ordered
    application exact — source-chunk order equals record order
    (backends/step.py) — so this function is the single chunking rule for
    ``ShardedTpuBackend`` staging (prepare_shard, the superbatch ring, the
    per-round path).

    ``out`` packs each chunk straight into the caller's ``[S, nbytes]``
    rows via ``pack_batch(out=)`` — the sharded superbatch stager hands
    its ring-slot rows here, so an unstaged batch goes file/socket →
    packed ring row with no intermediate stack-then-copy."""
    c = chunk_config.batch_size
    n = len(batch)
    if n > c * space_shards:
        raise ValueError(
            f"batch of {n} exceeds batch_size {c * space_shards}"
        )
    nbytes = packed_nbytes(chunk_config, c)
    if out is None:
        out = np.empty((space_shards, nbytes), dtype=np.uint8)
    elif out.shape != (space_shards, nbytes) or out.dtype != np.uint8:
        raise ValueError(
            f"pack_chunks out buffer must be uint8[{space_shards}, "
            f"{nbytes}], got {out.dtype}{list(out.shape)}"
        )
    for s in range(space_shards):
        lo = s * c
        pack_batch(
            batch.take(np.arange(lo, min(lo + c, n))),
            chunk_config,
            use_native=use_native,
            out=out[s],
        )
    return out


def _shape_tables(out: dict, config: AnalyzerConfig) -> dict:
    """Reshape the flat fold tables of an unpacked row (host or device)."""
    from kafka_topic_analyzer_tpu.ops.ddsketch import ddsketch_num_buckets

    p = config.num_partitions
    out["counts"] = out["counts"].reshape(p, 7)
    if "qcounts" in out:
        out["qcounts"] = out["qcounts"].reshape(
            -1, ddsketch_num_buckets(config.quantile_buckets)
        )
    tm = out.pop("ts_minmax")
    out["ts_min"], out["ts_max"] = tm[:p], tm[p:]
    sm = out.pop("sz_minmax")
    out["sz_min"], out["sz_max"] = sm[:p], sm[p:]
    return out


def unpack_numpy(buf: np.ndarray, config: AnalyzerConfig) -> Dict[str, np.ndarray]:
    """Host-side reference unpack (tests + the device self-check oracle)."""
    out: Dict[str, np.ndarray] = {
        "n_valid": buf[:HEADER_BYTES].view(np.int32)[0]
    }
    pos = HEADER_BYTES
    for name, dtype, count in _sections(config, config.batch_size):
        nbytes = np.dtype(dtype).itemsize * count
        out[name] = buf[pos : pos + nbytes].view(dtype)
        pos += nbytes
    return _shape_tables(out, config)


# ---------------------------------------------------------------------------
# unpack (device, inside jit)
#
# "hll_regs" (table mode) flows through the generic section loop in both
# unpackers untouched — it is already u8[2^p] and the step consumes it
# elementwise.


@obs_trace.device_scope("unpack")
def unpack_device(buf, config: AnalyzerConfig):
    """uint8[N] → dict of typed device arrays (runs under jit)."""
    from kafka_topic_analyzer_tpu.jax_support import jnp, lax

    header = lax.bitcast_convert_type(buf[:HEADER_BYTES].reshape(4, 4), jnp.int32)
    out = {"n_valid": header[0]}
    pos = HEADER_BYTES
    for name, dtype, count in _sections(config, config.batch_size):
        itemsize = np.dtype(dtype).itemsize
        sec = buf[pos : pos + itemsize * count]
        out[name] = (
            sec
            if itemsize == 1
            else lax.bitcast_convert_type(
                sec.reshape(-1, itemsize), jnp.dtype(dtype)
            )
        )
        pos += itemsize * count
    return _shape_tables(out, config)
