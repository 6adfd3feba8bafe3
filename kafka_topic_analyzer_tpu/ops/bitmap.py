"""Packed-bit alive-key bitmap with last-writer-wins table applies.

TPU-native replacement for the reference's alive-key ``BitSet`` indexed by
fnv32 hashes (src/metric.rs:256-305): same observable semantics — a key is
alive iff its *latest* record (per key, in offset order) has a non-null
value; collisions conflate keys exactly as the reference's 32-bit hash does —
but updated a dispatch at a time on device.  The host resolves
last-writer-wins per slot (packing.py's alive table), so the device only
applies the result: ``words = (words & ~clear) | set``, with the set/clear
masks shipped as words, or deduped (slot, aliveness) pairs applied to the
words they touch.

Correctness across dispatches relies on batches arriving in per-partition
offset order, and across devices on every partition being pinned to one
data shard (a Kafka key lives in exactly one partition, so shard-local
last-writer-wins composes into an exact OR-merge; records.py ordering
contract).

The slot space can additionally be sharded over the mesh's 'space' axis: each
space shard masks updates to its slot range, so no collective is needed per
batch and the final merge over the data axis is an elementwise OR (pmax).
"""

from __future__ import annotations

from kafka_topic_analyzer_tpu.jax_support import jnp


#: Odd multiplier (2^32 / golden ratio) that spreads the pair apply's
#: no-op entries over the bitmap (`bitmap_apply_pairs`).
_SPREAD = 0x9E3779B1


def bitmap_num_words(bits: int, space_shards: int = 1) -> int:
    total_words = 1 << max(bits - 5, 0)
    if total_words % space_shards:
        raise ValueError(f"2^{bits} slots not divisible into {space_shards} space shards")
    return total_words // space_shards


def bitmap_apply_pairs(
    words,        # uint32[W] — this shard's packed bits
    slot,         # uint32[B] — deduped slots (prefix of n_pairs is live)
    alive_flag,   # uint8[B]  — 1 = last record for the slot had a value
    n_pairs,      # scalar i32 — live prefix length
    bits: int,
    space_index=0,
    space_shards: int = 1,
):
    """Apply host-deduped (slot, aliveness) pairs: the alive table's PAIR
    form (packing.alive_table_mode == 1), in place at O(pairs).

    The host already performed last-writer-wins per slot
    (packing.py::dedupe_slots_*), so each live slot appears exactly once
    and distinct slots in a word own distinct bits.  Each pair gathers its
    word, turns its bit to what the pair says, and scatter-adds the
    difference (``+bit``, ``-bit`` mod 2^32, or 0) back into ``words``:
    every delta touches its own bit, so no carry or borrow crosses bits
    and the sum equals ``(words & ~clear) | set`` bit for bit.  Nothing of
    the bitmap's size is built besides the (donated) result.

    Entries past ``n_pairs`` and slots outside this space shard add 0 at
    a position hashed from their own: in bounds, and spread over the
    bitmap, since updates that crowd one stretch of words serialize.
    """
    from kafka_topic_analyzer_tpu.jax_support import lax

    B = slot.shape[0]
    W = bitmap_num_words(bits, space_shards)
    pos = lax.iota(jnp.int64, B)
    local = slot.astype(jnp.int64) - jnp.int64(W * 32) * space_index
    in_shard = (pos < n_pairs) & (local >= 0) & (local < W * 32)
    # W is a power of two and an odd multiplier permutes the low bits; in
    # 32 bits, as a 64-bit remainder added ~65 ms to each build of the step
    # on a v5e.
    spread = (lax.iota(jnp.uint32, B) * jnp.uint32(_SPREAD)) & jnp.uint32(W - 1)
    word = lax.select(
        in_shard, (local >> 5).astype(jnp.int32), spread.astype(jnp.int32)
    )[:, None]
    bit = lax.shift_left(jnp.uint32(1), (local & 31).astype(jnp.uint32))
    # lax's gather and scatter, not jnp's ``.at[]``, which traces and lowers
    # slower: the step is built anew for every scan.
    dims = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(0,), start_index_map=(0,))
    old = lax.gather(words, word, dims, (1,),
                     mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    new = lax.select(alive_flag != 0, old | bit, old & ~bit)
    delta = lax.select(in_shard, new - old, lax.full_like(old, 0))
    return lax.scatter_add(
        words, word, delta,
        lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0,)),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def bitmap_apply_masks(
    words,        # uint32[W] — this shard's packed bits
    set_words,    # uint32[W_total] — full-bitmap LWW set mask
    clear_words,  # uint32[W_total] — full-bitmap LWW clear mask
    bits: int,
    space_index=0,
    space_shards: int = 1,
):
    """Apply host-built LWW set/clear word masks: the compacted alive
    table's MASK form (packing.alive_table_mode == 2).

    The host already resolved last-writer-wins per slot straight into
    bitmask form (a later set clears the slot's clear bit and vice
    versa), so the whole apply is ONE elementwise pass —
    ``(words & ~clear) | set`` — with no scatter and no per-batch scratch
    allocation.  Under a space-sharded mesh each shard dynamic-slices its
    word range out of the replicated full-bitmap masks (slot-range
    ownership, same rule as the pair forms)."""
    from kafka_topic_analyzer_tpu.jax_support import lax

    W = bitmap_num_words(bits, space_shards)
    if space_shards > 1:
        base = (jnp.int32(W) * space_index).astype(jnp.int32)
        set_words = lax.dynamic_slice(set_words, (base,), (W,))
        clear_words = lax.dynamic_slice(clear_words, (base,), (W,))
    return (words & ~clear_words) | set_words


def bitmap_popcount(words):
    """Number of alive slots — ``BitSet::len()`` (src/metric.rs:282-284)."""
    from kafka_topic_analyzer_tpu.jax_support import lax

    return jnp.sum(lax.population_count(words).astype(jnp.int64))
