"""Packed-bit alive-key bitmap with last-writer-wins table applies.

TPU-native replacement for the reference's alive-key ``BitSet`` indexed by
fnv32 hashes (src/metric.rs:256-305): same observable semantics — a key is
alive iff its *latest* record (per key, in offset order) has a non-null
value; collisions conflate keys exactly as the reference's 32-bit hash does —
but updated a dispatch at a time on device.  The host resolves
last-writer-wins per slot (packing.py's alive table), so the device only
applies the result: ``words = (words & ~clear) | set``, with the set/clear
masks shipped as words or built from deduped (slot, aliveness) pairs.

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
    form (packing.alive_table_mode == 1).

    The host already performed last-writer-wins per slot
    (packing.py::dedupe_slots_*), so each live slot appears exactly once —
    distinct slots in a word own distinct bits, making scatter-add equal to
    bitwise OR, and no device-side sort is needed.
    """
    B = slot.shape[0]
    W = bitmap_num_words(bits, space_shards)
    live = jnp.arange(B, dtype=jnp.int32) < n_pairs
    s = slot.astype(jnp.int64)
    shard_base = jnp.int64(W * 32) * space_index
    in_shard = live & (s >= shard_base) & (s < shard_base + W * 32)
    local = s - shard_base
    word = jnp.where(in_shard, local >> 5, W).astype(jnp.int32)
    bit = jnp.uint32(1) << (local & 31).astype(jnp.uint32)
    alive = alive_flag.astype(bool)
    set_mask = jnp.where(in_shard & alive, bit, jnp.uint32(0))
    clear_mask = jnp.where(in_shard & ~alive, bit, jnp.uint32(0))
    scratch = jnp.zeros((W + 1,), dtype=jnp.uint32)
    set_words = scratch.at[word].add(set_mask)[:W]
    clear_words = scratch.at[word].add(clear_mask)[:W]
    return (words & ~clear_words) | set_words


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
