"""The pure analyzer step: fold one record batch into the analyzer state.

This is the device-side computation shared by the single-device TPU backend
(jitted directly) and the sharded backend (wrapped in ``shard_map`` —
parallel/sharded.py).  It is a pure function of (state, batch arrays) with
the config captured statically, so each feature combination compiles once.

It replaces the reference's hot loop body (src/kafka.rs:98-133 fanning out to
``handle_message`` per message) with a handful of fused batched reductions.

Each phase runs under a named scope (obs/trace.py::device_scope) —
``kta.counters`` (counters and extremes), ``kta.alive`` (the alive
bitmap), ``kta.hll``, ``kta.ddsketch``; the unpack is ``kta.unpack``
(packing.py) — so the device ops in a profiler trace name the phase they
belong to.  The scopes are metadata: they change no compiled instruction.
"""

from __future__ import annotations

import functools
from typing import Dict

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.models.compaction import AliveBitmapState, HLLState
from kafka_topic_analyzer_tpu.models.message_metrics import MessageMetricsState
from kafka_topic_analyzer_tpu.models.quantiles import DDSketchState
from kafka_topic_analyzer_tpu.models.state import AnalyzerState
from kafka_topic_analyzer_tpu.jax_support import jnp
from kafka_topic_analyzer_tpu.obs import trace as obs_trace
from kafka_topic_analyzer_tpu.ops.bitmap import bitmap_apply_pairs
from kafka_topic_analyzer_tpu.ops.counters import counters_update, extremes_update
from kafka_topic_analyzer_tpu.ops.ddsketch import ddsketch_update
from kafka_topic_analyzer_tpu.ops.hll import hll_apply


@obs_trace.device_scope("alive")
def apply_pair_table(
    state: AnalyzerState,
    pairs,
    config: AnalyzerConfig,
    space_index=0,
) -> AnalyzerState:
    """Apply one dispatch's compacted alive table (DESIGN.md §19).

    ``pairs`` is the `packing.unpack_pair_table_device` dict: the host
    already LWW-merged every batch of the dispatch in stream order, so
    ONE apply — after the dispatch's scan — replays exactly what the
    per-batch scatters inside the scan body would have produced (LWW
    compaction is LWW-associative), paying the bitmap update once per
    dispatch instead of once per batch.  The table's form decides the
    kernel (one rule, packing.alive_table_mode, read here via the section
    names): set/clear word MASKS merge elementwise like any other v5
    table — no scatter at all — while the bounded pair list keeps the
    scatter apply for slot spaces too large to mask.  Under a
    space-sharded mesh each shard masks/slices to its slot range
    (``space_index``); the table is replicated over the space axis by its
    input spec, so no per-step collective remains on the compacted path."""
    if state.alive is None:
        return state
    if "alive_set" in pairs:
        from kafka_topic_analyzer_tpu.ops.bitmap import bitmap_apply_masks

        words = bitmap_apply_masks(
            state.alive.words,
            pairs["alive_set"],
            pairs["alive_clear"],
            bits=config.alive_bitmap_bits,
            space_index=space_index,
            space_shards=config.space_shards,
        )
    else:
        words = bitmap_apply_pairs(
            state.alive.words,
            pairs["alive_slot"],
            pairs["alive_flag"],
            pairs["n_pairs"],
            bits=config.alive_bitmap_bits,
            space_index=space_index,
            space_shards=config.space_shards,
        )
    return AnalyzerState(
        metrics=state.metrics,
        alive=AliveBitmapState(words=words),
        hll=state.hll,
        quantiles=state.quantiles,
    )


def superbatch_fold(
    state: AnalyzerState,
    bufs,
    unpack,
    config: AnalyzerConfig,
    space_index=0,
    space_axis: "str | None" = None,
    pairs=None,
):
    """Fold a stacked superbatch — K packed buffers on a leading axis —
    into the state with a single ``lax.scan`` over that axis.

    This is the dispatch-amortization half of the superbatch layer: ONE
    jitted dispatch (state donated once) folds K batches, where the
    per-batch path paid K dispatches and K donation round-trips.  The
    scan body is exactly ``analyzer_step`` on ``unpack(bufs[k])``, applied
    k = 0..K-1 in order — the same order the sequential path dispatches —
    so every fold (including the order-sensitive last-writer-wins alive
    bitmap) produces byte-identical state.  ``unpack`` is injected (a
    closure over ``packing.unpack_device`` and the per-chunk config) so
    this module stays free of the wire-layout dependency; under a mesh it
    may use ``space_axis`` collectives — collectives inside a scan body
    run once per step, in step order, preserving the lockstep contract.

    Returns ``(state, n_valid)`` where ``n_valid`` is the int32[K] vector
    of per-batch valid counts: a small non-donated output the backends
    use as a completion token for the bounded in-flight dispatch queue
    (it cannot alias a donated state leaf, so blocking on it is safe
    after later dispatches have consumed the state).

    ``pairs`` (the compacted alive path) is the dispatch's merged pair
    table, applied ONCE after the scan — see `apply_pair_table`; order is
    preserved because the host merge already resolved per-slot last
    writers across the K batches, and every other fold is
    order-insensitive.
    """
    from kafka_topic_analyzer_tpu.jax_support import lax

    def body(st, buf):
        arrays = unpack(buf)
        return (
            analyzer_step(st, arrays, config, space_index, space_axis),
            arrays["n_valid"],
        )

    state, n_valid = lax.scan(body, state, bufs)
    if pairs is not None:
        state = apply_pair_table(state, pairs, config, space_index)
    return state, n_valid


@obs_trace.device_scope("alive")
def _apply_alive(
    alive_state,
    arrays: Dict[str, "jnp.ndarray"],
    config: AnalyzerConfig,
    space_index,
    space_axis: "str | None",
):
    """Alive-bitmap pair application shared by both wire formats (the
    pairs are already host-pre-reduced in v4 AND v5, so the step is
    identical).  Returns the new AliveBitmapState."""
    if space_axis is not None and config.space_shards > 1:
        from kafka_topic_analyzer_tpu.jax_support import lax

        # Route over ICI: gather every space shard's pair chunk, then
        # apply them in source order (chunk s holds records
        # [s*C, (s+1)*C) of the data row's batch, and all_gather
        # stacks by axis index, so gathered order == record order).
        #
        # Documented trade-off (ADVICE r2): the unrolled loop applies
        # all S chunks on EVERY space shard, so per-step bitmap work
        # (and trace size) is replicated S-fold instead of scaling
        # down with the space axis.  Acceptable at the small S this
        # targets (2-4 on one slice); if large space meshes become a
        # target, switch to a fori_loop over a stacked pair array or
        # pre-route pairs by slot range so each shard applies only
        # its own slots.
        slots = lax.all_gather(arrays["alive_slot"], space_axis)
        flags = lax.all_gather(arrays["alive_flag"], space_axis)
        counts = lax.all_gather(arrays["n_pairs"], space_axis)
        words = alive_state.words
        for s in range(config.space_shards):
            words = bitmap_apply_pairs(
                words,
                slots[s],
                flags[s],
                counts[s],
                bits=config.alive_bitmap_bits,
                space_index=space_index,
                space_shards=config.space_shards,
            )
    else:
        words = bitmap_apply_pairs(
            alive_state.words,
            arrays["alive_slot"],
            arrays["alive_flag"],
            arrays["n_pairs"],
            bits=config.alive_bitmap_bits,
            space_index=space_index,
            space_shards=config.space_shards,
        )
    return AliveBitmapState(words=words)


def _analyzer_step_v5(
    state: AnalyzerState,
    arrays: Dict[str, "jnp.ndarray"],
    config: AnalyzerConfig,
    space_index=0,
    space_axis: "str | None" = None,
) -> AnalyzerState:
    """Wire-v5 fold: the batch arrives as per-partition partial-fold
    TABLES (packing.py module docstring), so every reduction here is an
    elementwise table merge — integer adds for counters and DDSketch
    buckets, min/max for extremes, max for HLL registers — O(P·H) work
    per dispatch where the v4 step scattered O(B) records.  Associativity
    and commutativity of those integer merges (DESIGN.md §2/§16) is what
    makes the result byte-identical to the v4 fold; the superbatch scan
    and sharded chunk paths carry over untouched for the same reason."""
    m = state.metrics
    with obs_trace.device_scope("counters"):
        delta = arrays["counts"]  # int64[P, 7], COUNTER_CHANNELS order
        if config.use_pallas_counters:
            from kafka_topic_analyzer_tpu.ops.pallas_counters import (
                pallas_counters_merge,
            )

            per_partition = pallas_counters_merge(
                m.per_partition, delta, interpret=config.pallas_interpret
            )
        else:
            per_partition = m.per_partition + delta
        earliest, latest, smallest, largest = extremes_update(
            m.earliest_s,
            m.latest_s,
            m.smallest,
            m.largest,
            arrays["ts_min"],
            arrays["ts_max"],
            arrays["sz_min"],
            arrays["sz_max"],
        )
        metrics = MessageMetricsState(
            per_partition=per_partition,
            earliest_s=earliest,
            latest_s=latest,
            smallest=smallest,
            largest=largest,
            # Global sums are the column sums of the delta table: channels
            # 5/6 are the key/value byte sums, channel 0 the record count.
            overall_size=m.overall_size + jnp.sum(delta[:, 5] + delta[:, 6]),
            overall_count=m.overall_count + jnp.sum(delta[:, 0]),
        )

    alive_state = state.alive
    if alive_state is not None and "alive_slot" in arrays:
        # Compacted configs ship no per-row pair sections: the dispatch's
        # merged pair table applies ONCE after the scan (apply_pair_table).
        alive_state = _apply_alive(
            alive_state, arrays, config, space_index, space_axis
        )

    hll_state = state.hll
    if hll_state is not None:
        with obs_trace.device_scope("hll"):
            if "hll_regs" in arrays:
                regs = jnp.maximum(
                    hll_state.regs,
                    arrays["hll_regs"].astype(jnp.int32).reshape(
                        -1, hll_state.regs.shape[1]
                    ),
                )
            elif "hll_idx32" in arrays:
                # v5 flat pairs: the index already encodes
                # (row << p | bucket), so the scatter-max lands on the
                # flattened register file.
                from kafka_topic_analyzer_tpu.ops.hll import hll_apply_flat

                regs = hll_apply_flat(
                    hll_state.regs, arrays["hll_idx32"], arrays["hll_rho"]
                )
            else:
                regs = hll_apply(
                    hll_state.regs, arrays["hll_idx"], arrays["hll_rho"],
                    partition=None,
                )
            hll_state = HLLState(regs=regs)

    q_state = state.quantiles
    if q_state is not None:
        with obs_trace.device_scope("ddsketch"):
            q_state = DDSketchState(counts=q_state.counts + arrays["qcounts"])

    return AnalyzerState(
        metrics=metrics, alive=alive_state, hll=hll_state, quantiles=q_state
    )


def analyzer_step(
    state: AnalyzerState,
    arrays: Dict[str, "jnp.ndarray"],
    config: AnalyzerConfig,
    space_index=0,
    space_axis: "str | None" = None,
) -> AnalyzerState:
    """Fold one batch (or, under a space-sharded mesh, one contiguous CHUNK
    of a data row's batch) into the analyzer state.

    ``space_axis`` names the mesh axis the record stream is chunked over
    (parallel/sharded.py).  When given, bitmap updates are redistributed
    on-device: every space shard all_gathers the (slot, aliveness) pair
    chunks over ICI and applies them in source-chunk order, which preserves
    exact last-writer-wins semantics even when one key's updates straddle
    chunk boundaries (host dedupe is per chunk, so cross-chunk duplicates
    are resolved here by application order).  All other reductions stay
    chunk-local; the space axis is reduced once at finalize.

    Wire-v5 buffers (the per-partition combiner tables — ``counts``
    present in ``arrays``) take the table-merge fold instead; the
    per-record path below is the v4 layout's."""
    if "counts" in arrays:
        return _analyzer_step_v5(
            state, arrays, config, space_index, space_axis
        )
    valid = arrays["valid"]
    key_null = arrays["key_null"]
    value_null = arrays["value_null"]
    key_len = arrays["key_len"]
    value_len = arrays["value_len"]

    m = state.metrics
    with obs_trace.device_scope("counters"):
        if config.use_pallas_counters:
            from kafka_topic_analyzer_tpu.ops.pallas_counters import (
                pallas_counters_update,
            )

            counters_fn = functools.partial(
                pallas_counters_update, interpret=config.pallas_interpret
            )
        else:
            counters_fn = counters_update
        per_partition = counters_fn(
            m.per_partition,
            arrays["partition"],
            key_len,
            value_len,
            key_null,
            value_null,
            valid,
            config.num_partitions,
        )
        earliest, latest, smallest, largest = extremes_update(
            m.earliest_s,
            m.latest_s,
            m.smallest,
            m.largest,
            arrays["ts_min"],
            arrays["ts_max"],
            arrays["sz_min"],
            arrays["sz_max"],
        )
        kn = valid & ~key_null
        vn = valid & ~value_null
        k_bytes = jnp.where(kn, key_len, 0).astype(jnp.int64)
        v_bytes = jnp.where(vn, value_len, 0).astype(jnp.int64)
        metrics = MessageMetricsState(
            per_partition=per_partition,
            earliest_s=earliest,
            latest_s=latest,
            smallest=smallest,
            largest=largest,
            overall_size=m.overall_size + jnp.sum(k_bytes + v_bytes),
            overall_count=m.overall_count + jnp.sum(valid.astype(jnp.int64)),
        )

    alive_state = state.alive
    if alive_state is not None and "alive_slot" in arrays:
        # Compacted configs ship no per-row pair sections: the dispatch's
        # merged pair table applies ONCE after the scan (apply_pair_table).
        alive_state = _apply_alive(
            alive_state, arrays, config, space_index, space_axis
        )

    hll_state = state.hll
    if hll_state is not None:
        with obs_trace.device_scope("hll"):
            if "hll_regs" in arrays:
                # Table mode (wire v3): the host already reduced the batch
                # to a register table (R rows — 1 global or P
                # per-partition) — merge elementwise, no scatter on the
                # device hot path.
                regs = jnp.maximum(
                    hll_state.regs,
                    arrays["hll_regs"].astype(jnp.int32).reshape(
                        -1, hll_state.regs.shape[1]
                    ),
                )
            else:
                regs = hll_apply(
                    hll_state.regs,
                    arrays["hll_idx"],
                    arrays["hll_rho"],
                    partition=(
                        arrays["partition"]
                        if config.distinct_keys_per_partition
                        else None
                    ),
                )
            hll_state = HLLState(regs=regs)

    q_state = state.quantiles
    if q_state is not None:
        with obs_trace.device_scope("ddsketch"):
            msg_size = k_bytes + v_bytes
            counts = ddsketch_update(
                q_state.counts,
                msg_size,
                # quantiles over sized (non-tombstone) messages, like
                # min/max
                vn,
                config.quantile_gamma,
                config.quantile_buckets,
                partition=(
                    arrays["partition"]
                    if config.quantiles_per_partition
                    else None
                ),
            )
            q_state = DDSketchState(counts=counts)

    return AnalyzerState(
        metrics=metrics, alive=alive_state, hll=hll_state, quantiles=q_state
    )
