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

from typing import Dict

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.models.compaction import AliveBitmapState, HLLState
from kafka_topic_analyzer_tpu.models.message_metrics import MessageMetricsState
from kafka_topic_analyzer_tpu.models.quantiles import DDSketchState
from kafka_topic_analyzer_tpu.models.state import AnalyzerState
from kafka_topic_analyzer_tpu.jax_support import jnp
from kafka_topic_analyzer_tpu.obs import trace as obs_trace
from kafka_topic_analyzer_tpu.ops.bitmap import (
    bitmap_apply_masks,
    bitmap_apply_pairs,
)
from kafka_topic_analyzer_tpu.ops.counters import extremes_update
from kafka_topic_analyzer_tpu.ops.hll import hll_apply, hll_apply_flat


@obs_trace.device_scope("alive")
def apply_pair_table(
    state: AnalyzerState,
    pairs,
    config: AnalyzerConfig,
    space_index=0,
) -> AnalyzerState:
    """Apply one dispatch's alive table (DESIGN.md §19).

    ``pairs`` is the `packing.unpack_pair_table_device` dict: the host
    already LWW-merged every batch of the dispatch in stream order, so
    ONE apply — after the dispatch's scan — equals the per-record replay
    (LWW compaction is LWW-associative), paying the bitmap update once per
    dispatch instead of once per batch.  The table's form decides the
    kernel (one rule, packing.alive_table_mode, read here via the section
    names): set/clear word MASKS merge elementwise like any other fold
    table — no scatter at all — while the bounded pair list is applied
    in place, a gather and a scatter-add on the words its pairs touch,
    for slot spaces too large to mask.  Under a
    space-sharded mesh each shard masks/slices to its slot range
    (``space_index``); the table is replicated over the space axis by its
    input spec, so no per-step collective exists."""
    if state.alive is None:
        return state
    if "alive_set" in pairs:
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
    pairs=None,
):
    """Fold a stacked superbatch — K packed buffers on a leading axis —
    into the state with a single ``lax.scan`` over that axis.

    This is the dispatch-amortization half of the superbatch layer: ONE
    jitted dispatch (state donated once) folds K batches, where the
    per-batch path paid K dispatches and K donation round-trips.  The
    scan body is exactly ``analyzer_step`` on ``unpack(bufs[k])``, applied
    k = 0..K-1 in order — the same order the sequential path dispatches.
    ``unpack`` is injected (a closure over ``packing.unpack_device`` and
    the per-chunk config) so this module stays free of the wire-layout
    dependency.

    Returns ``(state, n_valid)`` where ``n_valid`` is the int32[K] vector
    of per-batch valid counts: a small non-donated output the backends
    use as a completion token for the bounded in-flight dispatch queue
    (it cannot alias a donated state leaf, so blocking on it is safe
    after later dispatches have consumed the state).

    ``pairs`` (alive-key scans) is the dispatch's merged alive table,
    applied ONCE after the scan — see `apply_pair_table`; order is
    preserved because the host merge already resolved per-slot last
    writers across the K batches, and every other fold is
    order-insensitive.
    """
    from kafka_topic_analyzer_tpu.jax_support import lax

    def body(st, buf):
        arrays = unpack(buf)
        return analyzer_step(st, arrays, config), arrays["n_valid"]

    state, n_valid = lax.scan(body, state, bufs)
    if pairs is not None:
        state = apply_pair_table(state, pairs, config, space_index)
    return state, n_valid


def analyzer_step(
    state: AnalyzerState,
    arrays: Dict[str, "jnp.ndarray"],
    config: AnalyzerConfig,
) -> AnalyzerState:
    """Fold one packed batch (or, under a space-sharded mesh, one
    contiguous CHUNK of a data row's batch) into the analyzer state.

    The batch arrives as per-partition partial-fold TABLES (packing.py
    module docstring), so every reduction here is an elementwise table
    merge — integer adds for counters and DDSketch buckets, min/max for
    extremes, max for HLL registers — O(P·H) work per dispatch.  The
    alive bitmap is not touched here: the dispatch's alive table applies
    once after the scan (`apply_pair_table`).  Every reduction is
    chunk-local; the mesh's axes reduce once at finalize."""
    m = state.metrics
    with obs_trace.device_scope("counters"):
        delta = arrays["counts"]  # int64[P, 7], COUNTER_CHANNELS order
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
            per_partition=m.per_partition + delta,
            earliest_s=earliest,
            latest_s=latest,
            smallest=smallest,
            largest=largest,
            # Global sums are the column sums of the delta table: channels
            # 5/6 are the key/value byte sums, channel 0 the record count.
            overall_size=m.overall_size + jnp.sum(delta[:, 5] + delta[:, 6]),
            overall_count=m.overall_count + jnp.sum(delta[:, 0]),
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
                # Flat pairs: the index already encodes (row << p |
                # bucket), so the scatter-max lands on the flattened
                # register file.
                regs = hll_apply_flat(
                    hll_state.regs, arrays["hll_idx32"], arrays["hll_rho"]
                )
            else:
                regs = hll_apply(
                    hll_state.regs, arrays["hll_idx"], arrays["hll_rho"]
                )
            hll_state = HLLState(regs=regs)

    q_state = state.quantiles
    if q_state is not None:
        with obs_trace.device_scope("ddsketch"):
            q_state = DDSketchState(counts=q_state.counts + arrays["qcounts"])

    return AnalyzerState(
        metrics=metrics, alive=state.alive, hll=hll_state, quantiles=q_state
    )
