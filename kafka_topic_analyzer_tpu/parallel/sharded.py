"""Sharded TPU backend: shard_map update steps + collective finalize.

The record stream is sharded over BOTH mesh axes: partitions are assigned
to data rows (parallel/mesh.py::assign_partitions), and each data row's
batch is split into `space_shards` contiguous chunks — one per space
shard, `batch_size / space_shards` records each.  Host→device transfer
and per-device reduction work therefore scale down with the full device
count, not just the data axis (with the batch replicated over 'space',
the old layout, every added space shard re-transferred and re-reduced
the whole batch).

Per-step communication is a single small ICI collective: the alive
bitmap's host-deduped (slot, aliveness) pairs are all_gathered over
'space' and applied in source-chunk order, because a slot's updates may
straddle chunk boundaries and last-writer-wins is order-sensitive
(backends/step.py).  Under alive-pair COMPACTION (the wire-v5 default —
``AnalyzerConfig.compact_alive``, DESIGN §19) even that disappears: the
host LWW-merges each data row's pairs per DISPATCH into one bounded
table whose ``P(data, None)`` spec replicates it over 'space' at
transfer time, and each space shard applies its slot range once after
the (scanned) step — no per-step collective remains.  Everything else
is chunk-local per step; the remaining axes reduce once, in finalize:

- counters / byte sums / counts : ``psum``   over ('data', 'space')
- timestamp & size extremes     : ``pmin`` / ``pmax`` over ('data', 'space')
- HLL registers                 : ``pmax``  over ('data', 'space')
- DDSketch bucket counts        : ``psum``  over ('data', 'space')
- alive bitmap                  : ``all_gather`` over 'data' + OR-reduce
                                  (bit-OR has no wired-in collective; the
                                  gather is one-shot), popcount, then
                                  ``psum`` over 'space'

State layout: metrics / HLL / DDSketch leaves gain a leading device axis
of size D·S sharded over ('data', 'space') jointly; the bitmap keeps a
leading 'data' axis with its word axis sharded over 'space' (slot-range
ownership).  The update step is jitted with the state donated, exactly
like the single-device path.
"""

from __future__ import annotations

import dataclasses as _dc
import functools
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from kafka_topic_analyzer_tpu.backends.base import (
    DispatchQueue,
    MetricBackend,
    instrument_steps,
)
from kafka_topic_analyzer_tpu.backends.finalize import metrics_from_state
from kafka_topic_analyzer_tpu.backends.step import (
    analyzer_step,
    apply_pair_table,
    superbatch_fold,
)
from kafka_topic_analyzer_tpu.config import AnalyzerConfig, DispatchConfig
from kafka_topic_analyzer_tpu.packing import (
    batch_alive_pairs,
    pack_chunks,
    pack_pair_table,
    pair_table_capacity,
    unpack_device,
    unpack_pair_table_device,
)
from kafka_topic_analyzer_tpu.jax_support import (
    jnp,
    lax,
    require_device_platform,
)
from kafka_topic_analyzer_tpu.models.compaction import AliveBitmapState
from kafka_topic_analyzer_tpu.models.message_metrics import MessageMetricsState
from kafka_topic_analyzer_tpu.models.state import AnalyzerState
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.ops.bitmap import bitmap_num_words
from kafka_topic_analyzer_tpu.parallel.mesh import DATA_AXIS, SPACE_AXIS, make_mesh
from kafka_topic_analyzer_tpu.records import RecordBatch
from kafka_topic_analyzer_tpu.results import TopicMetrics
from kafka_topic_analyzer_tpu.utils.timefmt import utc_now_seconds


#: Leading device axis of the record-parallel state leaves: sharded over
#: data AND space jointly (D·S rows), since each (data, space) device folds
#: its own record chunk.
_DEV = (DATA_AXIS, SPACE_AXIS)


def _state_specs(config: AnalyzerConfig) -> AnalyzerState:
    """PartitionSpec pytree matching the stacked AnalyzerState."""
    metrics = MessageMetricsState(
        per_partition=P(_DEV),
        earliest_s=P(_DEV),
        latest_s=P(_DEV),
        smallest=P(_DEV),
        largest=P(_DEV),
        overall_size=P(_DEV),
        overall_count=P(_DEV),
    )
    alive = (
        AliveBitmapState(words=P(DATA_AXIS, SPACE_AXIS))
        if config.count_alive_keys
        else None
    )
    from kafka_topic_analyzer_tpu.models.compaction import HLLState
    from kafka_topic_analyzer_tpu.models.quantiles import DDSketchState

    hll = HLLState(regs=P(_DEV)) if config.enable_hll else None
    quantiles = DDSketchState(counts=P(_DEV)) if config.enable_quantiles else None
    return AnalyzerState(metrics=metrics, alive=alive, hll=hll, quantiles=quantiles)


def _stack(x, axes):
    """Every device's ``x`` on a new leading axis, replicated over ``axes``.

    Each device writes ``x`` into its own row of zeros and a psum
    assembles the rows.  Two things need that over pmin/pmax/all_gather:
    the TPU lowers only SUM all-reduces of 64-bit integers, so 64-bit
    min/max merges reduce this table; and JAX infers a psum's result as
    replicated, which an all_gather's is not.  Callers stack small
    (per-partition or per-process) values, so the bytes stay small."""
    table = jnp.zeros((lax.axis_size(axes),) + x.shape, x.dtype)
    table = lax.dynamic_update_index_in_dim(
        table, x, lax.axis_index(axes), 0
    )
    return lax.psum(table, axes)


def _global_put(x: np.ndarray, mesh, spec) -> jax.Array:
    """Place a host-replicated numpy value as a global sharded array.

    `jax.device_put` only accepts shardings whose devices are all
    addressable; under multi-controller (`jax.distributed`) each process
    holds the same host value, so materializing per-shard via callback
    builds the same global array on every process."""
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


def _stacked_init(config: AnalyzerConfig, mesh) -> AnalyzerState:
    """Host-built stacked state (leading device axis), placed with shardings."""
    d = config.data_shards
    dev = d * config.space_shards  # record-parallel leaves: one row per device
    p = config.num_partitions
    i64max = np.iinfo(np.int64).max
    i64min = np.iinfo(np.int64).min
    metrics = MessageMetricsState(
        per_partition=np.zeros((dev, p, 7), np.int64),
        earliest_s=np.full((dev, p), i64max, np.int64),
        latest_s=np.full((dev, p), i64min, np.int64),
        smallest=np.full((dev, p), i64max, np.int64),
        largest=np.zeros((dev, p), np.int64),
        overall_size=np.zeros((dev,), np.int64),
        overall_count=np.zeros((dev,), np.int64),
    )
    alive = None
    if config.count_alive_keys:
        w_local = bitmap_num_words(config.alive_bitmap_bits, config.space_shards)
        alive = AliveBitmapState(
            words=np.zeros((d, w_local * config.space_shards), np.uint32)
        )
    hll = None
    if config.enable_hll:
        from kafka_topic_analyzer_tpu.models.compaction import HLLState

        rows = config.num_partitions if config.distinct_keys_per_partition else 1
        hll = HLLState(regs=np.zeros((dev, rows, config.hll_m), np.int32))
    quantiles = None
    if config.enable_quantiles:
        from kafka_topic_analyzer_tpu.models.quantiles import DDSketchState
        from kafka_topic_analyzer_tpu.ops.ddsketch import ddsketch_num_buckets

        rows = config.num_partitions if config.quantiles_per_partition else 1
        quantiles = DDSketchState(
            counts=np.zeros(
                (dev, rows, ddsketch_num_buckets(config.quantile_buckets)), np.int64
            )
        )
    state = AnalyzerState(metrics=metrics, alive=alive, hll=hll, quantiles=quantiles)
    specs = _state_specs(config)
    return jax.tree.map(lambda x, s: _global_put(x, mesh, s), state, specs)


class PackedShard:
    """One data row's batch already packed into its space chunks
    ``[S, chunk_nbytes]`` by ``ShardedTpuBackend.prepare_shard`` — the
    sharded counterpart of ``backends.tpu.StagedBatch``.  Just a typed
    array: all bookkeeping stays with the decoded batch the engine holds.

    ``pairs`` rides the compacted alive path: the row batch's LWW
    ``(slot u32[n], flag u8[n])`` host arrays in stream order (per-chunk
    deduped on the fused path — the dispatch merge resolves cross-chunk
    duplicates), None when compaction is off."""

    __slots__ = ("chunks", "pairs")

    def __init__(self, chunks: np.ndarray, pairs=None):
        self.chunks = chunks
        self.pairs = pairs


@instrument_steps
class ShardedTpuBackend(MetricBackend):
    """Multi-device backend over a (data, space) mesh.

    Feed it via `update_shards` with one batch per data shard (the engine
    routes each partition to a fixed shard — records.py ordering contract).
    `update` also works for convenience and splits a mixed batch by the
    partition→shard assignment.
    """

    def __init__(
        self,
        config: AnalyzerConfig,
        mesh=None,
        init_now_s: "int | None" = None,
        use_native: bool = True,
        dispatch: "DispatchConfig | None" = None,
    ):
        self.mesh = mesh if mesh is not None else make_mesh(*config.mesh_shape)
        device = self.mesh.devices.flat[0]
        require_device_platform(device)
        if config.use_pallas_counters:
            config = _dc.replace(
                config, pallas_interpret=device.platform != "tpu"
            )
        super().__init__(config)
        self.init_now_s = utc_now_seconds() if init_now_s is None else init_now_s
        if dict(zip(self.mesh.axis_names, self.mesh.devices.shape)) != {
            DATA_AXIS: config.data_shards,
            SPACE_AXIS: config.space_shards,
        }:
            raise ValueError("mesh shape does not match config.mesh_shape")
        self.state = _stacked_init(config, self.mesh)
        self._specs = _state_specs(config)
        # Packed buffers: one CHUNK (batch_size / space_shards records) per
        # (data, space) device — shape (D, S, chunk_nbytes).
        self._buf_sharding = NamedSharding(self.mesh, P(DATA_AXIS, SPACE_AXIS))
        self._row_sharding = NamedSharding(self.mesh, P(DATA_AXIS))

        if config.batch_size % config.space_shards:
            raise ValueError(
                "batch_size must divide evenly into space_shards chunks"
            )
        if (
            config.use_pallas_counters
            and config.wire_format == 4
            and config.chunk_size % 1024
        ):
            # v4 MXU-kernel block constraint only; the v5 table merge
            # (pallas_counters_merge) pads any shape internally.
            raise ValueError(
                "use_pallas_counters requires a per-space-shard chunk "
                "(batch_size / space_shards) that is a multiple of 1024"
            )
        self._chunk_config = (
            _dc.replace(config, batch_size=config.chunk_size)
            if config.space_shards > 1
            else config
        )
        self.use_native = use_native
        # Multi-controller support: the data rows THIS process feeds, and
        # whether device transfers must go through the process-local API.
        from kafka_topic_analyzer_tpu.parallel.mesh import local_data_rows

        self.local_rows = local_data_rows(self.mesh)
        self._multiprocess = jax.process_count() > 1
        rows = self.local_rows
        self._rows_contiguous = rows == list(
            range(rows[0], rows[0] + len(rows))
        ) if rows else True
        #: Per-process snapshots assemble contiguous local row blocks; a
        #: mesh that interleaves process ownership along the data axis
        #: can't snapshot — the engine degrades with a warning instead of
        #: crashing at the first snapshot interval.
        self.snapshot_capable = not self._multiprocess or self._rows_contiguous

        chunk_config = self._chunk_config
        # Compacted alive path (DESIGN.md §19): each data row ships ONE
        # LWW-merged pair table per dispatch, replicated over the space
        # axis by its P(data, None) spec — each space shard applies its
        # slot range AFTER the scan, so the per-step pair all_gather over
        # 'space' disappears from the compacted step entirely.
        self._compact = config.compact_alive
        self._pair_cap1 = (
            pair_table_capacity(config, config.batch_size, 1)
            if self._compact
            else 0
        )
        self._pair_sharding = NamedSharding(self.mesh, P(DATA_AXIS, None))

        def _step_body(state, bufs, ptabs=None):
            local = jax.tree.map(lambda x: x[0], state)
            arrays = unpack_device(bufs[0, 0], chunk_config)
            space_idx = lax.axis_index(SPACE_AXIS)
            new = analyzer_step(
                local,
                arrays,
                chunk_config,
                space_index=space_idx,
                space_axis=SPACE_AXIS,
            )
            if ptabs is not None:
                new = apply_pair_table(
                    new,
                    unpack_pair_table_device(
                        ptabs[0], config, self._pair_cap1
                    ),
                    config,
                    space_index=space_idx,
                )
            return jax.tree.map(lambda x: x[None], new)

        # The Pallas counter kernel declares its varying axes (vma) so the
        # static checker stays on for compiled TPU runs; jax's pallas
        # INTERPRETER (what a mesh off the TPU runs) internally builds
        # replicated constants that trip the checker — a jax-side
        # limitation its own error message acknowledges — so only that
        # combination relaxes it.
        relax_vma = config.use_pallas_counters and config.pallas_interpret
        step = jax.shard_map(
            _step_body,
            mesh=self.mesh,
            in_specs=(
                (self._specs, P(DATA_AXIS, SPACE_AXIS), P(DATA_AXIS, None))
                if self._compact
                else (self._specs, P(DATA_AXIS, SPACE_AXIS))
            ),
            out_specs=self._specs,
            check_vma=not relax_vma,
        )
        self._step = jax.jit(step, donate_argnums=(0,))
        self._merge = jax.jit(self._build_merge())

        # Superbatch dispatch layer: K rounds of per-row chunk stacks
        # folded by ONE scanned collective dispatch (state donated once
        # per superbatch).  The scanned axis is the ROUND axis: scan step
        # k replays exactly what the per-round collective step would have
        # done at round k — including the alive-pair all_gather over
        # 'space', which runs once per scan step in step order, so
        # last-writer-wins application order is preserved across the
        # scanned axis and results stay byte-identical.
        self.dispatch_config = dispatch if dispatch is not None else DispatchConfig()
        self.superbatch_k = self.dispatch_config.resolve(config.batch_size)
        self.dispatch_depth = self.dispatch_config.depth
        if self.superbatch_k > 1:
            self._pair_cap_k = (
                pair_table_capacity(
                    config, config.batch_size, self.superbatch_k
                )
                if self._compact
                else 0
            )

            def _superstep_body(state, bufs, ptabs=None):
                # bufs block: [K, 1, 1, chunk_nbytes] per (data, space)
                # device (in_spec puts the round axis on no mesh axis).
                local = jax.tree.map(lambda x: x[0], state)
                space_idx = lax.axis_index(SPACE_AXIS)
                local, n_valid = superbatch_fold(
                    local,
                    bufs,
                    lambda buf: unpack_device(buf[0, 0], chunk_config),
                    chunk_config,
                    space_index=space_idx,
                    space_axis=SPACE_AXIS,
                    # Compacted path: the row's K rounds merged into one
                    # table, applied once after the scanned rounds — the
                    # per-scan-step pair all_gather is gone.
                    pairs=(
                        unpack_pair_table_device(
                            ptabs[0], config, self._pair_cap_k
                        )
                        if ptabs is not None
                        else None
                    ),
                )
                # Completion token: per-device [1, 1] block → global
                # [D, S] (no extra collective; any leaf syncs the step).
                token = jnp.sum(n_valid).astype(jnp.int32).reshape(1, 1)
                return jax.tree.map(lambda x: x[None], local), token

            superstep = jax.shard_map(
                _superstep_body,
                mesh=self.mesh,
                in_specs=(
                    (
                        self._specs,
                        P(None, DATA_AXIS, SPACE_AXIS),
                        P(DATA_AXIS, None),
                    )
                    if self._compact
                    else (self._specs, P(None, DATA_AXIS, SPACE_AXIS))
                ),
                out_specs=(self._specs, P(DATA_AXIS, SPACE_AXIS)),
                check_vma=not relax_vma,
            )
            self._superstep = jax.jit(superstep, donate_argnums=(0,))
            self._superbuf_sharding = NamedSharding(
                self.mesh, P(None, DATA_AXIS, SPACE_AXIS)
            )
            self._queue = DispatchQueue(self.dispatch_depth)
            from kafka_topic_analyzer_tpu.packing import (
                SuperbatchStager,
                packed_nbytes,
            )

            # One collective round stages as [local_rows, S, chunk_nbytes];
            # the ring assembles K of them in a single pass (no
            # stack-then-restack copy) into transfer-quiescent memory.
            self._stager = SuperbatchStager(
                (
                    len(self.local_rows),
                    config.space_shards,
                    packed_nbytes(self._chunk_config, config.chunk_size),
                ),
                self.superbatch_k,
                self.dispatch_depth,
            )
            self._empty_chunks: "Optional[np.ndarray]" = None

    # -- merge ---------------------------------------------------------------

    def _build_merge(self):
        config = self.config
        specs = self._specs

        def merge_body(state):
            local = jax.tree.map(lambda x: x[0], state)
            m = local.metrics
            # Record-parallel leaves fold per (data, space) device, so their
            # reductions span both mesh axes.
            dev_axes = (DATA_AXIS, SPACE_AXIS)
            merged = MessageMetricsState(
                per_partition=lax.psum(m.per_partition, dev_axes),
                earliest_s=_stack(m.earliest_s, dev_axes).min(0),
                latest_s=_stack(m.latest_s, dev_axes).max(0),
                smallest=_stack(m.smallest, dev_axes).min(0),
                largest=_stack(m.largest, dev_axes).max(0),
                overall_size=lax.psum(m.overall_size, dev_axes),
                overall_count=lax.psum(m.overall_count, dev_axes),
            )
            alive_count = jnp.int64(-1)
            if local.alive is not None:
                gathered = lax.all_gather(local.alive.words, DATA_AXIS)  # [D, W]
                words = lax.reduce(
                    gathered, np.uint32(0), lambda a, b: a | b, (0,)
                )
                pops = jnp.sum(lax.population_count(words).astype(jnp.int64))
                # The OR-reduced words are equal on every data shard but vma
                # still marks them varying over 'data'; a max over the data
                # rows makes the replication explicit (a no-op numerically).
                alive_count = _stack(
                    lax.psum(pops, SPACE_AXIS), DATA_AXIS
                ).max(0)
            hll_regs = (
                lax.pmax(local.hll.regs, dev_axes) if local.hll is not None else None
            )
            dd_counts = (
                lax.psum(local.quantiles.counts, dev_axes)
                if local.quantiles is not None
                else None
            )
            return merged, alive_count, hll_regs, dd_counts

        out_specs = (
            jax.tree.map(lambda _: P(), _state_specs(self.config).metrics),
            P(),
            P() if config.enable_hll else None,
            P() if config.enable_quantiles else None,
        )
        return jax.shard_map(
            merge_body,
            mesh=self.mesh,
            in_specs=(specs,),
            out_specs=out_specs,
        )

    # -- update --------------------------------------------------------------

    def _pack_chunks(
        self,
        batch: "Optional[RecordBatch]",
        out: "Optional[np.ndarray]" = None,
    ) -> np.ndarray:
        """Contiguous 1/S record chunks of one data row's batch, packed
        into ``[S, chunk_nbytes]`` (packing.pack_chunks — the single
        chunking rule).  ``out`` packs straight into a caller buffer (the
        superbatch stager's ring rows) instead of allocating."""
        if batch is None:
            batch = RecordBatch.empty(0)
        return pack_chunks(
            batch,
            self._chunk_config,
            self.config.space_shards,
            use_native=self.use_native,
            out=out,
        )

    def _row_pairs(self, batch: "Optional[RecordBatch]"):
        """One data row's LWW pairs for the compacted path (None rows —
        another process's, or identity pads — contribute none)."""
        if batch is None or len(batch) == 0:
            return (np.empty(0, np.uint32), np.empty(0, np.uint8))
        return batch_alive_pairs(batch, self.config, self.use_native)

    def _pack_row_pair_tables(self, pair_lists_per_row, cap) -> np.ndarray:
        """``[local_rows, pair_table_nbytes]`` — one merged table per fed
        data row, raw→emitted compaction split booked (never silent)."""
        bufs = []
        for pair_lists in pair_lists_per_row:
            buf, raw, emitted = pack_pair_table(
                pair_lists, self.config, cap, use_native=self.use_native
            )
            obs_metrics.ALIVE_PAIRS_RAW.inc(raw)
            obs_metrics.ALIVE_PAIRS_EMITTED.inc(emitted)
            bufs.append(buf)
        return np.stack(bufs)

    def _put_pair_tables(self, tables: np.ndarray):
        obs_metrics.WIRE_BYTES.inc(int(tables.nbytes))
        if self._multiprocess:
            return jax.make_array_from_process_local_data(
                self._pair_sharding,
                tables,
                global_shape=(self.config.data_shards,) + tables.shape[1:],
            )
        return jax.device_put(tables, self._pair_sharding)

    def prepare_shard(self, batch: RecordBatch) -> "PackedShard":
        """Pack one data row's batch ahead of its collective step — safe on
        a prefetch worker thread (pure numpy/C++), so the S-way chunk
        packing of every row overlaps the device's current step instead of
        serializing in front of update_shards (engine staging).  Compacted
        alive configs dedupe the row's pairs here too (the dispatch merges
        them per row)."""
        if self._compact:
            return PackedShard(
                self._pack_chunks(batch), self._row_pairs(batch)
            )
        return PackedShard(self._pack_chunks(batch))

    def make_fused_sink(self, dense_of):
        """A packing.FusedPackSink whose rows are this backend's
        ``[S, chunk_nbytes]`` chunk stacks — records fill chunk 0..S-1 at
        chunk_size each, the exact ``pack_chunks`` rule, so a fused row
        is byte-for-byte what ``prepare_shard`` would have staged.  One
        sink per fed data row's ingest stream (engine.run_scan).  Under
        compaction the sink hands the row's harvested pairs to the staged
        form (PackedShard.pairs)."""
        from kafka_topic_analyzer_tpu.packing import FusedPackSink

        return FusedPackSink(
            self._chunk_config,
            self.config.chunk_size,
            dense_of,
            stage=PackedShard,
            space_shards=self.config.space_shards,
            chunk_rows=True,
        )

    def update_shards(
        self, batches: "List[RecordBatch | PackedShard | None]"
    ) -> None:
        """One collective step; ``batches[d]`` feeds data row ``d`` — a
        decoded batch, or a ``PackedShard`` staged via ``prepare_shard``.

        Under multi-controller, entries for rows another process hosts are
        ignored here (that process supplies them in ITS call) — the engine
        passes None for them.  Every process must call this in lockstep:
        the compiled step is a global program."""
        d = self.config.data_shards
        if len(batches) != d:
            raise ValueError(f"expected {d} shard batches, got {len(batches)}")

        local = [batches[r] for r in self.local_rows]
        per_shard = np.stack([
            b.chunks if isinstance(b, PackedShard) else self._pack_chunks(b)
            for b in local
        ])  # [local_rows, S, chunk_nbytes]
        obs_metrics.WIRE_BYTES.inc(int(per_shard.nbytes))  # this process's rows
        if self._multiprocess:
            bufs = jax.make_array_from_process_local_data(
                self._buf_sharding,
                per_shard,
                global_shape=(d,) + per_shard.shape[1:],
            )
        else:
            bufs = jax.device_put(per_shard, self._buf_sharding)
        if self._compact:
            tables = self._pack_row_pair_tables(
                [
                    [
                        b.pairs
                        if isinstance(b, PackedShard) and b.pairs is not None
                        else self._row_pairs(
                            None if isinstance(b, PackedShard) else b
                        )
                    ]
                    for b in local
                ],
                self._pair_cap1,
            )
            self.state = self._step(
                self.state, bufs, self._put_pair_tables(tables)
            )
            return
        self.state = self._step(self.state, bufs)

    def update_shards_superbatch(
        self, rounds: "List[List[RecordBatch | PackedShard | None]]"
    ) -> None:
        """Fold up to K rounds of shard batches in ONE scanned collective
        dispatch — byte-identical to K sequential ``update_shards`` calls
        (the scan replays them in order).  A partial tail is padded to K
        with empty rounds (identity folds) so the compiled program count
        stays one.  Collective: under multi-controller every process must
        call this in lockstep with the same round count — the engine's
        per-round ``global_any`` agreement guarantees all processes
        accumulate and flush at the same rounds."""
        k = self.superbatch_k
        if not rounds or len(rounds) > k:
            raise ValueError(f"superbatch of {len(rounds)} rounds (K={k})")
        d = self.config.data_shards
        for batches in rounds:
            if len(batches) != d:
                raise ValueError(
                    f"expected {d} shard batches per round, got {len(batches)}"
                )
        self._queue.throttle()  # before staging: bounds host stacks too
        stacked = self._stager.next_slot()  # [K, local_rows, S, chunk_nbytes]
        for i, batches in enumerate(rounds):
            for j, r in enumerate(self.local_rows):
                b = batches[r]
                if isinstance(b, PackedShard):
                    # Worker-staged upstream (parallel ingest packs before
                    # the fan-in order — and hence the ring row — is
                    # known): one copy into the ring.
                    np.copyto(stacked[i, j], b.chunks)
                else:
                    # Unstaged: pack straight into the ring row, no
                    # intermediate [S, nbytes] stack.
                    self._pack_chunks(b, out=stacked[i, j])
        if len(rounds) < k:
            if self._empty_chunks is None:
                self._empty_chunks = np.stack(
                    [self._pack_chunks(None) for _ in self.local_rows]
                )
            for i in range(len(rounds), k):
                np.copyto(stacked[i], self._empty_chunks)
        obs_metrics.WIRE_BYTES.inc(int(stacked.nbytes))  # this process's rows
        if self._multiprocess:
            bufs = jax.make_array_from_process_local_data(
                self._superbuf_sharding,
                stacked,
                global_shape=(k, d) + stacked.shape[2:],
            )
        else:
            bufs = jax.device_put(stacked, self._superbuf_sharding)
        if self._compact:
            # Per-row LWW merge across the superbatch's K rounds, in round
            # order — the scanned steps then fold pair-free and each row's
            # table applies once after the scan (identity-pad rounds
            # contribute no pairs).
            per_row_lists = []
            for r in self.local_rows:
                lists = []
                for batches in rounds:
                    b = batches[r]
                    if isinstance(b, PackedShard):
                        if b.pairs is not None:
                            lists.append(b.pairs)
                    else:
                        lists.append(self._row_pairs(b))
                per_row_lists.append(lists)
            tables = self._pack_row_pair_tables(
                per_row_lists, self._pair_cap_k
            )
            self.state, token = self._superstep(
                self.state, bufs, self._put_pair_tables(tables)
            )
        else:
            self.state, token = self._superstep(self.state, bufs)
        self._queue.launched(token, len(rounds))

    def global_any(self, flag: bool) -> bool:
        """All-process OR of a host flag, via a psum over the data axis.

        The multi-host scan loop's agreement point: processes drain their
        shard streams at different times, but collective steps must stay in
        lockstep — each round every process contributes "I still have
        data", and the loop continues iff anyone does.  Same result on
        every process (it's a collective), so break decisions stay
        consistent and deadlock-free."""
        if not hasattr(self, "_any_fn"):
            def body(x):
                return lax.psum(x, DATA_AXIS)

            self._any_fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=P(DATA_AXIS),
                    out_specs=P(),
                )
            )
        local = np.full((len(self.local_rows),), int(flag), np.int32)
        if self._multiprocess:
            arr = jax.make_array_from_process_local_data(
                self._row_sharding,
                local,
                global_shape=(self.config.data_shards,),
            )
        else:
            arr = jax.device_put(local, self._row_sharding)
        return bool(np.asarray(self._any_fn(arr)).sum() > 0)

    def gather_telemetry(self) -> "List[dict]":
        """Per-process registry snapshots, one per controller.

        Multi-controller aggregation over the same lockstep collective
        machinery as ``global_any``: each process JSON-encodes its local
        obs registry snapshot, the fleet agrees on the max payload size
        (pmax over the data axis), and the length-prefixed padded byte
        rows are all_gathered so every process can decode every
        snapshot.  Rows are deduped by process id (a process hosting R
        data rows contributes R identical copies).  Collective — every
        process must call it at the same point (the engine does, in
        ``run_scan``'s tail); the report process then folds the list with
        ``obs.registry.merge_snapshots`` into the cluster-wide view.

        This same merge is what gives mesh scans a FLEET-WIDE bottleneck
        verdict: every occupancy signal the scan doctor attributes from
        (live stage seconds, throttle waits, worker stall/active seconds,
        fetch/decode seconds — obs/doctor.py) is a counter, and counters
        sum across this gather, so process 0's digest attributes the
        whole fleet without shipping any flight-recorder series."""
        import json

        from kafka_topic_analyzer_tpu.obs.registry import default_registry

        snap = default_registry().snapshot()
        if not self._multiprocess:
            return [snap]
        payload = json.dumps(
            {"pid": jax.process_index(), "telemetry": snap}
        ).encode()

        def _row_array(local: np.ndarray, sharding, global_rows: int):
            return jax.make_array_from_process_local_data(
                sharding, local, global_shape=(global_rows,) + local.shape[1:]
            )

        d = self.config.data_shards
        n_local = len(self.local_rows)
        # Round 1: agree on the widest payload (pmax over 'data').
        if not hasattr(self, "_pmax_fn"):
            self._pmax_fn = jax.jit(
                jax.shard_map(
                    lambda x: lax.pmax(x, DATA_AXIS),
                    mesh=self.mesh,
                    in_specs=P(DATA_AXIS),
                    out_specs=P(),
                )
            )
        lens = np.full((n_local,), len(payload), np.int32)
        width = int(np.asarray(
            self._pmax_fn(_row_array(lens, self._row_sharding, d))
        ).max())
        # Round 2: gather the length-prefixed, zero-padded rows, as int32
        # (a psum of bytes is not a reduction every backend lowers) and
        # replicated, as the P(None, None) out_spec requires.  Not
        # cached/jitted: the width varies per call and this runs once per
        # scan.
        gather = jax.shard_map(
            lambda x: _stack(x[0].astype(jnp.int32), DATA_AXIS),
            mesh=self.mesh,
            in_specs=P(DATA_AXIS, None),
            out_specs=P(None, None),
        )
        rows = np.zeros((n_local, 4 + width), np.uint8)
        prefix = np.frombuffer(
            len(payload).to_bytes(4, "big"), np.uint8
        )
        for r in range(n_local):
            rows[r, :4] = prefix
            rows[r, 4:4 + len(payload)] = np.frombuffer(payload, np.uint8)
        sharding = NamedSharding(self.mesh, P(DATA_AXIS, None))
        gathered = np.asarray(
            jax.jit(gather)(_row_array(rows, sharding, d))
        ).astype(np.uint8)
        out: "dict[int, dict]" = {}
        for r in range(d):
            n = int.from_bytes(gathered[r, :4].tobytes(), "big")
            doc = json.loads(gathered[r, 4:4 + n].tobytes().decode())
            out.setdefault(doc["pid"], doc["telemetry"])
        return [out[pid] for pid in sorted(out)]

    def update(self, batch: RecordBatch) -> None:
        """Split a mixed batch by partition→shard (partition % D)."""
        d = self.config.data_shards
        shard_of = np.asarray(batch.partition) % d
        self.update_shards(
            [batch.take(np.nonzero(shard_of == s)[0]) for s in range(d)]
        )

    def drain_dispatch(self) -> None:
        """Retire every in-flight superbatch dispatch WITHOUT launching a
        new collective — the engine's failure path calls this before its
        final snapshot (DESIGN.md §14 lockstep flush protocol).

        Lockstep-safe even when only THIS controller is stopping: the
        queued completion tokens belong to scanned steps that every
        controller already launched at a lockstep-agreed round (the
        engine accumulates and flushes superbatches only after the
        per-round ``global_any`` agreement), so blocking on them is a
        local wait on collective programs that are already running
        fleet-wide — never a one-sided collective that could deadlock a
        peer.  Contrast with the partial-tail flush, which WOULD launch a
        new collective and is therefore skipped on multi-controller fault
        paths (engine.py ``fault_flush``)."""
        if self.superbatch_k > 1:
            self._queue.drain()

    def block_until_ready(self) -> None:
        self.drain_dispatch()
        jax.block_until_ready(self.state)

    # -- snapshot/resume (checkpoint.py) -------------------------------------

    def get_state(self) -> AnalyzerState:
        return self.state

    def set_state(self, host_state: AnalyzerState) -> None:
        self.state = jax.tree.map(
            lambda x, s: jax.device_put(
                np.asarray(x), NamedSharding(self.mesh, s)
            ),
            host_state,
            self._specs,
        )

    @property
    def controller_index(self) -> int:
        """This process's index in the fleet (0 single-controller) — the
        engine prefixes per-worker ingest telemetry labels with it so the
        cross-controller merge unions worker samples instead of summing
        unrelated workers that happen to share an id."""
        return jax.process_index() if self._multiprocess else 0

    @property
    def snapshot_scope(self):
        """None single-controller; (pid, nproc, local_rows) under
        jax.distributed — the engine then snapshots per process via
        get_state_local/set_state_local (data shards fold independently,
        so per-process files need no coordination)."""
        if not self._multiprocess:
            return None
        return (jax.process_index(), jax.process_count(), self.local_rows)

    def get_state_local(self) -> AnalyzerState:
        """Host copy of THIS process's data rows of every state leaf."""
        rows = self.local_rows
        row0 = rows[0]
        if rows != list(range(row0, row0 + len(rows))):
            raise NotImplementedError(
                "snapshots need contiguous local data rows"
            )

        d = self.config.data_shards

        def to_local(arr):
            # Record-parallel leaves carry D·S leading rows (one per
            # device), the bitmap D; either way each data row owns a
            # contiguous `scale`-row block of the leading axis.
            scale = arr.shape[0] // d
            base = row0 * scale
            local_shape = (len(rows) * scale,) + arr.shape[1:]
            buf = np.empty(local_shape, dtype=arr.dtype)
            for sh in arr.addressable_shards:
                idx = sh.index
                r = idx[0]
                lo = (r.start or 0) - base
                hi = (r.stop if r.stop is not None else arr.shape[0]) - base
                buf[(slice(lo, hi),) + tuple(idx[1:])] = np.asarray(sh.data)
            return buf

        return jax.tree.map(to_local, self.state)

    def set_state_local(self, local_state: AnalyzerState) -> None:
        """Rebuild the global state from THIS process's rows (the other
        processes supply theirs in their own call)."""
        d = self.config.data_shards
        n_local = len(self.local_rows)

        def put(x, s):
            x = np.asarray(x)
            scale = x.shape[0] // n_local
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, s),
                x,
                global_shape=(d * scale,) + x.shape[1:],
            )

        self.state = jax.tree.map(put, local_state, self._specs)

    # -- finalize ------------------------------------------------------------

    def finalize(self) -> TopicMetrics:
        # Complete the dispatch-latency histogram before the merge
        # collective syncs the state anyway.
        self.drain_dispatch()
        merged, alive_count, hll_regs, dd_counts = self._merge(self.state)
        merged = jax.tree.map(np.asarray, jax.device_get(merged))
        alive_count = int(alive_count)

        from kafka_topic_analyzer_tpu.models.compaction import HLLState
        from kafka_topic_analyzer_tpu.models.quantiles import DDSketchState

        host_state = AnalyzerState(
            metrics=merged,
            alive=None,
            hll=HLLState(regs=np.asarray(hll_regs)) if hll_regs is not None else None,
            quantiles=(
                DDSketchState(counts=np.asarray(dd_counts))
                if dd_counts is not None
                else None
            ),
        )
        metrics = metrics_from_state(host_state, self.config, self.init_now_s)
        if self.config.count_alive_keys:
            metrics.alive_keys = alive_count
        return metrics
