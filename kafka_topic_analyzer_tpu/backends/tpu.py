"""TPU backend: jitted streaming reduction with donated device state.

Design (SURVEY.md §7 M3 + the transfer work in packing.py):
- the accumulator state lives on device for the whole scan; each `update`
  dispatches one jitted step with the state buffers *donated*, so XLA updates
  them in place and the host never round-trips the state (hard part (e));
- each batch crosses the host→device boundary as ONE packed uint8 buffer in
  wire format v4 (packing.py's module docstring is the layout contract) —
  minimal bytes per record, host-side pre-reduction for the bitmap/HLL
  updates;
- dispatch is asynchronous — the host thread returns immediately and keeps
  packing the next batch while the device works; `finalize` synchronizes;
- at ``--superbatch K`` > 1, K packed buffers stack into one contiguous
  ``uint8[K, N]`` host array folded by a single jitted ``lax.scan`` dispatch
  (state donated once per superbatch, one large transfer), with up to
  ``--dispatch-depth`` superbatches in flight (bounded by DispatchQueue);
- a one-time pack→unpack self-check at init guards the bitcast layout
  against byte-order surprises on new platforms.

Multi-device runs go through `kafka_topic_analyzer_tpu.parallel.sharded`
(same packed step under ``shard_map``).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np

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
from kafka_topic_analyzer_tpu.jax_support import require_device_platform
from kafka_topic_analyzer_tpu.models.state import AnalyzerState
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.packing import (
    SuperbatchStager,
    batch_alive_pairs,
    pack_batch,
    pack_pair_table,
    packed_nbytes,
    pair_table_capacity,
    unpack_device,
    unpack_numpy,
    unpack_pair_table_device,
)
from kafka_topic_analyzer_tpu.records import RecordBatch
from kafka_topic_analyzer_tpu.results import TopicMetrics
from kafka_topic_analyzer_tpu.utils.timefmt import utc_now_seconds


def make_packed_step(config: AnalyzerConfig):
    """The jittable forward step: (state, packed uint8 buffer) → state.

    Under alive-pair compaction (``config.compact_alive``) the step takes
    a second buffer — the batch's packed pair table — applied after the
    fold exactly like the superbatch path applies its merged table."""
    if config.compact_alive:
        cap = pair_table_capacity(config, config.batch_size, 1)

        def step_c(state: AnalyzerState, buf, pairbuf) -> AnalyzerState:
            st = analyzer_step(state, unpack_device(buf, config), config)
            return apply_pair_table(
                st, unpack_pair_table_device(pairbuf, config, cap), config
            )

        return step_c

    def step(state: AnalyzerState, buf) -> AnalyzerState:
        return analyzer_step(state, unpack_device(buf, config), config)

    return step


def make_packed_superstep(config: AnalyzerConfig, k: int = 1):
    """The jittable superbatch step: (state, uint8[K, N]) → (state, token).

    One dispatch scan-folds the K stacked packed buffers in order
    (backends/step.py::superbatch_fold), donating the state once per
    superbatch instead of once per batch.  The token (int32[K] of
    per-batch valid counts) is a small non-donated output used by the
    bounded dispatch queue as a completion marker.

    Under alive-pair compaction the superstep takes the dispatch's merged
    pair table (capacity ``pair_table_capacity(config, B, k)``) and
    applies it ONCE after the scan — this is the compaction win: the
    O(W) bitmap mask apply leaves the scan body entirely."""
    if config.compact_alive:
        cap = pair_table_capacity(config, config.batch_size, k)

        def superstep_c(state: AnalyzerState, bufs, pairbuf):
            return superbatch_fold(
                state,
                bufs,
                lambda b: unpack_device(b, config),
                config,
                pairs=unpack_pair_table_device(pairbuf, config, cap),
            )

        return superstep_c

    def superstep(state: AnalyzerState, bufs):
        return superbatch_fold(
            state, bufs, lambda b: unpack_device(b, config), config
        )

    return superstep


class StagedBatch:
    """A batch already packed for (or launched into) host→device transfer.

    Produced by ``TpuBackend.prepare`` — designed to run on a prefetch
    worker thread (engine.run_scan stages there), so the pack (native,
    GIL-released) overlaps the device's current step instead of
    serializing in front of the next dispatch.  At superbatch K=1 the
    worker also starts the async ``device_put`` (the explicit
    double-buffered host→device pipeline SURVEY.md §7 M5 calls for;
    prefetch depth bounds in-flight buffers); at K>1 ``buf`` stays a HOST
    buffer — the fan-in order decides which superbatch row it lands in,
    and the whole stack crosses in one large transfer at dispatch time.
    Deliberately just a typed buffer: all bookkeeping (counts, bytes,
    offsets) stays with the decoded batch the engine already holds.

    ``pairs`` rides the compacted alive path (DESIGN.md §19): at K=1 it
    is the batch's PACKED pair-table buffer (device-put alongside the
    row on the producing thread); at K>1 the raw ``(slot u32[n], flag
    u8[n])`` host arrays the dispatch-time merge consumes.  None when
    compaction is off.
    """

    __slots__ = ("buf", "pairs")

    def __init__(self, buf, pairs=None):
        self.buf = buf
        self.pairs = pairs


def self_check_unpack(device=None) -> None:
    """One-time guard: pack a known batch on the host, unpack it on the
    device, and compare — catches any bitcast/byte-order mismatch before it
    could corrupt results.  Runs BOTH wire formats: v4's per-record
    columns and v5's combiner tables (including the quantile section)
    cross the same bitcast boundary."""
    from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

    spec = SyntheticSpec(
        num_partitions=3, messages_per_partition=40, keys_per_partition=16, seed=11
    )
    batch = next(SyntheticSource(spec).batches(100))
    for wire_format in (4, 5):
        config = AnalyzerConfig(
            num_partitions=3,
            batch_size=128,
            count_alive_keys=True,
            alive_bitmap_bits=16,
            enable_hll=True,
            hll_p=8,
            enable_quantiles=True,
            wire_format=wire_format,
        )
        buf = pack_batch(batch, config, use_native=False)
        expected = unpack_numpy(buf, config)
        unpack = jax.jit(lambda b, c=config: unpack_device(b, c))
        got = unpack(jax.device_put(buf, device))
        for name, exp in expected.items():
            g = np.asarray(got[name])
            if not np.array_equal(g, np.asarray(exp)):
                raise RuntimeError(
                    f"packed-transfer self-check failed on wire-v"
                    f"{wire_format} field {name!r}: device unpack disagrees "
                    f"with host layout (byte order?)"
                )


_checked_devices: "set[str]" = set()


@instrument_steps
class TpuBackend(MetricBackend):
    def __init__(
        self,
        config: AnalyzerConfig,
        init_now_s: "int | None" = None,
        device=None,
        use_native: bool = True,
        dispatch: "DispatchConfig | None" = None,
    ):
        self.device = device if device is not None else jax.devices()[0]
        require_device_platform(self.device)
        if config.use_pallas_counters:
            config = dataclasses.replace(
                config, pallas_interpret=self.device.platform != "tpu"
            )
        super().__init__(config)
        self.init_now_s = utc_now_seconds() if init_now_s is None else init_now_s
        self.use_native = use_native
        key = str(self.device)
        if key not in _checked_devices and not os.environ.get("KTA_SKIP_SELFCHECK"):
            self_check_unpack(self.device)
            _checked_devices.add(key)
        with jax.default_device(self.device):
            self.state = AnalyzerState.init(config)
        # State donation is an accelerator-memory optimization only.  On
        # the host-CPU platform it is actively UNSAFE under the fleet's
        # concurrent per-topic scan threads: concurrent dispatches of a
        # donated-state executable race XLA:CPU's donation bookkeeping,
        # and a live state buffer can be freed while still referenced —
        # the resumed fold then reads recycled heap memory (pointer-sized
        # garbage in counts/HLL registers).  States are KBs on CPU, so
        # the extra copy per step costs nothing measurable there.
        self._donate = (0,) if self.device.platform != "cpu" else ()
        self._step = jax.jit(
            make_packed_step(config), donate_argnums=self._donate
        )
        # Superbatch dispatch layer (config.DispatchConfig): K packed
        # buffers per jitted scan dispatch, up to `depth` superbatches in
        # flight.  K=1 keeps the classic one-dispatch-per-batch path
        # (prepare launches the transfer itself) untouched.
        self.dispatch_config = dispatch if dispatch is not None else DispatchConfig()
        self.superbatch_k = self.dispatch_config.resolve(config.batch_size)
        self.dispatch_depth = self.dispatch_config.depth
        # Compacted alive path (DESIGN.md §19): per-dispatch pair-table
        # capacities for the two dispatch shapes this backend compiles.
        self._compact = config.compact_alive
        self._pair_cap1 = (
            pair_table_capacity(config, config.batch_size, 1)
            if self._compact
            else 0
        )
        if self.superbatch_k > 1:
            self._pair_cap_k = (
                pair_table_capacity(config, config.batch_size, self.superbatch_k)
                if self._compact
                else 0
            )
            self._superstep = jax.jit(
                make_packed_superstep(config, self.superbatch_k),
                donate_argnums=self._donate,
            )
            self._stager = SuperbatchStager(
                (packed_nbytes(config, config.batch_size),),
                self.superbatch_k,
                self.dispatch_depth,
            )
            self._queue = DispatchQueue(self.dispatch_depth)
            self._empty_buf: "np.ndarray | None" = None

    def set_dispatch_depth(self, depth: int) -> None:
        """Re-bound the in-flight dispatch window between passes — the
        fleet scheduler's dispatch-share grants (DESIGN.md §20) become
        real backpressure here, not just ledger rows.  Shrinks apply
        immediately (DispatchQueue.throttle reads the bound per call,
        and a live shrink just waits in-flight work below the new bound);
        grows clamp at the CONSTRUCTED depth, because the stager ring was
        sized then and a wider window would outrun its slots."""
        depth = max(1, min(int(depth), self.dispatch_config.depth))
        self.dispatch_depth = depth
        q = getattr(self, "_queue", None)
        if q is not None:
            q.depth = depth

    def _pack_pairs(self, pair_lists, cap) -> np.ndarray:
        """Merge + pack a dispatch's pair table, booking the raw→emitted
        compaction split (never silent — the --stats ratio reads these)."""
        buf, raw, emitted = pack_pair_table(
            pair_lists, self.config, cap, use_native=self.use_native
        )
        obs_metrics.ALIVE_PAIRS_RAW.inc(raw)
        obs_metrics.ALIVE_PAIRS_EMITTED.inc(emitted)
        return buf

    def prepare(self, batch: RecordBatch) -> StagedBatch:
        """Pack (and, at superbatch K=1, start the host→device transfer
        for) a batch that will be fed to ``update``/``update_superbatch``
        later.  Safe to call from a worker thread (jax dispatch is
        thread-safe; the packers are pure numpy/C++).  At K>1 the buffer
        stays on the host: it is copied into its superbatch row at fan-in
        time and crosses in the stack's single large transfer.  Compacted
        alive configs stage the batch's pairs alongside: packed + put at
        K=1 (the whole table is this batch's), raw host arrays at K>1
        (the dispatch-time merge spans the superbatch)."""
        buf = pack_batch(batch, self.config, use_native=self.use_native)
        if self.superbatch_k > 1:
            if self._compact:
                return StagedBatch(
                    buf, batch_alive_pairs(batch, self.config, self.use_native)
                )
            return StagedBatch(buf)
        if self._compact:
            pairbuf = self._pack_pairs(
                [batch_alive_pairs(batch, self.config, self.use_native)],
                self._pair_cap1,
            )
            return StagedBatch(
                jax.device_put(buf, self.device),
                jax.device_put(pairbuf, self.device),
            )
        return StagedBatch(jax.device_put(buf, self.device))

    def make_fused_sink(self, dense_of):
        """A packing.FusedPackSink staged for this backend: fused rows
        come out exactly like ``prepare``'s output (async ``device_put``
        on the producing thread at K=1; host buffer at K>1, copied into
        its superbatch stager row at fan-in time).  One sink per ingest
        stream — sinks are single-threaded state."""
        from kafka_topic_analyzer_tpu.packing import FusedPackSink

        def stage(buf, pairs=None):
            if self.superbatch_k > 1:
                return StagedBatch(buf, pairs)
            if self._compact:
                pairbuf = self._pack_pairs([pairs], self._pair_cap1)
                return StagedBatch(
                    jax.device_put(buf, self.device),
                    jax.device_put(pairbuf, self.device),
                )
            return StagedBatch(jax.device_put(buf, self.device))

        return FusedPackSink(
            self.config, self.config.batch_size, dense_of, stage=stage
        )

    def update(self, batch: "RecordBatch | StagedBatch") -> None:
        if isinstance(batch, StagedBatch):
            obs_metrics.WIRE_BYTES.inc(int(batch.buf.nbytes))
            if self._compact:
                obs_metrics.WIRE_BYTES.inc(int(batch.pairs.nbytes))
                self.state = self._step(self.state, batch.buf, batch.pairs)
            else:
                self.state = self._step(self.state, batch.buf)
            return
        buf = pack_batch(batch, self.config, use_native=self.use_native)
        obs_metrics.WIRE_BYTES.inc(int(buf.nbytes))
        if self._compact:
            pairbuf = self._pack_pairs(
                [batch_alive_pairs(batch, self.config, self.use_native)],
                self._pair_cap1,
            )
            obs_metrics.WIRE_BYTES.inc(int(pairbuf.nbytes))
            self.state = self._step(
                self.state,
                jax.device_put(buf, self.device),
                jax.device_put(pairbuf, self.device),
            )
            return
        self.state = self._step(self.state, jax.device_put(buf, self.device))

    def _empty_packed(self) -> np.ndarray:
        """Identity-fold pad for a partial superbatch tail: a packed empty
        batch (n_valid 0, n_pairs 0, identity-filled extreme tables, zero
        HLL registers) folds as a no-op, so padding the tail to K keeps
        ONE compiled superstep instead of one per tail length."""
        if self._empty_buf is None:
            self._empty_buf = pack_batch(
                RecordBatch.empty(0), self.config, use_native=self.use_native
            )
        return self._empty_buf

    def update_superbatch(self, staged: "list[StagedBatch | RecordBatch]") -> None:
        """Fold up to K batches in one scanned dispatch (in list order —
        byte-identical to K sequential ``update`` calls).  Blocks in the
        dispatch queue's throttle while ``dispatch_depth`` superbatches
        are already in flight; that blocking is the backpressure that
        keeps staged-buffer memory bounded."""
        k = self.superbatch_k
        if not staged or len(staged) > k:
            raise ValueError(f"superbatch of {len(staged)} batches (K={k})")
        self._queue.throttle()  # before staging: bounds host rows too
        rows = self._stager.next_slot()
        pair_lists = []
        for i, item in enumerate(staged):
            if isinstance(item, StagedBatch):
                np.copyto(rows[i], np.asarray(item.buf))
                if self._compact and item.pairs is not None:
                    pair_lists.append(item.pairs)
            else:
                pack_batch(
                    item, self.config, use_native=self.use_native, out=rows[i]
                )
                if self._compact:
                    pair_lists.append(
                        batch_alive_pairs(item, self.config, self.use_native)
                    )
        for i in range(len(staged), k):
            np.copyto(rows[i], self._empty_packed())
        obs_metrics.WIRE_BYTES.inc(int(rows.nbytes))
        bufs = jax.device_put(rows, self.device)
        if self._compact:
            # The compaction tentpole: LWW-merge the superbatch's pairs in
            # fold order into ONE bounded table — the device applies it
            # once after the scan instead of scattering inside every scan
            # step (identity-padded tail rows contribute no pairs).
            pairbuf = self._pack_pairs(pair_lists, self._pair_cap_k)
            obs_metrics.WIRE_BYTES.inc(int(pairbuf.nbytes))
            self.state, token = self._superstep(
                self.state, bufs, jax.device_put(pairbuf, self.device)
            )
        else:
            self.state, token = self._superstep(self.state, bufs)
        self._queue.launched(token, len(staged))

    def drain_dispatch(self) -> None:
        """Retire every in-flight superbatch dispatch without launching a
        new one — the engine's failure path calls this before the final
        snapshot so the dispatch-latency histogram and in-flight gauge
        close out and the snapshotted state is provably quiescent.  (The
        single-device twin of ShardedTpuBackend.drain_dispatch, where the
        no-new-collective property is what makes it lockstep-safe.)"""
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
            lambda x: jax.device_put(np.asarray(x), self.device), host_state
        )

    def finalize(self) -> TopicMetrics:
        # Retire every in-flight dispatch first so the latency histogram
        # is complete (device_get below syncs anyway).
        self.drain_dispatch()
        host_state = jax.tree.map(np.asarray, jax.device_get(self.state))
        return metrics_from_state(host_state, self.config, self.init_now_s)
