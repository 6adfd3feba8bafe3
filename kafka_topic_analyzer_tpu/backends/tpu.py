"""TPU backend: jitted streaming reduction with donated device state.

Design (SURVEY.md §7 M3 + the transfer work in packing.py):
- the accumulator state lives on device for the whole scan; each `update`
  dispatches one jitted step with the state buffers *donated*, so XLA updates
  them in place and the host never round-trips the state (hard part (e));
- each batch crosses the host→device boundary as ONE packed uint8 buffer of
  host-reduced fold tables (packing.py's module docstring is the layout
  contract), and each dispatch of an alive-key scan one more buffer, its
  merged alive table;
- dispatch is asynchronous — the host thread returns immediately and keeps
  packing the next batch while the device works; `finalize` synchronizes,
  counts the alive bitmap on the device and copies back all but the bitmap;
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
from kafka_topic_analyzer_tpu.obs import trace as obs_trace
from kafka_topic_analyzer_tpu.ops.bitmap import bitmap_popcount
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

    Alive-key scans take a second buffer — the batch's packed alive table
    — applied after the fold exactly like the superbatch path applies its
    merged table."""
    if config.count_alive_keys:
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

    Alive-key scans take the dispatch's merged alive table (capacity
    ``pair_table_capacity(config, B, k)``) and apply it ONCE after the
    scan: the O(W) bitmap apply stays out of the scan body."""
    if config.count_alive_keys:
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


def _with_token(step):
    """``step`` returning, besides the new state, one element of it as a
    completion token: an output of its own that no later step's donation
    of the state takes, so the dispatch queue can block on it."""

    def step_t(state, *bufs):
        state = step(state, *bufs)
        return state, jax.tree.leaves(state)[0].ravel()[:1]

    return step_t


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

    ``pairs`` carries an alive-key scan's pairs (DESIGN.md §19): at K=1
    it is the batch's PACKED alive-table buffer (device-put alongside the
    row on the producing thread); at K>1 the raw ``(slot u32[n], flag
    u8[n])`` host arrays the dispatch-time merge consumes.  None when the
    scan counts no alive keys.
    """

    __slots__ = ("buf", "pairs")

    def __init__(self, buf, pairs=None):
        self.buf = buf
        self.pairs = pairs


def self_check_unpack(device=None) -> None:
    """One-time guard: pack a known batch on the host, unpack it on the
    device, and compare — catches any bitcast/byte-order mismatch before it
    could corrupt results.  The tables (quantile section included) and the
    HLL pairs cross the same bitcast boundary every scan's rows do."""
    from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec

    spec = SyntheticSpec(
        num_partitions=3, messages_per_partition=40, keys_per_partition=16, seed=11
    )
    batch = next(SyntheticSource(spec).batches(100))
    config = AnalyzerConfig(
        num_partitions=3,
        batch_size=128,
        enable_hll=True,
        hll_p=8,
        enable_quantiles=True,
    )
    buf = pack_batch(batch, config, use_native=False)
    expected = unpack_numpy(buf, config)
    got = jax.jit(lambda b: unpack_device(b, config))(
        jax.device_put(buf, device)
    )
    for name, exp in expected.items():
        if not np.array_equal(np.asarray(got[name]), np.asarray(exp)):
            raise RuntimeError(
                f"packed-transfer self-check failed on field {name!r}: "
                "device unpack disagrees with host layout (byte order?)"
            )


_checked_devices: "set[str]" = set()

#: The alive bitmap's popcount, one program a process: every audit builds a
#: backend of its own, and a jit per backend would build it again each time.
_alive_count = jax.jit(bitmap_popcount)


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
            _with_token(make_packed_step(config)),
            donate_argnums=self._donate,
        )
        # Superbatch dispatch layer (config.DispatchConfig): K packed
        # buffers per jitted scan dispatch, up to `depth` superbatches in
        # flight.  K=1 keeps the classic one-dispatch-per-batch path
        # (prepare launches the transfer itself) untouched.
        self.dispatch_config = dispatch if dispatch is not None else DispatchConfig()
        self.superbatch_k = self.dispatch_config.resolve(config.batch_size)
        self.dispatch_depth = self.dispatch_config.depth
        # Bounds the superbatches in flight, and at K=1 the batches staged
        # on the host that ``update`` places (each in-flight step holds
        # its input rows on the device).
        self._queue = DispatchQueue(self.dispatch_depth)
        # Alive tables (DESIGN.md §19): per-dispatch table capacities for
        # the two dispatch shapes this backend compiles.
        self._alive = config.count_alive_keys
        self._pair_cap1 = (
            pair_table_capacity(config, config.batch_size, 1)
            if self._alive
            else 0
        )
        if self.superbatch_k > 1:
            self._pair_cap_k = (
                pair_table_capacity(config, config.batch_size, self.superbatch_k)
                if self._alive
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
        self._queue.depth = depth

    def _pack_pairs(self, pair_lists, cap) -> np.ndarray:
        """Merge + pack a dispatch's pair table, booking the raw→emitted
        compaction split (never silent — the --stats ratio reads these)."""
        buf, raw, emitted = pack_pair_table(
            pair_lists, self.config, cap, use_native=self.use_native
        )
        obs_metrics.ALIVE_PAIRS_RAW.inc(raw)
        obs_metrics.ALIVE_PAIRS_EMITTED.inc(emitted)
        return buf

    def _stage(self, buf, pairs, place: bool) -> StagedBatch:
        """A packed row and its raw alive pairs in their staged form.  At
        K=1 the pairs are packed into the batch's own table here, and with
        ``place`` both buffers start their host→device transfer; at K>1
        both stay raw on the host for the dispatch-time stack and merge."""
        if self.superbatch_k > 1:
            return StagedBatch(buf, pairs)
        staged = StagedBatch(
            buf,
            self._pack_pairs([pairs], self._pair_cap1)
            if self._alive else None,
        )
        return self.place(staged) if place else staged

    def place(self, staged: StagedBatch) -> StagedBatch:
        """Start the host→device transfer of a batch staged with
        ``place=False`` (``update`` does, at dispatch).  At K>1 rows cross
        in their superbatch's stack at dispatch time, so the batch stays
        as it is."""
        if self.superbatch_k > 1:
            return staged
        return StagedBatch(
            jax.device_put(staged.buf, self.device),
            None if staged.pairs is None
            else jax.device_put(staged.pairs, self.device),
        )

    def prepare(self, batch: RecordBatch, place: bool = True) -> StagedBatch:
        """Pack (and, at superbatch K=1, start the host→device transfer
        for) a batch that will be fed to ``update``/``update_superbatch``
        later.  Safe to call from a worker thread (jax dispatch is
        thread-safe; the packers are pure numpy/C++).  At K>1 the buffer
        stays on the host: it is copied into its superbatch row at fan-in
        time and crosses in the stack's single large transfer.  Alive-key
        scans stage the batch's pairs alongside: packed + put at
        K=1 (the whole table is this batch's), raw host arrays at K>1
        (the dispatch-time merge spans the superbatch).  ``place=False``
        packs without the transfer: ``update`` starts it at dispatch,
        where the dispatch depth bounds the staged rows on the device."""
        buf = pack_batch(batch, self.config, use_native=self.use_native)
        pairs = (
            batch_alive_pairs(batch, self.config, self.use_native)
            if self._alive else None
        )
        return self._stage(buf, pairs, place)

    def make_fused_sink(self, dense_of, place: bool = True):
        """A packing.FusedPackSink staged for this backend: fused rows
        come out exactly like ``prepare``'s output (async ``device_put``
        on the producing thread at K=1 unless ``place`` is False; host
        buffer at K>1, copied into its superbatch stager row at fan-in
        time).  One sink per ingest stream — sinks are single-threaded
        state."""
        from kafka_topic_analyzer_tpu.packing import FusedPackSink

        def stage(buf, pairs=None):
            return self._stage(buf, pairs, place)

        return FusedPackSink(
            self.config, self.config.batch_size, dense_of, stage=stage
        )

    def update(self, batch: "RecordBatch | StagedBatch") -> None:
        if isinstance(batch, StagedBatch):
            # Staged with ``place=False`` (the ingest streams of a
            # multi-stream scan): placed here, once fewer than
            # ``dispatch_depth`` such steps are in flight, so the device
            # holds no more staged rows than the one-stream path.
            on_host = isinstance(batch.buf, np.ndarray)
            if on_host:
                self._queue.throttle()
                batch = self.place(batch)
            obs_metrics.WIRE_BYTES.inc(int(batch.buf.nbytes))
            if self._alive:
                obs_metrics.WIRE_BYTES.inc(int(batch.pairs.nbytes))
                self.state, token = self._step(
                    self.state, batch.buf, batch.pairs
                )
            else:
                self.state, token = self._step(self.state, batch.buf)
            if on_host:
                self._queue.launched(token, 1)
            return
        buf = pack_batch(batch, self.config, use_native=self.use_native)
        obs_metrics.WIRE_BYTES.inc(int(buf.nbytes))
        if self._alive:
            pairbuf = self._pack_pairs(
                [batch_alive_pairs(batch, self.config, self.use_native)],
                self._pair_cap1,
            )
            obs_metrics.WIRE_BYTES.inc(int(pairbuf.nbytes))
            self.state, _ = self._step(
                self.state,
                jax.device_put(buf, self.device),
                jax.device_put(pairbuf, self.device),
            )
            return
        self.state, _ = self._step(
            self.state, jax.device_put(buf, self.device)
        )

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
                if self._alive and item.pairs is not None:
                    pair_lists.append(item.pairs)
            else:
                pack_batch(
                    item, self.config, use_native=self.use_native, out=rows[i]
                )
                if self._alive:
                    pair_lists.append(
                        batch_alive_pairs(item, self.config, self.use_native)
                    )
        for i in range(len(staged), k):
            np.copyto(rows[i], self._empty_packed())
        obs_metrics.WIRE_BYTES.inc(int(rows.nbytes))
        bufs = jax.device_put(rows, self.device)
        if self._alive:
            # LWW-merge the superbatch's pairs in fold order into ONE
            # bounded table — the device applies it once after the scan
            # (identity-padded tail rows contribute no pairs).
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
        # Retire every in-flight step first, so the latency histogram
        # closes and the reduce span times the popcount alone.
        self.block_until_ready()
        # The alive bitmap stays on the device: only its popcount crosses.
        count = None
        if self.state.alive is not None:
            with obs_trace.maybe_span("finalize.reduce", cat="finalize"):
                count = jax.block_until_ready(
                    _alive_count(self.state.alive.words)
                )
        with obs_trace.maybe_span("finalize.device_get", cat="finalize"):
            host_state, count = jax.tree.map(
                np.asarray,
                jax.device_get(
                    (dataclasses.replace(self.state, alive=None), count)
                ),
            )
        obs_metrics.FINALIZE_D2H_BYTES.inc(
            sum(leaf.nbytes for leaf in jax.tree.leaves((host_state, count)))
        )
        with obs_trace.maybe_span("finalize.host", cat="finalize"):
            return metrics_from_state(
                host_state, self.config, self.init_now_s,
                alive_keys=None if count is None else int(count),
            )
