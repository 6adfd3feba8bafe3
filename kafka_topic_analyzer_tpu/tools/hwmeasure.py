"""The two device measurements bench.py's breakdown reports: host->device
transfer bandwidth, and the device-resident analyzer-step rate with
donated state.
"""

from __future__ import annotations

import time


#: The size of the one timed put behind bench.py's `transfer_gbps`.
TRANSFER_MIB = 8


def transfer_gbps() -> float:
    """Time one synchronous ``TRANSFER_MIB`` device_put; returns GB/s."""
    import jax
    import numpy as np

    # Untimed warmup put: the process's first transfer pays one-time
    # allocator/stream setup, which would otherwise deflate the figure.
    jax.device_put(np.ones((1 << 16,), np.uint8)).block_until_ready()
    host = np.ones((TRANSFER_MIB << 20,), np.uint8)
    t0 = time.perf_counter()
    jax.device_put(host).block_until_ready()
    return round(TRANSFER_MIB / 1024 / (time.perf_counter() - t0), 4)


def timed_step_loop(config, feed, *, steps: int) -> dict:
    """Warmup-compile the packed analyzer step, then time `steps` steps
    with donated state, cycling `feed` (packed uint8 buffers already on
    the device).

    Returns {"msgs_per_sec", "compile_s", "state"} — rate uses
    config.batch_size records per step.
    """
    import jax

    from kafka_topic_analyzer_tpu.backends.tpu import make_packed_step
    from kafka_topic_analyzer_tpu.models.state import AnalyzerState

    state = AnalyzerState.init(config)
    step = jax.jit(make_packed_step(config), donate_argnums=(0,))

    # Compacted alive configs take a pair-table buffer per step.  The
    # feed is already-packed rows (no decoded batch to dedupe), so the
    # loop ships identity (empty) tables — the device cost is shape-
    # static under jit, so the timed rate still includes the full
    # per-dispatch pair-apply work.
    pair_feed = None
    if config.compact_alive:
        from kafka_topic_analyzer_tpu.packing import (
            pack_pair_table,
            pair_table_capacity,
        )

        cap = pair_table_capacity(config, config.batch_size, 1)
        # ONE shared buffer: the step never donates it, and a mask-form
        # table can be tens of MB — duplicating it per feed entry would
        # just pin device memory for identical bytes.
        pair_feed = jax.device_put(pack_pair_table([], config, cap)[0])

    def run(i, st):
        buf = feed[i % len(feed)]
        if pair_feed is not None:
            return step(st, buf, pair_feed)
        return step(st, buf)

    t0 = time.perf_counter()
    state = run(0, state)
    jax.block_until_ready(state)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(steps):
        state = run(i, state)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return {
        "msgs_per_sec": round(steps * config.batch_size / dt, 1),
        "compile_s": round(compile_s, 2),
        "state": state,
    }
