"""Turn a (host-fetched) AnalyzerState into TopicMetrics."""

from __future__ import annotations

import numpy as np

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.ops.ddsketch import ddsketch_quantiles
from kafka_topic_analyzer_tpu.ops.hll import hll_estimate
from kafka_topic_analyzer_tpu.results import (
    QUANTILE_PROBS,
    QuantileSummary,
    TopicMetrics,
    finalize_extremes,
)


def metrics_from_state(
    state,
    config: AnalyzerConfig,
    init_now_s: int,
    alive_keys: "int | None" = None,
) -> TopicMetrics:
    """``state`` is an AnalyzerState whose leaves are host numpy arrays
    (already merged across devices if the run was sharded).  Its alive
    bitmap is not read: ``alive_keys`` is the bitmap's popcount, counted
    where the bitmap lives."""
    m = state.metrics
    # Per-partition extremes reduce to the reference's global lines.
    earliest, latest, smallest = finalize_extremes(
        int(np.min(m.earliest_s)),
        int(np.max(m.latest_s)),
        int(np.min(m.smallest)),
        init_now_s,
    )
    extremes = np.stack(
        [
            np.asarray(m.earliest_s),
            np.asarray(m.latest_s),
            np.asarray(m.smallest),
            np.asarray(m.largest),
        ],
        axis=1,
    )
    hll = None
    hll_pp = None
    if state.hll is not None:
        regs = np.asarray(state.hll.regs)
        # Global estimate from the union of rows (elementwise max is the HLL
        # merge); per-partition estimates from each row.
        hll = hll_estimate(regs.max(axis=0))
        if config.distinct_keys_per_partition:
            hll_pp = [hll_estimate(regs[r]) for r in range(regs.shape[0])]
    quantiles = None
    quantiles_pp = None
    if state.quantiles is not None:
        counts = np.asarray(state.quantiles.counts)
        # Global quantiles from the exact sum of rows (DDSketch merge = add).
        vals = ddsketch_quantiles(
            counts.sum(axis=0), QUANTILE_PROBS, config.quantile_gamma
        )
        quantiles = QuantileSummary(list(QUANTILE_PROBS), vals)
        if config.quantiles_per_partition:
            quantiles_pp = [
                QuantileSummary(
                    list(QUANTILE_PROBS),
                    ddsketch_quantiles(
                        counts[r], QUANTILE_PROBS, config.quantile_gamma
                    ),
                )
                for r in range(counts.shape[0])
            ]
    return TopicMetrics(
        partitions=list(range(config.num_partitions)),
        per_partition=np.asarray(m.per_partition),
        earliest_ts_s=earliest,
        latest_ts_s=latest,
        smallest_message=smallest,
        largest_message=int(np.max(m.largest)),
        overall_size=int(m.overall_size),
        overall_count=int(m.overall_count),
        alive_keys=alive_keys,
        distinct_keys_hll=hll,
        distinct_keys_hll_per_partition=hll_pp,
        quantiles=quantiles,
        quantiles_per_partition=quantiles_pp,
        per_partition_extremes=extremes,
        init_now_s=init_now_s,
    )
