"""The CPU oracle's answer in the device path's terms.

``CpuExactBackend`` (backends/cpu.py) keeps EXACT distinct-key sets and an
exact size histogram; the device path reports HLL estimates and DDSketch
quantiles.  ``sketched`` turns the oracle's exact state into the sketches
the published algorithms give for it — registers from the key hashes by
the HLL rule written out here, bucket counts from the sizes by the shared
integer edge table — so a device scan's whole document, sketch lines
included, must EQUAL the oracle's, with no tolerance.  Nothing here uses
the packers or the device step.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.ops.ddsketch import (
    ddsketch_edges,
    ddsketch_num_buckets,
    ddsketch_quantiles,
)
from kafka_topic_analyzer_tpu.ops.fnv import splitmix64
from kafka_topic_analyzer_tpu.ops.hll import hll_estimate
from kafka_topic_analyzer_tpu.results import QUANTILE_PROBS, QuantileSummary


def _registers(hashes, p: int) -> np.ndarray:
    """HLL registers of a set of 64-bit key hashes: bucket = the top p
    bits of splitmix64(h), rho = leading zeros of the rest + 1."""
    regs = np.zeros(1 << p, dtype=np.int64)
    for h in hashes:
        x = splitmix64(int(h))
        rest = (x << p) & ((1 << 64) - 1)
        rho = 64 - p + 1 if rest == 0 else 64 - rest.bit_length() + 1
        idx = x >> (64 - p)
        regs[idx] = max(regs[idx], rho)
    return regs


def _quantiles(counts: np.ndarray, gamma: float) -> QuantileSummary:
    return QuantileSummary(
        list(QUANTILE_PROBS), ddsketch_quantiles(counts, QUANTILE_PROBS, gamma)
    )


def sketched(backend: CpuExactBackend, metrics):
    """``metrics`` (the oracle's finalize) with its exact distinct counts
    and quantiles replaced by the sketches of the oracle's exact state."""
    cfg = backend.config
    out = dataclasses.replace(
        metrics,
        distinct_keys_exact=None,
        distinct_keys_exact_per_partition=None,
    )
    if cfg.enable_hll:
        rows = [_registers(s, cfg.hll_p) for s in backend._seen_keys]
        out.distinct_keys_hll = hll_estimate(np.max(rows, axis=0))
        if cfg.distinct_keys_per_partition:
            out.distinct_keys_hll_per_partition = [
                hll_estimate(r) for r in rows
            ]
    if cfg.enable_quantiles:
        nb = ddsketch_num_buckets(cfg.quantile_buckets)
        edges = ddsketch_edges(cfg.quantile_gamma, cfg.quantile_buckets)
        counts = np.zeros((cfg.num_partitions, nb), dtype=np.int64)
        for key, n in backend._size_counts.items():
            size = key & 0xFFFFFFFF
            bucket = (
                0 if size == 0
                else int(np.searchsorted(edges, size, side="left")) + 1
            )
            counts[key >> 32, bucket] += n
        out.quantiles = _quantiles(counts.sum(axis=0), cfg.quantile_gamma)
        if cfg.quantiles_per_partition:
            out.quantiles_per_partition = [
                _quantiles(c, cfg.quantile_gamma) for c in counts
            ]
    return out
