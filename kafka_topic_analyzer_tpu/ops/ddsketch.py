"""DDSketch message-size quantiles (host bucket rule, host estimate).

New capability (BASELINE.json config 4: p50/p99 payload-size percentiles over
1B mixed-size messages).  The classic t-digest keeps a variable-length list
of centroids — hostile to XLA's static shapes — so the TPU-native choice is
DDSketch (Masson et al., VLDB'19): fixed log-γ buckets, a guaranteed relative
error α, updates that are a bincount, and a merge that is plain vector
addition (the device adds the packers' bucket-count tables; ``psum`` over
ICI).  The independent referee is the
CPU oracle's exact size histogram (backends/cpu.py), which parity tests
compare against within 2α.

Bucket layout for non-negative integer sizes:
- bucket 0: size == 0 (possible: alive record with empty value and null key);
- bucket i in [1, nbuckets]: ceil(log_gamma(size)) == i-? (see code) — sizes
  up to gamma^nbuckets;
- bucket nbuckets+1: overflow.

Quantile answers carry relative error ≤ α (= ``quantile_alpha``).
"""

from __future__ import annotations

import functools

import numpy as np


def ddsketch_num_buckets(nbuckets: int) -> int:
    return nbuckets + 2  # zero bucket + log buckets + overflow


@functools.lru_cache(maxsize=8)
def ddsketch_edges(gamma: float, nbuckets: int) -> np.ndarray:
    """Integer bucket boundaries: ``edges[i]`` is the largest integer size
    assigned to log bucket ``i + 1``, i.e. ``floor(gamma^i)``.

    The bucket of an integer size ``s >= 1`` is
    ``searchsorted(edges, s, side='left') + 1`` — exactly the closed-form
    ``min k such that s <= gamma^(k-1)`` (``s <= gamma^i`` iff
    ``s <= floor(gamma^i)`` for integer ``s``), saturating naturally at
    the overflow bucket ``nbuckets + 1``.  This table is the ONE bucket
    rule shared by the numpy packer and the native C++ packers (packing.py
    / native/ingest.cpp): an integer comparison is exact everywhere, where
    a float ``ceil(log(s)/log(gamma))`` could round differently between
    two libms.  Cached per (gamma, nbuckets); the array is frozen because
    the native packers hold raw pointers into it.
    """
    powers = np.power(np.float64(gamma), np.arange(nbuckets, dtype=np.float64))
    # Clip before the int cast: an operator-supplied (alpha, nbuckets) pair
    # can push gamma^i past 2^63 (float inf → undefined int64 cast).  Any
    # edge above 2^62 is unreachable anyway (sizes are <= u16 + u32 bytes).
    edges = np.floor(np.minimum(powers, 2.0**62)).astype(np.int64)
    edges.setflags(write=False)
    return edges


def ddsketch_bucket_numpy(
    sizes: np.ndarray, gamma: float, nbuckets: int
) -> np.ndarray:
    """Host-side bucket index per size (the numpy packer's reduction):
    0 for size 0, the shared edge-table bucket otherwise."""
    idx = np.searchsorted(
        ddsketch_edges(gamma, nbuckets), sizes, side="left"
    ).astype(np.int64) + 1
    return np.where(sizes == 0, 0, idx)


def ddsketch_quantiles(counts: np.ndarray, probs, gamma: float) -> "list[float]":
    """Host-side quantile extraction from final bucket counts."""
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    out: "list[float]" = []
    if total == 0:
        return [float("nan") for _ in probs]
    cum = np.cumsum(counts)
    nbuckets = counts.shape[0] - 2
    for q in probs:
        rank = max(0, min(total - 1, int(np.ceil(q * total)) - 1))
        b = int(np.searchsorted(cum, rank + 1))
        if b == 0:
            out.append(0.0)
        elif b > nbuckets:
            out.append(float("inf"))
        else:
            # midpoint of (gamma^(b-2), gamma^(b-1)]: 2*gamma^(b-1)/(gamma+1)
            out.append(float(2.0 * gamma ** (b - 1) / (gamma + 1.0)))
    return out
