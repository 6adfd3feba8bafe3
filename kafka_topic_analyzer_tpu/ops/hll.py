"""HyperLogLog distinct-key sketch (device update, host estimate).

New capability replacing the reference's O(2^32)-bit alive bitset with an
O(2^p)-register sketch for distinct-key counting (BASELINE.json north star).
The register update is a masked scatter-max — associative and commutative, so
per-device registers merge with an elementwise max (``pmax`` over ICI), the
streaming analog of the reference's single-threaded ``BitSet`` (SURVEY.md
§5.7).

Estimator: Ertl's improved raw estimator ("New cardinality estimation
algorithms for HyperLogLog sketches", Ertl 2017, §2.3) — unbiased over the
whole cardinality range from the register histogram alone, with no
linear-counting switchover and no bias valley just past it (the classic
Flajolet estimator's weak band is exactly where mid-size topics land).
Standard error ~1.04/sqrt(2^p): 0.41% at the default p=16, comfortably
inside BASELINE.md's ≤1% budget rather than riding its 2σ edge (r3's
recorded 1.6% on config 3 was a ~2σ draw at p=14).
"""

from __future__ import annotations

import numpy as np

from kafka_topic_analyzer_tpu.jax_support import jnp


def hll_apply(regs, idx, rho):
    """Apply host pre-split HLL updates (packing.py::hll_idx_rho_numpy) to
    the single global register row ``int32[1, m]``: one scatter-max of rho.
    Masked records carry rho=0, a no-op under max."""
    rows, m = regs.shape
    return (
        regs.reshape(-1).at[idx.astype(jnp.int32)]
        .max(rho.astype(jnp.int32))
        .reshape(rows, m)
    )


def hll_apply_flat(regs, idx32, rho):
    """Apply flat HLL pairs: ``idx32`` already encodes ``row << p |
    bucket`` (packing.py's per-partition pair mode — the register row
    rides inside the index).  One scatter-max into the flattened register
    file; masked records carry (0, 0), a no-op under max."""
    rows, m = regs.shape
    return (
        regs.reshape(-1)
        .at[idx32.astype(jnp.int64)]
        .max(rho.astype(jnp.int32))
        .reshape(rows, m)
    )


def _sigma(x: float) -> float:
    """Ertl 2017 eq. (14): power series for the small-cardinality
    (register-value-0) term.  Converges in <60 iterations for float64."""
    if x == 1.0:
        return float("inf")
    y = 1.0
    z = x
    while True:
        x = x * x
        z_prev = z
        z = z + x * y
        y = 2.0 * y
        if z == z_prev:
            return z


def _tau(x: float) -> float:
    """Ertl 2017 eq. (23): power series for the saturated-register
    (register-value-q+1) term."""
    if x == 0.0 or x == 1.0:
        return 0.0
    y = 1.0
    z = 1.0 - x
    while True:
        x = np.sqrt(x)
        z_prev = z
        y = 0.5 * y
        z = z - (1.0 - x) ** 2 * y
        if z == z_prev:
            return z / 3.0


def hll_estimate(regs: np.ndarray) -> float:
    """Host-side cardinality estimate from final registers: Ertl's
    improved raw estimator (2017, algorithm 6) over the register
    histogram.  Unbiased across the full range — no linear-counting
    branch, no empirical bias tables."""
    regs = np.asarray(regs)
    m = regs.shape[0]
    if m & (m - 1):
        raise ValueError("register count must be a power of two")
    p = int(m).bit_length() - 1
    q = 64 - p  # max rho is q + 1 (the packers cap it at 64 - p + 1)
    counts = np.bincount(regs.astype(np.int64), minlength=q + 2)
    z = m * _tau(1.0 - counts[q + 1] / m)
    for k in range(q, 0, -1):
        z = 0.5 * (z + float(counts[k]))
    z = z + m * _sigma(counts[0] / m)
    alpha_inf = 0.5 / np.log(2.0)
    return float(alpha_inf * m * m / z)
