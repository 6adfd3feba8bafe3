"""Exact per-partition extremes merge, in JAX, and the state sentinels.

This is the device half of ``MessageMetrics::handle_message``
(src/metric.rs:207-252): the host packers reduce each batch to a ``[P, 7]``
counter-delta table and per-partition timestamp/size extremes (packing.py),
and the device merges those tables — the counters by a plain add in the
step (backends/step.py), the extremes by the elementwise min/max below.

Semantics preserved exactly (SURVEY.md §3.4):
- "alive" = record with non-null value, counted per record;
- key bytes count only when the key is non-null; value bytes only when the
  value is non-null;
- min/max message size is key_len+value_len and *excludes tombstones*
  (src/metric.rs:249-251);
- timestamps participate at second granularity, missing timestamps as 0.
"""

from __future__ import annotations

from kafka_topic_analyzer_tpu.jax_support import jnp

#: Sentinel for "never seen" minima (mapped to the reference's u64::MAX
#: reporting rule at finalize, src/metric.rs:177-183).
I64_MAX = jnp.iinfo(jnp.int64).max
I64_MIN = jnp.iinfo(jnp.int64).min


def extremes_update(
    earliest_s,     # int64[P], I64_MAX sentinel when unset
    latest_s,       # int64[P], I64_MIN sentinel
    smallest,       # int64[P], I64_MAX sentinel
    largest,        # int64[P]
    ts_min,         # int64[P], host-pre-reduced (packing.ts_minmax_table)
    ts_max,         # int64[P]
    sz_min,         # int64[P], host-pre-reduced (packing.sz_minmax_table)
    sz_max,         # int64[P]
):
    """Merge per-partition timestamp and message-size extremes.

    Both arrive already reduced per partition by the host — min/max is
    associative, so elementwise-merging batch tables is exact; tombstone
    exclusion for sizes happens at table build (src/metric.rs:249-251).
    No per-record work remains here.
    """
    return (
        jnp.minimum(earliest_s, ts_min),
        jnp.maximum(latest_s, ts_max),
        jnp.minimum(smallest, sz_min),
        jnp.maximum(largest, sz_max),
    )
