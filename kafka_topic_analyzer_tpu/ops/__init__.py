"""Compute kernels (numpy host-side + jax device-side).

The reference computes everything one message at a time behind a virtual
``MetricHandler`` dispatch (``src/kafka.rs:18-20``, ``src/metric.rs:206-253``).
Here every kernel is a batched reduction over a structure-of-arrays
`RecordBatch` or a merge of host-reduced tables, shaped so XLA can fuse it.
"""
