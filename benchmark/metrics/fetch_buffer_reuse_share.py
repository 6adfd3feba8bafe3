"""Share of response receive buffers a wire stream reused from its ring
(io/kafka_wire.py), ``kta_fetch_buffers_total``: ``reused`` over
``reused`` plus ``fresh``.  None where the program books no such
counter."""


def read(run):
    reused = run.counter("kta_fetch_buffers_total", outcome="reused")
    fresh = run.counter("kta_fetch_buffers_total", outcome="fresh")
    total = (reused or 0.0) + (fresh or 0.0)
    return (reused or 0.0) / total if total else None
