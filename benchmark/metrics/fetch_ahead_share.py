"""Share of fetch responses a wire stream took from its background
receiver (io/kafka_wire.py), ``kta_fetch_ahead_total``: ``ahead`` over
``ahead`` plus ``inline``.  None where the program books no such
counter."""


def read(run):
    ahead = run.counter("kta_fetch_ahead_total", outcome="ahead")
    inline = run.counter("kta_fetch_ahead_total", outcome="inline")
    total = (ahead or 0.0) + (inline or 0.0)
    return (ahead or 0.0) / total if total else None
