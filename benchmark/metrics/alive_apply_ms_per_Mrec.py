"""Device milliseconds of the ops under the step's ``kta.alive`` scope
(backends/step.py: the alive bitmap's mask or pair apply) per 10^6
records folded while traced.  Reads ``run.host_spans``; None where the
run has none, or no op carries the scope (cells without ``-c``)."""


def read(run):
    h = getattr(run, "host_spans", None)
    s = None if h is None else h.scope_s.get("kta.alive")
    if not s or not run.trace_records:
        return None
    return 1e3 * s / (run.trace_records / 1e6)
