"""Share of the traced span in which the device was idle while the drive
loop waited for ingest and an ingest thread was in ``kta.fetch`` and not
decoding (benchmark/host_spans.py).  Reads ``run.host_spans``, the
attribution of the run's trace; None where the run has none."""


def read(run):
    h = getattr(run, "host_spans", None)
    return None if h is None else h.share("fetch")
