"""Share of the traced span in which the device was idle while the drive
loop waited for ingest and an ingest thread was in ``kta.decode_pack``
(or its ``kta.stage`` child; benchmark/host_spans.py).  Reads
``run.host_spans``; None where the run has none."""


def read(run):
    h = getattr(run, "host_spans", None)
    return None if h is None else h.share("decode_pack")
