"""Ingest streams an audit opened (engine.py): ``kta_ingest_streams_total``
over the audits run.  None where the program books no such counter."""


def read(run):
    n = run.counter("kta_ingest_streams_total")
    if n is None or not run.audits:
        return None
    return n / len(run.audits)
