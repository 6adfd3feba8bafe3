"""Seconds waiting for the first bytes of broker responses (io/kafka_wire.py
``read_response``), ``kta_fetch_first_byte_seconds_total``, per 10^6 records
folded: the part of ``fetch_wait_s_per_Mrec`` that is the broker's answer
time; the rest is transfer."""


def read(run):
    s = run.counter("kta_fetch_first_byte_seconds_total")
    n = run.counter("kta_scan_records_total")
    return s / (n / 1e6) if s is not None and n else None
