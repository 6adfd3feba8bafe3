"""MiB of analyzer state copied device to host at finalize, per audit:
``kta_finalize_d2h_bytes_total`` over the audits run."""


def read(run):
    b = run.counter("kta_finalize_d2h_bytes_total")
    if b is None or not run.audits:
        return None
    return b / 2**20 / len(run.audits)
