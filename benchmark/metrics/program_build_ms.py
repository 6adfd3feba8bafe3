"""Milliseconds per audit JAX spends building device programs (tracing,
lowering, and compiling or loading from the compilation cache):
``kta_program_build_seconds_total`` over the audits run."""


def read(run):
    s = run.counter("kta_program_build_seconds_total")
    if s is None or not run.audits:
        return None
    return 1e3 * s / len(run.audits)
