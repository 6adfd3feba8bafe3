"""Milliseconds per audit in the mesh backend's collective merge at
finalize (parallel/sharded.py, the ``finalize.merge`` span):
``kta_mesh_merge_seconds_total`` over the audits run.  None where the
program books no such counter."""


def read(run):
    s = run.counter("kta_mesh_merge_seconds_total")
    if s is None or not run.audits:
        return None
    return 1e3 * s / len(run.audits)
