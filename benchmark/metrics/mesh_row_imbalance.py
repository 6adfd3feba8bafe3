"""The heaviest data row's records over the mean row's, in a sharded
(``--mesh``) scan: ``kta_mesh_row_records_total{row}``.  The rows step in
lockstep, so 1 is an even split and the heaviest row sets the pace.
None where the program books no such counter."""


def read(run):
    rows = [s["value"] for s in run.deltas.get(
        "kta_mesh_row_records_total", [])]
    total = sum(rows)
    if not rows or total <= 0:
        return None
    return max(rows) / (total / len(rows))
