"""The four-chip mesh cell: its readers on hand-built runs, and a tiny copy
of the cell run through the harness on four virtual CPU devices against
the plain reference."""

import json
import os

import pytest

from benchmark import harness, manifest
from benchkit import REPO, compile_cache, copy_with_tiny_cell

READERS = ("mesh_merge_ms", "mesh_row_imbalance")
#: What the mesh cell's configuration may state apart from the topic of
#: the one-chip changelog cells, which it audits unchanged.
DEPLOYMENT_KEYS = {"name", "source", "deployment", "assumed"}
#: The tiny cell keeps ``tiny-changelog``'s shape with 10 partitions, so
#: that round-robin puts 3/3/2/2 of them on the four rows as in the cell.
#: Its bitmap has 2^20 slots: every virtual device holds one, and the
#: merge gathers all four on each.
TINY_MESH_CONFIG = {"name": "tiny-changelog-mesh4", "partitions": 10,
                    "alive_bitmap_bits": 20}
TINY_MESH_FLAGS = ["-c", "--distinct-keys", "--quantiles", "--mesh", "4",
                   "--batch-size", "1024", "--alive-bitmap-bits", "20"]
BATCH = 1024


def _run(deltas, audits=1, records_per_audit=1 << 24):
    return harness.Run(
        audits=[harness.Audit(f"audit-{i:04d}", 0.0, 1.0, 0, "")
                for i in range(audits)],
        records_per_audit=records_per_audit, setup_s=0.0, peak_bytes=0,
        deltas=deltas)


def _read(name, run, root=REPO):
    return manifest.load_reader(root, name)(run)


def _merge_seconds(s):
    return {"kta_mesh_merge_seconds_total": [{"labels": {}, "value": s}]}


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_nothing(name):
    assert _read(name, _run({})) is None
    assert _read(name, _run({"kta_scan_records_total": [
        {"labels": {}, "value": 1e6}]})) is None


def test_merge_ms_is_per_audit():
    assert _read("mesh_merge_ms", _run(_merge_seconds(0.09), audits=3)) \
        == pytest.approx(30.0)


def test_imbalance_of_a_known_row_split():
    rows = {"kta_mesh_row_records_total": [
        {"labels": {"row": str(r)}, "value": v}
        for r, v in enumerate((300.0, 300.0, 200.0, 200.0))]}
    assert _read("mesh_row_imbalance", _run(rows)) == pytest.approx(1.2)
    even = {"kta_mesh_row_records_total": [
        {"labels": {"row": str(r)}, "value": 5.0} for r in range(4)]}
    assert _read("mesh_row_imbalance", _run(even)) == 1.0


def test_the_mesh_cell_audits_the_changelog_topic_on_four_chips():
    cell = manifest.load_cell(REPO, "changelog-alive-mesh4")
    ref = manifest.load_cell(REPO, "changelog-alive")
    topic = {k: v for k, v in cell.config.items() if k not in DEPLOYMENT_KEYS}
    assert topic == {k: v for k, v in ref.config.items()
                     if k not in DEPLOYMENT_KEYS}
    flags = cell.traffic["flags"]
    assert int(flags[flags.index("--mesh") + 1]) == cell.chips == 4
    assert flags[:flags.index("--mesh")] == ref.traffic["flags"]
    assert cell.traffic["warmup_records"] == ref.traffic["warmup_records"]


def _copy_with_tiny_mesh_cell(dest):
    """The tiny changelog cell's copy of the benchmark, with a four-chip
    cell ``tiny-mesh4`` added by files and entries, and listed for the
    mesh metrics."""
    from benchkit import TINY_CONFIG, TINY_TRAFFIC, add_cell

    root = copy_with_tiny_cell(dest)
    doc = add_cell(root, "tiny-mesh4", {**TINY_CONFIG, **TINY_MESH_CONFIG},
                   {**TINY_TRAFFIC, "flags": TINY_MESH_FLAGS}, chips=4)
    for m in doc["per_layer"]:
        if m["name"] in READERS:
            m["workloads"].append("tiny-mesh4")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


@pytest.fixture(scope="module")
def tiny_mesh(tmp_path_factory):
    root = _copy_with_tiny_mesh_cell(str(tmp_path_factory.mktemp("mesh")))
    with compile_cache(str(tmp_path_factory.mktemp("jax_cache"))):
        yield root


# Seed 2**31 + 11: the rows hold 1,152 / 976 / 1,357 / 515 of the 4,000
# records, at 1,024 a batch, so rows 1 and 3 run dry after one round and
# rows 0 and 2 after two (asserted below).
@pytest.mark.parametrize("seed", [2**31 + 11, 2**33 + 5])
def test_tiny_mesh_cell_matches_the_reference(tiny_mesh, monkeypatch, seed):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    runs = []

    class Captured(harness.Run):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Captured)
    result = harness.run(tiny_mesh, "tiny-mesh4", seed, 0.5, False,
                         chip_check=False)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert {k: v["value"] for k, v in result["checks"].items()} == {
        "report_lines_wrong": 0.0, "hll_rel_gap": 0.0,
        "quantile_rel_gap": 0.0}

    (run,) = runs
    # Rows of other scans in this process book nothing in the window.
    rows = {s["labels"]["row"]: s["value"] / len(run.audits)
            for s in run.deltas["kta_mesh_row_records_total"] if s["value"]}
    assert sorted(rows) == ["0", "1", "2", "3"]
    assert sum(rows.values()) == pytest.approx(4000)
    if seed == 2**31 + 11:
        assert [-(-rows[r] // BATCH) for r in "0123"] == [2, 1, 2, 1]

    values = {name: _read(name, run, tiny_mesh) for name in READERS}
    assert values["mesh_row_imbalance"] >= 1
    assert values["mesh_merge_ms"] > 0
