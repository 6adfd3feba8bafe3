"""The harness finds every cell, configuration, traffic mix and metric by
name, and a cell is added by writing files alone."""

import json
import os

import pytest

from benchmark import generator, manifest
from benchkit import REPO, copy_with_tiny_cell

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    DOC = json.load(_f)


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_each_cell_loads_from_its_files(cell):
    c = manifest.load_cell(REPO, cell)
    entry = next(w for w in DOC["workloads"] if w["name"] == cell)
    assert c.config["name"] == entry["config"]
    assert c.chips == entry["chips"] in (1, 4)
    assert isinstance(c.traffic["flags"], list)
    kinds = {m.kind for m in c.metrics}
    assert kinds == {"end_to_end", "per_layer"}
    assert "setup_s" in {m.name for m in c.metrics}
    assert all(callable(m.read) for m in c.metrics)


@pytest.mark.parametrize("cfg", DOC["configs"], ids=lambda c: c["name"])
def test_config_files_state_what_the_manifest_says(cfg):
    with open(os.path.join(REPO, cfg["file"])) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    for key in data["reduced"]:
        assert data[key] < data["published"][key]
    # the generator's optional key, where a configuration states it
    assert data.get("compression", "none") in generator.CODECS


def test_config_guarantees_are_the_cli_defaults():
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig

    d = AnalyzerConfig(num_partitions=1)
    for cfg in DOC["configs"]:
        with open(os.path.join(REPO, cfg["file"])) as f:
            data = json.load(f)
        assert (data["alive_bitmap_bits"], data["hll_p"],
                data["quantile_alpha"], data["quantile_buckets"]) == (
            d.alive_bitmap_bits, d.hll_p, d.quantile_alpha,
            d.quantile_buckets)


def test_a_cell_is_added_by_files_only(tmp_path):
    before = {p: open(os.path.join(REPO, "benchmark", p), "rb").read()
              for p in os.listdir(os.path.join(REPO, "benchmark"))
              if p.endswith(".py")}
    root = copy_with_tiny_cell(str(tmp_path))
    cell = manifest.load_cell(root, "tiny-alive")
    assert cell.config["records"] == 4000
    assert "-c" in cell.traffic["flags"]
    added = next(m for m in cell.metrics if m.name == "audits_run")
    assert added.kind == "per_layer"
    assert added.read(type("R", (), {"audits": [1, 2]})()) == 2.0
    for p, body in before.items():
        with open(os.path.join(root, "benchmark", p), "rb") as f:
            assert f.read() == body
    # the other cells do not report the new metric
    other = manifest.load_cell(root, DOC["workloads"][0]["name"])
    assert "audits_run" not in {m.name for m in other.metrics}


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell(REPO, "no-such-cell")
    root = copy_with_tiny_cell(str(tmp_path))
    os.remove(os.path.join(root, "benchmark", "metrics", "audits_run.py"))
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell(root, "tiny-alive")


def test_peaks_table_has_its_source():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        doc = json.load(f)
    assert "TPU v5e" in doc["source"]
    v5e = manifest.peaks(REPO)["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
