"""The reader of the ingest streams an audit opens: nothing from a program
without the counter, the per-audit count from one with it, and the count
``auto`` resolves to from a tiny cell run through the harness."""

import json
import os

import pytest

from benchmark import harness, manifest
from benchkit import REPO, compile_cache, copy_with_tiny_cell, TINY_CONFIG


def _run(deltas, audits=1):
    return harness.Run(
        audits=[harness.Audit(f"audit-{i:04d}", 0.0, 1.0, 0, "")
                for i in range(audits)],
        records_per_audit=1 << 24, setup_s=0.0, peak_bytes=0, deltas=deltas)


def _read(run):
    return manifest.load_reader(REPO, "ingest_streams")(run)


def _streams(n):
    return {"kta_ingest_streams_total": [{"labels": {}, "value": n}]}


def test_a_program_without_the_counter_reads_nothing():
    assert _read(_run({})) is None
    assert _read(_run({"kta_ingest_resolved_workers": [
        {"labels": {}, "value": 10.0}]})) is None
    assert _read(_run(_streams(10.0), audits=0)) is None


@pytest.mark.parametrize("total,audits,per_audit", [
    (10.0, 1, 10.0), (30.0, 3, 10.0), (48.0, 4, 12.0), (4.0, 4, 1.0)])
def test_streams_per_audit(total, audits, per_audit):
    assert _read(_run(_streams(total), audits)) == per_audit


def test_a_decode_and_pack_counter_in_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    m = next(m for m in doc["per_layer"] if m["name"] == "ingest_streams")
    assert (m["layer"], m["source"], m["moves"], m["better"]) == (
        "decode and pack", "program_counter", "audit_records_per_s",
        "higher")
    assert "workloads" not in m
    assert doc["per_layer"][-1] is m


def test_a_tiny_cell_opens_the_streams_auto_resolves_to(tmp_path,
                                                        monkeypatch):
    from kafka_topic_analyzer_tpu.config import IngestConfig

    runs = []

    class Captured(harness.Run):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Captured)
    root = copy_with_tiny_cell(str(tmp_path))
    with compile_cache(str(tmp_path / "jax_cache")):
        result = harness.run(root, "tiny-alive", 2**31 + 23, 0.5, False,
                             chip_check=False)
    assert result["correct"] is True
    (run,) = runs
    assert _read(run) == IngestConfig("auto").resolve(
        TINY_CONFIG["partitions"])
