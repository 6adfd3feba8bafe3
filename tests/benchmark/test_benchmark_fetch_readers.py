"""Readers of the fetch layer's receive-ahead and buffer-ring shares."""

import pytest

from benchmark import harness, manifest
from benchkit import REPO

READERS = ("fetch_ahead_share", "fetch_buffer_reuse_share")


def _run(deltas):
    return harness.Run(audits=[], records_per_audit=0, setup_s=0.0,
                       peak_bytes=0, deltas=deltas)


def _read(name, deltas):
    return manifest.load_reader(REPO, name)(_run(deltas))


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counter_reads_nothing(name):
    assert _read(name, {}) is None
    assert _read(name, {"kta_fetch_seconds_total": [
        {"labels": {}, "value": 1.0}]}) is None


@pytest.mark.parametrize("name,counter,num,other", [
    ("fetch_ahead_share", "kta_fetch_ahead_total", "ahead", "inline"),
    ("fetch_buffer_reuse_share", "kta_fetch_buffers_total", "reused",
     "fresh"),
])
def test_share_of_the_two_outcomes(name, counter, num, other):
    deltas = {counter: [
        {"labels": {"outcome": num}, "value": 30.0},
        {"labels": {"outcome": other}, "value": 10.0},
        # Drained speculation counts in neither side of the share.
        {"labels": {"outcome": "discarded"}, "value": 5.0},
    ]}
    assert _read(name, deltas) == pytest.approx(0.75)
    deltas[counter][0]["value"] = 0.0
    assert _read(name, deltas) == 0.0
    assert _read(name, {counter: [
        {"labels": {"outcome": other}, "value": 0.0}]}) is None


def test_both_are_fetch_layer_counters_in_every_cell():
    import json
    import os

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entries = {m["name"]: m for m in doc["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert (m["layer"], m["source"], m["moves"]) == (
            "fetch", "program_counter", "audit_records_per_s")
        assert "workloads" not in m
