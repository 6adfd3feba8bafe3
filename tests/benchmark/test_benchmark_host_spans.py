"""Idle gaps put down to host stages (benchmark/host_spans.py), and the
readers of the metrics built on them and on the new program counters."""

import gzip
import json
import os

import pytest

from benchmark import harness, host_spans, manifest
from benchmark.host_spans import CATEGORIES, Op, Span
from benchkit import REPO

SAMPLE = os.path.join(REPO, "benchmark", "trace_sample",
                      "host_spans.json.gz")
NEW_METRICS = ("idle_share_fetch", "idle_share_decode_pack",
               "fetch_first_byte_s_per_Mrec", "program_build_ms",
               "alive_apply_ms_per_Mrec", "finalize_d2h_MiB")
DRIVE, INGEST = 1, 2


def _sums_to_the_span(a):
    assert all(v >= 0 for v in a.idle_s.values())
    assert set(a.idle_s) == set(CATEGORIES)
    assert sum(a.idle_s.values()) + a.busy_s == pytest.approx(
        a.span_s, abs=1e-6)


def test_a_fetch_gap_overlapping_decode_pack_is_decode_pack():
    spans = [Span(DRIVE, "kta.ingest", 0, 100),
             Span(INGEST, "kta.fetch", 10, 60),
             Span(INGEST, "kta.fetch.first_byte", 10, 20),
             Span(INGEST, "kta.decode_pack", 40, 80),
             Span(INGEST, "kta.stage", 70, 80)]
    a = host_spans.attribute(spans, [Op("fusion", 90, 100)])
    assert a.span_s == pytest.approx(100e-9)
    assert a.busy_s == pytest.approx(10e-9)
    assert a.idle_s["fetch"] == pytest.approx(30e-9)         # 10..40
    assert a.idle_s["decode_pack"] == pytest.approx(40e-9)   # 40..80
    assert a.idle_s["ingest_other"] == pytest.approx(20e-9)  # 0..10, 80..90
    _sums_to_the_span(a)


def test_drive_stages_come_first_then_outside_scan_then_the_rest():
    spans = [Span(DRIVE, "kta.dispatch", 0, 10),
             Span(INGEST, "kta.decode_pack", 0, 30),  # not waited on
             Span(DRIVE, "kta.finalize", 20, 40),
             Span(DRIVE, "kta.finalize.device_get", 20, 30),
             Span(DRIVE, "kta.ingest", 60, 70),
             Span(DRIVE, "kta.dispatch", 75, 80),
             Span(DRIVE, "kta.scan", 55, 100)]
    a = host_spans.attribute(spans, [Op("fusion.1", 76, 79, "kta.alive")])
    assert a.span_s == pytest.approx(100e-9)
    assert a.idle_s["dispatch"] == pytest.approx((10 + 2) * 1e-9)
    assert a.idle_s["finalize"] == pytest.approx(20e-9)
    # finalize ends a scan; the next scan's kta.scan starts at 55
    assert a.idle_s["outside_scan"] == pytest.approx(15e-9)  # 40..55
    assert a.idle_s["ingest_other"] == pytest.approx(10e-9)  # 60..70
    # 10..20 between two stages; 55..60, 70..75, 80..100 inside the scan
    assert a.idle_s["unattributed"] == pytest.approx(40e-9)
    assert a.scope_s == {"kta.alive": pytest.approx(3e-9)}
    _sums_to_the_span(a)


def test_the_span_is_what_the_host_record_covers():
    spans = [Span(DRIVE, "kta.ingest", 1000, 2000),
             Span(INGEST, "kta.fetch", 0, 3000)]
    ops = [Op("copy", 0, 500), Op("fusion", 1900, 2500)]
    a = host_spans.attribute(spans, ops)
    assert a.span_s == pytest.approx(1000e-9)
    assert a.busy_s == pytest.approx(100e-9)
    assert a.idle_s["fetch"] == pytest.approx(900e-9)
    _sums_to_the_span(a)


def test_device_steps_led_by_a_dispatch():
    spans = [Span(DRIVE, "kta.dispatch", 0, 1e6),
             Span(DRIVE, "kta.dispatch", 200e6, 201e6),
             Span(DRIVE, "kta.ingest", 201e6, 400e6)]
    steps = [Op("jit_step_c", 2e6, 5e6), Op("jit_step_c", 100e6, 110e6),
             Op("jit_step_c", 230e6, 240e6)]
    a = host_spans.attribute(spans, [], steps)
    assert a.step_offsets_s == pytest.approx([2e-3, 100e-3, 30e-3])
    assert a.steps_led == 2


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _int(num: int, v: int) -> bytes:
    return _varint(num << 3) + _varint(v)


def _bytes(num: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, stat_names, ops):
    """An XPlane: stat metadata {id: name}; ops [(hlo text, stats)], each
    stat (metadata id, str value or ref id)."""
    out = _bytes(2, name)
    for sid, sname in stat_names.items():
        out += _bytes(5, _int(1, sid) + _bytes(2, _int(1, sid)
                                               + _bytes(2, sname)))
    for i, (text, stats) in enumerate(ops, 1):
        md = _int(1, i) + _bytes(2, text) + _bytes(4, text.split()[0])
        for sid, value in stats:
            md += _bytes(5, _int(1, sid) + (
                _bytes(5, value) if isinstance(value, str)
                else _int(7, value)))
        out += _bytes(4, _int(1, i) + _bytes(2, md))
    return _bytes(1, out)


def test_op_scopes_read_tf_op_from_event_metadata():
    stats = {1: "tf_op", 2: "jit(step)/kta.counters/add", 3: "flops"}
    ops = [("%slice_or_fusion = u32[8] fusion(...)",
            [(3, 9), (1, "jit(step_c)/kta.alive/or")]),
           ("%add.1 = s64[2] add(...)", [(1, 2)]),        # by reference
           ("%copy.3 = u32[8] copy(...)", [(1, "state.alive.words")])]
    data = (_plane("/device:TPU:0", stats, ops)
            + _plane("/host:CPU", stats, ops[:1]))
    assert host_spans.op_scopes(data) == {
        "%slice_or_fusion = u32[8] fusion(...)": "kta.alive",
        "%add.1 = s64[2] add(...)": "kta.counters",
    }


def test_no_drive_thread_reads_nothing():
    assert host_spans.attribute([Span(INGEST, "kta.fetch", 0, 5)], []) \
        is None


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded trace")
def test_recorded_chip_sample():
    with gzip.open(SAMPLE, "rt") as f:
        doc = json.load(f)
    a = host_spans.attribute(
        [Span(*s) for s in doc["spans"]], [Op(*o) for o in doc["ops"]],
        [Op(*s) for s in doc["steps"]])
    _sums_to_the_span(a)
    assert a.busy_s > 0
    for c in CATEGORIES:
        assert a.idle_s[c] == pytest.approx(doc["idle_s"][c], abs=1e-6)
    # The alive scope holds the three full-bitmap fusions, and little else.
    fusions = sum((o[2] - o[1]) / 1e9 for o in doc["ops"]
                  if o[0] in ("slice_or_fusion", "fusion", "fusion.1"))
    assert 1.0 <= a.scope_s["kta.alive"] / fusions < 1.15


def _empty_run(**kw):
    return harness.Run(audits=[], records_per_audit=0, setup_s=0.0,
                       peak_bytes=0, deltas={}, **kw)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_reader_reads_nothing_without_its_input(name):
    assert manifest.load_reader(REPO, name)(_empty_run()) is None


def test_counter_readers_per_audit_and_per_record():
    a = harness.Audit("t", 0.0, 1.0, 0, "")
    deltas = {
        "kta_scan_records_total": [{"labels": {}, "value": 2e6}],
        "kta_fetch_first_byte_seconds_total": [{"labels": {}, "value": 0.5}],
        "kta_program_build_seconds_total": [{"labels": {}, "value": 0.3}],
        "kta_finalize_d2h_bytes_total": [{"labels": {}, "value": 3 * 2**20}],
    }
    run = harness.Run([a, a, a], 1, 0.0, 0, deltas)
    read = lambda n: manifest.load_reader(REPO, n)(run)  # noqa: E731
    assert read("fetch_first_byte_s_per_Mrec") == pytest.approx(0.25)
    assert read("program_build_ms") == pytest.approx(100.0)
    assert read("finalize_d2h_MiB") == pytest.approx(1.0)


def test_attribution_readers_read_the_attribution():
    a = host_spans.Attribution(
        span_s=2.0, busy_s=0.5,
        idle_s=dict({c: 0.0 for c in CATEGORIES}, fetch=0.5,
                    decode_pack=1.0),
        scope_s={"kta.alive": 0.02})
    run = _empty_run(trace_records=4e6)
    run.host_spans = a
    read = lambda n: manifest.load_reader(REPO, n)(run)  # noqa: E731
    assert read("idle_share_fetch") == pytest.approx(0.25)
    assert read("idle_share_decode_pack") == pytest.approx(0.5)
    assert read("alive_apply_ms_per_Mrec") == pytest.approx(5.0)
