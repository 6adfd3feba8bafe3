"""Integration: the scan pipeline's telemetry outputs.

Covers the ISSUE acceptance criteria end to end:
- a chaos-suite scan (fake_broker fault injection) exposes non-zero
  retry/eviction/degraded counters via the live Prometheus endpoint, with
  matching JSONL events;
- the --trace-json output is valid Chrome trace-event JSON whose
  per-stage span totals agree with ScanProfile stage seconds within 5%;
- the engine's registry counters agree with the scan result, and the
  final heartbeat drains the per-partition lag gauges to zero.

The default registry is process-global, so every test starts from a
reset() (registrations survive; values zero)."""

import json
import re
import urllib.request

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.obs import events as obs_events
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.obs.exporters import PrometheusExporter
from kafka_topic_analyzer_tpu.obs.registry import default_registry
from kafka_topic_analyzer_tpu.obs.trace import SpanTracer

from fake_broker import ChaosTrigger, FakeBroker, FaultInjector

TOPIC = "telemetry.topic"

FAST_RETRY = {
    "retry.backoff.ms": "5",
    "reconnect.backoff.max.ms": "40",
}


@pytest.fixture(autouse=True)
def _reset_registry():
    default_registry().reset()
    yield
    default_registry().reset()


def _mk_records(partition: int, n: int):
    return [
        (
            i,
            1_600_000_000_000 + i * 1000,
            f"k{partition}-{i % 37}".encode() if i % 5 else None,
            bytes(20 + (i % 13)) if i % 7 else None,
        )
        for i in range(n)
    ]


def _synthetic_scan(tracer=None, **kwargs):
    spec = SyntheticSpec(
        num_partitions=2, messages_per_partition=400, keys_per_partition=50
    )
    cfg = AnalyzerConfig(num_partitions=2, batch_size=128)
    return run_scan(
        "synth",
        SyntheticSource(spec),
        CpuExactBackend(cfg, init_now_s=10**10),
        128,
        tracer=tracer,
        **kwargs,
    )


def _scrape(port: int) -> str:
    url = f"http://127.0.0.1:{port}/metrics"
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.read().decode()


def _sample(text: str, name: str) -> float:
    m = re.search(rf"^{re.escape(name)}(?:{{[^}}]*}})? (\S+)$", text, re.M)
    assert m, f"{name} missing from exposition:\n{text}"
    return float(m.group(1))


# ---------------------------------------------------------------------------
# engine counters + heartbeat gauges


def test_engine_counters_match_scan_result():
    result = _synthetic_scan()
    assert obs_metrics.SCAN_RECORDS.value == result.metrics.overall_count
    assert obs_metrics.SCAN_BATCHES.value > 0
    hist = obs_metrics.BATCH_RECORDS.samples()[0]
    assert hist["count"] == obs_metrics.SCAN_BATCHES.value
    assert hist["sum"] == result.metrics.overall_count
    # The forced closing heartbeat reports drained partitions: zero lag.
    for s in obs_metrics.PARTITION_LAG.samples():
        assert s["value"] == 0.0
    # The dispatch stage booked every batch, and the finalize histogram
    # the one finalize.
    assert (
        obs_metrics.STAGE_RECORDS.labels(stage="dispatch").value
        == result.metrics.overall_count
    )
    assert obs_metrics.STAGE_SECONDS.labels(stage="dispatch").value > 0
    assert obs_metrics.BACKEND_FINALIZE_SECONDS.samples()[0]["count"] == 1


def test_scan_result_carries_merged_telemetry():
    result = _synthetic_scan()
    tel = result.telemetry
    assert tel is not None
    assert (
        tel["kta_scan_records_total"]["samples"][0]["value"]
        == result.metrics.overall_count
    )
    stages = {
        s["labels"]["stage"]
        for s in tel["kta_stage_seconds_total"]["samples"]
    }
    assert {"ingest", "dispatch", "finalize"} <= stages
    json.dumps(tel)  # the --json telemetry block must be JSON-able


def test_scan_lifecycle_events():
    seen = []
    sink = lambda etype, fields: seen.append((etype, fields))  # noqa: E731
    obs_events.add_sink(sink)
    try:
        result = _synthetic_scan()
    finally:
        obs_events.remove_sink(sink)
    types = [t for t, _ in seen]
    assert types[0] == "scan_start"
    assert types[-1] == "scan_end"
    assert "heartbeat" in types
    end = dict(seen[-1][1])
    assert end["records"] == result.metrics.overall_count
    assert end["degraded"] == 0


# ---------------------------------------------------------------------------
# trace spans vs ScanProfile


def test_trace_json_valid_and_agrees_with_profile(tmp_path):
    tracer = SpanTracer()
    result = _synthetic_scan(tracer=tracer)
    path = tmp_path / "trace.json"
    tracer.write(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert evs, "trace must carry events"
    for ev in evs:
        assert ev["ph"] == "X"
        assert set(ev) >= {"name", "cat", "ts", "dur", "pid", "tid"}
    span_totals: "dict[str, float]" = {}
    for ev in evs:
        if ev["cat"] == "stage":
            span_totals[ev["name"]] = (
                span_totals.get(ev["name"], 0.0) + ev["dur"] / 1e6
            )
    for name, st in result.profile.stages.items():
        assert span_totals[name] == pytest.approx(st.seconds, rel=0.05), (
            f"stage {name}: trace says {span_totals[name]}, "
            f"profile says {st.seconds}"
        )


# ---------------------------------------------------------------------------
# chaos: fault counters via the live scrape endpoint + matching events


@pytest.mark.chaos
def test_chaos_scan_exposes_fault_counters(tmp_path):
    records = {p: _mk_records(p, 400) for p in range(2)}
    events_path = tmp_path / "events.jsonl"
    sink = obs_events.JsonlEventLog(str(events_path))
    obs_events.add_sink(sink)
    exporter = PrometheusExporter(0)
    try:
        with FakeBroker(TOPIC, records, max_records_per_fetch=60) as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            )
            cfg = AnalyzerConfig(num_partitions=2, batch_size=128)
            # Arm after the first batch (init handshake must succeed): the
            # next fetch round hits a dropped connection, then a refused
            # reconnect — transport failure, eviction, backoff, recovery.
            trigger = ChaosTrigger(
                src, 1,
                lambda: setattr(
                    broker, "faults",
                    FaultInjector()
                    .drop_connection(100, times=1)
                    .refuse_connections(times=1),
                ),
            )
            result = run_scan(
                TOPIC, trigger, CpuExactBackend(cfg, init_now_s=10**10), 128
            )
            src.close()
            assert broker.faults.exhausted()
        assert not result.degraded_partitions
        assert result.metrics.overall_count == 800

        text = _scrape(exporter.port)
        assert _sample(text, "kta_transport_failures_total") >= 1
        assert _sample(text, "kta_connection_evictions_total") >= 1
        assert _sample(text, "kta_backoff_sleeps_total") >= 1
        assert _sample(text, "kta_scan_records_total") == 800
        assert _sample(text, "kta_fetch_requests_total") >= 1
        assert _sample(text, "kta_scan_degraded_partitions") == 0
    finally:
        exporter.close()
        obs_events.remove_sink(sink)
        sink.close()

    docs = [json.loads(l) for l in events_path.read_text().splitlines()]
    by_type: "dict[str, list[dict]]" = {}
    for d in docs:
        by_type.setdefault(d["type"], []).append(d)
    assert "scan_start" in by_type and "scan_end" in by_type
    # The JSONL log and the registry tell the same fault story.
    assert len(by_type["transport_failure"]) >= 1
    assert len(by_type["connection_evicted"]) >= 1
    assert by_type["scan_end"][0]["degraded"] == 0


@pytest.mark.chaos
def test_degraded_scan_books_budget_exhaustion(tmp_path):
    records = {p: _mk_records(p, 200) for p in range(2)}
    events_path = tmp_path / "events.jsonl"
    sink = obs_events.JsonlEventLog(str(events_path))
    obs_events.add_sink(sink)
    try:
        with FakeBroker(TOPIC, records, max_records_per_fetch=60) as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}",
                TOPIC,
                overrides=dict(FAST_RETRY, **{"transport.retry.budget": "2"}),
            )
            cfg = AnalyzerConfig(num_partitions=2, batch_size=128)
            # Permanently dead past the first batch: both partitions
            # exhaust their budget and degrade (scan still returns).
            trigger = ChaosTrigger(
                src, 1,
                lambda: setattr(
                    broker, "faults",
                    FaultInjector()
                    .drop_connection(0, times=10**6)
                    .refuse_connections(times=10**6),
                ),
            )
            result = run_scan(
                TOPIC, trigger, CpuExactBackend(cfg, init_now_s=10**10), 128
            )
            src.close()
        assert set(result.degraded_partitions) == {0, 1}
        assert obs_metrics.RETRY_BUDGET_EXHAUSTIONS.value == 2
        assert obs_metrics.DEGRADED_PARTITIONS.value == 2
        tel = result.telemetry
        assert (
            tel["kta_retry_budget_exhaustions_total"]["samples"][0]["value"]
            == 2
        )
    finally:
        obs_events.remove_sink(sink)
        sink.close()
    types = [json.loads(l)["type"] for l in events_path.read_text().splitlines()]
    assert types.count("retry_budget_exhausted") == 2
    assert types.count("partition_degraded") == 2


# ---------------------------------------------------------------------------
# CLI flags end to end


def test_cli_telemetry_flags(tmp_path, capsys):
    from kafka_topic_analyzer_tpu import cli

    events_path = tmp_path / "events.jsonl"
    trace_path = tmp_path / "trace.json"
    rc = cli.main([
        "-t", "cli.topic",
        "--source", "synthetic",
        "--synthetic", "partitions=2,messages=300",
        "--quiet", "--json", "--stats",
        "--events-jsonl", str(events_path),
        "--trace-json", str(trace_path),
    ])
    assert rc == 0
    captured = capsys.readouterr()
    doc = json.loads(captured.out.splitlines()[-1])
    assert (
        doc["telemetry"]["kta_scan_records_total"]["samples"][0]["value"]
        == 600
    )
    assert "telemetry:" in captured.err  # --stats digest
    assert "scan stages:" in captured.err
    types = [json.loads(l)["type"] for l in events_path.read_text().splitlines()]
    assert types[0] == "scan_start" and types[-1] == "scan_end"
    trace_doc = json.loads(trace_path.read_text())
    names = {e["name"] for e in trace_doc["traceEvents"]}
    assert {"ingest", "dispatch"} <= names
