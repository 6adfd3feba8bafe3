"""The program's spans on the profiler's clock, its step scopes, and the
counters booked beside them (obs/trace.py, backends/step.py)."""

from __future__ import annotations

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend, make_packed_step
from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
from kafka_topic_analyzer_tpu.io.native import native_available
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.obs import trace as obs_trace

from fake_broker import FakeBroker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPIC = "spans.topic"
CFG = AnalyzerConfig(
    num_partitions=3, batch_size=128,
    count_alive_keys=True, alive_bitmap_bits=16,
    enable_hll=True, hll_p=8, enable_quantiles=True,
)
RECORDS = {
    p: [(i, 1_600_000_000_000 + i, f"k{p}-{i % 17}".encode(),
         bytes(10 + i % 9)) for i in range(200)]
    for p in range(3)
}


def _profiled_wire_scan(trace_dir: str, workers: int = 1):
    """One loopback wire scan under the JAX profiler; its host events."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        src = KafkaWireSource(f"127.0.0.1:{broker.port}", TOPIC)
        backend = TpuBackend(CFG, init_now_s=10**10)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            result = run_scan(TOPIC, src, backend, CFG.batch_size,
                              ingest_workers=workers)
        finally:
            jax.profiler.stop_trace()
            src.close()
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(obs_trace.PREFIX)]
    return result, events


@pytest.mark.skipif(not native_available(), reason="native shim unavailable")
@pytest.mark.parametrize("workers", [1, 2])
def test_wire_scan_spans_land_in_the_profiler_trace(tmp_path, workers):
    result, events = _profiled_wire_scan(str(tmp_path), workers)
    assert result.metrics.overall_count == 600
    names = {n for n, _ in events}
    assert {"kta.scan", "kta.ingest", "kta.dispatch", "kta.fetch",
            "kta.fetch.first_byte", "kta.decode_pack", "kta.stage",
            "kta.finalize", "kta.finalize.reduce", "kta.finalize.device_get",
            "kta.finalize.host"} <= names
    (scan_args,) = [a for n, a in events if n == "kta.scan"]
    scan = scan_args["scan"]
    # Every span of the scan carries its id, ingest threads' included.
    assert all(a.get("scan") == scan for _, a in events)
    staged = {a["batch"] for n, a in events if n == "kta.stage"}
    dispatched = {a["batch"] for n, a in events if n == "kta.dispatch"}
    decoded = {a["batch"] for n, a in events if n == "kta.decode_pack"}
    # Rows of 128 records (five for one stream), each staged and
    # dispatched under its own batch number, which its decode-and-pack
    # spans carry.  A span carries the row open when it starts, so a
    # stream's last row, filled only by records that spilled over from
    # the span of the row before, may have none of its own.
    assert len(staged) >= 5 and dispatched == staged
    assert len(staged & decoded) >= len(staged) - workers


@pytest.mark.skipif(not native_available(), reason="native shim unavailable")
def test_trace_json_keeps_its_names_and_gains_the_new_spans(tmp_path):
    tracer = obs_trace.SpanTracer()
    obs_trace.set_active(tracer)  # as the CLI's --trace-json does
    try:
        _profiled_wire_scan(str(tmp_path))
    finally:
        obs_trace.set_active(None)
    names = {e["name"] for e in tracer.events()}
    assert {"ingest", "dispatch", "finalize", "fetch"} <= names
    assert {"scan", "fetch.first_byte", "decode_pack", "stage",
            "finalize.reduce", "finalize.device_get", "finalize.host"} <= names
    assert not any(n.startswith(obs_trace.PREFIX) for n in names)
    spans = {e["name"]: e for e in tracer.events()
             if e["name"].startswith("finalize.")}
    # The bitmap is counted on the device before the copy starts.
    assert (spans["finalize.reduce"]["ts"] + spans["finalize.reduce"]["dur"]
            <= spans["finalize.device_get"]["ts"])


def test_spans_stay_jax_free_where_jax_is_not_loaded():
    code = (
        "import sys\n"
        "from kafka_topic_analyzer_tpu.obs import trace\n"
        "from kafka_topic_analyzer_tpu.utils.profiling import ScanProfile\n"
        "with trace.scan_span():\n"
        "    with trace.maybe_span('fetch', batch=trace.next_batch()):\n"
        "        pass\n"
        "    with ScanProfile().stage('ingest'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


def test_scan_ids_and_batch_numbers():
    with obs_trace.scan_span() as a:
        assert [obs_trace.next_batch() for _ in range(3)] == [0, 1, 2]
        with obs_trace.scan_span() as b:
            assert b == a + 1 and obs_trace.next_batch() == 0
        assert obs_trace.next_batch() == 3


def test_step_scopes_in_the_lowered_step():
    import jax

    from kafka_topic_analyzer_tpu.models.state import AnalyzerState
    from kafka_topic_analyzer_tpu.packing import (
        packed_nbytes,
        pair_table_capacity,
        pair_table_nbytes,
    )

    assert CFG.count_alive_keys
    cap = pair_table_capacity(CFG, CFG.batch_size, 1)
    args = (
        jax.eval_shape(lambda: AnalyzerState.init(CFG)),
        jax.ShapeDtypeStruct((packed_nbytes(CFG, CFG.batch_size),),
                             np.uint8),
        jax.ShapeDtypeStruct((pair_table_nbytes(CFG, cap),), np.uint8),
    )
    text = jax.jit(make_packed_step(CFG)).lower(*args).as_text(
        debug_info=True)
    for scope in ("kta.unpack", "kta.counters", "kta.alive", "kta.hll",
                  "kta.ddsketch"):
        assert f"/{scope}/" in text, scope


def test_program_build_seconds_rise_on_a_first_call_only():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 3 + 1).sum())
    x = jnp.arange(11.0)
    b0 = obs_metrics.PROGRAM_BUILD_SECONDS.value
    f(x).block_until_ready()
    b1 = obs_metrics.PROGRAM_BUILD_SECONDS.value
    f(x).block_until_ready()
    b2 = obs_metrics.PROGRAM_BUILD_SECONDS.value
    assert b1 > b0
    assert b2 == b1


def test_program_build_listener_is_registered_once():
    from jax._src import monitoring

    obs_metrics.install_program_build_listener()
    obs_metrics.install_program_build_listener()
    listeners = monitoring._event_duration_secs_listeners
    assert listeners.count(obs_metrics._on_duration_event) == 1


@pytest.mark.parametrize("alive", [True, False], ids=["alive", "no-alive"])
def test_finalize_d2h_bytes_equal_the_state_bytes(alive):
    """The copy moves the state's leaves; with an alive bitmap, the
    bitmap's int64 popcount in place of its words."""
    import dataclasses

    import jax

    cfg = dataclasses.replace(CFG, count_alive_keys=alive)
    backend = TpuBackend(cfg, init_now_s=10**10)
    state_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(backend.state))
    if alive:
        state_bytes += 8 - backend.state.alive.words.nbytes
    before = obs_metrics.FINALIZE_D2H_BYTES.value
    backend.finalize()
    assert obs_metrics.FINALIZE_D2H_BYTES.value - before == state_bytes


@pytest.mark.skipif(len(__import__("jax").devices()) < 4,
                    reason="needs 4 (virtual) devices")
def test_sharded_finalize_books_its_merge_and_rows():
    from kafka_topic_analyzer_tpu.io.synthetic import (
        SyntheticSource,
        SyntheticSpec,
    )
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg = AnalyzerConfig(
        num_partitions=5, batch_size=128, count_alive_keys=True,
        alive_bitmap_bits=16, enable_hll=True, hll_p=8,
        enable_quantiles=True, mesh_shape=(4, 1),
    )
    spec = SyntheticSpec(num_partitions=5, messages_per_partition=300,
                         keys_per_partition=40, seed=7)
    backend = ShardedTpuBackend(cfg, init_now_s=10**10)
    text = backend._merge.lower(backend.state).as_text(debug_info=True)
    assert '"kta.merge/' in text

    rows = obs_metrics.MESH_ROW_RECORDS
    before = {r: rows.labels(row=r).value for r in range(4)}
    scan0 = obs_metrics.SCAN_RECORDS.value
    merge0 = obs_metrics.MESH_MERGE_SECONDS.value
    d2h0 = obs_metrics.FINALIZE_D2H_BYTES.value
    tracer = obs_trace.SpanTracer()
    obs_trace.set_active(tracer)
    try:
        result = run_scan("t", SyntheticSource(spec), backend, cfg.batch_size)
    finally:
        obs_trace.set_active(None)
    assert result.metrics.overall_count == 1500

    booked = [rows.labels(row=r).value - before[r] for r in range(4)]
    # Round-robin: partitions 0 and 4 on row 0, one partition on each other.
    assert booked == [600, 300, 300, 300]
    assert sum(booked) == obs_metrics.SCAN_RECORDS.value - scan0
    assert obs_metrics.MESH_MERGE_SECONDS.value > merge0
    # The merged counters and sketches come back, not the rows' bitmaps.
    d2h = obs_metrics.FINALIZE_D2H_BYTES.value - d2h0
    assert 0 < d2h < (1 << 16) // 8 * 4

    spans = {e["name"]: e for e in tracer.events()
             if e["name"].startswith("finalize")}
    outer = spans["finalize"]
    for name in ("finalize.merge", "finalize.device_get", "finalize.host"):
        inner = spans[name]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert (spans["finalize.merge"]["ts"] + spans["finalize.merge"]["dur"]
            <= spans["finalize.device_get"]["ts"])
