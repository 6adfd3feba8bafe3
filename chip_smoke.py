#!/usr/bin/env python3
"""First chip run of the ``--backend tpu`` scan, through the CLI.

A loopback Kafka broker (tools/bench_e2e.py's TemplateBroker, in a spawned
child that never touches JAX) serves a seeded topic: 16 partitions, 2^23
records, 2^20 distinct keys, values of 139-750 bytes (BASELINE.md's
published run), one record in ten a tombstone.  This process scans it with
``kafka_topic_analyzer_tpu.cli.main`` at the CLI's default widths (batch
2^18, a 2^32-bit alive bitmap, HLL p=16, 2560 DDSketch buckets) and checks:

- the TPU report against the ``--backend cpu`` oracle: exact lines equal,
  HLL within 1 %, quantiles within 2α (BASELINE's budget);
- a warm rerun equal to the first TPU report;
- the device is a TPU, the native shim loaded, every record went through
  the fused decode→pack path.

``--chips 4`` runs only the mesh path: the same topic at ``--mesh 4`` and
``--mesh 2,2``, each report identical to the one-device report, with the
state spread over all four chips.

Earlier lines are first-chip-run figures, not benchmark results.  The last
line is ``{"ok": true, "device": {...}}``; any failure exits non-zero
without it.  This module imports nothing at top level: the broker child is
spawned, and re-imports it.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import time

PARTITIONS = 16
RECORDS = 1 << 23
RECORDS_PER_BATCH = 4096
#: 2^20 distinct keys: 256 templates of 4096 keys, 16 per partition.
TEMPLATES = 256
VALUE_MIN, VALUE_MAX = 139, 750
TOMBSTONE_EVERY = 10
BATCH_SIZE = 1 << 18
TOPIC = "chip-smoke"
#: Report keys that must match exactly; the sketch keys are checked
#: against the oracle's exact values within budget.
EXACT_KEYS = ("topic", "overall", "partitions", "alive_keys",
              "extremes_per_partition")
SKETCH_KEYS = ("distinct_keys_hll", "size_quantiles")
HLL_BUDGET = 0.01


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(f"chip_smoke: {line}", flush=True)


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    found = devices[0].platform
    check(found == "tpu", f"found platform {found!r}, need 'tpu'")
    check(len(devices) >= chips,
          f"found {len(devices)} tpu devices, need {chips}")
    return devices


def broker():
    from kafka_topic_analyzer_tpu.io import kafka_codec as kc
    from kafka_topic_analyzer_tpu.tools.bench_e2e import BrokerProcess

    return BrokerProcess(
        topic=TOPIC, partitions=PARTITIONS,
        windows=RECORDS // (PARTITIONS * RECORDS_PER_BATCH),
        R=RECORDS_PER_BATCH, n_templates=TEMPLATES, vmin=VALUE_MIN,
        vmax=VALUE_MAX, compression=kc.COMPRESSION_NONE,
        tombstone_every=TOMBSTONE_EVERY,
    )


def _counter(name: str) -> float:
    from kafka_topic_analyzer_tpu.obs.registry import default_registry

    doc = default_registry().snapshot().get(name, {})
    return sum(s["value"] for s in doc.get("samples", []))


class Scan:
    """One ``cli.main`` scan of the broker's topic: its JSON report, wall
    seconds, backend compile seconds, fused/fallback record deltas, and the
    backend the CLI built (to inspect where its state lives)."""

    def __init__(self, port: int, backend: str, *extra: str):
        from kafka_topic_analyzer_tpu import cli

        argv = ["-t", TOPIC, "--source", "kafka", "-b", f"127.0.0.1:{port}",
                "--backend", backend, "-c", "--distinct-keys", "--quantiles",
                "--json", "--quiet", "--batch-size", str(BATCH_SIZE),
                "--alive-bitmap-bits", "32", *extra]
        self.label = " ".join([backend, *extra])
        built = []
        make = cli._make_cli_backend

        def capture(*a, **k):
            built.append(make(*a, **k))
            return built[-1]

        compile_s = []

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compile_s.append(duration)

        import jax

        jax.monitoring.register_event_duration_secs_listener(on_event)
        fused0 = _counter("kta_fused_records_total")
        fallback0 = _counter("kta_fused_fallback_total")
        out = io.StringIO()
        cli._make_cli_backend = capture
        t0 = time.perf_counter()
        try:
            with contextlib.redirect_stdout(out):
                rc = cli.main(argv)
        finally:
            self.seconds = time.perf_counter() - t0
            cli._make_cli_backend = make
            jax.monitoring.unregister_event_duration_listener(on_event)
        check(rc == 0, f"scan [{self.label}] exited {rc}")
        self.report = json.loads(out.getvalue())
        self.compile_s = sum(compile_s)
        self.fused = _counter("kta_fused_records_total") - fused0
        self.fallback = _counter("kta_fused_fallback_total") - fallback0
        self.backend = built[0]
        self.count = self.report["overall"]["count"]
        say(f"scan [{self.label}]: {self.count} records in "
            f"{self.seconds:.3f}s ({self.count / self.seconds:,.0f} "
            f"records/s incl. compile {self.compile_s:.3f}s)")

    def exact(self) -> str:
        return json.dumps({k: self.report.get(k) for k in EXACT_KEYS},
                          sort_keys=True)

    def analysis(self) -> str:
        """Every line of the report, sketches included."""
        return json.dumps(
            {k: self.report.get(k) for k in EXACT_KEYS + SKETCH_KEYS},
            sort_keys=True,
        )


def state_bytes_by_device(backend) -> "dict[int, int]":
    import jax

    out: "dict[int, int]" = {}
    for leaf in jax.tree.leaves(backend.state):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = (
                out.get(shard.device.id, 0) + shard.data.nbytes
            )
    return out


def compare_to_oracle(tpu: Scan, cpu: Scan) -> None:
    check(tpu.exact() == cpu.exact(),
          "exact report lines differ from the --backend cpu oracle")
    exact_keys = cpu.report["distinct_keys_exact"]
    hll = tpu.report["distinct_keys_hll"]
    hll_err = abs(hll - exact_keys) / exact_keys
    say(f"oracle: exact lines identical; alive_keys "
        f"{tpu.report['alive_keys']}; distinct keys exact {exact_keys}, "
        f"HLL {hll:.1f} (rel. error {hll_err:.6f}, budget {HLL_BUDGET})")
    check(hll_err <= HLL_BUDGET, f"HLL error {hll_err} over {HLL_BUDGET}")
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig

    budget = 2 * AnalyzerConfig(num_partitions=1).quantile_alpha
    for q, want in cpu.report["size_quantiles"].items():
        got = tpu.report["size_quantiles"][q]
        err = abs(got - want) / want
        say(f"oracle: quantile {q}: exact {want}, sketch {got:.3f} "
            f"(rel. error {err:.6f}, budget {budget})")
        check(err <= budget, f"quantile {q} error {err} over 2α={budget}")


def one_chip(port: int, device) -> None:
    cold = Scan(port, "tpu")
    check(cold.backend.device.platform == "tpu",
          f"scan ran on {cold.backend.device.platform}")
    check(cold.count >= RECORDS, f"scanned {cold.count} < {RECORDS} records")
    check(cold.fused == cold.count and cold.fallback == 0,
          f"fused path: {cold.fused} fused, {cold.fallback} fallback "
          f"of {cold.count} records")
    say(f"fused decode→pack: {cold.fused:.0f} records, 0 fallback")
    del cold.backend
    gc.collect()
    warm = Scan(port, "tpu")
    del warm.backend
    gc.collect()
    check(warm.analysis() == cold.analysis(), "warm rerun report differs")
    stats = device.memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"(bytes_limit {stats.get('bytes_limit')})")
    say(f"compile seconds (cold scan) {cold.compile_s:.3f}; "
        f"warm scan {warm.seconds:.3f}s = "
        f"{warm.count / warm.seconds:,.0f} records/s")
    cpu = Scan(port, "cpu")
    compare_to_oracle(cold, cpu)


def four_chips(port: int, devices) -> None:
    single = Scan(port, "tpu")
    del single.backend
    gc.collect()
    for mesh in ("4", "2,2"):
        scan = Scan(port, "tpu", "--mesh", mesh)
        ids = [d.id for d in scan.backend.mesh.devices.flat]
        by_device = state_bytes_by_device(scan.backend)
        say(f"mesh {mesh}: devices {ids}; state bytes per device "
            f"{by_device}")
        check(sorted(ids) == sorted(d.id for d in devices[:4]),
              f"mesh {mesh} did not take the four local chips")
        check(sorted(by_device) == sorted(ids)
              and len(set(by_device.values())) == 1,
              f"mesh {mesh}: state not spread evenly over the chips")
        del scan.backend
        gc.collect()
        check(scan.analysis() == single.analysis(),
              f"--mesh {mesh} report differs from the one-device report")
        say(f"--mesh {mesh} report identical to the one-device report")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --mesh 4 / --mesh 2,2 path")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        devices = require_tpu(args.chips)
        try:
            import kafka_topic_analyzer_tpu  # noqa: F401
        except ImportError as e:
            raise SmokeFailure(f"the analyzer is not beside this script: {e}")
        say(f"device {devices[0].device_kind} x{len(devices)} "
            f"({devices[0].platform}); the figures below are a first chip "
            "run, not benchmark results")
        # Built (on first use) before the broker child needs it too.
        from kafka_topic_analyzer_tpu.io.native import native_status

        ok, why = native_status()
        check(ok, f"native shim not loaded ({why})")
        say("native shim loaded")
        with broker() as port:
            if args.chips == 4:
                four_chips(port, devices)
            else:
                one_chip(port, devices[0])
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
