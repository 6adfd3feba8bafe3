"""Benchmark: analyzer throughput (msgs/s) on the local device.

Protocol: pre-materialize synthetic record batches on the host (ingest is
benchmarked separately — the native shim's generator runs at memory
bandwidth), then stream them through the jitted TPU backend with donated
state, and report end-to-end messages/second over the timed window.

Output contract: the LAST JSON line on stdout is the result —
  {"metric": "msgs_per_sec", "value": N, "unit": "msgs/s", "vs_baseline": R,
   "platform": ..., "device_kind": ..., "device_count": ..., ...}
vs_baseline is the ratio to the reference's only published number,
590,221 msgs/s (BASELINE.md, demo_output.png).  The device fields say what
the number was measured on: the platform is JAX's own (``JAX_PLATFORMS``),
and a run on the host CPU is a host number, never a chip number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

BASELINE_MSGS_PER_SEC = 590_221.0


#: BASELINE.json benchmark configs (scaled-down record counts; the shape of
#: each workload — partitions, features, key cardinality — is preserved).
CONFIGS = {
    1: dict(partitions=1, features="counters", keys=10_000,
            help="1-partition default metrics scan"),
    2: dict(partitions=16, features="counters,quantiles", keys=200_000,
            help="16-partition size histograms + ts range"),
    3: dict(partitions=16, features="counters,alive,hll", keys=1_000_000,
            help="log-compacted alive/distinct keys"),
    4: dict(partitions=16, features="counters,quantiles", keys=200_000,
            vmin=10, vmax=65_000, help="mixed-size payload percentiles"),
    5: dict(partitions=64, features="counters,alive,hll,quantiles",
            keys=500_000, help="8-topic fan-in shape (64 total rows)"),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, choices=sorted(CONFIGS),
                    help="BASELINE.json workload preset (overrides "
                         "--partitions/--features)")
    ap.add_argument("--partitions", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=1 << 20,
                    help="records per device step (default: 2^20)")
    ap.add_argument("--batches", type=int, default=8,
                    help="distinct pre-materialized batches")
    ap.add_argument("--steps", type=int, default=64,
                    help="timed device steps (cycling the batches)")
    ap.add_argument("--features", default="counters,hll,quantiles",
                    help="comma set: counters,alive,hll,quantiles")
    ap.add_argument("--alive-bits", type=int, default=26)
    ap.add_argument("--keys", type=int, default=200_000)
    ap.add_argument("--vmin", type=int, default=100)
    ap.add_argument("--vmax", type=int, default=420)
    ap.add_argument("--superbatch", default="1", metavar="K|auto",
                    help="stack K packed batches per jitted scan dispatch "
                         "(state donated once per superbatch; 'auto' "
                         "targets 2^20 records/dispatch)")
    ap.add_argument("--dispatch-depth", type=int, default=2,
                    help="superbatches allowed in flight while the device "
                         "folds (default 2)")
    ap.add_argument("--accuracy", action="store_true",
                    help="also run the CPU-exact oracle over the same records "
                         "and report sketch errors (BASELINE metric: msgs/s "
                         "profiled + sketch error vs exact)")
    ap.add_argument("--accuracy-seeds", type=int, default=6,
                    help="extra independent dataset seeds for the sketch-"
                         "error distribution (mean/max reported alongside "
                         "the main run's draw, so a single ±2σ draw can't "
                         "masquerade as the sketch's accuracy — r3 weak #2)")
    ap.add_argument("--accuracy-seed-batches", type=int, default=None,
                    help="batches per accuracy seed (default: same as "
                         "--batches, so the seed distribution is measured at "
                         "the SAME cardinality as the main draw — HLL error "
                         "depends on cardinality, r4 weak #5)")
    args = ap.parse_args()
    # Validate argument combinations immediately — a bad value must fail
    # here, not after the multi-minute timed run has already burned its
    # budget (the old post-run check lost the whole measurement).
    if (
        args.accuracy_seed_batches is not None
        and args.accuracy_seed_batches < 1
    ):
        ap.error("--accuracy-seed-batches must be >= 1")
    if args.config:
        preset = CONFIGS[args.config]
        args.partitions = preset["partitions"]
        args.features = preset["features"]
        args.keys = preset.get("keys", args.keys)
        args.vmin = preset.get("vmin", args.vmin)
        args.vmax = preset.get("vmax", args.vmax)
        print(f"bench: config {args.config} — {preset['help']}", file=sys.stderr)

    import jax

    device = jax.devices()[0]
    platform = device.platform

    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig
    from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
    from kafka_topic_analyzer_tpu.packing import packed_nbytes

    feats = set(args.features.split(","))
    config = AnalyzerConfig(
        num_partitions=args.partitions,
        batch_size=args.batch_size,
        count_alive_keys="alive" in feats,
        alive_bitmap_bits=args.alive_bits,
        enable_hll="hll" in feats,
        enable_quantiles="quantiles" in feats,
    )
    spec = SyntheticSpec(
        num_partitions=args.partitions,
        messages_per_partition=(args.batch_size * args.batches) // args.partitions,
        keys_per_partition=args.keys,
        key_null_permille=50,
        tombstone_permille=100,
        value_len_min=args.vmin,
        value_len_max=args.vmax,
        seed=0xBEEF,
    )

    print(f"bench: device={jax.devices()[0]}", file=sys.stderr)
    t_gen = time.perf_counter()
    try:
        from kafka_topic_analyzer_tpu.io.native import NativeSyntheticSource

        src = NativeSyntheticSource(spec)
    except Exception:
        src = SyntheticSource(spec)
    host_batches = list(src.batches(args.batch_size))
    host_batches = [b.pad_to(args.batch_size) for b in host_batches]
    gen_s = time.perf_counter() - t_gen
    total_host = sum(b.num_valid for b in host_batches)
    print(
        f"bench: generated {total_host} records in {gen_s:.1f}s "
        f"({total_host / gen_s:,.0f}/s host, {type(src).__name__}); "
        f"{packed_nbytes(config, args.batch_size) / args.batch_size:.1f} B/record on the wire",
        file=sys.stderr,
    )

    from kafka_topic_analyzer_tpu.config import DispatchConfig

    dispatch = DispatchConfig.parse(args.superbatch, args.dispatch_depth)
    backend = TpuBackend(config, init_now_s=0, dispatch=dispatch)
    super_k = backend.superbatch_k
    # Warmup: compile + first-touch — one full superbatch so the timed
    # loop never pays the scan-step compile.  The warmup batches are part
    # of the fold (and of the accuracy oracle's identical feed below).
    warmup = [host_batches[i % len(host_batches)] for i in range(super_k)]
    if super_k > 1:
        backend.update_superbatch(warmup)
    else:
        backend.update(warmup[0])
    backend.block_until_ready()

    t0 = time.perf_counter()
    if super_k > 1:
        for i in range(0, args.steps, super_k):
            backend.update_superbatch([
                host_batches[j % len(host_batches)]
                for j in range(i, min(i + super_k, args.steps))
            ])
    else:
        for i in range(args.steps):
            backend.update(host_batches[i % len(host_batches)])
    backend.block_until_ready()
    dt = time.perf_counter() - t0

    n = args.steps * args.batch_size
    msgs_per_sec = n / dt
    metrics = backend.finalize()
    assert int(metrics.overall_count) == n + super_k * args.batch_size  # incl. warmup

    print(
        f"bench: {n} records in {dt:.3f}s on {jax.devices()[0].platform}",
        file=sys.stderr,
    )
    result = {
        "metric": "msgs_per_sec",
        "value": round(msgs_per_sec, 1),
        "unit": "msgs/s",
        "vs_baseline": round(msgs_per_sec / BASELINE_MSGS_PER_SEC, 2),
        "batch_size": args.batch_size,
        "platform": platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }

    # Measured breakdown: where does the streamed number bind?  (a)
    # host->device bandwidth; (b) the device-resident step rate — what the
    # same chip sustains once transfer is off the critical path.
    # Accelerator platforms only: on the host CPU there is no device for
    # these numbers to describe — a host-to-host memcpy reported as
    # `transfer_gbps` would poison cross-report comparisons.
    if platform != "cpu":
        try:
            from kafka_topic_analyzer_tpu.packing import pack_batch
            from kafka_topic_analyzer_tpu.tools.hwmeasure import (
                timed_step_loop,
                transfer_gbps,
            )

            result["transfer_gbps"] = transfer_gbps()
            dev_bufs = [
                jax.device_put(pack_batch(b, config))
                for b in host_batches[: min(2, len(host_batches))]
            ]
            jax.block_until_ready(dev_bufs)
            resident = timed_step_loop(
                config, dev_bufs, steps=min(32, args.steps)
            )
            result["device_resident_msgs_per_sec"] = resident["msgs_per_sec"]
        except Exception as e:  # breakdown is informative, never fatal
            result["breakdown_error"] = repr(e)

    if args.accuracy and (config.enable_hll or config.enable_quantiles):
        # Sketch error vs the CPU-exact oracle — fed EXACTLY the sequence the
        # device consumed (warmup batch + steps cycling the batch list), so
        # the comparison measures sketch error, not dataset mismatch.
        from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend

        t_acc = time.perf_counter()
        oracle = CpuExactBackend(config, init_now_s=0)
        for b in warmup:  # mirror the device warmup (K batches)
            oracle.update(b)
        for i in range(args.steps):
            oracle.update(host_batches[i % len(host_batches)])
        exact = oracle.finalize()
        sketch = metrics
        if config.enable_hll and exact.distinct_keys_exact:
            result["hll_rel_error"] = round(
                abs(sketch.distinct_keys_hll - exact.distinct_keys_exact)
                / exact.distinct_keys_exact,
                5,
            )
        if config.enable_quantiles and exact.quantiles is not None:
            errs = [
                abs(s - e) / e
                for s, e in zip(sketch.quantiles.values, exact.quantiles.values)
                if e
            ]
            result["quantile_rel_error_max"] = round(max(errs), 5) if errs else 0.0

        # Error DISTRIBUTION over independent seeds: one draw cannot tell a
        # within-budget sketch from a lucky one (r3's config-3 record was a
        # ~2σ draw read as the truth).  Each seed gets its own dataset at
        # the SAME batch count as the main run — HLL error is a function of
        # cardinality, so a smaller per-seed dataset would measure a
        # different distribution than the headline draw's (r4 weak #5).
        # Shapes are identical so the jitted step is compile-cache warm.
        seed_errs_hll: "list[float]" = []
        seed_errs_q: "list[float]" = []
        acc_batches = (args.accuracy_seed_batches
                       if args.accuracy_seed_batches is not None
                       else args.batches)
        if args.accuracy_seeds > 0:
            result["accuracy_seed_batches"] = acc_batches
            result["accuracy_seed_records"] = acc_batches * args.batch_size
        for s in range(max(0, args.accuracy_seeds)):
            import dataclasses as _dc

            sspec = _dc.replace(
                spec,
                seed=0xACC0 + s,
                messages_per_partition=(args.batch_size * acc_batches)
                // args.partitions,
            )
            try:
                ssrc = NativeSyntheticSource(sspec)
            except Exception:
                ssrc = SyntheticSource(sspec)
            sbatches = [
                b.pad_to(args.batch_size)
                for b in ssrc.batches(args.batch_size)
            ]
            sk_backend = TpuBackend(config, init_now_s=0)
            sk_oracle = CpuExactBackend(config, init_now_s=0)
            for b in sbatches:
                sk_backend.update(b)
                sk_oracle.update(b)
            sk = sk_backend.finalize()
            ex = sk_oracle.finalize()
            if config.enable_hll and ex.distinct_keys_exact:
                seed_errs_hll.append(
                    abs(sk.distinct_keys_hll - ex.distinct_keys_exact)
                    / ex.distinct_keys_exact
                )
            if config.enable_quantiles and ex.quantiles is not None:
                qe = [
                    abs(a - e) / e
                    for a, e in zip(sk.quantiles.values, ex.quantiles.values)
                    if e
                ]
                if qe:
                    seed_errs_q.append(max(qe))
        if seed_errs_hll:
            result["hll_rel_error_seeds"] = [round(e, 5) for e in seed_errs_hll]
            result["hll_rel_error_mean"] = round(
                sum(seed_errs_hll) / len(seed_errs_hll), 5
            )
            result["hll_rel_error_max"] = round(max(seed_errs_hll), 5)
        if seed_errs_q:
            result["quantile_rel_error_seeds_max"] = round(max(seed_errs_q), 5)
        print(
            f"bench: accuracy referee took {time.perf_counter() - t_acc:.1f}s "
            f"({len(seed_errs_hll) or len(seed_errs_q)} extra seeds)",
            file=sys.stderr,
        )

    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
