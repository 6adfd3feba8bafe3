"""Run every BASELINE benchmark config and emit one JSON report.

Usage:
    python -m kafka_topic_analyzer_tpu.tools.bench_all [--batch-size N]
        [--steps N] [--out report.json]

Each config runs through bench.py in a subprocess (fresh jit caches, honest
per-config timing); the report maps config id → bench JSON line.  This
parent never imports JAX: each child in turn is the one process that holds
the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=None,
                    help="records per device step (default: bench.py's)")
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--out", default="-")
    ap.add_argument("--configs", default="1,2,3,4,5")
    args = ap.parse_args(argv)

    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    report = {}
    for cfg in [int(c) for c in args.configs.split(",") if c]:
        cmd = [
            sys.executable, os.path.join(repo, "bench.py"),
            "--config", str(cfg),
            "--batches", str(args.batches),
            "--steps", str(args.steps),
            "--accuracy",  # the BASELINE metric includes sketch error
        ]
        if args.batch_size:
            cmd += ["--batch-size", str(args.batch_size)]
        print(f"bench_all: running config {cfg}...", file=sys.stderr)
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            report[str(cfg)] = {"error": proc.stderr.strip()[-500:]}
            continue
        last = proc.stdout.strip().splitlines()[-1]
        report[str(cfg)] = json.loads(last)
        print(f"bench_all: config {cfg}: {last}", file=sys.stderr)

    # End-to-end pipeline figure (broker → wire client → decode → pack →
    # device) next to the device-path numbers — the apples-to-apples
    # comparison to the reference's published 590,221 msgs/s
    # (demo_output.png, src/main.rs:130).
    cmd = [
        sys.executable, "-m", "kafka_topic_analyzer_tpu.tools.bench_e2e",
        "--backend", "tpu", "--quiet",
    ]
    print("bench_all: running e2e pipeline...", file=sys.stderr)
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=repo)
    if proc.returncode != 0:
        report["e2e"] = {"error": proc.stderr.strip()[-500:]}
    else:
        last = proc.stdout.strip().splitlines()[-1]
        report["e2e"] = json.loads(last)
        print(f"bench_all: e2e: {last}", file=sys.stderr)

    out = json.dumps(report, indent=2)
    if args.out == "-":
        print(out)
    else:
        with open(args.out, "w") as f:
            f.write(out + "\n")
        print(f"bench_all: wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
