"""Create .ktaseg segment dumps from a synthetic workload spec.

Usage:
    python -m kafka_topic_analyzer_tpu.tools.make_segments \
        --out /tmp/segs --topic demo \
        --synthetic "partitions=4,messages=100000,keys=5000"
"""

from __future__ import annotations

import argparse
import os
import sys

from kafka_topic_analyzer_tpu.cli import parse_kv_pairs
from kafka_topic_analyzer_tpu.io.segfile import write_segment_from_batches
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec


def spec_from_kv(text: "str | None") -> SyntheticSpec:
    return SyntheticSpec.from_kv(parse_kv_pairs(text))


class _Parser(argparse.ArgumentParser):
    """Points ``--partitions 4``-style mistakes at the ``--synthetic`` kv
    form (the workload shape is a spec string, not individual flags —
    the bare "unrecognized arguments" error cost a first-time user real
    confusion)."""

    def error(self, message: str) -> "None":
        if "unrecognized arguments" in message:
            stray = [
                w.lstrip("-").replace("-", "_")
                for w in message.split(":", 1)[-1].split()
                if w.startswith("--")
            ]
            near = sorted(
                k for k in SyntheticSpec.KV_KEYS
                if any(s and (s in k or k in s) for s in stray)
            )
            hint = (
                "workload shape is given as one --synthetic spec, e.g. "
                '--synthetic "partitions=4,messages=100000,keys=5000"; '
                "valid keys: " + ", ".join(sorted(SyntheticSpec.KV_KEYS))
            )
            if near:
                hint = f"did you mean --synthetic \"{near[0]}=...\"? " + hint
            message = f"{message}\n{' ' * 7}{hint}"
        super().error(message)


def main(argv: "list[str] | None" = None) -> int:
    ap = _Parser(
        prog="make_segments",
        epilog="--synthetic takes the analyzer CLI's comma-separated k=v "
               "spec; valid keys: " + ", ".join(sorted(SyntheticSpec.KV_KEYS)),
    )
    ap.add_argument("--out", required=True, help="output directory")
    ap.add_argument("--topic", required=True)
    ap.add_argument("--synthetic",
                    help="workload spec, comma separated k=v (same format as "
                         "the analyzer CLI), e.g. "
                         "\"partitions=4,messages=100000,keys=5000\"")
    ap.add_argument("--batch-size", type=int, default=1 << 20)
    ap.add_argument("--chunk-records", type=int, default=0,
                    help="roll output into {topic}-{p}.cN.ktaseg chunks of "
                         "this many records (0 = one chunk per partition) — "
                         "the shape remote-tier read-ahead works against")
    ap.add_argument("--native", choices=["auto", "on", "off"], default="auto")
    args = ap.parse_args(argv)

    try:
        spec = spec_from_kv(args.synthetic)
    except ValueError as e:
        # Same clean one-line reporting as the analyzer CLI's
        # user_input_phase (the messages name the offending key).
        print(f"error: {e}", file=sys.stderr)
        return 1
    src: SyntheticSource
    if args.native in ("auto", "on"):
        try:
            from kafka_topic_analyzer_tpu.io.native import NativeSyntheticSource

            src = NativeSyntheticSource(spec)
        except Exception:
            if args.native == "on":
                raise
            src = SyntheticSource(spec)
    else:
        src = SyntheticSource(spec)

    os.makedirs(args.out, exist_ok=True)
    if args.chunk_records > 0:
        from kafka_topic_analyzer_tpu.io.segfile import SegmentDumpWriter

        writer = SegmentDumpWriter(
            args.out, args.topic, records_per_chunk=args.chunk_records
        )
        # Batch at the chunk size so rolling (batch-granular) lands chunks
        # of exactly the requested record count.
        for p in src.partitions():
            for b in src.batches(
                min(args.batch_size, args.chunk_records), partitions=[p]
            ):
                writer.append(b)
        writer.close()
        n = len(os.listdir(args.out))
        print(f"wrote {n} rolled chunk file(s) to {args.out}",
              file=sys.stderr)
        return 0
    for p in src.partitions():
        batches = list(src.batches(args.batch_size, partitions=[p]))
        path = write_segment_from_batches(args.out, args.topic, p, batches)
        print(f"wrote {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
