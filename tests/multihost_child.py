"""Child process for the turnkey multi-host test (test_multihost.py).

Each process runs the SAME code — the turnkey contract (SURVEY.md §5.8):
initialize jax.distributed, build the global (data, space) mesh, and let
the engine feed exactly the data rows this process hosts
(`ShardedTpuBackend.local_rows`).  Process 0 writes the merged metrics
dict as JSON; the parent test compares it against a single-process run.

Usage: python multihost_child.py <pid> <nprocs> <port> <out.json>
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


class _Interrupt(RuntimeError):
    pass


class _StepBomb:
    """Raise after N collective steps — N is the SAME on every process
    (update_shards runs in lockstep), so the interrupt is synchronized
    and no process is left waiting in a collective."""

    def __init__(self, inner, limit: int):
        self._inner = inner
        self._limit = limit
        self._n = 0

    def update_shards(self, batches):
        self._n += 1
        if self._n > self._limit:
            raise _Interrupt()
        return self._inner.update_shards(batches)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def main() -> int:
    pid, nprocs, port, out_path = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
    )
    mode = sys.argv[5] if len(sys.argv) > 5 else "plain"
    snap_dir = sys.argv[6] if len(sys.argv) > 6 else None
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=pid,
    )
    assert len(jax.devices()) == 8, jax.devices()
    assert jax.local_device_count() == 4

    from kafka_topic_analyzer_tpu.config import AnalyzerConfig
    from kafka_topic_analyzer_tpu.engine import run_scan
    from kafka_topic_analyzer_tpu.io.synthetic import (
        SyntheticSource,
        SyntheticSpec,
    )
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    # The "workers" mode spreads 16 partitions over the 8 data rows (2
    # per row) so a per-controller --ingest-workers 8 budget gives every
    # row a real 2-worker fan-in; the other modes keep the original 6.
    n_partitions = 16 if mode == "workers" else 6
    spec = SyntheticSpec(
        num_partitions=n_partitions,
        messages_per_partition=5000,
        keys_per_partition=500,
        key_null_permille=50,
        tombstone_permille=100,
        seed=42,
    )
    config = AnalyzerConfig(
        num_partitions=n_partitions,
        batch_size=2048,
        count_alive_keys=True,
        alive_bitmap_bits=16,
        enable_hll=True,
        enable_quantiles=True,
        mesh_shape=(8, 1),
    )
    backend = ShardedTpuBackend(config, init_now_s=10**10)
    # The turnkey contract under test: this process feeds only its rows.
    assert len(backend.local_rows) == 4, backend.local_rows

    if mode == "resume":
        # Interrupted scan with per-step per-process snapshots, then a
        # resumed scan with a FRESH backend — the multi-host
        # checkpoint/resume contract (checkpoint._snapshot_path).
        try:
            run_scan(
                "mh-topic",
                SyntheticSource(spec),
                _StepBomb(backend, 1),
                batch_size=2048,
                snapshot_dir=snap_dir,
                snapshot_every_s=0.0,
            )
            raise AssertionError("interrupt did not fire")
        except _Interrupt:
            pass
        assert os.path.exists(
            os.path.join(snap_dir, f"scan_snapshot.p{pid}of{nprocs}.npz")
        ), "per-process snapshot file missing"

        captured: "list" = []

        class CaptureStart:
            def __init__(self, inner):
                self._inner = inner

            def batches(self, batch_size, partitions=None, start_at=None):
                captured.append(start_at)
                return self._inner.batches(batch_size, partitions, start_at)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        backend = ShardedTpuBackend(config, init_now_s=10**10)
        result = run_scan(
            "mh-topic",
            CaptureStart(SyntheticSource(spec)),
            backend,
            batch_size=2048,
            snapshot_dir=snap_dir,
            resume=True,
        )
        # Resume must actually have engaged: the engine fed this process's
        # shard streams from the snapshot's offsets, not from zero.
        assert any(
            s and any(v > 0 for v in s.values()) for s in captured
        ), f"resume did not advance start offsets: {captured}"
    elif mode == "workers":
        # PR-7 tentpole under real multi-controller: each process runs
        # per-row ParallelIngest fan-ins over ITS shard partitions while
        # the collective rounds stay in lockstep.
        result = run_scan(
            "mh-topic", SyntheticSource(spec), backend, batch_size=2048,
            ingest_workers=8,
        )
        assert result.ingest_workers == 8, result.ingest_workers
        assert result.ingest_workers_per_controller == [8, 8], (
            result.ingest_workers_per_controller
        )
        # Controller-prefixed worker labels: the merged registry carries
        # BOTH controllers' fan-in workers as a disjoint union.
        recs = result.telemetry["kta_ingest_worker_records_total"]["samples"]
        labels = sorted(s["labels"]["worker"] for s in recs)
        assert labels == sorted(
            f"c{c}.{w}" for c in range(2) for w in range(8)
        ), labels
        assert all(s["value"] > 0 for s in recs), recs
    else:
        result = run_scan(
            "mh-topic", SyntheticSource(spec), backend, batch_size=2048
        )

    # Cluster-wide telemetry merge: each process's lag/ETA gauges cover
    # only the partitions ITS local rows feed, so the merged view is a
    # disjoint union — every partition appears exactly once, drained to
    # zero (a process must never report full lag for a partition another
    # process scanned).
    lag = result.telemetry["kta_partition_lag"]["samples"]
    parts = sorted(s["labels"]["partition"] for s in lag)
    assert parts == sorted(str(p) for p in range(n_partitions)), parts
    assert all(s["value"] == 0 for s in lag), lag
    if mode in ("plain", "workers"):
        # The merged counter sums both processes' folds to the full topic.
        # (Not asserted under "resume": the interrupted scan's partial
        # counts share this process's registry with the resumed run's.)
        assert (
            result.telemetry["kta_scan_records_total"]["samples"][0]["value"]
            == n_partitions * 5000
        )

    if jax.process_index() == 0:
        doc = result.metrics.to_dict(result.start_offsets, result.end_offsets)
        with open(out_path, "w") as f:
            json.dump(doc, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
