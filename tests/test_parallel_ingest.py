"""Parallel partition-sharded ingest: determinism + fault composition.

The tentpole contract (DESIGN.md §11): for any worker count N, a scan's
`ScanResult` — metrics, degraded/corrupt maps, resume offsets — is
byte-identical to the sequential (N=1) scan of the same topic.  That must
hold not just for clean topics but COMPOSED with the resilience machinery
of earlier PRs: transport faults (`FaultInjector` kills) and deterministic
corruption (`CorruptionInjector` poison) landing inside one worker's
partition group.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.backends.cpu import CpuExactBackend
from kafka_topic_analyzer_tpu.config import (
    AnalyzerConfig,
    CorruptionConfig,
    IngestConfig,
)
from kafka_topic_analyzer_tpu.engine import run_scan
from kafka_topic_analyzer_tpu.io import kafka_codec as kc
from kafka_topic_analyzer_tpu.io.kafka_wire import KafkaWireSource
from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics
from kafka_topic_analyzer_tpu.parallel.ingest import (
    ParallelIngest,
    shard_partitions,
)

from fake_broker import (
    ChaosTrigger,
    CorruptionInjector,
    FakeBroker,
    FakeCluster,
    FaultInjector,
)

pytestmark = pytest.mark.ingest

TOPIC = "pingest.topic"

FAST_RETRY = {
    "retry.backoff.ms": "5",
    "reconnect.backoff.max.ms": "40",
}


def _mk_records(partition: int, n: int):
    return [
        (
            i,
            1_600_000_000_000 + i * 1000,
            f"k{partition}-{i % 29}".encode() if i % 5 else None,
            bytes(20 + (i % 13)) if i % 7 else None,
        )
        for i in range(n)
    ]


N_PARTS = 4
N_REC = 300
RECORDS = {p: _mk_records(p, N_REC) for p in range(N_PARTS)}

CFG = AnalyzerConfig(
    num_partitions=N_PARTS, batch_size=128,
    count_alive_keys=True, alive_bitmap_bits=16,
)


def _scan(source, workers=1, batch_size=128):
    backend = CpuExactBackend(CFG, init_now_s=10**10)
    result = run_scan(
        TOPIC, source, backend, batch_size, ingest_workers=workers
    )
    close = getattr(source, "inner", source)
    close.close()
    return result


def _full_doc(result) -> dict:
    """EVERYTHING the determinism contract covers, in one comparable doc:
    metrics, scan window, degraded/corrupt maps."""
    return {
        "metrics": result.metrics.to_dict(
            result.start_offsets, result.end_offsets
        ),
        "start": result.start_offsets,
        "end": result.end_offsets,
        "degraded": result.degraded_partitions,
        "corrupt": result.corrupt_partitions,
    }


# ---------------------------------------------------------------------------
# unit: sharding + sizing


def test_shard_partitions_disjoint_cover():
    parts = [0, 2, 3, 7, 9, 11, 40]
    for n in (1, 2, 3, 4, 7, 9):
        groups = shard_partitions(parts, n)
        assert len(groups) == min(n, len(parts))
        flat = sorted(p for g in groups for p in g)
        assert flat == sorted(parts)  # disjoint cover, nothing dropped
        # Round-robin rule matches the mesh data-shard assignment.
        if n <= len(parts):
            assert groups[0][0] == 0
    with pytest.raises(ValueError):
        shard_partitions(parts, 0)


def test_ingest_config_sizing():
    assert IngestConfig.parse("3").resolve(64) == 3
    assert IngestConfig.parse("8").resolve(4) == 4  # clamp to partitions
    auto = IngestConfig.parse("auto").resolve(10**6)
    # auto sizes from SCHEDULABLE cores (cgroup/affinity aware), one short.
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    assert auto == max(1, cores - 1)
    assert IngestConfig.parse("auto").resolve(2) == 2 or auto == 1
    with pytest.raises(ValueError):
        IngestConfig.parse("0")
    with pytest.raises(ValueError):
        IngestConfig.parse("many")


# ---------------------------------------------------------------------------
# determinism: N workers == 1 worker, byte for byte


@pytest.fixture(scope="module")
def baseline():
    """Sequential (N=1) fake-broker scan — the byte-exact referee."""
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        result = _scan(
            KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            )
        )
    assert not result.degraded_partitions
    return _full_doc(result)


@pytest.mark.parametrize("workers", [2, 3, 4])
def test_n_workers_byte_identical_to_sequential(baseline, workers):
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        result = _scan(
            KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            ),
            workers=workers,
        )
    assert result.ingest_workers == workers
    assert _full_doc(result) == baseline


def test_workers_beyond_partitions_clamp(baseline):
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        result = _scan(
            KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            ),
            workers=99,
        )
    assert result.ingest_workers == N_PARTS
    assert _full_doc(result) == baseline


def test_parallel_synthetic_and_staged_backend_deterministic():
    """Cluster-free determinism across worker counts AND the staged
    (prepare-on-worker) path: the TPU backend packs on the ingest workers."""
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend

    spec = SyntheticSpec(
        num_partitions=5, messages_per_partition=1500,
        keys_per_partition=31, seed=3,
    )
    cfg = AnalyzerConfig(
        num_partitions=5, batch_size=256,
        count_alive_keys=True, alive_bitmap_bits=16, enable_hll=True,
    )

    def doc(workers):
        r = run_scan(
            "t", SyntheticSource(spec), TpuBackend(cfg, init_now_s=10**10),
            256, ingest_workers=workers,
        )
        return r.metrics.to_dict(r.start_offsets, r.end_offsets)

    ref = doc(1)
    for n in (2, 3, 5):
        assert doc(n) == ref


# ---------------------------------------------------------------------------
# fault composition: chaos + corruption confined to one worker's partitions


def test_transport_fault_in_one_worker_absorbed(baseline):
    """A connection kill mid-scan (FaultInjector) lands on one worker's
    stream; recovery must keep the N-worker result byte-identical."""
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        src = KafkaWireSource(
            f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
        )
        trigger = ChaosTrigger(
            src, 2,
            lambda: setattr(
                broker, "faults",
                FaultInjector().drop_connection(100, times=2),
            ),
        )
        result = _scan(trigger, workers=3)
    assert not result.degraded_partitions
    assert _full_doc(result) == baseline


def test_degraded_partition_in_one_worker_matches_sequential():
    """Node 1 dies for good: its partitions degrade inside whichever worker
    owns them, the other workers finish exact, and the whole ScanResult
    (including the degraded map) matches the sequential scan under the
    same fault plan."""

    class _ArmOnFirstFetch(FaultInjector):
        """Inert until armed; arming is one atomic Event.set().  A plain
        ``node.faults = FaultInjector()...`` hand-off from the delay
        callback loses a GIL-preemption race: while the arming thread is
        stalled mid-expression, other handler threads' sends still see
        ``faults is None`` and a whole partition can drain and dodge
        degradation.  Pre-installing the injector and gating it on an
        Event leaves no such window — at worst one already-checked
        in-flight response escapes per connection, which cannot finish a
        multi-fetch partition."""

        def __init__(self):
            super().__init__()
            self.armed = threading.Event()
            self.drop_connection(0, times=10**6)
            self.refuse_connections(times=10**6)

        def take_drop(self):
            return super().take_drop() if self.armed.is_set() else None

        def take_refusal(self):
            return super().take_refusal() if self.armed.is_set() else False

    def run(workers):
        inj = _ArmOnFirstFetch()

        def arm_on_first_fetch(api_key: int, node_id: int) -> float:
            if api_key == kc.API_FETCH and node_id == 1:
                inj.armed.set()
            return 0.0

        with FakeCluster(
            TOPIC, RECORDS, n_nodes=2, max_records_per_fetch=60,
            response_delay=arm_on_first_fetch,
        ) as cluster:
            cluster.nodes[1].faults = inj
            src = KafkaWireSource(
                cluster.bootstrap, TOPIC,
                overrides=dict(
                    FAST_RETRY,
                    **{
                        "transport.retry.budget": "3",
                        "socket.timeout.ms": "500",
                    },
                ),
            )
            return _scan(src, workers=workers)

    seq = run(1)
    par = run(3)
    # Reason strings embed each run's ephemeral broker port, so the
    # cross-run comparison is structural: same partitions, same cause.
    assert seq.degraded_partitions
    assert set(par.degraded_partitions) == set(seq.degraded_partitions)
    for p, reason in par.degraded_partitions.items():
        assert "transport failures" in reason
        assert "transport failures" in seq.degraded_partitions[p]
    # Healthy partitions' rows byte-match; the degraded tail undercounts
    # identically (the kill point is deterministic: first fetch to node 1).
    sdoc, pdoc = _full_doc(seq), _full_doc(par)
    healthy = [
        str(p) for p in range(N_PARTS) if p not in seq.degraded_partitions
    ]
    for p in healthy:
        assert pdoc["metrics"]["partitions"][p] == sdoc["metrics"]["partitions"][p]
    assert pdoc["start"] == sdoc["start"] and pdoc["end"] == sdoc["end"]


def test_corruption_in_one_worker_matches_sequential(tmp_path):
    """Deterministic poison in partition 1's chunks (exactly one worker's
    group under N=3) with --on-corruption=quarantine: metrics, the corrupt
    accounting map, and the quarantine spool all match the sequential scan."""
    def poisoned():
        inj = (
            CorruptionInjector()
            .flip_byte(1, chunk=1, offset=-1)     # crc-mismatch
            .flip_byte(1, chunk=3, offset=-3)     # crc-mismatch
        )
        return FakeBroker(
            TOPIC, RECORDS, max_records_per_fetch=50, corruption=inj,
            honor_partition_max_bytes=True,
        )

    def run(workers, qdir):
        with poisoned() as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC,
                overrides=dict(FAST_RETRY, **{"check.crcs": "true"}),
                corruption=CorruptionConfig(
                    policy="quarantine", quarantine_dir=qdir
                ),
            )
            return _scan(src, workers=workers)

    seq = run(1, str(tmp_path / "q1"))
    par = run(3, str(tmp_path / "q3"))
    assert set(seq.corrupt_partitions) == {1}
    assert _full_doc(par) == _full_doc(seq)
    spooled = sorted(os.listdir(tmp_path / "q3"))
    assert spooled == sorted(os.listdir(tmp_path / "q1"))
    assert len([f for f in spooled if f.endswith(".bin")]) == 2


def test_snapshot_offsets_identical_across_worker_counts(tmp_path):
    """Checkpoints stay fold-consistent per partition: the final snapshot's
    resume offsets (and records_seen) are byte-identical for N=1 and N=3."""
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend

    def snap_meta(workers, d):
        with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            )
            run_scan(
                TOPIC, src, TpuBackend(CFG, init_now_s=10**10), 128,
                snapshot_dir=str(d), snapshot_every_s=0.0,
                ingest_workers=workers,
            )
            src.close()
        with np.load(
            os.path.join(str(d), "scan_snapshot.npz"), allow_pickle=False
        ) as z:
            meta = json.loads(str(z["__meta__"]))
        return meta["next_offsets"], meta["records_seen"]

    assert snap_meta(1, tmp_path / "w1") == snap_meta(3, tmp_path / "w3")


# ---------------------------------------------------------------------------
# composed parallelism: mesh x workers x superbatch in ONE scan (PR-7
# tentpole).  The contract (DESIGN.md §14): for any (mesh, workers, K)
# the ScanResult is byte-identical to the sequential single-device scan.

MATRIX_SPEC = SyntheticSpec(
    num_partitions=5, messages_per_partition=1000,
    keys_per_partition=31, tombstone_permille=120, seed=3,
)
MATRIX_BASE = dict(
    num_partitions=5, batch_size=256,
    count_alive_keys=True, alive_bitmap_bits=16, enable_hll=True, hll_p=10,
)


def _composed_backend(mesh, k):
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
    from kafka_topic_analyzer_tpu.config import DispatchConfig

    dispatch = DispatchConfig(superbatch=k, depth=2)
    if mesh == 1:
        return TpuBackend(
            AnalyzerConfig(**MATRIX_BASE), init_now_s=10**10,
            dispatch=dispatch,
        )
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    return ShardedTpuBackend(
        AnalyzerConfig(**MATRIX_BASE, mesh_shape=(mesh, 1)),
        init_now_s=10**10, dispatch=dispatch,
    )


@pytest.fixture(scope="module")
def composed_baseline():
    """Sequential single-device scan — the matrix's byte-exact referee."""
    r = run_scan("t", SyntheticSource(MATRIX_SPEC), _composed_backend(1, 1), 256)
    return _full_doc(r)


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("workers", [1, 2, 4])
@pytest.mark.parametrize("mesh", [1, 2, 4])
def test_composed_matrix_byte_identical(composed_baseline, mesh, workers, k):
    if (mesh, workers, k) == (1, 1, 1):
        return  # the referee itself
    import jax

    if mesh > len(jax.devices()):
        pytest.skip("needs more virtual devices")
    r = run_scan(
        "t", SyntheticSource(MATRIX_SPEC), _composed_backend(mesh, k), 256,
        ingest_workers=workers,
    )
    assert r.superbatch_k == k
    assert _full_doc(r) == composed_baseline
    # The resolved per-controller record always covers this process.
    assert r.ingest_workers_per_controller == [r.ingest_workers]
    if mesh == 1:
        assert r.ingest_workers == min(workers, 5)
    else:
        # Sharded: every fed row needs >= 1 stream, extras go to the rows
        # with the most partitions — never more than one per partition.
        assert min(mesh, 5) <= r.ingest_workers <= 5


def _sharded_wire_backend(k=1):
    """A (2, 1) sharded-mesh backend for the wire tests below: 4
    partitions split rows [0, 2] / [1, 3], so ingest_workers=4 gives each
    row a 2-worker fan-in (the per-controller composition under test)."""
    from kafka_topic_analyzer_tpu.config import DispatchConfig
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    cfg = AnalyzerConfig(
        num_partitions=N_PARTS, batch_size=128,
        count_alive_keys=True, alive_bitmap_bits=16, mesh_shape=(2, 1),
    )
    return ShardedTpuBackend(
        cfg, init_now_s=10**10, dispatch=DispatchConfig(superbatch=k, depth=2)
    )


def test_composed_fault_in_one_worker_absorbed():
    """A transport kill lands inside ONE worker's stream of ONE data
    row's fan-in (mesh 2 x workers 4 x K 2); retry + recovery must keep
    the composed result byte-identical to the sequential sharded scan."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")

    def run(workers, chaos, k=1):
        with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC, overrides=dict(FAST_RETRY)
            )
            feed = src
            if chaos:
                feed = ChaosTrigger(
                    src, 2,
                    lambda: setattr(
                        broker, "faults",
                        FaultInjector().drop_connection(100, times=2),
                    ),
                )
            result = run_scan(
                TOPIC, feed, _sharded_wire_backend(k=k), 128,
                ingest_workers=workers,
            )
            src.close()
        return result

    ref = run(1, chaos=False)
    assert not ref.degraded_partitions
    faulted = run(4, chaos=True, k=2)
    assert not faulted.degraded_partitions
    assert faulted.ingest_workers == 4
    assert _full_doc(faulted) == _full_doc(ref)


def test_composed_corruption_in_one_worker_matches_sequential(tmp_path):
    """Deterministic poison in partition 1 — exactly one worker's group of
    one row's fan-in under mesh 2 x workers 4 — with quarantine: metrics,
    corrupt accounting, and the spool all match the sequential sharded
    scan."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")

    def poisoned():
        inj = (
            CorruptionInjector()
            .flip_byte(1, chunk=1, offset=-1)
            .flip_byte(1, chunk=3, offset=-3)
        )
        return FakeBroker(
            TOPIC, RECORDS, max_records_per_fetch=50, corruption=inj,
            honor_partition_max_bytes=True,
        )

    def run(workers, qdir, k=1):
        with poisoned() as broker:
            src = KafkaWireSource(
                f"127.0.0.1:{broker.port}", TOPIC,
                overrides=dict(FAST_RETRY, **{"check.crcs": "true"}),
                corruption=CorruptionConfig(
                    policy="quarantine", quarantine_dir=qdir
                ),
            )
            result = run_scan(
                TOPIC, src, _sharded_wire_backend(k=k), 128,
                ingest_workers=workers,
            )
            src.close()
        return result

    seq = run(1, str(tmp_path / "q1"))
    par = run(4, str(tmp_path / "q4"), k=2)
    assert set(seq.corrupt_partitions) == {1}
    assert _full_doc(par) == _full_doc(seq)
    assert sorted(os.listdir(tmp_path / "q4")) == sorted(
        os.listdir(tmp_path / "q1")
    )


def test_allocate_row_workers_deterministic():
    from kafka_topic_analyzer_tpu.parallel.ingest import allocate_row_workers

    # Floor: every non-empty row gets a stream even under a tiny budget.
    assert allocate_row_workers(1, {0: 3, 1: 2}) == {0: 1, 1: 1}
    # Extras chase the highest partitions-per-worker ratio, ties by row.
    assert allocate_row_workers(4, {0: 3, 1: 2}) == {0: 2, 1: 2}
    assert allocate_row_workers(5, {0: 3, 1: 2}) == {0: 3, 1: 2}
    # Clamped at the row's partition count; empty rows get nothing.
    assert allocate_row_workers(99, {0: 3, 1: 0, 2: 1}) == {0: 3, 1: 0, 2: 1}
    with pytest.raises(ValueError):
        allocate_row_workers(0, {0: 1})


# ---------------------------------------------------------------------------
# pool mechanics: error propagation, close-on-exit, metrics


class _Boom(Exception):
    pass


class _ExplodingSource(SyntheticSource):
    """batches() dies after 2 batches — but only the stream owning
    ``bad_partition``; other workers' streams run clean."""

    def __init__(self, spec, bad_partition):
        super().__init__(spec)
        self.bad = bad_partition

    def batches(self, batch_size, partitions=None, start_at=None):
        it = super().batches(batch_size, partitions, start_at)
        if partitions is None or self.bad not in partitions:
            yield from it
            return
        for i, b in enumerate(it):
            if i >= 2:
                raise _Boom()
            yield b


class _SlowSource(SyntheticSource):
    def batches(self, batch_size, partitions=None, start_at=None):
        for b in super().batches(batch_size, partitions, start_at):
            time.sleep(0.2)
            yield b


@pytest.mark.parametrize("backend,fails", [
    ("cpu", "worker"),
    # The staged backend places rows at dispatch: a failure in a stream
    # or in a dispatch must stop every thread.
    ("tpu", "worker"),
    ("tpu", "dispatch"),
])
def test_worker_error_aborts_scan_without_leaks(backend, fails):
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend

    spec = SyntheticSpec(num_partitions=4, messages_per_partition=4000)
    cfg = AnalyzerConfig(num_partitions=4, batch_size=128)
    if backend == "cpu":
        backend = CpuExactBackend(cfg, init_now_s=0)
    else:
        backend = TpuBackend(cfg, init_now_s=0)
    source = _ExplodingSource(spec, bad_partition=1)
    if fails == "dispatch":
        # Streams slower than the drive loop: it is waiting on a worker's
        # queue when the scan fails.
        source = _SlowSource(spec)
        update, calls = backend.update, []

        def failing_update(staged):
            calls.append(1)
            if len(calls) > 6:
                raise _Boom()
            update(staged)

        backend.update = failing_update
    before = threading.active_count()
    t0 = time.monotonic()
    with pytest.raises(_Boom):
        run_scan("t", source, backend, 128, ingest_workers=3)
    assert time.monotonic() - t0 < 4  # no join waited out its timeout
    deadline = time.monotonic() + 5
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before


def test_pool_close_cancels_and_closes_streams():
    """Abandoning the fan-in mid-stream closes every worker's underlying
    generator (GeneratorExit), not just the threads."""
    spec = SyntheticSpec(num_partitions=3, messages_per_partition=5000)
    closed = []

    class Tracking(SyntheticSource):
        def batches(self, batch_size, partitions=None, start_at=None):
            try:
                yield from super().batches(batch_size, partitions, start_at)
            finally:
                closed.append(tuple(partitions or ()))

    pool = ParallelIngest(
        Tracking(spec), 64, shard_partitions([0, 1, 2], 3), depth=2
    )
    next(iter(pool))
    pool.close()
    pool.close()  # idempotent
    assert len(closed) == 3


def test_per_worker_telemetry_recorded():
    from kafka_topic_analyzer_tpu.results import IngestStats

    spec = SyntheticSpec(num_partitions=4, messages_per_partition=1000)
    cfg = AnalyzerConfig(num_partitions=4, batch_size=256)
    result = run_scan(
        "t", SyntheticSource(spec), CpuExactBackend(cfg, init_now_s=0),
        256, ingest_workers=2,
    )
    stats = IngestStats.from_telemetry(result.telemetry)
    assert set(stats.workers) >= {"0", "1"}
    assert sum(stats.workers.values()) >= 4000  # cumulative registry


# ---------------------------------------------------------------------------
# CLI surface


@pytest.mark.parametrize("mesh", ["2", "1,2"])
def test_cli_workers_compose_with_sharded_mesh(capsys, mesh):
    """--ingest-workers composes with --mesh (the PR-7 tentpole): the
    sharded scan runs a per-controller fan-in and the --json report
    records the resolved per-controller counts."""
    from kafka_topic_analyzer_tpu import cli

    rc = cli.main([
        "-t", "t", "--source", "synthetic",
        "--synthetic", "partitions=4,messages=2000",
        "--mesh", mesh, "--backend", "tpu",
        "--ingest-workers", "2", "--json", "--quiet",
    ])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert doc["ingest_workers"] == 2
    assert doc["ingest_workers_per_controller"] == [2]


def test_cli_workers_resolve_passthrough_under_mesh():
    """Under a sharded mesh the CLI hands the PARSED IngestConfig to the
    engine unresolved: per-controller resolution needs each controller's
    shard partition count (and its own core count for 'auto'), which the
    CLI cannot know for remote hosts."""
    from kafka_topic_analyzer_tpu.cli import build_parser, resolve_ingest_workers
    from kafka_topic_analyzer_tpu.config import IngestConfig

    args = build_parser().parse_args(
        ["-t", "t", "--ingest-workers", "auto"]
    )
    assert resolve_ingest_workers(args, (2, 1), 64) == IngestConfig("auto")
    assert resolve_ingest_workers(args, (1, 2), 64) == IngestConfig("auto")
    assert resolve_ingest_workers(args, (1, 1), 64) >= 1
    args = build_parser().parse_args(["-t", "t", "--ingest-workers", "3"])
    assert resolve_ingest_workers(args, (4, 1), 64) == IngestConfig(3)
    assert resolve_ingest_workers(args, (1, 1), 64) == 3


def test_cli_rejects_bad_worker_spec(capsys):
    from kafka_topic_analyzer_tpu import cli

    rc = cli.main([
        "-t", "t", "--source", "synthetic",
        "--synthetic", "partitions=4,messages=100",
        "--ingest-workers", "lots", "--quiet",
    ])
    assert rc == 1
    assert "--ingest-workers" in capsys.readouterr().err


def test_cli_stats_and_json_report_worker_count(capsys):
    from kafka_topic_analyzer_tpu import cli

    rc = cli.main([
        "-t", "t", "--source", "synthetic",
        "--synthetic", "partitions=4,messages=2000",
        "--ingest-workers", "3", "--stats", "--json", "--quiet",
    ])
    assert rc == 0
    out = capsys.readouterr()
    doc = json.loads(out.out.splitlines()[-1])
    assert doc["ingest_workers"] == 3
    assert "ingest: 3 worker(s)" in out.err


# ---------------------------------------------------------------------------
# one staging point: the device holds no more staged batches with N streams
# than with one


STAGE_PARTS = 16
STAGE_RECORDS = {p: _mk_records(p, 2000) for p in range(STAGE_PARTS)}
#: A fetch response carries 60 records a partition, under one 1024-record
#: row, so the one-stream path completes at most one row a response and
#: holds, at most, its prefetch depth (2), the row its thread is staging,
#: and the two the drive loop holds around a dispatch.
ONE_STREAM_STAGED = 2 + 1 + 2
_ONE_STREAM: dict = {}


def _staged_rows_peak(monkeypatch, workers):
    """Most packed rows the device held at once while a compacted (-c)
    scan at K=1 read the 16-partition topic, with a drive loop slow enough
    that every queue fills; and the scan's full doc."""
    import weakref

    import jax

    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
    from kafka_topic_analyzer_tpu.packing import packed_nbytes

    backend = TpuBackend(
        AnalyzerConfig(
            num_partitions=STAGE_PARTS, batch_size=1024,
            count_alive_keys=True, alive_bitmap_bits=16,
        ),
        init_now_s=10**10,
    )
    assert backend.superbatch_k == 1 and backend.config.count_alive_keys
    row = (packed_nbytes(backend.config, backend.config.batch_size),)
    lock = threading.Lock()
    live = {"now": 0, "peak": 0}

    def freed():
        with lock:
            live["now"] -= 1

    put = jax.device_put

    def counting_put(x, *args, **kwargs):
        out = put(x, *args, **kwargs)
        if getattr(x, "dtype", None) == np.uint8 and x.shape == row:
            with lock:
                live["now"] += 1
                live["peak"] = max(live["peak"], live["now"])
            weakref.finalize(out, freed)
        return out

    update = backend.update

    def slow_update(staged):
        time.sleep(0.004)
        update(staged)

    backend.update = slow_update
    with monkeypatch.context() as m:
        m.setattr(jax, "device_put", counting_put)
        with FakeBroker(
            TOPIC, STAGE_RECORDS, max_records_per_fetch=60
        ) as broker:
            result = run_scan(
                TOPIC,
                KafkaWireSource(
                    f"127.0.0.1:{broker.port}", TOPIC,
                    overrides=dict(FAST_RETRY),
                ),
                backend, backend.config.batch_size, ingest_workers=workers,
            )
    assert live["now"] == 0
    return live["peak"], result


@pytest.mark.parametrize("workers", [1, 4, "auto"])
def test_device_staged_batches_bounded_by_one_stream(monkeypatch, workers):
    from kafka_topic_analyzer_tpu.config import DispatchConfig

    peak, result = _staged_rows_peak(monkeypatch, workers)
    assert result.ingest_workers == IngestConfig(workers).resolve(STAGE_PARTS)
    assert 1 <= peak <= ONE_STREAM_STAGED
    if result.ingest_workers > 1:
        # Rows are placed at their dispatch: no more than the steps the
        # dispatch depth lets be in flight.
        assert peak <= DispatchConfig().depth
    if not _ONE_STREAM:
        _ONE_STREAM["doc"] = _full_doc(
            _staged_rows_peak(monkeypatch, 1)[1]
        ) if workers != 1 else _full_doc(result)
    assert _full_doc(result) == _ONE_STREAM["doc"]


def test_cli_defaults_to_auto_with_the_sequential_report(capsys):
    """With no flag the CLI runs ``auto`` streams, and its report equals
    ``--ingest-workers 1``'s line for line, keyed (-c --distinct-keys
    --quantiles) and keyless."""
    from kafka_topic_analyzer_tpu import cli

    args = cli.build_parser().parse_args(["-t", "t"])
    assert cli.resolve_ingest_workers(args, (1, 1), 6) == IngestConfig(
        "auto"
    ).resolve(6)
    # A mesh keeps one stream per data row unless asked for more.
    assert cli.resolve_ingest_workers(args, (2, 1), 6) == IngestConfig(1)
    assert IngestConfig().workers == "auto"
    parts = 6
    keyless = {
        p: [(o, ts, None, v) for o, ts, _, v in _mk_records(p, 400)]
        for p in range(parts)
    }
    keyed = {p: _mk_records(p, 400) for p in range(parts)}
    for records, flags in (
        (keyed, ["-c", "--distinct-keys", "--quantiles"]),
        (keyless, ["--quantiles"]),
    ):
        reports = []
        for workers in ([], ["--ingest-workers", "1"]):
            streams = obs_metrics.INGEST_STREAMS.value
            with FakeBroker(
                TOPIC, records, max_records_per_fetch=60
            ) as broker:
                rc = cli.main([
                    "-t", TOPIC, "-b", f"127.0.0.1:{broker.port}",
                    "--backend", "tpu", "--alive-bitmap-bits", "16",
                    "--batch-size", "1024", "--quiet", *flags, *workers,
                ])
            assert rc == 0
            reports.append([
                line for line in capsys.readouterr().out.splitlines()
                if not line.startswith(("Scanning took:", "Estimated Msg/s:"))
            ])
            assert obs_metrics.INGEST_STREAMS.value - streams == (
                1 if workers else IngestConfig("auto").resolve(parts)
            )
        assert reports[0] == reports[1]
        assert any(line.startswith("Topic ") for line in reports[0])


@pytest.mark.parametrize("mesh,workers,streams", [
    (1, 3, 3),
    (1, "auto", None),
    (2, 1, 2),  # every data row opens one stream
    (2, 3, 3),
])
def test_streams_counter_books_the_resolved_count(mesh, workers, streams):
    import jax

    if mesh > len(jax.devices()):
        pytest.skip("needs more virtual devices")
    before = obs_metrics.INGEST_STREAMS.value
    r = run_scan(
        "t", SyntheticSource(MATRIX_SPEC), _composed_backend(mesh, 1), 256,
        ingest_workers=workers,
    )
    if streams is None:
        streams = IngestConfig(workers).resolve(5)
    assert r.ingest_workers == streams
    assert obs_metrics.INGEST_STREAMS.value - before == streams


@pytest.mark.parametrize("mesh", ["1", "2"])
@pytest.mark.parametrize("workers", ["1", "3"])
def test_a_finished_audit_frees_its_backend_without_the_cycle_collector(
    monkeypatch, capsys, mesh, workers
):
    """An audit's device state (the alive bitmap, 512 MiB at the CLI
    default) goes with the audit, whatever the ingest streams: no stream
    keeps the backend in a reference cycle that only a later cyclic
    collection would free, or back-to-back audits would hold several."""
    import gc
    import weakref

    import jax

    from kafka_topic_analyzer_tpu import cli
    from kafka_topic_analyzer_tpu.backends.tpu import TpuBackend
    from kafka_topic_analyzer_tpu.parallel.sharded import ShardedTpuBackend

    if int(mesh) > len(jax.devices()):
        pytest.skip("needs more virtual devices")
    built = []
    for cls in (TpuBackend, ShardedTpuBackend):
        def init(self, *args, _init=cls.__init__, **kwargs):
            _init(self, *args, **kwargs)
            built.append(weakref.ref(self))

        monkeypatch.setattr(cls, "__init__", init)
    with FakeBroker(TOPIC, RECORDS, max_records_per_fetch=60) as broker:
        gc.collect()
        gc.disable()
        try:
            rc = cli.main([
                "-t", TOPIC, "-b", f"127.0.0.1:{broker.port}",
                "--backend", "tpu", "-c", "--alive-bitmap-bits", "16",
                "--batch-size", "1024", "--mesh", mesh,
                "--ingest-workers", workers, "--quiet",
            ])
            capsys.readouterr()
            assert rc == 0 and len(built) == 1
            assert built[0]() is None
        finally:
            gc.enable()
