"""bench.py output contract: a result line that names its device, and
setup-phase error messages that name the offending key."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_result_names_its_device():
    """The result line says what it was measured on, as JAX reports it:
    a host-CPU run reads as platform "cpu", never as a chip number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--batch-size", "2048", "--batches", "2", "--steps", "4",
         "--partitions", "4", "--features", "counters"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 1, proc.stdout
    doc = json.loads(lines[-1])
    assert doc["platform"] == "cpu"
    assert doc["device_kind"] == "cpu"
    assert doc["device_count"] >= 1
    assert "transfer_gbps" not in doc


def test_accuracy_seed_referee_matches_main_run_cardinality():
    """The per-seed sketch-error referee must run at the SAME dataset size
    as the main draw (HLL error depends on cardinality — r4 weak #5), and
    the recorded JSON must say what N each seed used."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--batch-size", "2048", "--batches", "6", "--steps", "6",
         "--partitions", "4", "--features", "counters,hll",
         "--keys", "5000", "--accuracy", "--accuracy-seeds", "1"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    doc = json.loads(lines[-1])
    # default: per-seed batch count == the main run's --batches (not a cap)
    assert doc["accuracy_seed_batches"] == 6
    assert doc["accuracy_seed_records"] == 6 * 2048
    assert len(doc["hll_rel_error_seeds"]) == 1


def test_synthetic_kv_errors_name_the_key():
    from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSpec

    with pytest.raises(ValueError, match=r"key_null.*per-mille.*'0\.05'"):
        SyntheticSpec.from_kv({"key_null": "0.05"})
    with pytest.raises(ValueError, match=r"'tombstones'.*per-mille"):
        SyntheticSpec.from_kv({"tombstones": "1500"})  # out of 0..1000
    with pytest.raises(ValueError, match=r"unknown --synthetic key 'mesages'"):
        SyntheticSpec.from_kv({"mesages": "10"})
    with pytest.raises(ValueError, match=r"'partitions'.*integer.*'two'"):
        SyntheticSpec.from_kv({"partitions": "two"})
    with pytest.raises(ValueError, match=r"'partitions'.*positive"):
        SyntheticSpec.from_kv({"partitions": "0"})
    with pytest.raises(ValueError, match=r"'keys'.*positive"):
        SyntheticSpec.from_kv({"keys": "0"})
    with pytest.raises(ValueError, match=r"'vmax'.*>= vmin"):
        SyntheticSpec.from_kv({"vmin": "400", "vmax": "100"})
    # vmin alone above the default vmax means fixed-size values, not an error
    spec = SyntheticSpec.from_kv({"vmin": "500"})
    assert (spec.value_len_min, spec.value_len_max) == (500, 500)
    # trailing comma (empty key) stays accepted
    SyntheticSpec.from_kv({"partitions": "2", "": ""})
    # hex seeds stay accepted
    assert SyntheticSpec.from_kv({"seed": "0x10"}).seed == 0x10


def test_cli_reports_synthetic_kv_error_cleanly(capsys):
    from kafka_topic_analyzer_tpu.cli import main

    rc = main([
        "-t", "t", "--source", "synthetic",
        "--synthetic", "key_null=0.05", "--quiet", "--native", "off",
    ])
    assert rc == 1
    err = capsys.readouterr().err
    assert "key_null" in err and "per-mille" in err and "0.05" in err
    assert "Traceback" not in err


def test_mesh_parse_error_names_the_flag():
    from kafka_topic_analyzer_tpu.cli import parse_mesh

    with pytest.raises(ValueError, match=r"--mesh '4x2'.*device"):
        parse_mesh("4x2")
    with pytest.raises(ValueError, match=r"--mesh '1,2,3'"):
        parse_mesh("1,2,3")
    with pytest.raises(ValueError, match=r"--mesh '0'.*positive"):
        parse_mesh("0")
    with pytest.raises(ValueError, match=r"--mesh '-4,2'.*positive"):
        parse_mesh("-4,2")


@pytest.mark.ingest
def test_bench_ingest_workers_smoke(capsys):
    """--workers N drives the real fan-in over a loopback broker and
    reports aggregate + per-worker rates."""
    import json as _json

    from kafka_topic_analyzer_tpu.tools import bench_ingest

    rc = bench_ingest.main([
        "--records", "120000", "--records-per-batch", "512",
        "--partitions", "4", "--batch-size", "4096",
        "--repeat", "1", "--skip-drain", "--workers", "2",
    ])
    assert rc == 0
    doc = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["workers"] == 2
    assert doc["scan_msgs_per_sec"] > 0
    assert set(doc["scan_worker_records"]) == {"0", "1"}
    # windows = 120000 // (4 partitions * 512 rpb) = 58 -> 58*512*4 records
    assert sum(doc["scan_worker_records"].values()) == 58 * 512 * 4
