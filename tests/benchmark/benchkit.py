"""Helpers for the benchmark's tests: a tiny cell added to a copy of the
benchmark by files alone, and a harness run of it on the CPU."""

from __future__ import annotations

import contextlib
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny-changelog",
    "source": "test-only shape of changelog-ref",
    "partitions": 3,
    "records": 4000,
    "key_bytes": 9,
    "key_ids": 1500,
    "key_zipf_s": 0.99,
    "partitioner": "murmur2",
    "tombstone_share": 0.1,
    "message_bytes_min": 139,
    "message_bytes_mean": 270,
    "message_bytes_max": 750,
    "records_per_batch": 58,
    "produce_rate_per_s": 1000,
    "alive_bitmap_bits": 32,
    "hll_p": 16,
    "quantile_alpha": 0.005,
    "quantile_buckets": 2560,
    "reduced": ["records"],
}
TINY_TRAFFIC = {
    "why": "test-only: every sketch on, small device batches",
    "flags": ["-c", "--distinct-keys", "--quantiles", "--batch-size", "1024"],
    "warmup_records": 3072,
}
TINY_METRIC = '''
def read(run):
    return float(len(run.audits))
'''


with open(os.path.join(REPO, "benchmark", "configs",
                       "omb-1topic-16p-1kb.json")) as _f:
    #: ``omb-1topic-16p-1kb`` with the producer's ``compression.type=zstd``,
    #: cut to 4,096 records: 16 partitions of six batches.
    TINY_ZSTD_CONFIG = dict(json.load(_f), name="tiny-omb-zstd",
                            records=4096, compression="zstd")
TINY_ZSTD_TRAFFIC = {
    "why": "test-only: counters and size quantiles, small device batches",
    "flags": ["--quantiles", "--batch-size", "1024"],
    "warmup_records": 1600,
}


def add_cell(root: str, workload: str, config: dict, traffic: dict,
             chips: int = 1) -> dict:
    """Add the cell ``workload`` to the benchmark copy at ``root`` by
    writing its configuration and traffic files and their manifest
    entries; returns the manifest, written."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", f"{config['name']}.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", f"{workload}.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": config["name"], "source": "test",
        "file": f"benchmark/configs/{config['name']}.json",
        "reduced": ["records"], "why": "test"})
    doc["workloads"].append({
        "name": workload, "config": config["name"], "traffic": workload,
        "chips": chips, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return doc


def copy_with_tiny_cell(dest: str) -> str:
    """A copy of the benchmark under ``dest`` with one more cell,
    ``tiny-alive``, added by writing files and manifest entries only."""
    root = os.path.join(dest, "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "benchmark", "metrics", "audits_run.py"),
              "w") as f:
        f.write(TINY_METRIC)
    doc = add_cell(root, "tiny-alive", TINY_CONFIG, TINY_TRAFFIC)
    doc["per_layer"].append({
        "name": "audits_run", "unit": "audits", "better": "higher",
        "source": "host_clock", "layer": "test", "moves":
        "audit_records_per_s", "workloads": ["tiny-alive"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def copy_with_tiny_zstd_cell(dest: str) -> str:
    """The tiny cell's copy of the benchmark with one more cell,
    ``tiny-zstd``, a zstd-compressed topic, added by files and manifest
    entries only."""
    root = copy_with_tiny_cell(dest)
    add_cell(root, "tiny-zstd", TINY_ZSTD_CONFIG, TINY_ZSTD_TRAFFIC)
    return root


@contextlib.contextmanager
def compile_cache(path: str):
    """JAX's persistent compilation cache in ``path`` for the duration: the
    program builds new jitted steps in every audit, and only the cache keeps
    them from compiling again inside the window."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    import kafka_topic_analyzer_tpu.jax_support  # noqa: F401  (sets a dir)

    old = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old[0])
        jax.config.update("jax_compilation_cache_dir", old[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[2])
        cc.reset_cache()


def run_tiny(tmp_path, seed: int = 2**31 + 11, trace: bool = False) -> dict:
    from benchmark import harness

    root = copy_with_tiny_cell(str(tmp_path))
    with compile_cache(str(tmp_path / "jax_cache")):
        return harness.run(root, "tiny-alive", seed, 0.5, trace,
                           chip_check=False)
