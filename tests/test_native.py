"""Native C++ ingest shim: bit-parity with the numpy generator and hashers."""

import numpy as np
import pytest

from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.ops.fnv import fnv1a32_ref, fnv1a64
from kafka_topic_analyzer_tpu.records import RecordBatch

native = pytest.importorskip("kafka_topic_analyzer_tpu.io.native")

if not native.native_available():  # pragma: no cover
    pytest.skip("native shim could not be built", allow_module_level=True)

SPEC = SyntheticSpec(
    num_partitions=5,
    messages_per_partition=4_000,
    keys_per_partition=123,
    key_null_permille=70,
    tombstone_permille=130,
    value_len_min=5,
    value_len_max=500,
    seed=0xABCD,
)


def test_native_generator_bit_parity():
    py_src = SyntheticSource(SPEC)
    nat_src = native.NativeSyntheticSource(SPEC)
    a = RecordBatch.concat(list(py_src.batches(1024)))
    b = RecordBatch.concat(list(nat_src.batches(1024)))
    for name, _ in RecordBatch.FIELDS:
        assert np.array_equal(getattr(a, name), getattr(b, name)), name


def test_native_generator_partition_slice_parity():
    py_src = SyntheticSource(SPEC)
    nat_src = native.NativeSyntheticSource(SPEC)
    a = RecordBatch.concat(list(py_src.batches(700, partitions=[1, 4])))
    b = RecordBatch.concat(list(nat_src.batches(700, partitions=[1, 4])))
    for name, _ in RecordBatch.FIELDS:
        assert np.array_equal(getattr(a, name), getattr(b, name)), name


def test_native_hash_batch_matches_scalar():
    rng = np.random.default_rng(1)
    slices = [rng.integers(0, 256, size=int(n), dtype=np.uint8).tobytes()
              for n in rng.integers(0, 40, size=257)]
    data = b"".join(slices)
    offsets = np.zeros(len(slices) + 1, dtype=np.int64)
    np.cumsum([len(s) for s in slices], out=offsets[1:])
    h32, h64 = native.hash_batch_native(data, offsets)
    for i, s in enumerate(slices):
        assert int(h32[i]) == fnv1a32_ref(s)
        assert int(h64[i]) == fnv1a64(s)


@pytest.mark.parametrize("change", ["source", "cpu"])
def test_build_dir_is_keyed_on_source_and_cpu(tmp_path, monkeypatch, change):
    """A changed ingest.cpp, or a CPU with another instruction set (the
    build uses -march=native), selects a new in-tree build path: a binary
    left on disk, or copied with the tree to another machine, is never
    loaded for them."""
    src = tmp_path / "ingest.cpp"
    src.write_text("int kta_version() { return 14; }\n")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_host_isa", lambda: "flags : sse4_2 avx2")
    before = native._build_dir()
    if change == "source":
        src.write_text("int kta_version() { return 14; } // fixed\n")
    else:
        monkeypatch.setattr(native, "_host_isa", lambda: "flags : sse4_2")
    after = native._build_dir()
    assert before != after
    for d in (before, after):
        assert d.startswith(str(tmp_path / "build")), d
        assert f"v{native._ABI}-" in d
