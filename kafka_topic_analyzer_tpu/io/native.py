"""ctypes bindings to the native C++ ingest shim (native/ingest.cpp).

Auto-builds ``libkta_ingest.so`` with the repo's Makefile on first use (g++
is part of the environment; no Python build deps needed).  The native layer
fills caller-allocated numpy buffers directly — zero copies on the Python
side — and is asserted bit-identical to the numpy generator by
tests/test_native.py.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Iterator, List, Optional

import numpy as np

from kafka_topic_analyzer_tpu.io.synthetic import SyntheticSource, SyntheticSpec
from kafka_topic_analyzer_tpu.records import RecordBatch

#: The C++ source ships INSIDE the package (package-data in pyproject) so
#: an installed wheel can build it on first use, not just a checkout.
_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)
#: ABI version baked into the filename (see native/Makefile): a rebuild can
#: never be shadowed by a stale still-mapped library at the same path.
_ABI = 15
_SO_NAME = f"libkta_ingest.v{_ABI}.so"

#: Env knob that disables the native shim entirely (pure-Python chain
#: everywhere, including the fused decode→pack path).  Tier-1 must pass
#: with it set — every native call site keeps a reachable Python fallback
#: (tools/lint.sh rule 6).
_DISABLE_ENV = "KTA_DISABLE_NATIVE"


def _host_isa() -> str:
    """The instruction set a build on this host targets: the Makefile
    compiles with ``-march=native``, so a binary built on one CPU may
    trap (SIGILL) on another.  The CPU flags line names it."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    import platform

    return platform.machine()


def _build_dir() -> str:
    """Where the shim for THIS source on THIS CPU lives: keyed on the ABI
    and a hash of ingest.cpp and the host's instruction set, so a binary
    left on disk (and copied along with the tree to another machine) is
    never loaded in place of one built here from the source beside it —
    the ABI is an interface version, and a source change without an
    interface change must still select a fresh build.  The in-tree build
    dir is preferred; read-only installs (site-packages owned by root,
    containers) fall back to a per-user cache under the same key."""
    import hashlib

    digest = hashlib.sha256(_host_isa().encode())
    with open(os.path.join(_NATIVE_DIR, "ingest.cpp"), "rb") as f:
        digest.update(f.read())
    key = f"v{_ABI}-{digest.hexdigest()[:12]}"
    in_tree = os.path.join(_NATIVE_DIR, "build", key)
    if os.access(_NATIVE_DIR, os.W_OK) or os.path.exists(
        os.path.join(in_tree, _SO_NAME)
    ):
        return in_tree
    return os.path.join(os.path.expanduser("~"), ".cache", "kta-native", key)


_lock = threading.Lock()
_lib: "ctypes.CDLL | None" = None
_load_error: "Exception | None" = None


class _KtaSynthSpec(ctypes.Structure):
    # Mirrors struct KtaSynthSpec in native/ingest.cpp (wire contract).
    _fields_ = [
        ("seed", ctypes.c_uint64),
        ("num_partitions", ctypes.c_int32),
        ("messages_per_partition", ctypes.c_int64),
        ("keys_per_partition", ctypes.c_uint64),
        ("key_null_permille", ctypes.c_int32),
        ("tombstone_permille", ctypes.c_int32),
        ("value_len_min", ctypes.c_int32),
        ("value_len_max", ctypes.c_int32),
        ("key_digits", ctypes.c_int32),
        ("ts_start_ms", ctypes.c_int64),
        ("ts_step_ms", ctypes.c_int64),
    ]


def _build(build_dir: str) -> None:
    """Build into a private directory, then move the library into place:
    processes that build at once (test workers, a broker child and its
    parent) each publish a whole file, and a loader never maps a half
    written one."""
    import shutil

    tmp = f"{build_dir}.tmp-{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "-s", f"BUILD={tmp}"],
            check=True,
            capture_output=True,
            text=True,
        )
        os.makedirs(build_dir, exist_ok=True)
        os.replace(
            os.path.join(tmp, _SO_NAME), os.path.join(build_dir, _SO_NAME)
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def load_library(build_if_missing: bool = True) -> ctypes.CDLL:
    """Load (building if needed) the native shim; raises on failure.

    A failed build/load is cached ONCE, with its reason: hot paths probe
    via `native_available` without re-running `make` every time, and the
    fused-fallback telemetry / ``--stats`` digest surface the cached
    reason class (`native_status`) instead of each call site re-probing.
    """
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            raise _load_error
        try:
            if os.environ.get(_DISABLE_ENV):
                raise RuntimeError(
                    f"native shim disabled via {_DISABLE_ENV}"
                )
            so_path = os.path.join(_build_dir(), _SO_NAME)
            if not os.path.exists(so_path):
                if not build_if_missing:
                    raise FileNotFoundError(so_path)
                _build(os.path.dirname(so_path))
            lib = ctypes.CDLL(so_path)
            lib.kta_version.restype = ctypes.c_int32
            if lib.kta_version() != _ABI:
                raise RuntimeError(
                    f"libkta_ingest ABI mismatch: {so_path} reports "
                    f"{lib.kta_version()}, expected {_ABI}"
                )
            lib.kta_synth_batch.restype = ctypes.c_int32
            lib.kta_hash_batch.restype = ctypes.c_int32
            lib.kta_dedupe_slots.restype = ctypes.c_int64
            lib.kta_pack_batch.restype = ctypes.c_int64
            lib.kta_decode_records.restype = ctypes.c_int64
            lib.kta_scan_record_set.restype = ctypes.c_int64
            lib.kta_decode_record_set.restype = ctypes.c_int64
            lib.kta_crc32c.restype = ctypes.c_uint32
            lib.kta_pack_scratch_len.restype = ctypes.c_int64
            lib.kta_pairs_to_masks.restype = ctypes.c_int64
            lib.kta_pack_row_init.restype = ctypes.c_int64
            lib.kta_decode_pack_record_set.restype = ctypes.c_int64
            lib.kta_pack_append_columns.restype = ctypes.c_int64
        except Exception as e:  # remember the failure
            _load_error = e
            raise
        _lib = lib
        return lib


def native_available() -> bool:
    try:
        load_library()
        return True
    except Exception:
        return False


def native_status() -> "tuple[bool, str]":
    """(available, reason) — the cached load outcome in one probe.

    ``reason`` is a short, bounded label suitable for a metric label or a
    ``--stats`` line: ``""`` when the shim loaded, else one of
    ``disabled`` (KTA_DISABLE_NATIVE), ``build-failed`` (make error),
    ``abi-mismatch``, or ``load-failed`` (missing/undloadable .so).  The
    negative result is cached by `load_library` — probing here never
    re-runs the build."""
    if native_available():
        return True, ""
    err = _load_error
    if isinstance(err, RuntimeError) and _DISABLE_ENV in str(err):
        return False, "disabled"
    if isinstance(err, subprocess.CalledProcessError):
        return False, "build-failed"
    if isinstance(err, RuntimeError) and "ABI mismatch" in str(err):
        return False, "abi-mismatch"
    return False, "load-failed"


def _as_ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def synth_batch_native(
    spec: SyntheticSpec,
    parts: np.ndarray,
    lo: int,
    hi: int,
    threads: int = 0,
) -> RecordBatch:
    """Generate records for global indices [lo, hi) via the C++ shim."""
    lib = load_library()
    n = hi - lo
    if threads <= 0:
        threads = min(os.cpu_count() or 1, 16)
    parts = np.ascontiguousarray(parts, dtype=np.int32)
    out = {name: np.empty(n, dtype=dt) for name, dt in RecordBatch.FIELDS}
    cspec = _KtaSynthSpec(
        seed=spec.seed,
        num_partitions=spec.num_partitions,
        messages_per_partition=spec.messages_per_partition,
        keys_per_partition=spec.keys_per_partition,
        key_null_permille=spec.key_null_permille,
        tombstone_permille=spec.tombstone_permille,
        value_len_min=spec.value_len_min,
        value_len_max=spec.value_len_max,
        key_digits=spec.key_digits,
        ts_start_ms=spec.ts_start_ms,
        ts_step_ms=spec.ts_step_ms,
    )
    rc = lib.kta_synth_batch(
        ctypes.byref(cspec),
        _as_ptr(parts, ctypes.c_int32),
        ctypes.c_int32(len(parts)),
        ctypes.c_int64(lo),
        ctypes.c_int64(hi),
        ctypes.c_int32(threads),
        _as_ptr(out["partition"], ctypes.c_int32),
        _as_ptr(out["key_len"], ctypes.c_int32),
        _as_ptr(out["value_len"], ctypes.c_int32),
        _as_ptr(out["key_null"], ctypes.c_uint8),
        _as_ptr(out["value_null"], ctypes.c_uint8),
        _as_ptr(out["ts_s"], ctypes.c_int64),
        _as_ptr(out["key_hash32"], ctypes.c_uint32),
        _as_ptr(out["key_hash64"], ctypes.c_uint64),
        _as_ptr(out["valid"], ctypes.c_uint8),
    )
    if rc != 0:
        raise RuntimeError(f"kta_synth_batch failed with rc={rc}")
    return RecordBatch(**out)


def hash_batch_native(
    data: bytes | np.ndarray, offsets: np.ndarray, threads: int = 0
) -> "tuple[np.ndarray, np.ndarray]":
    """Hash n packed byte slices: returns (fnv32-variant, fnv64) arrays."""
    lib = load_library()
    if threads <= 0:
        threads = min(os.cpu_count() or 1, 16)
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, bytes) else data
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    h32 = np.empty(n, dtype=np.uint32)
    h64 = np.empty(n, dtype=np.uint64)
    rc = lib.kta_hash_batch(
        _as_ptr(buf, ctypes.c_uint8),
        _as_ptr(offsets, ctypes.c_int64),
        ctypes.c_int64(n),
        ctypes.c_int32(threads),
        _as_ptr(h32, ctypes.c_uint32),
        _as_ptr(h64, ctypes.c_uint64),
    )
    if rc != 0:
        raise RuntimeError(f"kta_hash_batch failed with rc={rc}")
    return h32, h64


def dedupe_slots_native(
    h32: np.ndarray, active: np.ndarray, alive: np.ndarray, bits: int
) -> "tuple[np.ndarray, np.ndarray]":
    """Last-writer-wins (slot, aliveness) dedupe via the C++ shim.

    NOTE: pair order differs from the numpy implementation (first-touch vs
    sorted) — callers must not rely on ordering, only on the set semantics.
    """
    lib = load_library()
    n = len(h32)
    h32 = np.ascontiguousarray(h32, dtype=np.uint32)
    active = np.ascontiguousarray(active, dtype=np.uint8)
    alive = np.ascontiguousarray(alive, dtype=np.uint8)
    slot_out = np.empty(n, dtype=np.uint32)
    alive_out = np.empty(n, dtype=np.uint8)
    count = lib.kta_dedupe_slots(
        _as_ptr(h32, ctypes.c_uint32),
        _as_ptr(active, ctypes.c_uint8),
        _as_ptr(alive, ctypes.c_uint8),
        ctypes.c_int64(n),
        ctypes.c_int32(bits),
        _as_ptr(slot_out, ctypes.c_uint32),
        _as_ptr(alive_out, ctypes.c_uint8),
    )
    if count < 0:
        raise RuntimeError(f"kta_dedupe_slots failed with rc={count}")
    return slot_out[:count], alive_out[:count]


def pairs_to_masks_native(
    slots: np.ndarray,
    flags: np.ndarray,
    bits: int,
    set_out: np.ndarray,
    clear_out: np.ndarray,
) -> int:
    """LWW-apply a raw (slot, flag) pair stream — stream order, duplicates
    allowed — straight into zeroed set/clear word masks (the compacted
    alive table's MASK form, packing.alive_table_mode == 2).  Returns the
    distinct touched-slot count (emitted-pairs telemetry)."""
    lib = load_library()
    slots = np.ascontiguousarray(slots, dtype=np.uint32)
    flags = np.ascontiguousarray(flags, dtype=np.uint8)
    touched = lib.kta_pairs_to_masks(
        _as_ptr(slots, ctypes.c_uint32),
        _as_ptr(flags, ctypes.c_uint8),
        ctypes.c_int64(len(slots)),
        ctypes.c_int32(bits),
        _as_ptr(set_out, ctypes.c_uint32),
        _as_ptr(clear_out, ctypes.c_uint32),
    )
    if touched < 0:
        raise RuntimeError(f"kta_pairs_to_masks failed rc={touched}")
    return int(touched)


#: The decoder's SoA layout — ONE spec for every allocation site (per-frame
#: decode, record-set decode, the marker-only empty result).
_SOA_COLUMNS = (
    ("offsets", np.int64),
    ("ts_ms", np.int64),
    ("key_len", np.int32),
    ("value_len", np.int32),
    ("key_null", np.uint8),
    ("value_null", np.uint8),
    ("key_hash32", np.uint32),
    ("key_hash64", np.uint64),
)


def _soa_columns(n: int) -> "dict[str, np.ndarray]":
    return {k: np.empty(n, dtype=d) for k, d in _SOA_COLUMNS}


def decode_records_native(frame) -> "dict[str, np.ndarray] | None":
    """Decode one RecordBatch v2 frame (kafka_codec.BatchFrame) into SoA
    columns with key hashes computed inline — the wire client's hot half
    (the Python per-record generator manages ~225k records/s; this runs at
    tens of millions).  Returns None on malformed input so the caller can
    fall back to the Python decoder for a precise error."""
    if getattr(frame, "legacy_records", None) is not None:
        return None  # MessageSet v0/v1: the Python per-record path decodes
    lib = load_library()
    n = frame.num_records
    # num_records is an untrusted wire field: a valid record needs >= 7
    # payload bytes, so a count beyond len/7 is malformed — reject BEFORE
    # sizing eight output arrays by it (a hostile header could otherwise
    # demand ~80 GB of allocations).
    if n > max(len(frame.payload) // 7, 0):
        return None
    payload = np.frombuffer(frame.payload, dtype=np.uint8)
    out = _soa_columns(n)
    rc = lib.kta_decode_records(
        _as_ptr(payload, ctypes.c_uint8),
        ctypes.c_int64(len(payload)),
        ctypes.c_int32(n),
        ctypes.c_int64(frame.base_offset),
        ctypes.c_int64(frame.first_ts),
        _as_ptr(out["offsets"], ctypes.c_int64),
        _as_ptr(out["ts_ms"], ctypes.c_int64),
        _as_ptr(out["key_len"], ctypes.c_int32),
        _as_ptr(out["value_len"], ctypes.c_int32),
        _as_ptr(out["key_null"], ctypes.c_uint8),
        _as_ptr(out["value_null"], ctypes.c_uint8),
        _as_ptr(out["key_hash32"], ctypes.c_uint32),
        _as_ptr(out["key_hash64"], ctypes.c_uint64),
    )
    if rc != n:
        return None
    return out


def scan_record_set_native(
    buf, verify_crc: bool = False
) -> "tuple[int, int, int]":
    """Header-jump walk of a record set's native-decodable prefix:
    (record_count, consumed_bytes, covered_end) without touching records.
    The wire client's send-ahead uses covered_end as the speculative next
    fetch offset while the full decode proceeds."""
    lib = load_library()
    data = np.frombuffer(buf, dtype=np.uint8)
    consumed = ctypes.c_int64(0)
    covered = ctypes.c_int64(-1)
    n = lib.kta_scan_record_set(
        _as_ptr(data, ctypes.c_uint8),
        ctypes.c_int64(len(data)),
        ctypes.c_int32(1 if verify_crc else 0),
        ctypes.byref(consumed),
        ctypes.byref(covered),
    )
    if n < 0:
        return 0, 0, -1
    return int(n), int(consumed.value), int(covered.value)


def decode_record_set_native(
    buf,
    verify_crc: bool = False,
    prescan: "tuple[int, int, int] | None" = None,
) -> "tuple[dict[str, np.ndarray], int, int] | None":
    """Decode the native-decodable PREFIX of a whole fetch record set
    (consecutive complete uncompressed v2 frames) in one C++ call.

    Returns (SoA columns, consumed_bytes, covered_end) — covered_end is
    the compaction-aware max of base_offset+last_offset_delta+1 across
    decoded frames (-1 when none).  None when the shim is unavailable.
    Frames past `consumed` (compressed, legacy MessageSet, truncated tail,
    malformed) are the caller's per-frame path; a malformed frame inside
    the prefix returns consumed=0 so that path can raise precisely.

    ``prescan``: a scan_record_set_native result for this buffer, so a
    caller that already walked the headers (the send-ahead speculation)
    doesn't pay the scan — or its CRC pass — a second time."""
    lib = load_library()
    data = np.frombuffer(buf, dtype=np.uint8)
    consumed = ctypes.c_int64(0)
    scan_covered = ctypes.c_int64(-1)
    if prescan is not None:
        n = prescan[0]
        verify_crc = False  # the prescan already checksummed the prefix
        consumed.value, scan_covered.value = prescan[1], prescan[2]
    else:
        n = lib.kta_scan_record_set(
            _as_ptr(data, ctypes.c_uint8),
            ctypes.c_int64(len(data)),
            ctypes.c_int32(1 if verify_crc else 0),
            ctypes.byref(consumed),
            ctypes.byref(scan_covered),
        )
    if n < 0:
        return {}, 0, -1
    if n == 0:
        # No messages in the decodable prefix, but it may still cover
        # offsets (a transaction-marker-only stretch): the caller must
        # advance past it, so consumed/covered ride along with empty
        # columns.
        return _soa_columns(0), int(consumed.value), int(scan_covered.value)
    out = _soa_columns(n)
    covered = ctypes.c_int64(-1)
    rc = lib.kta_decode_record_set(
        _as_ptr(data, ctypes.c_uint8),
        ctypes.c_int64(len(data)),
        ctypes.c_int32(1 if verify_crc else 0),
        ctypes.c_int64(n),
        _as_ptr(out["offsets"], ctypes.c_int64),
        _as_ptr(out["ts_ms"], ctypes.c_int64),
        _as_ptr(out["key_len"], ctypes.c_int32),
        _as_ptr(out["value_len"], ctypes.c_int32),
        _as_ptr(out["key_null"], ctypes.c_uint8),
        _as_ptr(out["value_null"], ctypes.c_uint8),
        _as_ptr(out["key_hash32"], ctypes.c_uint32),
        _as_ptr(out["key_hash64"], ctypes.c_uint64),
        ctypes.byref(consumed),
        ctypes.byref(covered),
    )
    if rc != n:
        return {}, 0, -1  # malformed inside prefix: per-frame path reports
    return out, int(consumed.value), int(covered.value)


def _quant_section(config) -> "tuple[int, int, np.ndarray | None]":
    """(q_rows, q_nbuckets, edges) for the DDSketch section — (0, 0,
    None) when the config ships no quantile table.  The edge array is the
    ddsketch_edges lru-cached singleton, so the pointer handed to C++
    stays alive for the process lifetime."""
    if not config.enable_quantiles:
        return 0, 0, None
    from kafka_topic_analyzer_tpu.ops.ddsketch import ddsketch_edges

    q_rows = config.num_partitions if config.quantiles_per_partition else 1
    return (
        q_rows,
        config.quantile_buckets,
        ddsketch_edges(config.quantile_gamma, config.quantile_buckets),
    )


def _edges_ptr(edges: "np.ndarray | None"):
    if edges is None:
        return ctypes.POINTER(ctypes.c_int64)()
    return _as_ptr(edges, ctypes.c_int64)


def pack_batch_native(
    batch, config, out: "np.ndarray | None" = None
) -> "np.ndarray | None":
    """Fused SoA→packed-row packing in C++ (packing.py's module docstring
    is the layout contract).  Returns None when the shim rejects the batch
    (out of range values) so the numpy path can raise its descriptive
    error.
    ``out`` packs into a caller-provided contiguous ``uint8[packed_nbytes]``
    buffer (e.g. a SuperbatchStager row) instead of allocating one — note
    that a rejected batch may leave partial bytes in it (the numpy
    fallback overwrites every byte before raising or returning)."""
    from kafka_topic_analyzer_tpu.packing import (
        hll_table_rows,
        hll_wire_mode,
        packed_nbytes,
    )

    lib = load_library()
    b = config.batch_size
    n = len(batch)
    if n > b:
        raise ValueError(f"batch of {n} exceeds batch_size {b}")
    hll_rows = hll_table_rows(config, b)
    q_rows, q_nb, edges = _quant_section(config)
    if out is None:
        out = np.empty(packed_nbytes(config, b), dtype=np.uint8)
    elif (
        out.shape != (packed_nbytes(config, b),)
        or out.dtype != np.uint8
        or not out.flags.c_contiguous
    ):
        raise ValueError(
            "pack_batch_native out= must be contiguous uint8[packed_nbytes]"
        )
    c = np.ascontiguousarray  # strided views would be read with wrong strides
    nbytes = lib.kta_pack_batch(
        _as_ptr(c(batch.partition), ctypes.c_int32),
        _as_ptr(c(batch.key_len), ctypes.c_int32),
        _as_ptr(c(batch.value_len), ctypes.c_int32),
        _as_ptr(c(batch.key_null).view(np.uint8), ctypes.c_uint8),
        _as_ptr(c(batch.value_null).view(np.uint8), ctypes.c_uint8),
        _as_ptr(c(batch.ts_s), ctypes.c_int64),
        _as_ptr(c(batch.key_hash64), ctypes.c_uint64),
        ctypes.c_int64(batch.num_valid),
        ctypes.c_int64(b),
        ctypes.c_int32(config.num_partitions),
        ctypes.c_int32(hll_wire_mode(config, b)),
        ctypes.c_int32(config.hll_p),
        ctypes.c_int32(hll_rows),
        ctypes.c_int32(q_rows),
        ctypes.c_int32(q_nb),
        _edges_ptr(edges),
        _as_ptr(out, ctypes.c_uint8),
        ctypes.c_int64(out.nbytes),
    )
    if nbytes < 0:
        return None
    assert nbytes == out.nbytes, (nbytes, out.nbytes)
    return out


# ---------------------------------------------------------------------------
# fused decode→pack (native/ingest.cpp fused entry points)
#
# One GIL-released C++ pass from raw record-set bytes (or already-decoded
# SoA columns on the fallback half) straight into a packed row —
# the SoA materialization between kta_decode_record_set and kta_pack_batch
# never happens.  packing.FusedPackSink owns row/scratch lifecycle; these
# are the thin ctypes wrappers.  Every caller keeps a reachable
# python-chain fallback (lint rule 6): a missing shim degrades to the
# decode→RecordBatch→pack_batch chain, never to an error.


def _fused_pack_params(config, batch_size: int) -> "tuple":
    """The (b, P, with_alive, alive_bits, with_hll, hll_p, hll_rows,
    q_rows, q_nbuckets, edges) tail shared by the fused entry
    points — derived through the same packing.py rules as
    pack_batch_native, so the fused row layout can never skew from the
    chained one."""
    from kafka_topic_analyzer_tpu.packing import hll_table_rows, hll_wire_mode

    q_rows, q_nb, edges = _quant_section(config)
    return (
        batch_size,
        config.num_partitions,
        1 if config.count_alive_keys else 0,
        config.alive_bitmap_bits,
        hll_wire_mode(config, batch_size),
        config.hll_p,
        hll_table_rows(config, batch_size),
        q_rows,
        q_nb,
        edges,
    )


def _fused_ctail(params) -> "list":
    b, P, wa, ab, wh, hp, hr, qr, qn, edges = params
    return [
        ctypes.c_int64(b), ctypes.c_int32(P), ctypes.c_int32(wa),
        ctypes.c_int32(ab), ctypes.c_int32(wh), ctypes.c_int32(hp),
        ctypes.c_int32(hr), ctypes.c_int32(qr), ctypes.c_int32(qn),
        _edges_ptr(edges),
    ]


def _raise_pack_range(value: int) -> None:
    """Map the fused pass's pack-range error detail (the offending length)
    onto the SAME ValueError messages packing.pack_batch raises, so a scan
    aborts identically whichever path met the out-of-range record."""
    from kafka_topic_analyzer_tpu.packing import MAX_KEY_LEN

    if value < 0:
        raise ValueError("negative key/value length in record batch")
    raise ValueError(
        f"key length {int(value)} exceeds the packed "
        f"transfer limit of {MAX_KEY_LEN} bytes"
    )


def pack_scratch_len(config, batch_size: int) -> int:
    """int64 elements of append scratch one fused row needs (includes the
    alive-pair emission region for alive-key scans)."""
    lib = load_library()
    n = lib.kta_pack_scratch_len(
        ctypes.c_int64(batch_size),
        ctypes.c_int32(1 if config.count_alive_keys else 0),
        ctypes.c_int32(config.alive_bitmap_bits),
    )
    if n < 0:
        raise RuntimeError("kta_pack_scratch_len rejected batch_size")
    return int(n)


def pack_take_pairs(
    scratch: np.ndarray, batch_size: int
) -> "tuple[np.ndarray, np.ndarray]":
    """Copy the current row's (slot, alive) pairs out of the fused
    scratch's emission region.  Callers harvest BEFORE the next
    ``pack_row_init`` re-initializes the scratch; the returned arrays are
    copies, safe past the row's lifetime.  The region is the scratch's
    tail — its last ``ceil(5·B / 8)`` int64 elements, by the shim's
    layout contract (``pack_scratch_len`` sizes the scratch exactly)."""
    n = int(scratch[1])
    region = scratch[len(scratch) - (5 * batch_size + 7) // 8 :].view(np.uint8)
    slots = region[: 4 * batch_size].view(np.uint32)[:n].copy()
    flags = region[4 * batch_size : 5 * batch_size][:n].copy()
    return slots, flags


def pack_row_init(out: np.ndarray, scratch: np.ndarray, config,
                  batch_size: int) -> None:
    """Initialize a packed row for incremental fused appends.  The
    initialized row is byte-identical to a packed empty batch, so it
    doubles as the partial-row / superbatch identity pad."""
    lib = load_library()
    need = lib.kta_pack_row_init(
        _as_ptr(out, ctypes.c_uint8),
        ctypes.c_int64(out.nbytes),
        _as_ptr(scratch, ctypes.c_int64),
        ctypes.c_int64(len(scratch)),
        *_fused_ctail(_fused_pack_params(config, batch_size)),
    )
    if need != out.nbytes:
        raise RuntimeError(
            f"kta_pack_row_init layout mismatch: need={need}, "
            f"buffer={out.nbytes}"
        )


def decode_pack_record_set_native(
    data: np.ndarray,
    out: np.ndarray,
    scratch: np.ndarray,
    config,
    batch_size: int,
    dense_partition: int,
    min_off: int,
    max_off: int,
    verify_crc: bool = False,
    start_pos: int = 0,
    skip: int = 0,
) -> "tuple[int, int, int, int, int, bool, int]":
    """Fused decode→pack over a record set's native-decodable prefix.

    Returns ``(appended, consumed, covered_end, last_off, last_ts_s,
    row_full, resume_skip)`` — on ``row_full`` the caller rotates rows and
    re-calls with ``start_pos=consumed, skip=resume_skip``.  A malformed
    frame ends the walk at its boundary (the per-frame python chain
    classifies it from ``consumed``); a record the packed layout cannot
    carry raises the same ValueError the numpy packer would."""
    lib = load_library()
    st = np.zeros(8, dtype=np.int64)
    st[4] = skip
    rc = lib.kta_decode_pack_record_set(
        _as_ptr(data, ctypes.c_uint8),
        ctypes.c_int64(len(data)),
        ctypes.c_int32(1 if verify_crc else 0),
        ctypes.c_int64(start_pos),
        ctypes.c_int64(min_off),
        ctypes.c_int64(max_off),
        ctypes.c_int32(dense_partition),
        *_fused_ctail(_fused_pack_params(config, batch_size)),
        _as_ptr(out, ctypes.c_uint8),
        ctypes.c_int64(out.nbytes),
        _as_ptr(scratch, ctypes.c_int64),
        _as_ptr(st, ctypes.c_int64),
    )
    if rc == -2:
        _raise_pack_range(int(st[7]))
    if rc < 0:
        raise RuntimeError(f"kta_decode_pack_record_set failed rc={rc}")
    return (
        int(rc), int(st[0]), int(st[1]), int(st[2]), int(st[3]),
        bool(st[5]), int(st[4]),
    )


def pack_append_columns_native(
    out: np.ndarray,
    scratch: np.ndarray,
    config,
    batch_size: int,
    dense_partition: int,
    key_len: np.ndarray,
    value_len: np.ndarray,
    key_null: np.ndarray,
    value_null: np.ndarray,
    ts: np.ndarray,
    key_hash32: np.ndarray,
    key_hash64: np.ndarray,
    start: int,
    n: int,
    ts_mode: int = 0,
) -> int:
    """Append records ``[start, n)`` — ``n`` is the EXCLUSIVE end index
    into the columns, not a count — of single-partition SoA columns into a
    fused row (stops at row capacity; returns appended count).
    ``ts_mode``: 0 = ts[] already seconds, 1 = ms floor-divided (segment
    reader rule), 2 = ms clamped at 0 then divided (wire decoder rule)."""
    lib = load_library()
    c = np.ascontiguousarray
    detail = np.zeros(2, dtype=np.int64)
    rc = lib.kta_pack_append_columns(
        _as_ptr(out, ctypes.c_uint8),
        ctypes.c_int64(out.nbytes),
        _as_ptr(scratch, ctypes.c_int64),
        ctypes.c_int32(dense_partition),
        _as_ptr(c(key_len, dtype=np.int32), ctypes.c_int32),
        _as_ptr(c(value_len, dtype=np.int32), ctypes.c_int32),
        _as_ptr(c(key_null).view(np.uint8), ctypes.c_uint8),
        _as_ptr(c(value_null).view(np.uint8), ctypes.c_uint8),
        _as_ptr(c(ts, dtype=np.int64), ctypes.c_int64),
        ctypes.c_int32(ts_mode),
        _as_ptr(c(key_hash32, dtype=np.uint32), ctypes.c_uint32),
        _as_ptr(c(key_hash64, dtype=np.uint64), ctypes.c_uint64),
        ctypes.c_int64(start),
        ctypes.c_int64(n),
        *_fused_ctail(_fused_pack_params(config, batch_size)),
        _as_ptr(detail, ctypes.c_int64),
    )
    if rc == -2:
        _raise_pack_range(int(detail[1]))
    if rc < 0:
        raise RuntimeError(f"kta_pack_append_columns failed rc={rc}")
    return int(rc)


class NativeSyntheticSource(SyntheticSource):
    """SyntheticSource with generation delegated to the C++ shim.

    Identical stream to the numpy implementation (asserted by parity tests);
    an order of magnitude faster, which matters when the host generator must
    keep a TPU fed (SURVEY.md §7 hard part (a)).
    """

    def __init__(self, spec: SyntheticSpec, threads: int = 0):
        super().__init__(spec)
        self.threads = threads
        load_library()  # fail fast if the shim cannot be built

    def batches(
        self,
        batch_size: int,
        partitions: Optional[List[int]] = None,
        start_at: "Optional[dict[int, int]] | None" = None,
    ) -> Iterator[RecordBatch]:
        parts = np.array(
            sorted(partitions) if partitions is not None else self.partitions(),
            dtype=np.int32,
        )
        if len(parts) == 0:
            return
        n = self.spec.messages_per_partition
        if start_at:
            # Partition-sequential resume: with a single partition, the
            # global index equals the offset.
            for p in parts.tolist():
                one = np.array([p], dtype=np.int32)
                for lo in range(min(start_at.get(p, 0), n), n, batch_size):
                    yield synth_batch_native(
                        self.spec, one, lo, min(lo + batch_size, n), self.threads
                    )
            return
        total = n * len(parts)
        for lo in range(0, total, batch_size):
            yield synth_batch_native(
                self.spec, parts, lo, min(lo + batch_size, total), self.threads
            )
