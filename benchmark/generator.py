"""Seeded topic generator: record arrays from a configuration, and the
Kafka v2 record batches a broker serves for them.

Everything is vectorised numpy, and partitions are built and encoded on
threads, so a topic of 2^24 records is built in seconds.  The same seed
gives the same topic.  Every seed gives the same multiset of key ranks;
each partition's value sizes and tombstones are drawn at a quantile grid
of its record count.  The seed chooses which key id carries which rank
(and so which partition it lands in) and the order of the records, so
seeds differ in placement and hardly in the amount of work.

A configuration (``benchmark/configs/<name>.json``) fixes:

- ``partitions``, ``records``: topic shape;
- ``key_bytes``: 0 for keyless records (null keys), else keys are
  ``b"k"`` followed by ``key_bytes - 1`` decimal digits of the key id;
- ``key_ids``, ``key_zipf_s``: key ids are drawn Zipf(s) over
  ``key_ids`` ranks; ranks map to ids by a seeded bijection, so the hot
  keys differ from seed to seed (YCSB's scrambled zipfian);
- ``partitioner``: ``murmur2`` (Kafka's default for keyed records) or
  ``sticky`` (keyless records, spread evenly over the partitions);
- ``tombstone_share``: share of records with a null value;
- ``message_bytes_min/mean/max``: key + value bytes of non-tombstones; value
  sizes follow a truncated exponential with that minimum, mean and maximum;
- ``records_per_batch``: records per v2 record batch, per partition;
- ``produce_rate_per_s``: the topic spans ``records / rate`` seconds, and
  each partition's records are spread evenly over that span.

Optional key; a configuration without it gives the bytes it always gave:

- ``compression``: the producer's ``compression.type``, ``"none"`` (the
  default) or ``"zstd"``.  A zstd batch is framed as Kafka's Java producer
  writes it at its default level, 3: the 61-byte header with attribute
  bits 0-2 set to 4, then the records section after ``count`` as one zstd
  frame written by a streaming compressor (zstd-jni's output stream: no
  content size, no checksum).  Batch lengths and CRCs cover the compressed
  batch; offsets, timestamps and counts are those of the uncompressed
  batch.  Uncompressed values are zero bytes.  Compressed ones are
  OpenMessaging Benchmark's randomized payloads (its workload fields
  ``useRandomizedPayloads``, ``randomBytesRatio``,
  ``randomizedPayloadPoolSize``): a seeded pool of ``PAYLOAD_POOL_SIZE``
  payloads, each ``RANDOM_BYTES_RATIO`` of its bytes random and the rest
  zeros, one drawn at random for each record.  A value shorter than the
  pool's payloads takes that share of its own length in random bytes.
"""

from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import numpy as np

#: 2025-01-01T00:00:00Z in ms: the first record's timestamp.
BASE_TS_MS = 1_735_689_600_000
BATCH_HEADER_BYTES = 61
#: Bytes of a v2 batch header before the CRC-covered part (base offset,
#: batch length, leader epoch, magic, crc).
CRC_START = 21
CODECS = ("none", "zstd")
#: The Java producer's zstd level where ``compression.level`` is unset.
ZSTD_DEFAULT_LEVEL = 3
#: Attribute bits 0-2 of a zstd batch.
ATTR_ZSTD = 4
#: OMB's ``randomBytesRatio`` and ``randomizedPayloadPoolSize`` for a
#: compressed topic; OMB sets no default for either.  This share puts
#: zstd-3 at about three to one on batches of 50 values of 1 KiB.
RANDOM_BYTES_RATIO = 0.3
PAYLOAD_POOL_SIZE = 1000
#: A compressed partition is encoded and compressed in pieces of about this
#: many records, one job each, so that every thread works to the end and
#: none holds a whole partition uncompressed.
PIECE_RECORDS = 1 << 16


def codec(cfg: dict) -> str:
    """The configuration's producer codec."""
    name = cfg.get("compression", "none")
    if name not in CODECS:
        raise ValueError(f"unknown compression {name!r}")
    if name == "zstd":
        try:
            import zstandard  # noqa: F401
        except ImportError as e:
            raise RuntimeError(
                "compression 'zstd' needs the zstandard module, the "
                "benchmark's own zstd binding") from e
    return name


def random_bytes(value_len: np.ndarray) -> np.ndarray:
    """Random bytes at the head of each value, as OMB counts them:
    ``(int) (messageSize * randomBytesRatio)``; none for a null value."""
    return (np.maximum(value_len, 0) * RANDOM_BYTES_RATIO).astype(np.int64)


def payload_pool(seed: int, width: int) -> np.ndarray:
    """``uint8[PAYLOAD_POOL_SIZE, width]``: the random bytes of the pool's
    payloads, at most ``width`` of each."""
    return np.random.default_rng([int(seed) % (1 << 64), 0]).integers(
        0, 256, size=(PAYLOAD_POOL_SIZE, width), dtype=np.uint8)


def payload_choice(seed: int, n: int) -> np.ndarray:
    """The pool payload of each of a topic's ``n`` records."""
    return np.random.default_rng([int(seed) % (1 << 64), 1]).integers(
        0, PAYLOAD_POOL_SIZE, size=n, dtype=np.int32)


@dataclasses.dataclass
class Topic:
    """Records partition after partition, each partition's in offset
    order."""

    partitions: int
    partition: np.ndarray  # int32
    key_id: np.ndarray     # int64, -1 for a null key
    key_bytes: int         # 0: every key is null
    value_len: np.ndarray  # int32, -1 for a null value (tombstone)
    ts_ms: np.ndarray      # int64
    payload_seed: int = 0  # draws a compressed topic's payloads

    @property
    def n(self) -> int:
        return int(self.partition.shape[0])

    def in_partition_order(self) -> np.ndarray:
        """Permutation that lists each partition's records in offset order,
        partition after partition (the identity for a generated topic)."""
        return np.argsort(self.partition, kind="stable")


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def _grid(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def zipf_counts(n: int, ranks: int, s: float) -> np.ndarray:
    """How many of ``n`` Zipf(s) draws over ``ranks`` ranks fall on each
    rank, drawn at the quantile grid: the same for every seed."""
    w = np.arange(1, ranks + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    upto = np.minimum(np.floor(cdf * n + 0.5).astype(np.int64), n)
    upto[-1] = n
    return np.diff(upto, prepend=0)


def scramble(ranks: np.ndarray, ids: int, rng: np.random.Generator
             ) -> np.ndarray:
    """A seeded bijection of [0, ids): rank -> (a * rank + b) mod ids."""
    while True:
        a = int(rng.integers(1, ids)) if ids > 1 else 1
        if math.gcd(a, ids) == 1:
            break
    b = int(rng.integers(0, ids))
    return (ranks.astype(np.int64) * a + b) % ids


def _trunc_exp_mean(theta: float, width: float) -> float:
    return theta - width / math.expm1(width / theta)


def value_sizes(n: int, vmin: int, vmean: float, vmax: int) -> np.ndarray:
    """``n`` sizes in [vmin, vmax] from a truncated exponential whose mean is
    ``vmean``, at the quantile grid (the same multiset for every seed)."""
    if vmin == vmax:
        return np.full(n, vmin, dtype=np.int32)
    width = vmax - vmin
    target = vmean - vmin
    if not 0 < target < width / 2:
        raise ValueError(f"no truncated exponential has mean {vmean} "
                         f"in [{vmin}, {vmax}]")
    lo, hi = width / 600, 1e6 * width
    for _ in range(200):
        mid = math.sqrt(lo * hi)
        if _trunc_exp_mean(mid, width) < target:
            lo = mid
        else:
            hi = mid
    theta = math.sqrt(lo * hi)
    e = -theta * np.log1p(-_grid(n) * -math.expm1(-width / theta))
    return (vmin + np.floor(e + 0.5)).astype(np.int32)


def murmur2_partition(keys: np.ndarray, partitions: int) -> np.ndarray:
    """Kafka's default partitioner for keyed records:
    ``(murmur2(key) & 0x7fffffff) % partitions`` over ``uint8[n, L]`` keys."""
    m = np.uint32(0x5BD1E995)
    n, length = keys.shape
    k8 = keys.astype(np.uint32)
    with np.errstate(over="ignore"):
        h = np.full(n, np.uint32(0x9747B28C) ^ np.uint32(length), np.uint32)
        for i in range(length // 4):
            k = (k8[:, 4 * i] | (k8[:, 4 * i + 1] << 8)
                 | (k8[:, 4 * i + 2] << 16) | (k8[:, 4 * i + 3] << 24))
            k = k * m
            k ^= k >> np.uint32(24)
            k = k * m
            h = h * m
            h ^= k
        tail = length & ~3
        rest = length % 4
        if rest >= 3:
            h ^= k8[:, tail + 2] << 16
        if rest >= 2:
            h ^= k8[:, tail + 1] << 8
        if rest >= 1:
            h ^= k8[:, tail]
            h = h * m
        h ^= h >> np.uint32(13)
        h = h * m
        h ^= h >> np.uint32(15)
    return ((h & np.uint32(0x7FFFFFFF)) % np.uint32(partitions)).astype(
        np.int32)


def key_matrix(key_id: np.ndarray, key_bytes: int) -> np.ndarray:
    """``uint8[n, key_bytes]``: ``b"k"`` and the id in decimal, zero-padded."""
    digits = key_bytes - 1
    out = np.empty((key_id.shape[0], key_bytes), dtype=np.uint8)
    out[:, 0] = ord("k")
    rest = key_id.astype(np.int64)
    for col in range(key_bytes - 1, 0, -1):
        out[:, col] = ord("0") + rest % 10
        rest = rest // 10
    if digits < 19 and np.any(rest):
        raise ValueError(f"key ids do not fit {digits} digits")
    return out


def generate(cfg: dict, seed: int, threads: int = 8) -> Topic:
    n = int(cfg["records"])
    parts = int(cfg["partitions"])
    rng = rng_for(seed)
    key_bytes = int(cfg["key_bytes"])
    pool = ThreadPoolExecutor(threads)
    if cfg["partitioner"] == "murmur2":
        if not key_bytes:
            raise ValueError("murmur2 partitioning needs keys")
        counts = zipf_counts(n, int(cfg["key_ids"]), float(cfg["key_zipf_s"]))
        ranks = np.flatnonzero(counts)
        counts = counts[ranks]
        ids = scramble(ranks, int(cfg["key_ids"]), rng)
        part_of = np.concatenate(list(pool.map(
            lambda c: murmur2_partition(key_matrix(c, key_bytes), parts),
            np.array_split(ids, threads))))
        sizes = np.bincount(part_of, weights=counts,
                            minlength=parts).astype(np.int64)
    elif cfg["partitioner"] == "sticky":
        if key_bytes:
            raise ValueError("the sticky partitioner is for keyless records")
        sizes = np.full(parts, n // parts) + (np.arange(parts) < n % parts)
    else:
        raise ValueError(f"unknown partitioner {cfg['partitioner']!r}")
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    key_id = np.full(n, -1, dtype=np.int64)
    value_len = np.empty(n, dtype=np.int32)
    ts_ms = np.empty(n, dtype=np.int64)
    span_ms = 1000.0 * n / float(cfg["produce_rate_per_s"])
    vmin = int(cfg["message_bytes_min"]) - key_bytes
    vmax = int(cfg["message_bytes_max"]) - key_bytes
    vmean = float(cfg["message_bytes_mean"]) - key_bytes
    sub = rng.integers(0, 1 << 63, size=parts)

    def fill(p: int) -> None:
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        m = hi - lo
        prng = rng_for(int(sub[p]))
        tombs = round(m * float(cfg["tombstone_share"]))
        value_len[lo:hi] = np.concatenate([
            np.full(tombs, -1, dtype=np.int32),
            value_sizes(m - tombs, vmin, vmean, vmax)])[prng.permutation(m)]
        if key_bytes:
            mine = part_of == p
            keys = np.repeat(ids[mine], counts[mine])
            key_id[lo:hi] = keys[prng.permutation(m)]
        ts_ms[lo:hi] = BASE_TS_MS + np.floor(
            (np.arange(m) + 0.5) * (span_ms / max(m, 1))).astype(np.int64)

    with pool:
        list(pool.map(fill, range(parts)))
    partition = np.repeat(np.arange(parts, dtype=np.int32), sizes)
    # drawn after everything above, which it leaves as it was
    payload_seed = int(rng.integers(0, 1 << 63))
    return Topic(parts, partition, key_id, key_bytes, value_len, ts_ms,
                 payload_seed)


# -- wire encoding -----------------------------------------------------------


def _zigzag(v: np.ndarray) -> np.ndarray:
    v = v.astype(np.int64)
    return ((v << 1) ^ (v >> 63)).astype(np.uint64)


def _varint_len(z: np.ndarray) -> np.ndarray:
    out = np.ones(z.shape, dtype=np.int64)
    for k in range(1, 10):
        out += z >= np.uint64(1 << (7 * k))
    return out


def _put_varint(buf: np.ndarray, pos: np.ndarray, z: np.ndarray,
                zlen: np.ndarray) -> None:
    """Write every varint's bytes, and zeros after its end up to the
    longest one's length: callers write the fields of a record in order, so
    a later field overwrites those zeros, and the bytes after the last
    varint (a value, the headers count) are zeros anyway."""
    for k in range(int(zlen.max(initial=1))):
        byte = (z >> np.uint64(7 * k)) & np.uint64(0x7F)
        byte |= (zlen > k + 1).astype(np.uint64) << np.uint64(7)
        buf[pos + k] = byte.astype(np.uint8)


_HEADER = np.dtype([
    ("base_offset", ">i8"), ("batch_length", ">i4"), ("leader_epoch", ">i4"),
    ("magic", "i1"), ("crc", ">u4"), ("attributes", ">i2"),
    ("last_offset_delta", ">i4"), ("first_ts", ">i8"), ("max_ts", ">i8"),
    ("producer_id", ">i8"), ("producer_epoch", ">i2"),
    ("base_sequence", ">i4"), ("count", ">i4"),
])
assert _HEADER.itemsize == BATCH_HEADER_BYTES


@dataclasses.dataclass
class PartitionLog:
    """One partition's log: batch ``b`` is ``buf[starts[b]:ends[b]]`` and
    holds offsets from ``base_offsets[b]``; ``end_offset`` is the high
    watermark."""

    buf: np.ndarray
    starts: np.ndarray
    ends: np.ndarray
    base_offsets: np.ndarray
    end_offset: int


def encode_partition(ts: np.ndarray, key_id: np.ndarray, key_bytes: int,
                     value_len: np.ndarray, records_per_batch: int,
                     payloads: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                     first_offset: int = 0) -> PartitionLog:
    """Encode one partition's records, in offset order from
    ``first_offset``, as uncompressed v2 batches of ``records_per_batch``
    records, CRCs left zero (``put_crcs`` writes them).  Values are zero
    bytes, or with ``payloads = (pool, choice)`` each value starts with
    the random bytes of its pool payload ``pool[choice[i]]``."""
    n = ts.shape[0]
    offset = np.arange(n, dtype=np.int64)
    batch = offset // records_per_batch
    bstart_rec = offset[::records_per_batch]
    nbatches = bstart_rec.shape[0]
    first_ts = ts[bstart_rec]
    key_len = np.where(key_id >= 0, key_bytes, -1)
    value_len = value_len.astype(np.int64)

    z_ts = _zigzag(ts - first_ts[batch])
    z_off = _zigzag(offset % records_per_batch)
    z_key = _zigzag(key_len)
    z_val = _zigzag(value_len)
    l_ts, l_off, l_key, l_val = (_varint_len(z) for z in
                                 (z_ts, z_off, z_key, z_val))
    # attributes + fields + key + value + headers count
    body = (1 + l_ts + l_off + l_key + np.maximum(key_len, 0) + l_val
            + np.maximum(value_len, 0) + 1)
    z_body = _zigzag(body)
    l_body = _varint_len(z_body)
    total = l_body + body
    rec_pos = np.cumsum(total) - total + BATCH_HEADER_BYTES * (batch + 1)
    batch_total = BATCH_HEADER_BYTES + (
        np.add.reduceat(total, bstart_rec) if n else np.zeros(0, np.int64))
    batch_start = np.cumsum(batch_total) - batch_total
    batch_end = batch_start + batch_total
    buf = np.zeros(int(batch_end[-1]) if nbatches else 0, dtype=np.uint8)

    heads = np.zeros(nbatches, dtype=_HEADER)
    heads["base_offset"] = bstart_rec + first_offset
    heads["batch_length"] = batch_total - 12
    heads["magic"] = 2
    counts = np.diff(np.append(bstart_rec, n))
    heads["last_offset_delta"] = counts - 1
    heads["first_ts"] = first_ts
    heads["max_ts"] = np.maximum.reduceat(ts, bstart_rec) if n else 0
    heads["producer_id"] = -1
    heads["producer_epoch"] = -1
    heads["base_sequence"] = -1
    heads["count"] = counts
    hb = heads.view(np.uint8).reshape(nbatches, BATCH_HEADER_BYTES)
    for col in range(BATCH_HEADER_BYTES):
        buf[batch_start + col] = hb[:, col]

    pos = rec_pos
    _put_varint(buf, pos, z_body, l_body)
    pos = pos + l_body + 1  # attributes stay 0
    _put_varint(buf, pos, z_ts, l_ts)
    pos = pos + l_ts
    _put_varint(buf, pos, z_off, l_off)
    pos = pos + l_off
    _put_varint(buf, pos, z_key, l_key)
    pos = pos + l_key
    if key_bytes:
        keyed = np.flatnonzero(key_len > 0)
        keys = key_matrix(key_id[keyed], key_bytes)
        for col in range(key_bytes):
            buf[pos[keyed] + col] = keys[:, col]
    pos = pos + np.maximum(key_len, 0)
    _put_varint(buf, pos, z_val, l_val)
    if payloads is not None:
        pool, choice = payloads
        _put_values(buf, pos + l_val, random_bytes(value_len), pool.ravel(),
                    choice.astype(np.int64) * pool.shape[1])
    # The headers count is 0: nothing to write.

    return PartitionLog(buf, batch_start, batch_end,
                        bstart_rec + first_offset, first_offset + n)


def _put_values(buf: np.ndarray, pos: np.ndarray, lens: np.ndarray,
                src: np.ndarray, offs: np.ndarray,
                chunk: int = 1 << 16) -> None:
    """``buf[pos[i]:pos[i] + lens[i]] = src[offs[i]:offs[i] + lens[i]]``
    for every value: a gather and a scatter of rows for each length."""
    order = np.argsort(lens, kind="stable")
    cuts = np.flatnonzero(np.diff(lens[order])) + 1
    for rows in np.split(order, cuts):
        length = int(lens[rows[0]]) if rows.size else 0
        if length <= 0:
            continue
        rows_src = np.lib.stride_tricks.sliding_window_view(src, length)
        dst = np.lib.stride_tricks.sliding_window_view(buf, length,
                                                       writeable=True)
        for a in range(0, rows.size, chunk):
            r = rows[a:a + chunk]
            dst[pos[r]] = rows_src[offs[r]]


def _put_be(buf: np.ndarray, at: np.ndarray, values, dtype: str) -> None:
    """Write ``values`` big-endian as ``dtype`` at ``buf[at[i]:]``."""
    width = np.dtype(dtype).itemsize
    be = np.broadcast_to(np.asarray(values, dtype=dtype), at.shape)
    be = np.ascontiguousarray(be).view(np.uint8).reshape(at.shape[0], width)
    for col in range(width):
        buf[at + col] = be[:, col]


def compress_zstd(log: PartitionLog) -> PartitionLog:
    """``log`` with each batch's records section compressed into one zstd
    frame as Kafka's Java producer writes it (attributes 4; CRCs left
    zero).

    The compressor is the ``zstandard`` module's, the benchmark's own
    binding: a streaming frame (zstd-jni's output stream), so no content
    size, and no checksum.  It releases the GIL."""
    import zstandard

    cctx = zstandard.ZstdCompressor(level=ZSTD_DEFAULT_LEVEL,
                                    write_content_size=False,
                                    write_checksum=False)
    parts: List[bytes] = []
    view = memoryview(log.buf)
    for a, b in zip(log.starts.tolist(), log.ends.tolist()):
        stream = cctx.compressobj()
        parts += (bytes(view[a:a + BATCH_HEADER_BYTES]),
                  stream.compress(view[a + BATCH_HEADER_BYTES:b])
                  + stream.flush())
    total = BATCH_HEADER_BYTES + np.array([len(f) for f in parts[1::2]],
                                          dtype=np.int64)
    ends = np.cumsum(total)
    starts = ends - total
    buf = np.frombuffer(bytearray().join(parts), dtype=np.uint8)
    _put_be(buf, starts + _HEADER.fields["batch_length"][1], total - 12,
            ">i4")
    _put_be(buf, starts + _HEADER.fields["attributes"][1], ATTR_ZSTD, ">i2")
    return PartitionLog(buf, starts, ends, log.base_offsets, log.end_offset)


def _join(pieces: List[PartitionLog]) -> PartitionLog:
    """One partition's log from its pieces, in offset order."""
    if len(pieces) == 1:
        return pieces[0]
    shift = np.cumsum([0] + [g.buf.size for g in pieces[:-1]])
    return PartitionLog(
        np.concatenate([g.buf for g in pieces]),
        np.concatenate([g.starts + d for g, d in zip(pieces, shift)]),
        np.concatenate([g.ends + d for g, d in zip(pieces, shift)]),
        np.concatenate([g.base_offsets for g in pieces]),
        pieces[-1].end_offset)


def put_crcs(log: PartitionLog, lo: int, hi: int, crc32c) -> None:
    """Checksum batches ``lo:hi`` of a partition and write their CRCs."""
    starts = log.starts[lo:hi]
    crcs = crc32c(log.buf, starts + CRC_START, log.ends[lo:hi])
    _put_be(log.buf, starts + _HEADER.fields["crc"][1], crcs, ">u4")


def encode(topic: Topic, records_per_batch: int, crc32c,
           threads: int = 8, compression: str = "none"
           ) -> List[PartitionLog]:
    """Every partition's log, encoded, compressed with the producer codec
    ``compression`` and checksummed on ``threads`` threads (numpy, the
    compressor and the checksum release the GIL).  ``crc32c(buf, starts,
    stops)`` returns the CRC of each ``buf[starts[i]:stops[i]]``."""
    if compression not in CODECS:
        raise ValueError(f"unknown compression {compression!r}")
    bounds = np.searchsorted(topic.partition, np.arange(topic.partitions + 1))
    sizes = np.diff(bounds)
    if compression == "none":
        payloads = None
        piece = int(sizes.max(initial=1))
    else:
        payloads = (payload_pool(topic.payload_seed, int(random_bytes(
                        topic.value_len).max(initial=0))),
                    payload_choice(topic.payload_seed, topic.n))
        piece = records_per_batch * max(1, PIECE_RECORDS // records_per_batch)
    jobs = [(p, a, min(a + piece, int(sizes[p])))
            for p in range(topic.partitions)
            for a in range(0, max(int(sizes[p]), 1), piece)]

    def one(job) -> PartitionLog:
        p, a, b = job
        lo, hi = int(bounds[p]) + a, int(bounds[p]) + b
        log = encode_partition(
            topic.ts_ms[lo:hi], topic.key_id[lo:hi], topic.key_bytes,
            topic.value_len[lo:hi], records_per_batch,
            None if payloads is None else (payloads[0], payloads[1][lo:hi]),
            a)
        return log if payloads is None else compress_zstd(log)

    with ThreadPoolExecutor(threads) as pool:
        done = list(pool.map(one, jobs))
        logs = [_join([g for (q, _, _), g in zip(jobs, done) if q == p])
                for p in range(topic.partitions)]
        # Checksums in equal slices of all batches, so every thread works
        # to the end whatever the partitions' sizes.
        total = sum(len(g.starts) for g in logs)
        step = max(1, -(-total // (4 * threads)))
        jobs = [(g, lo, min(lo + step, len(g.starts)))
                for g in logs for lo in range(0, len(g.starts), step)]
        for f in [pool.submit(put_crcs, g, lo, hi, crc32c)
                  for g, lo, hi in jobs]:
            f.result()
    return logs
