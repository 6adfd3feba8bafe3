"""Loopback Kafka broker for the benchmark, in a spawned child process.

The child never imports JAX: it generates the topic from the seed
(generator.py), encodes it as v2 record batches, and serves it over the
Kafka protocol on 127.0.0.1.  It speaks what the analyzer's wire client
negotiates: ApiVersions v0, Metadata v1-v5, ListOffsets v1 and Fetch v4,
with the per-partition and request-level byte budgets of a real broker
(the first batch of a response is always served whole).

Every topic name is served.  A name that starts with ``warmup`` holds the
first whole batches of each partition up to ``warmup_records`` records in
all; any other name holds the whole topic.  The encoders here are the
benchmark's own, so a change to the program's codec cannot move the
yardstick.
"""

from __future__ import annotations

import socket
import struct
import threading
from typing import List, Optional

import numpy as np

API_FETCH, API_LIST_OFFSETS, API_METADATA, API_VERSIONS = 1, 2, 3, 18
ERR_UNKNOWN_TOPIC_OR_PARTITION = 3
LATEST = -1
WARMUP_PREFIX = "warmup"


def crc32c_native(buf: np.ndarray, starts: np.ndarray,
                  stops: np.ndarray) -> np.ndarray:
    """CRC32-C of ``buf[starts[i]:stops[i]]`` for every i, with the native
    shim's checksum (ctypes releases the GIL)."""
    import ctypes

    from kafka_topic_analyzer_tpu.io.native import load_library

    fn = load_library().kta_crc32c
    fn.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    fn.restype = ctypes.c_uint32
    base = buf.ctypes.data
    return np.array([fn(base + int(a), int(b - a))
                     for a, b in zip(starts, stops)], dtype=np.uint32)


class _Writer:
    def __init__(self):
        self.parts: List[bytes] = []

    def i8(self, v):
        self.parts.append(struct.pack(">b", v))
        return self

    def i16(self, v):
        self.parts.append(struct.pack(">h", v))
        return self

    def i32(self, v):
        self.parts.append(struct.pack(">i", v))
        return self

    def i64(self, v):
        self.parts.append(struct.pack(">q", v))
        return self

    def string(self, s: Optional[str]):
        if s is None:
            return self.i16(-1)
        b = s.encode()
        self.i16(len(b))
        self.parts.append(b)
        return self

    def done(self) -> bytes:
        return b"".join(self.parts)


class _Reader:
    def __init__(self, buf: bytes):
        self.buf = buf
        self.pos = 0

    def _take(self, fmt: str):
        (v,) = struct.unpack_from(fmt, self.buf, self.pos)
        self.pos += struct.calcsize(fmt)
        return v

    def i8(self):
        return self._take(">b")

    def i16(self):
        return self._take(">h")

    def i32(self):
        return self._take(">i")

    def i64(self):
        return self._take(">q")

    def string(self) -> Optional[str]:
        n = self.i16()
        if n < 0:
            return None
        s = self.buf[self.pos:self.pos + n].decode()
        self.pos += n
        return s


def _recv_exact(conn: socket.socket, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = conn.recv(n - len(buf))
        except OSError:
            return None
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def _sendmsg_all(conn: socket.socket, bufs: list) -> None:
    views = [memoryview(b).cast("B") for b in bufs]
    i = 0
    while i < len(views):
        sent = conn.sendmsg(views[i:i + 512])
        while i < len(views) and sent >= len(views[i]):
            sent -= len(views[i])
            i += 1
        if sent:
            views[i] = views[i][sent:]


class Broker:
    """Serves a list of ``generator.PartitionLog`` on one listening socket,
    a thread per connection."""

    def __init__(self, logs, warmup_records: int):
        self.logs = logs
        self.parts = len(logs)
        self.views = [memoryview(log.buf) for log in logs]
        share = -(-warmup_records // self.parts)
        self.warm_end = []
        for log in logs:
            b = int(np.searchsorted(log.base_offsets, share))
            self.warm_end.append(int(log.base_offsets[b])
                                 if b < len(log.base_offsets)
                                 else log.end_offset)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        self._stop = threading.Event()

    def start(self) -> "Broker":
        threading.Thread(target=self._accept, daemon=True).start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self.sock.close()

    def _accept(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn: socket.socket) -> None:
        with conn:
            while not self._stop.is_set():
                head = _recv_exact(conn, 4)
                if head is None:
                    return
                payload = _recv_exact(conn, struct.unpack(">i", head)[0])
                if payload is None:
                    return
                r = _Reader(payload)
                api, version, corr = r.i16(), r.i16(), r.i32()
                r.string()  # client id
                iov = self._dispatch(api, version, r)
                total = sum(len(b) for b in iov)
                _sendmsg_all(conn, [struct.pack(">ii", 4 + total, corr)]
                             + iov)

    def end_offset(self, topic: str, p: int) -> int:
        if topic.startswith(WARMUP_PREFIX):
            return self.warm_end[p]
        return self.logs[p].end_offset

    def _dispatch(self, api: int, version: int, r: _Reader) -> list:
        if api == API_VERSIONS:
            w = _Writer().i16(0).i32(3)
            for key, lo, hi in ((API_FETCH, 4, 4), (API_LIST_OFFSETS, 1, 1),
                                (API_METADATA, 1, 5)):
                w.i16(key).i16(lo).i16(hi)
            return [w.done()]
        if api == API_METADATA:
            return [self._metadata(version, r)]
        if api == API_LIST_OFFSETS:
            return [self._list_offsets(r)]
        if api == API_FETCH:
            return self._fetch(r)
        raise ValueError(f"benchmark broker: unsupported api {api}")

    def _metadata(self, version: int, r: _Reader) -> bytes:
        n = r.i32()
        names = [r.string() for _ in range(max(n, 0))]
        w = _Writer()
        if version >= 3:
            w.i32(0)  # throttle
        w.i32(1).i32(0).string("127.0.0.1").i32(self.port)
        if version >= 1:
            w.string(None)  # rack
        if version >= 2:
            w.string(None)  # cluster id
        if version >= 1:
            w.i32(0)  # controller
        w.i32(len(names))
        for name in names:
            w.i16(0).string(name)
            if version >= 1:
                w.i8(0)  # internal
            w.i32(self.parts)
            for p in range(self.parts):
                w.i16(0).i32(p).i32(0).i32(1).i32(0).i32(1).i32(0)
                if version >= 5:
                    w.i32(0)  # offline replicas
        return w.done()

    def _list_offsets(self, r: _Reader) -> bytes:
        r.i32()  # replica
        w = _Writer()
        topics = r.i32()
        w.i32(topics)
        for _ in range(topics):
            topic = r.string() or ""
            nparts = r.i32()
            w.string(topic).i32(nparts)
            for _ in range(nparts):
                p, ts = r.i32(), r.i64()
                if not 0 <= p < self.parts:
                    w.i32(p).i16(ERR_UNKNOWN_TOPIC_OR_PARTITION)
                    w.i64(-1).i64(-1)
                    continue
                off = self.end_offset(topic, p) if ts == LATEST else 0
                w.i32(p).i16(0).i64(-1).i64(off)
        return w.done()

    def _fetch(self, r: _Reader) -> list:
        r.i32()  # replica
        r.i32()  # max wait
        r.i32()  # min bytes
        budget = r.i32()
        r.i8()  # isolation
        topics = r.i32()
        head = _Writer().i32(0).i32(topics)  # throttle, topics
        iov = [head.done()]
        served = False
        for _ in range(topics):
            topic = r.string() or ""
            nparts = r.i32()
            iov.append(_Writer().string(topic).i32(nparts).done())
            for _ in range(nparts):
                p, offset, pmax = r.i32(), r.i64(), r.i32()
                if not 0 <= p < self.parts:
                    w = _Writer().i32(p).i16(ERR_UNKNOWN_TOPIC_OR_PARTITION)
                    iov.append(w.i64(-1).i64(-1).i32(0).i32(0).done())
                    continue
                end = self.end_offset(topic, p)
                lo, hi = self._span(p, offset, end, min(pmax, budget),
                                    not served)
                budget = max(0, budget - (hi - lo))
                served = served or hi > lo
                # high watermark, last stable offset, no aborted txns
                w = _Writer().i32(p).i16(0).i64(end).i64(end).i32(0)
                iov.append(w.i32(hi - lo).done())
                if hi > lo:
                    iov.append(self.views[p][lo:hi])
        return iov

    def _span(self, p: int, offset: int, end: int, limit: int,
              first: bool) -> "tuple[int, int]":
        """Byte range of the whole batches from the one holding ``offset``,
        within ``limit`` bytes (one batch at least where ``first``)."""
        log = self.logs[p]
        base = log.base_offsets
        if offset >= end or not len(base):
            return 0, 0
        b0 = max(0, int(np.searchsorted(base, offset, side="right")) - 1)
        b_end = int(np.searchsorted(base, end, side="left"))
        starts, ends = log.starts, log.ends
        lo = int(starts[b0])
        b1 = int(np.searchsorted(ends, lo + limit, side="right"))
        b1 = min(max(b1, b0 + 1 if first else b0), b_end)
        return (lo, int(ends[b1 - 1])) if b1 > b0 else (0, 0)


def _child(pipe, cfg: dict, seed: int, warmup_records: int) -> None:
    """Spawned entry: generate, encode, serve, report the port, and serve
    until the parent says stop (or dies)."""
    import os
    import sys
    import time

    from benchmark import generator

    threads = os.cpu_count() or 8
    t0 = time.monotonic()
    topic = generator.generate(cfg, seed, threads)
    t1 = time.monotonic()
    logs = generator.encode(topic, int(cfg["records_per_batch"]),
                            crc32c_native, threads, generator.codec(cfg))
    del topic
    broker = Broker(logs, warmup_records).start()
    print(f"bench: broker serves {sum(g.end_offset for g in logs)} records "
          f"in {sum(g.buf.size for g in logs)} bytes: generated in "
          f"{t1 - t0:.2f}s, encoded in {time.monotonic() - t1:.2f}s",
          file=sys.stderr, flush=True)
    pipe.send(("port", broker.port))
    try:
        pipe.recv()
    except EOFError:
        pass
    broker.stop()


class BrokerProcess:
    """The broker in a spawned child.  ``start()`` returns at once, so the
    parent can start JAX while the child generates; ``port()`` waits for
    it to serve."""

    def __init__(self, cfg: dict, seed: int, warmup_records: int):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self._pipe, child_end = ctx.Pipe()
        self._proc = ctx.Process(
            target=_child, args=(child_end, cfg, seed, warmup_records),
            daemon=True)
        self._proc.start()
        child_end.close()
        self._port: Optional[int] = None

    def port(self, timeout_s: float = 300.0) -> int:
        if self._port is None:
            if not self._pipe.poll(timeout_s):
                raise RuntimeError(
                    f"benchmark broker did not serve within {timeout_s}s")
            try:
                kind, value = self._pipe.recv()
            except EOFError:
                self._proc.join(5)
                raise RuntimeError(
                    f"benchmark broker exited with code "
                    f"{self._proc.exitcode} before serving") from None
            self._port = value
        return self._port

    def stop(self) -> None:
        """Stop serving, or stop generating where it does not serve yet."""
        if self._port is None:
            self._proc.terminate()
        else:
            try:
                self._pipe.send("stop")
            except OSError:
                pass
        self._proc.join(10)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(10)
        if self._proc.is_alive():
            self._proc.kill()
            self._proc.join()
        self._pipe.close()

    def __enter__(self) -> "BrokerProcess":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
