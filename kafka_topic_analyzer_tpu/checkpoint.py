"""Scan snapshots: resumable long scans (SURVEY.md §5.4).

The reference deliberately has no resume: offsets are stored but never
committed, and every run rescans from earliest (src/kafka.rs:28-34,
src/main.rs:63-65's stale help text notwithstanding).  For 1B-message scans
that is wasteful, so the TPU build adds periodic snapshots: the analyzer
state is a small, associatively-merged pytree, so a snapshot is just

    (config fingerprint, per-partition next offsets, state arrays)

written atomically.  Resuming replays nothing: the saved state already
folds every record below the saved offsets, and the source continues from
them.  Works because updates are deterministic folds and batches respect
per-partition offset order (records.py contract).

Format: one ``.npz`` per snapshot (atomic rename), holding the state leaves
flattened by pytree path plus offset/config metadata as JSON strings.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.models.state import AnalyzerState

SNAPSHOT_NAME = "scan_snapshot.npz"


class StaleLeaseEpochError(ValueError):
    """A snapshot save or load was FENCED: the caller's lease epoch is
    older than the epoch already stamped on disk (DESIGN.md §23).

    This is the zombie-writer guard of the lease layer (fleet/lease.py):
    an instance that lost its topic lease while paused mid-pass must
    never land a checkpoint over its successor's, and must never resume
    FROM a successor's checkpoint as if it still owned the topic.  Named
    (rather than a bare ValueError) the same way the mesh-pinned
    fingerprint rejection is: the operator-facing message says who
    fenced whom and what to do about it."""


def _fingerprint_at(
    config: AnalyzerConfig, topic: str, version: int, mesh_free: bool = False
) -> str:
    fields = dataclasses.asdict(config)
    if mesh_free:
        # Mesh-free snapshots store the CANONICAL (single-device-layout)
        # state, which every mesh can adopt — so the mesh shape is pure
        # execution strategy for them and must not pin the fingerprint.
        fields.pop("mesh_shape", None)
    if config.enable_quantiles:
        # PR 9 changed the DDSketch bucket rule (float32 log → the shared
        # integer edge table, ops/ddsketch.ddsketch_edges): borderline
        # sizes can land one bucket over vs the old rule, so a pre-change
        # quantile snapshot's accumulated buckets must NOT merge with
        # new-rule buckets — stamp the rule so those snapshots are
        # cleanly rejected instead.  Quantile-free configs keep their
        # pre-change fingerprints (no bucket state to skew).
        fields["ddsketch_bucket_rule"] = "edges-v1"
    payload = json.dumps(
        {"topic": topic, "state_version": version, **fields},
        sort_keys=True,
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def mesh_free_snapshots(config: AnalyzerConfig) -> bool:
    """True when this config's snapshots canonicalize to the mesh-free
    single-device layout and may resume under ANY mesh shape (and any
    --ingest-workers / --superbatch / --dispatch-depth — those never
    entered the fingerprint).

    Every analyzer fold except one is associative AND commutative across
    device rows (counters and DDSketch rows add, extremes and HLL
    registers merge by min/max), so a stacked state folds down to one
    canonical row at save time and redistributes as (canonical, identity,
    identity, ...) at load time — the mesh's finalize reduction then
    reproduces exactly the canonical values (DESIGN.md §14).  The
    exception is the alive-key bitmap: last-writer-wins bit CLEARS only
    resolve correctly against the same row that set the bit, and the
    partition→row assignment changes with the mesh — so alive-key scans
    keep the mesh-pinned fingerprint (resuming them under a different
    mesh is a clean error, not a silent miscount)."""
    return not config.count_alive_keys


def config_fingerprint(config: AnalyzerConfig, topic: str) -> str:
    """Snapshot compatibility key (the one new snapshots are stamped with):
    anything that changes state shapes or fold semantics participates.

    state_version: bump whenever the AnalyzerState layout changes so stale
    snapshots are rejected instead of shape-erroring.  v3: space_shards>1
    meshes changed record-parallel leaves from D to D*S leading rows
    (parallel/sharded.py, r2 commit 9409a31).  S=1 layouts were untouched
    by that change, so they stamp version 2 — and loaders additionally
    accept the v3-stamped fingerprint for S=1 configs
    (`acceptable_fingerprints`), keeping both pre-r2 AND r2/r3-era
    single-space-shard snapshots resumable (the r2/r3 code stamped every
    config v3).  v4 (r7): configs without the alive bitmap store the
    CANONICAL mesh-free layout (see `mesh_free_snapshots`) and drop
    mesh_shape from the fingerprint — any-mesh↔any-mesh resume."""
    if mesh_free_snapshots(config):
        return _fingerprint_at(config, topic, 4, mesh_free=True)
    version = 2 if config.space_shards == 1 else 3
    return _fingerprint_at(config, topic, version)


def acceptable_fingerprints(config: AnalyzerConfig, topic: str) -> "set[str]":
    """All fingerprints a loader should accept for this config: the
    canonical one, plus compatible legacy stamps — the v3 variant for S=1
    configs whose state layout is identical under both version labels,
    and (for mesh-free configs) the pre-v4 mesh-pinned stamps of the SAME
    mesh, whose stacked leaves still match the current backend's template
    exactly (see config_fingerprint)."""
    out = {config_fingerprint(config, topic)}
    if mesh_free_snapshots(config):
        # Legacy (pre-r7) snapshots of this exact mesh: stacked layout,
        # mesh-pinned stamp.  Shapes match the current template, so they
        # load directly.
        out.add(_fingerprint_at(config, topic, 2 if config.space_shards == 1 else 3))
    if config.space_shards == 1:
        out.add(_fingerprint_at(config, topic, 3))
    return out


def _canonicalize(state: AnalyzerState) -> AnalyzerState:
    """Fold a stacked state's leading device axis down to the canonical
    single-device layout via the state's OWN associative merge
    (`AnalyzerState.merge` — the single source of the per-leaf law: sums
    add, extremes min/max, HLL registers max, DDSketch buckets add).
    Already-canonical states pass through untouched.  Never called with an
    alive bitmap (mesh_free_snapshots gates it out: bit clears are only
    exact against the row that set the bit)."""
    assert state.alive is None, "alive-bitmap states are mesh-pinned"
    probe = np.asarray(state.metrics.per_partition)
    if probe.ndim == 2:
        return state  # single-device layout already
    acc = None
    for i in range(probe.shape[0]):
        row = jax.tree.map(lambda x: np.asarray(x)[i], state)
        acc = row if acc is None else acc.merge(row)
    return jax.tree.map(np.asarray, acc)


def _distribute(
    canonical: AnalyzerState, template: AnalyzerState, identity: AnalyzerState
) -> AnalyzerState:
    """Inverse placement for resuming a canonical snapshot on a stacked
    (device-row-stacked) template: device row 0 carries the canonical
    fold, every other row its leaf's merge IDENTITY — ``identity`` is a
    fresh `AnalyzerState.init` (a fresh state IS the merge identity;
    that is what makes merging one in a no-op), broadcast to the
    template's stacked shape.  The backend's finalize reduction then
    reproduces exactly the canonical values, and records folded after
    the resume land in whichever row their partition now maps to —
    byte-identical either way, because every one of these folds is
    associative and commutative across rows."""

    def place(ident, tmpl, canon) -> np.ndarray:
        out = np.broadcast_to(
            np.asarray(ident), np.asarray(tmpl).shape
        ).copy()
        out[0] = np.asarray(canon)
        return out

    return jax.tree.map(place, identity, template, canonical)


def _flatten(state: AnalyzerState) -> Dict[str, np.ndarray]:
    flat = {}
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in leaves:
        key = "state" + "".join(str(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _snapshot_path(directory: str, scope) -> str:
    """Single-controller snapshots are one file; multi-controller scans
    write one file PER PROCESS (its own data rows + its own partitions'
    offsets).  Data shards fold independently, so each process may resume
    from its file with no cross-process coordination — a process without
    a file simply rescans its shards from zero, which is still exact."""
    if scope is None:
        return os.path.join(directory, SNAPSHOT_NAME)
    pid, nproc, _rows = scope
    return os.path.join(directory, f"scan_snapshot.p{pid}of{nproc}.npz")


def save_snapshot(
    directory: str,
    topic: str,
    config: AnalyzerConfig,
    state: AnalyzerState,
    next_offsets: Dict[int, int],
    records_seen: int,
    init_now_s: int,
    scope=None,
    degraded: "Optional[Dict[int, str]]" = None,
    corrupt: "Optional[list]" = None,
    lease_epoch: "Optional[int]" = None,
    lost: "Optional[list]" = None,
    partition_meta: "Optional[Dict[int, dict]]" = None,
) -> str:
    """Atomically write the snapshot; returns its path.

    ``scope``: None, or ``(process_index, process_count, local_rows)`` for
    multi-controller runs — ``state`` is then the PROCESS-LOCAL rows
    (ShardedTpuBackend.get_state_local).

    ``degraded``: partition -> reason for partitions the scan dropped
    (transport-fault degradation).  Informational only — resume reads
    ``next_offsets``, which already stop at each degraded partition's last
    folded record — but it lets an operator see from the snapshot alone
    why a rerun is needed.

    ``corrupt``: the span list of poisoned frames the scan skipped or
    quarantined (KafkaWireSource.corruption_spans format).  NOT merely
    informational: a --resume seeds the source with it
    (`load_corrupt_spans`) so re-walking an already-skipped span — the
    offset tracker cannot advance past a span that yielded no records —
    neither re-counts nor double-quarantines it.

    ``lost``: the span list of offset ranges the log mutated away from the
    scan (KafkaWireSource.lost_spans format — retention races, truncation,
    resume-below-log-start).  Like ``corrupt``, NOT merely informational: a
    --resume seeds the source with it (`load_lost_spans`) so the logical
    scan's final report still names the loss, without re-booking it.

    ``partition_meta``: per-partition durable-fencing facts
    ({partition: {leader_epoch, log_start_offset}},
    KafkaWireSource.partition_meta format).  Resume validates the saved
    cursor against the live log with these (`load_partition_meta` →
    validate_resume): a cursor below the live log start is a named
    retention loss BEFORE the first fetch, and an epoch that moved since
    the save triggers the OffsetForLeaderEpoch divergence check.

    ``lease_epoch``: the writer's topic-ownership lease epoch under a
    multi-instance fleet (fleet/lease.py).  The save is FENCED at write
    time: if the on-disk snapshot already carries a NEWER epoch, a
    successor instance owns this topic and the write raises
    `StaleLeaseEpochError` instead of clobbering its state.  None (solo
    scans, lease-less fleets) skips the check and stamps nothing.

    The fence is check-then-act (read the stamp, then rename), so it
    closes only once the successor's FIRST save lands: a zombie at
    epoch N racing a successor (epoch N+1) that has acquired but not
    yet saved still reads stamp <= N and lands one stale checkpoint.
    The successor's save then overwrites it, bounding the damage to at
    most one stale pass — but a crash inside that window resumes from
    the zombie's state, and anything the zombie published during that
    pass was double-scanned (DESIGN.md §23 failure matrix)."""
    os.makedirs(directory, exist_ok=True)
    if lease_epoch is not None:
        try:
            prev = snapshot_info(directory, scope)
        except Exception:
            prev = None  # unreadable/truncated snapshot cannot outrank us
        prev_epoch = int((prev or {}).get("lease_epoch", 0))
        if prev_epoch > int(lease_epoch):
            raise StaleLeaseEpochError(
                f"STALE-LEASE-EPOCH: refusing to save snapshot for topic "
                f"{topic!r}: the on-disk snapshot carries lease epoch "
                f"{prev_epoch}, this writer holds epoch {int(lease_epoch)} "
                "— this instance was fenced (its topic lease expired and "
                "a successor took over; DESIGN.md §23).  Do not retry: "
                "the successor's checkpoint is the live one"
            )
    host_state = jax.tree.map(np.asarray, jax.device_get(state))
    if mesh_free_snapshots(config):
        # Store the canonical mesh-free layout (v4 stamp): a stacked
        # state folds its leading device axis down host-side, so ANY mesh
        # (or the single device) can adopt the snapshot on resume.
        host_state = _canonicalize(host_state)
    flat = _flatten(host_state)
    meta = {
        "fingerprint": config_fingerprint(config, topic),
        "topic": topic,
        "next_offsets": {str(k): int(v) for k, v in next_offsets.items()},
        "records_seen": int(records_seen),
        "init_now_s": int(init_now_s),
    }
    if degraded:
        meta["degraded"] = {str(k): str(v) for k, v in degraded.items()}
    if corrupt:
        meta["corrupt_spans"] = list(corrupt)
    if lost:
        meta["lost_spans"] = list(lost)
    if partition_meta:
        meta["partition_meta"] = {
            str(k): {
                "leader_epoch": int(v.get("leader_epoch", -1)),
                "log_start_offset": int(v.get("log_start_offset", -1)),
            }
            for k, v in partition_meta.items()
        }
    if lease_epoch is not None:
        meta["lease_epoch"] = int(lease_epoch)
    if scope is not None:
        meta["process"] = [int(scope[0]), int(scope[1])]
        meta["local_rows"] = [int(r) for r in scope[2]]
    path = _snapshot_path(directory, scope)
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".npz.tmp")
    os.close(fd)
    try:
        with open(tmp, "wb") as f:
            np.savez(f, __meta__=json.dumps(meta), **flat)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def _fingerprint_mismatch_message(
    path: str, meta: dict, config: AnalyzerConfig, topic: str
) -> str:
    """A rejection message that NAMES the cause when it can.

    Alive-key scans are mesh-pinned (`mesh_free_snapshots` — LWW bit
    clears only resolve against the row that set the bit), and "I resumed
    an alive scan on a different mesh" is by far the most common way to
    hit this error — so instead of a bare "fingerprint mismatch", probe
    whether the snapshot's stamp matches THIS config under some other
    mesh shape and, when it does, say which mesh wrote it and what a
    resume is allowed to change."""
    base = (
        f"snapshot at {path} was taken with a different topic/config "
        "(fingerprint mismatch)"
    )
    if not config.count_alive_keys:
        return base + " — delete it or match the original flags"
    # Probe plausible writer meshes: same config, different (data, space)
    # shape.  Bounded sweep — meshes are small integer grids.
    stamp = meta.get("fingerprint")
    for d in range(1, 65):
        for s in (1, 2, 4, 8):
            shape = (d, s)
            if shape == tuple(config.mesh_shape):
                continue
            try:
                probe = dataclasses.replace(config, mesh_shape=shape)
            except ValueError:
                continue
            # s==1 writers stamp version 2 today, but r2/r3-era builds
            # stamped every config v3 (see acceptable_fingerprints) —
            # probe both so legacy snapshots get the same diagnosis.
            versions = (2, 3) if s == 1 else (3,)
            if any(
                _fingerprint_at(probe, topic, v) == stamp for v in versions
            ):
                return (
                    f"snapshot at {path} is MESH-PINNED and was written by "
                    f"a mesh {shape[0]}x{shape[1]} scan: this scan counts "
                    "alive keys (-c/--count-alive-keys), and alive-key "
                    "snapshots only resume under the ORIGINAL mesh shape "
                    "(last-writer-wins bit clears must land on the data "
                    "row that set the bit — DESIGN.md §14).  Resume with "
                    f"--mesh {shape[0]},{shape[1]} (ingest workers, "
                    "superbatch, dispatch depth, wire format and "
                    "alive-compaction may all change freely), or delete "
                    "the snapshot to rescan under "
                    f"--mesh {config.mesh_shape[0]},{config.mesh_shape[1]}"
                )
    return (
        base
        + " — this scan counts alive keys (-c/--count-alive-keys), whose "
        "snapshots additionally pin the mesh shape; delete the snapshot "
        "or match the original flags"
    )


def load_snapshot(
    directory: str,
    topic: str,
    config: AnalyzerConfig,
    template: Optional[AnalyzerState] = None,
    scope=None,
    lease_epoch: "Optional[int]" = None,
) -> Optional[Tuple[AnalyzerState, Dict[int, int], int, int]]:
    """Load (state, next_offsets, records_seen, init_now_s), or None if no
    compatible snapshot exists.  An incompatible snapshot (different config/
    topic) raises — silently restarting would double-count.

    ``lease_epoch``: the loader's topic-ownership lease epoch
    (fleet/lease.py).  A snapshot stamped with a NEWER epoch was written
    by a successor instance — the loader was fenced, and resuming from
    (then overwriting) the successor's state would double-count: raises
    `StaleLeaseEpochError`.  A snapshot with an older or absent epoch
    loads normally — that is exactly the failover path, where epoch E+1
    resumes its predecessor's epoch-E checkpoint.

    ``template`` supplies the expected state shapes; it defaults to the
    single-device layout.  Sharded backends pass their freshly-initialized
    (data-stacked) state — the engine uses ``backend.get_state()`` — since
    their leaves carry a leading data-shard axis.  With ``scope`` set (see
    save_snapshot) the template and returned state are process-local.
    """
    path = _snapshot_path(directory, scope)
    if not os.path.exists(path):
        return None
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        if meta["fingerprint"] not in acceptable_fingerprints(config, topic):
            raise ValueError(
                _fingerprint_mismatch_message(path, meta, config, topic)
            )
        if (
            lease_epoch is not None
            and int(meta.get("lease_epoch", 0)) > int(lease_epoch)
        ):
            raise StaleLeaseEpochError(
                f"STALE-LEASE-EPOCH: refusing to resume topic {topic!r} "
                f"from {path}: the snapshot was written under lease epoch "
                f"{int(meta['lease_epoch'])}, this loader holds epoch "
                f"{int(lease_epoch)} — this instance was fenced (a "
                "successor owns the topic; DESIGN.md §23).  Re-acquire "
                "the lease to get a current epoch before resuming"
            )
        if scope is not None:
            pid, nproc, rows = scope
            if meta.get("process") != [pid, nproc] or meta.get(
                "local_rows"
            ) != [int(r) for r in rows]:
                raise ValueError(
                    f"snapshot at {path} belongs to a different process "
                    "layout (process/data-row mismatch) — delete it or "
                    "rerun with the original mesh and process count"
                )
        if template is None:
            template = AnalyzerState.init(config)
        template = jax.tree.map(np.asarray, jax.device_get(template))
        flat = _flatten(template)
        loaded = {k: z[k] for k in flat}
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(template)
    direct = all(
        loaded["state" + "".join(str(p) for p in path_key)].shape
        == np.asarray(leaf).shape
        for path_key, leaf in leaves_p
    )
    canon_identity = None
    if not direct and mesh_free_snapshots(config):
        # Cross-mesh resume: the stored leaves are the canonical
        # single-device layout (v4 snapshots always are), the template is
        # this backend's stacked layout.  Validate against the canonical
        # shapes instead, then redistribute below: row 0 = canonical,
        # other rows = identity (see _distribute — the fresh init state
        # doubles as both the shape template and the identity values).
        canon_identity = jax.tree.map(
            np.asarray, jax.device_get(AnalyzerState.init(config))
        )
        leaves_p, treedef = jax.tree_util.tree_flatten_with_path(
            canon_identity
        )
    new_leaves = []
    for path_key, leaf in leaves_p:
        key = "state" + "".join(str(p) for p in path_key)
        arr = loaded[key]
        if arr.shape != leaf.shape or arr.dtype != np.asarray(leaf).dtype:
            raise ValueError(f"snapshot leaf {key} has shape {arr.shape}")
        new_leaves.append(arr)
    state = jax.tree_util.tree_unflatten(treedef, new_leaves)
    if canon_identity is not None:
        state = _distribute(state, template, canon_identity)
    offsets = {int(k): int(v) for k, v in meta["next_offsets"].items()}
    return state, offsets, int(meta["records_seen"]), int(meta["init_now_s"])


def snapshot_info(directory: str, scope=None) -> "Optional[dict]":
    """Snapshot METADATA (fingerprint, topic, per-partition next offsets,
    records_seen, degraded/corrupt annotations) without loading the state
    arrays — or None when no snapshot exists.  The follow service's
    startup banner reads this to report where a ``--resume`` will pick up
    (serve/follow.py), and operator tooling can answer "how far did the
    dead service get" from the file alone, before paying the .npz load.
    Works on any snapshot — batch- or follow-written; the format never
    learned the difference."""
    path = _snapshot_path(directory, scope)
    if not os.path.exists(path):
        return None
    with np.load(path, allow_pickle=False) as z:
        return json.loads(str(z["__meta__"]))


HISTORY_DIR_NAME = "_kta_history"


def history_dir(directory: str) -> str:
    """Where the disk-backed telemetry history (obs/history.py,
    ``--history-bytes``) lives: a reserved subdirectory of the
    ``--snapshot-dir``, so a SIGTERM→restart that resumes the state from
    its checkpoint resumes the telemetry series from the same place —
    one directory to move, back up, or delete.  The underscore-prefixed
    reserved name keeps it out of the fleet's per-topic snapshot
    inventory (`list_topic_snapshots` skips directories without a
    snapshot file; a real Kafka topic named exactly ``_kta_history``
    would collide — don't).  Process-wide: fleet runs share one history
    (the recorder's tracks are process totals; per-topic lag lives in
    the labeled gauges)."""
    return os.path.join(directory, HISTORY_DIR_NAME)


def topic_snapshot_dir(directory: str, topic: str) -> str:
    """Fleet-mode checkpoint namespacing: each topic's snapshots live in
    their own subdirectory of the fleet ``--snapshot-dir`` (Kafka topic
    names are ``[a-zA-Z0-9._-]``, so the name IS a safe path segment).  A
    solo scan of one topic pointed at the same subdirectory resumes the
    fleet's checkpoint and vice versa — the snapshot format never learns
    it was written by a fleet."""
    return os.path.join(directory, topic)


def list_topic_snapshots(directory: str) -> "dict[str, dict]":
    """topic -> snapshot metadata for every per-topic snapshot under a
    fleet snapshot directory (`snapshot_info` over each subdirectory) —
    the fleet resume banner: "which topics will pick up where" from the
    files alone, before any broker handshake or state load."""
    out: "dict[str, dict]" = {}
    if not os.path.isdir(directory):
        return out
    for name in sorted(os.listdir(directory)):
        sub = os.path.join(directory, name)
        if not os.path.isdir(sub):
            continue
        try:
            info = snapshot_info(sub)
        except Exception:
            # One topic's corrupt/truncated snapshot (a fleet killed
            # mid-write) must not break the inventory — the fleet's
            # isolation contract starts at the banner.  That topic's own
            # resume will surface the real error in its status row.
            import logging

            logging.getLogger(__name__).warning(
                "snapshot inventory: unreadable snapshot under %r "
                "(skipped)", sub, exc_info=True,
            )
            continue
        if info is not None:
            out[name] = info
    return out


def load_corrupt_spans(directory: str, scope=None) -> list:
    """The ``corrupt_spans`` metadata of a snapshot, or [] when the
    snapshot (or the list) is absent.  Split from `load_snapshot` so the
    engine can seed the source without changing that function's
    long-standing 4-tuple contract."""
    path = _snapshot_path(directory, scope)
    if not os.path.exists(path):
        return []
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
    return list(meta.get("corrupt_spans", []))


def load_lost_spans(directory: str, scope=None) -> list:
    """The ``lost_spans`` metadata of a snapshot, or [] when the snapshot
    (or the list) is absent — same split-from-`load_snapshot` rationale as
    `load_corrupt_spans`."""
    path = _snapshot_path(directory, scope)
    if not os.path.exists(path):
        return []
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
    return list(meta.get("lost_spans", []))


def load_partition_meta(directory: str, scope=None) -> "Dict[int, dict]":
    """The ``partition_meta`` durable-fencing map of a snapshot
    ({partition: {leader_epoch, log_start_offset}}), or {} when the
    snapshot (or the map) is absent."""
    path = _snapshot_path(directory, scope)
    if not os.path.exists(path):
        return {}
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
    return {
        int(k): {
            "leader_epoch": int(v.get("leader_epoch", -1)),
            "log_start_offset": int(v.get("log_start_offset", -1)),
        }
        for k, v in meta.get("partition_meta", {}).items()
    }
