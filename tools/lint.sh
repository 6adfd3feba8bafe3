#!/usr/bin/env bash
# Static lint pass, run as part of tools/tier1.sh.
#
# Rule: library modules never call print().  User-facing output must route
# through report.py (the renderer), the spinner (utils/progress.py), or the
# obs exporters — a print() buried in a library module corrupts --json
# stdout and bypasses the quiet/stats flags.  CLI entry points are exempt:
# cli.py (renders the report + banners), report.py (builds the strings the
# CLI prints), and the kafka_topic_analyzer_tpu/tools/ bench/probe scripts
# (standalone __main__ programs whose stdout IS their output format).
#
# AST-based, not grep: a print inside a string (e.g. a `python -c`
# snippet) must not trip it.
cd "$(dirname "$0")/.." || exit 1
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
ALLOWED = {
    PKG / "cli.py",
    PKG / "report.py",
}
ALLOWED_DIRS = (PKG / "tools",)

failures = []
for path in sorted(PKG.rglob("*.py")):
    if path in ALLOWED or any(d in path.parents for d in ALLOWED_DIRS):
        continue
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            failures.append(f"{path}:{node.lineno}: print() in library module")

if failures:
    print("lint: bare print() calls found (route output through report.py,")
    print("lint: the spinner, or the obs exporters):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (no print() in library modules)")
EOF

# Second rule: decode-surface functions under io/ must never raise a BARE
# ValueError or struct.error — untrusted wire input must classify (a typed
# subclass: kafka_codec's CorruptFrameError taxonomy, compression's
# CorruptPayloadError, zstd_py's CorruptZstdStream, segfile's
# CorruptSegmentError family).  The segment READER surface (SegmentFile*,
# SegmentCatalog, *SegmentStore classes) counts as decode surface: .ktaseg
# files are untrusted on-disk input exactly like fetched frames.
# Encode-side helpers (ByteWriter, encode_*, *_compress_*, write_segment*,
# SegmentDumpWriter) are exempt: they validate caller input, not stored
# bytes.
python - <<'EOF'
import ast
import pathlib
import re
import sys

IO_DIR = pathlib.Path("kafka_topic_analyzer_tpu") / "io"
DECODE_SURFACE = re.compile(
    r"decode|decompress|salvage|iter_batch|_iter_frames|_parse_frame"
    r"|_resync|_plausible|scan_record|_read_uvarint|_output_size"
    r"|_output_bound|_snappy_raw|_lz4_block|_decode_legacy"
    r"|SegmentFile|SegmentCatalog|SegmentStore"
    # The fused decode→pack entry points consume the same untrusted wire
    # bytes (io/native.py bindings; decode_pack* is caught by "decode").
    # _raise_pack_range is NOT decode surface: it mirrors the packer's
    # caller-config ValueError (packing.pack_batch), not a wire
    # classification — the wire taxonomy for fused streams still comes
    # from the per-frame chain the walk falls back to.
    r"|pack_append_columns|pack_row_init|append_record_set"
)
ENCODE_SIDE = re.compile(
    r"encode|compress_xerial|compress_frame|_compress\b"
    r"|write_segment|SegmentDumpWriter"
)

failures = []
for path in sorted(IO_DIR.glob("*.py")):
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))

    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack = []

        def visit_FunctionDef(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_ClassDef(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        def visit_Raise(self, node):
            name = None
            exc = node.exc
            if isinstance(exc, ast.Call):
                exc = exc.func
            if isinstance(exc, ast.Name):
                name = exc.id
            elif isinstance(exc, ast.Attribute):
                name = f"{getattr(exc.value, 'id', '?')}.{exc.attr}"
            if name in ("ValueError", "struct.error"):
                qual = ".".join(self.stack)
                in_decode = any(DECODE_SURFACE.search(s) for s in self.stack)
                in_encode = any(
                    ENCODE_SIDE.search(s) and "decompress" not in s
                    for s in self.stack
                ) or "ByteWriter" in self.stack
                if in_decode and not in_encode:
                    failures.append(
                        f"{path}:{node.lineno}: bare {name} in decode-surface "
                        f"function {qual!r}"
                    )
            self.generic_visit(node)

    V().visit(tree)

if failures:
    print("lint: bare ValueError/struct.error raised on the io/ decode")
    print("lint: surface (untrusted wire input must raise a classified")
    print("lint: error type — see io/kafka_codec.py CorruptFrameError):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (io/ decode surface raises only classified error types)")
EOF

# Third rule: parallel-ingest WORKER code paths (methods of *Worker*
# classes in parallel/ingest.py — code that runs on an ingest worker
# thread) must never mutate scan-shared container state without a lock.
# Shared mutable state crosses worker threads ONLY through the per-worker
# queue.Queue (thread-safe by construction) or the obs instruments (each
# guarded by its own lock); any container mutation on `self.X` / a
# closed-over name is flagged unless it sits inside a `with <...lock...>:`
# block.  Local variables are exempt (thread-confined).
python - <<'EOF'
import ast
import pathlib
import sys

PATH = pathlib.Path("kafka_topic_analyzer_tpu") / "parallel" / "ingest.py"
MUTATORS = {
    "update", "setdefault", "pop", "popitem", "clear",
    "append", "extend", "insert", "remove", "add", "discard",
}
#: Receivers whose mutation is the sanctioned cross-thread channel.
SAFE_RECEIVERS = ("queue",)

tree = ast.parse(PATH.read_text(encoding="utf-8"), filename=str(PATH))
failures = []


def local_names(fn) -> set:
    out = set(a.arg for a in fn.args.args)
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        out.add(n.id)
        elif isinstance(node, (ast.For, ast.comprehension)):
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    out.add(n.id)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            for n in ast.walk(node.optional_vars):
                if isinstance(n, ast.Name):
                    out.add(n.id)
    return out


def receiver_root(expr):
    """(root, dotted) for a Name/Attribute chain; (None, repr) otherwise."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return expr.id, ".".join(reversed(parts))
    return None, ast.dump(expr)[:40]


def check_worker_fn(cls_name, fn):
    locals_ = local_names(fn)
    guarded = set()  # nodes lexically under a `with <...lock...>` item

    def mark_guarded(node):
        for child in ast.walk(node):
            guarded.add(id(child))

    for node in ast.walk(fn):
        if isinstance(node, ast.With):
            for item in node.items:
                src = ast.unparse(item.context_expr).lower()
                if "lock" in src:
                    mark_guarded(node)

    def flag(node, what, recv):
        if id(node) in guarded:
            return
        failures.append(
            f"{PATH}:{node.lineno}: {what} on scan-shared {recv!r} in "
            f"worker path {cls_name}.{fn.name} without a lock"
        )

    for node in ast.walk(fn):
        # container[key] = / del container[key] / container[key] += on a
        # non-local receiver
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target] if isinstance(node, ast.AugAssign)
                else node.targets
            )
            for t in targets:
                if isinstance(t, ast.Subscript):
                    root, dotted = receiver_root(t.value)
                    leaf = dotted.rsplit(".", 1)[-1]
                    if (root == "self" or root not in locals_) and not any(
                        s in leaf for s in SAFE_RECEIVERS
                    ):
                        flag(node, "subscript mutation", dotted)
        # container.mutator(...) on a non-local receiver
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr in MUTATORS:
                root, dotted = receiver_root(node.func.value)
                leaf = dotted.rsplit(".", 1)[-1]
                if (root == "self" or root not in locals_) and not any(
                    s in leaf for s in SAFE_RECEIVERS
                ):
                    flag(node, f".{node.func.attr}()", dotted)


for node in ast.walk(tree):
    if isinstance(node, ast.ClassDef) and "Worker" in node.name:
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                check_worker_fn(node.name, item)

if failures:
    print("lint: unsynchronized scan-shared container mutation in a")
    print("lint: parallel-ingest worker code path (share through the")
    print("lint: worker queue / obs instruments, or hold a lock):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (parallel-ingest worker paths mutate no unlocked shared state)")
EOF

# Fourth rule: the superbatch drive loop can never hold more than
# --dispatch-depth staged superbatches.  Structurally enforced two ways:
# (a) in-flight dispatch bookkeeping (any attribute whose name contains
#     'inflight') is CONFINED to backends/base.py's DispatchQueue — no
#     drive loop or backend keeps its own unbounded in-flight list;
# (b) every function that records a launch (`.launched(`) also calls the
#     bound (`.throttle(`) in the same body, so a dispatch site cannot
#     launch without first blocking below the depth limit.
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
QUEUE_HOME = PKG / "backends" / "base.py"
#: Where device dispatch lives: the backends, the mesh layer, the engine.
#: (io/kafka_wire.py has its own fetch-request `_inflight` — a different,
#: per-connection send-ahead window, bounded by the wire layer itself.)
DISPATCH_SCOPE = [PKG / "engine.py"] + sorted(
    (PKG / "backends").glob("*.py")
) + sorted((PKG / "parallel").glob("*.py"))

failures = []
for path in sorted(PKG.rglob("*.py")):
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    # (a) inflight bookkeeping confined to DispatchQueue.
    if path != QUEUE_HOME and path in DISPATCH_SCOPE:
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and "inflight" in node.attr.lower():
                failures.append(
                    f"{path}:{node.lineno}: in-flight dispatch bookkeeping "
                    f"({node.attr!r}) outside backends/base.DispatchQueue"
                )
    # (b) launch sites must throttle.
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        calls = {
            n.func.attr
            for n in ast.walk(node)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
        }
        if "launched" in calls and "throttle" not in calls:
            failures.append(
                f"{path}:{node.lineno}: {node.name!r} launches a dispatch "
                "without calling the depth throttle first"
            )

if failures:
    print("lint: superbatch dispatch-depth bound violated (in-flight")
    print("lint: tracking lives in backends/base.DispatchQueue; every")
    print("lint: launch site must throttle to --dispatch-depth first):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (drive loops bound staged superbatches by dispatch depth)")
EOF

# Fifth rule: lockstep safety on the sharded mesh.  Every collective call
# site in the sharded superbatch/drain path must be reachable by ALL
# controllers: a collective launched under a condition that can DIFFER
# between controllers (process-local rows, process index, per-row
# liveness, locally-observed degradation/corruption) is a deadlock — one
# controller enters the collective, its peers never do.  AST rule over
# parallel/sharded.py and engine.py: calls to the collective entry points
# must not sit lexically under an `if`/`while` whose condition (or a
# `for` whose iterable) references a per-controller-varying name.
# Uniform guards (feature flags, superbatch config, `_multiprocess` —
# process_count is the same everywhere) stay legal.
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
FILES = [PKG / "parallel" / "sharded.py", PKG / "engine.py"]
#: Host-level collective entry points (methods that launch a program every
#: controller must join).  Traced-code collectives (lax.psum etc.) compile
#: uniformly and are exempt — only runtime call sites can diverge.
COLLECTIVE_ATTRS = {
    "_step", "_superstep", "_any_fn", "_merge", "_pmax_fn",
    "update_shards", "update_shards_superbatch", "global_any",
    "gather_telemetry",
}
COLLECTIVE_NAMES = {"lockstep", "dispatch_fn"}
#: Names whose value varies per controller: a collective under a test of
#: one of these is one-sided.
VARYING = {
    "local_rows", "process_index", "addressable_shards", "feed_rows",
    "alive", "degraded", "corrupt", "local_flag", "step_valid",
    "fed_partitions", "row_workers",
}

failures = []
for path in FILES:
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    # Parent links for ancestor walks.
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def names_in(expr):
        return {
            n.id for n in ast.walk(expr) if isinstance(n, ast.Name)
        } | {
            n.attr for n in ast.walk(expr) if isinstance(n, ast.Attribute)
        }

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        is_collective = (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in COLLECTIVE_ATTRS
        ) or (
            isinstance(node.func, ast.Name)
            and node.func.id in COLLECTIVE_NAMES
        )
        if not is_collective:
            continue
        cur = node
        while cur in parents:
            parent = parents[cur]
            bad = None
            if isinstance(parent, (ast.If, ast.While)) and cur in (
                parent.body + parent.orelse
            ):
                # Only the guarded blocks — not the test expression itself.
                bad = names_in(parent.test) & VARYING
            elif isinstance(parent, ast.For) and cur in parent.body:
                bad = names_in(parent.iter) & VARYING
            elif isinstance(parent, ast.IfExp):
                bad = names_in(parent.test) & VARYING
            if bad:
                label = (
                    node.func.attr
                    if isinstance(node.func, ast.Attribute)
                    else node.func.id
                )
                failures.append(
                    f"{path}:{node.lineno}: collective {label!r} guarded by "
                    f"per-controller-varying name(s) {sorted(bad)} — "
                    "unreachable on peers, would deadlock the fleet"
                )
                break
            cur = parent

if failures:
    print("lint: collective call sites must be reachable by ALL")
    print("lint: controllers (no collective under a per-controller")
    print("lint: early-return or varying condition — DESIGN.md §14):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (sharded collectives sit on lockstep-reachable paths)")
EOF

# Sixth rule: the fused decode→pack path is an OPTIMIZATION, never a
# dependency.  (a) Every fused call site (sink.append_*, sink draining,
# make_sink invocation) must sit under a guard that can turn it off —
# tier-1 passes with the native build disabled via KTA_DISABLE_NATIVE, so
# each site needs a reachable python-chain fallback branch.  (b) The
# kill-switch env knobs must exist where the gates read them.
# packing.py (the sink implementation itself) is exempt: it is only
# reachable through gated call sites, by this very rule.
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
FILES = [
    PKG / "engine.py",
    PKG / "io" / "kafka_wire.py",
    PKG / "io" / "segfile.py",
    PKG / "parallel" / "ingest.py",
]
#: Calls that enter the fused path.
FUSED_ATTRS = {
    "append_record_set", "append_columns", "append_batch",
    "take_completed",
}
FUSED_NAMES = {"make_sink"}
#: Names whose truthiness gates the fused path off.
GUARDS = {
    "sink", "fused", "sink_factory", "use_native_decode",
    "native_available", "fused_ingest_enabled", "supports_fused_sink",
}

failures = []
for path in FILES:
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def names_in(expr):
        return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        label = None
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in FUSED_ATTRS
        ):
            # Only sink-ish receivers; batch.take()/writer.append() etc.
            # share method names but different receivers.
            root = node.func.value
            while isinstance(root, ast.Attribute):
                root = root.value
            if not (isinstance(root, ast.Name) and "sink" in root.id.lower()):
                continue
            label = node.func.attr
        elif isinstance(node.func, ast.Name) and node.func.id in FUSED_NAMES:
            label = node.func.id
        if label is None:
            continue
        guarded = False
        cur = node
        while cur in parents and not guarded:
            parent = parents[cur]
            test = None
            if isinstance(parent, (ast.If, ast.While)) and cur is not parent.test:
                test = parent.test
            elif isinstance(parent, ast.IfExp) and cur is not parent.test:
                test = parent.test
            if test is not None and names_in(test) & GUARDS:
                guarded = True
            cur = parent
        if not guarded:
            failures.append(
                f"{path}:{node.lineno}: fused call {label!r} has no "
                "reachable python-chain fallback guard (sink/fused gate)"
            )

# (b) kill-switch knobs live where the gates read them.
if "KTA_DISABLE_NATIVE" not in (PKG / "io" / "native.py").read_text():
    failures.append(
        "io/native.py: KTA_DISABLE_NATIVE env knob missing (tier-1 must "
        "be runnable with the native build disabled)"
    )
if "KTA_DISABLE_FUSED" not in (PKG / "packing.py").read_text():
    failures.append(
        "packing.py: KTA_DISABLE_FUSED env knob missing from "
        "fused_ingest_enabled"
    )
# (c) alive-pair compaction is an optimization with the same contract:
# the env kill switch must exist at the one resolution site (config.py),
# and the engine must book every bypassed compaction with its reason
# (kta_alive_compaction_off_total — a silent bypass is a lint failure).
if "KTA_DISABLE_COMPACTION" not in (PKG / "config.py").read_text():
    failures.append(
        "config.py: KTA_DISABLE_COMPACTION env knob missing from the "
        "alive_compaction resolution (__post_init__)"
    )
if "ALIVE_COMPACTION_OFF.labels(" not in (PKG / "engine.py").read_text():
    failures.append(
        "engine.py: kta_alive_compaction_off_total booking missing — an "
        "alive-key scan running uncompacted must record its reason"
    )

if failures:
    print("lint: fused decode→pack call sites must be gated so the")
    print("lint: python chain stays reachable (no hard native dependency):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (fused call sites keep a reachable python-chain fallback)")
EOF

# Seventh rule: wire-layout offsets and dtypes may only be derived from
# packing._sections (the single layout source for BOTH wire formats).
# Backends, the native shim glue, the parallel layer, and tests must not
# hand-carve a packed buffer — a literal-offset slice-and-view would pin
# one format's layout and silently skew when the section list changes
# (v4→v5 moved every offset).  AST rule: in the scoped files, (a) no
# references to packing.HEADER_BYTES (offset arithmetic belongs next to
# the section list), and (b) no `.view(dtype)` / `frombuffer`-style
# retyping of a subscript whose slice bounds are integer literals >= 16
# (the header size — i.e. a hard-coded section offset).
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
SCOPE = (
    sorted((PKG / "backends").glob("*.py"))
    + sorted((PKG / "parallel").glob("*.py"))
    + [PKG / "io" / "native.py"]
    + sorted(pathlib.Path("tests").glob("*.py"))
)

#: The compacted pair-table layout (PR 12) has exactly one source too:
#: packing._sections(pair_table=True) behind these helpers.  Scoped files
#: may CALL them (imported from packing) but never re-derive the layout.
PAIR_HELPERS = {
    "pack_pair_table", "unpack_pair_table_device",
    "unpack_pair_table_numpy", "pair_table_capacity", "pair_table_nbytes",
}

failures = []
for path in SCOPE:
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    packing_imports = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.ImportFrom)
            and node.module
            and node.module.endswith("packing")
        ):
            packing_imports |= {a.name for a in node.names}
    for node in ast.walk(tree):
        # (c) pair-table helpers must come from packing (no local
        # reimplementation/shadowing of the pair-table buffer layout;
        # wrappers that CALL the imported helpers are fine).
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
            node.name in PAIR_HELPERS
        ):
            failures.append(
                f"{path}:{node.lineno}: local {node.name!r} definition "
                "shadows the packing helper — the pair-table layout "
                "lives in packing._sections only"
            )
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in PAIR_HELPERS
            and node.func.id not in packing_imports
        ):
            failures.append(
                f"{path}:{node.lineno}: {node.func.id} called without "
                "importing it from packing — pair tables are only "
                "addressed via packing._sections' helpers"
            )
        # (a) HEADER_BYTES belongs to packing.py.
        if isinstance(node, ast.Name) and node.id == "HEADER_BYTES":
            failures.append(
                f"{path}:{node.lineno}: HEADER_BYTES referenced outside "
                "packing.py — derive section positions from "
                "packing._sections"
            )
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "HEADER_BYTES"
        ):
            failures.append(
                f"{path}:{node.lineno}: packing.HEADER_BYTES referenced — "
                "derive section positions from packing._sections"
            )
        # (b) literal-offset slice retyped in place: buf[123:456].view(...)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "view"
            and isinstance(node.func.value, ast.Subscript)
            and isinstance(node.func.value.slice, ast.Slice)
        ):
            sl = node.func.value.slice
            bounds = [
                b.value
                for b in (sl.lower, sl.upper)
                if isinstance(b, ast.Constant) and isinstance(b.value, int)
            ]
            if any(b >= 16 for b in bounds):
                failures.append(
                    f"{path}:{node.lineno}: hard-coded wire offset "
                    "(literal slice + .view) — derive offsets from "
                    "packing._sections / unpack_numpy"
                )

if failures:
    print("lint: wire-layout offsets hard-coded outside packing._sections")
    print("lint: (the section list is the single layout source — wire v4")
    print("lint: AND v5; see packing.py module docstring / DESIGN.md §16):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (wire offsets derive only from packing._sections)")
EOF

# Eighth rule: every instrument in obs/metrics.py (the one catalog module)
# must carry a cross-process MERGE POLICY and a README catalog row.
# Counters and histograms are additive by construction (obs/registry.py's
# merge algebra — the only sound policy for monotone series), so their
# policy is the type itself; gauges are ambiguous (fleet-max vs
# disjoint-local-sum) and MUST pass an explicit merge= keyword — a gauge
# added without one silently gets max-merged, which undercounts every
# disjoint-per-process quantity the moment a mesh scan gathers telemetry.
# And every constructed metric name must have a row in the README metric
# catalog, so the documented surface can never lag the shipped one.
python - <<'EOF'
import ast
import pathlib
import sys

METRICS = pathlib.Path("kafka_topic_analyzer_tpu") / "obs" / "metrics.py"
README = pathlib.Path("README.md").read_text(encoding="utf-8")

failures = []
names = []
tree = ast.parse(METRICS.read_text(encoding="utf-8"), filename=str(METRICS))
for node in ast.walk(tree):
    if not (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr in ("counter", "gauge", "histogram")
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == "_REG"
    ):
        continue
    if not (
        node.args
        and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, str)
    ):
        failures.append(
            f"{METRICS}:{node.lineno}: instrument name must be a string "
            "literal (the catalog is audited statically)"
        )
        continue
    name = node.args[0].value
    names.append((node.lineno, name))
    if node.func.attr == "gauge":
        kws = {kw.arg for kw in node.keywords}
        if "merge" not in kws:
            failures.append(
                f"{METRICS}:{node.lineno}: gauge {name!r} does not declare "
                "an explicit merge= policy (max for same-quantity gauges, "
                "sum for disjoint per-process counts)"
            )

for lineno, name in names:
    if name not in README:
        failures.append(
            f"{METRICS}:{lineno}: instrument {name!r} has no README "
            "metric-catalog row"
        )

if failures:
    print("lint: obs/metrics.py instruments must declare a merge policy")
    print("lint: (explicit merge= on every gauge) and carry a README")
    print("lint: metric-catalog row:")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print(f"lint: OK ({len(names)} instruments: merge policies declared, "
      "README catalog rows present)")
EOF

# Ninth rule: the service HTTP surface can never stall ingest.  Handler
# code (do_* methods / BaseHTTPRequestHandler subclasses) under serve/
# and obs/exporters.py may not call into the drive loop or fold state
# (run/update/finalize/get_state/..., the source read loop, the window
# fold), may not take locks of its own (.acquire / `with <lock>`), and
# may not SERIALIZE (json.dumps / gzip.compress / GzipFile): encoding
# happens ONCE on the publishing side (serve/state.py's publish-time
# triple, history/flight's *_bytes accessors) — a handler that
# serializes per request turns N pollers into N encodes and re-creates
# the very cost the conditional-GET plane removes.  Everything a handler
# serves must come through a designated snapshot accessor —
# ServiceState.entry, healthz_entry, window_etag/window_bytes,
# series_etag/series_bytes, subscribe/next_frame, or render_prometheus
# over a registry snapshot — whose single-reference-swap locking is
# owned by the publishing side.  A scrape is then O(headers) work, and a
# slow client can never hold a lock the fold path wants (DESIGN.md §18
# snapshot-consistency rule, §26 read path).  The SSE publisher
# (serve/push.py SsePublisher) is the one piece of serving-plane code
# with its own thread + lock, so it gets the complementary no-fold-state
# check: its methods may never reach a drive-loop entry point either —
# it consumes published events, it never drives publishing.
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
#: obs/health.py and obs/history.py are in scope for the same reason
#: exporters.py is: the /healthz and /history surfaces live behind them,
#: and any handler code that grows there inherits the purity rule.
SCOPE = sorted((PKG / "serve").glob("*.py")) + [
    PKG / "obs" / "exporters.py",
    PKG / "obs" / "health.py",
    PKG / "obs" / "history.py",
]
#: Drive-loop / fold-state entry points a handler must never reach.
DRIVE_CALLS = {
    "run", "run_scan", "run_follow",
    "update", "update_shards", "update_superbatch",
    "update_shards_superbatch", "finalize",
    "get_state", "set_state", "get_state_local", "set_state_local",
    "observe_batch", "observe", "merge", "merged",
    "batches", "refresh_watermarks", "watermarks",
    "publish", "request_stop",
    # Alert-engine mutation points: a probe must never trigger an
    # evaluation (evaluation belongs to the poll/heartbeat boundaries).
    "evaluate", "maybe_evaluate", "append",
}
#: The sanctioned read-only snapshot accessors.  /healthz reads the
#: engine's pre-serialized verdict; /history and /flight read their
#: stores' pre-encoded (body, etag) pairs under the stores' own locks;
#: /report.json reads the publish-time (raw, gzipped, etag) triple;
#: /events reads pre-formatted frames off its subscriber queue.
ACCESSORS = {"report_bytes", "snapshot", "series", "active",
             "render_prometheus", "healthz", "window", "doc",
             "alerts_block", "entry", "healthz_entry",
             "window_etag", "window_bytes", "series_etag", "series_bytes",
             "subscribe", "unsubscribe", "next_frame"}
#: Per-request serialization is forbidden in handlers: encoding is paid
#: once at publish time, never per scrape (DESIGN.md §26).
SERIALIZERS = {"dumps", "dump", "compress", "GzipFile"}

failures = []
for path in SCOPE:
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))

    handler_fns = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            bases = {
                getattr(b, "id", getattr(b, "attr", "")) for b in node.bases
            }
            is_handler_cls = node.name.endswith("Handler") or any(
                "Handler" in b for b in bases
            )
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if is_handler_cls or item.name.startswith("do_"):
                        handler_fns.append((node.name, item))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name.startswith("do_"):
                handler_fns.append(("", node))

    for cls_name, fn in handler_fns:
        qual = f"{cls_name}.{fn.name}" if cls_name else fn.name
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = None
                if isinstance(node.func, ast.Attribute):
                    name = node.func.attr
                elif isinstance(node.func, ast.Name):
                    name = node.func.id
                if name in ACCESSORS:
                    continue
                if name in DRIVE_CALLS:
                    failures.append(
                        f"{path}:{node.lineno}: HTTP handler {qual!r} calls "
                        f"drive-loop/fold-state entry point {name!r} — serve "
                        "from the designated snapshot accessor instead"
                    )
                if name in SERIALIZERS:
                    failures.append(
                        f"{path}:{node.lineno}: HTTP handler {qual!r} "
                        f"serializes per request ({name!r}) — encoding is "
                        "paid once at publish time (serve/state.py, the "
                        "history/flight *_bytes accessors), never per scrape"
                    )
                if name == "acquire":
                    failures.append(
                        f"{path}:{node.lineno}: HTTP handler {qual!r} takes "
                        "a lock (.acquire) — locking belongs to the snapshot "
                        "accessor, not the scrape path"
                    )
            if isinstance(node, ast.With):
                for item in node.items:
                    src = ast.unparse(item.context_expr).lower()
                    if "lock" in src:
                        failures.append(
                            f"{path}:{node.lineno}: HTTP handler {qual!r} "
                            "holds a lock (`with ...lock...`) — serve "
                            "pre-published snapshots instead"
                        )

# The SSE publisher's no-fold-state check: SsePublisher consumes the
# publish stream, it must never drive it.  Its own intake deque/subscriber
# list mutations (.append) and its own lock are its sanctioned machinery,
# so only the fold/drive entry points are forbidden — not container
# mutators or locking.
PUSH = PKG / "serve" / "push.py"
FOLD_CALLS = DRIVE_CALLS - {"append", "request_stop"}
push_tree = ast.parse(PUSH.read_text(encoding="utf-8"), filename=str(PUSH))
for node in ast.walk(push_tree):
    if not (isinstance(node, ast.ClassDef) and node.name == "SsePublisher"):
        continue
    for item in node.body:
        if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for n in ast.walk(item):
            if isinstance(n, ast.Call):
                name = None
                if isinstance(n.func, ast.Attribute):
                    name = n.func.attr
                elif isinstance(n.func, ast.Name):
                    name = n.func.id
                if name in FOLD_CALLS and name not in ACCESSORS:
                    failures.append(
                        f"{PUSH}:{n.lineno}: SsePublisher.{item.name} calls "
                        f"drive-loop/fold-state entry point {name!r} — the "
                        "publisher consumes published events, it never "
                        "drives publishing"
                    )

if failures:
    print("lint: service HTTP handlers must read only designated snapshot")
    print("lint: accessors (no drive-loop calls, no per-request")
    print("lint: serialization, no fold-state locks — a slow scrape can")
    print("lint: never stall ingest; DESIGN.md §18/§26):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (service HTTP handlers read only published snapshots; "
      "SSE publisher drives nothing)")
EOF

# Tenth rule: the fleet admission layer is PURE BOOKKEEPING.  (a) The
# scheduler (fleet/scheduler.py) — and any HTTP-handler code under
# fleet/ — may not call collective or drive-loop entry points directly
# (rule 9's surface plus the host-level collectives from rule 5): the
# layer that decides WHO runs must never be the layer that runs them, or
# an admission decision could block on a fetch, hold a fold lock, or
# launch a one-sided collective.  Only fleet/service.py drives scans.
# (b) Every admission decision books a kta_fleet_* reason: each decision
# method on the scheduler (admit/release/skip/rebalance families) must
# reference a FLEET_* instrument — the admission trace must be
# reconstructible from the counters alone (DESIGN.md §20).
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
FLEET = sorted((PKG / "fleet").glob("*.py"))
SCHEDULER = PKG / "fleet" / "scheduler.py"
#: Rule 9's drive-loop surface + rule 5's host-level collectives.
FORBIDDEN = {
    "run", "run_scan", "run_follow", "run_batch",
    "update", "update_shards", "update_superbatch",
    "update_shards_superbatch", "finalize",
    "get_state", "set_state", "get_state_local", "set_state_local",
    "observe_batch", "observe", "batches",
    "refresh_watermarks", "watermarks",
    "global_any", "gather_telemetry", "_step", "_superstep",
}
#: Scheduler methods that ARE admission decisions: each must book.
DECISION_PREFIXES = ("admit", "release", "skip_", "rebalance")

failures = []
for path in FLEET:
    tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    is_scheduler = path == SCHEDULER
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            bases = {
                getattr(b, "id", getattr(b, "attr", "")) for b in node.bases
            }
            is_handler = node.name.endswith("Handler") or any(
                "Handler" in b for b in bases
            )
            for item in node.body:
                if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                check_calls = is_scheduler or is_handler
                if check_calls:
                    for n in ast.walk(item):
                        if isinstance(n, ast.Call):
                            name = None
                            if isinstance(n.func, ast.Attribute):
                                name = n.func.attr
                            elif isinstance(n.func, ast.Name):
                                name = n.func.id
                            if name in FORBIDDEN:
                                failures.append(
                                    f"{path}:{n.lineno}: fleet scheduler/"
                                    f"handler {node.name}.{item.name} calls "
                                    f"drive-loop/collective entry point "
                                    f"{name!r} — only fleet/service.py "
                                    "drives scans"
                                )
                if is_scheduler and item.name.startswith(DECISION_PREFIXES):
                    books = any(
                        isinstance(n, ast.Attribute)
                        and n.attr.startswith("FLEET_")
                        for n in ast.walk(item)
                    )
                    if not books:
                        failures.append(
                            f"{path}:{item.lineno}: admission decision "
                            f"{node.name}.{item.name} books no kta_fleet_* "
                            "reason (obs/metrics FLEET_* instrument)"
                        )

if failures:
    print("lint: the fleet admission layer must stay pure bookkeeping")
    print("lint: (no drive-loop/collective calls from the scheduler or")
    print("lint: fleet handlers; every admission decision books a")
    print("lint: kta_fleet_* reason — DESIGN.md §20):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (fleet scheduler is pure; admission decisions book reasons)")
EOF

# Eleventh rule: the remote segment tier's network I/O is confined to the
# retry-budget wrapper.  (a) Raw HTTP request primitives (.request /
# .getresponse) may appear ONLY inside io/objstore.py's RetryingHttp —
# the one class that paces attempts through io/retry.Backoff and routes
# failure streaks through the PartitionRetryBudget; any other call site
# would be a bare retry loop (or no retry at all).  (b) No other io/
# module may import an HTTP client (http.client, urllib.request) — the
# object-store protocol has exactly one door.  (The Kafka wire client's
# raw socket use is its own protocol layer, with its own PR-1 budget.)  (c) No unbooked sleeps:
# time.sleep is forbidden in io/objstore.py, io/segstore.py and
# io/segfile.py (pacing goes through Backoff.sleep_for, which books
# kta_backoff_sleep_seconds_total).  (d) Every fallback-to-direct-fetch
# path books a kta_segstore_* reason: each except handler in
# SegmentCache's get/put must either re-raise or reference the
# SEGSTORE_FALLBACK instrument (via _book_fallback) — a silent cache
# bypass is a lint failure.
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
OBJSTORE = PKG / "io" / "objstore.py"
NO_SLEEP = [OBJSTORE, PKG / "io" / "segstore.py", PKG / "io" / "segfile.py"]
NET_MODULES = {"http", "urllib"}

failures = []

# (a) request/getresponse confined to RetryingHttp.
tree = ast.parse(OBJSTORE.read_text(encoding="utf-8"), filename=str(OBJSTORE))
class_of = {}
for node in ast.walk(tree):
    if isinstance(node, ast.ClassDef):
        for child in ast.walk(node):
            class_of.setdefault(id(child), node.name)
for node in ast.walk(tree):
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr in ("request", "getresponse", "urlopen")
    ):
        if class_of.get(id(node)) != "RetryingHttp":
            failures.append(
                f"{OBJSTORE}:{node.lineno}: raw HTTP call "
                f"{node.func.attr!r} outside RetryingHttp (the "
                "retry-budget wrapper is the only network door)"
            )

# (b) no other io/ module imports an HTTP/socket client.
for path in sorted((PKG / "io").glob("*.py")):
    if path == OBJSTORE:
        continue
    t = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    for node in ast.walk(t):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            mods = [node.module]
        for mod in mods:
            root = mod.split(".")[0]
            if root in NET_MODULES:
                failures.append(
                    f"{path}:{node.lineno}: imports {mod!r} — remote-store "
                    "network I/O belongs to io/objstore.py's RetryingHttp"
                )

# (c) no unbooked sleeps on the remote tier.
for path in NO_SLEEP:
    t = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    for node in ast.walk(t):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "sleep"
        ):
            failures.append(
                f"{path}:{node.lineno}: bare sleep() — pace retries via "
                "io/retry.Backoff.sleep_for (booked) instead"
            )

# (d) cache fallback paths book their reason.
def references_fallback(handler) -> bool:
    for n in ast.walk(handler):
        if isinstance(n, ast.Name) and "fallback" in n.id.lower():
            return True
        if isinstance(n, ast.Attribute) and n.attr.startswith("SEGSTORE_"):
            return True
        if isinstance(n, ast.Raise):
            return True
    return False

for node in ast.walk(tree):
    if isinstance(node, ast.ClassDef) and node.name == "SegmentCache":
        for item in node.body:
            if not isinstance(item, ast.FunctionDef):
                continue
            if item.name not in ("get", "put"):
                continue
            for n in ast.walk(item):
                if isinstance(n, ast.ExceptHandler) and not (
                    references_fallback(n)
                ):
                    # Handlers that only signal a MISS (return None) are
                    # cache-absent, not a fallback: the miss counter in
                    # the same body books them.  Require at least the
                    # miss/fallback instrument in the enclosing function.
                    books = any(
                        isinstance(m, ast.Attribute)
                        and m.attr.startswith("SEGSTORE_")
                        for m in ast.walk(item)
                    )
                    if not books:
                        failures.append(
                            f"{OBJSTORE}:{n.lineno}: SegmentCache."
                            f"{item.name} swallows an error without "
                            "booking a kta_segstore_* reason"
                        )

if failures:
    print("lint: remote segment tier network/booking discipline violated")
    print("lint: (HTTP only via RetryingHttp, sleeps only via Backoff,")
    print("lint: cache bypasses always booked — DESIGN.md §21):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (remote segment tier: one network door, booked fallbacks)")
EOF

# Twelfth rule: no silent alert-state changes.  The health engine's rule
# state machine (obs/health.py) may change an alert's state ONLY inside
# HealthEngine._transition — the one method that books
# kta_alerts_transitions_total{rule=,state=} (and moves the firing
# gauge / emits the typed event).  AST-enforced two ways:
# (a) every assignment to a `.state` attribute in obs/health.py sits
#     lexically inside `_transition` (dataclass field defaults are
#     class-body Name targets, not attribute assignments, and stay
#     legal);
# (b) `_transition` itself references the ALERTS_TRANSITIONS instrument
#     and the event bus — a transition that books nothing is a lint
#     failure, not a code-review nit.
python - <<'EOF'
import ast
import pathlib
import sys

HEALTH = pathlib.Path("kafka_topic_analyzer_tpu") / "obs" / "health.py"

tree = ast.parse(HEALTH.read_text(encoding="utf-8"), filename=str(HEALTH))
failures = []

# Map every node to its enclosing function name.
enclosing = {}


def walk(node, fn_name):
    for child in ast.iter_child_nodes(node):
        name = fn_name
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name = child.name
        enclosing[id(child)] = name
        walk(child, name)


walk(tree, "<module>")

transition_fn = None
for node in ast.walk(tree):
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
        node.name == "_transition"
    ):
        transition_fn = node
    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        for t in targets:
            if isinstance(t, ast.Attribute) and t.attr == "state":
                if enclosing.get(id(node)) != "_transition":
                    failures.append(
                        f"{HEALTH}:{node.lineno}: alert state assigned "
                        f"outside HealthEngine._transition (silent state "
                        "change) — route it through _transition"
                    )

if transition_fn is None:
    failures.append(f"{HEALTH}: HealthEngine._transition missing")
else:
    names = {
        n.attr for n in ast.walk(transition_fn)
        if isinstance(n, ast.Attribute)
    } | {
        n.id for n in ast.walk(transition_fn) if isinstance(n, ast.Name)
    }
    if "ALERTS_TRANSITIONS" not in names:
        failures.append(
            f"{HEALTH}:{transition_fn.lineno}: _transition does not book "
            "kta_alerts_transitions_total (obs/metrics ALERTS_TRANSITIONS)"
        )
    if "emit" not in names:
        failures.append(
            f"{HEALTH}:{transition_fn.lineno}: _transition emits no typed "
            "event on the JSONL bus"
        )

if failures:
    print("lint: alert-state transitions must all route through")
    print("lint: HealthEngine._transition, which books the transitions")
    print("lint: counter and emits the typed event (DESIGN.md §22):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (alert-state transitions book their reason; none silent)")
EOF

# Thirteenth rule: no silent lease-ownership changes.  The fleet's
# arbitration layer (fleet/lease.py) may change a held lease's state
# ONLY inside LeaseManager._transition — the one method that books the
# kta_lease_* instruments (acquisitions/held/losses, plus
# kta_fleet_failovers_total on takeover) and emits the typed event.
# AST-enforced three ways:
# (a) every assignment to a `.state` attribute in fleet/lease.py sits
#     lexically inside `_transition` (dataclass field defaults are
#     class-body Name targets, not attribute assignments, and stay
#     legal);
# (b) `_transition` itself references the lease instruments and the
#     event bus — a transition that books nothing is a lint failure;
# (c) every acquire/renew/release/fence decision method books a reason:
#     it must reference a LEASE_*/FLEET_FAILOVERS instrument, call
#     `_transition`, or delegate to another decision method — no
#     decision path is silent.
python - <<'EOF'
import ast
import pathlib
import sys

LEASE = pathlib.Path("kafka_topic_analyzer_tpu") / "fleet" / "lease.py"

tree = ast.parse(LEASE.read_text(encoding="utf-8"), filename=str(LEASE))
failures = []

# Map every node to its enclosing function name.
enclosing = {}


def walk(node, fn_name):
    for child in ast.iter_child_nodes(node):
        name = fn_name
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name = child.name
        enclosing[id(child)] = name
        walk(child, name)


walk(tree, "<module>")

DECISION_PREFIXES = ("acquire", "renew", "release", "fence")
INSTRUMENTS = {
    "LEASE_ACQUISITIONS", "LEASE_RENEWALS", "LEASE_LOSSES", "LEASE_HELD",
    "FLEET_FAILOVERS",
}


def refs(fn):
    return {
        n.attr for n in ast.walk(fn) if isinstance(n, ast.Attribute)
    } | {n.id for n in ast.walk(fn) if isinstance(n, ast.Name)}


transition_fn = None
decision_fns = []
for node in ast.walk(tree):
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        if node.name == "_transition":
            transition_fn = node
        stripped = node.name.lstrip("_")
        if stripped.startswith(DECISION_PREFIXES):
            decision_fns.append(node)
    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        for t in targets:
            if isinstance(t, ast.Attribute) and t.attr == "state":
                if enclosing.get(id(node)) != "_transition":
                    failures.append(
                        f"{LEASE}:{node.lineno}: lease state assigned "
                        f"outside LeaseManager._transition (silent "
                        "ownership change) — route it through _transition"
                    )

if transition_fn is None:
    failures.append(f"{LEASE}: LeaseManager._transition missing")
else:
    names = refs(transition_fn)
    if not (INSTRUMENTS & names):
        failures.append(
            f"{LEASE}:{transition_fn.lineno}: _transition books no "
            "kta_lease_* instrument (obs/metrics LEASE_*)"
        )
    if "emit" not in names:
        failures.append(
            f"{LEASE}:{transition_fn.lineno}: _transition emits no typed "
            "event on the JSONL bus"
        )

if not decision_fns:
    failures.append(
        f"{LEASE}: no acquire/renew/release/fence decision methods found"
    )
for fn in decision_fns:
    names = refs(fn)
    delegates = any(
        n.lstrip("_").startswith(DECISION_PREFIXES)
        for n in names
        if n != fn.name
    )
    if not (INSTRUMENTS & names) and "_transition" not in names and (
        not delegates
    ):
        failures.append(
            f"{LEASE}:{fn.lineno}: decision method {fn.name} books no "
            "kta_lease_* reason (no instrument, no _transition, no "
            "delegation to a booking decision method)"
        )

if failures:
    print("lint: lease-ownership transitions must all route through")
    print("lint: LeaseManager._transition, which books the kta_lease_*")
    print("lint: instruments and emits the typed event (DESIGN.md §23):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (lease transitions book their reason; none silent)")
EOF

# Fourteenth rule: no silent cursor jumps past unread log.  The wire
# source (io/kafka_wire.py) may advance a partition cursor past offsets
# it never read ONLY on a path that books the skip: the kta_log_*
# family through KafkaWireSource._note_lost (retention races, epoch
# fences, truncation, resume-below-log-start) or the corruption ledger
# through _note_corrupt/book_corruption (poison-frame skips).
# AST-enforced four ways:
# (a) _note_lost is the one loss choke point: it books BOTH kta_log_*
#     counters, emits the typed event, and carries the --on-data-loss
#     fail abort (DataLossError) — booking that cannot meter or abort
#     is a lint failure;
# (b) every function classifying a log-mutation signal (referencing
#     ERR_OFFSET_OUT_OF_RANGE / ERR_FENCED_LEADER_EPOCH /
#     ERR_UNKNOWN_LEADER_EPOCH) must reach _note_lost or a LOG_*
#     instrument — no mutation-classified path is silent;
# (c) every subscript assignment to a cursor map (next_offset/offsets)
#     whose value is NOT a read-derived progression (last+1, covered,
#     frame_next, max_frame_end — values bounded by frames actually
#     read) sits in a function that references a booking helper or a
#     LOG_*/CORRUPT* instrument;
# (d) the follow service's watermark poll (serve/follow.py _poll) books
#     kta_log_watermark_regressions_total and emits the event before it
#     holds or adopts a regressed head.
python - <<'EOF'
import ast
import pathlib
import sys

WIRE = pathlib.Path("kafka_topic_analyzer_tpu") / "io" / "kafka_wire.py"
FOLLOW = pathlib.Path("kafka_topic_analyzer_tpu") / "serve" / "follow.py"

failures = []

MUTATION_SIGNALS = {
    "ERR_OFFSET_OUT_OF_RANGE",
    "ERR_FENCED_LEADER_EPOCH",
    "ERR_UNKNOWN_LEADER_EPOCH",
}
BOOKERS = {"_note_lost", "_note_corrupt", "book_corruption"}
CURSOR_MAPS = {"next_offset", "offsets"}
#: Value leaves that prove the advance is bounded by frames actually
#: read (batch-header progression), not by watermarks or probes.
PROGRESSION_NAMES = {"last", "covered", "frame_next", "max_frame_end"}


def refs(fn):
    return {
        n.attr for n in ast.walk(fn) if isinstance(n, ast.Attribute)
    } | {n.id for n in ast.walk(fn) if isinstance(n, ast.Name)}


def books(names):
    return bool(
        BOOKERS & names
        or any(n.startswith(("LOG_", "CORRUPT")) for n in names)
    )


def nearest_functions(tree):
    enclosing = {}

    def walk(node, fn):
        for child in ast.iter_child_nodes(node):
            f = fn
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                f = child
            enclosing[id(child)] = f
            walk(child, f)

    walk(tree, None)
    return enclosing


wire_tree = ast.parse(WIRE.read_text(encoding="utf-8"), filename=str(WIRE))
enclosing = nearest_functions(wire_tree)

# (a) the choke point itself.
note_lost = None
for node in ast.walk(wire_tree):
    if isinstance(node, ast.FunctionDef) and node.name == "_note_lost":
        note_lost = node
if note_lost is None:
    failures.append(f"{WIRE}: KafkaWireSource._note_lost missing")
else:
    names = refs(note_lost)
    for need in ("LOG_LOST_RECORDS", "LOG_LOST_RANGES", "emit",
                 "DataLossError"):
        if need not in names:
            failures.append(
                f"{WIRE}:{note_lost.lineno}: _note_lost does not "
                f"reference {need} — loss booking must meter both "
                "kta_log_* counters, emit the event, and carry the "
                "--on-data-loss fail abort"
            )

# (b) mutation-signal classification is never silent.
for node in ast.walk(wire_tree):
    if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        continue
    names = refs(node)
    if MUTATION_SIGNALS & names and node.name != "_note_lost":
        if "_note_lost" not in names and not any(
            n.startswith("LOG_") for n in names
        ):
            failures.append(
                f"{WIRE}:{node.lineno}: {node.name} classifies a "
                "log-mutation signal but never reaches _note_lost or a "
                "kta_log_* instrument"
            )

# (c) cursor jumps book their reason.
for node in ast.walk(wire_tree):
    if not isinstance(node, ast.Assign):
        continue
    for t in node.targets:
        if not (
            isinstance(t, ast.Subscript)
            and isinstance(t.value, ast.Name)
            and t.value.id in CURSOR_MAPS
        ):
            continue
        value_leaves = {
            n.id for n in ast.walk(node.value) if isinstance(n, ast.Name)
        }
        if value_leaves & PROGRESSION_NAMES:
            continue  # read-derived advance: always legal
        fn = enclosing.get(id(node))
        fn_names = refs(fn) if fn is not None else set()
        if not books(fn_names):
            failures.append(
                f"{WIRE}:{node.lineno}: cursor jump "
                f"({t.value.id}[...] = non-progression value) in "
                f"{getattr(fn, 'name', '<module>')} books no kta_log_*/"
                "corruption reason — a skip past unread offsets must be "
                "accounted"
            )

# (d) the follow poll books watermark regressions.
follow_tree = ast.parse(
    FOLLOW.read_text(encoding="utf-8"), filename=str(FOLLOW)
)
poll = None
for node in ast.walk(follow_tree):
    if isinstance(node, ast.FunctionDef) and node.name == "_poll":
        poll = node
if poll is None:
    failures.append(f"{FOLLOW}: FollowService._poll missing")
else:
    names = refs(poll)
    if "LOG_WATERMARK_REGRESSIONS" not in names:
        failures.append(
            f"{FOLLOW}:{poll.lineno}: _poll handles end-watermark "
            "regression without booking "
            "kta_log_watermark_regressions_total"
        )
    if "emit" not in names:
        failures.append(
            f"{FOLLOW}:{poll.lineno}: _poll emits no typed event for "
            "watermark regression"
        )

if failures:
    print("lint: cursor advances past unread log must book a kta_log_*")
    print("lint: (or corruption) reason — the scan never skips offsets")
    print("lint: silently (DESIGN.md §24):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (cursor jumps book their loss reason; none silent)")
EOF

# Fifteenth rule: one fetch scheduler per process (DESIGN.md §25).  The
# process-wide scheduler in io/fetchsched.py is the single admission
# point for every remote segment byte, so: (a) no privately-constructed
# pools or bare threads in io/segstore.py, io/objstore.py or
# io/segfile.py — ThreadPoolExecutor / threading.Thread constructions
# (and concurrent.futures imports) are forbidden there; io/fetchsched.py
# is the only module of the remote tier allowed to spawn workers.
# (b) Cache-trust latching is confined to its choke points: the
# SegmentCache._trusted set may be touched ONLY inside _latch_trusted /
# _unlatch_trusted / _is_trusted (plus the __init__ assignment), and the
# hit-side choke point must book kta_segstore_cache_verify_latched_total
# — an unbooked trust decision is a lint failure.
python - <<'EOF'
import ast
import pathlib
import sys

PKG = pathlib.Path("kafka_topic_analyzer_tpu")
OBJSTORE = PKG / "io" / "objstore.py"
NO_POOLS = [OBJSTORE, PKG / "io" / "segstore.py", PKG / "io" / "segfile.py"]

failures = []

# (a) no private pools/threads outside the scheduler.
for path in NO_POOLS:
    t = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
    for node in ast.walk(t):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            mods = [node.module]
        for mod in mods:
            if mod.split(".")[0] == "concurrent":
                failures.append(
                    f"{path}:{node.lineno}: imports {mod!r} — remote "
                    "fetch concurrency belongs to io/fetchsched.py's "
                    "process-wide scheduler"
                )
        if isinstance(node, ast.Call):
            name = None
            if isinstance(node.func, ast.Attribute):
                name = node.func.attr
            elif isinstance(node.func, ast.Name):
                name = node.func.id
            if name in ("ThreadPoolExecutor", "Thread"):
                failures.append(
                    f"{path}:{node.lineno}: constructs {name} — the "
                    "process-wide fetch scheduler (io/fetchsched.py) is "
                    "the only worker pool of the remote tier"
                )

# (b) trust latching confined to the booked choke points.
tree = ast.parse(OBJSTORE.read_text(encoding="utf-8"), filename=str(OBJSTORE))
CHOKE = {"_latch_trusted", "_unlatch_trusted", "_is_trusted", "__init__"}
cache = None
for node in ast.walk(tree):
    if isinstance(node, ast.ClassDef) and node.name == "SegmentCache":
        cache = node
if cache is None:
    failures.append(f"{OBJSTORE}: SegmentCache missing")
else:
    func_of = {}
    for item in cache.body:
        if isinstance(item, ast.FunctionDef):
            for child in ast.walk(item):
                func_of.setdefault(id(child), item.name)
    for node in ast.walk(cache):
        if isinstance(node, ast.Attribute) and node.attr == "_trusted":
            fn = func_of.get(id(node))
            if fn not in CHOKE:
                failures.append(
                    f"{OBJSTORE}:{node.lineno}: SegmentCache._trusted "
                    f"touched in {fn!r} — trust transitions go through "
                    "_latch_trusted/_unlatch_trusted/_is_trusted only"
                )
    hit_side = next(
        (i for i in cache.body
         if isinstance(i, ast.FunctionDef) and i.name == "_is_trusted"),
        None,
    )
    if hit_side is None:
        failures.append(
            f"{OBJSTORE}: SegmentCache._is_trusted (the hit-side trust "
            "choke point) missing"
        )
    elif not any(
        isinstance(n, ast.Attribute)
        and n.attr == "SEGSTORE_CACHE_VERIFY_LATCHED"
        for n in ast.walk(hit_side)
    ):
        failures.append(
            f"{OBJSTORE}:{hit_side.lineno}: _is_trusted serves latched "
            "hits without booking "
            "kta_segstore_cache_verify_latched_total"
        )

if failures:
    print("lint: one fetch scheduler per process violated (no private")
    print("lint: pools on the remote tier; cache-trust latching only via")
    print("lint: its booked choke points — DESIGN.md §25):")
    for f in failures:
        print(f"  {f}")
    sys.exit(1)
print("lint: OK (one fetch scheduler; trust latching booked at its choke points)")
EOF
