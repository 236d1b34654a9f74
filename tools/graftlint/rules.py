"""graftlint rule catalog — 8 JAX hazard classes this repo has actually hit.

Each rule cites the incident that motivated it (PR numbers refer to
CHANGES.md entries):

1. direct-shard-map      — PR 1: the seed suite was 100% import-broken
   because `shard_map` moved between jax versions; `h2o_tpu/parallel/
   mesh.py` is the ONLY sanctioned import point, so the next move is a
   one-line fix.
2. pspec-concat          — PR 1: on jax 0.4.x `PartitionSpec.__add__`
   returns a plain tuple, which shard_map rejects; specs must be built in
   one constructor call (`parallel/mrtask.py` carries the war story).
3. narrow-int-accumulate — PR 2: the binned histogram scan summed int8
   codes; reductions over sub-int32 operands overflow silently on device.
4. untracked-resident    — device arrays parked on objects bypass the HBM
   Cleaner ledger (`backend/memory.py`) and silently distort every
   budget-driven planner; residency must be Cleaner-tracked.
5. timing-without-sync   — jax dispatch is async: a wall-clock delta over
   un-synced device work measures dispatch, not compute (the bench JSONL
   sidecar numbers exist to be trusted).
6. host-sync-in-trace    — `.item()`/`float()`/`np.asarray` on traced
   values fail under jit, or worse: silently bake a trace-time constant in.
7. nondeterminism-in-trace — `np.random`/`time.time()` inside traced code
   executes ONCE at trace time; every later call replays the frozen value.
8. unregistered-knob     — literal `H2O_TPU_*` env reads must be declared
   in `h2o_tpu/utils/knobs.py` so the knob surface stays documented and
   greppable (the OptArgs discipline, enforced).
9. unregistered-failpoint — PR 5: literal failpoint site names must be
   declared in `h2o_tpu/utils/failpoints.py`; an undeclared site is a
   fault drill nobody can arm (the knobs discipline, applied to fault
   injection).
10. swallowed-retryable  — PR 5: `except Exception: pass` around an
   instrumented (failpoint) site swallows injected faults — and with them
   the real transient failures the drill stands in for; transient errors
   route through `utils/retry.py` or unwind typed.
11. unregistered-metric  — PR 6: literal metric names emitted through
   `utils/telemetry.py` accessors must be declared in its registry; an
   undeclared name raises at runtime (KeyError, the knobs contract) — this
   rule catches it before a hot path does.
13. direct-device-put    — PR 10 (multi-chip sharded frames): mesh-sharded
   `jax.device_put` calls belong to `parallel/mesh.py`'s put_* helpers or
   the frame layer (`frame/vec.py`, `frame/chunks.py`). Placement policy —
   what is row-sharded, what replicates per chip — decides per-chip HBM
   and collective layouts; a stray `device_put(x, NamedSharding(...))` in
   a builder silently re-lays frame data outside the one reviewable
   policy (the GSPMD merge mis-partition hid exactly there).
18. use-after-donate     — PR 12 (async pipelined training): a variable
   passed through a `donate_argnums` position of a jitted callable hands
   its buffer to the runtime — reading it afterwards dies at dispatch
   time with "array has been deleted" (or silently copies on backends
   without donation). The pipelined GBM chunk loop donates the carried
   margin across dispatches; this rule pins the rebind-or-copy
   discipline everywhere the pattern spreads. (Rules 14-17 are the
   interprocedural concurrency pass in `concurrency.py`.)
19. unscoped-profiler-capture — PR 13 (fleet observability): jax.profiler
   `start_trace`/`stop_trace`/`trace` outside `utils/telemetry.py` /
   `utils/fleetobs.py`. Captures must ride the span-scoped API
   (`telemetry.device_profile`/`capture`): it mirrors the live span
   stack into TraceAnnotations (XLA ops nest under `train.gbm.chunk` in
   Perfetto), enforces one session per process, and guarantees
   stop_trace on every exit path — an ad-hoc start_trace leaks a
   session the next capture then cannot open.
24. thread-without-trace-context — PR 15 (causal observability):
   contextvars do not cross `threading.Thread(target=...)` starts or
   executor submits, so a worker thread spawned in a span-bearing module
   (one that imports `utils/telemetry`) mints ORPHAN trace ids for every
   span it opens — the MicroBatcher and shadow-scorer spans silently
   fell out of their requests' traces for two PRs before anyone noticed.
   Thread targets and executor submissions in such modules must route
   through `telemetry.carry_context(fn)` (capture-at-wrap semantics);
   threads that legitimately own no causality (the REST acceptor, the
   teardown thread, the watchdog) carry an inline suppression with the
   reason. (Rules 20-23 are the dataflow pass in `dataflow.py`.)
"""

from __future__ import annotations

import ast
import os

from .core import (REPO_ROOT, FileContext, Rule, Violation, dotted_name,
                   function_scopes, normalize, scope_statements)

#: the one sanctioned shard_map definition site
MESH_PATH = "h2o_tpu/parallel/mesh.py"
KNOBS_PATH = "h2o_tpu/utils/knobs.py"
FAILPOINTS_PATH = "h2o_tpu/utils/failpoints.py"
TELEMETRY_PATH = "h2o_tpu/utils/telemetry.py"

_NARROW_INTS = {"int8", "int16", "uint8", "uint16"}
_WIDE_TYPES = {"int32", "int64", "uint32", "uint64",
               "float32", "float64", "bfloat16", "float16"}


def _norm_func(node: ast.Call, ctx: FileContext) -> str | None:
    return normalize(dotted_name(node.func), ctx.aliases)


class DirectShardMap(Rule):
    id = "direct-shard-map"
    doc = ("shard_map imported/used outside h2o_tpu/parallel/mesh.py — "
           "import it from the repo's single import point")

    def check(self, tree, ctx):
        if ctx.relpath == MESH_PATH:
            return []
        out = []
        spans: list[tuple] = []  # flagged attribute-chain spans
        msg = ("direct jax shard_map use — import it from "
               "h2o_tpu.parallel.mesh (the repo's single import "
               "point)")
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                names = {a.name for a in node.names}
                if (mod == "jax.experimental.shard_map"
                        or (mod in ("jax", "jax.experimental")
                            and "shard_map" in names)):
                    out.append(self.violation(ctx, node, msg))
            elif isinstance(node, ast.Import):
                if any(a.name.startswith("jax.experimental.shard_map")
                       for a in node.names):
                    out.append(self.violation(ctx, node, msg))
            elif isinstance(node, ast.Attribute):
                dn = normalize(dotted_name(node), ctx.aliases)
                if dn and (dn == "jax.shard_map"
                           or "experimental.shard_map" in dn):
                    # outermost matching attribute only: ast.walk visits
                    # outer before inner, so skip a chain whose span is
                    # CONTAINED in an already-flagged one (two disjoint
                    # uses on one line both report)
                    lo = (node.lineno, node.col_offset)
                    hi = (node.end_lineno, node.end_col_offset)
                    if not any(s0 <= lo and hi <= s1 for s0, s1 in spans):
                        spans.append((lo, hi))
                        out.append(self.violation(ctx, node, msg))
        return out


#: the sanctioned mesh-sharded placement sites — the mesh helpers
#: themselves plus the frame layer's (re)hydration paths
PLACEMENT_PATHS = (MESH_PATH, "h2o_tpu/frame/vec.py",
                   "h2o_tpu/frame/chunks.py")


class DirectDevicePut(Rule):
    id = "direct-device-put"
    doc = ("mesh-sharded jax.device_put outside parallel/mesh.py / the "
           "frame layer — route frame-data placement through the mesh "
           "put_* helpers so sharding policy stays in one place")

    #: constructors whose result is a mesh sharding (placing with a bare
    #: Device object — serving replica pinning — is NOT flagged: that is
    #: device selection, not frame-data partitioning)
    _SHARDING_CTORS = {"NamedSharding", "PositionalSharding",
                       "row_sharding", "replicated"}

    def _is_sharding(self, node, shard_vars) -> bool:
        if isinstance(node, ast.Name):
            return node.id in shard_vars
        if isinstance(node, ast.Call):
            dn = dotted_name(node.func)
            return bool(dn) and dn.split(".")[-1] in self._SHARDING_CTORS
        return False

    def check(self, tree, ctx):
        if ctx.relpath in PLACEMENT_PATHS:
            return []
        out = []
        for scope in function_scopes(tree):
            shard_vars: set[str] = set()
            stmts = sorted(scope_statements(scope),
                           key=lambda n: (getattr(n, "lineno", 0),
                                          getattr(n, "col_offset", 0)))
            for node in stmts:
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and self._is_sharding(node.value, shard_vars)):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            shard_vars.add(t.id)
                if not isinstance(node, ast.Call):
                    continue
                fn = _norm_func(node, ctx)
                if not fn or not fn.endswith("device_put"):
                    continue
                target = node.args[1] if len(node.args) >= 2 else None
                for kw in node.keywords:
                    if kw.arg in ("device", "sharding"):
                        target = kw.value
                if target is not None and self._is_sharding(target,
                                                            shard_vars):
                    out.append(self.violation(
                        ctx, node,
                        "mesh-sharded device_put outside the sanctioned "
                        "placement sites — use parallel/mesh.py's "
                        "put_row_sharded/put_replicated/put_sharded (or "
                        "the frame layer) so per-chip placement policy "
                        "stays reviewable in one place"))
        return out


class PSpecConcat(Rule):
    id = "pspec-concat"
    doc = ("PartitionSpec combined via '+' — jax 0.4.x __add__ returns a "
           "raw tuple; build the spec in one constructor call")

    _CTORS = {"PartitionSpec", "P"}

    def _is_spec(self, node, spec_vars) -> bool:
        if isinstance(node, ast.Call):
            dn = dotted_name(node.func)
            return bool(dn) and dn.split(".")[-1] in self._CTORS
        if isinstance(node, ast.Name):
            return node.id in spec_vars
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            # nested concat chains: (P(a) + P(b)) + P(c)
            return (self._is_spec(node.left, spec_vars)
                    or self._is_spec(node.right, spec_vars))
        return False

    def check(self, tree, ctx):
        out = []
        for scope in function_scopes(tree):
            spec_vars: set[str] = set()
            spans: list[tuple] = []  # flagged BinOp spans (outermost wins)
            stmts = sorted(scope_statements(scope),
                           key=lambda n: (getattr(n, "lineno", 0),
                                          getattr(n, "col_offset", 0)))
            for node in stmts:
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and self._is_spec(node.value, spec_vars)):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            spec_vars.add(t.id)
                if (isinstance(node, ast.BinOp)
                        and isinstance(node.op, ast.Add)
                        and (self._is_spec(node.left, spec_vars)
                             or self._is_spec(node.right, spec_vars))):
                    # one violation per chain: the sorted order visits the
                    # OUTERMOST BinOp of `(P(a)+P(b))+P(c)` first, and the
                    # inner adds live inside its span
                    lo = (node.lineno, node.col_offset)
                    hi = (node.end_lineno, node.end_col_offset)
                    if any(s0 <= lo and hi <= s1 for s0, s1 in spans):
                        continue
                    spans.append((lo, hi))
                    out.append(self.violation(
                        ctx, node,
                        "PartitionSpec '+' concatenation — on jax 0.4.x "
                        "P.__add__ returns a plain tuple (shard_map rejects "
                        "it); pass all axes to one PartitionSpec(...) call"))
        return out


class NarrowIntAccumulate(Rule):
    id = "narrow-int-accumulate"
    doc = ("jnp.sum/segment_sum/psum over int8/int16 operands without an "
           "explicit int32 upcast — silent on-device overflow")

    _ACCUM = {"jnp.sum", "lax.psum", "jnp.cumsum", "lax.psum_scatter"}
    _ACCUM_SUFFIX = ("segment_sum",)

    def _dtype_of(self, node) -> str | None:
        """Name of the dtype an expression mentions ('int8', 'float32'...),
        for the handful of spellings the repo uses."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        dn = dotted_name(node)
        if dn:
            return dn.split(".")[-1]
        return None

    def _is_narrow_expr(self, node, narrow_vars) -> bool:
        if isinstance(node, ast.Name):
            return node.id in narrow_vars
        if isinstance(node, ast.Call):
            # x.astype(jnp.int8) / x.astype("int16")
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("astype", "view") and node.args):
                return self._dtype_of(node.args[0]) in _NARROW_INTS
            # jnp.zeros(shape, jnp.int8) / jnp.asarray(x, dtype=jnp.int8)
            for kw in node.keywords:
                if kw.arg == "dtype":
                    return self._dtype_of(kw.value) in _NARROW_INTS
            if len(node.args) >= 2:
                if self._dtype_of(node.args[-1]) in _NARROW_INTS:
                    return True
        if isinstance(node, ast.BinOp):
            return (self._is_narrow_expr(node.left, narrow_vars)
                    or self._is_narrow_expr(node.right, narrow_vars))
        return False

    def _has_upcast(self, call: ast.Call) -> bool:
        for kw in call.keywords:
            if kw.arg == "dtype":
                return self._dtype_of(kw.value) in _WIDE_TYPES
        if call.args:
            a = call.args[0]
            if (isinstance(a, ast.Call)
                    and isinstance(a.func, ast.Attribute)
                    and a.func.attr == "astype" and a.args
                    and self._dtype_of(a.args[0]) in _WIDE_TYPES):
                return True
        return False

    def check(self, tree, ctx):
        out = []
        for scope in function_scopes(tree):
            narrow_vars: set[str] = set()
            stmts = sorted(scope_statements(scope),
                           key=lambda n: (getattr(n, "lineno", 0),
                                          getattr(n, "col_offset", 0)))
            # pass 1: variables bound to narrow-int expressions
            for node in stmts:
                if isinstance(node, ast.Assign):
                    if self._is_narrow_expr(node.value, narrow_vars):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                narrow_vars.add(t.id)
                    else:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                narrow_vars.discard(t.id)
            # pass 2: accumulations over narrow operands
            for node in stmts:
                if not isinstance(node, ast.Call):
                    continue
                fn = _norm_func(node, ctx)
                is_accum = (fn in self._ACCUM
                            or (fn or "").endswith(self._ACCUM_SUFFIX))
                # narrow_arr.sum() method form
                if (not is_accum and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("sum", "cumsum")
                        and self._is_narrow_expr(node.func.value,
                                                 narrow_vars)):
                    is_accum = True
                    arg = node.func.value
                else:
                    arg = node.args[0] if node.args else None
                if not is_accum or arg is None:
                    continue
                if (self._is_narrow_expr(arg, narrow_vars)
                        and not self._has_upcast(node)):
                    out.append(self.violation(
                        ctx, node,
                        "accumulation over a sub-int32 operand — pass "
                        "dtype=jnp.int32 or .astype(jnp.int32) first "
                        "(PR 2 binned-histogram overflow class)"))
        return out


class UntrackedResident(Rule):
    id = "untracked-resident"
    doc = ("device array assigned to self.* in frame/ or models/ classes "
           "with no Cleaner.track/_put_sharding registration — silent HBM "
           "ledger leak vs backend/memory.py")

    _SCOPES = ("h2o_tpu/frame/", "h2o_tpu/models/")
    _TRACKED_BASES = {"Vec", "CodedVec", "BinnedView", "Keyed", "Frame"}

    def _device_expr(self, node, ctx) -> bool:
        if not isinstance(node, ast.Call):
            return False
        fn = _norm_func(node, ctx)
        if fn is None:
            return False
        return (fn.startswith("jnp.")
                or fn in ("jax.device_put", "jax.make_array_from_callback"))

    def check(self, tree, ctx):
        if not ctx.relpath.startswith(self._SCOPES):
            return []
        out = []
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            base_names = {dn.split(".")[-1] for dn in
                          (dotted_name(b) for b in cls.bases) if dn}
            if base_names & self._TRACKED_BASES:
                continue  # Vec/Keyed subclasses register via __init__
            registered = False
            for node in ast.walk(cls):
                if (isinstance(node, ast.Attribute)
                        and node.attr in ("track", "_put_sharding")):
                    registered = True
                    break
            if registered:
                continue
            for node in ast.walk(cls):
                if not isinstance(node, ast.Assign):
                    continue
                if not self._device_expr(node.value, ctx):
                    continue
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        out.append(self.violation(
                            ctx, node,
                            f"device array parked on self.{t.attr} with no "
                            f"Cleaner.track/_put_sharding registration — "
                            f"invisible to the HBM ledger "
                            f"(backend/memory.py)"))
        return out


class TimingWithoutSync(Rule):
    id = "timing-without-sync"
    doc = ("wall-clock delta spans jax dispatch with no block_until_ready/"
           "device_get — measures dispatch, not compute")

    _CLOCKS = {"time.time", "time.perf_counter", "time.monotonic",
               "perf_counter", "monotonic"}
    #: repo entry points that dispatch device work behind a host call.
    #: train_model is NOT here: ModelBuilder.train drains the model's
    #: device arrays before returning (model_base.py), so timing around it
    #: is honest by contract — and that contract is itself lint-protected,
    #: because model_base.run's own timed window classifies build_impl as
    #: dispatch and needs the block_until_ready to stay clean.
    _DISPATCH_METHODS = {"build_impl"}
    _SYNC_NAMES = {"block_until_ready", "device_get", "to_numpy", "item"}
    _SYNC_FULL = {"np.asarray", "np.array"}
    _BENIGN_JAX = {"jax.devices", "jax.local_devices", "jax.device_count",
                   "jax.default_backend", "jax.process_index",
                   "jax.process_count", "jax.clear_caches",
                   "jax.config.update", "jax.debug.print"}

    def _is_clock(self, node, ctx) -> bool:
        return (isinstance(node, ast.Call)
                and _norm_func(node, ctx) in self._CLOCKS)

    def _classify(self, node: ast.Call, ctx) -> str | None:
        """'sync' | 'dispatch' | None for a call node."""
        fn = _norm_func(node, ctx)
        last = (fn or (node.func.attr if isinstance(node.func, ast.Attribute)
                       else "")).split(".")[-1]
        if fn in self._SYNC_FULL or last in self._SYNC_NAMES:
            return "sync"
        if last in self._DISPATCH_METHODS:
            return "dispatch"
        if fn is None:
            return None
        if fn in self._BENIGN_JAX or fn in self._CLOCKS:
            return None
        if (fn.startswith(("jnp.", "lax.", "jax."))
                or fn in ("jnp", "lax")):
            return "dispatch"
        return None

    def check(self, tree, ctx):
        out = []
        for scope in function_scopes(tree):
            starts: dict[str, list[int]] = {}   # timer var -> assign lines
            deltas: list[tuple[int, ast.BinOp, str]] = []
            calls: list[tuple[int, str]] = []
            for node in scope_statements(scope):
                if (isinstance(node, ast.Assign)
                        and self._is_clock(node.value, ctx)):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            starts.setdefault(t.id, []).append(node.lineno)
                elif isinstance(node, ast.BinOp) and isinstance(node.op,
                                                                ast.Sub):
                    if (self._is_clock(node.left, ctx)
                            and isinstance(node.right, ast.Name)):
                        deltas.append((node.lineno, node, node.right.id))
                elif isinstance(node, ast.Call):
                    kind = self._classify(node, ctx)
                    if kind:
                        calls.append((node.lineno, kind))
            for dline, dnode, tvar in deltas:
                cands = [ln for ln in starts.get(tvar, []) if ln < dline]
                if not cands:
                    continue
                t0 = max(cands)  # the LATEST restart before this read
                window = [(ln, k) for ln, k in calls if t0 < ln <= dline]
                if (any(k == "dispatch" for _, k in window)
                        and not any(k == "sync" for _, k in window)):
                    out.append(self.violation(
                        ctx, dnode,
                        f"timed window (line {t0}..{dline}) spans jax "
                        f"dispatch with no block_until_ready/device_get — "
                        f"the delta measures dispatch, not compute"))
        return out


class HostSyncInTrace(Rule):
    id = "host-sync-in-trace"
    doc = (".item()/float()/bool()/np.asarray on traced values inside "
           "jit/scan/shard_map bodies — fails under jit or bakes in a "
           "trace-time constant")

    _CASTS = {"float", "bool"}
    _FULL = {"np.asarray", "np.array", "jax.device_get"}

    @staticmethod
    def _static_arg(node) -> bool:
        """Arguments that are trace-static: literals, or anything derived
        from .shape/.ndim/.size/.dtype/len() (python ints at trace time)."""
        if isinstance(node, ast.Constant):
            return True
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in (
                    "shape", "ndim", "size", "dtype", "itemsize"):
                return True
            if (isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name)
                    and sub.func.id == "len"):
                return True
        return False

    def check(self, tree, ctx):
        out = []
        seen: set[int] = set()
        for fn in ctx.traced:
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call) or id(node) in seen:
                        continue
                    seen.add(id(node))
                    msg = None
                    if (isinstance(node.func, ast.Name)
                            and node.func.id in self._CASTS
                            and node.args
                            and not self._static_arg(node.args[0])):
                        msg = (f"{node.func.id}() on a traced value inside "
                               f"a jit/scan/shard_map body")
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr == "item"):
                        msg = ".item() on a traced value inside a traced body"
                    elif _norm_func(node, ctx) in self._FULL:
                        msg = (f"{_norm_func(node, ctx)} inside a traced "
                               f"body forces a host sync at trace time")
                    if msg:
                        out.append(self.violation(
                            ctx, node, msg + " — fails under jit or "
                            "freezes a trace-time constant"))
        return out


class NondeterminismInTrace(Rule):
    id = "nondeterminism-in-trace"
    doc = ("np.random/time.time reachable from traced code — the value "
           "freezes at trace time and silently replays")

    _PREFIXES = ("np.random.", "random.")
    _FULL = {"time.time", "time.perf_counter", "time.monotonic",
             "time.time_ns", "uuid.uuid4", "np.random"}

    def check(self, tree, ctx):
        out = []
        seen: set[int] = set()
        for fn in ctx.traced:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                f = _norm_func(node, ctx)
                if f and (f in self._FULL
                          or f.startswith(self._PREFIXES)):
                    out.append(self.violation(
                        ctx, node,
                        f"{f}() inside a traced body executes ONCE at "
                        f"trace time — use jax.random with a threaded key "
                        f"(or hoist the host value out of the trace)"))
        return out


def registered_knobs(root: str = REPO_ROOT) -> set[str]:
    """Knob names declared in h2o_tpu/utils/knobs.py — read via AST so the
    linter never imports the (jax-heavy) package it lints."""
    path = os.path.join(root, KNOBS_PATH)
    names: set[str] = set()
    if not os.path.exists(path):
        return names
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("H2O_TPU_")
                and dotted_name(node.func) in ("_knob", "Knob")):
            names.add(node.args[0].value)
    return names


class UnregisteredKnob(Rule):
    id = "unregistered-knob"
    doc = ("literal H2O_TPU_* env read not declared in the "
           "h2o_tpu/utils/knobs.py registry")

    _GETTERS = {"os.environ.get", "os.getenv", "environ.get"}

    def __init__(self, registry: set[str] | None = None):
        self._registry = registry

    @property
    def registry(self) -> set[str]:
        if self._registry is None:
            self._registry = registered_knobs()
        return self._registry

    def _flag(self, ctx, node, name):
        return self.violation(
            ctx, node,
            f"env knob {name!r} is not declared in h2o_tpu/utils/knobs.py "
            f"— register it (name, default, docstring) so the knob surface "
            f"stays documented")

    def check(self, tree, ctx):
        if ctx.relpath == KNOBS_PATH:
            return []
        out = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                fn = normalize(dotted_name(node.func), ctx.aliases)
                if (fn in self._GETTERS and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    name = node.args[0].value
                    if (name.startswith("H2O_TPU_")
                            and name not in self.registry):
                        out.append(self._flag(ctx, node, name))
            elif (isinstance(node, ast.Subscript)
                  and isinstance(node.ctx, ast.Load)):
                base = normalize(dotted_name(node.value), ctx.aliases)
                if base in ("os.environ", "environ"):
                    sl = node.slice
                    if (isinstance(sl, ast.Constant)
                            and isinstance(sl.value, str)
                            and sl.value.startswith("H2O_TPU_")
                            and sl.value not in self.registry):
                        out.append(self._flag(ctx, node, sl.value))
        return out


def registered_failpoints(root: str = REPO_ROOT) -> set[str]:
    """Failpoint sites declared in h2o_tpu/utils/failpoints.py — AST-parsed
    like the knob registry, so the linter never imports the package."""
    path = os.path.join(root, FAILPOINTS_PATH)
    names: set[str] = set()
    if not os.path.exists(path):
        return names
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and dotted_name(node.func) in ("_failpoint", "Failpoint")):
            names.add(node.args[0].value)
    return names


class UnregisteredFailpoint(Rule):
    id = "unregistered-failpoint"
    doc = ("literal failpoint site name not declared in the "
           "h2o_tpu/utils/failpoints.py registry")

    #: accessor attributes whose literal first argument is a site name
    _ACCESSORS = ("hit", "arm", "disarm", "is_armed", "hits")

    def __init__(self, registry: set[str] | None = None):
        self._registry = registry

    @property
    def registry(self) -> set[str]:
        if self._registry is None:
            self._registry = registered_failpoints()
        return self._registry

    def check(self, tree, ctx):
        if ctx.relpath == FAILPOINTS_PATH:
            return []
        out = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            fn = _norm_func(node, ctx)
            if fn is None or not any(
                    fn.endswith(f"failpoints.{acc}")
                    for acc in self._ACCESSORS):
                continue
            name = node.args[0].value
            if name not in self.registry:
                out.append(self.violation(
                    ctx, node,
                    f"failpoint {name!r} is not declared in "
                    f"h2o_tpu/utils/failpoints.py — register it (name, "
                    f"docstring) so every fault drill stays armable and "
                    f"documented"))
        return out


def _contains_failpoint_hit(stmts, ctx) -> bool:
    for stmt in stmts:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                fn = _norm_func(node, ctx)
                if fn is not None and fn.endswith("failpoints.hit"):
                    return True
    return False


class SwallowedRetryable(Rule):
    id = "swallowed-retryable"
    doc = ("broad except-and-ignore around an instrumented (failpoint) "
           "site — injected faults, and the real transient failures they "
           "stand in for, must not vanish silently")

    _BROAD = {"Exception", "BaseException"}

    def _is_broad_expr(self, t) -> bool:
        """Exception/BaseException as a bare Name, dotted builtins.*, or any
        member of a tuple handler — `except (Exception,):` swallows exactly
        as much as `except Exception:`."""
        if isinstance(t, ast.Name):
            return t.id in self._BROAD
        if isinstance(t, ast.Attribute):
            return (t.attr in self._BROAD
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "builtins")
        if isinstance(t, ast.Tuple):
            return any(self._is_broad_expr(el) for el in t.elts)
        return False

    def check(self, tree, ctx):
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Try):
                continue
            if not _contains_failpoint_hit(node.body, ctx):
                continue
            for handler in node.handlers:
                t = handler.type
                broad = t is None or self._is_broad_expr(t)
                if not broad:
                    continue
                body = [s for s in handler.body
                        if not (isinstance(s, ast.Expr)
                                and isinstance(s.value, ast.Constant))]
                ignores = all(isinstance(s, (ast.Pass, ast.Continue))
                              for s in body)
                if ignores:
                    out.append(self.violation(
                        ctx, handler,
                        "broad except silently ignores failures from an "
                        "instrumented site — a failpoint drill (and the "
                        "real transient fault it models) would vanish "
                        "here; retry through utils/retry.py or let the "
                        "typed error unwind"))
        return out


def registered_metrics(root: str = REPO_ROOT) -> set[str]:
    """Metric names declared in h2o_tpu/utils/telemetry.py — AST-parsed
    like the knob/failpoint registries, so the linter never imports the
    (jax-adjacent) package."""
    path = os.path.join(root, TELEMETRY_PATH)
    names: set[str] = set()
    if not os.path.exists(path):
        return names
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and dotted_name(node.func) in ("_counter", "_gauge",
                                               "_histogram", "Metric")):
            names.add(node.args[0].value)
    return names


class UnregisteredMetric(Rule):
    id = "unregistered-metric"
    doc = ("literal metric name emitted through utils/telemetry.py "
           "accessors but not declared in its registry")

    #: accessors whose literal FIRST argument is a metric name
    _ACCESSORS = ("inc", "observe", "set_gauge", "value")
    #: span/lap constructors carry the metric as a `metric=` keyword
    _METRIC_KW = ("span", "lap", "Lap")

    def __init__(self, registry: set[str] | None = None):
        self._registry = registry

    @property
    def registry(self) -> set[str]:
        if self._registry is None:
            self._registry = registered_metrics()
        return self._registry

    def _flag(self, ctx, node, name):
        return self.violation(
            ctx, node,
            f"metric {name!r} is not declared in "
            f"h2o_tpu/utils/telemetry.py — register it (name, kind, "
            f"docstring) so /3/Metrics stays documented and the emit "
            f"cannot KeyError a hot path at runtime")

    def check(self, tree, ctx):
        if ctx.relpath == TELEMETRY_PATH:
            return []
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _norm_func(node, ctx)
            if fn is None:
                continue
            if (any(fn.endswith(f"telemetry.{acc}")
                    for acc in self._ACCESSORS)
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                name = node.args[0].value
                if name not in self.registry:
                    out.append(self._flag(ctx, node, name))
            elif any(fn.endswith(f"telemetry.{c}")
                     for c in self._METRIC_KW):
                for kw in node.keywords:
                    if (kw.arg == "metric"
                            and isinstance(kw.value, ast.Constant)
                            and isinstance(kw.value.value, str)
                            and kw.value.value not in self.registry):
                        out.append(self._flag(ctx, node, kw.value.value))
        return out


class UseAfterDonate(Rule):
    id = "use-after-donate"
    doc = ("variable read after being passed through a donate_argnums "
           "position of the same jitted callable — the donated buffer is "
           "gone at dispatch; rebind the result or copy first")

    @staticmethod
    def _donated_positions(call: ast.Call):
        """frozenset of donated positions from a jax.jit call's
        donate_argnums (int or tuple/list of int literals), else None."""
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return frozenset([v.value])
            if isinstance(v, (ast.Tuple, ast.List)):
                vals = frozenset(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, int))
                if vals:
                    return vals
        return None

    #: factory callables known to return a donating trainer: callee name
    #: -> donated positions of the RETURNED callable when the factory is
    #: called with donate=True (engine.make_train_fn donates the carried
    #: margin, argument 3). This per-file rule still can't see the chunk
    #: loop's `*step_args` dispatch — the pass-3 `donate-across-calls`
    #: rule (tools/graftlint/dataflow.py) resolves donating factories
    #: through the call graph and star-dispatch through tuple packs, so
    #: that flow IS lint-visible now; this list keeps the cheap per-file
    #: rule useful for same-file reads (tests included — pass 3 scopes
    #: to h2o_tpu/ + bench.py).
    _DONATING_FACTORIES = {"make_train_fn": frozenset([3])}

    def _binding_positions(self, value: ast.expr, ctx) -> frozenset | None:
        """Donated positions for a callable bound from ``value``: a
        literal `jax.jit(..., donate_argnums=...)` call, a known donating
        factory called with donate=True, or an IfExp with either arm one
        of those (conservative: donation assumed when any arm donates)."""
        if isinstance(value, ast.IfExp):
            return (self._binding_positions(value.body, ctx)
                    or self._binding_positions(value.orelse, ctx))
        if not isinstance(value, ast.Call):
            return None
        fn = _norm_func(value, ctx)
        if fn and fn.endswith("jax.jit"):
            return self._donated_positions(value)
        tail = (fn or "").rsplit(".", 1)[-1]
        if tail in self._DONATING_FACTORIES:
            for kw in value.keywords:
                if (kw.arg == "donate"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True):
                    return self._DONATING_FACTORIES[tail]
        return None

    def check(self, tree, ctx):
        # pass 1, file-wide: bindings of donating callables — literal
        # `name = jax.jit(..., donate_argnums=...)`, donating factories,
        # and IfExp-wrapped variants
        donating: dict[str, frozenset] = {}
        for node in ast.walk(tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                pos = self._binding_positions(node.value, ctx)
                if pos:
                    donating[node.targets[0].id] = pos
        if not donating:
            return []
        out = []
        msg = ("read of {name!r} after it was donated to {fn!r} "
               "(donate_argnums) — the buffer is deleted at dispatch; "
               "rebind the call's result or copy before dispatching")
        for scope in function_scopes(tree):
            # line-ordered event stream: loads check against the donated
            # set, call-site donations mark at the call's END line (args
            # may span lines), stores/dels clear at their statement's END
            # line (RHS evaluates before targets bind — `f, o = fn(x, f)`
            # donates the old f and rebinds, which is the clean idiom)
            events = []   # (line, phase, name, node, fn)
            for node in scope_statements(scope):
                if isinstance(node, ast.stmt):
                    end = node.end_lineno or node.lineno
                    for sub in ast.walk(node):
                        if (isinstance(sub, ast.Name)
                                and isinstance(sub.ctx, (ast.Store,
                                                         ast.Del))):
                            events.append((end, 2, sub.id, None, None))
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in donating):
                    end = node.end_lineno or node.lineno
                    for p in donating[node.func.id]:
                        if (p < len(node.args)
                                and isinstance(node.args[p], ast.Name)):
                            events.append((end, 1, node.args[p].id, None,
                                           node.func.id))
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)):
                    events.append((node.lineno, 0, node.id, node, None))
            donated: dict[str, str] = {}   # name -> donating fn
            for _line, phase, name, node, fn in sorted(
                    events, key=lambda e: (e[0], e[1])):
                if phase == 0 and name in donated:
                    out.append(self.violation(
                        ctx, node, msg.format(name=name,
                                              fn=donated[name])))
                    del donated[name]   # one report per donation
                elif phase == 1:
                    donated[name] = fn
                elif phase == 2:
                    donated.pop(name, None)
        return out


#: the sanctioned jax.profiler capture sites — telemetry owns the
#: span-scoped capture API (annotations + guaranteed stop_trace),
#: fleetobs the fleet-coordinated captures
PROFILER_PATHS = ("h2o_tpu/utils/telemetry.py", "h2o_tpu/utils/fleetobs.py")


class UnscopedProfilerCapture(Rule):
    id = "unscoped-profiler-capture"
    doc = ("jax.profiler start_trace/stop_trace/trace outside "
           "utils/telemetry.py / utils/fleetobs.py — captures must ride "
           "the span-scoped API (telemetry.device_profile / capture) so "
           "TraceAnnotations nest XLA ops under the span names and "
           "stop_trace is guaranteed on every exit path")

    _CAPTURE_NAMES = ("start_trace", "stop_trace", "trace",
                      "start_server")

    def _is_capture(self, dn: str) -> bool:
        if not dn or "profiler" not in dn:
            return False
        tail = dn.rsplit(".", 1)[-1]
        return tail in self._CAPTURE_NAMES

    def check(self, tree, ctx):
        if ctx.relpath in PROFILER_PATHS:
            return []
        out = []
        spans: list[tuple] = []
        msg = ("unscoped jax.profiler capture — route it through "
               "utils/telemetry.py's device_profile()/capture() (the "
               "span-scoped API: annotations nest XLA ops under telemetry "
               "span names, one session per process is enforced, and "
               "stop_trace cannot be leaked on an error path)")
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                names = {a.name for a in node.names}
                if (mod.endswith("jax.profiler") or mod == "jax.profiler") \
                        and names & set(self._CAPTURE_NAMES):
                    out.append(self.violation(ctx, node, msg))
            elif isinstance(node, ast.Attribute):
                dn = normalize(dotted_name(node), ctx.aliases)
                if dn and self._is_capture(dn):
                    # outermost matching attribute chain only (the
                    # direct-shard-map span discipline)
                    lo = (node.lineno, node.col_offset)
                    hi = (node.end_lineno, node.end_col_offset)
                    if not any(s0 <= lo and hi <= s1 for s0, s1 in spans):
                        spans.append((lo, hi))
                        out.append(self.violation(ctx, node, msg))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)):
                # bare `start_trace(...)` resolved through an import alias
                dn = normalize(dotted_name(node.func), ctx.aliases)
                if dn and self._is_capture(dn) and "profiler" in dn:
                    out.append(self.violation(ctx, node, msg))
        return out


class ThreadWithoutTraceContext(Rule):
    id = "thread-without-trace-context"
    doc = ("threading.Thread(target=...) / executor submit in a module "
           "that imports utils/telemetry must wrap the callable in "
           "telemetry.carry_context(...) — contextvars do not cross "
           "thread starts, so the worker's spans orphan into fresh trace "
           "ids (the MicroBatcher/shadow-scorer hole PR 15 closed)")

    _MSG = ("worker thread/submit in a span-bearing module without "
            "telemetry.carry_context() — the thread's spans will mint "
            "orphan trace ids instead of nesting under the submitter's "
            "(wrap the target: Thread(target=telemetry.carry_context(fn)) "
            "/ ex.submit(telemetry.carry_context(fn), ...); threads that "
            "own no causality suppress inline with the reason)")

    @staticmethod
    def _bears_spans(tree) -> bool:
        """Module imports utils/telemetry (module- or function-level) —
        the modules whose spans can orphan."""
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                if any(a.name == "telemetry" for a in node.names) or \
                        (node.module or "").endswith("telemetry"):
                    return True
            elif isinstance(node, ast.Import):
                if any(a.name.endswith(".telemetry") for a in node.names):
                    return True
        return False

    @staticmethod
    def _is_carried(node) -> bool:
        """True when the callable expression routes through
        carry_context (telemetry.carry_context(fn) or an alias of it)."""
        if not isinstance(node, ast.Call):
            return False
        dn = dotted_name(node.func)
        return bool(dn) and dn.rsplit(".", 1)[-1] == "carry_context"

    def _executor_vars(self, tree, ctx) -> set:
        """Names bound to ThreadPoolExecutor/ProcessPoolExecutor
        instances — via assignment or `with ...() as ex:`."""
        out = set()

        def _note(target, value):
            if isinstance(target, ast.Name) and isinstance(value, ast.Call):
                dn = normalize(dotted_name(value.func), ctx.aliases) or ""
                if dn.rsplit(".", 1)[-1] in ("ThreadPoolExecutor",
                                             "ProcessPoolExecutor"):
                    out.add(target.id)

        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    _note(t, node.value)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        _note(item.optional_vars, item.context_expr)
        return out

    def check(self, tree, ctx):
        if not ctx.relpath.startswith("h2o_tpu/"):
            return []           # the span-bearing tree; tests/tools spawn
        if ctx.relpath == TELEMETRY_PATH:
            return []           # carry_context's own home
        if not self._bears_spans(tree):
            return []
        out = []
        executors = self._executor_vars(tree, ctx)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            dn = normalize(dotted_name(node.func), ctx.aliases) or ""
            if dn == "threading.Thread" or dn.endswith(".threading.Thread"):
                # positional signature is Thread(group, target, ...) —
                # args[0] is GROUP, the callable is args[1]
                target = next((kw.value for kw in node.keywords
                               if kw.arg == "target"),
                              node.args[1] if len(node.args) > 1 else None)
                if target is not None and not self._is_carried(target):
                    out.append(self.violation(ctx, node, self._MSG))
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("submit", "map") \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id in executors:
                fn = node.args[0] if node.args else None
                if fn is not None and not self._is_carried(fn):
                    out.append(self.violation(ctx, node, self._MSG))
        return out


ALL_RULES = (DirectShardMap, DirectDevicePut, PSpecConcat,
             NarrowIntAccumulate, UntrackedResident, TimingWithoutSync,
             HostSyncInTrace, NondeterminismInTrace, UnregisteredKnob,
             UnregisteredFailpoint, SwallowedRetryable, UnregisteredMetric,
             UseAfterDonate, UnscopedProfilerCapture,
             ThreadWithoutTraceContext)
