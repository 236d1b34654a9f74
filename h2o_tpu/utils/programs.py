"""Program cost registry — per-program XLA cost/memory accounting.

The reference answers "where did the cluster's cycles go" with WaterMeter
and per-task `MRTask.profile()`; the TPU-native equivalent question is
"what does each COMPILED PROGRAM cost" — XLA knows (the compiler emits a
per-executable cost model and a memory assignment), but until this module
those numbers evaporated the moment an executable left the compile path.

Every compiled executable that passes through the repo's jit/AOT choke
points registers here under a STABLE program id:

- ``models/gbm.py _aot_train_step`` — the tree chunk step (the engine's
  ``_TRAIN_FN_CACHE`` programs reach XLA through this AOT site);
- ``parallel/mrtask.py _dispatch`` — every DrJAX-style driver program
  (rollups, binning, generic mr_reduce/mr_map), via :func:`tracked`;
- ``models/glm.py _make_irls_kernel`` — the GLM IRLS step (jit and
  shard_map shapes), via :func:`tracked`;
- ``serving/scorer.py CompiledScorer.warmup`` — one entry per bucket
  executable.

Each record pairs the STATIC cost (``cost_analysis()``: flops, bytes
accessed; ``memory_analysis()``: argument/output/temp/generated-code
bytes) with two measured sides, each named as what it is:

- ``wall``: HOST dispatch walls (a bounded per-program ring fed by
  :class:`Tracked` dispatches or explicit :func:`note_wall` calls).
  Dispatch is asynchronous, so these are ENQUEUE times, not device compute
  (unless the caller drains), and nothing is divided by them;
- ``device``: seconds and executions of the record's XLA MODULE on the
  device's own clock, folded in from a profiler capture by
  :func:`fold_capture` (``POST /3/Profiler/capture`` folds its own); null
  until a capture held the module. A record's ``module`` is the name jit
  gave its program (``jit_<telemetry.PROGRAMS name>``), the same name the
  ``XLA Modules`` line of a device trace and the ``compile`` timeline
  events carry: records that share a module share its reading.

:func:`tracked` wraps a jitted callable: the first dispatch per argument
signature AOT-lowers and compiles (the SAME single compile the jit
dispatch would have paid — the jitted twin's own cache is never populated),
registers the executable's analyses, and dispatches the compiled object.
A compile error raises as is; an enclosing trace steps the wrapper aside;
an executable that REJECTS its inputs (re-sharded arguments) falls back to
the jitted twin permanently for that signature, with a warning. Results are
bit-identical either way: both paths execute the XLA program lowered from
the same arguments.

Surfaced as ``GET /3/Programs`` (JSON + Prometheus families via the
telemetry provider hook), embedded per-leg in the bench sidecar
(``record["programs"]``), and included in every flight-recorder bundle.
"""

from __future__ import annotations

import glob
import hashlib
import os
import re
import threading
import time
import weakref
from collections import deque

from . import compilemeter, telemetry

#: measured dispatch walls kept per program (host seconds)
_WALL_WINDOW = 128

_LOCK = threading.Lock()
_REGISTRY: dict[str, "ProgramRecord"] = {}

#: live Tracked instances, weakly held — a Tracked's lifetime belongs to
#: whoever holds it, and with it the executables of every signature it has
#: dispatched: mrtask caches them on the map function (the gc reclaims
#: program + closure together), the GLM keeps its IRLS steps for the
#: process (`glm._kept`), so a wrapper outlives the job that built it and
#: the next job of the same shapes loads nothing. The jobs.py
#: CLEAR_CACHES_EVERY sweep calls :func:`clear_compiled` over whatever is
#: still alive so directly-held executables honor the same long-server
#: hygiene bound as the AOT caches
_TRACKED: "weakref.WeakSet" = weakref.WeakSet()


class ProgramRecord:
    __slots__ = ("pid", "kind", "name", "module", "labels", "flops",
                 "bytes_accessed", "memory", "registered_ms",
                 "dispatch_count", "walls", "device")

    def __init__(self, pid, kind, name, module, labels, flops,
                 bytes_accessed, memory):
        self.pid = pid
        self.kind = kind            # "train" | "dispatch" | "serving"
        self.name = name
        self.module = module        # the XLA module's name, jit_<function>
        self.device = None          # fold_capture's reading of that module
        self.labels = labels
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.memory = memory
        self.registered_ms = int(time.time() * 1000)
        self.dispatch_count = 0
        self.walls: deque = deque(maxlen=_WALL_WINDOW)


def _cost_of(compiled) -> tuple[float, float]:
    """(flops, bytes accessed) from an executable's XLA cost model; a
    backend that reports neither yields (0, 0) rather than failing the
    registration (accounting must never gate a train)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # pragma: no cover — backend without a cost model
        return 0.0, 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return 0.0, 0.0
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


def _memory_of(compiled) -> dict:
    """Memory assignment of the executable: the figures the real-TPU HBM
    budget planning needs next to the Cleaner's runtime ledger."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # pragma: no cover
        return {}
    if ma is None:
        return {}
    out = {}
    for field, key in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes",
                        "generated_code_bytes")):
        v = getattr(ma, field, None)
        if v is not None:
            out[key] = int(v)
    return out


def _stable_pid(kind: str, name: str, sig, labels: dict) -> str:
    """Readable + stable program id: same program shape in two processes
    (or two runs) gets the same id — no ``id()``s, no pointers. The short
    hash disambiguates programs that share name and leading shape."""
    mat = repr((kind, name, sig, tuple(sorted(labels.items()))))
    h = hashlib.sha1(mat.encode()).hexdigest()[:8]
    shape = ""
    if sig:
        first = sig[0]
        if isinstance(first, tuple) and first and isinstance(first[0], tuple):
            shape = "x".join(str(d) for d in first[0])
    base = f"{name}[{shape}]" if shape else name
    return f"{base}#{h}"


def module_of(jitted) -> str | None:
    """The XLA module name jit gives ``jitted``'s program: ``jit_`` and the
    function's ``__name__`` (a `telemetry.program` name on the hot path)."""
    fn = getattr(jitted, "__name__", None)
    return f"jit_{fn}" if fn else None


def register_compiled(name: str, compiled, kind: str, sig=None,
                      wall_metric: str | None = None,
                      module: str | None = None, **labels) -> str:
    """Register one compiled executable's analyses; idempotent per id
    (re-registration refreshes the static figures, keeps the wall ring).
    ``wall_metric`` names the DECLARED telemetry histogram whose walls
    already time this program's dispatches (the /3/Programs join);
    ``module`` is the program's XLA module name (:func:`module_of` of the
    jitted function it was lowered from)."""
    flops, nbytes = _cost_of(compiled)
    memory = _memory_of(compiled)
    if wall_metric is not None:
        labels["wall_metric"] = wall_metric
    pid = _stable_pid(kind, name, sig, labels)
    with _LOCK:
        rec = _REGISTRY.get(pid)
        if rec is None:
            rec = ProgramRecord(pid, kind, name, module, dict(labels),
                                flops, nbytes, memory)
            _REGISTRY[pid] = rec
        else:
            rec.flops, rec.bytes_accessed, rec.memory = flops, nbytes, memory
            rec.module = module or rec.module
    telemetry.inc("programs.registered.count")
    return pid


def note_wall(pid: str, seconds: float) -> None:
    """Record one measured dispatch wall for a registered program (host
    wall — see the module caveat on async dispatch)."""
    with _LOCK:
        rec = _REGISTRY.get(pid)
        if rec is None:
            return
        rec.dispatch_count += 1
        rec.walls.append(seconds)


class Tracked:
    """Per-signature AOT dispatch wrapper over a jitted callable — the
    instrumentation shape of ``gbm._aot_train_step`` made reusable. One
    compile per signature either way; the compiled object additionally
    yields its cost/memory analyses and a measured dispatch wall. The
    executables live as long as the wrapper (or until :meth:`clear`): a
    wrapper made per job reloads its program per job, one its owner keeps
    dispatches a known signature with no load at all."""

    __slots__ = ("name", "kind", "labels", "_jitted", "_compiled", "_pids",
                 "__weakref__")

    def __init__(self, name: str, jitted, kind: str, **labels):
        self.name = name
        self.kind = kind
        self.labels = labels
        self._jitted = jitted
        #: sig -> compiled executable, or False once a signature fell back
        self._compiled: dict = {}
        self._pids: dict = {}
        with _LOCK:
            _TRACKED.add(self)

    # AOT passthrough so a Tracked can stand wherever the jitted stood
    def lower(self, *args, **kw):
        return self._jitted.lower(*args, **kw)

    @staticmethod
    def _sig_of(a):
        shape = getattr(a, "shape", None)
        if shape is None:
            return (type(a).__name__,)
        sharding = getattr(a, "sharding", None)
        try:
            hash(sharding)
        except TypeError:  # pragma: no cover — unhashable sharding
            sharding = str(sharding)
        return (tuple(shape), str(getattr(a, "dtype", "?")), sharding)

    def _concrete(self, args) -> bool:
        from jax.core import Tracer

        return not any(isinstance(a, Tracer) for a in args)

    def clear(self) -> None:
        """Drop the compiled executables (long-server cache hygiene); the
        next dispatch per signature recompiles and re-registers."""
        self._compiled.clear()

    def __call__(self, *args):
        if not self._concrete(args):
            # under an enclosing trace the wrapper steps aside entirely
            return self._jitted(*args)
        key = tuple(self._sig_of(a) for a in args)
        ent = self._compiled.get(key)
        if ent is None:
            # a compile error surfaces HERE, once — the jitted twin would
            # hand the same program to the same compiler and fail again.
            # The load has a span of its own (train.program.load, ...),
            # opened once a signature for as long as the wrapper is kept;
            # `compiles`/`uncached` say whether it built or replayed
            with telemetry.span(f"{self.kind}.program.load",
                                program=self.name) as sp:
                with compilemeter.scoped() as sc:
                    ent = self._jitted.lower(*args).compile()
                sp.attrs["compiles"] = sc.compiles
                sp.attrs["uncached"] = sc.uncached
            sig = tuple((s[0], s[1]) for s in key
                        if isinstance(s, tuple) and len(s) == 3)
            self._pids[key] = register_compiled(
                self.name, ent, self.kind, sig=sig,
                module=module_of(self._jitted), **self.labels)
            self._compiled[key] = ent
        if ent is False:
            return self._jitted(*args)
        t0 = time.perf_counter()
        try:
            out = ent(*args)
        except (TypeError, ValueError) as e:
            # executable input REJECTION — a compiled object refuses
            # mismatched dtypes/shapes/pytrees with TypeError and
            # mismatched shardings with ValueError (measured on this
            # jax) — permanently degrade this signature to the jitted
            # twin, exactly the pre-registry dispatch. Genuine device
            # runtime faults (XlaRuntimeError: OOM, INTERNAL, failed
            # collectives) do NOT match and surface unchanged: silently
            # re-running a whole program on a faulting device would
            # double time-to-failure and bury the real traceback.
            from . import log

            log.warn(f"program {self.name}: compiled executable rejected "
                     f"its inputs ({e!r:.200}) — signature degrades to "
                     f"jit dispatch")
            self._compiled[key] = False
            return self._jitted(*args)
        pid = self._pids.get(key)
        if pid is not None:
            note_wall(pid, time.perf_counter() - t0)
        return out


def tracked(name: str, jitted, kind: str, **labels) -> Tracked:
    return Tracked(name, jitted, kind, **labels)


def clear_compiled() -> None:
    """Drop every Tracked executable (the jobs.py CLEAR_CACHES_EVERY sweep
    — directly-held compiled objects are invisible to jax.clear_caches).
    Registry records (pure numbers) survive; only executables drop."""
    with _LOCK:
        live = list(_TRACKED)
    for t in live:
        t.clear()


def snapshot() -> dict:
    """{pid: record} — the /3/Programs payload. Static cost + memory
    figures, the XLA module's name, HOST dispatch-wall quantiles
    (telemetry's nearest-rank formula — ONE quantile definition across
    /3/Metrics and /3/Programs; enqueue times, see the module doc) and the
    module's device seconds from the last capture folded in (None before
    any)."""
    out: dict[str, dict] = {}
    with _LOCK:
        recs = list(_REGISTRY.values())
    for rec in recs:
        walls = list(rec.walls)
        pcts = telemetry._percentiles(walls)
        entry = {
            "kind": rec.kind, "name": rec.name, "module": rec.module,
            "labels": rec.labels,
            "flops": rec.flops, "bytes_accessed": rec.bytes_accessed,
            "memory": rec.memory, "registered_ms": rec.registered_ms,
            "dispatch_count": rec.dispatch_count,
            "wall": {"count": len(walls),
                     "p50_s": pcts["p50"], "p95_s": pcts["p95"],
                     "total_s": round(sum(walls), 6) if walls else 0.0},
            "device": rec.device,
        }
        out[rec.pid] = entry
    return out


# ---------------------------------------------------------------------------
# the device's clock: a profiler capture folded into the registry
# ---------------------------------------------------------------------------
_DEVICE_PLANE = "/device:"
_MODULES_LINE = "XLA Modules"
_RUN_ID = re.compile(r"\(\d+\)$")

#: the XLA module names of `telemetry.PROGRAMS`
DECLARED_MODULES = frozenset(f"jit_{p}" for p in telemetry.PROGRAMS)

#: the last capture folded in (fold_capture's return), for /3/Programs
_LAST_FOLD: list = [None]


def capture_modules(path: str) -> dict:
    """{device plane: {module: [seconds, executions]}} of a capture (its
    directory, or the ``.xplane.pb`` itself): the ``XLA Modules`` line of
    every device plane that has one (a TPU chip's trace holds a second
    ``/device:`` plane without: it is no chip), one event a program
    execution, the run id jit appends to a module's name
    (``jit_gbm_level(123)``) cut off. A program in flight when the capture
    was told to stop has left its event in the captures read so far (the
    profiler stops some tens of ms later): seen, not promised."""
    from jax.profiler import ProfileData

    if not path.endswith(".pb"):
        hits = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                         recursive=True)
        if not hits:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(hits, key=os.path.getmtime)
    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(_DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != _MODULES_LINE:
                continue
            mods = planes.setdefault(plane.name, {})
            for ev in line.events:
                m = mods.setdefault(_RUN_ID.sub("", ev.name), [0.0, 0])
                m[0] += ev.duration_ns / 1e9
                m[1] += 1
    return planes


def fold_capture(path: str) -> dict:
    """Fold a capture's device seconds by program into the registry. For
    each XLA module of the capture: seconds and executions, averaged over
    the device planes (a program under ``shard_map`` runs once on every
    chip). A module that is a registry record's gets that reading as the
    record's ``device`` block (records that share a module share it); one
    named by `telemetry.PROGRAMS` without a record (the set-up's programs)
    is listed under ``programs`` all the same; every other one (the eager
    primitives, jit's own helpers) under ``undeclared``. Returns
    {capture, planes, programs: {module: {seconds, executions, records}},
    undeclared: {module: {seconds, executions}}}; :func:`last_fold` keeps
    it for /3/Programs."""
    planes = capture_modules(path)
    n = max(len(planes), 1)
    total: dict = {}
    for mods in planes.values():
        for name, (secs, runs) in mods.items():
            t = total.setdefault(name, [0.0, 0])
            t[0] += secs
            t[1] += runs
    out = {"capture": path, "planes": len(planes), "programs": {},
           "undeclared": {}}
    with _LOCK:
        by_module: dict = {}
        for rec in _REGISTRY.values():
            by_module.setdefault(rec.module, []).append(rec)
        for name, (secs, runs) in sorted(total.items(),
                                         key=lambda kv: -kv[1][0]):
            read = {"seconds": secs / n, "executions": runs / n}
            recs = by_module.get(name, [])
            for rec in recs:
                rec.device = dict(read, capture=path)
            if recs or name in DECLARED_MODULES:
                out["programs"][name] = dict(
                    read, records=sorted(r.pid for r in recs))
            else:
                out["undeclared"][name] = read
        _LAST_FOLD[0] = out
    return out


def last_fold() -> dict | None:
    """What the last :func:`fold_capture` returned (None before any)."""
    return _LAST_FOLD[0]


def ids() -> set:
    with _LOCK:
        return set(_REGISTRY)


def snapshot_delta(before_ids: set) -> dict:
    """Programs registered since ``before_ids`` (the bench sidecar's
    per-leg program-cost block): static figures only, compact."""
    out = {}
    for pid, rec in snapshot().items():
        if pid in before_ids:
            continue
        out[pid] = {"kind": rec["kind"], "flops": rec["flops"],
                    "bytes_accessed": rec["bytes_accessed"],
                    "memory": rec["memory"],
                    "dispatch_count": rec["dispatch_count"]}
    return out


def prometheus_lines() -> list:
    """Per-program Prometheus families (telemetry provider hook — the
    registry proper stays label-free, like serving's per-model stats)."""
    lines = []
    snap = snapshot()
    if not snap:
        return lines
    esc = telemetry.prom_label_escape
    lines.append("# HELP h2o_tpu_program_flops XLA cost-model flops per "
                 "dispatch of a registered program")
    lines.append("# TYPE h2o_tpu_program_flops gauge")
    for pid, rec in snap.items():
        lbl = f'program="{esc(pid)}",kind="{esc(rec["kind"])}"'
        lines.append(f'h2o_tpu_program_flops{{{lbl}}} {rec["flops"]:g}')
    lines.append("# HELP h2o_tpu_program_bytes_accessed XLA cost-model "
                 "bytes accessed per dispatch")
    lines.append("# TYPE h2o_tpu_program_bytes_accessed gauge")
    for pid, rec in snap.items():
        lbl = f'program="{esc(pid)}",kind="{esc(rec["kind"])}"'
        lines.append(f'h2o_tpu_program_bytes_accessed{{{lbl}}} '
                     f'{rec["bytes_accessed"]:g}')
    lines.append("# HELP h2o_tpu_program_dispatch_count measured dispatches "
                 "through the registry's tracked paths")
    lines.append("# TYPE h2o_tpu_program_dispatch_count counter")
    for pid, rec in snap.items():
        lbl = f'program="{esc(pid)}",kind="{esc(rec["kind"])}"'
        lines.append(f'h2o_tpu_program_dispatch_count{{{lbl}}} '
                     f'{rec["dispatch_count"]:g}')
    return lines


telemetry.add_prometheus_provider(prometheus_lines)


def reset() -> None:
    """Drop every record and tracked executable (test isolation)."""
    clear_compiled()
    with _LOCK:
        _REGISTRY.clear()
        _LAST_FOLD[0] = None
