"""Unified telemetry — process-global metrics registry + span tracing.

The reference treats observability as a first-class surface: `water/
TimeLine.java`'s always-on event ring, `WaterMeter` CPU/I-O counters and
MRTask's per-phase `.profile()`. This module is the one registry those
analogs report into, with the `utils/knobs.py` discipline applied to
metrics: every metric is DECLARED here with a kind and a one-line doc,
accessors raise ``KeyError`` on undeclared names, and graftlint's
``unregistered-metric`` rule fails the build on any literal metric-name
emit missing from this registry (AST-parsed — the linter never imports
jax).

Three metric kinds:

- **counter** — monotone total (``inc``). The fast path is lock-free:
  each thread accumulates into its own shard of a per-metric dict (a
  thread only ever writes its own key, so there is no cross-thread
  read-modify-write to lose), and readers sum an atomic ``dict()`` copy.
- **gauge** — last-set value (``set_gauge``), optionally tracking the
  process-lifetime peak (the HBM watermark).
- **histogram** — bounded ring of observations (``observe``) plus
  sharded count/sum totals; snapshots report p50/p95/p99/max over the
  ring window (``H2O_TPU_METRICS_HIST_WINDOW``) and exact count/sum.

Span tracing (``span("mrtask.dispatch", ...)``): context managers that
nest, carry one trace id through a job (contextvars — a REST request's
span and the training spans under it share the id), time themselves and
optional sub-``phase``s, and land in the timeline ring (`/3/Timeline`) as
typed ``span`` events. When ``H2O_TPU_TRACE_DIR`` is set every span is
ALSO appended to a per-process chrome-tracing file
(``trace_<pid>.trace.json``) loadable in Perfetto / chrome://tracing, so
a whole training run can be opened in a trace viewer.

Device scopes (``scope("gbm.route")``): the declared names of ``SCOPES``
put on the level program's and the IRLS step's XLA ops with
``jax.named_scope`` — the device-trace half of the span names above.
`tools/trace_scopes.py` sums a capture's device seconds by them.

Causality does not stop at process or thread boundaries:

- **wire propagation** — trace ids are 32-hex (W3C trace-context shaped);
  :func:`current_traceparent` renders the innermost open span as a
  ``traceparent`` header value (the client wire attaches it), and
  :func:`remote_context` adopts an incoming header so the server's
  request span nests under the REMOTE parent with the same trace id —
  one Perfetto session shows client→REST→job→train-chunk under one id.
- **thread propagation** — contextvars do not cross ``threading.Thread``
  or executor submits, so a worker thread's spans silently orphan into
  fresh trace ids. :func:`carry_context` wraps a callable with the
  context captured AT WRAP TIME (the submitting thread's open span);
  every span-bearing module that spawns threads routes targets through
  it (graftlint rule ``thread-without-trace-context`` pins that).
- **span sinks** — a root span opened with ``sink=`` collects its whole
  finished subtree (bounded, closed at root exit) — the raw material of
  the tail-based slow-request capture (`utils/slowtrace.py`).

Recording is always-on (the reference's ring never turns off) and cheap:
a disabled registry (``H2O_TPU_METRICS_ENABLED=0``) still validates names
but skips the writes. Span durations measure HOST wall between enter and
exit — jax dispatch is async, so a span around an un-synced device call
measures dispatch, not compute (the drained-compute bench contract is
unaffected: `model_base.train` blocks before its timer reads).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import json
import os
import threading
import time
from collections import deque

from . import knobs, timeline

_MISSING = object()


# ---------------------------------------------------------------------------
# registry (the knobs.py discipline, applied to metrics)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    kind: str           # "counter" | "gauge" | "histogram"
    doc: str


METRICS: dict[str, Metric] = {}


class _Counter:
    __slots__ = ("shards",)

    def __init__(self):
        #: thread-id -> that thread's accumulated total. A thread only
        #: writes ITS OWN key, so `d[tid] = d.get(tid, 0) + n` races with
        #: nobody; `dict(d)` (one C-level call) gives readers an atomic
        #: copy to sum.
        self.shards: dict[int, float] = {}

    def value(self) -> float:
        return sum(dict(self.shards).values())


class _Gauge:
    __slots__ = ("value", "peak", "track_peak")

    def __init__(self, track_peak: bool = False):
        self.value = 0.0
        self.peak = 0.0
        self.track_peak = track_peak


class _Hist:
    __slots__ = ("ring", "count", "sum")

    def __init__(self, window: int):
        self.ring: deque = deque(maxlen=window)  # deque append is atomic
        self.count = _Counter()
        self.sum = _Counter()


_COUNTERS: dict[str, _Counter] = {}
_GAUGES: dict[str, _Gauge] = {}
_HISTS: dict[str, _Hist] = {}


def _hist_window() -> int:
    return max(knobs.get_int("H2O_TPU_METRICS_HIST_WINDOW"), 16)


def _counter(name: str, doc: str) -> None:
    METRICS[name] = Metric(name, "counter", doc)
    _COUNTERS[name] = _Counter()


def _gauge(name: str, doc: str, track_peak: bool = False) -> None:
    METRICS[name] = Metric(name, "gauge", doc)
    _GAUGES[name] = _Gauge(track_peak=track_peak)


def _histogram(name: str, doc: str) -> None:
    METRICS[name] = Metric(name, "histogram", doc)
    _HISTS[name] = _Hist(_hist_window())


# -- MRTask driver (parallel/mrtask.py — DrJAX-style per-stage accounting) --
_counter("mrtask.dispatch.count",
         "mr_reduce/mr_map driver dispatches")
_counter("mrtask.program.build.count",
         "driver-program cache misses (a fresh shard_map trace + compile)")
_counter("mrtask.payload.in.bytes",
         "bytes of input operands handed to MRTask dispatches")
_counter("mrtask.payload.out.bytes",
         "bytes of result leaves returned by MRTask dispatches")
_histogram("mrtask.dispatch.seconds",
           "host wall per driver dispatch (build + async dispatch; device "
           "compute drains at the caller's sync point)")

# -- training loops ----------------------------------------------------------
_counter("train.count", "completed training jobs")
_histogram("train.seconds",
           "drained train wall per job (the run_time_ms source — "
           "block_until_ready runs before the clock is read)")
_counter("train.chunk.count", "GBM/DRF boosting-chunk iterations")
_counter("train.gbm.psum_bytes",
         "bytes of level histogram handed to the cross-shard psum, a shard, "
         "counted from shapes at chunk dispatch (0 on one row shard)")
_counter("train.gbm.hist_onehot_cells",
         "one-hot cells the level histograms of a chunk's trees generate "
         "(rows x features x trees x, summed over levels, the bins with the "
         "NA slot at a flat level or k * H + L at a two-digit one), "
         "counted from shapes at chunk dispatch")
_counter("train.glm.path.lambdas",
         "lambdas a GLM lambda_search fitted before its path ended, added "
         "once a job")
_counter("train.glm.path.iterations",
         "IRLS iterations (Gram passes) a GLM lambda_search spent on its "
         "path, added once a job")
_counter("train.glm.path.steps_as_probe",
         "plateau tests of a GLM lambda_search answered by the deviance of "
         "the step dispatched ahead at the new beta (another lambda "
         "follows), added once a job")
_counter("train.glm.path.probes",
         "deviance probes a GLM lambda_search dispatched on its path (at "
         "its last planned lambda only), added once a job")
_counter("train.glm.program.kept",
         "calls of the GLM's two program factories (IRLS step, deviance "
         "probe) handed the program an earlier call built: nothing is "
         "traced, lowered or loaded")
_counter("train.glm.program.built",
         "calls of the GLM's two program factories that built the program: "
         "a family's first train in the process, or its first after a cache "
         "sweep or an eviction")
_counter("train.glm.interact.pairs_streamed",
         "numeric x numeric interaction pairs a GLM job formed inside its "
         "IRLS step's row blocks (never stored as columns), added once a job")
_counter("train.glm.interact.kernel_rows",
         "rows the streamed interaction step's Pallas kernel formed and "
         "contracted (`interact.interaction_gram`), added at each dispatch "
         "from shapes alone: R a pass, summed over row shards")
_counter("train.glm.interact.columns_appended",
         "interaction columns glm._apply_interactions appended to a frame "
         "copy: categorical pairs, and numeric pairs on the paths that do "
         "not stream them (L-BFGS, GAM, CoxPH, scoring of such models)")
_counter("train.gam.iterations",
         "IRLS iterations a GAM job ran, added once a job")
_counter("train.gam.design_bytes",
         "bytes a GAM job's two design programs move, from shapes alone: "
         "the smooth columns read by the sums program, every column read "
         "and the (R, P+1) design written by the design program")
_histogram("train.chunk.seconds",
           "wall per boosting chunk (train_fn dispatch + scoring + "
           "history, the score_tree_interval boundary)")
_counter("train.epoch.count", "DeepLearning epochs completed")
_histogram("train.epoch.seconds",
           "wall between DL epoch boundaries (async dispatch wall)")
_counter("train.checkpoint.count",
         "auto-recovery checkpoint writes (backend/persist.py)")
_histogram("train.checkpoint.seconds",
           "wall per auto-recovery checkpoint write (the preemption "
           "insurance premium, measured)")
_histogram("train.compile.seconds",
           "drained wall of the AOT lower+compile of the tree train step "
           "at build setup (near-zero when the persistent compile cache "
           "replays it — the cold-start meter)")

# -- HBM Cleaner (backend/memory.py) -----------------------------------------
_gauge("cleaner.hbm.live.bytes",
       "device-resident bytes the Cleaner ledger currently tracks",
       track_peak=True)
_gauge("cleaner.hbm.limit.bytes",
       "resolved Cleaner HBM budget (0 while unlimited/unresolved)")
_counter("cleaner.spill.count", "Vec device buffers spilled to ice")
_counter("cleaner.spill.bytes", "bytes spilled to ice")
_counter("cleaner.rehydrate.count", "spilled Vecs reloaded to device")
_counter("cleaner.rehydrate.bytes", "bytes reloaded from ice")
_counter("cleaner.emergency_sweep.count",
         "spill-everything sweeps triggered by device OOM")

# -- parser ------------------------------------------------------------------
_counter("parser.parse.count", "frames parsed (io/parser.py parse_file)")
_counter("parser.rows.count", "rows ingested by the parser")
_histogram("parser.parse.seconds", "wall per parse_file call")

# -- fault tolerance ---------------------------------------------------------
_counter("failpoint.fired.count",
         "armed failpoint injections that actually fired")
_counter("retry.attempt.count",
         "retries scheduled by utils/retry.py (transient failures seen)")

# -- serving (h2o_tpu/serving/ — the global face of per-model stats.py) ------
_counter("serving.request.count", "scoring requests across all models")
_counter("serving.request.rows", "rows scored across all models")
_histogram("serving.request.seconds",
           "end-to-end request latency (encode + queue + score)")
_counter("serving.batch.count", "micro-batcher device calls")
_counter("serving.batch.rows", "rows through micro-batched device calls")
_counter("serving.rejected.count", "requests rejected by backpressure (429)")
_counter("serving.timeout.count", "requests expired while queued (408)")
_counter("serving.recompile.count",
         "steady-state scorer bucket-miss recompiles (contract: 0)")

# -- serving control plane (serving/control.py + router.py) ------------------
_counter("serving.admission.rejected.count",
         "registrations/re-placements refused by the fleet HBM quota "
         "(REST: 429 + Retry-After)")
_counter("serving.placement.evicted.count",
         "cold placements evicted under quota pressure (lazily re-placed "
         "on first hit)")
_counter("serving.replica.dead.count",
         "replica scorers marked dead after a score-path fault")
_counter("serving.replica.reroute.count",
         "requests re-dispatched around a dying replica (contract: no "
         "request fails because its replica died under it)")
_counter("serving.route.count", "requests scored through a routed endpoint")
_counter("serving.route.shadow.rows",
         "rows shadow-scored off the response path")
_counter("serving.route.shadow.dropped.count",
         "shadow jobs dropped because the shadow queue was full (the "
         "response path never blocks on shadow work)")
_histogram("serving.route.divergence",
           "per-row |prediction delta| between the serving variant and a "
           "shadow variant over identical rows (canary drift monitor)")

# -- REST control plane ------------------------------------------------------
_counter("rest.request.count", "REST requests routed")
_counter("rest.error.count", "REST requests answered with a 5xx")
_histogram("rest.request.seconds", "wall per routed REST request")

# -- concurrency sanitizer (utils/sanitizer.py) ------------------------------
_counter("sanitizer.violation.count",
         "lock-order inversions observed + @guarded_by assertion "
         "failures raised by the runtime sanitizer (contract: 0 — every "
         "count is a typed error somewhere)")

# -- XLA ---------------------------------------------------------------------
_counter("xla.compile.count",
         "XLA backend compiles observed since utils/compilemeter.py "
         "installed its jax.monitoring listener")
_counter("jobs.cache_sweeps",
         "times backend/jobs.py dropped every compiled program the process "
         "held (H2O_TPU_CLEAR_CACHES_EVERY finished jobs that each entered "
         "the compile path; a process that replays what it has never does)")

# -- fleet observability plane (utils/programs.py / fleetobs.py / -----------
# -- flightrec.py + the profiler capture surface) ----------------------------
_counter("programs.registered.count",
         "compiled executables registered in the program cost registry "
         "(utils/programs.py — one per (program, signature))")
_counter("profiler.capture.count",
         "jax.profiler device-trace captures completed (span-scoped "
         "H2O_TPU_PROFILE_DIR sessions + POST /3/Profiler/capture)")
_counter("fleet.scrape.count",
         "peer-process metric scrapes attempted by the fleet collector "
         "(utils/fleetobs.py; failures count too — they carry an error "
         "in the merged view)")
_histogram("fleet.scrape.seconds",
           "wall per full fleet collection (every peer + spool read + "
           "merge) behind GET /3/Metrics?fleet=1")
_counter("flight.dump.count",
         "flight-recorder diagnostic bundles written to "
         "H2O_TPU_FLIGHT_DIR (utils/flightrec.py; contract: every count "
         "is a terminal event somewhere)")

# -- causal observability plane (utils/slo.py / watchdog.py / ---------------
# -- slowtrace.py / health.py) ----------------------------------------------
_counter("watchdog.trip.count",
         "watchdog detector trips (utils/watchdog.py — hung job, stalled "
         "MRTask dispatch, Cleaner thrash, serving queue stall; each trip "
         "also lands a typed timeline event + a proactive flight bundle)")
_gauge("watchdog.hung_jobs",
       "running jobs with no progress heartbeat within "
       "H2O_TPU_WATCHDOG_JOB_BUDGET_MS, as of the last watchdog sweep")
_gauge("watchdog.stalled_dispatch",
       "MRTask driver dispatches in flight longer than "
       "H2O_TPU_WATCHDOG_DISPATCH_BUDGET_MS, as of the last sweep")
_gauge("watchdog.cleaner_thrash",
       "1 while the Cleaner spilled AND rehydrated more than "
       "H2O_TPU_WATCHDOG_THRASH_OPS times within one watchdog interval "
       "(spill/reload churn — the memory death spiral), else 0")
_gauge("watchdog.queue_stall",
       "serving batchers whose oldest queued request has waited past "
       "H2O_TPU_WATCHDOG_QUEUE_BUDGET_MS, as of the last sweep")
_gauge("slo.worst_burn",
       "max burn rate across every declared SLO (utils/slo.py): 1.0 = "
       "exactly consuming the error budget, >1 = burning faster — the "
       "autoscaling/rollback loops' one-number health signal; refreshed "
       "by every GET /3/Metrics and /3/Health serve (slo.burn_snapshot), "
       "so both poll surfaces read a current value")
_counter("slowtrace.captured.count",
         "requests whose full span tree was persisted by the tail-based "
         "slow-request capture (utils/slowtrace.py — SLO p99 breachers "
         "only, behind GET /3/SlowTraces)")
_counter("health.poll.count",
         "GET /3/Health evaluations (excluded from the timeline ring "
         "like the PR 6 monitoring polls — a 1s readiness poller must "
         "not cycle the event ring)")

# -- workload manager (h2o_tpu/workload/ — tenants, lanes, preemption) -------
_counter("workload.submitted.count",
         "jobs submitted through the workload manager (all tenants; the "
         "per-tenant split rides the h2o_tpu_tenant_* Prometheus lines)")
_counter("workload.rejected.count",
         "submissions rejected by tenant quota admission (REST surfaces "
         "them as 429 + Retry-After)")
_counter("workload.dispatch.count",
         "queue entries handed a slot by the fair-share lottery "
         "(includes force-dispatches from the aging starvation bound)")
_counter("workload.preempt.count",
         "running jobs preempted at a chunk/epoch boundary (priority "
         "arrival, serving pressure, or the shed policy) — state force-"
         "checkpointed, HBM reservation released")
_counter("workload.resume.count",
         "parked (preempted) jobs re-admitted and resumed from their "
         "boundary checkpoint")
_counter("workload.shed.count",
         "shed-policy preemptions specifically (SLO burn / typed health "
         "degradation picked the victim tenant)")
_counter("workload.requeue.count",
         "managed jobs requeued by a watchdog hung-job/trip signal "
         "instead of paging (the PR 15 watchdog feeding the scheduler)")
_gauge("workload.running", "managed jobs currently holding a slot")
_gauge("workload.queue.depth", "managed jobs waiting for a slot")
_gauge("workload.parked",
       "preempted jobs parked host-side awaiting re-admission")
_histogram("workload.queue.wait.seconds",
           "queue wait per managed dispatch (submission or re-admission "
           "to slot grant) — backs the workload.wait SLO burn")


def _lookup(name: str) -> Metric:
    try:
        return METRICS[name]
    except KeyError:
        raise KeyError(
            f"unregistered metric {name!r} — declare it in "
            f"h2o_tpu/utils/telemetry.py (graftlint rule "
            f"unregistered-metric enforces the same statically)") from None


#: device scopes — the level program's and the Gram step's phases as they
#: appear in a device trace (``jax.named_scope`` lands in the ``op_name``
#: metadata of the XLA ops traced under it, so any capture of the REAL
#: program splits device time by these names; nothing runs when no capture
#: does). Scopes nest: an op's scope is the INNERMOST declared name on its
#: path. Ops the compiler makes itself (layout copies, copy loops) carry no
#: metadata and read "unscoped". Declared once here, like METRICS:
#: :func:`scope` raises on any other name.
SCOPES: tuple[str, ...] = (
    "gbm.sketch",    # binning._sketch_core: the quantile sketch's passes
    "gbm.bin",       # binning.bin_matrix / bin_column, the BinnedView coding
    "gbm.grad",      # grad_fn per tree (make_train_fn.spmd)
    "gbm.route",     # row routing off a level's splits (all formulations)
    "gbm.hist",      # level-histogram accumulation (kernels/hist.py)
    "gbm.psum",      # the level histogram's cross-shard reduction
    "gbm.split",     # engine._find_splits
    "gbm.leaf",      # per-node totals, quantile leaves, leaf values
    "gbm.score",     # fused boundary scoring inside the chunk step
    "glm.eta",       # link, weights, working response of the IRLS step
    "glm.gram",      # kernels/gram.gram_accumulate
    "glm.deviance",  # the family deviance (IRLS step and probe)
    "gam.basis",     # gam._design_program / _basis_sums: the smooths' columns
)


def scope(name: str):
    """``jax.named_scope`` for a DECLARED device scope (KeyError otherwise —
    the registry's own discipline). Metadata only: the traced values, the
    XLA module's name and the compile-cache key do not change."""
    if name not in SCOPES:
        raise KeyError(
            f"undeclared device scope {name!r} — declare it in SCOPES "
            f"(h2o_tpu/utils/telemetry.py)")
    import jax

    return jax.named_scope(name)


#: device programs — the jitted functions a training job dispatches, under
#: the ONE name each has everywhere: :func:`program` gives the function it
#: decorates that ``__name__``, so ``jax.jit`` names its XLA module
#: ``jit_<name>`` (the ``XLA Modules`` line of a device trace, the
#: ``compile`` timeline events), and `utils/programs.py` keeps that module
#: name beside the registry id. A name carries its layer as a prefix, so
#: one ``contains`` sums a layer (``jit_gbm_setup_``). Declared once here,
#: like SCOPES: :func:`program` raises on any other name.
PROGRAMS: tuple[str, ...] = (
    "gbm_level",            # engine.make_train_fn: a chunk of trees
    "gbm_setup_sketch",     # binning._sketch_core, jit and shard_map forms
    "gbm_setup_minmax",     # binning._col_minmax: the columns' extrema
    "gbm_setup_distinct",   # binning._distinct_values: exact small-data bins
    "gbm_setup_bin",        # binning.bin_column / bin_matrix: values to codes
    "gbm_setup_stack",      # chunks._stack_codes; gbm._codes_to_f32 back
    "gbm_setup_prep",       # gbm._jit_prep: response, mask and weights
    "gbm_setup_init",       # gbm._jit_init_f / _jit_full_like: start margin
    "gbm_setup_keys",       # gbm._jit_keys: the per-tree PRNG keys
    "gbm_score_raw",        # gbm._metrics_raw: margin to score0, unfused
    "metrics_fused",        # metrics._fused_metric_kernel: mask, weights, k.
    "metrics_regression",   # metrics._regression_kernel
    "metrics_binomial",     # metrics._binomial_hist_kernel: AUC histograms
    "metrics_multinomial",  # metrics._multinomial_kernel
    "metrics_mauc",         # metrics._mauc_kernel: the multinomial AUC family
    "glm_probe",            # glm._make_dev_kernel: the deviance probe
    "glm_irls_sharded",     # glm._make_irls_kernel: the step under shard_map
    "glm_interact_step",    # glm._make_interact_step: IRLS step, products
                            # formed per row block (one shard or shard_map)
    "glm_interact_eta",     # glm._make_interact_eta: probabilities and
                            # deviance through the products' quadratic form
    "glm_interact_moments",  # datainfo._moments_program: the products'
                             # statistics from the design's columns
    # the one name without its layer's prefix: the benchmark's
    # irls_program_s reads the XLA module jit__core by name
    "_core",                # glm._make_irls_kernel: the IRLS step, one shard
    "gam_design",           # gam._design_program: the whole (R, P+1) design
    "gam_design_sums",      # gam._basis_sums: the bases' sums over the rows
    "mrtask_driver",        # parallel/mrtask.py: a DrJAX-style driver program
    "merge_expand",         # rapids/merge.py: the sharded merge's expansion
    "uplift_level",         # models/uplift.py: a chunk of uplift trees
)


def program(name: str):
    """Decorator under ``jax.jit``: the function takes the DECLARED program
    name as its ``__name__`` (KeyError otherwise), which is what jit names
    the XLA module after. Nothing else changes: same arguments, same body,
    the lowered text differs in the module's name only."""
    if name not in PROGRAMS:
        raise KeyError(
            f"undeclared device program {name!r} — declare it in PROGRAMS "
            f"(h2o_tpu/utils/telemetry.py)")

    def rename(fn):
        fn.__name__ = name
        return fn

    return rename


def _enabled() -> bool:
    return knobs.get_bool("H2O_TPU_METRICS_ENABLED")


def enabled() -> bool:
    """Public master-switch read — gates optional instrumentation work
    whose COST exists even when the emits are skipped (slow-trace capture,
    SLO windows)."""
    return _enabled()


# ---------------------------------------------------------------------------
# emit accessors — the lint-checked surface
# ---------------------------------------------------------------------------
def inc(name: str, n: float = 1) -> None:
    """Add ``n`` to a declared counter (lock-free per-thread shard)."""
    c = _COUNTERS.get(name)
    if c is None:
        _lookup(name)
        raise KeyError(f"metric {name!r} is a {METRICS[name].kind}, not a "
                       f"counter — use the matching accessor")
    if not _enabled():
        return
    tid = threading.get_ident()
    c.shards[tid] = c.shards.get(tid, 0) + n


def set_gauge(name: str, value: float) -> None:
    g = _GAUGES.get(name)
    if g is None:
        _lookup(name)
        raise KeyError(f"metric {name!r} is a {METRICS[name].kind}, not a "
                       f"gauge — use the matching accessor")
    if not _enabled():
        return
    g.value = value
    if g.track_peak and value > g.peak:
        g.peak = value


def observe(name: str, value: float) -> None:
    h = _HISTS.get(name)
    if h is None:
        _lookup(name)
        raise KeyError(f"metric {name!r} is a {METRICS[name].kind}, not a "
                       f"histogram — use the matching accessor")
    if not _enabled():
        return
    h.ring.append(value)
    tid = threading.get_ident()
    h.count.shards[tid] = h.count.shards.get(tid, 0) + 1
    h.sum.shards[tid] = h.sum.shards.get(tid, 0) + value


def value(name: str) -> float:
    """Current counter total or gauge value (histograms: use snapshot)."""
    m = _lookup(name)
    if m.kind == "counter":
        return _COUNTERS[name].value()
    if m.kind == "gauge":
        return _GAUGES[name].value
    return _HISTS[name].count.value()


def hist_values(name: str) -> list:
    """The recent-window ring of a declared histogram, oldest first — the
    raw observations behind the snapshot percentiles. `utils/slo.py`
    computes rolling latency-breach fractions off these SAME rings instead
    of keeping a second latency window."""
    m = _lookup(name)
    if m.kind != "histogram":
        raise KeyError(f"metric {name!r} is a {m.kind}, not a histogram")
    return list(_HISTS[name].ring)


# ---------------------------------------------------------------------------
# snapshots — the /3/Metrics payload and the bench sidecar delta
# ---------------------------------------------------------------------------
def _percentiles(vals: list) -> dict:
    if not vals:
        return {"p50": None, "p95": None, "p99": None, "max": None}
    s = sorted(vals)
    n = len(s)

    def pct(q):
        return s[min(int(q * (n - 1) + 0.5), n - 1)]

    return {"p50": pct(0.50), "p95": pct(0.95), "p99": pct(0.99),
            "max": s[-1]}


def snapshot() -> dict:
    """Full typed registry state: {name: {kind, value|...}}. Installs the
    compile-count listener opportunistically (jax may not be up yet in a
    bare control-plane process — then compiles simply read 0)."""
    try:
        from . import compilemeter

        compilemeter.install()
    except Exception:  # pragma: no cover - no jax in a stripped process
        pass
    out: dict[str, dict] = {}
    for name, m in METRICS.items():
        if m.kind == "counter":
            out[name] = {"kind": "counter", "value": _COUNTERS[name].value()}
        elif m.kind == "gauge":
            g = _GAUGES[name]
            rec = {"kind": "gauge", "value": g.value}
            if g.track_peak:
                rec["peak"] = g.peak
            out[name] = rec
        else:
            h = _HISTS[name]
            vals = list(h.ring)
            out[name] = {"kind": "histogram", "count": h.count.value(),
                         "sum": round(h.sum.value(), 6),
                         "window": len(vals), **{
                             k: (None if v is None else round(v, 6))
                             for k, v in _percentiles(vals).items()}}
    return out


def snapshot_delta(before: dict, after: dict | None = None) -> dict:
    """What happened between two snapshots, compact: counters report the
    delta (zero deltas dropped), gauges the after-value (+ peak when
    tracked), histograms the count/sum delta."""
    after = snapshot() if after is None else after
    out: dict[str, dict] = {}
    for name, rec in after.items():
        prev = before.get(name, {})
        if rec["kind"] == "counter":
            d = rec["value"] - prev.get("value", 0)
            if d:
                out[name] = {"delta": d}
        elif rec["kind"] == "gauge":
            g = {"value": rec["value"]}
            if "peak" in rec:
                g["peak"] = rec["peak"]
            out[name] = g
        else:
            dc = rec["count"] - prev.get("count", 0)
            if dc:
                out[name] = {"count": dc,
                             "sum_s": round(rec["sum"]
                                            - prev.get("sum", 0.0), 6),
                             "p99": rec["p99"]}
    return out


#: extra exposition sources: callables returning pre-formatted Prometheus
#: text lines. The registry itself stays label-free (fleet totals); a
#: subsystem with a natural label dimension (serving's per-model stats
#: windows) registers a provider instead of a second metrics registry.
_PROM_PROVIDERS: list = []


def add_prometheus_provider(fn) -> None:
    """Register a ``() -> list[str]`` of exposition lines appended to
    :func:`prometheus` output. Idempotent per callable."""
    if fn not in _PROM_PROVIDERS:
        _PROM_PROVIDERS.append(fn)


def prom_label_escape(label) -> str:
    """Prometheus label-value escaping (backslash, quote, newline) — the
    ONE implementation every labelled provider shares: label values are
    externally chosen (client model ids, backend device names) and one bad
    value must not make the whole scrape unparseable."""
    return (str(label).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def prometheus() -> str:
    """Prometheus text exposition (format 0.0.4) of the whole registry —
    dots become underscores, everything is prefixed ``h2o_tpu_``."""
    lines = []
    for name, m in sorted(METRICS.items()):
        pname = "h2o_tpu_" + name.replace(".", "_").replace("-", "_")
        lines.append(f"# HELP {pname} {m.doc}")
        if m.kind == "counter":
            lines.append(f"# TYPE {pname} counter")
            lines.append(f"{pname} {_COUNTERS[name].value():g}")
        elif m.kind == "gauge":
            g = _GAUGES[name]
            lines.append(f"# TYPE {pname} gauge")
            lines.append(f"{pname} {g.value:g}")
            if g.track_peak:
                lines.append(f"# HELP {pname}_peak process-lifetime peak "
                             f"of {pname}")
                lines.append(f"# TYPE {pname}_peak gauge")
                lines.append(f"{pname}_peak {g.peak:g}")
        else:
            h = _HISTS[name]
            vals = list(h.ring)
            pc = _percentiles(vals)
            lines.append(f"# TYPE {pname} summary")
            for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
                if pc[key] is not None:
                    lines.append(f'{pname}{{quantile="{q}"}} {pc[key]:g}')
            lines.append(f"{pname}_sum {h.sum.value():g}")
            lines.append(f"{pname}_count {h.count.value():g}")
    for provider in list(_PROM_PROVIDERS):
        try:
            lines.extend(provider())
        except Exception:  # pragma: no cover — a sick provider must not
            pass           # take down the whole scrape
    return "\n".join(lines) + "\n"


def describe() -> str:
    """Human-readable registry dump (the knobs.describe analog)."""
    out = []
    for m in sorted(METRICS.values(), key=lambda m: m.name):
        out.append(f"{m.name}  [{m.kind}]")
        out.append(f"    {m.doc}")
    return "\n".join(out)


def reset() -> None:
    """Zero every metric (test isolation — production never calls this)."""
    for c in _COUNTERS.values():
        c.shards.clear()
    for g in _GAUGES.values():
        g.value = 0.0
        g.peak = 0.0
    for name in _HISTS:
        _HISTS[name] = _Hist(_hist_window())


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------
#: (trace_id, span_id, sink) of the innermost open span in this context.
#: trace_id is 32 lowercase hex (W3C trace-context shaped, wire-portable);
#: span_id is a process-local int for local spans or the 16-hex string of
#: a REMOTE parent adopted from a traceparent header; sink is the span
#: tree collector of the enclosing captured request (None outside one).
_CTX: contextvars.ContextVar = contextvars.ContextVar("h2o_tpu_trace",
                                                      default=None)
_IDS = itertools.count(1)


def _mint_trace_id() -> str:
    import uuid

    return uuid.uuid4().hex


class SpanSink:
    """Bounded collector of finished span records under one root span.

    Every span whose context inherits the sink appends its record on exit
    — including spans from worker threads that adopted the context via
    :func:`carry_context`. The root CLOSES the sink when it exits, so a
    long-lived descendant (a background training job rooted under a REST
    request) cannot grow a dead request's tree forever; ``cap`` bounds
    the live tree the same way the timeline ring is bounded."""

    __slots__ = ("items", "cap", "closed")

    def __init__(self, cap: int = 512):
        self.items: list[dict] = []
        self.cap = cap
        self.closed = False

    def add(self, rec: dict) -> None:
        # list.append is atomic under the GIL; a dropped record past the
        # cap/close loses detail, never correctness
        if not self.closed and len(self.items) < self.cap:
            self.items.append(rec)

    def close(self) -> list[dict]:
        self.closed = True
        return self.items


# -- cross-boundary propagation ---------------------------------------------
_TRACEPARENT_RE = None  # compiled lazily (re import stays top-level-free)


def _traceparent_parse(header):
    """(trace_id, parent_span) from a W3C-style ``traceparent`` header, or
    None when absent/malformed — a bad header must degrade to a fresh
    trace, never 400 the request."""
    global _TRACEPARENT_RE
    if not header or not isinstance(header, str):
        return None
    if _TRACEPARENT_RE is None:
        import re

        _TRACEPARENT_RE = re.compile(
            r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None or m.group(1) == "ff" or set(m.group(2)) == {"0"} \
            or set(m.group(3)) == {"0"}:
        return None
    return m.group(2), m.group(3)


def current_traceparent() -> str | None:
    """The innermost open span as a ``traceparent`` header value
    (``00-<trace32>-<span16>-01``), or None outside any span — what
    `api/client.py`'s ``_send`` attaches to every request so the server
    side can root its request span under the caller's."""
    cur = _CTX.get()
    if cur is None:
        return None
    trace, span_id = cur[0], cur[1]
    if isinstance(span_id, int):
        span_hex = f"{span_id & 0xFFFFFFFFFFFFFFFF:016x}"
    else:                       # re-forwarding an adopted remote parent
        span_hex = str(span_id)[-16:].rjust(16, "0")
    # legacy/foreign trace ids normalize to 32 hex by hashing — the header
    # must always parse on the far side
    if len(trace) != 32 or not all(c in "0123456789abcdef" for c in trace):
        import hashlib

        trace = hashlib.sha256(trace.encode()).hexdigest()[:32]
    return f"00-{trace}-{span_hex}-01"


@contextlib.contextmanager
def remote_context(traceparent: str | None):
    """Adopt an incoming ``traceparent`` for the duration of the block:
    spans opened inside reuse the REMOTE trace id and record the remote
    span as their parent — the server half of wire propagation. A
    missing/malformed header makes this a no-op (fresh local trace)."""
    parsed = _traceparent_parse(traceparent)
    if parsed is None:
        yield None
        return
    trace, parent = parsed
    token = _CTX.set((trace, parent, None))
    try:
        yield trace
    finally:
        _CTX.reset(token)


def carry_context(fn):
    """Bind ``fn`` to the CURRENT span context (captured at wrap time) so
    running it on another thread keeps the trace id and parent linkage —
    ``Thread(target=carry_context(run))`` / ``ex.submit(carry_context(f),
    x)``. Contextvars do not cross thread starts or executor submits;
    without this, worker-thread spans mint orphan trace ids (the
    shadow-scorer/MicroBatcher hole this helper closes — graftlint rule
    ``thread-without-trace-context`` enforces adoption)."""
    import functools

    captured = _CTX.get()

    @functools.wraps(fn)
    def _carried(*args, **kwargs):
        token = _CTX.set(captured)
        try:
            return fn(*args, **kwargs)
        finally:
            _CTX.reset(token)

    return _carried


class Span:
    __slots__ = ("name", "metric", "attrs", "trace_id", "span_id",
                 "parent_id", "phases", "t0_ns")

    def __init__(self, name, metric, attrs, trace_id, span_id, parent_id):
        self.name = name
        self.metric = metric
        self.attrs = attrs
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id          # int (local) | str (remote)
        self.phases: dict[str, float] = {}
        self.t0_ns = 0

    @contextlib.contextmanager
    def phase(self, phase_name: str):
        """Sub-phase accounting inside the span (MRProfile's setup/map/
        reduce split) — totals land on the span's timeline event."""
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            dt = (time.perf_counter_ns() - t0) / 1e9
            self.phases[phase_name] = self.phases.get(phase_name, 0.0) + dt


@contextlib.contextmanager
def span(name: str, metric: str | None = None, ring: bool = True,
         sink: SpanSink | None = None, **attrs):
    """Open a traced span: nests (contextvars), shares the enclosing trace
    id or mints a 32-hex one, records a typed ``span`` timeline event on
    exit (plus the chrome-trace line when ``H2O_TPU_TRACE_DIR`` is set),
    and observes ``metric`` (a declared histogram) with its duration.
    ``attrs`` are small JSON-able labels; keep them cheap — this runs on
    hot-path boundaries.

    ``ring=False`` keeps the span OUT of the timeline ring (trace file
    and sink still see it) — for per-request spans whose rate would cycle
    the 4096-event ring the way monitoring polls would. ``sink=`` makes
    this span a capture root: its whole finished subtree (across
    carry_context'd threads) accumulates into the sink, which closes at
    root exit; children inherit the enclosing sink automatically."""
    if metric is not None and metric not in _HISTS:
        _lookup(metric)  # typed KeyError for undeclared / non-histogram
        raise KeyError(f"span metric {metric!r} must be a histogram")
    parent = _CTX.get()
    span_id = next(_IDS)
    trace_id = parent[0] if parent else _mint_trace_id()
    root_sink = sink
    if sink is None and parent is not None and len(parent) > 2:
        sink = parent[2]
    sp = Span(name, metric, attrs, trace_id, span_id,
              parent[1] if parent else None)
    token = _CTX.set((trace_id, span_id, sink))
    # while a device-profiler session is live, mirror the span stack into
    # jax TraceAnnotations so XLA ops nest under the SAME names in
    # Perfetto (train.gbm.chunk wraps its device ops) — one global read
    # when no capture is running, nothing on the steady-state span path
    ann = None
    if _PROFILE_ACTIVE[0]:
        try:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        except Exception:  # pragma: no cover — profiler backend quirk
            ann = None
    sp.t0_ns = time.perf_counter_ns()
    try:
        yield sp
    finally:
        dur_ns = time.perf_counter_ns() - sp.t0_ns
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:  # pragma: no cover
                pass
        _CTX.reset(token)
        if _enabled():
            detail = dict(sp.attrs)
            detail["trace"] = sp.trace_id
            detail["span"] = sp.span_id
            if sp.parent_id is not None:
                detail["parent"] = sp.parent_id
            for k, v in sp.phases.items():
                detail[f"{k}_s"] = round(v, 6)
            if ring:
                timeline.record("span", name,
                                dur_us=dur_ns // 1000, **detail)
            if sp.metric is not None:
                observe(sp.metric, dur_ns / 1e9)
            if sink is not None:
                sink.add({"name": name, "dur_us": dur_ns // 1000,
                          "t0_us": sp.t0_ns // 1000,
                          "tid": threading.get_ident(), **detail})
            _trace_emit(sp, dur_ns)
        if root_sink is not None:
            items = root_sink.close()
            # a NESTED capture root (a serving.score request inside a
            # rest.request capture) must not sever the enclosing tree:
            # fold the finished subtree into the parent's sink so the
            # outer slow-trace still carries the inner latency detail
            outer = parent[2] if (parent is not None and len(parent) > 2) \
                else None
            if outer is not None and outer is not root_sink:
                for rec in items:
                    outer.add(rec)


def trace_id() -> str | None:
    """Trace id of the innermost open span (None outside any span)."""
    cur = _CTX.get()
    return cur[0] if cur else None


class Lap:
    """Boundary-to-boundary timer whose clock math lives HERE (one audited
    site) instead of inside a training loop: ``tick()`` observes the wall
    since the previous tick into a declared histogram + timeline event.
    First tick only starts the clock. Durations are async-dispatch wall
    unless the loop syncs — same caveat as spans."""

    __slots__ = ("metric", "what", "_t0")

    def __init__(self, metric: str | None = None, what: str | None = None):
        if metric is not None and metric not in _HISTS:
            _lookup(metric)
            raise KeyError(f"lap metric {metric!r} must be a histogram")
        self.metric = metric
        self.what = what
        self._t0: float | None = None

    def tick(self, **detail) -> float | None:
        now = time.perf_counter()
        dt = None
        if self._t0 is not None:
            dt = now - self._t0
            if _enabled():
                if self.metric is not None:
                    observe(self.metric, dt)
                if self.what is not None:
                    timeline.record("lap", self.what,
                                    dur_us=int(dt * 1e6), **detail)
        self._t0 = now
        return dt


def lap(metric: str | None = None, what: str | None = None) -> Lap:
    return Lap(metric=metric, what=what)


# ---------------------------------------------------------------------------
# chrome-tracing / Perfetto export
# ---------------------------------------------------------------------------
_TRACE_LOCK = threading.Lock()
_TRACE_FILE = None        # open handle once the dir knob resolves
_TRACE_DIR_SEEN = None    # knob value the handle was opened for


def trace_path() -> str | None:
    """Path of this process's chrome-trace file (None when export is off)."""
    d = knobs.get_str("H2O_TPU_TRACE_DIR")
    if not d:
        return None
    return os.path.join(d, f"trace_{os.getpid()}.trace.json")


def _trace_emit(sp: Span, dur_ns: int) -> None:
    global _TRACE_FILE, _TRACE_DIR_SEEN
    d = knobs.get_str("H2O_TPU_TRACE_DIR")
    if not d:
        return
    ev = {"name": sp.name, "ph": "X", "ts": sp.t0_ns // 1000,
          "dur": max(dur_ns // 1000, 1), "pid": os.getpid(),
          "tid": threading.get_ident(),
          "args": {**{k: v for k, v in sp.attrs.items()},
                   "trace": sp.trace_id,
                   **{f"{k}_s": round(v, 6) for k, v in sp.phases.items()}}}
    line = json.dumps(ev)
    with _TRACE_LOCK:
        if _TRACE_FILE is None or _TRACE_DIR_SEEN != d:
            os.makedirs(d, exist_ok=True)
            if _TRACE_FILE is not None:
                try:
                    _TRACE_FILE.close()
                except OSError:  # pragma: no cover
                    pass
            _TRACE_FILE = open(trace_path(), "a")
            _TRACE_DIR_SEEN = d
        # chrome's JSON Array Format: "[" then comma-separated events; the
        # closing "]" is explicitly optional, so an append-only stream
        # stays loadable after a crash (read_trace normalizes). ONE write
        # call per event, leader + record + newline fused, and the whole
        # emit serialized under _TRACE_LOCK: concurrent span exits from N
        # threads can never interleave partial JSON lines, and a reader
        # sees whole lines (plus at most one torn tail mid-flush, which
        # read_trace drops)
        ldr = "[\n" if _TRACE_FILE.tell() == 0 else ",\n"
        _TRACE_FILE.write(ldr + line)
        _TRACE_FILE.flush()


def read_trace(path: str) -> list[dict]:
    """Load a chrome-trace export back as a list of event dicts.

    Normalizes what the streaming writer legitimately leaves: the missing
    closing bracket, a trailing comma, and — when read while a writer is
    mid-flush or after a crash tore the tail — an incomplete final record,
    which is dropped rather than failing the whole load (the flight
    recorder and the fleet trace merge both read live files)."""
    with open(path) as f:
        text = f.read().rstrip().rstrip(",")
    if not text:
        return []
    try:
        return json.loads(text if text.endswith("]") else text + "\n]")
    except json.JSONDecodeError:
        pass
    # torn tail: every complete record is one ",\n"-led line — reparse
    # line-wise and drop whatever the crash/in-flight write left behind
    out = []
    for ln in text.lstrip("[").split(",\n"):
        ln = ln.strip().rstrip(",").rstrip("]").strip()
        if not ln:
            continue
        try:
            out.append(json.loads(ln))
        except json.JSONDecodeError:
            continue
    return out


# ---------------------------------------------------------------------------
# on-demand device profiling (jax.profiler, span-scoped)
# ---------------------------------------------------------------------------
# The ONLY sanctioned jax.profiler capture site (with fleetobs.py) —
# graftlint rule 19 `unscoped-profiler-capture` pins that: a start_trace
# grown elsewhere would skip the span annotations and could leak a
# never-stopped session. One session per process (jax's own limit); the
# span stack mirrors into TraceAnnotations while a session is live, so
# XLA ops nest under train.gbm.chunk / mrtask.dispatch in Perfetto.

#: one-element list so span()'s hot-path read is a plain load (no lock);
#: flipped only under _PROFILE_LOCK
_PROFILE_ACTIVE = [False]
_PROFILE_LOCK = threading.Lock()
_PROFILE_SEQ = itertools.count(1)


def profile_dir() -> str | None:
    """H2O_TPU_PROFILE_DIR when set — arms span-scoped device capture."""
    return knobs.get_str("H2O_TPU_PROFILE_DIR") or None


@contextlib.contextmanager
def device_profile(what: str, out_dir: str | None = None):
    """Span-scoped ``jax.profiler`` capture around the caller's region.

    Yields the capture directory (``<dir>/<what>_<pid>_<n>``), or None
    when profiling is not armed (no ``H2O_TPU_PROFILE_DIR`` and no
    explicit ``out_dir``) or another session already runs in this process
    — the caller's region executes unchanged either way. stop_trace is
    guaranteed on exit, which is the whole point of scoping captures."""
    d = out_dir or profile_dir()
    if not d:
        yield None
        return
    with _PROFILE_LOCK:
        busy = _PROFILE_ACTIVE[0]
        if not busy:
            _PROFILE_ACTIVE[0] = True
    if busy:
        # NEVER yield while holding _PROFILE_LOCK: the caller's body runs
        # at the yield point, and anything there touching the lock (the
        # capture() busy-vs-failed diagnosis does) would self-deadlock
        yield None
        return
    path = os.path.join(
        d, f"{what.replace('/', '_')}_{os.getpid()}_{next(_PROFILE_SEQ)}")
    try:
        import jax

        os.makedirs(path, exist_ok=True)
        jax.profiler.start_trace(path)
    except Exception as e:  # pragma: no cover — backend without profiler
        from . import log

        log.err(f"jax.profiler start_trace failed: {e!r}")
        with _PROFILE_LOCK:
            _PROFILE_ACTIVE[0] = False
        yield None
        return
    try:
        yield path
    finally:
        try:
            # a stop failure (backend trace-collection error) must never
            # replace the caller's in-flight exception — the flight
            # recorder would bundle the profiler's error instead of the
            # crash that matters
            try:
                jax.profiler.stop_trace()
            except Exception:  # pragma: no cover — backend quirk
                pass
            else:
                inc("profiler.capture.count")
                timeline.record("profiler", what, dir=path)
        finally:
            with _PROFILE_LOCK:
                _PROFILE_ACTIVE[0] = False


def capture(ms: int, out_dir: str | None = None) -> str:
    """Bounded LIVE capture — the ``POST /3/Profiler/capture?ms=N`` body:
    profile whatever this process is doing for ``ms`` milliseconds and
    return the capture directory. Runs on the calling (REST handler)
    thread; concurrent training/serving work is what gets captured.
    Raises ValueError when a session is already live (REST: 400)."""
    import tempfile

    ms = int(ms)
    if not 0 < ms <= 60_000:
        raise ValueError(f"capture ms must be in (0, 60000], got {ms}")
    d = out_dir or profile_dir() or tempfile.mkdtemp(
        prefix="h2o_tpu_profile_")
    with device_profile("capture", out_dir=d) as path:
        if path is None:
            # device_profile yields None for BOTH "busy" and "start_trace
            # failed" — tell the operator which one this was (a 400 'busy'
            # on a process where no session ever started sends them
            # hunting a phantom concurrent capture)
            with _PROFILE_LOCK:
                busy = _PROFILE_ACTIVE[0]
            if busy:
                raise ValueError(
                    "a profiler session is already live in this process "
                    "— one capture at a time (jax.profiler limit)")
            raise RuntimeError(
                "jax.profiler failed to start a session on this backend "
                "— see the server log for the start_trace error")
        time.sleep(ms / 1000.0)
    # the capture's device seconds by program go into the registry before
    # the path goes back (GET /3/Programs then shows them). Here and not in
    # device_profile: a caller that scopes its own capture pays for no read
    # of the dump it did not ask for
    from . import log, programs

    try:
        programs.fold_capture(path)
    except Exception as e:  # an unreadable dump: the capture still returns
        log.warn(f"profiler capture {path}: not folded into the program "
                 f"registry ({e!r:.200})")
    return path
