"""Central registry of every ``H2O_TPU_*`` environment knob.

The reference keeps its expert properties behind one reflective surface
(`water/H2O.OptArgs` + `sys.ai.h2o.*` system properties); this repo grew the
same knobs ad hoc — `os.environ.get("H2O_TPU_...")` scattered through the
runtime, each with its own inline default and no single place a reader (or a
linter) can ask "what knobs exist and what do they do". This module is that
place: every knob is declared HERE with its name, type, default, and one-line
docstring, and graftlint's ``unregistered-knob`` rule fails the build on any
literal ``H2O_TPU_*`` environment read whose name is not declared below
(`tools/graftlint/rules.py` parses this file's AST — no import needed).

Reads stay dynamic: accessors consult ``os.environ`` at call time, so tests
that monkeypatch the environment keep working, and `utils/optargs.py`'s
"CLI > env > default, exported back to env" contract is untouched — this
registry documents and types the env surface, it does not cache it.

Accessors:

- ``raw(name, default=None)``  — exact ``os.environ.get`` semantics (string
  or the given default), plus the registration check. The graftlint
  ``--fix`` rewrite targets this: ``os.environ.get("H2O_TPU_X", d)`` →
  ``knobs.raw("H2O_TPU_X", d)`` is behavior-preserving.
- ``get_str/get_int/get_bool(name, default=...)`` — typed reads falling back
  to the REGISTERED default when the variable is unset/empty (an explicit
  ``default=`` overrides the registered one).

Every accessor raises ``KeyError`` for an undeclared name, so a new knob
cannot ship without a registry line (the same invariant the linter enforces
statically).
"""

from __future__ import annotations

import dataclasses
import os

_MISSING = object()

#: strings that read as False for bool knobs (superset of the historic
#: per-site spellings: BINNED_STORE used {0,false,off}, ALLOW_WIRE_UDF
#: {0,false}). Set-but-EMPTY is handled as UNSET, not falsy — a stale
#: `export VAR=` line must not silently flip BINNED_STORE/ALLOW_WIRE_UDF
#: off (their pre-registry reads defaulted "" to on).
_FALSY = ("0", "false", "off", "no")


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    kind: str           # "str" | "int" | "bool"
    default: object
    doc: str


KNOBS: dict[str, Knob] = {}


def _knob(name: str, kind: str, default, doc: str) -> None:
    KNOBS[name] = Knob(name, kind, default, doc)


# -- launcher / runtime (mirrors utils/optargs.py, the CLI surface) ---------
_knob("H2O_TPU_REST_PORT", "int", 54321,
      "REST API port (optargs --port)")
_knob("H2O_TPU_DRIVER", "str", "",
      "python module run as the multi-host SPMD driver instead of REST")
_knob("H2O_TPU_ASSISTED_CLUSTERING", "bool", False,
      "start the clustering sidecar API before touching any JAX backend")
_knob("H2O_TPU_ASSISTED_CLUSTERING_API_PORT", "int", 8080,
      "port for the assisted-clustering sidecar API")
_knob("H2O_TPU_PROCESS_ID", "int", 0,
      "this process's rank when joining an assisted-clustering cloud")
_knob("H2O_TPU_ICE_DIR", "str", "",
      "spill directory for the HBM Cleaner and NodePersistentStorage")
_knob("H2O_TPU_NPS_DIR", "str", "",
      "NodePersistentStorage root (default: <ice>/nps)")

# -- memory / frames --------------------------------------------------------
_knob("H2O_TPU_HBM_LIMIT_BYTES", "int", 0,
      "pin the Cleaner/planner HBM budget exactly (0/unset = backend "
      "resolution: memory_stats bytes_limit; an error on a TPU that "
      "reports none, unlimited on CPU)")
_knob("H2O_TPU_MAX_FRAME_BYTES", "int", 12 * 1024 ** 3,
      "refuse parses whose f32 frame would exceed this (FrameSizeMonitor)")
_knob("H2O_TPU_BINNED_STORE", "bool", True,
      "train trees from the chunk store's int8/int16 binned view instead "
      "of the stacked f32 matrix (frame/chunks.py); 0 reverts")
_knob("H2O_TPU_ROW_SHARDS", "int", 0,
      "row shards of the lazily-built default mesh (parallel/mesh.py): "
      "how many devices split the data-parallel 'rows' axis; 0/unset = "
      "all devices (the historic default). Read ONCE at mesh "
      "construction — set it before any frame is placed")
_knob("H2O_TPU_SHARDED_MERGE", "bool", True,
      "run the rapids merge expansion phase-2 sharded over the mesh rows "
      "axis (explicit per-shard delta-scatter+cumsum fills inside "
      "shard_map, rapids/merge.py); 0 reverts to the replicated oracle "
      "the sharded path is bit-parity-pinned against")

# -- engine knobs -----------------------------------------------------------
_knob("H2O_TPU_EXACT_BIN_ROWS", "int", 16384,
      "rows at or below which tree binning may use exact small-data cuts")
_knob("H2O_TPU_HIST_SEG_WIDTH", "int", 8,
      "bin widths at/below this accumulate via segment-sum instead of the "
      "one-hot matmul in the histogram scan (0 disables the path; "
      "backend/kernels/hist.py)")
_knob("H2O_TPU_ASYNC_PSUM", "bool", True,
      "overlapped per-level histogram reduction: each width bucket's ICI "
      "psum is issued before the next bucket's local scan so the "
      "collective hides under compute; 0 reverts to the PR 10 shape "
      "(one joint scan, psums after). Bit-equal either way")
_knob("H2O_TPU_CLEAR_CACHES_EVERY", "int", 64,
      "drop live XLA executables every N finished jobs that built a "
      "program (entered the compile path; long-server hygiene; 0 = never)")
_knob("H2O_TPU_PDP_BATCH_ROWS", "int", 2_000_000,
      "row budget per batched partial-dependence predict")

# -- serving (h2o_tpu/serving/ online scoring runtime) ----------------------
_knob("H2O_TPU_SERVING_BUCKETS", "str", "1,4,16,64,128,256,512",
      "comma list of padded-batch bucket sizes the serving scorer "
      "AOT-compiles at registration; requests pad up to the smallest "
      "bucket that fits, larger batches chunk through the biggest. "
      "Dense x2 steps at the top where scoring is linear in rows and "
      "padding waste costs real milliseconds; x4 at the bottom where "
      "dispatch overhead dominates (measured: a 119-row batch scores "
      "3.6 ms at bucket 128 vs 20 ms padded to 512)")
_knob("H2O_TPU_SERVING_MAX_BATCH", "int", 512,
      "most rows the micro-batcher coalesces into one device call "
      "(effectively clamped to the largest compiled bucket)")
_knob("H2O_TPU_SERVING_MAX_WAIT_US", "int", 2000,
      "how long the micro-batch worker holds the first queued request "
      "open for coalescing before dispatching a partial batch (0 = "
      "dispatch immediately, no coalescing window)")
_knob("H2O_TPU_SERVING_QUEUE_DEPTH", "int", 1024,
      "bounded request-queue depth per served model; submits beyond it "
      "are rejected with QueueFullError (REST: 429 + Retry-After)")
_knob("H2O_TPU_SERVING_DEADLINE_MS", "int", 1000,
      "default per-request deadline; a request still queued past it "
      "raises DeadlineExceededError (REST: 408); 0 = no deadline")
_knob("H2O_TPU_SERVING_STATS_WINDOW", "int", 2048,
      "ring-buffer length of the per-model latency/throughput window "
      "behind GET /3/Serving/stats")

# -- serving control plane (h2o_tpu/serving/control.py + router.py) ----------
_knob("H2O_TPU_SERVING_QUOTA_FRACTION", "str", "0.35",
      "fraction of the resolved Cleaner HBM budget the serving fleet may "
      "reserve for placed models (admission rejects registrations beyond "
      "it with 429 + Retry-After); models place unlimited when no budget "
      "resolves (CPU without H2O_TPU_HBM_LIMIT_BYTES)")
_knob("H2O_TPU_SERVING_PRIORITY", "str", "hot",
      "default placement priority class for registrations that don't pass "
      "one: 'hot' pins residency (never evicted while registered), 'cold' "
      "is evictable under quota pressure and lazily re-placed — paying "
      "its bucket compiles again — on first hit")
_knob("H2O_TPU_SERVING_REPLICAS", "int", 1,
      "default replica scorers per registration; replicas are placed "
      "round-robin across mesh devices and dispatched least-loaded by "
      "live batcher queue depth")
_knob("H2O_TPU_SERVING_ROUTE_SEED", "int", 42,
      "default seed for a route's deterministic weighted split when the "
      "route doesn't carry its own (same seed + same request order = "
      "exactly the same variant sequence)")
_knob("H2O_TPU_SERVING_SHADOW", "bool", True,
      "master switch for shadow traffic: 0 skips off-response-path "
      "shadow scoring (and divergence stats) even on routes that "
      "configure shadow variants")
_knob("H2O_TPU_CLIENT_KEEPALIVE", "bool", True,
      "pool one persistent HTTP connection per client thread "
      "(api/client.py), auto-reconnecting on a stale socket; 0 reverts "
      "to one connection per request (the serving_wire bench baseline)")

# -- runtime sanitizers (utils/sanitizer.py) ---------------------------------
_knob("H2O_TPU_SANITIZE", "str", "",
      "comma list of runtime sanitizer modes (utils/sanitizer.py): "
      "'locks' = instrumented lock wrappers that track per-thread "
      "acquisition stacks + the global lock-order graph and raise a "
      "typed LockOrderViolation on an OBSERVED inversion; 'guards' = "
      "@guarded_by('_lock') assertions on lock-protected methods; "
      "'transfers' = jax transfer guards scoped over the hot sections "
      "(train chunk dispatch, MRTask dispatch, serving score path, "
      "Cleaner sweep) raising a typed TransferGuardViolation on an "
      "implicit device->host conversion; 'recompiles' = any uncached "
      "XLA compile inside a declared-steady section (GBM post-first-"
      "boundary, serving post-registration) raises a typed "
      "SteadyStateCompileError. locks are consulted at construction — "
      "build the runtime after setting it; empty = zero overhead")

# -- fault tolerance (failpoints / auto-checkpoints / retry) ----------------
_knob("H2O_TPU_FAILPOINTS", "str", "",
      "comma list of site:spec deterministic fault injections "
      "(utils/failpoints.py — spec grammar: action[(arg)][*N|@K], action "
      "in raise|sleep|http); empty = nothing armed")
_knob("H2O_TPU_CHECKPOINT_SECS", "int", 600,
      "wall-clock seconds between auto-recovery checkpoints while a "
      "training job with auto_recovery_dir runs; 0 = checkpoint at every "
      "iteration boundary (the kill-resume parity tests pin this)")
_knob("H2O_TPU_AUTO_RECOVERY_DIR", "str", "",
      "base auto-recovery directory armed for every training job whose "
      "params leave auto_recovery_dir unset (preemption-proof by default "
      "on preemptible pools); each job checkpoints into its own "
      "<algo>_<pid>_<jobkey> subdirectory so overlapping jobs never "
      "clobber each other — resume_training takes that subdir; empty = off")
_knob("H2O_TPU_RETRY_ATTEMPTS", "int", 4,
      "total tries (first call + retries) utils/retry.py allows before "
      "raising the typed RetryBudgetExceeded")
_knob("H2O_TPU_RETRY_BASE_MS", "int", 100,
      "first-retry backoff in ms; doubles per retry (full jitter unless "
      "H2O_TPU_RETRY_JITTER=0)")
_knob("H2O_TPU_RETRY_MAX_MS", "int", 5000,
      "backoff cap in ms — also caps server-directed Retry-After sleeps")
_knob("H2O_TPU_RETRY_BUDGET_MS", "int", 20000,
      "wall-clock retry budget in ms; exceeded -> RetryBudgetExceeded "
      "even with attempts left")
_knob("H2O_TPU_RETRY_JITTER", "bool", True,
      "0 pins backoff to the deterministic cap sequence (tests); default "
      "full jitter so a fleet never thunders back in lockstep")

# -- observability (utils/telemetry.py + timeline.py) ------------------------
_knob("H2O_TPU_METRICS_ENABLED", "bool", True,
      "master switch for the telemetry registry + span/timeline/trace "
      "recording, including every direct timeline.record site (always-on "
      "by default, like the reference's TimeLine ring; 0 skips the writes "
      "but keeps name validation)")
_knob("H2O_TPU_METRICS_HIST_WINDOW", "int", 1024,
      "observations kept per histogram metric (read at import) — "
      "percentiles in /3/Metrics describe this recent window, memory "
      "stays bounded")
_knob("H2O_TPU_TIMELINE_EVENTS", "int", 4096,
      "capacity of the /3/Timeline event ring (read at import; the "
      "reference's TimeLine keeps 2048)")
_knob("H2O_TPU_TRACE_DIR", "str", "",
      "directory for per-process chrome-tracing span exports "
      "(trace_<pid>.trace.json, loadable in Perfetto); empty = off")

# -- fleet observability plane (programs / profiler / fleetobs / flightrec) --
_knob("H2O_TPU_PROFILE_DIR", "str", "",
      "arm span-scoped jax.profiler device capture: training jobs wrap "
      "their root span in a bounded profiler session written under this "
      "directory, and the live span stack mirrors into TraceAnnotations "
      "so XLA ops nest under the telemetry span names in Perfetto "
      "(utils/telemetry.py device_profile — the only sanctioned capture "
      "site, graftlint rule unscoped-profiler-capture); empty = off")
_knob("H2O_TPU_FLIGHT_DIR", "str", "",
      "crash flight-recorder bundle directory (utils/flightrec.py): "
      "typed terminal events (device OOM after emergency sweep, "
      "LockOrderViolation, unhandled train/serving crash, the armed "
      "flightrec.dump drill failpoint) write an atomic diagnostics "
      "bundle — metrics/timeline/logs/thread-dump/Cleaner ledger/program "
      "registry/knobs — here; empty = off")
_knob("H2O_TPU_FLIGHT_MAX_BUNDLES", "int", 32,
      "most flight bundles kept in H2O_TPU_FLIGHT_DIR before the oldest "
      "are reaped (a crash storm must not fill the disk)")
_knob("H2O_TPU_FLEET_PEERS", "str", "",
      "comma list of peer-process /3/Metrics endpoints "
      "(host:port or full http:// URLs) the fleet collector scrapes for "
      "GET /3/Metrics?fleet=1 (utils/fleetobs.py); empty = self (+ spool)")
_knob("H2O_TPU_FLEET_SPOOL", "str", "",
      "shared spool directory where non-HTTP processes (bench "
      "subprocesses, batch workers) drop metric snapshots "
      "(fleetobs.write_spool) for the fleet merge; empty = off")
_knob("H2O_TPU_FLEET_TIMEOUT_MS", "int", 500,
      "per-peer scrape timeout for the fleet collector — one slow/dead "
      "replica bounds, not blocks, the merged view")
_knob("H2O_TPU_FLEET_SPOOL_MAX_AGE_MS", "int", 900_000,
      "spool snapshots older than this (file mtime) are reported stale "
      "instead of merged — a dead process's last snapshot must not sum "
      "into the fleet totals forever (0 = no cutoff)")
_knob("H2O_TPU_FLEET_INTERVAL_MS", "int", 0,
      "minimum ms between live fleet scrapes; within the window "
      "GET /3/Metrics?fleet=1 serves the cached merge (0 = scrape on "
      "every request)")

# -- causal observability plane (slo / watchdog / slowtrace / health) --------
_knob("H2O_TPU_SLO", "str", "",
      "per-deployment SLO overrides (utils/slo.py) as comma-separated "
      "'<slo>.p99_ms=<ms>' / '<slo>.error_budget=<frac>' pairs, e.g. "
      "'serving.score.p99_ms=50,rest.request.error_budget=0.05'; "
      "undeclared SLO names raise KeyError (the knobs discipline); "
      "empty = the declared defaults")
_knob("H2O_TPU_SLO_WINDOW_S", "int", 300,
      "rolling window (seconds) the SLO error-burn rate is computed "
      "over; latency burn rides the telemetry histogram rings' own "
      "H2O_TPU_METRICS_HIST_WINDOW observation window")
_knob("H2O_TPU_SLOWTRACE_KEEP", "int", 64,
      "slow-request capture ring size (utils/slowtrace.py): how many "
      "SLO-p99-breaching requests keep their full span tree + program "
      "dispatch walls behind GET /3/SlowTraces (newest win)")
_knob("H2O_TPU_SLOWTRACE_MIN_MS", "int", 0,
      "floor for slow-request capture: requests faster than this never "
      "persist even when their SLO p99 target is lower (a deliberately "
      "tight test SLO must not flood the ring in production); 0 = the "
      "SLO targets alone decide")
_knob("H2O_TPU_WATCHDOG_MS", "int", 0,
      "watchdog supervisor sweep interval (utils/watchdog.py): every "
      "interval one thread checks for hung jobs, stalled MRTask "
      "dispatch, Cleaner spill/rehydrate thrash and serving queue "
      "stalls, each trip landing a typed timeline event + Prometheus "
      "gauge + proactive flight bundle; 0 = disarmed (no thread)")
_knob("H2O_TPU_WATCHDOG_JOB_BUDGET_MS", "int", 120_000,
      "a RUNNING job whose progress heartbeat (Job.beat — fed by every "
      "update/check_cancelled at chunk/epoch boundaries) is older than "
      "this trips the hung-job detector")
_knob("H2O_TPU_WATCHDOG_DISPATCH_BUDGET_MS", "int", 60_000,
      "an MRTask driver dispatch in flight longer than this trips the "
      "mrtask-stall detector (parallel/mrtask.py in-flight table)")
_knob("H2O_TPU_WATCHDOG_QUEUE_BUDGET_MS", "int", 10_000,
      "a serving batcher whose OLDEST queued request has waited longer "
      "than this trips the queue-stall detector (worker wedged or "
      "paused under live traffic)")
_knob("H2O_TPU_WATCHDOG_THRASH_OPS", "int", 16,
      "Cleaner spill AND rehydrate counters both advancing more than "
      "this within one watchdog interval trips the cleaner-thrash "
      "detector (evict/reload churn — the memory death spiral)")
_knob("H2O_TPU_HEALTH_HEADROOM_PCT", "int", 5,
      "GET /3/Health reports cleaner-headroom degradation when free HBM "
      "under the resolved budget (Cleaner live bytes + the serving "
      "reservation ledger both debited) falls below this percent")
_knob("H2O_TPU_HEALTH_QUEUE_PCT", "int", 80,
      "GET /3/Health reports serving-queue-saturation when any served "
      "model's live queue depth reaches this percent of its bounded "
      "capacity (the router should spray elsewhere BEFORE 429s start)")
_knob("H2O_TPU_HEALTH_BURN_MAX", "int", 10,
      "GET /3/Health reports slo-burn degradation when any declared "
      "SLO's burn rate exceeds this multiple of its error budget "
      "(burn 1.0 = exactly consuming the budget)")

# -- workload manager (h2o_tpu/workload/) ------------------------------------
_knob("H2O_TPU_TENANT", "str", "",
      "tenant this process submits work as (workload/tenants.py); the "
      "client attaches it as the X-H2O-TPU-Tenant request header, the "
      "server scopes each request's jobs/quota to it; empty = the "
      "'default' tenant (legacy single-tenant callers)")
_knob("H2O_TPU_WORKLOAD_SLOTS", "int", 0,
      "concurrent managed jobs the workload manager dispatches "
      "(workload/manager.py); excess submissions queue and drain under "
      "weighted fair-share, and preempted jobs auto-resume when a slot "
      "frees; 0 = unmanaged (every submit dispatches immediately — the "
      "legacy single-tenant behavior, no queueing, no auto-resume)")
_knob("H2O_TPU_WORKLOAD_SEED", "int", 42,
      "seed for the fair-share dispatch lottery (splitmix64, the PR 8 "
      "router construction) — same seed + same submission sequence = "
      "same dispatch order")
_knob("H2O_TPU_WORKLOAD_AGING", "int", 8,
      "starvation bound for the fair-share lottery: an entry that loses "
      "this many consecutive drawings is force-dispatched next, so the "
      "worst-case queue delay is bounded deterministically")
_knob("H2O_TPU_WORKLOAD_QUOTA", "str", "",
      "per-tenant HBM quota fractions as comma-separated "
      "'<tenant>=<frac>' pairs (e.g. 'team-a=0.5,team-b=0.25'); each "
      "fraction is taken of backend/memory.py base_hbm_limit_bytes() "
      "and debited through the one reservation ledger; unlisted tenants "
      "are unlimited; ignored entirely when no HBM budget resolves")
_knob("H2O_TPU_WORKLOAD_TICK_MS", "int", 1000,
      "workload maintenance cadence: how often the manager re-pumps the "
      "queue, re-admits parked (preempted) jobs and evaluates the "
      "SLO/health shed policy while managed work exists")
_knob("H2O_TPU_WORKLOAD_SHED_BURN", "int", 0,
      "shed policy trigger: when slo.worst_burn exceeds this multiple "
      "(or /3/Health degrades with cleaner-headroom / "
      "serving-queue-saturation), the highest-pressure tenant's lowest-"
      "priority running job is preempted at its next boundary; 0 = "
      "shed only on typed health degradation, never on burn alone")
_knob("H2O_TPU_WORKLOAD_RETRY_S", "int", 5,
      "seconds a shed (load-shed, not priority-preempted) job stays "
      "parked before re-admission is considered; also the Retry-After "
      "hint on 429 quota rejections")
_knob("H2O_TPU_WORKLOAD_DISPATCH_SLOTS", "int", 0,
      "concurrent MRTask driver dispatches allowed across tenants "
      "(workload/fairshare.py gate at parallel/mrtask.py _dispatch); "
      "waiters wake in weighted-fair order (lowest virtual time first); "
      "0 = ungated (the single-tenant default)")

# -- security ---------------------------------------------------------------
_knob("H2O_TPU_ALLOW_WIRE_UDF", "bool", True,
      "allow python: UDF references uploaded over the wire to execute")

# -- external systems -------------------------------------------------------
_knob("H2O_TPU_WEBHDFS_URL", "str", "",
      "explicit WebHDFS endpoint for hdfs:// persist")
_knob("H2O_TPU_WEBHDFS_PORT", "int", 9870,
      "WebHDFS port when hdfs:// URIs carry none")
_knob("H2O_TPU_HDFS_USER", "str", "",
      "user.name forwarded to WebHDFS (default: $USER)")
_knob("H2O_TPU_HIVE_JDBC", "str", "",
      "Hive JDBC endpoint for ImportHiveTable")

# -- test harness -----------------------------------------------------------
_knob("H2O_TPU_KEY_STRICT", "bool", False,
      "fail tests on leaked KVStore keys instead of reaping them")


def _lookup(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"unregistered knob {name!r} — declare it in "
            f"h2o_tpu/utils/knobs.py (graftlint rule unregistered-knob "
            f"enforces the same statically)") from None


def raw(name: str, default=None):
    """``os.environ.get`` semantics with the registration check: the raw
    string when set, else ``default`` untouched (NOT the registered
    default — this is the drop-in target for graftlint --fix rewrites)."""
    _lookup(name)
    return os.environ.get(name, default)


def get_str(name: str, default=_MISSING) -> str:
    """SET wins, even when set to the empty string — an exported-but-empty
    string knob means "nothing", not "give me the default"."""
    k = _lookup(name)
    v = os.environ.get(name)
    if v is not None:
        return v
    return k.default if default is _MISSING else default


def get_int(name: str, default=_MISSING) -> int:
    """Unset OR empty falls back to the default (there is no useful int
    reading of ""); sites that need set-but-empty to mean 0/disabled read
    through ``raw`` and keep their own coercion."""
    k = _lookup(name)
    v = os.environ.get(name)
    if v not in (None, ""):
        return int(v)
    d = k.default if default is _MISSING else default
    return d if d is None else int(d)


def get_bool(name: str, default=_MISSING) -> bool:
    k = _lookup(name)
    v = os.environ.get(name)
    if v is None or v.strip() == "":
        d = k.default if default is _MISSING else default
        return bool(d)
    return v.strip().lower() not in _FALSY


def describe() -> str:
    """Human-readable registry dump (the `printHelp` analog for env knobs)."""
    lines = []
    for k in sorted(KNOBS.values(), key=lambda k: k.name):
        lines.append(f"{k.name}  [{k.kind}, default {k.default!r}]")
        lines.append(f"    {k.doc}")
    return "\n".join(lines)
