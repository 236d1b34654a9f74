"""Job tracking — analog of `water/Job.java` (565 LoC).

The reference Job is a keyed, DKV-resident progress/cancel handle polled by
clients via `/3/Jobs` (`water/Job.java:199-224`). Here a Job wraps a Python
worker thread; progress is a float in [0,1] updated by the running builder, stop
requests are cooperative (builders poll ``stop_requested`` between iterations —
the same contract as `Job.stop_requested()` in the reference).
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable

from .kvstore import Keyed, STORE


class JobCancelled(Exception):
    pass


class JobPreempted(Exception):
    """Cooperative chunk-boundary preemption (h2o_tpu/workload/): the
    training loop observed a preempt request at a safe boundary, force-
    checkpointed its state and unwound. Unlike a cancel, the work is NOT
    lost — ``recovery_dir`` names the checkpoint ``resume_training``
    replays to a bit-equal model once the job is re-admitted."""

    def __init__(self, job_key: str, recovery_dir: str | None):
        self.recovery_dir = recovery_dir
        super().__init__(
            f"{job_key} preempted at a chunk boundary"
            + (f" (state parked in {recovery_dir})" if recovery_dir else ""))


class JobTimeoutError(Exception):
    """Typed wall-clock expiry: raised by ``Job.join(timeout=...)`` when the
    wait runs out, and by ``Job.check_max_runtime()`` when the
    ``max_runtime_secs`` budget expires before a builder has any partial
    result worth keeping. Carries the numbers callers used to have to parse
    out of message text: ``elapsed_s`` spent vs ``budget_s`` allowed."""

    def __init__(self, what: str, elapsed_s: float, budget_s: float):
        self.elapsed_s = elapsed_s
        self.budget_s = budget_s
        super().__init__(
            f"{what}: {elapsed_s:.1f}s elapsed of a {budget_s:.1f}s budget")


#: long-lived server hygiene: XLA's compiler accumulates per-program state
#: across hundreds of DISTINCT trainings and the CPU backend has been
#: observed to destabilize under it (the test suite resets per module —
#: `tests/conftest.py`; a server process needs the same bound). A compiled
#: program is otherwise the process's to keep (`gbm._AOT_STEP_CACHE`,
#: `glm._kept`, `programs.Tracked`): a job of shapes it has trained before
#: builds nothing. So what is counted is jobs that BUILT a program, told by
#: the compile-path counter having moved since the last job finished (real
#: compilations and persistent-cache replays alike): after every
#: H2O_TPU_CLEAR_CACHES_EVERY of them (default 64, 0 disables) the process
#: drops every compiled program it holds — they are re-derivable, so the
#: only cost is a reload on reuse. A server that replays the same programs
#: accumulates nothing and never sweeps; one that trains ever-different
#: shapes sweeps at every 64th job.
_jobs_built = 0       # finished jobs that entered the compile path
_compiles_seen = 0    # compilemeter.count() when the last job finished
_jobs_lock = threading.Lock()


def _note_job_finished() -> None:
    global _jobs_built, _compiles_seen
    from ..utils import compilemeter, telemetry
    from ..utils.knobs import raw

    # set-but-empty means DISABLED (int("" or 0) == 0 historically) — raw
    # keeps the unset/empty distinction get_int deliberately collapses
    every = int(raw("H2O_TPU_CLEAR_CACHES_EVERY", 64) or 0)
    if every <= 0:
        return
    seen = compilemeter.count()
    with _jobs_lock:
        built, _compiles_seen = seen != _compiles_seen, seen
        if not built:
            return
        _jobs_built += 1
        due = _jobs_built % every == 0
    if due:
        import gc

        import jax

        # the AOT train-step executables are held DIRECTLY (not through a
        # jit cache), so jax.clear_caches() alone cannot release them —
        # drop the dict first or the per-program XLA state this bound
        # exists for re-accumulates through the AOT path. sys.modules
        # lookup, not an import: a process that never trained trees has
        # nothing to clear and must not pull the models stack in here.
        import sys as _sys

        gbm_mod = _sys.modules.get("h2o_tpu.models.gbm")
        if gbm_mod is not None:
            gbm_mod._AOT_STEP_CACHE.clear()
        # the sharded merge-expand programs are likewise held directly,
        # keyed by data-dependent output sizes — a long server joining
        # ever-different frames would otherwise accumulate executables
        merge_mod = _sys.modules.get("h2o_tpu.rapids.merge")
        if merge_mod is not None:
            merge_mod._EXPAND_PROGS.clear()
        # Tracked program wrappers (utils/programs.py) hold their compiled
        # executables directly too — same invisibility to jax.clear_caches
        # as the AOT caches above; records (pure numbers) survive, the
        # executables recompile on next dispatch
        prog_mod = _sys.modules.get("h2o_tpu.utils.programs")
        if prog_mod is not None:
            prog_mod.clear_compiled()
        # the GLM's kept IRLS steps and probes: the store goes too, so a
        # family no job trains any more leaves nothing behind
        glm_mod = _sys.modules.get("h2o_tpu.models.glm")
        if glm_mod is not None:
            glm_mod.drop_kept_programs()
        gc.collect()
        jax.clear_caches()
        from ..utils.log import info

        telemetry.inc("jobs.cache_sweeps")
        info(f"cleared XLA compilation caches after {_jobs_built} jobs that "
             "built a program (H2O_TPU_CLEAR_CACHES_EVERY)")


class Job(Keyed):
    CREATED = "CREATED"
    RUNNING = "RUNNING"
    DONE = "DONE"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"
    PREEMPTED = "PREEMPTED"

    #: priority classes, strongest first — the reference's H2O.submitTask
    #: priority queues collapsed to four lanes. The workload manager's
    #: lottery weights and arrival-preemption both key off the ordinal.
    PRIORITIES = ("realtime", "interactive", "batch", "background")

    def __init__(self, description: str = "", work: float = 1.0, dest_key: str | None = None):
        from ..utils import sanitizer

        super().__init__(prefix="job")
        self.description = description
        self.dest_key = dest_key
        # status/result/exception/progress are written by the worker
        # thread and read by pollers (REST /3/Jobs, join, progress) — one
        # lock makes every transition atomic and publishes result+status
        # together (graftlint unguarded-shared-field GL14-job-state)
        self._lock = sanitizer.make_lock("Job._state")
        self.status = Job.CREATED
        self.exception: BaseException | None = None
        self.traceback: str | None = None
        self._work_total = max(work, 1e-12)
        self._worked = 0.0
        self.progress_msg = ""
        self.start_time = 0.0
        self.end_time = 0.0
        self._stop_requested = False
        self._thread: threading.Thread | None = None
        self.result: Any = None
        #: workload-manager identity (h2o_tpu/workload/): which tenant
        #: owns this job and which priority lane it dispatches under.
        #: Stamped at submit; "default"/"batch" for legacy direct starts.
        self.tenant = "default"
        self.priority = "batch"
        #: True once the builder armed auto-recovery — only then can a
        #: preempt request be honored without losing work
        self.preemptible = False
        self._preempt_requested = False
        #: checkpoint dir captured when a preemption lands (the resume
        #: handle /3/Jobs pollers and the manager read back)
        self.preempt_dir: str | None = None
        #: last progress heartbeat (wall clock) — refreshed by update()
        #: and check_cancelled(), i.e. at every chunk/epoch boundary; the
        #: watchdog's hung-job detector and /3/Health's job check read it
        self.last_beat = time.time()
        STORE.put_keyed(self)

    def beat(self) -> None:
        """Mark forward progress (hung-job watchdog heartbeat)."""
        with self._lock:
            self.last_beat = time.time()

    # -- lifecycle -----------------------------------------------------------
    def start(self, fn: Callable[[], Any], background: bool = True) -> "Job":
        def _run():
            # job transitions are timeline events, like the reference's
            # TimeLine records of task start/finish packets. State writes
            # happen under the lock; the builder fn and the timeline
            # emits run OUTSIDE it (fn holds the lock for nothing, and
            # blocking-under-lock stays clean).
            from ..utils import timeline

            with self._lock:
                self.status = Job.RUNNING
                self.start_time = self.last_beat = time.time()
            timeline.record("job", "start", job=str(self.key),
                            desc=self.description)
            try:
                result = fn()
                with self._lock:
                    self.result = result
                    self.status = (Job.CANCELLED if self._stop_requested
                                   else Job.DONE)
            except JobCancelled:
                with self._lock:
                    self.status = Job.CANCELLED
            except JobPreempted as e:
                # not a failure: the boundary checkpointed, the workload
                # manager parks the entry and resumes it bit-equal later
                with self._lock:
                    self.preempt_dir = e.recovery_dir
                    self.status = Job.PREEMPTED
            except BaseException as e:  # noqa: BLE001 - mirror of Job exception capture
                with self._lock:
                    self.exception = e
                    self.traceback = traceback.format_exc()
                    self.status = Job.FAILED
            finally:
                with self._lock:
                    self.end_time = time.time()
                    status = self.status
                    run_s = round(self.end_time - self.start_time, 3)
                timeline.record("job", status, job=str(self.key),
                                run_s=run_s)
                _note_job_finished()

        if background:
            from ..utils import telemetry

            # the worker thread adopts the SUBMITTER's span context
            # (captured here, in the REST handler / caller thread), so a
            # background training job's spans nest under the request that
            # started it instead of minting an orphan trace id
            self._thread = threading.Thread(
                target=telemetry.carry_context(_run), daemon=True,
                name=self.key)
            self._thread.start()
        else:
            _run()
        return self

    def join(self, timeout: float | None = None) -> Any:
        """Wait for the job. A bounded wait that runs out raises the typed
        ``JobTimeoutError`` (the job keeps running — this is the WAIT's
        budget) instead of the old behavior of silently returning None with
        the job still live."""
        if self._thread is not None:
            self._thread.join(timeout)
            if timeout is not None and self._thread.is_alive():
                with self._lock:
                    status = self.status
                raise JobTimeoutError(
                    f"join on {self.key} ({self.description!r}) timed out "
                    f"with the job still {status}",
                    elapsed_s=self.run_time, budget_s=timeout)
        with self._lock:  # status+exception+result publish atomically
            status, exc, result = self.status, self.exception, self.result
        if status == Job.FAILED and exc is not None:
            raise exc
        return result

    # -- progress / cancel ---------------------------------------------------
    @property
    def progress(self) -> float:
        with self._lock:
            if self.status == Job.DONE:
                return 1.0
            return min(1.0, self._worked / self._work_total)

    def update(self, worked: float, msg: str = "") -> None:
        with self._lock:
            self._worked += worked
            self.last_beat = time.time()
            if msg:
                self.progress_msg = msg

    def stop(self) -> None:
        """Request cooperative cancellation (`Job.stop_requested` contract)."""
        with self._lock:
            self._stop_requested = True

    deadline: float | None = None     # wall-clock expiry (max_runtime_secs)
    max_runtime_s: float | None = None  # the armed budget, for typed errors

    def set_max_runtime(self, secs: float) -> None:
        """Arm the per-model time budget (`Model.Parameters.max_runtime_secs`
        — the reference stops training and keeps the partial model)."""
        if secs and secs > 0:
            self.max_runtime_s = float(secs)
            self.deadline = time.time() + secs

    def time_exceeded(self) -> bool:
        """Iterative builders poll this between iterations and BREAK (keeping
        the partial model), unlike check_cancelled which unwinds."""
        return self.deadline is not None and time.time() > self.deadline

    def check_max_runtime(self) -> None:
        """The typed sibling of ``time_exceeded`` for call sites with NO
        partial result to keep: an expired budget raises ``JobTimeoutError``
        (elapsed vs budget attached) instead of letting the build run
        arbitrarily past its contract before the first keepable iteration."""
        if self.time_exceeded():
            raise JobTimeoutError(
                f"job {self.key} ({self.description!r}) exceeded "
                f"max_runtime_secs before producing a keepable result",
                elapsed_s=self.run_time,
                budget_s=self.max_runtime_s or 0.0)

    @property
    def stop_requested(self) -> bool:
        with self._lock:
            return self._stop_requested

    # -- preemption (h2o_tpu/workload/) --------------------------------------
    def request_preempt(self) -> None:
        """Ask the running builder to yield at its NEXT chunk/epoch
        boundary (model_base._recovery_tick polls this). Cooperative like
        stop(), but the builder checkpoints and raises ``JobPreempted``
        instead of discarding work. A no-op on non-preemptible jobs —
        the boundary poll ignores the flag when no recovery is armed."""
        with self._lock:
            self._preempt_requested = True

    @property
    def preempt_requested(self) -> bool:
        with self._lock:
            return self._preempt_requested

    def clear_preempt(self) -> None:
        with self._lock:
            self._preempt_requested = False

    def check_cancelled(self) -> None:
        """Builders call this between iterations; raises to unwind the
        driver. Doubling as the heartbeat: reaching a cancellation poll
        IS forward progress, so every chunk/epoch boundary refreshes
        ``last_beat`` without a second instrumentation site."""
        self.beat()
        if self.stop_requested:
            raise JobCancelled(self.key)

    @property
    def run_time(self) -> float:
        with self._lock:
            end = self.end_time or time.time()
            return end - self.start_time if self.start_time else 0.0

    def is_running(self) -> bool:
        with self._lock:
            return self.status in (Job.CREATED, Job.RUNNING)


def any_running() -> bool:
    """True when any job is live — `/3/SteamMetrics` reports zero idle time
    while the cluster is working (`water/api/SteamMetricsHandler`)."""
    from .kvstore import STORE

    return any(j.is_running() for j in STORE.values(Job))
