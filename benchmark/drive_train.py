"""Driver of the ``train_back_to_back`` traffic kind: training jobs over
REST from one client, back to back, through the entry points a user calls
(``h2o.init()`` in-process server, estimators of ``h2o_tpu.api``)."""

from __future__ import annotations

import importlib
import logging
import os
import shutil
import socket
import sys
import threading
import time

import numpy as np

from . import datagen, manifest, readers, trace_reduce, window, work_counts

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class WarningList(logging.Handler):
    """Every WARNING of the ``h2o_tpu`` logger: the main path must be the
    one that ran."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages: list = []

    def emit(self, record):
        self.messages.append(record.getMessage())


class _Slice:
    """A bounded slice of the window under the profiler, in a thread of its
    own: from ``start()`` until ``stop()`` or ``cap_s`` seconds, whichever
    comes first. Uses the program's one capture site, which mirrors its
    open spans into the trace as annotations."""

    def __init__(self, out_dir: str, cap_s: float):
        self.out_dir, self.cap_s = out_dir, cap_s
        self._stop, self._on = threading.Event(), threading.Event()
        self.path = None
        self._th = threading.Thread(target=self._body, daemon=True)

    def _body(self):
        from h2o_tpu.utils import telemetry

        with telemetry.device_profile("bench", out_dir=self.out_dir) as path:
            self.path = path
            self._on.set()
            self._stop.wait(self.cap_s)
        self._on.set()

    def start(self):
        self._th.start()
        self._on.wait(60)

    def stop(self):
        self._stop.set()
        self._th.join()


def _harvest(model_id: str, client_metrics: dict) -> dict:
    """What a timed job returned: the model as the server holds it under
    the id the client got, and the training metrics the client received."""
    from h2o_tpu.backend.kvstore import STORE

    model = STORE.get(model_id)
    got = {"logloss": client_metrics.get("logloss"),
           "auc": client_metrics.get("AUC")}
    if hasattr(model, "forest"):
        got.update({k: np.asarray(model.forest[k])
                    for k in ("feat", "thr", "val", "gain", "nanL")})
        got["f0"] = float(np.asarray(model.f0))
    else:
        got["coef"] = {k: float(v) for k, v in model.coef().items()}
    return got


def judge(compared: dict, not_compared=()) -> bool:
    """Every number compared is finite and within its limit. A number
    named in the configuration's ``not_compared`` is printed and does not
    decide; any other number without a limit decides against."""
    return all(
        k in not_compared or (
            c["limit"] is not None and np.isfinite(c["value"])
            and c["value"] <= c["limit"])
        for k, c in compared.items())


def _digest(res: dict) -> str:
    import hashlib

    sha = hashlib.sha256()
    for k in sorted(res):
        v = res[k]
        sha.update(k.encode())
        sha.update(np.ascontiguousarray(v).tobytes() if isinstance(v, np.ndarray)
                   else repr(v).encode())
    return sha.hexdigest()


def run(*, man, cell, config, mix, args, t_setup, root, tamper=None) -> dict:
    import jax

    import h2o_tpu.api as h2o
    from h2o_tpu.backend.kvstore import STORE
    from h2o_tpu.utils import compilemeter, programs, telemetry, timeline

    warn = WarningList()
    logging.getLogger("h2o_tpu").addHandler(warn)
    compilemeter.install()
    clock = time.perf_counter
    algo, nrow = config["algo"], int(config["data"]["rows"])
    phases = {"import_h2o": clock() - t_setup}
    mark = clock()

    def phase(name):
        nonlocal mark
        phases[name], mark = clock() - mark, clock()

    h2o.init(port=_free_port())
    phase("init")
    failed = [0]
    try:
        fr, cols = datagen.GENERATORS[config["data"]["generator"]](args.seed, nrow)
        jax.block_until_ready(cols)
        phase("frame")
        train_fr = h2o.get_frame(fr.key)
        est_cls = getattr(h2o, config["estimator"])
        params = dict(config["params"], seed=int(args.seed) % (1 << 31))
        feats = list(datagen.FEATURES)

        def job(_i):
            est = est_cls(**params)
            try:
                est.train(x=feats, y=datagen.RESPONSE, training_frame=train_fr)
            except Exception as e:  # a failed job counts; it never hides
                failed[0] += 1
                print(f"job failed: {e!r}", file=sys.stderr, flush=True)
                return None
            return est.model_id, est._model._metrics()

        for i in range(int(mix.get("warmup_jobs", 1))):
            if job(i) is None:
                raise RuntimeError("warm-up job failed")
        phase("warmup")

        def irls_dispatches():
            return sum(r["dispatch_count"] for r in programs.snapshot().values()
                       if r["name"].startswith("train.glm.irls."))

        irls = [0]

        def timed_job(i):
            before = irls_dispatches()
            out = job(i)
            irls[0] += irls_dispatches() - before
            return out

        meters = {"uncached_at_window_start": compilemeter.uncached_count()}
        count0, hits0 = compilemeter.count(), compilemeter.cache_hits()
        evs = timeline.snapshot()
        seq0 = evs[-1]["seq"] if evs else 0
        spans: list = []

        def collect_spans():
            nonlocal seq0
            new = timeline.snapshot(kind="span", since=seq0)
            all_new = timeline.snapshot(since=seq0)
            if all_new:
                seq0 = all_new[-1]["seq"]
            spans.extend(e for e in new if e["what"].startswith("train"))

        def window_job(i):
            out = timed_job(i) if args.trace else job(i)
            collect_spans()
            return out

        setup_s = clock() - t_setup
        t0, t1, jobs = window.run_back_to_back(window_job, args.seconds, clock)
        mark = clock()
        # real XLA compilations in the window, and programs that went
        # through the compile path only to be replayed from the cache
        meters["replays_in_window"] = compilemeter.cache_hits() - hits0
        meters["compiles_in_window"] = (compilemeter.count() - count0
                                        - meters["replays_in_window"])
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[: cell["chips"]])
        done = [j for j in jobs if j[2] is not None]
        results = [_harvest(*j[2]) for j in done]
        # the traced slice: the first seconds of ONE MORE job, started as
        # the window closes. Inside the window the profiler's own dump
        # (a minute and more for a few seconds of this program's device
        # events, in this process) would be timed as the job's.
        slice_, slice_s = None, None
        trace_dir = os.path.join(root, ".bench_trace", cell["name"])
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            slice_ = _Slice(trace_dir, float(mix["trace_slice"]["cap_s"]))
            slice_.start()
            ts = clock()
            job(len(jobs))
            slice_s = min(clock() - ts, slice_.cap_s)
            slice_.stop()
            phase("traced_job_and_dump")
        if tamper is not None:
            results = [tamper(r) for r in results]
    finally:
        h2o.shutdown()
    # the program's state goes before the reference runs: frame, models,
    # and whatever the store still holds
    STORE.clear()
    del fr, train_fr

    if done:
        meters["replays_per_job"] = meters["replays_in_window"] / len(done)
    metrics = {"setup_s": setup_s}
    if done:
        # the cell's own end-to-end job time (train_job_s.<algo>)
        for m in manifest.metrics_of(man, cell["name"], "end_to_end"):
            if m["name"].startswith("train_job_s"):
                metrics[m["name"]] = window.train_job_s(t0, t1, len(done))
    obs = {
        "algo": algo, "jobs": [{"start": s, "end": e} for s, e, r in jobs if r],
        "spans": spans, "meters": meters, "memory_peak_bytes": peak,
        "window_s": t1 - t0, "njobs": len(done),
        "trees": len(done) * int(config["params"].get("ntrees", 0)),
        "device_kind": jax.devices()[0].device_kind, "chips": cell["chips"],
    }
    # a GLM job's work is that of the IRLS iterations it ran: its stated
    # maximum unless the traced run counted fewer dispatches
    iters = irls[0] / len(done) if (irls[0] and done) else None
    ops, nbytes = work_counts.job_work(config, iters)
    obs["work"] = (ops * len(done), nbytes * len(done))
    obs["iterations_per_job"] = iters

    trace = None
    if args.trace and slice_ is not None:
        trace = trace_reduce.reduce_slice(slice_.path, slice_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
    obs["trace"] = trace

    if args.trace:
        metrics.update(readers.read_all(
            man, cell, root, obs, jax.devices()[0].platform == "tpu"))

    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    compared = {}
    distinct = {}
    for r in results:
        distinct.setdefault(_digest(r), r)
    # back-to-back jobs of one seed return the same model: each distinct
    # one is compared, up to three (a sample, in the order they came)
    data = ref.Data(cols, nrow)
    for r in list(distinct.values())[:3]:
        numbers = ref.compare(r, data, config)
        for k, v in numbers.items():
            lim = config["correct"]["limits"].get(k)
            if k not in compared or v > compared[k]["value"]:
                compared[k] = {"value": v, "limit": lim}
    phase("reference")
    if getattr(data, "times", None):
        print("reference parts (s): " + " ".join(
            f"{k}={v:.2f}" for k, v in data.times.items()), file=sys.stderr)
    print("phases (s): " + " ".join(f"{k}={v:.2f}" for k, v in phases.items())
          + f" window={t1 - t0:.2f} jobs={len(done)}", file=sys.stderr,
          flush=True)
    print("job walls (s): " + " ".join(f"{e - s:.3f}" for s, e, _ in jobs),
          file=sys.stderr)
    compared["distinct_results"] = {"value": len(distinct), "limit": 3}
    compared["jobs_failed"] = {"value": failed[0], "limit": 0}
    compared["program_warnings"] = {"value": len(warn.messages), "limit": 0}
    for msg in warn.messages[:5]:
        print(f"h2o_tpu warned: {msg}", file=sys.stderr)
    correct = bool(results) and judge(
        compared, config["correct"].get("not_compared", ()))
    return {"metrics": metrics, "correct": correct, "attempted": len(jobs),
            "failed": failed[0], "memory_peak_bytes": int(peak),
            "compared": compared, "trace": trace, "binding": obs.get("binding"),
            "trace_slice": None if not args.trace else {
                "what": mix["trace_slice"]["from"],
                "seconds": slice_s}}
