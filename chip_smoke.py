"""chip_smoke.py — the quickest proof that the main path still starts on the chip.

ONE process drives, through the entry points a user calls, at the full width
of the HIGGS configuration (11,000,000 x 28, 20 bins, depth 5; 20 trees
instead of 100):

    h2o.init() -> Frame on the mesh -> GBM train over REST -> GLM IRLSM over
    REST -> register_serving + score_rows over HTTP -> one level-0 histogram
    against a float64 reference

and exits 0 only when every phase passed ON A TPU. It refuses to run when
JAX finds no TPU. ``--rehearse-cpu`` is the explicit rehearsal for a sandbox
without a chip: it prints the platform it ran on, prints NO result line and
still exits non-zero, so a CPU run cannot be read as a pass.

Walls printed here are smoke output (is the program alive, where does a cold
start spend its time), not benchmark records. Any h2o_tpu warning on this
path (a rejected AOT step, a skipped phase sample, ...) fails the run: the
main path must be the one that ran.

    python chip_smoke.py                         # on the chip
    python chip_smoke.py --rehearse-cpu --rows 60000   # sandbox rehearsal
"""

from __future__ import annotations

import argparse
import hashlib
import json
import logging
import os
import socket
import sys
import time

import numpy as np

HIGGS_ROWS = 11_000_000
NTREES, INTERVAL, MAX_DEPTH, NBINS = 20, 10, 5, 20
#: training AUC a constant predictor gets, and the margin the 20-tree GBM
#: and the 28-feature logistic fit must clear on this generator (both land
#: near 0.72-0.77 at every size tried; 0.65 leaves room for seed noise and
#: none for a model that learned nothing)
AUC_CONSTANT, AUC_MARGIN = 0.5, 0.15
#: serving answers vs model.predict on the same rows: the same f32 forest
#: walk compiled twice (bucketed scorer / frame scorer) — equal to rounding
SERVING_ATOL = 1e-6
HIST_ROWS = 65_536
#: level-0 histogram vs float64 numpy. On TPU the one-hot contraction
#: multiplies at DEFAULT precision: every addend v is rounded to bf16 once
#: (8 significand bits -> relative error <= 2^-9) before an f32 accumulate,
#: so a cell's error is bounded by 2^-9 * sum|v| over its rows. The check
#: allows 2^-8 * sum|v| (the bound, doubled for f32 accumulation order) —
#: far below any wrong-bin or dropped-row error, which moves whole addends.
HIST_REL_BOUND = 2.0 ** -8


class _Warnings(logging.Handler):
    """Collects every WARNING+ record of the ``h2o_tpu`` logger."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record):
        self.messages.append(record.getMessage())


class _Phase:
    """One phase's wall, with the XLA compile seconds inside it (what a
    warm cache saves) and the programs / cache hits `compilemeter` counted.
    ``compile_secs`` is the run-wide list `main` feeds from jax.monitoring's
    backend-compile events."""

    def __init__(self, name: str, compile_secs: list):
        from h2o_tpu.utils import compilemeter

        self.name, self.t0 = name, time.time()
        self._secs, self._n0 = compile_secs, len(compile_secs)
        self._meter = compilemeter
        self._c0 = (compilemeter.count(), compilemeter.cache_hits())

    def done(self, **detail) -> None:
        secs = self._secs[self._n0:]
        detail = {"programs": self._meter.count() - self._c0[0],
                  "cache_hits": self._meter.cache_hits() - self._c0[1],
                  "compile_s": round(sum(secs), 1),
                  "slowest_compile_s": round(max(secs, default=0.0), 1),
                  **detail}
        print(f"[smoke] {self.name}: {time.time() - self.t0:.1f}s "
              + " ".join(f"{k}={v}" for k, v in detail.items()), flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _environment(jax) -> None:
    """Print what the hardware and the installation say about themselves."""
    import importlib.metadata as md

    import jaxlib

    from h2o_tpu.backend import native
    from h2o_tpu.utils import compile_cache

    devs = jax.devices()
    # printed only: `backend/memory.hbm_stats` is what raises on a TPU that
    # reports no limit, on the first budget any planner asks for
    limits = {str(d): (d.memory_stats() or {}).get("bytes_limit")
              for d in devs}
    cache_dir = compile_cache.ensure()
    env = {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": md.version("libtpu"),
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "bytes_limit": limits,
        "compile_cache_dir": cache_dir,
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "cache_entries_at_start":
            len(os.listdir(cache_dir)) if cache_dir else 0,
        "native_sort": "native" if native.lib() is not None else "numpy",
    }
    for k, v in env.items():
        print(f"[smoke] {k}={v}", flush=True)


def _check_placement(jax, fr, nrow: int) -> None:
    from h2o_tpu.parallel import mesh as meshmod

    n = jax.device_count()
    all_devs = set(jax.devices())
    for name in fr.names:
        data = fr.vec(name).data
        if set(data.sharding.device_set) != all_devs:
            raise AssertionError(
                f"column {name} lives on {len(data.sharding.device_set)} of "
                f"{n} devices")
        per_shard = meshmod.per_shard_nbytes(data)
        if abs(per_shard * n - data.nbytes) > 0.01 * data.nbytes:
            raise AssertionError(
                f"column {name}: per-shard {per_shard} B x {n} != total "
                f"{data.nbytes} B — not an even row split")
    print(f"[smoke] frame {nrow} x {fr.ncol - 1} + response on {n} "
          f"device(s), per-shard bytes/column="
          f"{meshmod.per_shard_nbytes(fr.vec(0).data)}", flush=True)


def _auc_above_floor(model, what: str) -> float:
    auc = float(model.output.training_metrics.auc)
    if not auc > AUC_CONSTANT + AUC_MARGIN:
        raise AssertionError(f"{what} training AUC {auc:.4f} is not above "
                             f"{AUC_CONSTANT} + {AUC_MARGIN}")
    return auc


def _train_gbm(h2o, fr, feats) -> tuple:
    from h2o_tpu.backend.kvstore import STORE

    est = h2o.H2OGradientBoostingEstimator(
        ntrees=NTREES, max_depth=MAX_DEPTH, nbins=NBINS, seed=42,
        score_tree_interval=INTERVAL)
    est.train(x=feats, y="response", training_frame=h2o.get_frame(fr.key))
    model = STORE.get(est.model_id)
    ntrees = int(np.asarray(model.forest["feat"]).shape[0])
    if ntrees != NTREES:
        raise AssertionError(f"forest holds {ntrees} trees, not {NTREES}")
    auc = _auc_above_floor(model, "GBM")
    # forest STRUCTURE digest (split features + NA directions): bit-equal
    # across mesh widths, or the SPMD histograms changed a split decision
    sha = hashlib.sha256()
    for k in ("feat", "nanL"):
        sha.update(np.ascontiguousarray(
            np.asarray(model.forest[k])).tobytes())
    return model, auc, sha.hexdigest()


def _train_glm(h2o, fr, feats) -> tuple:
    from h2o_tpu.backend.kvstore import STORE

    est = h2o.H2OGeneralizedLinearEstimator(
        family="binomial", solver="IRLSM", lambda_=0.0, max_iterations=5,
        seed=42)
    est.train(x=feats, y="response", training_frame=h2o.get_frame(fr.key))
    model = STORE.get(est.model_id)
    coef = {k: float(v) for k, v in model.coef().items()}
    if not all(np.isfinite(v) for v in coef.values()):
        raise AssertionError(f"GLM coefficients not finite: {coef}")
    return coef, _auc_above_floor(model, "GLM")


def _check_serving(h2o, fr, model, feats) -> dict:
    """score_rows over HTTP == model.predict on the same rows; no compile
    after registration."""
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.utils import compilemeter

    sizes = (1, 8, 300)
    n = max(sizes)
    # the first n rows only — a to_numpy() would ship whole columns to host
    cols = {f: np.asarray(fr.vec(f).data[:n]) for f in feats}
    want = model.predict(Frame.from_dict(dict(cols)))
    want_p1 = want.vec(want.ncol - 1).to_numpy()[:n]
    rows = [{f: float(cols[f][i]) for f in feats} for i in range(n)]

    info = h2o.register_serving(model.key, serving_id="smoke")
    before = compilemeter.count()
    worst = 0.0
    for k in sizes:
        preds = h2o.score_rows("smoke", rows[:k])
        if len(preds) != k:
            raise AssertionError(f"{k} rows sent, {len(preds)} answers")
        got = np.array([p["classProbabilities"][1] for p in preds])
        if not np.all(np.isfinite(got)):
            raise AssertionError(f"non-finite serving answers at {k} rows")
        worst = max(worst, float(np.max(np.abs(got - want_p1[:k]))))
    compiles = compilemeter.count() - before
    h2o.unregister_serving("smoke")
    if worst > SERVING_ATOL:
        raise AssertionError(f"serving answers differ from model.predict by "
                             f"{worst:.3g} > {SERVING_ATOL}")
    if compiles:
        raise AssertionError(f"{compiles} compile(s) after registration")
    return {"max_abs_diff": worst, "warmup_compiles":
            info.get("warmup_compiles"), "compiles_after_registration": 0}


def _check_level0_hist(jax) -> dict:
    """One level-0 histogram from the default kernel vs float64 numpy."""
    import jax.numpy as jnp

    from h2o_tpu.backend.kernels import hist

    rng = np.random.default_rng(7)
    F, B = 28, NBINS + 1
    codes = rng.integers(0, B, size=(HIST_ROWS, F)).astype(np.int8)
    vals = rng.normal(size=(HIST_ROWS, 3)).astype(np.float32)
    vals[:, 0] = 1.0                         # w, g, h — unit weights
    fn = jax.jit(lambda X, l, v: hist.level_hist_blocks(
        X, l, v, n_lv=1, nbins_tot=B, block=8192))
    got = np.asarray(fn(jnp.asarray(codes),
                        jnp.zeros(HIST_ROWS, jnp.int32),
                        jnp.asarray(vals)), np.float64)[:, 0]   # (F, B, V)
    ref = np.zeros((F, B, 3))
    mass = np.zeros((F, B, 3))
    v64 = vals.astype(np.float64)
    for f in range(F):
        np.add.at(ref[f], codes[:, f].astype(np.int64), v64)
        np.add.at(mass[f], codes[:, f].astype(np.int64), np.abs(v64))
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"histogram shape {got.shape} / non-finite")
    ratio = float(np.max(np.abs(got - ref) / mass))
    if ratio > HIST_REL_BOUND:
        raise AssertionError(
            f"level-0 histogram off by {ratio:.3g} x sum|v| per cell, bound "
            f"{HIST_REL_BOUND:.3g} (bf16 addends)")
    if not np.array_equal(got[:, :, 0], ref[:, :, 0]):
        raise AssertionError("row counts per bin differ from the reference")
    return {"max_err_over_mass": ratio, "bound": HIST_REL_BOUND}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="explicit rehearsal on whatever backend JAX finds: "
                         "prints the platform, never prints a result line, "
                         "never exits 0")
    ap.add_argument("--rows", type=int, default=HIGGS_ROWS,
                    help="rows of the HIGGS-shaped frame (rehearsal only)")
    args = ap.parse_args(argv)

    import jax

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU — JAX found platform={dev0.platform}; "
              f"refusing to run (--rehearse-cpu rehearses, and still does "
              f"not pass)", file=sys.stderr)
        return 2
    if args.rows != HIGGS_ROWS and not args.rehearse_cpu:
        print("chip_smoke: --rows is for --rehearse-cpu only",
              file=sys.stderr)
        return 2

    import h2o_tpu.api as h2o
    from h2o_tpu.utils import compilemeter

    import bench

    warn = _Warnings()
    logging.getLogger("h2o_tpu").addHandler(warn)
    compilemeter.install()
    compile_secs: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_secs.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    t_all = time.time()
    _environment(jax)

    ph = _Phase("init+health", compile_secs)
    conn = h2o.init(port=_free_port())
    try:
        if getattr(conn, "_server", None) is None:
            raise RuntimeError("h2o.init() attached to a foreign server")
        health = h2o.health()
        if not health.get("ready"):
            raise AssertionError(f"server not ready: {health}")
        ph.done(ready=health["ready"])

        ph = _Phase("frame", compile_secs)
        fr = bench._higgs_frame(args.rows)   # seeded; Frame.from_dict
        jax.block_until_ready([v.data for v in fr.vecs])
        _check_placement(jax, fr, args.rows)
        feats = [n for n in fr.names if n != "response"]
        ph.done()

        ph = _Phase("gbm", compile_secs)
        gbm, gbm_auc, struct_sha = _train_gbm(h2o, fr, feats)
        ph.done(auc=round(gbm_auc, 4), forest_struct_sha=struct_sha)

        ph = _Phase("glm", compile_secs)
        coef, glm_auc = _train_glm(h2o, fr, feats)
        ph.done(auc=round(glm_auc, 4))
        print("[smoke] glm_coef=" + json.dumps(coef), flush=True)

        ph = _Phase("serving", compile_secs)
        ph.done(**_check_serving(h2o, fr, gbm, feats))

        ph = _Phase("level0_hist", compile_secs)
        ph.done(**_check_level0_hist(jax))
    finally:
        h2o.shutdown()

    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()}
    counts = {"programs": compilemeter.count(),
              "cache_hits": compilemeter.cache_hits(),
              "uncached_compiles": compilemeter.uncached_count()}
    print(f"[smoke] peak_bytes_in_use={peaks}", flush=True)
    print(f"[smoke] compiles={counts} "
          f"wall_total={time.time() - t_all:.1f}s (smoke output, not a "
          f"benchmark)", flush=True)
    if warn.messages:
        raise AssertionError("h2o_tpu warned on the main path: "
                             + " | ".join(warn.messages))

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    if args.rehearse_cpu:
        print(f"chip_smoke: REHEARSAL passed every phase on platform="
              f"{dev0.platform} x{device['count']} at {args.rows} rows — "
              f"this is not a chip pass", flush=True)
        return 2
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
