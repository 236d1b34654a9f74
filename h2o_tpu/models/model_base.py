"""Model / ModelBuilder / Parameters — analog of `hex/Model.java` (3,535 LoC),
`hex/ModelBuilder.java` (2,198 LoC) and the per-algo `Model.Parameters` Iced
objects.

Semantics preserved from the reference:
- ``Parameters`` is a plain dataclass mirroring the REST-schema field names
  (training_frame, response_column, ignored_columns, weights_column, nfolds,
  seed, distribution, ...) so the h2o-py estimator surface maps 1:1.
- ``ModelBuilder.train()`` returns a Job running the driver on a worker thread
  (`hex/ModelBuilder.java:381-398` trainModel → Driver), cooperatively
  cancellable; ``train_model()`` is the blocking convenience.
- N-fold cross-validation orchestration (`hex/ModelBuilder.java:614`
  computeCrossValidation): fold assignment (random / modulo / stratified), one
  model per fold on the complement, holdout metrics, then the final model on
  the full frame. Fold builds are embarrassingly parallel across mesh slices in
  principle; here they run sequentially on the single controller (the mesh is
  busy either way).
- ``Model.score()`` adapts the test frame to training domains
  (`hex/Model.java:1638` adaptTestForTrain) then runs one device-side batch
  prediction — the BigScore MRTask analog (`hex/Model.java:2232`).
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.jobs import Job
from ..backend.kvstore import Keyed, STORE
from ..backend.memory import hbm_span_attrs
from ..frame.frame import Frame
from ..frame.vec import T_CAT, Vec
from .metrics import (make_binomial_metrics, make_multinomial_metrics,
                      make_regression_metrics)


@dataclass
class Parameters:
    """Common hyperparameters — `hex/Model.java` Model.Parameters."""

    training_frame: Optional[Frame] = None
    validation_frame: Optional[Frame] = None
    response_column: Optional[str] = None
    ignored_columns: list = field(default_factory=list)
    weights_column: Optional[str] = None
    offset_column: Optional[str] = None
    fold_column: Optional[str] = None
    nfolds: int = 0
    fold_assignment: str = "AUTO"  # AUTO|Random|Modulo|Stratified
    keep_cross_validation_models: bool = True
    keep_cross_validation_predictions: bool = False
    keep_cross_validation_fold_assignment: bool = False
    seed: int = -1
    max_runtime_secs: float = 0.0
    distribution: str = "AUTO"
    categorical_encoding: str = "AUTO"
    max_categorical_levels: int = 10  # EnumLimited top-k
                                      # (`hex/Model.java` _max_categorical_levels)
    ignore_const_cols: bool = True
    check_constant_response: bool = True  # `hex/tree/SharedTree` refuses a
                                          # constant response unless disabled
    balance_classes: bool = False
    stopping_rounds: int = 0
    stopping_metric: str = "AUTO"
    stopping_tolerance: float = 1e-3
    auc_type: str = "AUTO"  # multinomial AUC aggregate: AUTO(=NONE)|NONE|
                            # MACRO_OVR|WEIGHTED_OVR|MACRO_OVO|WEIGHTED_OVO
                            # (`hex/MultinomialAUC.java`, Model.Parameters
                            # _auc_type)
    checkpoint: Any = None          # prior model (or its key) to continue from
    export_checkpoints_dir: Optional[str] = None  # in-training snapshots
    auto_recovery_dir: Optional[str] = None  # preemption-proof training:
                                    # periodic atomic checkpoints of the
                                    # RUNNING job land here; resume_training
                                    # restarts a killed job BIT-EQUAL to the
                                    # uninterrupted run (backend/persist.py
                                    # TrainingRecovery; interval knob
                                    # H2O_TPU_CHECKPOINT_SECS, default dir
                                    # knob H2O_TPU_AUTO_RECOVERY_DIR)
    custom_metric_func: Any = None  # callable(y, raw_pred, w) -> (name, value)
                                    # — the CFuncRef/CMetricFunc UDF analog
                                    # (`water/udf/`, `hex/CMetricScoringTask`);
                                    # in-process Python replaces uploaded jars

    def clone(self, **overrides):
        return dataclasses.replace(self, **overrides)


class ModelOutput:
    """Analog of `hex/Model.Output` — everything the trained model publishes."""

    def __init__(self):
        self.names: list[str] = []
        self.domains: dict[str, list | None] = {}
        self.response_domain: list | None = None
        self.model_category = "Regression"  # Regression|Binomial|Multinomial|Clustering|...
        self.training_metrics = None
        self.validation_metrics = None
        self.cross_validation_metrics = None
        self.scoring_history: list[dict] = []
        self.variable_importances: dict | None = None
        self.run_time_ms = 0
        self.cv_models: list = []
        self.cv_holdout_predictions = None  # Frame, when kept


class Model(Keyed):
    algo_name = "base"

    def __init__(self, params: Parameters, output: ModelOutput, key=None):
        super().__init__(key=key, prefix=f"{self.algo_name}_model")
        self.params = params
        self.output = output
        STORE.put_keyed(self)

    # -- prediction ----------------------------------------------------------
    def score0(self, X: jax.Array) -> jax.Array:
        """Raw per-row prediction on a dense feature matrix — per-algo override
        (the `hex/Model.java:2232` score0 contract). Returns (n,) for
        regression, (n, 1+K) [label, p0..pK-1] for classification."""
        raise NotImplementedError

    def score_raw(self, X: jax.Array) -> jax.Array:
        """Traceable raw-matrix scoring for the serving runtime: X is a
        (B, F) float32 matrix with columns in ``output.names`` order and
        categoricals as training-domain codes (unseen levels NaN) — the
        exact matrix the base ``adapt_frame`` would build. Models whose
        ``adapt_frame`` does more than column selection (design expansion,
        spline bases, ...) must override this with their matrix-level
        transform; `serving/scorer.py` refuses models that override
        ``adapt_frame`` without also overriding ``score_raw``."""
        return self.score0(X)

    def pre_adapt(self, fr: Frame) -> Frame:
        """Replay the frozen categorical_encoding (if any) — every
        adapt_frame override must route incoming frames through this."""
        enc = getattr(self.output, "encoding_state", None)
        if enc is None:
            return fr
        from ..utils.linalg import apply_encoding_state

        return apply_encoding_state(fr, enc)

    def adapt_frame(self, fr: Frame) -> jax.Array:
        """adaptTestForTrain analog: select training columns in order, remap
        categorical codes onto the training domain (unseen levels → NaN)."""
        fr = self.pre_adapt(fr)
        cols = []
        for name in self.output.names:
            v = fr.vec(name)
            train_dom = self.output.domains.get(name)
            if train_dom is not None and v.domain != train_dom:
                remap = {lvl: i for i, lvl in enumerate(train_dom)}
                codes = np.full(len(v.domain or []), np.nan, dtype=np.float32)
                for i, lvl in enumerate(v.domain or []):
                    if lvl in remap:
                        codes[i] = remap[lvl]
                host = v.to_numpy()
                ok = ~np.isnan(host)
                newc = np.full(host.shape, np.nan, dtype=np.float32)
                newc[ok] = codes[host[ok].astype(np.int64)]
                v = Vec.from_numpy(newc, type=T_CAT, domain=train_dom)
            cols.append(v)
        tmp = Frame([n for n in self.output.names], cols)
        return tmp.as_matrix()

    def predict(self, fr: Frame) -> Frame:
        X = self.adapt_frame(fr)
        raw = self.score0(X)
        return self._predictions_frame(raw, fr.nrow)

    def _predictions_frame(self, raw, nrow) -> Frame:
        cat = self.output.model_category
        if cat == "Regression":
            return Frame(["predict"], [Vec.from_device(raw, nrow)])
        dom = self.output.response_domain or [str(i) for i in range(raw.shape[1] - 1)]
        names = ["predict"] + [f"p{d}" for d in dom]
        vecs = [Vec.from_device(raw[:, 0], nrow, type=T_CAT, domain=list(dom))]
        for j in range(1, raw.shape[1]):
            vecs.append(Vec.from_device(raw[:, j], nrow))
        return Frame(names, vecs)

    # -- metrics -------------------------------------------------------------
    def model_performance(self, fr: Frame | None = None):
        if fr is None:
            return self.output.training_metrics
        X = self.adapt_frame(fr)
        raw = self.score0(X)
        y = _response_device(fr, self.params.response_column, self.output.response_domain)
        w = fr.vec(self.params.weights_column).data if self.params.weights_column else None
        return make_metrics(self.output.model_category, y, raw, w,
                            auc_type=self.params.auc_type,
                            domain=self.output.response_domain)

    def score_with_metrics(self, fr: Frame) -> tuple[Frame, object]:
        """One scoring pass serving both the predictions frame and the
        metrics — the reference's BigScore MRTask computes both in a single
        map (`hex/Model.java:2232` score + MetricBuilder.perRow)."""
        X = self.adapt_frame(fr)
        raw = self.score0(X)
        y = _response_device(fr, self.params.response_column,
                             self.output.response_domain)
        w = fr.vec(self.params.weights_column).data \
            if self.params.weights_column else None
        return (self._predictions_frame(raw, fr.nrow),
                make_metrics(self.output.model_category, y, raw, w,
                             auc_type=self.params.auc_type,
                             domain=self.output.response_domain))

    def auc(self):
        """None when no AUC is available (regression, or multinomial with
        auc_type unset) — the pre-multinomial-AUC contract callers test with
        ``is None``; NaN placeholders never escape."""
        a = getattr(self.output.training_metrics, "auc", None)
        if a is None or (isinstance(a, float) and np.isnan(a)):
            return None
        return a

    # -- tabular views (`water/util/TwoDimTable` publications) ----------------
    def varimp_table(self):
        vi = self.output.variable_importances
        if not vi:
            return None
        from ..utils.twodimtable import TwoDimTable

        return TwoDimTable.from_dict("Variable Importances", {
            "variable": list(vi["variable"]),
            "relative_importance": [float(x) for x in vi["relative_importance"]],
            "scaled_importance": [float(x) for x in vi["scaled_importance"]],
            "percentage": [float(x) for x in vi["percentage"]]})

    def scoring_history_table(self):
        hist = self.output.scoring_history
        if not hist:
            return None
        from ..utils.twodimtable import TwoDimTable

        cols: dict[str, list] = {}
        for h in hist:
            for k, v in h.items():
                if k == "training_metrics":
                    for mk in ("logloss", "auc", "rmse", "mse"):
                        mv = getattr(v, mk, None)
                        # skip absent metrics AND NaN placeholders (multinomial
                        # auc with auc_type unset) — no all-NaN columns
                        if mv is not None and not np.isnan(mv):
                            cols.setdefault(f"training_{mk}", []).append(float(mv))
                elif isinstance(v, (int, float, str)):
                    cols.setdefault(k, []).append(v)
        return TwoDimTable.from_dict("Scoring History", cols)

    # -- binary export/import (`hex/Model.java` exportBinaryModel) ------------
    def save(self, path: str) -> str:
        from ..backend.persist import save_model

        return save_model(self, path)

    # -- export (`hex/ModelMojoWriter.java` hook) -----------------------------
    def save_mojo(self, path: str) -> str:
        from ..mojo.writer import export_mojo

        return export_mojo(self, path)

    download_mojo = save_mojo  # h2o-py surface alias

    def save_pojo(self, path: str, class_name: str | None = None) -> str:
        """Java source scorer (`hex/tree/TreeJCodeGen` / `toJavaPredict`)."""
        from ..mojo.pojo import export_pojo

        return export_pojo(self, path, class_name)

    download_pojo = save_pojo

    # -- explanation surface (`hex/PartialDependence`, `hex/PermutationVarImp`)
    def partial_dependence(self, fr, cols=None, nbins: int = 20,
                           weight_column=None, targets=None,
                           row_index: int = -1):
        from .explain import partial_dependence

        return partial_dependence(self, fr, cols, nbins, weight_column,
                                  targets, row_index=row_index)

    def permutation_importance(self, fr, metric: str = "AUTO",
                               n_repeats: int = 1, seed: int = -1):
        from .explain import permutation_varimp

        return permutation_varimp(self, fr, metric, n_repeats, seed)

    def remove_impl(self, store):
        for m in self.output.cv_models:
            store.remove(m.key)

    def __repr__(self):
        return (f"{type(self).__name__}({self.key}, {self.output.model_category})\n"
                f"{self.output.training_metrics!r}")


def make_metrics(category, y, raw, weights=None, auc_type="AUTO", domain=None):
    if category == "Binomial":
        return make_binomial_metrics(y, raw[:, 2], weights)
    if category == "Multinomial":
        return make_multinomial_metrics(y, raw[:, 1:], weights,
                                        auc_type=auc_type, domain=domain)
    return make_regression_metrics(y, raw, weights)


def _response_device(fr: Frame, response: str, train_dom) -> jax.Array:
    v = fr.vec(response)
    if train_dom is not None and v.domain is not None and v.domain != list(train_dom):
        remap = {lvl: i for i, lvl in enumerate(train_dom)}
        host = v.to_numpy()
        out = np.full(host.shape, np.nan, dtype=np.float32)
        ok = ~np.isnan(host)
        out[ok] = [remap.get((v.domain)[int(c)], np.nan) for c in host[ok]]
        return Vec.from_numpy(out).data
    return v.data


class ModelBuilder:
    """Per-algo builders subclass this and implement ``build_impl``."""

    algo_name = "base"
    supervised = True
    supports_cv = True  # False for transformers that consume fold_column
                        # themselves (TargetEncoder's KFold strategy)
    _constant_response_check = False  # True in tree builders (SharedTree)

    def __init__(self, params: Parameters):
        self.params = params
        self.job: Job | None = None
        self._validate()

    # -- validation (init(expensive) analog) ---------------------------------
    def _validate(self):
        p = self.params
        if p.training_frame is None:
            raise ValueError("training_frame is required")
        at = (getattr(p, "auc_type", "AUTO") or "AUTO").lower()
        if at not in ("auto", "none", "macro_ovr", "weighted_ovr",
                      "macro_ovo", "weighted_ovo"):
            raise ValueError(
                f"auc_type '{p.auc_type}' must be one of AUTO, NONE, "
                "MACRO_OVR, WEIGHTED_OVR, MACRO_OVO, WEIGHTED_OVO")
        if self.supervised:
            if not p.response_column:
                raise ValueError(f"{self.algo_name}: response_column is required")
            if p.training_frame.find(p.response_column) < 0:
                raise ValueError(f"response_column '{p.response_column}' not in frame")
            if p.check_constant_response and self._constant_response_check:
                # batch the response + candidate-feature rollups in one
                # fused pass — first rollup touch in a builder's life;
                # ignored columns never pay
                p.training_frame.ensure_rollups(self._rollup_names())
                rv = p.training_frame.vec(p.response_column)
                if not rv.is_string() and rv.data is not None:
                    r = rv.rollups()
                    if r.nacnt < rv.nrow and r.mins == r.maxs:
                        raise ValueError(
                            f"{self.algo_name}: response is constant — set "
                            "check_constant_response=False to train anyway "
                            "(hex/tree/SharedTree constant-response check)")

    def _rollup_names(self) -> list[str]:
        """Columns whose rollups a build will actually read: the response
        plus every non-ignored, non-special column."""
        p = self.params
        skip = set(p.ignored_columns) | {p.weights_column, p.offset_column,
                                         p.fold_column, None}
        return [n for n in p.training_frame.names if n not in skip]

    # -- feature selection ----------------------------------------------------
    def feature_names(self) -> list[str]:
        p = self.params
        # batch all missing rollups in one fused pass before the per-column
        # loop reads them (per-column eager rollups serialize device
        # round-trips — 38 s of an 11M-row cold train)
        if p.ignore_const_cols:
            p.training_frame.ensure_rollups(self._rollup_names())
        skip = set(p.ignored_columns) | {p.response_column, p.weights_column,
                                         p.offset_column, p.fold_column, None}
        out = []
        for name in p.training_frame.names:
            if name in skip:
                continue
            v = p.training_frame.vec(name)
            if v.is_string():
                continue
            if p.ignore_const_cols and v.data is not None:
                r = v.rollups()
                if r.nacnt == v.nrow or (r.mins == r.maxs):
                    continue
            out.append(name)
        if not out:
            raise ValueError(
                f"{self.algo_name}: no usable feature columns (all constant, "
                "all-NA, string, or ignored) — set ignore_const_cols=False to "
                "keep constant columns")
        return out

    def response_info(self):
        """(y array, model_category, response domain)."""
        p = self.params
        v = p.training_frame.vec(p.response_column)
        if v.is_string():
            # a T_STR vec is host-only (data=None) — letting it through
            # dies as an opaque TypeError deep in the jitted y/w prep
            raise ValueError(
                f"{self.algo_name}: response_column '{p.response_column}' "
                f"is a string column — convert it to categorical first "
                f"(h2o contract: frame['{p.response_column}']."
                f"asfactor(), or load via from_pandas which factorizes "
                f"object columns)")
        if v.is_categorical():
            k = len(v.domain)
            cat = "Binomial" if k == 2 else "Multinomial"
            return v.data, cat, v.domain
        dist = (p.distribution or "AUTO").lower()
        if dist in ("bernoulli", "quasibinomial"):
            return v.data, "Binomial", ["0", "1"]
        if dist == "multinomial":
            k = int(v.max()) + 1
            return v.data, "Multinomial", [str(i) for i in range(k)]
        return v.data, "Regression", None

    # -- training ------------------------------------------------------------
    def build_impl(self, job: Job) -> Model:
        raise NotImplementedError

    def train(self, background: bool = True) -> Job:
        """trainModel analog — returns the running Job."""
        self.job = Job(f"{self.algo_name} training", work=1.0)
        self.job.set_max_runtime(self.params.max_runtime_secs)

        def run():
            from ..utils import compile_cache, compilemeter, telemetry

            # persistent XLA compile cache, placed before the job's first
            # dispatch: ANY process that trains on an accelerator replays
            # its compiles (idempotent — the server/cluster entry points
            # place it earlier when they ran)
            compile_cache.ensure()
            t0 = time.time()
            # one root span per training job: everything recorded under it
            # (chunk/epoch spans, MRTask dispatches, checkpoints) shares
            # its trace id, so /3/Timeline and the chrome-trace export can
            # reassemble the whole job. Background jobs used to start a
            # fresh trace here (thread = fresh contextvars); since
            # Job.start adopts telemetry.carry_context, a REST-started
            # job nests under the request span — and through its
            # traceparent, under the REMOTE client's trace — while a
            # directly-driven train with no enclosing span still roots
            # its own trace here.
            compilemeter.install()  # compiles are countable from now on
            # H2O_TPU_PROFILE_DIR arms a span-scoped jax.profiler capture
            # of the whole job: the root span below (and every span nested
            # under it) mirrors into TraceAnnotations, so XLA ops nest
            # under train.gbm.chunk in Perfetto. Contextmanager yields
            # None (no session) when the knob is unset — zero overhead.
            with telemetry.device_profile(f"train.{self.algo_name}"), \
                    telemetry.span(f"train.{self.algo_name}",
                                   algo=self.algo_name,
                                   job=str(self.job.key)) as self._train_span:
                # arm auto-recovery BEFORE the encoding swap: the persisted
                # params/frames must be the ORIGINAL inputs so a resumed
                # process replays the (deterministic) encoding itself
                self._arm_auto_recovery()
                enc_state = self._apply_categorical_encoding()
                if self.supports_cv and (self.params.nfolds >= 2
                                         or self.params.fold_column):
                    model = self._train_with_cv(self.job)
                else:
                    model = self.build_impl(self.job)
                if enc_state is not None:
                    model.output.encoding_state = enc_state
                    for cv in model.output.cv_models:
                        cv.output.encoding_state = enc_state
                self._apply_custom_metric(model)
                # drain the device stream before reading the clock:
                # dispatch is async, and run_time_ms is the number
                # /3/Models reports. This is also the CONTRACT every
                # caller times against — graftlint's timing-without-sync
                # rule treats train_model as self-syncing because of this
                # block
                import jax

                from ..utils.blocking import device_arrays

                jax.block_until_ready(device_arrays(model))
                model.output.run_time_ms = int((time.time() - t0) * 1000)
                self._train_span.attrs.update(hbm_span_attrs())
            telemetry.inc("train.count")
            # drained above, so this histogram is honest compute wall
            telemetry.observe("train.seconds",
                              model.output.run_time_ms / 1000.0)
            self.job.dest_key = model.key
            if self._recovery is not None:
                self._recovery.mark_completed(model.key)
            return model

        def run_guarded():
            from ..backend.jobs import JobCancelled, JobPreempted

            try:
                return run()
            except (JobCancelled, JobPreempted):
                # a user cancel / boundary preemption is a HANDLED
                # outcome (Job maps them to CANCELLED / PREEMPTED), not
                # a terminal event — bundling it would rotate real crash
                # bundles out of the flight dir
                raise
            except Exception as e:  # noqa: BLE001 — re-raised verbatim
                # unhandled training crash: flight-record the terminal
                # state (metrics/timeline/threads/ledger/programs/knobs)
                # before the Job surfaces the failure. No-op unless
                # H2O_TPU_FLIGHT_DIR is set; never masks the real error.
                from ..utils import flightrec

                flightrec.dump("train-crash", e)
                raise

        # every training build dispatches through the workload manager:
        # tenant stamped + quota debited, and under H2O_TPU_WORKLOAD_SLOTS
        # the job queues for the fair-share lottery instead of starting
        # unconditionally. Unmanaged (the default) this is exactly the
        # old self.job.start(run_guarded, background) dispatch.
        from .. import workload

        workload.submit(self.job, run_guarded, background=background,
                        cost_bytes=workload.frame_cost(self.params))
        return self.job

    def train_model(self) -> Model:
        return self.train(background=False).join()

    # -- preemption-proof training (auto-recovery checkpoints) ----------------
    _recovery = None       # TrainingRecovery while an armed build runs
    _resume_state = None   # iteration state injected by resume_training

    def _arm_auto_recovery(self) -> None:
        """Arm periodic atomic checkpointing when the params (or the
        H2O_TPU_AUTO_RECOVERY_DIR knob) name a recovery dir. CV builds are
        excluded: fold sub-builds are cheap relative to orchestration and
        the manifest would need a per-fold protocol (grid.py's recovery
        already covers the expensive multi-model case)."""
        from ..utils import knobs

        self._recovery = None
        p = self.params
        rdir = getattr(p, "auto_recovery_dir", None)
        if not rdir:
            rdir = knobs.get_str("H2O_TPU_AUTO_RECOVERY_DIR")
            if rdir:
                # the knob arms EVERY job with one base dir — each job gets
                # its own subdir, or concurrent jobs would interleave their
                # manifests/state. (resume_training pins the exact subdir
                # back into params, so resumed jobs never re-derive one.)
                rdir = os.path.join(
                    rdir, f"{self.algo_name}_{os.getpid()}_{self.job.key}")
        if not rdir:
            return
        if self.supports_cv and (p.nfolds >= 2 or p.fold_column):
            from ..utils.log import warn

            warn("auto-recovery checkpoints are not supported for CV "
                 "builds — training without them")
            return
        from ..backend.persist import TrainingRecovery

        try:
            rec = TrainingRecovery(rdir)
            if self._resume_state is None:
                if not rec.init_for(self):
                    return
            else:
                import time as _t

                # resumed: interval restarts now
                rec._last_write = _t.monotonic()
        except OSError as e:
            # a training job must never die for its checkpoint insurance —
            # unwritable/invalid dir degrades to training without it
            from ..utils.log import warn

            warn(f"auto-recovery disabled: recovery dir {rdir!r} "
                 f"unusable ({e!r})")
            return
        self._recovery = rec
        # armed recovery is what makes boundary preemption lossless —
        # only now may the workload manager preempt this job
        self.job.preemptible = True

    def _recovery_tick(self, state_fn, progress: dict | None = None) -> None:
        """Builders call this at every iteration boundary they can resume
        from; the state is captured (and the write paid) only when the
        wall-clock interval has elapsed. ``state_fn`` returns the EXACT
        iteration state — device arrays welcome, they are pulled to host by
        the writer — such that restoring it and replaying the remaining
        iterations is bit-equal to never having stopped."""
        self._preempt_tick(state_fn, progress)
        rec = self._recovery
        if rec is None or not rec.due():
            return
        try:
            from ..utils import telemetry

            t0 = time.perf_counter()
            rec.save_state(state_fn(), progress)
            # the insurance premium, measured: checkpoint overhead rides
            # /3/Metrics next to the chunk/epoch walls it taxes
            telemetry.observe("train.checkpoint.seconds",
                              time.perf_counter() - t0)
            telemetry.inc("train.checkpoint.count")
        except OSError as e:
            # disk yanked mid-train (full / remount): lose the insurance,
            # keep the job. Injected faults are RuntimeErrors — they still
            # propagate, so kill-resume tests are unaffected.
            from ..utils.log import warn

            warn(f"auto-recovery disabled mid-train: checkpoint write to "
                 f"{rec.dir!r} failed ({e!r})")
            self._recovery = None

    def _preempt_tick(self, state_fn, progress: dict | None = None) -> None:
        """The workload preemption poll, riding the same boundaries as
        the checkpoint tick: a preempt request (Job.request_preempt or
        the ``workload.preempt`` failpoint) observed here force-
        checkpoints the iteration state — bypassing the due() interval,
        a preemption cannot wait for the clock — and unwinds with the
        typed ``JobPreempted`` the Job/manager park on. Ignored when no
        recovery is armed: a non-preemptible job never loses work."""
        from ..utils import failpoints

        job = self.job
        want = job is not None and job.preempt_requested
        try:
            failpoints.hit("workload.preempt")
        except failpoints.InjectedFault:
            # the injection IS the preempt request (raise(preempt)@K =
            # "preempt exactly before boundary K"), consumed here
            want = True
        if not want:
            return
        rec = self._recovery
        if rec is None:
            return
        from ..utils import telemetry

        rec.save_state(state_fn(), progress)
        telemetry.inc("train.checkpoint.count")
        telemetry.inc("workload.preempt.count")
        if job is not None:
            job.clear_preempt()
        from ..backend.jobs import JobPreempted

        raise JobPreempted(str(job.key) if job else "<no job>", rec.dir)

    def _take_resume_state(self):
        """The iteration state `resume_training` injected (None on a fresh
        build), guarded once for every builder: a recovery dir written by
        another algorithm must refuse loudly, never resume into the wrong
        build_impl."""
        rs = self._resume_state
        if rs is not None and rs.get("algo") != self.algo_name:
            raise ValueError(
                f"recovery state is for algo {rs.get('algo')!r}, "
                f"this builder is {self.algo_name!r}")
        return rs

    def _apply_categorical_encoding(self):
        """Eigen/OneHotExplicit/Binary/LabelEncoder/EnumLimited/SortByResponse
        categorical_encoding: freeze the transform on the training frame,
        swap the params to the encoded frames, and return the state the
        trained model replays at score time
        (`hex/Model.Parameters.CategoricalEncodingScheme` +
        `water/util/FrameUtils.java` encoder drivers)."""
        p = self.params
        from ..utils.linalg import apply_encoding_state, build_encoding_state

        skip = [p.response_column, p.weights_column, p.offset_column,
                p.fold_column] + list(p.ignored_columns)
        state = build_encoding_state(
            p.training_frame, p.categorical_encoding,
            skip=[s for s in skip if s], response=p.response_column,
            weights=p.weights_column,
            max_levels=int(getattr(p, "max_categorical_levels", 10) or 10))
        if state is None:
            return None
        updates = {"training_frame": apply_encoding_state(p.training_frame,
                                                          state)}
        if p.validation_frame is not None:
            updates["validation_frame"] = apply_encoding_state(
                p.validation_frame, state)
        self.params = p.clone(**updates)
        return state

    def _apply_custom_metric(self, model: Model) -> None:
        """One extra scoring pass evaluating the user's metric UDF, attached
        to the training metrics — `hex/CMetricScoringTask` role."""
        cmf = getattr(self.params, "custom_metric_func", None)
        if isinstance(cmf, str) and cmf.startswith("python:"):
            # wire-uploaded UDF reference (`water/udf/CFuncRef` format)
            from .custom_udf import resolve_custom_metric

            cmf = resolve_custom_metric(cmf)
        m = model.output.training_metrics
        if not callable(cmf) or m is None or not self.supervised:
            return
        fr = self.params.training_frame
        try:
            X = model.adapt_frame(fr)
            raw = np.asarray(model.score0(X))[: fr.nrow]
        except NotImplementedError:
            return
        y = fr.vec(self.params.response_column).to_numpy()
        w = (np.nan_to_num(fr.vec(self.params.weights_column).to_numpy())
             if self.params.weights_column else np.ones(fr.nrow, np.float32))
        name, value = cmf(y, raw, w)
        m.custom_metric_name = name
        m.custom_metric_value = float(value)

    # -- cross-validation (`hex/ModelBuilder.java:614`) -----------------------
    def _train_with_cv(self, job: Job) -> Model:
        p = self.params
        fr = p.training_frame
        folds = self._fold_assignment(fr)
        nf = int(folds.max()) + 1
        cv_models, holdout_metrics = [], []
        holdout_preds = None  # (nrow, pred_cols) assembled across folds
        for f in range(nf):
            job.check_cancelled()
            tr_idx = np.where(folds != f)[0]
            va_idx = np.where(folds == f)[0]
            tr = _subset_frame(fr, tr_idx)
            va = _subset_frame(fr, va_idx)
            sub = type(self)(p.clone(training_frame=tr, validation_frame=None,
                                     nfolds=0, fold_column=None))
            fold_job = Job(f"cv_{f}", work=1.0)
            fold_job.deadline = job.deadline  # folds share the outer budget
            m = sub.build_impl(fold_job)
            holdout_metrics.append(m.model_performance(va))
            if p.keep_cross_validation_predictions:
                pf = m.predict(va)
                cols = np.stack([pf.vec(i).to_numpy() for i in range(pf.ncol)],
                                axis=1)
                if holdout_preds is None:
                    holdout_preds = np.full((fr.nrow, pf.ncol), np.nan,
                                            dtype=np.float32)
                    holdout_preds_names = pf.names
                    # the reference also keeps the N per-fold prediction
                    # frames (full-length, zero outside the fold) behind
                    # keep_cross_validation_predictions
                    fold_pred_frames = []
                holdout_preds[va_idx] = cols
                full = np.zeros((fr.nrow, pf.ncol), dtype=np.float32)
                full[va_idx] = cols
                fold_pred_frames.append(Frame(
                    list(pf.names),
                    [Vec.from_numpy(full[:, j])
                     for j in range(pf.ncol)]))
            cv_models.append(m)
        main = self.build_impl(job)
        main.output.cross_validation_metrics = _mean_metrics(holdout_metrics)
        if p.keep_cross_validation_models:
            main.output.cv_models = cv_models
        from ..backend.kvstore import STORE, make_key

        if holdout_preds is not None:
            hp = Frame(list(holdout_preds_names),
                       [Vec.from_numpy(holdout_preds[:, j])
                        for j in range(holdout_preds.shape[1])],
                       key=make_key("cv_holdout_prediction"))
            STORE.put_keyed(hp)  # fetchable over the wire by key
            main.output.cv_holdout_predictions = hp
            for i, fp in enumerate(fold_pred_frames):
                fp.key = make_key(f"cv_{i + 1}_prediction")
                STORE.put_keyed(fp)
            main.output.cv_fold_predictions = fold_pred_frames
        if p.keep_cross_validation_fold_assignment:
            # `ModelBase.cross_validation_fold_assignment` — the per-row
            # fold index as a one-column frame
            fa = Frame(["fold_assignment"],
                       [Vec.from_numpy(folds.astype(np.float32))],
                       key=make_key("cv_fold_assignment"))
            STORE.put_keyed(fa)
            main.output.cv_fold_assignment = fa
        return main

    def _fold_assignment(self, fr: Frame) -> np.ndarray:
        p = self.params
        if p.fold_column:
            return fr.vec(p.fold_column).to_numpy().astype(np.int64)
        n = fr.nrow
        scheme = p.fold_assignment.upper()
        rng = np.random.default_rng(None if p.seed in (-1, None) else p.seed)
        if scheme == "MODULO":
            return np.arange(n) % p.nfolds
        if scheme == "STRATIFIED" and self.supervised:
            y = fr.vec(p.response_column).to_numpy()
            out = np.zeros(n, dtype=np.int64)
            for cls in np.unique(y[~np.isnan(y)]):
                idx = np.where(y == cls)[0]
                out[idx] = rng.permutation(len(idx)) % p.nfolds
            return out
        return rng.integers(0, p.nfolds, size=n)


def resume_training(recovery_dir: str) -> Model:
    """Restart a killed training job from its auto-recovery directory and
    train it to completion — the preemption-recovery entry point.

    Loads the builder class, original params (frames rehydrated from the
    recovery dir) and the latest checkpointed iteration state, then replays
    the remaining iterations. Because every RNG stream is indexed by global
    iteration (not process history) and the checkpoint captured the exact
    carried device state, the produced model is **bit-equal** to the one
    the uninterrupted run would have built — pinned by the
    kill-at-every-interval tests in tests/test_recovery.py.

    Raises ``ValueError`` when the dir holds no training manifest or the
    recorded job already completed (the manifest then names ``model_key``)."""
    import dataclasses as _dc

    from ..backend.persist import TrainingRecovery

    builder_cls, params, state, manifest = TrainingRecovery.load(recovery_dir)
    if manifest.get("completed"):
        raise ValueError(
            f"training in {recovery_dir} already completed "
            f"(model {manifest.get('model_key')!r}) — nothing to resume")
    params = _dc.replace(params, auto_recovery_dir=recovery_dir)
    builder = builder_cls(params)
    builder._resume_state = state  # None -> replays from the start
    return builder.train_model()


def _subset_frame(fr: Frame, idx: np.ndarray) -> Frame:
    return fr.take(idx)


def _mean_metrics(ms: list):
    if not ms:
        return None
    out = ms[0]
    for fname in ("mse", "rmse", "mae", "auc", "logloss", "r2",
                  "mean_per_class_error"):
        vals = [getattr(m, fname) for m in ms if hasattr(m, fname)]
        vals = [v for v in vals if v is not None and not np.isnan(v)]
        if vals and hasattr(out, fname):
            setattr(out, fname, float(np.mean(vals)))
    # combined CV metrics must not publish per-cluster stats — the
    # reference's ModelMetricsClustering for pooled folds has no
    # centroid_stats (pyunit_kmeans_cv pins this as null on the wire)
    out._cv_combined = True
    return out
