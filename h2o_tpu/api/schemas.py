"""Schema v3 DTO builders — the wire shapes of `water/api/schemas3/`.

The reference reflectively copies impl fields into versioned Schema objects
(`water/api/Schema.java:23-45`); here each builder function produces the JSON
dict for one schema class, keeping the reference's field names (frame_id,
column `data`/`domain`, job `status`/`progress`, model `algo`/`output`) so a
schema-v3 client recognises the payloads.
"""

from __future__ import annotations

import math

import numpy as np

from ..backend.jobs import Job
from ..frame.frame import Frame


def _clean(x):
    """JSON-safe scalar (NaN/inf → None, numpy → python)."""
    if isinstance(x, (np.floating, float)):
        x = float(x)
        return None if (math.isnan(x) or math.isinf(x)) else x
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.bool_,)):
        return bool(x)
    if isinstance(x, np.ndarray):
        return [_clean(v) for v in x.tolist()]
    if isinstance(x, dict):
        return {k: _clean(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_clean(v) for v in x]
    return x


def key_schema(key: str, type_: str = "Key") -> dict:
    return {"name": key, "type": type_, "URL": None}


def col_summary(name: str, vec, npreview: int = 0) -> dict:
    """`water/api/schemas3/FrameV3.ColV3`."""
    out = {"label": name, "type": vec.type, "missing_count": None,
           "domain": vec.domain, "domain_cardinality": vec.cardinality(),
           "mean": None, "sigma": None, "mins": [], "maxs": [], "data": None}
    if vec.data is not None:
        r = vec.rollups()
        out.update(missing_count=int(r.nacnt), mean=_clean(r.mean),
                   sigma=_clean(r.sigma), mins=[_clean(r.mins)],
                   maxs=[_clean(r.maxs)])
    else:
        out["missing_count"] = int(sum(1 for x in vec.host_data if x is None))
    if npreview:
        if vec.is_string():
            out["string_data"] = [None if x is None else str(x)
                                  for x in vec.host_data[:npreview]]
        else:
            out["data"] = _clean(vec.to_numpy()[:npreview])
    return out


def frame_schema(fr: Frame, npreview: int = 0) -> dict:
    """`water/api/schemas3/FrameV3` (summary form)."""
    return {
        "frame_id": key_schema(fr.key, "Key<Frame>"),
        "rows": fr.nrow,
        "num_columns": fr.ncol,
        "byte_size": sum((v.plen * 4) for v in fr.vecs if v.data is not None),
        "is_text": False,
        "columns": [col_summary(n, fr.vec(n), npreview) for n in fr.names],
    }


def frame_base(fr: Frame) -> dict:
    return {"frame_id": key_schema(fr.key, "Key<Frame>"), "rows": fr.nrow,
            "num_columns": fr.ncol}


def job_schema(job: Job) -> dict:
    """`water/api/schemas3/JobV3` (status names match `water/Job.java`)."""
    return {
        "key": key_schema(job.key, "Key<Job>"),
        "description": job.description,
        "status": job.status,
        "progress": _clean(job.progress),
        "progress_msg": job.progress_msg,
        "start_time": int(job.start_time * 1000),
        "msec": int(((job.end_time or job.start_time) - job.start_time) * 1000),
        "dest": key_schema(job.dest_key) if job.dest_key else None,
        "exception": None if job.exception is None else repr(job.exception),
        "stacktrace": job.traceback,
        "tenant": job.tenant,
        "priority": job.priority,
    }


def table_schema(t) -> dict | None:
    """`water/api/schemas3/TwoDimTableV3` (compact form)."""
    if t is None:
        return None
    return {"name": t.table_header, "description": t.description,
            "columns": [{"name": h, "type": ty}
                        for h, ty in zip(t.col_header, t.col_types)],
            "data": _clean([[r[i] for r in t.cell_values]
                            for i in range(len(t.col_header))])}


def scoring_history_schema(history) -> dict | None:
    """`ScoringHistoryV3` analog: one column-oriented table over the model's
    scoring snapshots — iteration markers kept verbatim, the per-snapshot
    metrics object flattened to `training_<metric>` columns (the column
    names h2o-py's learning_curve_plot reads off the wire)."""
    if not history:
        return None
    cols: dict[str, list] = {}
    metric_keys = ("rmse", "mse", "mae", "logloss", "auc", "pr_auc",
                   "mean_per_class_error", "r2", "residual_deviance",
                   "null_deviance")
    for i, h in enumerate(history):
        row: dict = {}
        for k, v in h.items():
            if isinstance(v, (int, float, str)) or v is None:
                row[k] = _clean(v)  # NaN/inf -> null (strict-JSON clients)
        for prefix, mobj in (("training", h.get("training_metrics")),
                             ("validation", h.get("validation_metrics"))):
            if mobj is None:
                continue
            for mk in metric_keys:
                v = getattr(mobj, mk, None)
                if v is not None:
                    row[f"{prefix}_{mk}"] = _clean(v)
            dev = getattr(mobj, "mean_residual_deviance",
                          getattr(mobj, "mse", None))
            if dev is not None:
                row[f"{prefix}_deviance"] = _clean(dev)
        for k in set(cols) | set(row):
            cols.setdefault(k, [None] * i).append(row.get(k))
    return cols


def metrics_schema(m) -> dict | None:
    if m is None:
        return None
    out = {}
    for f in ("mse", "rmse", "mae", "r2", "auc", "pr_auc", "logloss",
              "mean_per_class_error", "ks", "null_deviance",
              "residual_deviance", "aic", "gini"):
        v = getattr(m, f, None)
        if v is not None:
            # wire casing follows the reference schemas exactly
            # (ModelMetricsBaseV3.java:50 RMSE/MSE, BinomialV3 AUC/Gini/AIC)
            wire = {"auc": "AUC", "aic": "AIC", "mse": "MSE",
                    "rmse": "RMSE", "gini": "Gini"}.get(f, f)
            out[wire] = _clean(v)
    cmn = getattr(m, "custom_metric_name", None)
    if cmn is not None:
        out["custom_metric_name"] = cmn
        out["custom_metric_value"] = _clean(
            getattr(m, "custom_metric_value", None))
    cm = getattr(m, "confusion_matrix", None)
    if cm is not None:
        out["cm"] = {"table": _clean(cm)}
    for f in ("gains_lift_table", "max_criteria_and_metric_scores"):
        t = getattr(m, f, None)
        if t is not None:
            out[f] = table_schema(t)
    if hasattr(m, "tot_withinss"):
        # `ModelMetricsClusteringV3`: SS decomposition + per-cluster stats;
        # combined CV metrics carry centroid_stats = null (the reference
        # cannot pool per-cluster rows across folds)
        out["totss"] = _clean(m.totss)
        out["tot_withinss"] = _clean(m.tot_withinss)
        out["betweenss"] = _clean(m.betweenss)
        if getattr(m, "sizes", None) is not None and not getattr(
                m, "_cv_combined", False):
            out["centroid_stats"] = {
                "name": "Centroid Statistics",
                "columns": [{"name": "centroid", "type": "int"},
                            {"name": "size", "type": "double"},
                            {"name": "within_cluster_sum_of_squares",
                             "type": "double"}],
                "data": [
                    list(range(1, len(np.asarray(m.sizes)) + 1)),
                    _clean(np.asarray(m.sizes)),
                    _clean(np.asarray(m.withinss))]}
        else:
            out["centroid_stats"] = None
    ts = getattr(m, "thresholds_and_metric_scores", None)
    if ts is not None:
        # downsample the 1024-bin per-threshold arrays (stride 8 → 128 rows):
        # the full resolution lives on the model; the wire payload only feeds
        # client-side threshold lookups, where 1/128 granularity suffices
        out["thresholds_and_metric_scores"] = {
            k: _clean(np.asarray(v)[::8]) for k, v in ts.items()}
    return out


def serving_model_schema(info: dict) -> dict:
    """Wire shape of a serving registration (`POST /3/Serving/models/{id}`):
    the ServedModel.info() dict plus a key ref, JSON-cleaned."""
    out = _clean(dict(info))
    out["serving_model_id"] = key_schema(info["model_id"],
                                         "Key<ServingModel>")
    return out


def serving_stats_schema(stats: dict) -> dict:
    """Wire shape of `GET /3/Serving/stats`: {model_id: snapshot} from
    `serving/stats.py`, JSON-cleaned (NaN-free percentiles)."""
    return {"models": _clean(stats)}


def serving_route_schema(stats: dict) -> dict:
    """Wire shape of a serving route (`/3/Serving/routes/{endpoint}`):
    the Route.stats() dict — endpoint, seed, request count, per-variant
    weights/counters/divergence — JSON-cleaned."""
    return _clean(dict(stats))


def model_schema(model) -> dict:
    """`water/api/schemas3/ModelSchemaV3` (summary form)."""
    o = model.output
    out = {
        "model_id": key_schema(model.key, "Key<Model>"),
        "algo": getattr(model, "algo_override", None) or model.algo_name,
        "algo_full_name": getattr(model, "algo_override", None)
        or model.algo_name,
        "response_column_name": getattr(model.params, "response_column", None),
        "output": {
            "model_category": o.model_category,
            "names": o.names,
            "domains": _clean([o.domains.get(n) for n in o.names]),
            "response_domain": o.response_domain,
            "training_metrics": metrics_schema(o.training_metrics),
            "validation_metrics": metrics_schema(o.validation_metrics),
            "cross_validation_metrics": metrics_schema(o.cross_validation_metrics),
            "cross_validation_models": (
                [key_schema(m.key, "Key<Model>") for m in o.cv_models]
                if getattr(o, "cv_models", None) else None),
            "variable_importances": _clean(o.variable_importances),
            "scoring_history_length": len(o.scoring_history),
            "scoring_history": scoring_history_schema(o.scoring_history),
            "run_time_ms": o.run_time_ms,
        },
    }
    if getattr(o, "num_iterations", None) is not None:
        out["output"]["num_iterations"] = int(o.num_iterations)
    import dataclasses as _dc

    if _dc.is_dataclass(model.params):
        # `ModelSchemaV3.parameters` — actual vs default values per param
        # (h2o-py `model.parms[name]['actual_value']` reads these)
        plist = []
        for fld in _dc.fields(model.params):
            v = getattr(model.params, fld.name)
            if isinstance(v, Frame) or hasattr(v, "vecs"):
                v = {"name": getattr(v, "key", None)}
            default = None if fld.default is _dc.MISSING else fld.default
            if fld.default_factory is not _dc.MISSING:  # type: ignore
                default = fld.default_factory()
            plist.append({"name": fld.name, "label": fld.name,
                          "actual_value": _clean(v),
                          "default_value": _clean(default)})
        out["parameters"] = plist

    def _frame_ref(frobj):
        return {"name": frobj.key, "type": "Key<Frame>",
                "URL": f"/3/Frames/{frobj.key}"}

    if getattr(o, "cv_holdout_predictions", None) is not None \
            and o.cv_holdout_predictions.key:
        out["output"]["cross_validation_holdout_predictions_frame_id"] = \
            _frame_ref(o.cv_holdout_predictions)
    if getattr(o, "cv_fold_predictions", None):
        out["output"]["cross_validation_predictions"] = [
            _frame_ref(f) for f in o.cv_fold_predictions if f.key]
    if getattr(o, "cv_fold_assignment", None) is not None:
        out["output"]["cross_validation_fold_assignment_frame_id"] = \
            _frame_ref(o.cv_fold_assignment)
    if getattr(o, "weights_keys", None):
        # `DeepLearningModelOutputV3.weights/biases` — frame key refs with
        # the /3/Frames URL h2o-py's model.weights() splits apart
        out["output"]["weights"] = [
            {"name": k, "type": "Key<Frame>", "URL": f"/3/Frames/{k}"}
            for k in o.weights_keys]
        out["output"]["biases"] = [
            {"name": k, "type": "Key<Frame>", "URL": f"/3/Frames/{k}"}
            for k in o.biases_keys]
    if hasattr(model, "centers"):  # clustering: KMeansModelOutputV3.centers
        import numpy as _np

        c = _np.asarray(model.centers)
        # centers live in the EXPANDED feature space (one-hot categoricals),
        # which may be wider than the input names
        cnames = list(o.names) if len(o.names) == c.shape[1] else \
            [f"C{j + 1}" for j in range(c.shape[1])]
        out["output"]["centers"] = {
            "name": "Cluster means",
            "columns": [{"name": n, "type": "double"} for n in cnames],
            "data": _clean([c[:, j].tolist() for j in range(c.shape[1])])}
    if hasattr(model, "coef"):  # GLM-family: `hex/schemas/GLMModelV3`
        try:
            coefs = model.coef()
        except Exception as e:  # keep /3/Models listing alive, but visibly
            from ..utils.log import warn

            warn(f"coefficients_table for {model.key}: {e!r}")
            coefs = None
        flat = coefs and not any(isinstance(v, dict) for v in coefs.values())
        if flat:  # multinomial's {class: {coef: v}} ships per-class instead
            tbl = {"names": list(coefs), "coefficients": _clean(
                list(coefs.values()))}
            if hasattr(model, "coef_norm"):
                tbl["standardized_coefficients"] = _clean(
                    list(model.coef_norm().values()))
            for attr, col in (("std_errs", "std_errs"),
                              ("z_values", "z_values"),
                              ("p_values", "p_values")):
                d = getattr(model, attr, None)
                if d:
                    tbl[col] = _clean([d.get(n) for n in coefs])
            out["output"]["coefficients_table"] = tbl
        elif coefs:
            # `coefficients_table_multinomials_with_class_names` role: one
            # coefficient list per response class (`GLMModelV3.java:33`)
            any_class = next(iter(coefs.values()))
            out["output"]["coefficients_table_multinomial"] = {
                "names": list(any_class),
                "classes": list(coefs),
                "coefficients": [
                    _clean([coefs[k].get(n) for n in any_class])
                    for k in coefs]}
        disp = getattr(model, "dispersion_estimated", None)
        if disp is not None:
            out["output"]["dispersion"] = _clean(disp)
    return out


# ---------------------------------------------------------------------------
# fleet observability plane (PR 13)
# ---------------------------------------------------------------------------
def program_schema(pid: str, rec: dict) -> dict:
    """One `/3/Programs` entry, JSON-cleaned: the wire shape tools parse
    (the bench sidecar's program block uses the compact subset of these
    field names, so a consumer learns ONE schema)."""
    return {"program_id": pid, "kind": rec.get("kind"),
            "name": rec.get("name"), "module": rec.get("module"),
            "labels": _clean(rec.get("labels")),
            "flops": _clean(rec.get("flops")),
            "bytes_accessed": _clean(rec.get("bytes_accessed")),
            "memory": _clean(rec.get("memory")),
            "dispatch_count": rec.get("dispatch_count"),
            "wall": _clean(rec.get("wall")),
            "device": _clean(rec.get("device")),
            "registered_ms": rec.get("registered_ms")}


def programs_schema(snapshot: dict, last_fold) -> dict:
    """The full `GET /3/Programs` payload: the records, and what the last
    capture folded in read by XLA module (`programs.fold_capture`: the
    declared programs with and without a record, the undeclared by name;
    null before any capture)."""
    return {"programs": {pid: program_schema(pid, rec)
                         for pid, rec in snapshot.items()},
            "count": len(snapshot),
            "capture": _clean(last_fold)}


# ---------------------------------------------------------------------------
# causal observability plane (PR 15)
# ---------------------------------------------------------------------------
def health_schema(snap: dict) -> dict:
    """The `GET /3/Health` payload (utils/health.py snapshot), JSON-
    cleaned. ``ready``/``live`` and the typed ``degraded`` reasons are
    the contract autoscalers and rollout gates switch on; ``checks`` and
    the per-SLO ``slo`` burn block carry the supporting numbers."""
    return _clean(dict(snap))


def workload_schema(snap: dict) -> dict:
    """The `GET /3/Workload` payload (workload/manager.py snapshot),
    JSON-cleaned. ``tenants`` carries weights/quotas/counters, ``entries``
    the per-job scheduler state (QUEUED/RUNNING/PARKED/FINISHED) and
    ``slots``/``seed`` the dispatch configuration."""
    return _clean(dict(snap))


def slow_traces_schema(traces: list, total: int) -> dict:
    """The `GET /3/SlowTraces` payload: the tail-capture ring (each entry
    = SLO verdict + full span tree + program dispatch walls), plus the
    ever-captured total so a poller can detect rotation."""
    return {"slow_traces": _clean(list(traces)),
            "count": len(traces),
            "total_captured": int(total)}
