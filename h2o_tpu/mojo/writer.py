"""MOJO export — `hex/ModelMojoWriter.java` + per-algo writers analog.

Produces zips readable by the reference's standalone scorers
(`hex/genmodel/algos/{gbm,drf,glm,kmeans}`): GBM/DRF tree bytecode + aux
blobs named `trees/t%02d_%03d.bin` (`hex/tree/SharedTreeMojoWriter.java:81`),
GLM coefficient kv layout (`hex/genmodel/algos/glm/GlmMojoReader.java:19-41`),
KMeans standardized centers (`hex/genmodel/algos/kmeans/KMeansMojoReader.java`).

Conversion notes (engine -> MOJO semantics):
- Engine trees send x <= thr left; MOJO sends x >= splitVal right, so
  splitVal = nextafter(thr, +inf) (see format.encode_tree).
- DRF: the MOJO scorer averages raw leaf sums and sets p0 = preds[1]/T
  (`hex/genmodel/algos/drf/DrfMojoModel.java:38-58`), while the engine stores
  class-1 leaf probabilities plus a shared intercept f0 — leaves are
  rewritten (1 - leaf - f0 for binomial, leaf + f0 otherwise) so both paths
  produce identical numbers.
- Multinomial GBM: the per-class intercept f0[k] is folded into the first
  tree group's leaves (softmax is not shift-invariant per class, so the
  fold-in must happen exactly once).
- GLM: engine beta lives on the standardized scale; exported beta is
  destandardized (beta/sigma, intercept -= sum(beta*mean/sigma)) because the
  MOJO scorer only mean-imputes, never standardizes.
"""

from __future__ import annotations

import uuid as _uuid

import numpy as np

from .format import MojoZipWriter, build_model_ini, encode_tree, escape_line

_GBM_LINKS = {
    "bernoulli": "logit", "quasibinomial": "logit",
    "poisson": "log", "gamma": "log", "tweedie": "tweedie",
    "negativebinomial": "log",
}
_GLM_LINKS = {  # family link name -> LinkFunctionType name
    "identity": "identity", "logit": "logit", "log": "log",
    "inverse": "inverse", "tweedie": "tweedie",
}


def export_mojo(model, path: str) -> str:
    """Write `model` to `path` as a MOJO zip; returns the path."""
    algo = model.algo_name
    if algo in ("gbm", "drf", "xrt"):
        _write_tree_mojo(model, path)
    elif algo == "glm":
        _write_glm_mojo(model, path)
    elif algo == "kmeans":
        _write_kmeans_mojo(model, path)
    elif algo == "deeplearning":
        _write_deeplearning_mojo(model, path)
    elif algo in ("isolationforest", "extendedisolationforest"):
        _write_isofor_mojo(model, path)
    elif algo == "pca":
        _write_pca_mojo(model, path)
    elif algo == "coxph":
        _write_coxph_mojo(model, path)
    elif algo in ("isotonic", "isotonicregression"):
        _write_isotonic_mojo(model, path)
    elif algo == "word2vec":
        _write_word2vec_mojo(model, path)
    elif algo == "glrm":
        _write_glrm_mojo(model, path)
    elif algo == "targetencoder":
        _write_targetencoder_mojo(model, path)
    elif algo == "upliftdrf":
        _write_uplift_mojo(model, path)
    elif algo == "gam":
        _write_gam_mojo(model, path)
    elif algo == "rulefit":
        _write_rulefit_mojo(model, path)
    elif algo == "psvm":
        _write_psvm_mojo(model, path)
    elif algo == "stackedensemble":
        _write_ensemble_mojo(model, path)
    else:
        raise NotImplementedError(f"MOJO export not implemented for '{algo}'")
    return path


# ---------------------------------------------------------------------------
def _common_info(model, algo, algo_full, category, n_classes, columns,
                 domains, mojo_version):
    return {
        "h2o_version": "tpu-0.1.0",
        "mojo_version": mojo_version,
        "license": "Apache License Version 2.0",
        "algo": algo,
        "algorithm": algo_full,
        "endianness": "LITTLE_ENDIAN",
        "category": category,
        "uuid": str(_uuid.uuid4()),
        "supervised": category != "Clustering",
        "n_features": len(columns) - (0 if category == "Clustering" else 1),
        "n_classes": n_classes,
        "n_columns": len(columns),
        "n_domains": sum(d is not None for d in domains),
        "balance_classes": False,
        # a rapids model.reset.threshold must survive export
        "default_threshold": float(getattr(model, "default_threshold", 0.5)),
        "prior_class_distrib": "null",
        "model_class_distrib": "null",
        "timestamp": "1970-01-01 00:00:00",
        "escape_domain_values": True,
    }


def _write_common(zw, info, columns, domains):
    zw.write_text("model.ini", build_model_ini(info, columns, domains))
    di = 0
    for dom in domains:
        if dom is not None:
            zw.write_text(f"domains/d{di:03d}.txt",
                          "\n".join(escape_line(str(x)) for x in dom) + "\n")
            di += 1


def _supervised_columns(model):
    names = list(model.output.names)
    resp = model.params.response_column
    columns = names + [resp]
    domains = [model.output.domains.get(n) for n in names]
    domains.append(model.output.response_domain)
    return columns, domains



def _datainfo_spec(di) -> tuple[list, list, dict]:
    """(cats+nums column order, domains, info keys) for writers that must
    replay DataInfo.expand in the standalone scorer — single source of truth
    shared by the GLM/DL/PCA writers."""
    cats = [n for n in di.names if n in di.domains]
    nums = [n for n in di.names if n not in di.domains]
    lo = 0 if di.use_all_factor_levels else 1
    cat_offsets = [0]
    for n in cats:
        cat_offsets.append(cat_offsets[-1] + len(di.domains[n]) - lo)
    columns = cats + nums
    domains = [di.domains[n] for n in cats] + [None] * len(nums)
    info = {
        "use_all_factor_levels": di.use_all_factor_levels,
        "cats": len(cats),
        "cat_modes": [di.cat_modes[n] for n in cats],
        "cat_offsets": cat_offsets,
        "nums": len(nums),
        "num_means": [di.num_means[n] for n in nums],
        "num_sigmas": [di.num_sigmas[n] for n in nums],
        "standardize": di.standardize,
        "center": di.effective_center,
    }
    return columns, domains, info


# ---------------------------------------------------------------------------
def _write_tree_mojo(model, path: str):
    out = model.output
    category = out.model_category
    n_classes = {"Regression": 1, "Binomial": 2}.get(
        category, len(out.response_domain or []))
    columns, domains = _supervised_columns(model)

    feat = np.asarray(model.forest["feat"])
    thr = np.asarray(model.forest["thr"])
    nanL = np.asarray(model.forest["nanL"])
    val = np.asarray(model.forest["val"]).astype(np.float64)
    # categorical set-split routing tables -> reference bitset splits
    catd, iscat, nedges, cards = model.set_split_arrays_np()
    multi = feat.ndim == 3
    T = feat.shape[0]
    K = feat.shape[1] if multi else 1
    drf = model.cfg.drf_mode
    f0 = np.asarray(model.f0, dtype=np.float64)

    # Rewrite leaves so the reference scorer's combination rule reproduces
    # the engine's predictions exactly (see module docstring).
    leaves = feat < 0
    if drf:
        if category == "Binomial":
            val = np.where(leaves, 1.0 - val - float(f0), val)
        else:  # regression mean / multinomial per-class probs
            val = np.where(leaves, val + (f0[None, :, None] if multi else
                                          float(f0)), val)
        init_f = 0.0
    elif multi:  # multinomial GBM: fold f0[k] into the first tree group
        val = val.copy()
        val[0] = np.where(leaves[0], val[0] + f0[:, None], val[0])
        init_f = 0.0
    else:
        init_f = float(f0)

    algo = "drf" if drf else "gbm"
    full = "Distributed Random Forest" if drf else "Gradient Boosting Machine"
    info = _common_info(model, algo, full, category, n_classes, columns,
                        domains, mojo_version=1.30)
    info["n_trees"] = T
    info["n_trees_per_class"] = K
    if drf:
        info["binomial_double_trees"] = False
    else:
        info["distribution"] = model.dist.name
        info["init_f"] = init_f
        info["link_function"] = _GBM_LINKS.get(model.dist.name, "identity")

    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    for j in range(T):
        for i in range(K):
            tree = (feat[j, i], thr[j, i], nanL[j, i], val[j, i]) if multi \
                else (feat[j], thr[j], nanL[j], val[j])
            cd = None if catd is None else (catd[j, i] if multi else catd[j])
            blob, aux = encode_tree(*tree, catd=cd, iscat=iscat,
                                    nedges=nedges, cards=cards)
            zw.write_blob(f"trees/t{i:02d}_{j:03d}.bin", blob)
            zw.write_blob(f"trees/t{i:02d}_{j:03d}_aux.bin", aux)
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_glm_mojo(model, path: str):
    out = model.output
    category = out.model_category
    if type(model).__name__ == "GLMOrdinalModel":
        raise NotImplementedError(
            "ordinal GLM MOJO export: follow-up (needs a threshold spec; the "
            "reference's GlmOrdinalMojoModel)")
    di = model.dinfo
    if getattr(di, "products", ()):
        raise NotImplementedError(
            "GLM MOJO export of numeric interactions formed in the IRLS "
            "step: the MOJO has no product columns to read them from")
    cats = [n for n, c in zip(di.names, di.is_cat) if c]
    nums = [n for n, c in zip(di.names, di.is_cat) if not c]
    columns = cats + nums + [model.params.response_column]
    domains = [di.domains[n] for n in cats] + [None] * len(nums)
    domains.append(out.response_domain)

    lo = 0 if di.use_all_factor_levels else 1
    cat_offsets = [0]
    for n in cats:
        cat_offsets.append(cat_offsets[-1] + len(di.domains[n]) - lo)
    ncat_coefs = cat_offsets[-1]

    from ..models.glm import _destandardize

    beta_out = _destandardize(np.asarray(model.beta, dtype=np.float64), di)
    means = np.array([di.num_means[n] for n in nums])

    n_classes = {"Regression": 1, "Binomial": 2}.get(
        category, len(out.response_domain or []))
    info = _common_info(model, "glm", "Generalized Linear Modeling", category,
                        n_classes, columns, domains, mojo_version=1.00)
    info.update({
        "use_all_factor_levels": di.use_all_factor_levels,
        "cats": len(cats),
        "cat_modes": [di.cat_modes[n] for n in cats],
        "cat_offsets": cat_offsets,
        "nums": len(nums),
        "num_means": list(means),
        # The engine always imputes at predict time (DataInfo.expand imputes
        # in both MeanImputation and Skip modes; Skip only downweights
        # training rows) — so the standalone scorer must impute too.
        "mean_imputation": True,
        # multinomial: beta is the flattened (K, P+1) class-major matrix
        # (`GlmMultinomialMojoReader` layout role)
        "beta": list(beta_out.ravel()),
        "family": model.family.name,
        "link": _GLM_LINKS.get(model.family.link_name, "identity"),
        "tweedie_link_power": getattr(model.family, "tweedie_link_power", 0.0),
        "dispersion_estimated": 1.0,
    })
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_kmeans_mojo(model, path: str):
    di = model.dinfo
    if any(di.is_cat):
        raise NotImplementedError(
            "KMeans MOJO export supports numeric features only (categorical "
            "columns use one-hot distance in the engine, which has no "
            "equivalent in the reference's kmeans MOJO scorer)")
    columns = list(di.names)
    domains = [None] * len(columns)
    info = _common_info(model, "kmeans", "K-means", "Clustering", 1,
                        columns, domains, mojo_version=1.00)
    info["supervised"] = False
    info["n_features"] = len(columns)
    centers = np.asarray(model.centers_std, dtype=np.float64)
    info["standardize"] = di.standardize
    # Means are written even without standardization: the engine imputes NAs
    # with column means at predict time regardless (DataInfo.expand), so the
    # standalone scorer needs them to reproduce engine behavior. The
    # reference reader only consumes them when standardize=true; ours uses
    # them for imputation in both modes.
    info["standardize_means"] = [di.num_means[n] for n in columns]
    info["standardize_modes"] = [-1] * len(columns)
    if di.standardize:
        info["standardize_mults"] = [1.0 / di.num_sigmas[n] for n in columns]
    info["center_num"] = centers.shape[0]
    for i in range(centers.shape[0]):
        info[f"center_{i}"] = list(centers[i])
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_deeplearning_mojo(model, path: str):
    """DeepLearning MOJO — the `hex/genmodel/algos/deeplearning/
    DeeplearningMojoWriter` layout: per-layer weight/bias blobs plus the
    input-normalization spec (cats offsets + numeric means/sigmas) so the
    standalone scorer reproduces DataInfo.expand exactly."""
    di = model.dinfo
    out = model.output
    category = out.model_category
    if category == "AutoEncoder":
        raise NotImplementedError("autoencoder MOJO export not supported "
                                  "(the reference exports supervised DL only)")
    n_classes = {"Regression": 1, "Binomial": 2}.get(
        category, len(out.response_domain or []))
    # columns in DataInfo order (cats first) — the scorer indexes by position
    feat_cols, feat_doms, di_info = _datainfo_spec(di)
    columns = feat_cols + [model.params.response_column]
    domains = feat_doms + [out.response_domain]
    net = model.net
    info = _common_info(model, "deeplearning", "Deep Learning", category,
                        n_classes, columns, domains, mojo_version=1.00)
    info.update(di_info)
    info.update({
        "activation": model.params.activation,
        "n_layers": len(net),
        # H2O-style layer widths: maxout layers report post-max units
        "units": ([int(np.asarray(net[0]["W"]).shape[0])]
                  + [int(np.asarray(l["b"]).shape[0])
                     // (2 if (model.params.activation.lower()
                               .startswith("maxout") and i < len(net) - 1)
                         else 1)
                     for i, l in enumerate(net)]),
    })
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    for i, layer in enumerate(net):
        zw.write_blob(f"weights/w{i:02d}.bin",
                      np.asarray(layer["W"], dtype="<f4").tobytes())
        zw.write_blob(f"weights/b{i:02d}.bin",
                      np.asarray(layer["b"], dtype="<f4").tobytes())
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_isofor_mojo(model, path: str):
    """Isolation Forest MOJO — `hex/genmodel/algos/isofor` role. The engine's
    (extended) trees are hyperplane splits; blobs carry the per-node split
    vectors/thresholds and per-node sample counts, and the scorer reproduces
    2^(−E[pathlen]/c(n))."""
    out = model.output
    columns = list(out.names)
    domains = [out.domains.get(n) for n in columns]
    wvec, thr, is_split, counts = (np.asarray(a) for a in model.forest)
    info = _common_info(model, model.algo_name, "Isolation Forest",
                        "AnomalyDetection", 1, columns, domains,
                        mojo_version=1.00)
    info.update({
        "supervised": False,
        "n_features": len(columns),
        "n_trees": int(wvec.shape[0]),
        "n_nodes": int(wvec.shape[1]),
        "max_depth": int(model.depth),
        "sample_size": int(model.sample_size),
    })
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_blob("isofor/wvec.bin", wvec.astype("<f4").tobytes())
    zw.write_blob("isofor/thr.bin", thr.astype("<f4").tobytes())
    zw.write_blob("isofor/is_split.bin",
                  is_split.astype(np.uint8).tobytes())
    zw.write_blob("isofor/counts.bin", counts.astype("<f4").tobytes())
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_pca_mojo(model, path: str):
    """PCA MOJO — `hex/genmodel/algos/pca/PCAMojoWriter` role: the expanded-
    space eigenvector matrix + the DataInfo input spec, so the standalone
    scorer reproduces `(expand(x) − μ) @ V`."""
    di = model.dinfo
    columns, domains, di_info = _datainfo_spec(di)

    V = np.asarray(model.V, dtype=np.float64)      # (P, k)
    mu = np.asarray(model.mu, dtype=np.float64)
    if mu.ndim == 0:
        mu = np.full(V.shape[0], float(mu))
    info = _common_info(model, "pca", "Principal Components Analysis",
                        "DimReduction", 1, columns, domains, mojo_version=1.00)
    info.update(di_info)
    info.update({
        "supervised": False,
        "n_features": len(columns),
        "k": int(V.shape[1]),
    })
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_blob("pca/eigenvectors.bin", V.astype("<f8").tobytes())
    zw.write_blob("pca/mu.bin", mu.astype("<f8").tobytes())
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_coxph_mojo(model, path: str):
    """CoxPH MOJO — `hex/genmodel/algos/coxph/CoxPHMojoWriter` role: the
    coefficient vector + the centering means; the standalone scorer emits the
    centered linear predictor lp = (expand(x) − x̄)·β (hazard ratio =
    exp(lp)), matching the engine's predict()."""
    di = model.dinfo
    columns, domains, di_info = _datainfo_spec(di)
    columns = columns + [model.params.response_column]
    domains = domains + [None]
    info = _common_info(model, "coxph", "Cox Proportional Hazards",
                        "CoxPH", 1, columns, domains, mojo_version=1.00)
    info.update(di_info)
    info.update({
        "beta": [float(v) for v in np.asarray(model.beta)],
        "mean_x": [float(v) for v in np.asarray(model.mean_x)],
    })
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_isotonic_mojo(model, path: str):
    """Isotonic MOJO — `hex/genmodel/algos/isotonic/IsotonicRegressionMojoWriter`
    role: the fitted step thresholds; scoring is piecewise-linear
    interpolation clamped to the fitted range."""
    columns = list(model.output.names) + [model.params.response_column]
    domains = [None] * len(columns)
    info = _common_info(model, "isotonic", "Isotonic Regression", "Regression",
                        1, columns, domains, mojo_version=1.00)
    xs = np.asarray(model.xs, dtype=np.float64)
    ys = np.asarray(model.ys, dtype=np.float64)
    info.update({"n_thresholds": len(xs),
                 "thresholds_x": list(xs), "thresholds_y": list(ys),
                 "out_of_bounds": getattr(model.params, "out_of_bounds",
                                          "clip")})
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_word2vec_mojo(model, path: str):
    """Word2Vec MOJO — `hex/genmodel/algos/word2vec/Word2VecMojoWriter` role:
    the embedding matrix as one float blob + the vocabulary, word-aligned."""
    words = sorted(model.vocab, key=model.vocab.get)
    vectors = np.asarray(model.vectors, dtype="<f4")
    info = _common_info(model, "word2vec", "Word2Vec", "WordEmbedding", 1,
                        [], [], mojo_version=1.00)
    info.update({"supervised": False, "n_features": 0,
                 "vec_size": int(vectors.shape[1]),
                 "vocab_size": int(vectors.shape[0])})
    zw = MojoZipWriter()
    _write_common(zw, info, [], [])
    zw.write_text("word2vec/words.txt",
                  "\n".join(escape_line(w) for w in words) + "\n")
    zw.write_blob("word2vec/vectors.bin", vectors.tobytes())
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_glrm_mojo(model, path: str):
    """GLRM MOJO — `hex/genmodel/algos/glrm/GlrmMojoWriter` role: the
    archetype matrix Y + the DataInfo spec; the scorer projects rows onto the
    archetypes by masked least squares (the reference runs the same X-update
    iteration at scoring time)."""
    di = model.dinfo
    columns, domains, di_info = _datainfo_spec(di)
    Y = np.asarray(model.Y, dtype=np.float64)
    info = _common_info(model, "glrm", "Generalized Low Rank Modeling",
                        "DimReduction", 1, columns, domains, mojo_version=1.00)
    info.update(di_info)
    info.update({"supervised": False, "n_features": len(columns),
                 "k": int(Y.shape[0]), "expanded": int(Y.shape[1])})
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_blob("glrm/archetypes.bin", Y.astype("<f8").tobytes())
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_targetencoder_mojo(model, path: str):
    """TargetEncoder MOJO — `hex/genmodel/algos/targetencoder/
    TargetEncoderMojoWriter` role: per-column numerator/denominator tables +
    prior + blending hyperparameters. Scoring applies the no-leakage path
    (strategy None) exactly as `TargetEncoderMojoModel` does."""
    import json

    out = model.output
    cols = list(model.encodings)
    columns = cols + [model.params.response_column]
    domains = [out.domains[c] for c in cols] + [out.response_domain]
    info = _common_info(model, "targetencoder", "TargetEncoder", "TargetEncoder",
                        1, columns, domains, mojo_version=1.00)
    p = model.params
    info.update({
        "blending": bool(p.blending),
        "inflection_point": float(p.inflection_point),
        "smoothing": float(p.smoothing),
        "prior": [float(v) for v in np.asarray(model.prior)],
        "keep_original": bool(getattr(p, "keep_original_categorical_columns",
                                      True)),
    })
    tables = {c: {"num": np.asarray(model.encodings[c]["num"],
                                    dtype=np.float64).tolist(),
                  "den": np.asarray(model.encodings[c]["den"],
                                    dtype=np.float64).tolist()}
              for c in cols}
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_text("targetencoder/tables.json", json.dumps(tables))
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_uplift_mojo(model, path: str):
    """Uplift DRF MOJO — `hex/genmodel/algos/upliftdrf` role: paired
    treatment/control leaf values per tree. Trees are written as two tree
    groups (group 0 = treatment, group 1 = control) in the standard tree
    bytecode; the scorer averages each group and emits
    [uplift, p_y1_ct1, p_y1_ct0]."""
    out = model.output
    columns = list(out.names) + [model.params.response_column]
    domains = [out.domains.get(n) for n in out.names] + [out.response_domain]
    feat = np.asarray(model.forest["feat"])
    thr = np.asarray(model.forest["thr"])
    val_t = np.asarray(model.forest["val_t"]).astype(np.float64)
    val_c = np.asarray(model.forest["val_c"]).astype(np.float64)
    nanL = np.zeros_like(feat, dtype=bool)           # engine sends NA right
    T = feat.shape[0]
    info = _common_info(model, "upliftdrf", "Uplift Distributed Random Forest",
                        "BinomialUplift", 2, columns, domains,
                        mojo_version=1.30)
    info.update({"n_trees": T, "n_trees_per_class": 2,
                 "max_depth": int(model.cfg.max_depth),
                 "treatment_column": model.params.treatment_column})
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    for j in range(T):
        for gi, val in ((0, val_t), (1, val_c)):
            blob, aux = encode_tree(feat[j], thr[j], nanL[j], val[j])
            zw.write_blob(f"trees/t{gi:02d}_{j:03d}.bin", blob)
            zw.write_blob(f"trees/t{gi:02d}_{j:03d}_aux.bin", aux)
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_gam_mojo(model, path: str):
    """GAM MOJO — `hex/genmodel/algos/gam/GamMojoWriter` role: the linear
    DataInfo spec + per-gam-column spline specs (knots, degree, centering
    means) + the coefficient vector over [linear | spline bases]."""
    import json

    out = model.output
    category = out.model_category
    di = model.dinfo
    if di is not None and di.names:
        lin_cols, lin_doms, di_info = _datainfo_spec(di)
    else:
        lin_cols, lin_doms, di_info = [], [], {"cats": 0, "nums": 0}
    gam_cols = [s["column"] for s in model.gam_specs]
    columns = lin_cols + gam_cols + [model.params.response_column]
    domains = lin_doms + [None] * len(gam_cols) + [out.response_domain]
    n_classes = {"Regression": 1, "Binomial": 2}.get(
        category, len(out.response_domain or []))
    info = _common_info(model, "gam", "Generalized Additive Model", category,
                        n_classes, columns, domains, mojo_version=1.00)
    info.update(di_info)
    info.update({
        "beta": [float(v) for v in np.asarray(model.beta)],
        "family": model.family.name,
        "link": model.family.link_name,
        "n_lin": len(lin_cols),
    })
    specs = [{k: (v.tolist() if isinstance(v, np.ndarray) else v)
              for k, v in s.items()} for s in model.gam_specs]
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_text("gam/specs.json", json.dumps(specs))
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_rulefit_mojo(model, path: str):
    """RuleFit MOJO — `hex/genmodel/algos/rulefit/RuleFitMojoWriter` role:
    the packed rule tensors + linear-term standardization + the (raw-scale)
    GLM coefficients over the [rules | linear] design."""
    import json

    from ..models.glm import _destandardize

    out = model.output
    category = out.model_category
    columns = list(out.names) + [model.params.response_column]
    domains = [out.domains.get(n) for n in out.names] + [out.response_domain]
    n_classes = {"Regression": 1, "Binomial": 2}.get(
        category, len(out.response_domain or []))
    info = _common_info(model, "rulefit", "RuleFit", category, n_classes,
                        columns, domains, mojo_version=1.00)
    g = getattr(model, "glm_model", None)
    if g is not None:  # multinomial / legacy persisted fits carry a sub-GLM
        beta = _destandardize(np.asarray(g.beta, dtype=np.float64), g.dinfo)
        family_name, link_name = g.family.name, g.family.link_name
    elif getattr(model, "beta", None) is not None \
            and getattr(model, "family", None) is not None:
        # direct-fit AND streaming models: beta is already on the raw
        # design scale (standardize=False; linear-term standardization is
        # baked into the spec's lin_means/lin_sigmas) — the streaming-mode
        # export refusal this replaces predates the shared layout
        beta = np.asarray(model.beta, dtype=np.float64)
        family_name, link_name = model.family.name, model.family.link_name
    else:
        raise NotImplementedError(
            "MOJO export needs the model's fitted coefficients (beta + "
            "family) — this RuleFit model carries neither a sub-GLM nor a "
            "direct fit")
    info.update({
        "beta": list(beta.ravel()),
        "family": family_name,
        "link": link_name,
        "n_rules": 0 if model.rule_arrays is None
        else int(np.asarray(model.rule_arrays[0]).shape[0]),
    })
    spec = {
        "lin_names": list(model.lin_names),
        "lin_means": [float(v) for v in model.lin_stats[0]] if model.lin_names else [],
        "lin_sigmas": [float(v) for v in model.lin_stats[1]] if model.lin_names else [],
    }
    if model.rule_arrays is not None:
        fidx, thr, is_gt, na_left, act = (np.asarray(a)
                                          for a in model.rule_arrays)
        spec.update({"fidx": fidx.astype(int).tolist(), "thr": thr.tolist(),
                     "is_gt": is_gt.astype(int).tolist(),
                     "na_left": na_left.astype(int).tolist(),
                     "act": act.astype(int).tolist()})
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_text("rulefit/spec.json", json.dumps(spec))
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_psvm_mojo(model, path: str):
    """PSVM MOJO — `hex/genmodel/algos/psvm/SvmMojoWriter` role: the
    decision-function state (Nystrom landmarks + whitening + weights, or the
    plain linear weights) over the DataInfo-expanded features."""
    di = model.dinfo
    out = model.output
    feat_cols, feat_doms, di_info = _datainfo_spec(di)
    columns = feat_cols + [model.params.response_column]
    domains = feat_doms + [out.response_domain]
    info = _common_info(model, "psvm", "PSVM", "Binomial", 2, columns,
                        domains, mojo_version=1.00)
    info.update(di_info)
    info.update({"gamma": float(model.gamma), "bias": float(model.bias),
                 "kernel": "gaussian" if model.landmarks is not None
                 else "linear",
                 "sv_count": int(model.sv_count)})
    zw = MojoZipWriter()
    _write_common(zw, info, columns, domains)
    zw.write_blob("psvm/beta.bin",
                  np.asarray(model.beta, dtype="<f8").tobytes())
    if model.landmarks is not None:
        zw.write_blob("psvm/landmarks.bin",
                      np.asarray(model.landmarks, dtype="<f8").tobytes())
        zw.write_blob("psvm/whiten.bin",
                      np.asarray(model.whiten, dtype="<f8").tobytes())
    zw.finish(path)


# ---------------------------------------------------------------------------
def _write_ensemble_mojo(model, path: str):
    """Stacked Ensemble MOJO in the reference's `MultiModelMojoReader`
    layout (`hex/genmodel/algos/ensemble/StackedEnsembleMojoReader.java`):
    every sub-model MOJO is a nested DIRECTORY inside the same zip
    (``models/<ALGO>/<key>/...``), declared by ``submodel_count`` /
    ``submodel_key_i`` / ``submodel_dir_i``, with ``base_models_num``,
    ``base_model{i}`` and ``metalearner`` naming the roles. Genuine JVM
    ensemble MOJOs load through the matching reader; ours load in the JVM."""
    import os
    import shutil
    import tempfile
    import zipfile

    out = model.output
    category = out.model_category
    # the ensemble's own output.names is empty (it consumes base predictions);
    # the MOJO's feature columns are the union of the base models' features
    feats, doms = [], []
    for bm in model.base_models:
        for n in bm.output.names:
            if n not in feats:
                feats.append(n)
                doms.append(bm.output.domains.get(n))
    columns = feats + [model.params.response_column]
    domains = doms + [out.response_domain]
    n_classes = {"Regression": 1, "Binomial": 2}.get(
        category, len(out.response_domain or []))
    info = _common_info(model, "stackedensemble", "Stacked Ensemble", category,
                        n_classes, columns, domains, mojo_version=1.01)

    meta = model.metalearner
    submodels = [(str(meta.key), meta)] + [(str(bm.key), bm)
                                           for bm in model.base_models]
    seen = set()
    for key, _ in submodels:
        if key in seen:
            raise ValueError(f"duplicate sub-model key '{key}' in ensemble")
        seen.add(key)
    dirs = {key: f"models/{type(m).algo_name.upper()}/{key}/"
            for key, m in submodels}
    info["submodel_count"] = len(submodels)
    for i, (key, _) in enumerate(submodels):
        info[f"submodel_key_{i}"] = key
        info[f"submodel_dir_{i}"] = dirs[key]
    info["base_models_num"] = len(model.base_models)
    info["metalearner"] = str(meta.key)
    info["metalearner_transform"] = "NONE"
    for i, bm in enumerate(model.base_models):
        info[f"base_model{i}"] = str(bm.key)

    zw = MojoZipWriter()
    tmpdir = tempfile.mkdtemp()
    try:
        for key, m in submodels:
            sub = os.path.join(tmpdir, "sub.zip")
            export_mojo(m, sub)
            with zipfile.ZipFile(sub) as sz:
                for entry in sz.namelist():
                    zw.write_blob(dirs[key] + entry, sz.read(entry))
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    _write_common(zw, info, columns, domains)
    zw.finish(path)
