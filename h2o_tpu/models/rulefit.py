"""RuleFit — rules from a tree ensemble + sparse linear model.

Analog of `hex/rulefit/` (1,574 LoC): `RuleFit.java` trains depth-varying tree
models (`min_rule_length..max_rule_length`), extracts every root→node path as a
binary rule (`RuleExtractor.java`), deduplicates, then fits an L1 GLM over
[rules | linear terms] (`model_type` RULES / LINEAR / RULES_AND_LINEAR) and
reports the surviving rules by |coef|·support (`Rule.java` importance).

TPU-native structure: the ensembles come from our shared tree engine (forests
are already (T, N) device arrays); path extraction walks those arrays
host-side (tiny); rule evaluation — every rule over every row — is ONE jitted
pass of chained comparisons (rules × rows broadcast), and the sparse linear fit
reuses the GLM elastic-net path (sharded Gram + ADMM).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.jobs import Job
from ..frame.frame import Frame
from ..frame.vec import Vec
from .drf import DRF, DRFParameters
from .gbm import GBM, GBMParameters
from .glm import GLM, GLMParameters
from .model_base import Model, ModelBuilder, ModelOutput, make_metrics


@dataclass
class RuleFitParameters(GLMParameters):
    """Mirrors `hex/schemas/RuleFitV3`."""

    algorithm: str = "AUTO"        # AUTO(=DRF) | DRF | GBM
    min_rule_length: int = 3
    max_rule_length: int = 3
    max_num_rules: int = -1        # -1 = no cap (reference default)
    nlambdas: int = 20             # the lasso path walks at most 20 lambdas
                                   # (the GLM's own default, -1, resolves to
                                   # 100 or 30)
    model_type: str = "rules_and_linear"  # rules_and_linear | rules | linear
    rule_generation_ntrees: int = 50
    beta_epsilon: float = 1e-4     # the reference GLM IRLSM default — the
                                   # repo-wide GLMParameters pins 1e-5, but
                                   # at RuleFit's lasso-path scale the
                                   # tighter epsilon only buys "confirm"
                                   # Gram passes (post-solve beta moves
                                   # ~1e-4 between warm-started lambdas;
                                   # with the deviance probe this measured
                                   # 62 → 51 IRLS epochs over the
                                   # 20-lambda bench path)
    objective_epsilon: float = 1e-4  # the reference's lambda_search auto
                                   # default (GLM objective_epsilon docs:
                                   # 1e-4 when lambda_search is on, 1e-6
                                   # only at lambda=0) — tail-path lambdas
                                   # whose deviance no longer moves then
                                   # converge after ONE Gram pass


class Rule:
    """A conjunction of (feature, op, threshold[, na_goes]) conditions."""

    __slots__ = ("conds", "support", "coef", "rule_id", "origin",
                 "model_idx")

    def __init__(self, conds, rule_id, origin=None):
        self.conds = conds          # list of (fidx, '<='|'>', thr, na_left)
        self.support = 0.0
        self.coef = 0.0
        self.rule_id = rule_id
        #: (flat tree index, heap node) the rule's path ends at in its
        #: generating forest — rows satisfying the conds are EXACTLY the
        #: rows that visit that node, so `forest_covers` reads the rule's
        #: support without re-evaluating conditions over the matrix
        self.origin = origin
        self.model_idx = 0          # which depth-ensemble produced it

    def describe(self, names):
        parts = []
        for fidx, op, thr, _ in self.conds:
            parts.append(f"({names[fidx]} {op} {thr:.6g})")
        return " & ".join(parts)


def extract_rules(forest: dict, max_depth: int, min_len: int, max_len: int):
    """Walk the (T, N) full-binary-tree arrays; emit one rule per internal
    path of length in [min_len, max_len] (`hex/rulefit/RuleExtractor.java`)."""
    feat = np.asarray(forest["feat"])
    thr = np.asarray(forest["thr"])
    nanL = np.asarray(forest["nanL"])
    if feat.ndim == 3:  # multinomial (T, K, N) -> flatten classes
        T, K, N = feat.shape
        feat = feat.reshape(T * K, N)
        thr = thr.reshape(T * K, N)
        nanL = nanL.reshape(T * K, N)
    rules = []
    seen = set()
    for t in range(feat.shape[0]):
        stack = [(0, [])]
        while stack:
            node, conds = stack.pop()
            if conds and min_len <= len(conds) <= max_len:
                key = tuple(conds)
                if key not in seen:
                    seen.add(key)
                    rules.append(Rule(list(conds), len(rules),
                                      origin=(t, node)))
            f = feat[t, node]
            if f < 0 or len(conds) >= max_len:
                continue
            c_left = (int(f), "<=", float(thr[t, node]), bool(nanL[t, node]))
            c_right = (int(f), ">", float(thr[t, node]), bool(nanL[t, node]))
            stack.append((2 * node + 1, conds + [c_left]))
            stack.append((2 * node + 2, conds + [c_right]))
    return rules


def _rules_tensor(rules, F):
    """Pack rules into device arrays: per (rule, cond-slot): fidx, thr, is_gt,
    na_left, active. Max conds padded."""
    L = max(len(r.conds) for r in rules)
    R = len(rules)
    fidx = np.zeros((R, L), np.int32)
    thr = np.zeros((R, L), np.float32)
    is_gt = np.zeros((R, L), bool)
    na_left = np.zeros((R, L), bool)
    act = np.zeros((R, L), bool)
    for i, r in enumerate(rules):
        for j, (f, op, t, nl) in enumerate(r.conds):
            fidx[i, j] = f
            thr[i, j] = t
            is_gt[i, j] = op == ">"
            na_left[i, j] = nl
            act[i, j] = True
    return tuple(map(jnp.asarray, (fidx, thr, is_gt, na_left, act)))


@jax.jit
def eval_rules(X, fidx, thr, is_gt, na_left, act):
    """(rows, rules) 0/1 membership: every condition of the rule holds."""
    xv = X[:, fidx]                       # (rows, R, L)
    isna = jnp.isnan(xv)
    le = jnp.where(isna, na_left, xv <= thr)
    cond = jnp.where(is_gt, ~le, le)
    cond = jnp.where(act, cond, True)
    return jnp.all(cond, axis=2).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Streaming mode — benchmark scale. At 11M rows a ~700-rule design is ~30 GB
# (and eval_rules' (rows, rules, conds) gather intermediate ~90 GB): neither
# fits HBM. Instead the design exists only per row BLOCK inside one scanned
# program: rules evaluate via a condition-slot one-hot matmul (no gathers),
# the IRLS Gram/XWz accumulate across blocks, and scoring streams the same
# way. The (P, P) Gram is all that ever materializes.
# ---------------------------------------------------------------------------
def _build_design_block(xb, fidx, thr, gt, nal, act, lsel, mu_l, sg_l):
    """(rb, F) raw block -> (rb, P) design block, all matmul/elementwise.

    Rule conditions select their feature through a (R*L, F) one-hot — the
    engine's standard no-gather idiom — then compare/AND-reduce; linear
    terms standardize with NA -> mean imputation like RuleFitModel._design.
    Every tensor is an ARGUMENT (not a baked closure constant): one compiled
    program serves every fitted rule set of the same shape, so refits only
    pay tracing once per process (a per-fit closure re-traced and re-loaded
    several programs per call — most of RuleFit's warm benchmark wall).
    """
    F = xb.shape[1]
    xz = jnp.nan_to_num(xb)
    nanb = jnp.isnan(xb).astype(jnp.float32)

    def pick(M):
        # value selection must stay f32-exact: the MXU's default bf16
        # multiply would round values across rule thresholds (engine.py's
        # hi/lo trick)
        hi = xz.astype(jnp.bfloat16).astype(jnp.float32)
        lo = xz - hi
        return hi @ M.T + lo @ M.T

    blocks = []
    if fidx.shape[0]:
        R, L = fidx.shape
        SEL = jax.nn.one_hot(fidx.reshape(-1), F, dtype=jnp.float32)
        v = pick(SEL)                                 # (rb, R*L)
        isna = (nanb @ SEL.T) > 0.5
        le = jnp.where(isna, nal.reshape(-1)[None, :],
                       v <= thr.reshape(-1)[None, :])
        cond = jnp.where(gt.reshape(-1)[None, :], ~le, le)
        cond = jnp.where(act.reshape(-1)[None, :], cond, True)
        memb = jnp.all(cond.reshape(xb.shape[0], R, L), axis=2)
        blocks.append(memb.astype(jnp.float32))
    if lsel.shape[0]:
        LSEL = jax.nn.one_hot(lsel, F, dtype=jnp.float32)
        lv = pick(LSEL)
        lna = (nanb @ LSEL.T) > 0.5
        lv = jnp.where(lna, mu_l[None, :], lv)
        blocks.append((lv - mu_l[None, :]) / sg_l[None, :])
    return jnp.concatenate(blocks, axis=1)


#: design cells above which RuleFit streams (~2 GB of f32)
_STREAM_CELL_BUDGET = 1 << 29


def _stream_block(Rl: int, P: int, want: int = 65536) -> int:
    # large blocks keep the per-block Gram matmuls MXU-sized (8k-row blocks
    # measured scan/dispatch-bound at 11M rows); ~512 MB of transient f32
    # block cells is comfortable in 16 GB HBM
    from .tree.binning import _pow2_block

    return _pow2_block(Rl, max(256, min(want, (1 << 27) // max(P, 1))))


_STREAM_FN_CACHE: dict = {}


def _stream_prelude(family):
    """ONE fused program for the eager prelude — mask/weights/offset/
    intercept init. Eagerly these were ~6 separate 11M-row dispatches,
    each its own program to compile and load."""
    key = ("prelude", family.name, getattr(family, "link_name", None))
    fn = _STREAM_FN_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def prelude(y_dev, wcol, nrow):
        y = jnp.nan_to_num(y_dev)
        w = (~jnp.isnan(y_dev)).astype(jnp.float32)
        w = w * (jnp.arange(y.shape[0]) < nrow) * wcol
        return y, w, jnp.zeros_like(y), jnp.sum(w), family.init_intercept(y, w)

    return _STREAM_FN_CACHE.setdefault(key, prelude)


def _stream_step(family, rb: int):
    """Streaming GLMIterationTask, cached per (family, block size): scan row
    blocks, build the design block on the fly, accumulate (Gram, XWz,
    deviance, n). jax's own jit cache handles the shape axes."""
    key = ("step", family.name, getattr(family, "link_name", None),
           getattr(family, "p", None), getattr(family, "theta", None), rb)
    fn = _STREAM_FN_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def step(Xraw, y, w, beta, offset, fidx, thr, gt, nal, act, lsel,
             mu_l, sg_l):
        from ..backend.kernels import gram as gram_kernels

        Rl = Xraw.shape[0]
        nblk = Rl // rb

        def body(carry, blk):
            G, b_, dev, neff = carry
            xb, yb, wb, ob = blk
            A = _build_design_block(xb, fidx, thr, gt, nal, act, lsel,
                                    mu_l, sg_l)
            Ai = jnp.concatenate([A, jnp.ones((rb, 1), jnp.float32)], axis=1)
            eta = Ai @ beta + ob
            mu = family.linkinv(eta)
            d = family.dmu_deta(eta)
            V = family.variance(mu)
            W = wb * d * d / jnp.maximum(V, 1e-10)
            z = eta - ob + (yb - mu) / jnp.where(jnp.abs(d) < 1e-10, 1e-10, d)
            # the shared kernels-layer block math (backend/kernels/gram.py):
            # here the design block is BUILT in the same scan step, so the
            # whole design→Gram pipeline is one fused pass per block
            dG, db = gram_kernels.block_contrib(Ai, W, z)
            G = G + dG
            b_ = b_ + db
            dev = dev + jnp.sum(family.deviance(yb, mu, wb))
            neff = neff + jnp.sum(wb)
            return (G, b_, dev, neff), None

        P1 = beta.shape[0]
        init = (jnp.zeros((P1, P1), jnp.float32), jnp.zeros(P1, jnp.float32),
                jnp.float32(0.0), jnp.float32(0.0))
        (G, b_, dev, neff), _ = jax.lax.scan(
            body, init,
            (Xraw.reshape(nblk, rb, -1), y.reshape(nblk, rb),
             w.reshape(nblk, rb), offset.reshape(nblk, rb)))
        return G, b_, dev, neff

    return _STREAM_FN_CACHE.setdefault(key, step)


def _stream_scorer(rb: int):
    """Streaming X@beta for scoring, cached per block size."""
    key = ("score", rb)
    fn = _STREAM_FN_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def run(Xraw, beta, fidx, thr, gt, nal, act, lsel, mu_l, sg_l):
        Rl = Xraw.shape[0]
        nblk = Rl // rb

        def body(_, xb):
            A = _build_design_block(xb, fidx, thr, gt, nal, act, lsel,
                                    mu_l, sg_l)
            return None, A @ beta[:-1] + beta[-1]

        _, etas = jax.lax.scan(body, None, Xraw.reshape(nblk, rb, -1))
        return etas.reshape(Rl)

    return _STREAM_FN_CACHE.setdefault(key, run)


def _covers_support(submodels, rules, Xraw, nrow: int) -> np.ndarray:
    """Per-rule support read off the generating forests' node covers.

    A rule IS a root→node path, so the rows satisfying its conditions are
    exactly the rows that visit its origin node — `engine.forest_covers`
    counts those in one routing pass per sub-forest (the same one-hot
    traversal scoring uses), instead of re-evaluating every rule's
    condition conjunction over the full matrix (the old
    `_stream_rule_support` pass: a (rows × rules × conds) design rebuild
    that existed only to recover numbers the forests already knew).
    ``Xraw`` is the already-present raw feature matrix (the GLM phase
    holds it either way) — nothing re-stacks. Row-chunked so the (rows,
    n_nodes) traversal one-hots stay bounded; counts sum across chunks."""
    from .tree.engine import forest_covers

    valid = (jnp.arange(Xraw.shape[0]) < nrow).astype(jnp.float32)
    sup = np.zeros(len(rules), np.float32)
    by_model: dict[int, list[int]] = {}
    for i, r in enumerate(rules):
        by_model.setdefault(r.model_idx, []).append(i)
    for mi, idxs in sorted(by_model.items()):
        fo = submodels[mi].forest
        depth = submodels[mi].cfg.max_depth
        n_nodes = fo["feat"].shape[-1]
        step = max(8192, (1 << 26) // max(n_nodes, 1))
        cov = None
        for s0 in range(0, Xraw.shape[0], step):
            c = forest_covers(Xraw[s0:s0 + step], valid[s0:s0 + step],
                              fo["feat"], fo["thr"], fo["nanL"], depth)
            cov = c if cov is None else cov + c
        cov = np.asarray(cov)
        if cov.ndim == 3:  # multinomial (T, K, N): extract_rules flattened
            cov = cov.reshape(-1, cov.shape[-1])
        for i in idxs:
            t, node = rules[i].origin
            sup[i] = cov[t, node] / max(nrow, 1)
    return sup


def _stream_rule_support(Xraw, rule_arrays, nrow: int):
    """Per-rule membership frequency over the real rows, streamed — the
    pre-covers evaluation pass, kept as the independent parity oracle for
    `_covers_support` (tests pin covers == membership counts)."""
    R = rule_arrays[0].shape[0]
    rb = _stream_block(int(Xraw.shape[0]), R)
    key = ("support", rb)
    fn = _STREAM_FN_CACHE.get(key)
    if fn is None:
        @jax.jit
        def run(Xraw, valid, fidx, thr, gt, nal, act):
            nblk = Xraw.shape[0] // rb
            R_ = fidx.shape[0]
            empty_sel = jnp.zeros((0,), jnp.int32)
            empty_f = jnp.zeros((0,), jnp.float32)

            def body(acc, blk):
                xb, vb = blk
                memb = _build_design_block(xb, fidx, thr, gt, nal, act,
                                           empty_sel, empty_f, empty_f)
                return acc + (memb * vb[:, None]).sum(axis=0), None

            tot, _ = jax.lax.scan(
                body, jnp.zeros(R_, jnp.float32),
                (Xraw.reshape(nblk, rb, -1), valid.reshape(nblk, rb)))
            return tot

        fn = _STREAM_FN_CACHE.setdefault(key, run)
    valid = (jnp.arange(Xraw.shape[0]) < nrow).astype(jnp.float32)
    return fn(Xraw, valid, *rule_arrays) / max(nrow, 1)


class RuleFitModel(Model):
    algo_name = "rulefit"

    #: streaming mode (benchmark scale): adapt_frame returns the RAW feature
    #: matrix and score0 builds design blocks on the fly
    stream = False
    beta = None      # [rules..., linear..., intercept] in streaming mode
    family = None    # GLM family object (streaming scoring)

    def __init__(self, params, output, rules, rule_arrays, lin_names,
                 lin_stats, glm_model, key=None):
        self.rules = rules
        self.rule_arrays = rule_arrays    # packed tensors or None
        self.lin_names = lin_names        # linear-term feature names
        self.lin_stats = lin_stats        # (means, sigmas) for linear terms
        self.glm_model = glm_model        # fitted GLM over [rules|linear]
        super().__init__(params, output, key=key)

    def _stream_args(self):
        """The design-builder tensor arguments (rules + linear stats)."""
        names = self.output.names
        if self.rule_arrays is not None:
            fidx, thr, gt, nal, act = self.rule_arrays
        else:
            fidx = jnp.zeros((0, 1), jnp.int32)
            thr = jnp.zeros((0, 1), jnp.float32)
            gt = nal = act = jnp.zeros((0, 1), bool)
        lin_sel = ([names.index(n) for n in self.lin_names]
                   if self.lin_names else [])
        means, sigmas = self.lin_stats if self.lin_stats else ([], [])
        return (fidx, thr, gt, nal, act,
                jnp.asarray(np.asarray(lin_sel, np.int32)),
                jnp.asarray(np.asarray(means, np.float32)),
                jnp.asarray(np.asarray(sigmas, np.float32)))

    def _design(self, fr: Frame):
        blocks = []
        if self.rule_arrays is not None:
            X = fr.as_matrix(self.output.names)
            blocks.append(eval_rules(X, *self.rule_arrays))
        if self.lin_names:
            means, sigmas = self.lin_stats
            cols = []
            for n, mu, sg in zip(self.lin_names, means, sigmas):
                col = jnp.nan_to_num(fr.vec(n).data, nan=mu)
                cols.append((col - mu) / sg)
            blocks.append(jnp.stack(cols, axis=1))
        return jnp.concatenate(blocks, axis=1)

    def adapt_frame(self, fr: Frame):
        fr = self.pre_adapt(fr)
        if self.stream:
            return fr.as_matrix(self.output.names)
        return self._design(fr)

    def score0(self, X):
        if self.stream:
            P1 = len(self.beta)
            rb = _stream_block(int(X.shape[0]), P1)
            eta = _stream_scorer(rb)(
                X, jnp.asarray(self.beta, jnp.float32), *self._stream_args())
            mu = self.family.linkinv(eta)
            if self.output.model_category == "Binomial":
                label = (mu >= 0.5).astype(jnp.float32)
                return jnp.stack([label, 1 - mu, mu], axis=1)
            return mu
        if self.glm_model is not None:
            # multinomial fits and pre-kernels persisted models carry the
            # full sub-GLM — delegate
            return self.glm_model.score0(X)
        # direct-fit path: X is the [rules | linear] design, beta its
        # coefficients with the intercept last (the GLMModel.score0 math
        # without the sub-model object)
        beta = jnp.asarray(self.beta, jnp.float32)
        mu = self.family.linkinv(X @ beta[:-1] + beta[-1])
        if self.output.model_category == "Binomial":
            label = (mu >= 0.5).astype(jnp.float32)
            return jnp.stack([label, 1 - mu, mu], axis=1)
        return mu

    def rule_importance(self):
        """Rules the L1 fit kept, ranked by |coef| (`Rule.java` importance)."""
        names = self.output.names
        rows = []
        for r in self.rules:
            if abs(r.coef) > 1e-8:
                rows.append({"rule": r.describe(names), "coefficient": r.coef,
                             "support": r.support})
        rows.sort(key=lambda d: -abs(d["coefficient"]))
        return rows


class RuleFit(ModelBuilder):
    algo_name = "rulefit"

    def build_impl(self, job: Job) -> RuleFitModel:
        p = self.params
        fr = p.training_frame
        names = self.feature_names()
        y_dev, category, resp_domain = self.response_info()
        model_type = p.model_type.lower()

        rules, rule_arrays, submodels = [], None, []
        if "rules" in model_type:
            # depth-varying ensembles (`RuleFit.java` treeParameters loop)
            depths = range(p.min_rule_length, p.max_rule_length + 1)
            ntrees = max(p.rule_generation_ntrees // max(len(list(depths)), 1), 5)
            for depth in range(p.min_rule_length, p.max_rule_length + 1):
                job.check_cancelled()
                algo = (p.algorithm or "AUTO").upper()
                common = dict(training_frame=fr, response_column=p.response_column,
                              weights_column=p.weights_column, ntrees=ntrees,
                              max_depth=depth, seed=p.seed,
                              distribution=p.distribution)
                if algo in ("AUTO", "DRF"):
                    sub = DRF(DRFParameters(**common))
                else:
                    sub = GBM(GBMParameters(**common))
                # the rule language is threshold conjunctions (`hex/rulefit/
                # Rule.java` conditions) — keep the internal forests on
                # ordinal categorical splits so every path stays expressible
                sub._use_set_splits = False
                m = sub.build_impl(Job(f"rulefit_trees_d{depth}", 1.0))
                new_rules = extract_rules(m.forest, m.cfg.max_depth,
                                          p.min_rule_length,
                                          p.max_rule_length)
                for r in new_rules:
                    r.model_idx = len(submodels)
                submodels.append(m)
                rules += new_rules
            if p.max_num_rules > 0:
                rules = rules[: p.max_num_rules]
            for i, r in enumerate(rules):
                r.rule_id = i
            rule_arrays = _rules_tensor(rules, len(names)) if rules else None

        lin_names, lin_stats = [], None
        if "linear" in model_type:
            lin_names = [n for n in names if not fr.vec(n).is_categorical()]
            means = [float(np.nan_to_num(fr.vec(n).rollups().mean))
                     for n in lin_names]
            sigmas = [max(float(np.nan_to_num(fr.vec(n).rollups().sigma)), 1e-6)
                      for n in lin_names]
            lin_stats = (means, sigmas)

        output = ModelOutput()
        output.names = names
        output.domains = {n: fr.vec(n).domain for n in names}
        output.response_domain = list(resp_domain) if resp_domain else None
        output.model_category = category

        model = RuleFitModel(p, output, rules, rule_arrays, lin_names,
                             lin_stats, None)

        P_design = (len(rules) if rules else 0) + len(lin_names)
        plen = fr.vec(0).plen
        model.stream = plen * max(P_design, 1) > _STREAM_CELL_BUDGET
        if model.stream:
            # benchmark scale: the design never materializes — the L1 GLM
            # runs on the streaming IRLS (see _make_stream_irls)
            beta = self._fit_streaming(job, model, fr, y_dev, category)
        else:
            Xd = model._design(fr)
            beta = self._fit_design(job, model, Xd, y_dev, fr, category)
        model.beta = beta

        # pull coefficients back onto rules; support = rule frequency, read
        # off the generating forests' node covers (one routing pass per
        # sub-forest over the already-present raw matrix — no (rows ×
        # rules × conds) design rebuild; see _covers_support)
        n_rules = len(rules)
        if rules:
            sup = _covers_support(submodels, rules, fr.as_matrix(names),
                                  fr.nrow)
            for i, r in enumerate(rules):
                r.coef = float(beta[i])
                r.support = float(sup[i])

        raw = model.score0(model.adapt_frame(fr) if model.stream else Xd)
        y = jnp.nan_to_num(y_dev)
        ym = jnp.where(jnp.isnan(y_dev), jnp.nan, y)
        wm = (jnp.nan_to_num(fr.vec(p.weights_column).data)
              if p.weights_column else None)
        output.training_metrics = make_metrics(category, ym, raw, wm,
                                               auc_type=p.auc_type,
                                               domain=output.response_domain)
        output.variable_importances = None
        job.update(1.0)
        return model

    def _fit_design(self, job, model, Xd, y_dev, fr, category) -> np.ndarray:
        """L1 lambda path directly over the materialized rule/linear design
        (`RuleFit.java` glmParameters: alpha=1, lambda_search) — the GLM
        IRLS driver (`GLM._fit`, kernels-layer fused Gram) invoked on the
        matrix RuleFit already holds. The historic path round-tripped Xd
        through a per-column design Frame + DataInfo expansion purely to
        satisfy the builder API: ~430 Vec.from_device slices, a second
        (R, P) stack, and a full set of sub-model metrics nothing read —
        ~1 s of the CPU bench leg. Multinomial responses keep the Frame
        path (per-class block IRLS needs the full builder)."""
        p = self.params
        if category == "Multinomial":
            design = Frame([f"c{i}" for i in range(Xd.shape[1])],
                           [Vec.from_device(Xd[:, i], fr.nrow)
                            for i in range(Xd.shape[1])])
            design.add(p.response_column, fr.vec(p.response_column))
            if p.weights_column:
                design.add(p.weights_column, fr.vec(p.weights_column))
            gp = GLMParameters(
                training_frame=design, response_column=p.response_column,
                weights_column=p.weights_column, alpha=1.0,
                lambda_search=p.lambda_search or p.lambda_ is None,
                lambda_=p.lambda_, nlambdas=min(p.nlambdas, 20),
                standardize=False, family=p.family, seed=p.seed,
                max_iterations=p.max_iterations,
                beta_epsilon=p.beta_epsilon,
                objective_epsilon=p.objective_epsilon)
            glm_model = GLM(gp).build_impl(Job("rulefit_glm", 1.0))
            model.glm_model = glm_model
            return np.asarray(glm_model.beta)
        family = GLM._family(self, category)
        model.family = family
        gb = GLM(GLMParameters(
            training_frame=fr, response_column=p.response_column,
            weights_column=p.weights_column, alpha=1.0,
            lambda_search=p.lambda_search or p.lambda_ is None,
            lambda_=p.lambda_, nlambdas=min(p.nlambdas, 20),
            standardize=False, family=p.family, seed=p.seed,
            max_iterations=p.max_iterations, beta_epsilon=p.beta_epsilon,
            objective_epsilon=p.objective_epsilon))
        wcol = (jnp.nan_to_num(fr.vec(p.weights_column).data)
                if p.weights_column else jnp.ones((), jnp.float32))
        y, w, offset, _neff, _b0 = _stream_prelude(family)(
            y_dev, wcol, fr.nrow)
        beta = gb._fit(Xd, y, w, offset, family, job)[0]
        return np.asarray(beta, np.float64)

    def _fit_streaming(self, job, model, fr, y_dev, category) -> np.ndarray:
        """L1 lambda path over the streaming IRLS — mirrors GLM._fit's IRLSM
        loop with the design built per block (`RuleFit.java` glmParameters:
        alpha=1, lambda_search).

        Warm-path economics (profiled at bench shape, 11M rows x ~430 cols):
        each step() is a full scan over the streamed design (~0.4 s on chip),
        so the loop below spends exactly one step per lambda once the path is
        warm — the convergence test compares the post-solve beta against the
        incoming (previous-lambda) beta, which is the same warm-start
        argument glmnet's one-IRLS-step-per-lambda path rides. All step
        outputs come back in ONE device_get (the per-array np.asarray calls
        each paid a host round-trip), and the eager mask/intercept prelude
        is a single fused program (_stream_prelude)."""
        from .glm import _admm_solve

        p = self.params
        names = model.output.names
        family = GLM._family(self, category)
        model.family = family
        Xraw = fr.as_matrix(names)
        wcol = (jnp.nan_to_num(fr.vec(p.weights_column).data)
                if p.weights_column else jnp.ones((), jnp.float32))
        y, w, offset, neff_d, b0_d = _stream_prelude(family)(
            y_dev, wcol, fr.nrow)
        neff = float(neff_d)

        sargs = model._stream_args()
        P1 = ((len(model.rules) if model.rules else 0)
              + len(model.lin_names) + 1)
        rb = _stream_block(int(Xraw.shape[0]), P1)
        raw_step = _stream_step(family, rb)

        def step(bb):
            out = raw_step(Xraw, y, w, jnp.asarray(bb, jnp.float32), offset,
                           *sargs)
            G, b, dev, _ = jax.device_get(out)
            return (np.asarray(G, np.float64), np.asarray(b, np.float64),
                    float(dev))

        beta = np.zeros(P1, np.float64)
        beta[-1] = float(b0_d)
        free = np.zeros(P1, bool)
        free[-1] = True
        G0, b0, dev0 = step(beta)
        grad0 = np.abs(b0 - G0 @ beta)[:-1]
        lmax = float(grad0.max()) / max(neff, 1.0)
        nl = min(p.nlambdas, 20)
        lambdas = (np.geomspace(lmax, lmax * 1e-4, nl)
                   if (p.lambda_search or p.lambda_ is None)
                   else [p.lambda_])
        # beta is the intercept-only init here, so the lambda-max pass's
        # deviance IS the null deviance — no separate mu0 epoch
        nulldev = dev0
        dev_lambda_prev = np.inf
        # the lambda-max pass already evaluated step() at this beta — seed
        # the first iteration with it instead of paying a duplicate epoch
        # over the streamed design
        seeded = (G0, b0, dev0)
        for lam in lambdas:
            job.check_cancelled()
            l1 = float(lam) * neff  # alpha = 1 (pure lasso, like the ref)
            dev = np.inf
            # warm-started: convergence vs the previous-lambda beta means
            # one step per lambda on the steady path; the cap bounds the
            # pass count when a lambda actually moves the solution
            for _it in range(min(max(p.max_iterations, 1), 5)):
                if seeded is not None:
                    G, b, dev = seeded
                    seeded = None
                else:
                    G, b, dev = step(beta)
                beta_new = _admm_solve(G, b, l1, 0.0, free)
                diff = np.max(np.abs(beta_new - beta))
                beta = beta_new
                if diff < p.beta_epsilon:
                    break
            # lambda-search early stop (`LambdaSearchScoringHistory` role):
            # once an extra lambda stops buying deviance, the remaining path
            # only densifies coefficients the L1 ranking does not need
            if (dev_lambda_prev - dev) < 3e-4 * abs(nulldev):
                break
            dev_lambda_prev = dev
        return beta
