"""DataInfo — the modeling row codec. Analog of `hex/DataInfo.java` (~2,500 LoC).

Expands a Frame into the dense design matrix algorithms consume: categorical
one-hot blocks first then numeric columns (the reference's layout,
`hex/DataInfo.java:24,113-229`), with optional standardization of numerics,
``use_all_factor_levels`` control (drop-first by default, as GLM does), and
missing-value handling (MeanImputation: numeric -> mean, categorical -> mode;
or Skip: rows weighted out).

The expansion runs on device: one_hot per categorical + concat — categorical
codes are already in HBM, so wide one-hot blocks are produced where they are
consumed (feeding the Gram matmul) instead of shipping expanded rows around.
Means/sigmas/modes are frozen at train time and replayed at score time.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame


@dataclass
class DataInfo:
    names: list                      # source column names (feature order)
    is_cat: np.ndarray               # per source column
    domains: dict                    # name -> domain (cats)
    cat_modes: dict                  # name -> mode code (imputation)
    num_means: dict                  # name -> mean
    num_sigmas: dict                 # name -> sigma
    use_all_factor_levels: bool
    standardize: bool                 # divide numerics by sigma
    missing_values_handling: str      # MeanImputation | Skip
    expanded_names: list = field(default_factory=list)
    center: bool | None = None        # subtract numeric means; None = follow
                                      # `standardize`. Imputation always uses
                                      # the mean regardless.
    #: numeric x numeric products ``(a, b)`` that are model columns
    #: ``a_b`` (last in ``expanded_names``, statistics in ``num_means`` /
    #: ``num_sigmas``) but never columns of a frame or of ``expand``'s
    #: design: a caller forms them from the design's own columns
    #: (`add_products`, `models/glm.py`'s streamed IRLS step)
    products: tuple = ()

    @property
    def ncols_expanded(self) -> int:
        return len(self.expanded_names)

    def shift_scale(self, names):
        """Two arrays: what ``expand`` subtracts from each numeric column of
        ``names`` and divides it by (mean and deviation where it centres and
        standardises, else 0 and 1)."""
        return (np.array([self.num_means[n] if self.effective_center else 0.0
                          for n in names]),
                np.array([self.num_sigmas[n] if self.standardize else 1.0
                          for n in names]))

    def paired_columns(self, pairs=None):
        """``(cols, positions)`` of numeric pairs (default: ``products``):
        the design columns they read, ascending, and each pair as two
        positions in ``cols``."""
        pairs = self.products if pairs is None else pairs
        idx = {n: j for j, n in enumerate(self.expanded_names)}
        cols = tuple(sorted({idx[c] for pair in pairs for c in pair}))
        pos = {j: i for i, j in enumerate(cols)}
        return cols, tuple((pos[idx[a]], pos[idx[b]]) for a, b in pairs)

    def add_products(self, X, pairs, nrow: int) -> None:
        """Make each numeric pair ``(a, b)`` a model column ``a_b``: its mean
        and standard deviation over the ``nrow`` rows of ``X``, ``expand``'s
        design with its NAs marked (`mark_missing`), from ONE device pass
        (`product_moments`) where a stored product column would take a
        column of the frame each and a rollup pass. The statistics are a
        rollup's (`frame/vec.py`): over the rows where both columns hold a
        value, the mean, and the sample deviation (divisor n - 1), 1 where
        that is 0 or not finite."""
        cols, pos = self.paired_columns(pairs)
        names = [self.expanded_names[j] for j in cols]
        shift, scale = self.shift_scale(names)
        mu = np.array([self.num_means[n] for n in names])
        means, sigmas = product_moments(X, cols, shift - mu, scale, mu, nrow,
                                        pos)
        for (a, b), m, s in zip(pairs, means, sigmas):
            self.num_means[f"{a}_{b}"] = float(m)
            self.num_sigmas[f"{a}_{b}"] = (float(s) if np.isfinite(s)
                                          and s > 0 else 1.0)
            self.expanded_names.append(f"{a}_{b}")
        self.products = tuple(self.products) + tuple(pairs)

    def mark_missing(self, X, fr, pairs=None):
        """``X`` (``expand``'s design of ``fr``) with NaN put back where a
        column of a numeric pair (default: ``products``) is NA in ``fr``:
        ``expand`` imputes the column's mean, but the product of an NA
        imputes to the PRODUCT's mean, so the programs that form products
        (`product_moments`, `models/glm.py`'s streamed step and eta) read
        the NA and impute both themselves. ``X`` as it was where no such
        column holds an NA."""
        cols, _ = self.paired_columns(pairs)
        names = [self.expanded_names[j] for j in cols]
        fr.ensure_rollups(names)
        bad = tuple((j, n) for j, n in zip(cols, names)
                    if fr.vec(n).rollups().nacnt)
        if not bad:
            return X
        return _put_nan(X, tuple(fr.vec(n).data for _, n in bad),
                        tuple(j for j, _ in bad))

    @property
    def effective_center(self) -> bool:
        """Whether numeric columns are mean-centered (center defaults to
        following `standardize`). The single source of truth for expand(),
        GLM coef() destandardization, and the MOJO writer."""
        return self.standardize if self.center is None else self.center

    @staticmethod
    def make(fr: Frame, names, standardize=True, use_all_factor_levels=False,
             missing_values_handling="MeanImputation") -> "DataInfo":
        # categoricals first, then numerics — mirrors DataInfo column ordering
        fr.ensure_rollups(names)   # one fused pass, not one per column
        cats = [n for n in names if fr.vec(n).is_categorical()]
        nums = [n for n in names if not fr.vec(n).is_categorical()]
        ordered = cats + nums
        is_cat = np.array([True] * len(cats) + [False] * len(nums))
        domains, modes, means, sigmas = {}, {}, {}, {}
        expanded = []
        for n in cats:
            v = fr.vec(n)
            domains[n] = list(v.domain)
            host = v.to_numpy()
            ok = host[~np.isnan(host)].astype(np.int64)
            modes[n] = int(np.bincount(ok).argmax()) if ok.size else 0
            lo = 0 if use_all_factor_levels else 1
            expanded += [f"{n}.{v.domain[i]}" for i in range(lo, len(v.domain))]
        for n in nums:
            r = fr.vec(n).rollups()
            means[n] = float(np.nan_to_num(r.mean))
            sg = float(r.sigma)
            sigmas[n] = sg if np.isfinite(sg) and sg > 0 else 1.0
            expanded.append(n)
        return DataInfo(ordered, is_cat, domains, modes, means, sigmas,
                        use_all_factor_levels, standardize,
                        missing_values_handling, expanded)

    # -- device expansion -----------------------------------------------------
    def expand(self, fr: Frame):
        """Frame -> (X (plen, P) device matrix, valid_row mask (plen,)).

        Rows with NAs are imputed (MeanImputation) or flagged invalid (Skip).
        Unseen categorical levels at score time behave like NAs.
        """
        blocks = []
        valid = None
        for n in self.names:
            v = fr.vec(n)
            col = v.data
            if n in self.domains:
                dom = self.domains[n]
                if v.domain != dom and v.domain is not None:
                    col = _remap_codes(v, dom)
                card = len(dom)
                isna = jnp.isnan(col) | (col >= card)
                if self.missing_values_handling == "Skip":
                    valid = isna if valid is None else (valid | isna)
                codes = jnp.where(isna, self.cat_modes[n], col).astype(jnp.int32)
                oh = jax.nn.one_hot(codes, card, dtype=jnp.float32)
                lo = 0 if self.use_all_factor_levels else 1
                blocks.append(oh[:, lo:])
            else:
                isna = jnp.isnan(col)
                if self.missing_values_handling == "Skip":
                    valid = isna if valid is None else (valid | isna)
                x = jnp.where(isna, self.num_means[n], col)
                if self.effective_center:
                    x = x - self.num_means[n]
                if self.standardize:
                    x = x / self.num_sigmas[n]
                blocks.append(x[:, None])
        X = jnp.concatenate(blocks, axis=1)
        bad = valid if valid is not None else jnp.zeros(X.shape[0], jnp.bool_)
        return X, ~bad

    def column_plan(self, fr: Frame):
        """``((cols, fill, shift, scale), layout)``: `expand` taken apart for a
        caller that builds its design inside ONE jitted program
        (`expand_columns`, `models/gam.py`). ``cols`` are the frame's columns
        as they lie on the device, in ``self.names`` order (a categorical
        with another domain remapped to the training one); ``fill`` (the NA
        fill: mean or mode), ``shift`` and ``scale`` are (len(names),) ARRAYS,
        so the program takes them as arguments and a second frame of the
        same ``layout`` (a cardinality a column, 0 for a numeric; the first
        level kept; Skip or not) traces nothing."""
        cols, fill, shift, scale, cards = [], [], [], [], []
        for n in self.names:
            v = fr.vec(n)
            col = v.data
            if n in self.domains:
                dom = self.domains[n]
                if v.domain != dom and v.domain is not None:
                    col = _remap_codes(v, dom)
                cards.append(len(dom))
                fill.append(self.cat_modes[n])
                shift.append(0.0)
                scale.append(1.0)
            else:
                cards.append(0)
                fill.append(self.num_means[n])
                shift.append(self.num_means[n] if self.effective_center
                             else 0.0)
                scale.append(self.num_sigmas[n] if self.standardize else 1.0)
            cols.append(col)
        layout = (tuple(cards), 0 if self.use_all_factor_levels else 1,
                  self.missing_values_handling == "Skip")
        f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
        return (tuple(cols), f32(fill), f32(shift), f32(scale)), layout

    def expand_matrix(self, X):
        """Raw (N, len(names)) matrix → expanded (N, P) design, columns in
        ``self.names`` order with categoricals as training-domain codes.

        The traceable twin of ``expand()`` for callers that hold a matrix
        instead of a Frame (the serving runtime's compiled scorers): same
        per-column treatment — NA/out-of-domain categoricals impute to the
        mode before one-hot, numerics impute to the mean then center/scale
        — so a row expanded here is bit-identical to the same row expanded
        through a Frame. No valid-row mask: serving always imputes
        (MeanImputation semantics), it never drops rows.
        """
        blocks = []
        for j, n in enumerate(self.names):
            col = X[:, j]
            if n in self.domains:
                card = len(self.domains[n])
                # (col < 0) has no twin in expand(): frame codes can never
                # be negative, but a serving client CAN send a negative
                # pre-encoded level index — treat it like any other
                # invalid level (mode imputation), not as the one_hot
                # all-zeros row that aliases the dropped baseline level
                isna = jnp.isnan(col) | (col < 0) | (col >= card)
                codes = jnp.where(isna, self.cat_modes[n],
                                  col).astype(jnp.int32)
                oh = jax.nn.one_hot(codes, card, dtype=jnp.float32)
                lo = 0 if self.use_all_factor_levels else 1
                blocks.append(oh[:, lo:])
            else:
                isna = jnp.isnan(col)
                x = jnp.where(isna, self.num_means[n], col)
                if self.effective_center:
                    x = x - self.num_means[n]
                if self.standardize:
                    x = x / self.num_sigmas[n]
                blocks.append(x[:, None])
        return jnp.concatenate(blocks, axis=1)


def expand_columns(cols, fill, shift, scale, layout):
    """The traceable body of `DataInfo.expand` over `DataInfo.column_plan`'s
    output: ``(blocks, valid)``, the expanded design as a LIST of 2-D blocks
    in `expand`'s column order (a caller concatenates them into its one
    buffer: a categorical's one-hot each, then ALL the numerics as one
    block, `DataInfo.make` having put them last) and the rows that no NA
    under Skip has flagged. Same per-column treatment as `expand`: a
    categorical's NA or unseen level takes the mode before its one-hot, a
    numeric's NA the mean, then shift and scale (0 and 1 where the
    DataInfo neither centres nor standardises: exact in float32)."""
    cards, lo, skip = layout
    ncat = sum(1 for c in cards if c)
    assert all(cards[:ncat]) and not any(cards[ncat:]), cards
    blocks, bad = [], []
    for j in range(ncat):
        isna = jnp.isnan(cols[j]) | (cols[j] >= cards[j])
        bad.append(isna)
        codes = jnp.where(isna, fill[j], cols[j]).astype(jnp.int32)
        blocks.append((codes[:, None] == jnp.arange(lo, cards[j])[None, :])
                      .astype(jnp.float32))
    if ncat < len(cols):
        x = jnp.stack(cols[ncat:], axis=1)
        isna = jnp.isnan(x)
        bad.append(jnp.any(isna, axis=1))
        blocks.append((jnp.where(isna, fill[None, ncat:], x)
                       - shift[None, ncat:]) / scale[None, ncat:])
    valid = jnp.ones(cols[0].shape, jnp.bool_)
    if skip:
        for b in bad:
            valid = valid & ~b
    return blocks, valid


@functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
def _put_nan(X, raws, cols):
    """`DataInfo.mark_missing`'s write: one program, ``X`` donated."""
    put = jnp.stack(raws, axis=1)
    return X.at[:, cols].set(jnp.where(jnp.isnan(put), jnp.nan,
                                       X[:, cols]))


def lanes(x, idx):
    """``x[:, idx]`` for a static tuple of column indices, as slices: one a
    run of consecutive indices, concatenated (no gather on the TPU)."""
    runs, i = [], 0
    while i < len(idx):
        j = i + 1
        while j < len(idx) and idx[j] == idx[j - 1] + 1:
            j += 1
        runs.append(x[:, idx[i]:idx[j - 1] + 1])
        i = j
    return runs[0] if len(runs) == 1 else jnp.concatenate(runs, axis=1)


def _moments_core(cols, X, nrow, row0, centre, scale):
    """Per row block of ``X``, the float32 cross sums ``A'A`` of ``A = [v,
    c, c*c]`` at highest matmul precision, stacked a block: ``c = x -
    mean`` the centred columns (``c = X[:, cols] * scale + centre``) and
    ``v`` 1 where a row is under ``nrow`` and the column holds a value (an
    NA is a NaN, `DataInfo.mark_missing`), both 0 elsewhere. The caller
    sums the blocks in float64."""
    from ..backend.kernels import gram as gram_kernels

    R, k = X.shape[0], len(cols)
    nblk, rb, tail = gram_kernels.block_plan(R, 4 * k)

    def part(start, rows):
        xb = jax.lax.dynamic_slice_in_dim(X, start, rows, 0)
        live = (row0 + start + jnp.arange(rows)) < nrow
        c = lanes(xb, cols) * scale + centre
        ok = live[:, None] & ~jnp.isnan(c)
        c = jnp.where(ok, c, 0.0)
        A = jnp.concatenate([ok.astype(jnp.float32), c, c * c], axis=1)
        return jnp.einsum("ra,rb->ab", A, A,
                          precision=jax.lax.Precision.HIGHEST)

    if nblk == 1 and not tail:
        return part(0, R)[None]
    parts = jax.lax.map(lambda i: part(i * rb, rb), jnp.arange(nblk))
    if tail:
        parts = jnp.concatenate([parts, part(nblk * rb, tail)[None]])
    return parts


@functools.lru_cache(maxsize=8)
def _moments_program(cols: tuple, mesh, sharded: bool):
    """`_moments_core` as ONE program: plain jit on one shard, under
    ``shard_map`` over the rows on several (each shard's blocks stacked
    after the last shard's: a scan over row blocks of a row-sharded array
    under plain jit gathers the array in every step on the TPU)."""
    from ..parallel.mesh import ROWS, shard_map
    from ..utils import telemetry

    if not sharded:
        @telemetry.program("glm_interact_moments")
        def moments(X, nrow, centre, scale):
            return _moments_core(cols, X, nrow, 0, centre, scale)
        return jax.jit(moments)

    @telemetry.program("glm_interact_moments")
    def moments_sharded(X, nrow, centre, scale):
        row0 = jax.lax.axis_index(ROWS) * X.shape[0]
        return _moments_core(cols, X, nrow, row0, centre, scale)

    P = jax.sharding.PartitionSpec
    return jax.jit(shard_map(
        moments_sharded, mesh=mesh,
        in_specs=(P(ROWS, None), P(), P(), P()), out_specs=P(ROWS),
        check_vma=False))


def product_moments(X, cols, centre, scale, mu, nrow: int, pairs):
    """``(means, sigmas)`` of the products ``x_i x_j`` for ``(i, j)`` in
    ``pairs`` (positions in ``cols``), over the first ``nrow`` rows of the
    design ``X`` where both columns hold a value, as an appended product
    column's rollup takes them (an NA's product is NA). ``X``'s columns
    ``cols`` hold ``(x - mu - centre) / scale``, NaN at an NA.

    One device pass (`_moments_core`) sums the CENTRED columns' cross
    moments with each other and with the columns' value indicators ``v``
    in float32 a block; the blocks are summed in float64 here. With ``q =
    x_i x_j - mu_i mu_j = c_i c_j + mu_i c_j + mu_j c_i`` over the ``n``
    rows where ``v_i v_j``, the mean is ``mu_i mu_j + E q`` and the
    variance ``E q^2 - (E q)^2``, times ``n / (n - 1)``: no raw ``E p^2 -
    m^2`` cancels in float32."""
    from ..parallel.mesh import default_mesh, n_row_shards

    mesh = default_mesh()
    ns = n_row_shards(mesh)
    sharded = ns > 1 and X.shape[0] % ns == 0
    f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))  # noqa: E731
    M = np.asarray(_moments_program(tuple(cols), mesh, sharded)(
        X, jnp.int32(nrow), f32(centre), f32(scale)), np.float64).sum(0)
    k = len(cols)
    vv, vc, vc2 = (M[:k, t * k:(t + 1) * k] for t in range(3))
    cc, cc2, c2c2 = M[k:2 * k, k:2 * k], M[k:2 * k, 2 * k:], M[2 * k:, 2 * k:]
    mu = np.asarray(mu, np.float64)
    i, j = (np.array(a, np.int64) for a in zip(*pairs))
    n = vv[i, j]
    sq = cc[i, j] + mu[i] * vc[i, j] + mu[j] * vc[j, i]
    sq2 = (c2c2[i, j] + mu[i] ** 2 * vc2[i, j] + mu[j] ** 2 * vc2[j, i]
           + 2 * mu[i] * cc2[i, j] + 2 * mu[j] * cc2[j, i]
           + 2 * mu[i] * mu[j] * cc[i, j])
    eq = sq / np.maximum(n, 1)
    var = (np.maximum(sq2 / np.maximum(n, 1) - eq * eq, 0.0)
           * (n / np.maximum(n - 1, 1)))
    return mu[i] * mu[j] + eq, np.sqrt(var)


def _remap_codes(v, train_dom):
    remap = {lvl: i for i, lvl in enumerate(train_dom)}
    codes = np.full(len(v.domain), np.nan, dtype=np.float32)
    for i, lvl in enumerate(v.domain):
        if lvl in remap:
            codes[i] = remap[lvl]
    host = v.to_numpy()
    out = np.full(v.plen, np.nan, dtype=np.float32)
    ok = ~np.isnan(host)
    out[: len(host)][ok] = codes[host[ok].astype(np.int64)]
    from ..frame.vec import Vec

    return Vec.from_numpy(out[: len(host)]).data
