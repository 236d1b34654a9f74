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

    @property
    def ncols_expanded(self) -> int:
        return len(self.expanded_names)

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
