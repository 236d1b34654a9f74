"""Frame — a named collection of Vecs (distributed columns).

Reference: `water/fvec/Frame.java` (2,017 LoC). A Frame is column-oriented: an
ordered map name -> Vec, all with the same row count. Unlike the reference, the
columns here are row-sharded JAX arrays in HBM (see vec.py); all per-column chunk
alignment concerns (`VectorGroup`, `water/Key.java:108-120`) vanish because every
Vec uses the same padded sharding, so shard i of every column covers the same
global rows — the property MRTask's aligned-chunk map relied on.

Also provides the dense-matrix materialization used by model builders (the
`hex/DataInfo` handoff): ``as_matrix`` stacks selected numeric columns into an
(nrow_padded, ncol) float32 array, still row-sharded.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.kvstore import Keyed, STORE
from ..parallel import mesh as meshmod
from .vec import T_CAT, T_NUM, Vec


@jax.jit
def _stack_cols(*cols):
    return jnp.stack(cols, axis=1)


class Frame(Keyed):
    def __init__(self, names: Sequence[str] | None = None,
                 vecs: Sequence[Vec] | None = None, key: str | None = None):
        super().__init__(key=key, prefix="frame")
        self._names: list[str] = list(names or [])
        self._vecs: list[Vec] = list(vecs or [])
        assert len(self._names) == len(self._vecs)
        if self._vecs:
            nr = self._vecs[0].nrow
            assert all(v.nrow == nr for v in self._vecs), "column row counts differ"

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_dict(cols: dict, mesh=None, key: str | None = None) -> "Frame":
        names, vecs = [], []
        for name, col in cols.items():
            names.append(str(name))
            if isinstance(col, Vec):
                vecs.append(col)
            else:
                col = np.asarray(col)
                vecs.append(Vec.from_numpy(col, mesh=mesh))
        fr = Frame(names, vecs, key=key)
        STORE.put_keyed(fr)
        return fr

    @staticmethod
    def from_pandas(df, mesh=None, key: str | None = None) -> "Frame":
        names, vecs = [], []
        import pandas.api.types as pdt

        for name in df.columns:
            s = df[name]
            if not (pdt.is_numeric_dtype(s) or pdt.is_bool_dtype(s)
                    or pdt.is_datetime64_any_dtype(s)):
                if isinstance(s.dtype, __import__("pandas").CategoricalDtype):
                    codes = s.cat.codes.to_numpy().astype(np.float32)
                    codes[codes < 0] = np.nan
                    vecs.append(Vec.from_numpy(codes, type=T_CAT, mesh=mesh,
                                               domain=[str(x) for x in s.cat.categories]))
                else:
                    uniq, codes = _factorize(s.to_numpy())
                    vecs.append(Vec.from_numpy(codes, type=T_CAT, domain=uniq, mesh=mesh))
            elif pdt.is_datetime64_any_dtype(s):
                ms = s.astype("int64").to_numpy().astype(np.float64) / 1e6
                ms[s.isna().to_numpy()] = np.nan
                vecs.append(Vec.from_numpy(ms.astype(np.float32), type="time", mesh=mesh))
            else:
                vecs.append(Vec.from_numpy(s.to_numpy(dtype=np.float32, na_value=np.nan),
                                           mesh=mesh))
            names.append(str(name))
        fr = Frame(names, vecs, key=key)
        STORE.put_keyed(fr)
        return fr

    # -- shape / lookup ------------------------------------------------------
    @property
    def nrow(self) -> int:
        return self._vecs[0].nrow if self._vecs else 0

    @property
    def ncol(self) -> int:
        return len(self._vecs)

    @property
    def names(self) -> list[str]:
        return list(self._names)

    @property
    def vecs(self) -> list[Vec]:
        return list(self._vecs)

    def vec(self, name_or_idx) -> Vec:
        if isinstance(name_or_idx, int):
            return self._vecs[name_or_idx]
        return self._vecs[self._names.index(name_or_idx)]

    def __getitem__(self, sel):
        if isinstance(sel, str):
            return self.vec(sel)
        if isinstance(sel, (list, tuple)):
            return self.subframe(sel)
        return self._vecs[sel]

    def find(self, name: str) -> int:
        return self._names.index(name) if name in self._names else -1

    # -- mutation (builds new frames; Vecs are immutable-ish) ----------------
    def add(self, name: str, vec: Vec) -> "Frame":
        if self._vecs:
            assert vec.nrow == self.nrow
        self._names.append(name)
        self._vecs.append(vec)
        return self

    def remove(self, name: str) -> Vec:
        i = self._names.index(name)
        self._names.pop(i)
        return self._vecs.pop(i)

    def replace(self, name: str, vec: Vec) -> "Frame":
        i = self._names.index(name)
        self._vecs[i] = vec
        return self

    def subframe(self, names: Iterable[str]) -> "Frame":
        names = list(names)
        return Frame(names, [self.vec(n) for n in names])

    def rename(self, old: str, new: str) -> "Frame":
        self._names[self._names.index(old)] = new
        return self

    def take(self, idx) -> "Frame":
        """Row subset by integer index array — the shared helper behind
        split_frame, CV fold slicing, and rapids row selection."""
        import numpy as _np

        idx = _np.asarray(idx)
        cols = {}
        for name in self._names:
            v = self.vec(name)
            if v.is_string():
                cols[name] = Vec(None, len(idx), type=v.type,
                                 host_data=v.host_data[idx])
            else:
                cols[name] = Vec.from_numpy(v.to_numpy()[idx], type=v.type,
                                            domain=v.domain)
        return Frame(list(cols), list(cols.values()))

    def concat_rows(self, *others: "Frame") -> "Frame":
        """Row-wise concatenation (rbind) preserving types/domains of self."""
        import numpy as _np

        cols = {}
        for n in self._names:
            v0 = self.vec(n)
            if v0.is_string():
                parts = [self.vec(n).host_data] + [o.vec(n).host_data
                                                   for o in others]
                cols[n] = Vec(None, sum(len(p) for p in parts), type=v0.type,
                              host_data=_np.concatenate(parts))
            else:
                parts = [self.vec(n).to_numpy()] + [o.vec(n).to_numpy()
                                                    for o in others]
                cols[n] = Vec.from_numpy(_np.concatenate(parts), type=v0.type,
                                         domain=v0.domain)
        return Frame(list(cols), list(cols.values()))

    # -- device materialization ----------------------------------------------
    def as_matrix(self, names: Sequence[str] | None = None) -> jax.Array:
        """Stack columns into a row-sharded (plen, ncol) float32 matrix.

        One jitted program per column count: the eager jnp.stack emitted
        several chunked-concatenate XLA programs, each paying its own cold
        compile+load."""
        names = list(names) if names is not None else self._names
        cols = [self.vec(n) for n in names]
        assert all(c.data is not None for c in cols), "string cols can't go to HBM"
        return _stack_cols(*[c.data for c in cols])

    def ensure_rollups(self, names: Sequence[str] | None = None) -> None:
        """Compute every missing column rollup in batched fused programs —
        ONE device round-trip per ~2^28-cell block instead of one per column
        (29 serial per-column dispatches and host fetches on an 11M-row
        frame otherwise; this is the builders' pre-pass).

        The batch dispatches through the MRTask driver (`mr_reduce`), so
        every frame's first rollup touch shows up in /3/Metrics and the
        timeline as a real map/reduce with payload bytes and phase walls —
        the RollupStats MRTask, accounted like one."""
        from .vec import _ROLLUP_REDUCE, _rollup_mr_map, _rollups_from_scalars

        todo = [self.vec(n) for n in (names if names is not None
                                      else self._names)]
        todo = [v for v in todo if v._rollups is None
                and (v._data is not None or v._spill_path is not None)]
        coded = [v for v in todo if hasattr(v, "rollups_from_codes")]
        if coded:
            # coded columns batch in code space — one program per
            # (plen, dtype) stack, never decoding (`chunks.py`); sparse/raw
            # codecs come back and ride the decode-path batch below
            from .chunks import batch_code_rollups

            rest = set(map(id, batch_code_rollups(coded)))
            todo = [v for v in todo
                    if not hasattr(v, "rollups_from_codes")
                    or id(v) in rest]
        if len(todo) <= 1:
            return
        from ..backend.memory import hbm_budget_bytes

        # the (plen, C) stack is a fresh device copy: cap it at 1/8 of the
        # live HBM budget (f32 cells), keeping the historical 2^28-cell
        # (1 GiB) block when no accelerator budget is resolvable
        budget = hbm_budget_bytes()
        cell_cap = (budget // 32) if budget else (1 << 28)
        by_plen: dict[int, list] = {}
        for v in todo:
            by_plen.setdefault(v.plen, []).append(v)
        for plen, group in by_plen.items():
            block = max(1, cell_cap // max(plen, 1))
            for s0 in range(0, len(group), block):
                sub = group[s0:s0 + block]
                import jax
                import numpy as np

                from ..parallel.mrtask import mr_reduce

                r = jax.device_get(mr_reduce(
                    _rollup_mr_map, [v.data for v in sub],
                    nrow=max(v.nrow for v in sub),
                    reduce=_ROLLUP_REDUCE))
                for i, v in enumerate(sub):
                    n = int(r["n"][i])
                    scalars = {
                        "n": n,
                        "mean": r["sum"][i] / max(n, 1),
                        "var": max(float(r["varsum"][i]) / max(n, 1), 0.0),
                        "mins": r["mins"][i], "maxs": r["maxs"][i],
                        "zerocnt": r["zerocnt"][i],
                        "isint": bool(np.asarray(r["isint"][i])),
                    }
                    v._rollups = _rollups_from_scalars(v.nrow, scalars)

    def compress(self) -> "Frame":
        """Compressed-chunk copy of this frame: every column re-encoded with
        the narrowest bit-exact codec (`frame/chunks.py`) — the C1/C2-style
        coded storage the reference parses straight into. Columns no codec
        reproduces exactly stay raw f32 (shared, not copied)."""
        from .chunks import compress_frame

        return compress_frame(self)

    # -- host views ----------------------------------------------------------
    def to_pandas(self):
        import pandas as pd

        out = {}
        for name, v in zip(self._names, self._vecs):
            col = v.to_numpy()
            if v.type == T_CAT and v.domain is not None:
                codes = np.where(np.isnan(col), -1, col).astype(np.int64)
                out[name] = pd.Categorical.from_codes(
                    codes, categories=[str(d) for d in v.domain])
            else:
                out[name] = col
        return pd.DataFrame(out)

    def head(self, n: int = 10):
        return self.to_pandas().head(n)

    def types(self) -> dict[str, str]:
        return dict(zip(self._names, (v.type for v in self._vecs)))

    def remove_impl(self, store) -> None:
        for v in self._vecs:
            store.remove(v.key, cascade=False)

    def __repr__(self) -> str:
        return f"Frame({self.key}, {self.nrow}x{self.ncol} {self._names[:8]}{'...' if self.ncol > 8 else ''})"


def _factorize(arr: np.ndarray):
    """String column -> (sorted domain, float codes w/ NaN for NA).

    The host-side analog of distributed categorical interning
    (`water/parser/ParseDataset.java:502-601`): levels are collected, sorted
    lexicographically (H2O domain order), and values re-coded against the
    sorted domain.
    """
    mask = np.array([x is None or (isinstance(x, float) and np.isnan(x)) for x in arr],
                    dtype=bool)
    vals = np.asarray([("" if m else str(x)) for x, m in zip(arr, mask)])
    uniq = sorted(set(vals[~mask]))
    lookup = {u: i for i, u in enumerate(uniq)}
    codes = np.array([lookup.get(v, -1) for v in vals], dtype=np.float32)
    codes[mask] = np.nan
    return uniq, codes
