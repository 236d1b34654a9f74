"""Compressed columnar chunk store — the `water/fvec/C*Chunk` codec family
rebuilt for HBM.

The reference keeps every column as compressed chunks: constant runs
(`C0DChunk`), byte/short offset-scale codes (`C1Chunk`/`C2SChunk`),
sparse-zero runs (`CXIChunk`) — 21 codecs picked per chunk at parse time.
The seed design here deliberately dropped all of that for flat f32 arrays
(vec.py's "fixed-width vectorizable layouts" note), which is why an
Airlines-116M×31 expanded matrix is ~14 GB of f32 on one v5e chip. This
module brings the codec idea back in TPU-native form:

- **Coded columns** (`CodedVec`): ONE device array of codes per column plus
  host-side affine metadata. Codecs: ``const`` (one value), ``int8``/
  ``int16`` offset-scale (``value = offset + code·scale``, top code = NA),
  ``cat8``/``cat16`` (categorical level ids — same wire format, labelled for
  introspection), ``sparse0`` (row-index + f32-bit pairs for mostly-zero
  columns), and ``raw`` passthrough. Every codec is **verified bit-exact at
  encode time against the real device decode kernel** (NaN-aware); a column
  no codec reproduces exactly stays raw f32. Decoding is a per-access
  temporary — the f32 view never becomes resident state.
- **Cleaner residency**: coded bytes register with `backend/memory.py`'s
  Cleaner exactly like raw Vec buffers (CodedVec IS a Vec), so
  ``hbm_budget_bytes()`` stays honest while chunk views are alive, and
  coded columns spill/rehydrate under budget pressure like any other
  column (`tests/test_chunks.py` pins the eviction cycle).
- **Binned views** (`BinnedView`): the training-matrix analog of XGBoost's
  ELLPACK page — per-column quantile-bin codes packed into one
  device-resident (plen, F) int8 (int16 when any feature needs > 127 bins)
  matrix, built COLUMN BY COLUMN from Vec data + precomputed edges, so the
  raw f32 matrix is never stacked. The tree engine consumes it directly:
  blocks upcast to int32 inside the histogram scan body (VMEM-granular, no
  HBM-wide relayout — `models/tree/engine.py`), which is what makes int8
  storage a win where the always-int8 one-hot measured 5x slower
  (`binning.bin_matrix`'s historical note).

``H2O_TPU_BINNED_STORE=0`` disables the binned training path in the tree
builders (`models/gbm.py`); the chunk codecs themselves are opt-in via
``compress_frame`` / ``Frame.compress()``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import mesh as meshmod
from ..utils import telemetry
from .vec import Rollups, T_CAT, T_NUM, Vec

#: code-space caps: the top code of each width is the NA sentinel
_CAP8, _NA8 = 254, 255
_CAP16, _NA16 = 65534, 65535

_INT_KINDS = ("int8", "int16", "cat8", "cat16")


@dataclass(frozen=True)
class ChunkMeta:
    """Host-side decode metadata — the `Chunk` subclass header analog."""

    kind: str            # const | int8 | int16 | cat8 | cat16 | sparse0 | raw
    nrow: int            # logical rows (padding rows beyond this are NaN)
    plen: int            # padded device length
    offset: float = 0.0  # int codecs: value = offset + code * scale (f32)
    scale: float = 1.0
    na_code: int = 0     # int codecs: the NA/padding sentinel code
    value: float = float("nan")  # const codecs: the single value
    is_int: bool = False         # decoded values all integral (encode-time)
    zero_code: int = -1          # int codecs: code decoding to 0.0 (-1: none)


# ---------------------------------------------------------------------------
# Decode kernels — one jitted program per (kind, shape family); the affine
# params ride as operands so ingesting many columns never multiplies compiles.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("plen",))
def _decode_const(value, nrow, plen: int):
    i = jnp.arange(plen)
    return jnp.where(i < nrow, jnp.asarray(value, jnp.float32), jnp.nan)


@jax.jit
def _decode_intcode(codes, offset, scale, na_code):
    out = offset + codes.astype(jnp.float32) * scale
    return jnp.where(codes == na_code, jnp.nan, out)


@functools.partial(jax.jit, static_argnames=("plen",))
def _decode_sparse(packed, plen: int):
    vals = jax.lax.bitcast_convert_type(packed[1], jnp.float32)
    return jnp.zeros((plen,), jnp.float32).at[packed[0]].set(vals,
                                                             mode="drop")


def decode_chunk(coded: jax.Array, meta: ChunkMeta) -> jax.Array:
    """Coded device array + meta -> the f32 logical column (padding = NaN)."""
    k = meta.kind
    if k == "raw":
        return coded
    if k == "const":
        return _decode_const(np.float32(meta.value), np.int32(meta.nrow),
                             meta.plen)
    if k in _INT_KINDS:
        return _decode_intcode(coded, np.float32(meta.offset),
                               np.float32(meta.scale),
                               np.asarray(meta.na_code, coded.dtype))
    if k == "sparse0":
        return _decode_sparse(coded, meta.plen)
    raise ValueError(f"unknown chunk kind '{k}'")


# ---------------------------------------------------------------------------
# Encode (host-side; ingest/compress time)
# ---------------------------------------------------------------------------
def _bits_equal(a: np.ndarray, b: np.ndarray) -> bool:
    """f32 bit-equality with all NaNs identified (any-payload NaN == NaN)."""
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    same = a.view(np.int32) == b.view(np.int32)
    return bool(np.all(same | (np.isnan(a) & np.isnan(b))))


def _int_candidate(vals, nan_mask, nrow, plen, cap, na_code, dtype, kind,
                   is_int):
    """Offset-scale integer codes covering every distinct value exactly, or
    None. The affine params are chosen so the f32 decode arithmetic
    reproduces the original f32 bits (verified by the caller)."""
    finite = vals[~nan_mask]
    u = np.unique(finite.astype(np.float64))
    if u.size == 0:
        return None
    offset = float(u[0])
    if u.size == 1:
        scale = 1.0
    else:
        scale = float(np.min(np.diff(u)))
        if scale <= 0:
            return None
    q = (u - offset) / scale
    qr = np.round(q)
    if np.max(np.abs(q - qr)) > 1e-6 or qr[-1] > cap:
        return None
    codes = np.full(plen, na_code, dtype=dtype)
    c = np.round((vals[~nan_mask].astype(np.float64) - offset) / scale)
    if c.min() < 0 or c.max() > cap:
        return None
    codes[~nan_mask] = c.astype(dtype)
    # host-side replica of the decode arithmetic as a cheap pre-filter; the
    # device kernel itself re-verifies in from_vec (fma-safe)
    dec = np.float32(offset) + codes.astype(np.float32) * np.float32(scale)
    dec = np.where(codes == na_code, np.float32(np.nan), dec)
    if not _bits_equal(dec, vals):
        return None
    zq = np.round((0.0 - offset) / scale)
    zero_code = int(zq) if (0 <= zq <= cap and
                            np.float32(offset)
                            + np.float32(zq) * np.float32(scale) == 0.0) \
        else -1
    meta = ChunkMeta(kind=kind, nrow=nrow, plen=plen, offset=offset,
                     scale=scale, na_code=na_code, is_int=is_int,
                     zero_code=zero_code)
    return codes, meta


def encode_column(host: np.ndarray, nrow: int,
                  is_cat: bool = False) -> tuple[np.ndarray, ChunkMeta]:
    """Pick the narrowest codec that reproduces ``host`` (a padded (plen,)
    f32 column, NaN = NA/padding) bit-exactly. Preference order is by coded
    bytes: const, int8 (1 B/row), sparse when it beats 2 B/row, int16,
    sparse when it beats 4 B/row, raw."""
    vals = np.ascontiguousarray(host, np.float32)
    plen = vals.shape[0]
    nan_mask = np.isnan(vals)
    is_int = bool(np.all(vals[~nan_mask] == np.floor(vals[~nan_mask]))) \
        if (~nan_mask).any() else False

    if nan_mask[:nrow].all():          # all-NA logical column
        return (np.zeros(1, np.int8),
                ChunkMeta(kind="const", nrow=nrow, plen=plen,
                          value=float("nan")))
    bits = vals.view(np.int32)
    if not nan_mask[:nrow].any() and np.all(bits[:nrow] == bits[0]):
        return (np.zeros(1, np.int8),
                ChunkMeta(kind="const", nrow=nrow, plen=plen,
                          value=float(vals[0]), is_int=is_int))

    # sparse payload: rows whose BITS are nonzero (keeps -0.0 and every NaN,
    # padding tail included) as (row, f32-bits) int32 pairs
    nz = np.nonzero(bits)[0]
    sparse_bytes = 8 * max(nz.size, 1)

    def sparse_pack():
        packed = np.stack([nz.astype(np.int32), bits[nz]], axis=0)
        return packed, ChunkMeta(kind="sparse0", nrow=nrow, plen=plen,
                                 is_int=is_int)

    cand = _int_candidate(vals, nan_mask, nrow, plen, _CAP8, _NA8, np.uint8,
                          "cat8" if is_cat else "int8", is_int)
    if cand is not None:
        return cand
    if sparse_bytes < 2 * plen:
        return sparse_pack()
    cand = _int_candidate(vals, nan_mask, nrow, plen, _CAP16, _NA16,
                          np.uint16, "cat16" if is_cat else "int16", is_int)
    if cand is not None:
        return cand
    if sparse_bytes < 4 * plen:
        return sparse_pack()
    return vals, ChunkMeta(kind="raw", nrow=nrow, plen=plen, is_int=is_int)


# ---------------------------------------------------------------------------
# Rollups from codes (lossless stats without decoding)
# ---------------------------------------------------------------------------
@jax.jit
def _code_rollup_kernel(codes, na_code, zero_code):
    ok = codes != na_code
    cf = codes.astype(jnp.float32)
    n = jnp.sum(ok)
    mean = jnp.sum(jnp.where(ok, cf, 0.0)) / jnp.maximum(n, 1)
    d = jnp.where(ok, cf - mean, 0.0)
    return dict(
        n=n,
        mean=mean,
        var=jnp.maximum(jnp.sum(d * d) / jnp.maximum(n, 1), 0.0),
        cmin=jnp.min(jnp.where(ok, cf, jnp.inf)),
        cmax=jnp.max(jnp.where(ok, cf, -jnp.inf)),
        zerocnt=jnp.sum(ok & (codes == zero_code)),
    )


@jax.jit
def _code_rollup_kernel_cols(codes, na_codes, zero_codes):
    """Batched code-space rollups over a (plen, C) code stack — one program
    + ONE host transfer for C coded columns (the `_rollup_kernel_cols` role:
    the per-column eager path costs a device round trip PER COLUMN).
    na/zero codes ride as int32 so uint8/uint16
    stacks compare without reinterpreting -1 sentinels."""
    ok = codes.astype(jnp.int32) != na_codes[None, :]
    cf = codes.astype(jnp.float32)
    n = jnp.sum(ok, axis=0)
    mean = jnp.sum(jnp.where(ok, cf, 0.0), axis=0) / jnp.maximum(n, 1)
    d = jnp.where(ok, cf - mean[None, :], 0.0)
    return dict(
        n=n,
        mean=mean,
        var=jnp.maximum(jnp.sum(d * d, axis=0) / jnp.maximum(n, 1), 0.0),
        cmin=jnp.min(jnp.where(ok, cf, jnp.inf), axis=0),
        cmax=jnp.max(jnp.where(ok, cf, -jnp.inf), axis=0),
        zerocnt=jnp.sum(
            ok & (codes.astype(jnp.int32) == zero_codes[None, :]), axis=0),
    )


def _rollups_from_code_stats(meta: ChunkMeta, r: dict, nrow: int) -> Rollups:
    """Affine-map code-space stats back to value space (f32 min/max match
    the decode arithmetic; sample-variance correction as in vec.py)."""
    n = int(r["n"])
    var = (float(meta.scale) ** 2) * float(r["var"]) * (n / max(n - 1, 1))
    dec = lambda c: float(np.float32(meta.offset)
                          + np.float32(c) * np.float32(meta.scale))
    return Rollups(
        mins=dec(r["cmin"]) if n else np.nan,
        maxs=dec(r["cmax"]) if n else np.nan,
        mean=float(meta.offset + meta.scale * float(r["mean"])) if n
        else np.nan,
        sigma=float(np.sqrt(var)) if n else np.nan,
        nacnt=nrow - n,
        zerocnt=int(r["zerocnt"]),
        nrow=nrow,
        is_int=meta.is_int)


def batch_code_rollups(vecs) -> list:
    """Fill missing rollups for CodedVecs without decoding: consts resolve
    host-side, int-coded columns batch into ONE device program per
    (plen, dtype) stack. Returns the vecs it could NOT serve (sparse/raw —
    they join the caller's decode-path batch)."""
    rest: list = []
    by_shape: dict = {}
    for v in vecs:
        if v._rollups is not None:
            continue
        m = getattr(v, "meta", None)
        if m is None:
            rest.append(v)
        elif m.kind == "const":
            v.rollups_from_codes()
        elif m.kind in _INT_KINDS:
            by_shape.setdefault((m.plen, np.dtype(v._code_dtype()).name),
                                []).append(v)
        else:
            rest.append(v)
    for group in by_shape.values():
        if len(group) == 1:
            group[0].rollups_from_codes()
            continue
        codes = jnp.stack([v.coded for v in group], axis=1)
        na = jnp.asarray([v.meta.na_code for v in group], jnp.int32)
        zero = jnp.asarray([v.meta.zero_code for v in group], jnp.int32)
        r = jax.device_get(_code_rollup_kernel_cols(codes, na, zero))
        for i, v in enumerate(group):
            v._rollups = _rollups_from_code_stats(
                v.meta, {k: r[k][i] for k in r}, v.nrow)
    return rest


class CodedVec(Vec):
    """A Vec whose device-resident state is the CODED column.

    ``.data`` decodes on access (a per-call f32 temporary — never resident);
    ``.coded`` is the tracked device array the Cleaner budgets, spills and
    rehydrates. Rollups come straight off the codes for const/int codecs
    (min/max/NA/zero counts are lossless in code space; mean/sigma are the
    same centered f32 reduction the raw kernel runs, on codes then
    affine-mapped)."""

    def __init__(self, coded, meta: ChunkMeta, nrow: int, type: str = T_NUM,
                 domain=None, exact_data=None, key=None):
        self.meta = meta
        super().__init__(coded, nrow, type=type, domain=domain,
                         exact_data=exact_data, key=key)

    # -- storage access ------------------------------------------------------
    @property
    def plen(self) -> int:
        # the padded length is decode metadata — never launch a full-column
        # decode (the base property reads self.data) to answer a shape query
        return self.meta.plen

    @property
    def coded(self) -> jax.Array:
        """The coded device array (touches the LRU clock; rehydrates)."""
        return Vec.data.fget(self)

    @property
    def data(self) -> jax.Array:
        return decode_chunk(Vec.data.fget(self), self.meta)

    @data.setter
    def data(self, value):
        # overwriting with a plain device column degrades the codec to raw
        # passthrough — the coded ledger entry is swapped for the new bytes,
        # and plen must track the NEW buffer (Vec's contract is the live
        # device shape; a stale meta.plen would mis-group this vec in
        # ensure_rollups' same-plen stacks)
        self.meta = replace(self.meta, kind="raw",
                            plen=(self.meta.plen if value is None
                                  else int(value.shape[0])))
        Vec.data.fset(self, value)

    def _put_sharding(self):
        if self.meta.kind in ("const", "sparse0"):
            # (1,) / (2, nnz) payloads don't row-shard; replicate on reload
            return meshmod.replicated(meshmod.default_mesh())
        return super()._put_sharding()

    def coded_nbytes(self) -> int:
        """Device-resident coded bytes (0 while spilled)."""
        c = self._data
        return 0 if c is None else c.size * c.dtype.itemsize

    # -- rollups -------------------------------------------------------------
    def rollups_from_codes(self) -> bool:
        """Compute + cache rollups without decoding when the codec allows;
        False sends the caller down the decode path (sparse0/raw)."""
        if self._rollups is not None:
            return True
        m = self.meta
        if m.kind == "const":
            if np.isnan(m.value):
                self._rollups = Rollups(np.nan, np.nan, np.nan, np.nan,
                                        self.nrow, 0, self.nrow, False)
            else:
                self._rollups = Rollups(
                    m.value, m.value, m.value, 0.0, 0,
                    self.nrow if m.value == 0.0 else 0, self.nrow, m.is_int)
            return True
        if m.kind in _INT_KINDS:
            r = jax.device_get(_code_rollup_kernel(
                self.coded, np.asarray(m.na_code, self._code_dtype()),
                np.int32(m.zero_code)))
            self._rollups = _rollups_from_code_stats(m, r, self.nrow)
            return True
        return False

    def _code_dtype(self):
        c = self._data
        return c.dtype if c is not None else \
            (np.uint8 if self.meta.kind in ("int8", "cat8") else np.uint16)

    def rollups(self) -> Rollups:
        if self._rollups is None and not self.rollups_from_codes():
            return super().rollups()
        return self._rollups

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_vec(vec: Vec) -> Vec:
        """Compress one column. Returns ``vec`` unchanged when it has no
        device data (strings), is already coded, or no codec wins (raw).
        The chosen codec is re-verified against the DEVICE decode kernel
        (host pre-verification can't see backend fma fusion); a mismatch
        falls back to the raw column."""
        if isinstance(vec, CodedVec) or vec.data is None:
            return vec
        host = np.asarray(Vec.data.fget(vec))
        coded_np, meta = encode_column(host, vec.nrow,
                                       is_cat=vec.is_categorical())
        if meta.kind == "raw":
            return vec
        mesh = meshmod.default_mesh()
        sharding = (meshmod.replicated(mesh)
                    if meta.kind in ("const", "sparse0")
                    else meshmod.row_sharding(mesh))
        coded = jax.device_put(coded_np, sharding)
        if not _bits_equal(np.asarray(decode_chunk(coded, meta)), host):
            return vec
        return CodedVec(coded, meta, vec.nrow, type=vec.type,
                        domain=vec.domain, exact_data=vec.exact_data)

    def __repr__(self) -> str:
        return (f"CodedVec({self.key}, nrow={self.nrow}, type={self.type}, "
                f"codec={self.meta.kind})")


def compress_frame(fr):
    """A new Frame with every compressible column coded (`Frame.compress`)."""
    from ..backend.kvstore import STORE
    from .frame import Frame

    out = Frame(list(fr.names), [CodedVec.from_vec(v) for v in fr.vecs])
    STORE.put_keyed(out)
    return out


# ---------------------------------------------------------------------------
# BinnedView — device-resident int8/int16 binned training matrix
# ---------------------------------------------------------------------------
@jax.jit
@telemetry.program("gbm_setup_stack")
@telemetry.scope("gbm.bin")
def _stack_codes(*cols):
    return jnp.stack(cols, axis=1)


class BinnedView(Vec):
    """The packed (plen, F) bin-code matrix the tree engine trains on.

    Subclassing Vec buys the whole residency protocol for free: the coded
    bytes are Cleaner-tracked, so a model-building pass at the HBM edge
    budgets honestly against the live binned view (`hbm_budget_bytes()`
    subtracts it). The view is PINNED (never spilled): its consumer — the
    jitted train loop — holds the device buffer for the view's whole
    lifetime, so a sweep could only pay a multi-GB ice write and corrupt
    the ledger without freeing a byte of HBM."""

    def __init__(self, matrix, edges_np: np.ndarray, names=None):
        self.edges_np = edges_np
        self.col_names = list(names) if names is not None else None
        self._pinned = True  # before track(): the registering sweep must
                             # already see the pin
        super().__init__(matrix, matrix.shape[0], type="binned")

    @property
    def matrix(self) -> jax.Array:
        """The (plen, F) code matrix (touches the LRU clock; rehydrates)."""
        return Vec.data.fget(self)

    @property
    def nbytes(self) -> int:
        m = self._data
        return 0 if m is None else m.size * m.dtype.itemsize

    @staticmethod
    def code_dtype(nbins_tot: int):
        """Narrowest signed dtype holding codes 0..nbins_tot (NA bucket)."""
        if nbins_tot <= np.iinfo(np.int8).max:
            return jnp.int8
        if nbins_tot <= np.iinfo(np.int16).max:
            return jnp.int16
        return jnp.int32

    @staticmethod
    def build(cols, edges_np: np.ndarray, names=None) -> "BinnedView":
        """Bin column-by-column against ``edges_np`` ((F, W) NaN-padded cut
        rows) and pack into one narrow-dtype matrix. ``cols`` may be Vecs
        (CodedVecs decode one column at a time) or device arrays; the raw
        f32 matrix is never materialized — peak transient footprint is the
        per-column code vectors plus the packed matrix (2 coded bytes/cell
        at int8), vs 8 f32+int32 bytes/cell on the stacked path."""
        from ..models.tree.binning import _coldata, bin_column

        cols = list(cols)
        assert len(cols) == edges_np.shape[0], "one edge row per column"
        dtype = BinnedView.code_dtype(edges_np.shape[1] + 1)
        edges_dev = jnp.asarray(np.ascontiguousarray(edges_np, np.float32))
        codes = [bin_column(_coldata(c), edges_dev[f], dtype=dtype)
                 for f, c in enumerate(cols)]
        # EXPLICIT row_sharding for the packed matrix: the per-column code
        # vectors are row-sharded, but the stacked result's placement is
        # otherwise whatever GSPMD picked — the training-matrix layout the
        # per-chip HBM budget depends on must be policy, not inference
        # (each device holds exactly its plen/n_shards row slice, which the
        # shard_map trainer consumes without any relayout)
        matrix = jax.device_put(_stack_codes(*codes),
                                meshmod.row_sharding(meshmod.default_mesh()))
        return BinnedView(matrix, edges_np, names=names)

    def __repr__(self) -> str:
        shape = None if self._data is None else tuple(self._data.shape)
        return f"BinnedView({self.key}, shape={shape})"
