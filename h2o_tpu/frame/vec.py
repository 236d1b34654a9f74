"""Vec — a distributed column held in TPU HBM.

Reference: `water/fvec/Vec.java` (1,783 LoC) — a chunked, compressed, typed
distributed column with lazy rollup stats. The TPU-native design (SURVEY.md §7.2):

- storage is ONE row-sharded ``jax.Array`` (float32), padded to an equal-shard
  length; padding rows and missing values are both NaN. This replaces the 21
  per-chunk compression codecs (`water/fvec/C*.java`) — a deliberate divergence:
  HBM arrays want fixed-width vectorizable layouts, and bf16/int8 casts at the
  point of use recover the bandwidth that byte-packing bought on the JVM.
- the chunk layout / ESPC machinery (`fvec/Vec.java:152-166`) becomes "equal
  padded shards + global nrow"; per-row masks are derived on device.
- types mirror the reference (`fvec/Vec.java:12-103`): numeric, int, categorical
  (int codes + host-side domain), time (ms since epoch), string (host-side —
  variable-length data has no business in HBM).
- rollups (min/max/mean/sigma/NA-count/zero-count) are computed lazily by one
  fused device reduction and cached until the data version changes — mirroring
  `water/fvec/RollupStats.java` (572 LoC) without the volatile-task dance.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.kvstore import Keyed, make_key
from ..parallel import mesh as meshmod

T_NUM = "real"
T_INT = "int"
T_CAT = "enum"
T_TIME = "time"
T_STR = "string"
T_UUID = "uuid"
T_BAD = "bad"  # all-NA column

NUMERIC_TYPES = (T_NUM, T_INT, T_CAT, T_TIME, T_BAD)


class Rollups:
    """Cached summary stats — analog of `water/fvec/RollupStats.java`."""

    __slots__ = ("mins", "maxs", "mean", "sigma", "nacnt", "zerocnt", "nrow", "is_int")

    def __init__(self, mins, maxs, mean, sigma, nacnt, zerocnt, nrow, is_int):
        self.mins = mins
        self.maxs = maxs
        self.mean = mean
        self.sigma = sigma
        self.nacnt = nacnt
        self.zerocnt = zerocnt
        self.nrow = nrow
        self.is_int = is_int


@jax.jit
def _rollup_kernel(data: jax.Array):
    """Fused rollup pass; NaN rows (NA + padding) drop out.

    Variance is computed centered (two reductions inside one XLA program) —
    the E[x²]−mean² shortcut cancels catastrophically in f32 for columns with
    large mean relative to spread (time columns, IDs). The reference computes
    rollups in double (`water/fvec/RollupStats.java`); centering buys the same
    robustness without f64 on the MXU.
    """
    ok = ~jnp.isnan(data)
    x = jnp.where(ok, data, 0.0)
    n = jnp.sum(ok)
    mean = jnp.sum(x) / jnp.maximum(n, 1)
    d = jnp.where(ok, data - mean, 0.0)
    var = jnp.sum(d * d) / jnp.maximum(n, 1)
    return dict(
        mins=jnp.min(jnp.where(ok, data, jnp.inf)),
        maxs=jnp.max(jnp.where(ok, data, -jnp.inf)),
        mean=mean,
        var=jnp.maximum(var, 0.0),
        n=n,
        zerocnt=jnp.sum(ok & (data == 0.0)),
        isint=jnp.all(jnp.where(ok, data == jnp.floor(data), True)),
    )


@jax.jit
def _rollup_kernel_cols(X: jax.Array):
    """Batched rollups over a (plen, C) column stack — identical math to
    `_rollup_kernel`, one program + ONE host transfer for C columns
    (instead of a dispatch and a host fetch PER COLUMN).
    Production now dispatches `_rollup_mr_map` through the MRTask driver;
    this fused kernel stays as the bit-level parity ORACLE the telemetry
    tests pin the mr path against (tests/test_telemetry.py) — change the
    rollup math in both places or that test fails."""
    ok = ~jnp.isnan(X)
    x = jnp.where(ok, X, 0.0)
    n = jnp.sum(ok, axis=0)
    mean = jnp.sum(x, axis=0) / jnp.maximum(n, 1)
    d = jnp.where(ok, X - mean[None, :], 0.0)
    var = jnp.sum(d * d, axis=0) / jnp.maximum(n, 1)
    return dict(
        mins=jnp.min(jnp.where(ok, X, jnp.inf), axis=0),
        maxs=jnp.max(jnp.where(ok, X, -jnp.inf), axis=0),
        mean=mean,
        var=jnp.maximum(var, 0.0),
        n=n,
        zerocnt=jnp.sum(ok & (X == 0.0), axis=0),
        isint=jnp.all(jnp.where(ok, X == jnp.floor(X), True), axis=0),
    )


def _rollup_mr_map(cols, rows):
    """Per-shard rollup partials for the MRTask driver — the batched rollup
    pass as an actual map/reduce: the same centered-variance math as
    ``_rollup_kernel_cols`` (global mean via an INTERNAL ``psum`` — DrJAX's
    map-with-collectives shape), partial sums/mins/maxs combined by the
    driver's named monoids. NaN rows (NA + mesh padding) drop out of every
    reduction, exactly like the fused kernel. Module-level so the driver's
    per-map_fn program cache engages across frames."""
    from ..parallel.mesh import ROWS

    X = jnp.stack(cols, axis=1)  # (shard_rows, C)
    ok = ~jnp.isnan(X)
    x = jnp.where(ok, X, 0.0)
    n_part = jnp.sum(ok, axis=0)
    n = jax.lax.psum(n_part, ROWS)
    mean = jax.lax.psum(jnp.sum(x, axis=0), ROWS) / jnp.maximum(n, 1)
    d = jnp.where(ok, X - mean[None, :], 0.0)
    return {
        "n": n_part,
        "sum": jnp.sum(x, axis=0),
        "varsum": jnp.sum(d * d, axis=0),
        "mins": jnp.min(jnp.where(ok, X, jnp.inf), axis=0),
        "maxs": jnp.max(jnp.where(ok, X, -jnp.inf), axis=0),
        "zerocnt": jnp.sum(ok & (X == 0.0), axis=0),
        "isint": jnp.min(jnp.where(ok, X == jnp.floor(X),
                                   True).astype(jnp.int32), axis=0),
    }


#: the per-output monoids `_rollup_mr_map` reduces under
_ROLLUP_REDUCE = {"n": "sum", "sum": "sum", "varsum": "sum", "mins": "min",
                  "maxs": "max", "zerocnt": "sum", "isint": "min"}


def _rollups_from_scalars(nrow: int, r: dict) -> "Rollups":
    n = int(r["n"])
    var = float(r["var"]) * (n / max(n - 1, 1))  # sample variance
    return Rollups(
        mins=float(r["mins"]) if n else np.nan,
        maxs=float(r["maxs"]) if n else np.nan,
        mean=float(r["mean"]) if n else np.nan,
        sigma=float(np.sqrt(var)) if n else np.nan,
        nacnt=nrow - n,
        zerocnt=int(r["zerocnt"]),
        nrow=nrow,
        is_int=bool(r["isint"]),
    )


class Vec(Keyed):
    def __init__(
        self,
        data: jax.Array,
        nrow: int,
        type: str = T_NUM,
        domain: list[str] | None = None,
        key: str | None = None,
        host_data: np.ndarray | None = None,
        exact_data: np.ndarray | None = None,
    ):
        super().__init__(key=key, prefix="vec")
        import threading

        self._lock = threading.RLock()  # guards _data/_spill_path transitions
        self._data = data  # padded, row-sharded float32 (None for string vecs)
        self._spill_path: str | None = None  # Cleaner "ice" file when spilled
        self._last_access = 0
        self.nrow = int(nrow)
        self.type = type
        self.domain = domain  # categorical level names (host-side)
        self.host_data = host_data  # for T_STR/T_UUID: numpy object array
        self.exact_data = exact_data  # exact int64/f64 copy when f32 is lossy
        self._rollups: Rollups | None = None
        self._version = 0
        if data is not None:
            from ..backend.memory import CLEANER

            self._last_access = CLEANER.touch(self)
            CLEANER.track(self, data.size * data.dtype.itemsize)

    @property
    def data(self):
        """The device column. Spilled Vecs rehydrate transparently (the
        Cleaner's swap-in path, `water/Cleaner.java` lazy reload role).
        Thread-safe: concurrent readers and Cleaner sweeps serialize on the
        per-Vec lock, and the rehydrating access is excluded from the sweep
        it may trigger — the getter never returns None for a numeric vec."""
        from ..backend.memory import CLEANER

        with self._lock:
            if self._data is None and self._spill_path is not None:
                from ..utils import telemetry

                host = np.load(self._spill_path)
                self._data = self._rehydrate_put(host)
                CLEANER._remove_ice(self._spill_path)
                self._spill_path = None
                self._last_access = CLEANER.touch(self)
                nbytes = self._data.size * self._data.dtype.itemsize
                CLEANER.track(self, nbytes)
                telemetry.inc("cleaner.rehydrate.count")
                telemetry.inc("cleaner.rehydrate.bytes", nbytes)
            elif self._data is not None:
                self._last_access = CLEANER.touch(self)
            return self._data

    def _rehydrate_put(self, host: np.ndarray):
        """Spilled payload -> device, surviving a device OOM: when HBM is
        so contended the reload itself RESOURCE_EXHAUSTs, emergency-spill
        every other unpinned resident and retry once — losing one LRU round
        beats killing the job mid-scoring (the failure mode the
        ``cleaner.rehydrate`` failpoint injects on demand)."""
        import jax

        from ..backend.memory import CLEANER
        from ..utils import failpoints

        def put():
            failpoints.hit("cleaner.rehydrate")
            return jax.device_put(host, self._put_sharding())

        try:
            return put()
        except Exception as e:  # noqa: BLE001 — OOM-classified right below
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            from ..utils.log import warn

            freed = CLEANER.emergency_sweep(
                exclude=getattr(self, "_cleaner_token", None))
            warn(f"device OOM rehydrating {self.key}: emergency-spilled "
                 f"{freed} bytes, retrying")
            try:
                return put()  # a still-armed injection fails this too
            except Exception as e2:  # noqa: BLE001 — typed below
                if "RESOURCE_EXHAUSTED" in str(e2):
                    # OOM that survived the spill-everything sweep: the
                    # process genuinely cannot fit this buffer — the
                    # flight recorder's canonical terminal event (no-op
                    # unless H2O_TPU_FLIGHT_DIR is set)
                    from ..utils import flightrec

                    flightrec.dump("device-oom", e2)
                raise

    @data.setter
    def data(self, value):
        from ..backend.memory import CLEANER

        with self._lock:
            old = self._data
            old_path = self._spill_path
            self._data = value
            self._spill_path = None
            if old is not None or old_path is not None:
                CLEANER.note_freed(
                    self, 0 if old is None else old.size * old.dtype.itemsize,
                    old_path)
            if value is not None:
                self._last_access = CLEANER.touch(self)
                CLEANER.track(self, value.size * value.dtype.itemsize)

    def _put_sharding(self):
        """Sharding for (re)hydrating this Vec's device payload. Row-sharded
        by default; coded chunk payloads whose leading axis is not the row
        axis (const/sparse codecs, `frame/chunks.py`) override this."""
        from ..parallel.mesh import default_mesh, row_sharding

        return row_sharding(default_mesh())

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, type: str | None = None,
                   domain: list[str] | None = None, mesh=None) -> "Vec":
        """Build a row-sharded Vec from host data (the ingest endpoint)."""
        arr = np.asarray(arr)
        nrow = arr.shape[0]
        if arr.dtype == object or arr.dtype.kind in "US":
            return Vec(None, nrow, type=T_STR, host_data=np.asarray(arr, dtype=object))
        plen = meshmod.padded_len(nrow, mesh)
        buf = np.full(plen, np.nan, dtype=np.float32)
        f32 = arr.astype(np.float32)
        buf[:nrow] = f32
        if type is None:
            if domain is not None:
                type = T_CAT
            elif arr.dtype.kind in "iu" or (arr.dtype.kind == "b"):
                type = T_INT
            else:
                type = T_NUM
        # f32 is lossy above 2^24 (big int ids, ms-since-epoch times). Device
        # compute stays f32 (MXU wants it) but the exact values are retained
        # host-side so the logical column (to_numpy/at/export) is never corrupted
        # — the f32 HBM copy is then a compute projection, like the reference's
        # scaled-decimal codecs are a storage projection (`fvec/C2SChunk.java`).
        exact = None
        if arr.dtype.kind in "iuf" and arr.dtype.itemsize > 4 and nrow:
            back = f32.astype(arr.dtype) if arr.dtype.kind in "iu" else f32.astype(np.float64)
            with np.errstate(invalid="ignore"):
                lossy = ~np.isclose(back, arr, rtol=0, atol=0, equal_nan=True)
            if lossy.any():
                exact = arr.copy()
        data = jax.device_put(buf, meshmod.row_sharding(mesh))
        return Vec(data, nrow, type=type, domain=domain, exact_data=exact)

    @staticmethod
    def from_device(data: jax.Array, nrow: int, type: str = T_NUM,
                    domain: list[str] | None = None) -> "Vec":
        return Vec(data, nrow, type=type, domain=domain)

    # -- basic props ---------------------------------------------------------
    def __len__(self) -> int:
        return self.nrow

    @property
    def plen(self) -> int:
        return self.nrow if self.data is None else self.data.shape[0]

    def is_numeric(self) -> bool:
        return self.type in (T_NUM, T_INT)

    def is_categorical(self) -> bool:
        return self.type == T_CAT

    def is_string(self) -> bool:
        return self.type == T_STR

    def cardinality(self) -> int:
        return len(self.domain) if self.domain else -1

    # -- data access ---------------------------------------------------------
    def to_numpy(self) -> np.ndarray:
        """Gather the logical column to host (NA as NaN)."""
        if self.data is None:
            return self.host_data
        if self.exact_data is not None:
            return self.exact_data
        return np.asarray(self.data)[: self.nrow]

    def at(self, i: int):
        """Single-element read — `Chunk.at()` analog; O(1) but host-syncing."""
        if not -self.nrow <= i < self.nrow:
            raise IndexError(f"row {i} out of range for Vec of {self.nrow} rows")
        if i < 0:
            i += self.nrow
        if self.data is None:
            return self.host_data[i]
        if self.exact_data is not None:
            return self.exact_data[i]
        return float(self.data[i])

    def modified(self) -> None:
        """Invalidate cached rollups after an in-place-style update."""
        self._rollups = None
        self._version += 1

    # -- rollups -------------------------------------------------------------
    def rollups(self) -> Rollups:
        if self._rollups is None:
            if self.data is None:
                nacnt = int(sum(1 for v in self.host_data if v is None))
                self._rollups = Rollups(np.nan, np.nan, np.nan, np.nan,
                                        nacnt, 0, self.nrow, False)
            else:
                r = jax.device_get(_rollup_kernel(self.data))
                self._rollups = _rollups_from_scalars(self.nrow, r)
        return self._rollups

    def mean(self) -> float:
        return self.rollups().mean

    def sigma(self) -> float:
        return self.rollups().sigma

    def min(self) -> float:
        return self.rollups().mins

    def max(self) -> float:
        return self.rollups().maxs

    def nacnt(self) -> int:
        return self.rollups().nacnt

    # -- transforms ----------------------------------------------------------
    def with_data(self, data: jax.Array, type: str | None = None,
                  domain: Any = "__same__") -> "Vec":
        return Vec(data, self.nrow, type=type or self.type,
                   domain=self.domain if domain == "__same__" else domain)

    def astype_cat(self, domain: list[str]) -> "Vec":
        return Vec(self.data, self.nrow, type=T_CAT, domain=domain)

    def __repr__(self) -> str:
        dom = f", card={len(self.domain)}" if self.domain else ""
        return f"Vec({self.key}, nrow={self.nrow}, type={self.type}{dom})"
