"""Shape-bucketed scorers — the no-recompile contract of the serving runtime.

Every request shape that reaches XLA is a potential compile, and a compile
on the request path is a multi-second latency cliff. The fix is the same
one the DrJAX-style training driver uses for its batched map/reduce: pin
the shape set up front. A `CompiledScorer` AOT-compiles ONE executable per
configured bucket size (``H2O_TPU_SERVING_BUCKETS``, a power-of-two ladder)
at registration — `jit(...).lower(ShapeDtypeStruct).compile()` — and at
request time pads each micro-batch up to the smallest bucket that fits
(chunking through the largest bucket for oversized batches). A compiled
executable *cannot* retrace: steady-state serving performs zero compiles by
construction, and `utils/compilemeter.py` makes that assertable.

Padded rows are zero-filled and sliced off the output. Scoring is row-wise
(trees route rows independently, GLM/KMeans are per-row dots), so padding
can't perturb real rows — the parity tests pin batched-vs-single-row
BIT-equality across every bucket size and model category.

`HostScorer` is the same bucket/pad/mask surface over a host (numpy) batch
scorer — the standalone MOJO readers (`mojo/reader.py`) — so registered
MOJO files serve through the identical runtime with a trivially-zero
compile count.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..utils import compilemeter, knobs
from .errors import UnsupportedModelError


def bucket_sizes(override=None) -> tuple[int, ...]:
    """The configured bucket ladder, ascending and deduplicated."""
    if override is None:
        spec = knobs.get_str("H2O_TPU_SERVING_BUCKETS")
        sizes = [int(t) for t in spec.split(",") if t.strip()]
    else:
        sizes = [int(b) for b in override]
    sizes = sorted({b for b in sizes if b > 0})
    if not sizes:
        raise ValueError("serving bucket list is empty "
                         "(H2O_TPU_SERVING_BUCKETS)")
    return tuple(sizes)


class _BucketedScorer:
    """Shared pad/chunk/mask logic over a per-bucket batch scorer."""

    def __init__(self, n_features: int, buckets, dtype, device=None):
        self.n_features = int(n_features)
        self.buckets = bucket_sizes(buckets)
        self.dtype = dtype
        #: optional pinned placement (replica scorers compile one
        #: executable set per mesh device; None = backend default)
        self.device = device
        self.warmup_compiles = 0
        #: cumulative bucket-miss fallbacks — each one IS a steady-state
        #: compile. This, not a global-counter delta, feeds the stats
        #: `recompiles` gauge: the process compile counter also ticks for
        #: concurrent training/registration work that is not this model's
        #: fault (a false zero-recompile violation otherwise).
        self.fallback_compiles = 0

    # subclasses: score exactly one padded (b, F) bucket -> np.ndarray
    def _score_bucket(self, Xp: np.ndarray, b: int) -> np.ndarray:
        raise NotImplementedError

    def warmup(self) -> int:
        return 0

    def evict(self) -> int:
        """Drop placed executables (cold-priority eviction); returns the
        compiles a re-placement will cost (0 for host scorers)."""
        return 0

    @property
    def placed(self) -> bool:
        return True

    def _bucket_of(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def score(self, X: np.ndarray) -> np.ndarray:
        """(N, F) rows → (N, ...) predictions; N is unconstrained — batches
        beyond the largest bucket chunk through it."""
        X = np.asarray(X, dtype=self.dtype)
        if X.ndim != 2 or X.shape[1] != self.n_features:
            raise ValueError(f"expected (N, {self.n_features}) rows, got "
                             f"{X.shape}")
        outs = []
        i, n = 0, X.shape[0]
        while i < n:
            b = self._bucket_of(n - i)
            take = min(n - i, b)
            if take == b:
                Xp = X[i:i + b]
            else:
                Xp = np.zeros((b, self.n_features), dtype=self.dtype)
                Xp[:take] = X[i:i + take]
            out = np.asarray(self._score_bucket(Xp, b))
            outs.append(out[:take])
            i += take
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)


class CompiledScorer(_BucketedScorer):
    """Engine models: jit of ``model.score_raw`` AOT-compiled per bucket.

    ``device`` pins every executable (and every padded input) to one mesh
    device — the replica-placement lever: N replicas of a model are N
    CompiledScorers on N devices, and the executables CANNOT silently
    migrate (a compiled object rejects mismatched shardings loudly)."""

    def __init__(self, model, buckets=None, device=None):
        import jax

        from ..models.model_base import Model

        # a model that reshapes frames on the way in (GAM's spline basis,
        # DL's DataInfo pipeline, ...) but never declared a matrix-level
        # twin would silently score garbage — refuse it loudly instead
        if type(model).score_raw is Model.score_raw and \
                type(model).adapt_frame is not Model.adapt_frame:
            raise UnsupportedModelError(
                f"{type(model).__name__} overrides adapt_frame without a "
                f"score_raw matrix path — register its MOJO instead")
        # a frozen categorical_encoding renames/expands the columns before
        # base adapt_frame ever sees them (pre_adapt's encoding replay):
        # output.names are the ENCODED names, so the serving row encoder
        # would NaN every client cell and serve imputed garbage with a 200
        if getattr(model.output, "encoding_state", None) is not None:
            raise UnsupportedModelError(
                f"{type(model).__name__} was trained with a frozen "
                f"categorical_encoding — its raw-matrix path needs the "
                f"Frame-side encoding replay; register its MOJO instead")
        super().__init__(len(model.output.names), buckets, np.float32,
                         device=device)
        self._jit = jax.jit(model.score_raw)
        self._compiled: dict[int, object] = {}
        #: program-registry identity (utils/programs.py) — stable across
        #: processes serving the same model
        self._program_name = (f"serving.score."
                              f"{type(model).__name__.lower()}")
        self._model_key = str(getattr(model, "key", "?"))

    def warmup(self) -> int:
        """Compile every bucket and prime it with one scored batch of
        zeros; returns (and records) the XLA compiles that cost. After
        this, `_score_bucket` never compiles — the executables are frozen.
        """
        import contextlib

        import jax
        import jax.numpy as jnp

        before = compilemeter.count()
        pin = (jax.default_device(self.device) if self.device is not None
               else contextlib.nullcontext())
        from ..utils import programs

        with pin:
            for b in self.buckets:
                spec = jax.ShapeDtypeStruct((b, self.n_features),
                                            jnp.float32)
                # warmup IS the declared compile window: one AOT lower+
                # compile per registered bucket, counted and frozen — the
                # steady-state scorer never compiles again (the recompile
                # sanitizer arms only AFTER this boundary)
                self._compiled[b] = self._jit.lower(spec).compile()  # graftlint: disable=recompile-hazard
                # one real execution per bucket: surfaces runtime-only
                # errors (bad gather bounds, NaN traps) at registration,
                # not under load
                self._score_bucket(
                    np.zeros((b, self.n_features), np.float32), b)
                # one cost-registry entry per bucket executable — the
                # serving face of /3/Programs (what does a scored batch
                # COST, statically, per bucket)
                programs.register_compiled(
                    self._program_name, self._compiled[b], "serving",
                    sig=(((b, self.n_features), "float32"),),
                    wall_metric="serving.request.seconds",
                    module=programs.module_of(self._jit),
                    model=self._model_key, bucket=b)
        self.warmup_compiles = compilemeter.count() - before
        return self.warmup_compiles

    def evict(self) -> int:
        """Drop the compiled executables (the cold-priority eviction hook):
        the next warmup() re-pays the bucket compiles. Returns how many."""
        n = len(self._compiled)
        self._compiled.clear()
        return n

    @property
    def placed(self) -> bool:
        return bool(self._compiled)

    def _score_bucket(self, Xp: np.ndarray, b: int) -> np.ndarray:
        import jax

        from ..utils import sanitizer

        fn = self._compiled.get(b)
        aot = fn is not None
        # post-warmup the WHOLE score path is declared steady — a bucket-
        # miss landing on the jit fallback below is exactly the uncached
        # compile H2O_TPU_SANITIZE=recompiles raises typed on. With the
        # sanitizer off the miss keeps degrading to a counted compile.
        steady = bool(self._compiled)
        if fn is None:  # unreachable after warmup(); kept non-fatal so a
            fn = self._jit  # mis-sized bucket degrades to a counted compile
            self.fallback_compiles += 1
        # staging and result fetch are EXPLICIT transfers (device_put /
        # device_get), so the steady-state path runs silent under the full
        # transfer guard in both directions — any other implicit transfer
        # on the score path is a bug the sanitizer raises typed. The h2d
        # guard arms only on the AOT path: the jit fallback TRACES, and
        # tracing stages constants host->device legitimately.
        X = jax.device_put(Xp, self.device)
        with sanitizer.transfer_scope("serving.score",
                                      host_to_device=aot), \
                (compilemeter.no_compile_scope("serving.score") if steady
                 else contextlib.nullcontext()):
            out = fn(X)
            return np.asarray(jax.device_get(out))


class HostScorer(_BucketedScorer):
    """MOJO models: the numpy batch scorer behind the same bucket surface."""

    def __init__(self, mojo_model, n_features: int, buckets=None):
        super().__init__(n_features, buckets, np.float64)
        self._model = mojo_model

    def warmup(self) -> int:
        for b in self.buckets:
            self._score_bucket(np.zeros((b, self.n_features), np.float64), b)
        self.warmup_compiles = 0
        return 0

    def _score_bucket(self, Xp: np.ndarray, b: int) -> np.ndarray:
        return np.asarray(self._model.score(Xp))
