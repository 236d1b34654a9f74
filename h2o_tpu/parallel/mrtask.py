"""mr_task — the TPU-native MRTask (`water/MRTask.java`, 989 LoC).

The reference's compute engine is a distributed map/reduce: ``map(Chunk[])`` runs
data-local on every chunk's home node, partial results ``reduce`` pairwise up a
binary RPC tree over nodes and a fork-join tree within nodes
(`water/MRTask.java:94-119, 740-759, 855-926`). On TPU the entire mechanism —
task fan-out, data-locality, tree reduction — collapses into one SPMD program:
``shard_map`` runs the map on every device against its local row shard, and the
reduction is an XLA collective over ICI (`psum`/`pmin`/`pmax`), which subsumes
H2O's two-level reduce tree (SURVEY.md §2.4.2).

Two entry points:

- ``mr_reduce``  — map each shard to a pytree of partials, combine across shards
  with a named monoid per call (the `map`+`reduce` path).
- ``mr_map``     — map rows to new row-aligned outputs (the `outputFrame` path,
  `water/MRTask.java:226-251`): returns new sharded per-row arrays.

Map functions receive ``(local_cols, rows)`` where ``rows`` carries the global
row ids and validity mask for the shard — the analog of `Chunk.start()` plus the
ESPC row accounting. Padding rows (beyond the frame's nrow) must contribute the
monoid identity; ``rows.mask`` makes that a one-liner.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import ROWS, default_mesh, row_sharding, shard_map

_REDUCERS = {
    "sum": jax.lax.psum,
    "min": jax.lax.pmin,
    "max": jax.lax.pmax,
}


@dataclass
class RowInfo:
    """Per-shard row accounting handed to map functions inside shard_map."""

    ids: jax.Array  # (shard_rows,) int32 global row indices
    mask: jax.Array  # (shard_rows,) bool, False on padding rows
    nrow: int  # global logical row count

    def maskf(self, dtype=jnp.float32) -> jax.Array:
        return self.mask.astype(dtype)


def _row_info(shard_rows: int, nrow: int) -> RowInfo:
    idx = jax.lax.axis_index(ROWS)
    ids = idx * shard_rows + jnp.arange(shard_rows, dtype=jnp.int32)
    return RowInfo(ids=ids, mask=ids < nrow, nrow=nrow)


def _driver_program(map_fn, mesh: Mesh, nrow: int, reduce_key, avt,
                    out_rows: bool):
    """Build (and cache) the jitted shard_map for one (map_fn, mesh, shapes,
    nrow, reduction) signature. Without this every generic driver call paid a
    fresh trace + compile-cache lookup — the tree engine caches its train fn
    for exactly this reason (`engine.py` _TRAIN_FN_CACHE). Programs cache ON
    the map function object (the compiled program necessarily closes over
    map_fn, so any global cache would pin the closure — and every frame or
    array it captured — forever; as a function attribute the whole thing is
    one self-cycle the gc reclaims the moment the caller drops map_fn)."""
    per_fn = getattr(map_fn, "__h2o_mr_programs__", None)
    if per_fn is None:
        per_fn = {}
        try:
            map_fn.__h2o_mr_programs__ = per_fn
        except AttributeError:  # bound methods / partials: no caching
            per_fn = None
    sig = (mesh, nrow, reduce_key, avt, out_rows)
    if per_fn is not None:
        hit = per_fn.get(sig)
        if hit is not None:
            return hit
    prog = _build_driver_program(map_fn, mesh, nrow, reduce_key, avt,
                                 out_rows)
    if per_fn is not None:
        per_fn[sig] = prog
    return prog


def _build_driver_program(map_fn, mesh: Mesh, nrow: int, reduce_key, avt,
                          out_rows: bool):
    from ..utils import programs, telemetry

    telemetry.inc("mrtask.program.build.count")
    reduce = reduce_key if isinstance(reduce_key, (str, type(None))) \
        else dict(reduce_key)
    shard_rows = avt[0][0][0] // mesh.shape[ROWS]

    @telemetry.program("mrtask_driver")
    def spmd(*cols):
        rows = _row_info(shard_rows, nrow)
        out = map_fn(cols, rows)
        if out_rows:
            return out
        if isinstance(reduce, str):
            return jax.tree.map(lambda x: _REDUCERS[reduce](x, ROWS), out)
        return {k: jax.tree.map(lambda x: _REDUCERS[reduce[k]](x, ROWS), v)
                for k, v in out.items()}

    # each spec is built in ONE constructor call (graftlint `pspec-concat`)
    in_specs = tuple(P(ROWS, *([None] * (len(shape) - 1)))
                     for shape, _ in avt)
    out_specs = P(ROWS) if out_rows else P()
    jitted = jax.jit(shard_map(spmd, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs))
    # every driver program registers its XLA cost/memory analyses under a
    # stable id (utils/programs.py): the tracked wrapper AOT-compiles on
    # first dispatch — the same one compile the jit dispatch would pay —
    # and falls back to the jitted twin on any signature the executable
    # rejects, so dispatch behavior can only degrade to exactly this line
    return programs.tracked(
        f"mrtask.{getattr(map_fn, '__name__', 'map_fn')}", jitted,
        "dispatch", wall_metric="mrtask.dispatch.seconds",
        rows=nrow, out_rows=out_rows)


def _avt(arrays) -> tuple:
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def mr_reduce(
    map_fn: Callable[[Sequence[jax.Array], RowInfo], Any],
    arrays: Sequence[jax.Array],
    nrow: int,
    reduce: str | dict[str, str] = "sum",
    mesh: Mesh | None = None,
):
    """Distributed map/reduce over row-sharded columns.

    ``map_fn(local_arrays, rows) -> pytree`` runs per shard; leaves are combined
    across the ``rows`` mesh axis with the given monoid ("sum"|"min"|"max", or a
    dict keyed by top-level output name for mixed reductions). The result is
    replicated (every shard returns the full reduction) and returned to host.
    The compiled program is cached per (map_fn, mesh, shapes, nrow, reduction)
    — a second invocation with the same signature traces nothing. Like
    ``jax.jit``, values ``map_fn`` closes over are baked in at trace time:
    pass varying data through ``arrays``, not through captured mutable state.
    """
    from ..utils import failpoints

    failpoints.hit("mrtask.dispatch")
    mesh = mesh or default_mesh()
    arrays = tuple(arrays)
    reduce_key = reduce if isinstance(reduce, str) \
        else tuple(sorted(reduce.items()))
    return _dispatch(map_fn, mesh, nrow, reduce_key, arrays, out_rows=False)


#: thread-id -> (monotonic start, map_fn name) of driver dispatches
#: currently executing — the watchdog's mrtask-stall detector scans this
#: (a dispatch that never returns is otherwise invisible until a human
#: reads the timeline). Each thread writes only its own key.
_INFLIGHT: dict[int, tuple[float, str]] = {}


def inflight_dispatches() -> dict[int, tuple[float, str]]:
    """Atomic copy of the in-flight dispatch table (utils/watchdog.py)."""
    return dict(_INFLIGHT)


def _dispatch(map_fn, mesh, nrow, reduce_key, arrays, out_rows: bool):
    """Shared instrumented dispatch — DrJAX-style per-stage accounting for
    the driver: the ``build`` phase is the host-side program resolution
    (trace + compile on a cache miss), ``dispatch`` the async device launch
    (the map/reduce/psum itself runs inside the one compiled program; its
    device wall drains at the caller's sync point). Payload bytes in/out
    come from array metadata, so the accounting costs no transfers."""
    import threading
    import time

    from ..utils import sanitizer, telemetry
    from ..workload import fairshare

    in_bytes = sum(getattr(a, "nbytes", 0) for a in arrays)
    fn_name = getattr(map_fn, "__name__", "map_fn")
    tid = threading.get_ident()
    # tenant fair-share over the dispatch choke point: under
    # H2O_TPU_WORKLOAD_DISPATCH_SLOTS, concurrent drivers queue here and
    # wake lowest-virtual-time-first so one tenant's dispatch storm
    # cannot starve another's; free (one int read) when the knob is 0
    with fairshare.dispatch_slot(), \
            telemetry.span("mrtask.dispatch", metric="mrtask.dispatch.seconds",
                           fn=fn_name, rows=nrow, in_bytes=in_bytes) as sp:
        _INFLIGHT[tid] = (time.monotonic(), fn_name)
        try:
            with sp.phase("build"):
                fn = _driver_program(map_fn, mesh, nrow, reduce_key,
                                     _avt(arrays), out_rows)
            # H2O_TPU_SANITIZE=transfers: an implicit device->host sync
            # inside the driver dispatch raises typed (graftlint rule
            # host-transfer-in-hot-path is the static twin); no-op when off
            with sp.phase("dispatch"), \
                    sanitizer.transfer_scope("mrtask.dispatch"):
                out = fn(*arrays)
        finally:
            _INFLIGHT.pop(tid, None)
    telemetry.inc("mrtask.dispatch.count")
    telemetry.inc("mrtask.payload.in.bytes", in_bytes)
    telemetry.inc("mrtask.payload.out.bytes",
                  sum(getattr(x, "nbytes", 0)
                      for x in jax.tree.leaves(out)))
    return out


def mr_map(
    map_fn: Callable[[Sequence[jax.Array], RowInfo], Any],
    arrays: Sequence[jax.Array],
    nrow: int,
    mesh: Mesh | None = None,
):
    """Row-to-row distributed map producing new row-sharded arrays.

    This is the `outputFrame` path: map returns one or more per-row arrays
    (same leading dim as the shard); outputs stay sharded on the rows axis.
    Programs are cached like ``mr_reduce``'s.
    """
    from ..utils import failpoints

    failpoints.hit("mrtask.dispatch")
    mesh = mesh or default_mesh()
    arrays = tuple(arrays)
    return _dispatch(map_fn, mesh, nrow, None, arrays, out_rows=True)
