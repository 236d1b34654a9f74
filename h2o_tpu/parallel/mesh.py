"""Device-mesh management — the TPU-native replacement for H2O's cluster model.

The reference builds a "cloud" of symmetric JVM nodes with gossip heartbeats and
quorum consensus (`water/H2O.java`, `water/Paxos.java:10-33`). On TPU the set of
devices is fixed at process start and coordinated by the JAX runtime, so the whole
membership machinery collapses into a `jax.sharding.Mesh`. We keep a single global
mesh with two named axes:

- ``"rows"``  — the data-parallel axis. Frames are sharded along rows on this axis
  (the analog of H2O chunk distribution, `water/Key.java:108-120`).
- ``"cols"``  — an optional model/feature-parallel axis, used for wide-feature work
  (Gram accumulation over huge one-hot domains, SURVEY.md §5.7).

The mesh is lazily constructed over all available devices as a 1-D ``rows`` mesh by
default; tests and multi-chip dry-runs install explicit meshes via ``use_mesh``.
"""

from __future__ import annotations

import contextlib
import math

import jax
import numpy as np
# the single import point of ``shard_map`` for the repo (graftlint rule
# `direct-shard-map`): every SPMD program is built through this name, so
# where programs meet the mesh stays reviewable in one place
from jax import shard_map  # noqa: F401
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROWS = "rows"
COLS = "cols"

_active_mesh: Mesh | None = None


def make_mesh(devices=None, row_parallel: int | None = None) -> Mesh:
    """Build a (rows, cols) mesh over ``devices`` (default: all local devices).

    By default all devices go on the ``rows`` axis — H2O's only parallelism axis is
    rows (chunk distribution), so that is the right default here too.
    """
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    n = devices.size
    rp = n if row_parallel is None else row_parallel
    if n % rp != 0:
        raise ValueError(f"row_parallel={rp} does not divide device count {n}")
    grid = devices.reshape(rp, n // rp)
    return Mesh(grid, (ROWS, COLS))


def default_mesh() -> Mesh:
    global _active_mesh
    if _active_mesh is None:
        from ..utils.knobs import get_int

        # H2O_TPU_ROW_SHARDS picks how many of the devices go on the data-
        # parallel ``rows`` axis (0/unset = all of them — the historic
        # default). Read once, at lazy construction: every Frame placed
        # afterwards shards against this mesh, so flipping the knob
        # mid-process would strand existing columns on the old layout
        # (code that compares shard counts in one process installs
        # explicit meshes with `use_mesh` instead — bench `sharded` leg).
        shards = get_int("H2O_TPU_ROW_SHARDS")
        _active_mesh = make_mesh(row_parallel=shards if shards > 0 else None)
    return _active_mesh


def set_mesh(mesh: Mesh | None) -> None:
    global _active_mesh
    _active_mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    global _active_mesh
    prev = _active_mesh
    _active_mesh = mesh
    try:
        yield mesh
    finally:
        _active_mesh = prev


def n_row_shards(mesh: Mesh | None = None) -> int:
    mesh = mesh or default_mesh()
    return mesh.shape[ROWS]


def row_sharding(mesh: Mesh | None = None) -> NamedSharding:
    """Sharding for a per-row array: rows split over the ``rows`` axis."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P(ROWS))


def replicated(mesh: Mesh | None = None) -> NamedSharding:
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Sanctioned placement points. Frame data (columns, coded chunks, binned
# views, training matrices) is placed onto the mesh HERE or in frame/ —
# graftlint's `direct-device-put` rule flags mesh-sharded device_put calls
# anywhere else, so placement policy (what is row-sharded, what replicates)
# stays reviewable in two files instead of scattered through the builders.
# ---------------------------------------------------------------------------
def put_row_sharded(x, mesh: Mesh | None = None) -> jax.Array:
    """Place ``x`` row-sharded over the mesh's ``rows`` axis (leading dim
    split across row shards; any trailing dims replicated)."""
    return jax.device_put(x, row_sharding(mesh))


def put_replicated(x, mesh: Mesh | None = None) -> jax.Array:
    """Place ``x`` fully replicated (one copy per device) — split metadata
    (bin edges, constraint masks) every shard's compute reads whole."""
    return jax.device_put(x, replicated(mesh))


def put_sharded(x, spec: P, mesh: Mesh | None = None) -> jax.Array:
    """Place ``x`` with an explicit PartitionSpec (the 2-D rows×cols
    layouts GLM's feature-parallel Gram uses)."""
    mesh = mesh or default_mesh()
    return jax.device_put(x, NamedSharding(mesh, spec))


def device_nbytes(arr) -> dict:
    """Per-DEVICE byte footprint of one array ({device label: bytes}) —
    the ONE implementation of the addressable_shards walk (the Cleaner's
    per-device ledger and the bench accounting both read it): a
    row-sharded array costs ~nbytes/n_shards per chip, a replicated one
    costs full nbytes on EVERY chip. Host numpy (anything without shards)
    books under the synthetic ``host`` label."""
    if arr is None:
        return {}
    try:
        shards = arr.addressable_shards
    except AttributeError:
        return {"host": int(arr.size * arr.dtype.itemsize)}
    per_dev: dict = {}
    for s in shards:
        d = s.data
        label = str(s.device)
        per_dev[label] = per_dev.get(label, 0) + \
            int(d.size * d.dtype.itemsize)
    return per_dev


def per_shard_nbytes(arr) -> int:
    """Largest single-device byte footprint — the number a per-chip HBM
    budget actually pays."""
    return max(device_nbytes(arr).values(), default=0)


def padded_len(nrow: int, mesh: Mesh | None = None, multiple: int | None = None) -> int:
    """Padded row count: divisible by the row-shard count and a lane multiple.

    This is the ESPC analog (`water/fvec/Vec.java:152-166`): instead of a vector of
    per-chunk start offsets we use equal-size shards plus a global row count; rows
    beyond ``nrow`` are padding and masked out of every computation.

    The per-shard multiple scales with nrow (8 for small frames, 65,536 for
    large) so the tree engine's row-block scan always gets evenly divisible
    shards without wasting memory on tiny frames. Large frames pad to EIGHT
    8192-row blocks, not one: the TPU compiler's time on a blocked scan is
    linear in the block count whenever that count is not a multiple of 8
    (compile-only v5e target: the HIGGS train step took 195 s at 1343
    blocks and 2.4 s at 1344 — tests/test_chip_compile.py, PERF.md). The
    padding costs at most 0.6% of an 11M-row shard.
    """
    shards = n_row_shards(mesh)
    if multiple is None:
        multiple = (65_536 if nrow >= 1_000_000
                    else (256 if nrow >= 10_000 else 8))
    q = shards * multiple
    return int(math.ceil(max(nrow, 1) / q) * q)
