"""One level pass of the tree engine against a reference that shares no code
with it.

The engine's level pass (`hist.streamed_route_hist` with `engine._route_rows`
as its ``route_fn``) does two things to every row: it moves the row from its
node of the PREVIOUS level to a child, by that level's splits, and it adds
the row's ``[w, g, h]`` into the cell (feature, node, bin) of the level's
histogram. The reference below does the same in NumPy and float64, a row at
a time, and imports nothing from ``h2o_tpu/models/tree/``: it is the one
reference for a whole level of the engine's one level program.

Shapes are the HIGGS cells': 28 features, 20 bins + the NA bucket, three
statistics; a few row blocks. The grouped cases run the whole level
(`engine._pipelined_level_hist`: route, width-bucketed accumulation,
psum, scatter back) under ``shard_map`` on the test mesh, with a plan of
three width buckets and the overlapped reduction off and on.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

F, NBINS, V = 28, 20, 3
B = NBINS + 1                  # the NA code is NBINS, the last bin
BLOCK, ROWS = 512, 4 * 512
CAT_COL = 5                    # the categorical column of the set-split cases


# ---------------------------------------------------------------------------
# the reference: NumPy, float64, one row at a time
# ---------------------------------------------------------------------------
def ref_route(codes, node, split, offset, n_lv):
    """Each row's node after the splits of the level ``[offset, offset +
    n_lv)``: a row outside the level, or in a node that does not split,
    stays; an NA code goes by the node's NA direction; a code of a
    categorical split goes by the node's direction table; any other goes
    right when it is above the node's split bin."""
    out = node.copy()
    for r in range(node.shape[0]):
        n = int(node[r]) - offset
        if not 0 <= n < n_lv or not split["do_split"][n]:
            continue
        code = int(codes[r, split["feature"][n]])
        if code == NBINS:
            right = not split["na_left"][n]
        elif split["is_set"] is not None and split["is_set"][n]:
            right = split["direction"][n, code] > 0.5
        else:
            right = code > split["bin"][n]
        out[r] = 2 * int(node[r]) + 1 + int(right)
    return out


def ref_level_hist(codes, node, stats, offset, n_lv):
    """(F, n_lv, B, V) in float64: a row in the level's window adds its
    statistics at (feature, its node, its code) for every feature."""
    h = np.zeros((codes.shape[1], n_lv, B, stats.shape[1]))
    for r in range(node.shape[0]):
        n = int(node[r]) - offset
        if 0 <= n < n_lv:
            for f in range(codes.shape[1]):
                h[f, n, int(codes[r, f])] += stats[r].astype(np.float64)
    return h


def ref_level_pass(codes, node, stats, split, n_lv):
    """The level of ``n_lv`` nodes: route off its parent level, then sum."""
    parent_n, parent_off = n_lv // 2, n_lv // 2 - 1
    node = ref_route(codes, node, split, parent_off, parent_n)
    return ref_level_hist(codes, node, stats, n_lv - 1, n_lv), node


# ---------------------------------------------------------------------------
# the data of a case
# ---------------------------------------------------------------------------
def _case(n_lv, dtype, sets, seed):
    """Rows at the parent level (and a few that stopped above it), that
    level's splits, and the rows' statistics."""
    rng = np.random.default_rng(seed)
    parent_n, parent_off = n_lv // 2, n_lv // 2 - 1
    codes = rng.integers(0, NBINS, (ROWS, F))
    codes[rng.random((ROWS, F)) < 0.1] = NBINS                 # NA
    node = rng.integers(parent_off, parent_off + parent_n, ROWS)
    if parent_off:
        stopped = rng.random(ROWS) < 0.1       # leaves of shallower levels
        node[stopped] = rng.integers(0, parent_off, int(stopped.sum()))
    split = dict(
        feature=rng.integers(0, F, parent_n).astype(np.int32),
        bin=rng.integers(0, NBINS - 1, parent_n).astype(np.int32),
        na_left=np.arange(parent_n) % 2 == 0,
        do_split=np.ones(parent_n, bool), direction=None, is_set=None)
    if parent_n > 1:
        split["do_split"][-1] = False          # a node that became a leaf
        split["na_left"][0] = False            # both NA directions are used
    if sets:
        split["feature"][0] = CAT_COL
        split["is_set"] = split["feature"] == CAT_COL
        split["direction"] = (rng.random((parent_n, NBINS)) < 0.5
                              ).astype(np.float32)
    stats = rng.normal(size=(ROWS, V)).astype(np.float32)
    stats[:, 0] = rng.random(ROWS).astype(np.float32) * 2.0    # weights
    return (codes.astype(dtype), node.astype(np.int32), stats, split,
            parent_off, parent_n)


def _route_args(split, parent_off, parent_n):
    """The previous level's splits as `engine._route_rows` takes them."""
    dev = lambda a: None if a is None else jnp.asarray(a)   # noqa: E731
    return (dev(split["feature"]), dev(split["bin"]), dev(split["na_left"]),
            dev(split["do_split"]), dev(split["direction"]),
            dev(split["is_set"]), parent_off, parent_n)


CASES = [pytest.param(n_lv, dtype, sets,
                      id=f"nodes{n_lv}-{np.dtype(dtype).name}-"
                         f"{'sets' if sets else 'numeric'}")
         for n_lv in (2, 4, 8, 16) for dtype in (np.int8, np.int16)
         for sets in (False, True)]


def _assert_not_vacuous(codes, node, split, want_h, want_nd, parent_off,
                        parent_n, n_lv, sets):
    """Rows moved to both sides, rows stayed (below the root there are
    leaves), NA rows were routed, every node of the level got rows, and
    with sets a row went by the direction table."""
    moved = want_nd != node
    assert moved.any() and ((~moved).any() or n_lv == 2)
    assert (want_nd[moved] % 2 == 0).any() and (want_nd[moved] % 2 == 1).any()
    parent = np.clip(node - parent_off, 0, parent_n - 1)
    at_split = codes[np.arange(ROWS), split["feature"][parent]]
    assert (moved & (at_split == NBINS)).any()
    live = split["do_split"].repeat(2)
    assert (want_h[0, :, :, 0].sum(axis=1) > 0)[live].all()
    if sets:
        assert (moved & split["is_set"][parent] & (at_split < NBINS)).any()


@pytest.mark.parametrize("n_lv,dtype,sets", CASES)
def test_streamed_level_pass_equals_the_reference(n_lv, dtype, sets):
    from h2o_tpu.backend.kernels import hist
    from h2o_tpu.models.tree.engine import _route_rows

    codes, node, stats, split, parent_off, parent_n = _case(
        n_lv, dtype, sets, seed=100 * n_lv + 10 * sets + np.dtype(dtype).itemsize)
    cfg = types.SimpleNamespace(nbins=NBINS)
    args = _route_args(split, parent_off, parent_n)

    @jax.jit
    def level_pass(codes, node, stats):
        (h,), nd = hist.streamed_route_hist(
            codes, node, stats, lambda xb, n: _route_rows(xb, n, args, cfg),
            offset=n_lv - 1, n_lv=n_lv, nbins_tot=B, block=BLOCK)
        return h, nd

    h, nd = level_pass(jnp.asarray(codes), jnp.asarray(node),
                       jnp.asarray(stats))
    want_h, want_nd = ref_level_pass(codes, node, stats, split, n_lv)

    np.testing.assert_array_equal(np.asarray(nd), want_nd)
    assert h.shape == (F, n_lv, B, V)
    np.testing.assert_allclose(np.asarray(h, np.float64), want_h,
                               rtol=1e-5, atol=1e-4)
    _assert_not_vacuous(codes, node, split, want_h, want_nd, parent_off,
                        parent_n, n_lv, sets)


#: a width-bucketed plan, ``(features, width, mode)`` as `plan_hist_groups`
#: makes one: narrow columns summed by segment (data codes 0..6), the
#: numerics at 16 (codes 0..14), and a wide one-hot bucket at the full B
#: that holds the set splits' categorical column (codes 0..19). A bucket's
#: NA slot is its last bin; the global NA code stays NBINS.
GROUPS = ((tuple(range(5)), 8, "segsum"),
          (tuple(range(8, F)), 16, "onehot"),
          ((CAT_COL, 6, 7), B, "onehot"))
GROUP_BLOCK = 128              # two row blocks a shard on the 8-device mesh


def _grouped_case(n_lv, sets, seed):
    """`_case` with every column's data codes inside its bucket's width."""
    codes, node, stats, split, parent_off, parent_n = _case(
        n_lv, np.int8, sets, seed)
    for idxs, width, _mode in GROUPS:
        cols = codes[:, list(idxs)]
        codes[:, list(idxs)] = np.where(cols == NBINS, NBINS,
                                        cols % (width - 1))
    return codes, node, stats, split, parent_off, parent_n


GROUPED = [pytest.param(n_lv, sets, async_psum,
                        id=f"nodes{n_lv}-{'sets' if sets else 'numeric'}-"
                           f"async_psum_{'on' if async_psum else 'off'}")
           for n_lv in (2, 8) for sets in (False, True)
           for async_psum in (False, True)]


@pytest.mark.parametrize("n_lv,sets,async_psum", GROUPED)
def test_grouped_level_pass_equals_the_reference(n_lv, sets, async_psum):
    """The whole level under ``shard_map`` over the rows axis: each shard
    routes and accumulates its rows by width bucket, the buckets' psums
    (issued one by one with ``async_psum``) sum the shards, and the
    buckets scatter back into the (F, n_lv, B, V) layout."""
    from jax.sharding import PartitionSpec as P

    from h2o_tpu.models.tree.engine import _pipelined_level_hist
    from h2o_tpu.parallel.mesh import ROWS as ROW_AXIS
    from h2o_tpu.parallel.mesh import default_mesh, shard_map

    codes, node, stats, split, parent_off, parent_n = _grouped_case(
        n_lv, sets, seed=1000 + 10 * n_lv + sets)
    cfg = types.SimpleNamespace(nbins=NBINS, block_rows=GROUP_BLOCK,
                                hist_groups=GROUPS, async_psum=async_psum)
    args = _route_args(split, parent_off, parent_n)

    def spmd(c, n, v):
        return _pipelined_level_hist(c, n, v, args, n_lv - 1, n_lv, B, cfg)

    fn = shard_map(spmd, mesh=default_mesh(),
                   in_specs=(P(ROW_AXIS, None), P(ROW_AXIS),
                             P(ROW_AXIS, None)),
                   out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    h, nd = jax.jit(fn)(jnp.asarray(codes), jnp.asarray(node),
                        jnp.asarray(stats))
    want_h, want_nd = ref_level_pass(codes, node, stats, split, n_lv)

    np.testing.assert_array_equal(np.asarray(nd), want_nd)
    assert h.shape == (F, n_lv, B, V)
    np.testing.assert_allclose(np.asarray(h, np.float64), want_h,
                               rtol=1e-5, atol=1e-4)
    _assert_not_vacuous(codes, node, split, want_h, want_nd, parent_off,
                        parent_n, n_lv, sets)
    # every bucket holds rows in its data bins and in its NA slot
    for idxs, width, _mode in GROUPS:
        mass = want_h[list(idxs), :, :, 0].sum(axis=(0, 1))
        assert (mass[:width - 1] > 0).all() and mass[NBINS] > 0
        assert not mass[width - 1:NBINS].any()


FINAL_CASES = [pytest.param(n_lv, sets, dtype,
                            id=f"{n_lv}-{sets}" + (
                                "" if dtype is np.int8 else "-int16"))
               for dtype in (np.int8, np.int16)
               for n_lv in (2, 16) for sets in (False, True)]


@pytest.mark.parametrize("n_lv,sets,dtype", FINAL_CASES)
def test_final_route_equals_the_reference(n_lv, sets, dtype):
    """`engine._route_all`, the standalone route after a tree's last level
    (leaf totals read its node ids), against the same per-row walk."""
    from h2o_tpu.models.tree.engine import _route_all

    codes, node, _stats, split, parent_off, parent_n = _case(
        n_lv, dtype, sets,
        seed=7 * n_lv + sets + 50 * (np.dtype(dtype).itemsize - 1))
    cfg = types.SimpleNamespace(nbins=NBINS, block_rows=BLOCK)
    nd = jax.jit(lambda c, n: _route_all(
        c, n, _route_args(split, parent_off, parent_n), cfg))(
            jnp.asarray(codes), jnp.asarray(node))
    want = ref_route(codes, node, split, parent_off, parent_n)
    np.testing.assert_array_equal(np.asarray(nd), want)
    assert (want != node).any() and ((want == node).any() or n_lv == 2)
