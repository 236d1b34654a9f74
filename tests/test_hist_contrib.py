"""The level histogram's block contribution against a float64 ``np.add.at``.

`hist._flat_contrib` / `_one_group_contrib` contract a block's one-hot with
the node-routed statistics folded into ONE dimension (k = n_lv * V), the scans
carry that (k, F, B) shape, and `hist._unfold` lays the small result out as
(F, n_lv, B, V) once a level. No cell of the benchmark has V = 4, a
categorical column (width buckets) or K > 1 trees an iteration: these cases
hold them. Statistics are multiples of 1/8 (exact in bfloat16 and in float32
sums), so every comparison is for equality: a cell in the wrong place cannot
hide inside a tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.backend.kernels import hist

pytestmark = pytest.mark.kernels


def _inputs(R, F, B, n_lv, V, dtype, seed=0, lo=0, hi=None):
    """Codes in [0, B-1) with a tenth NA (= B-1); node ids LOCAL to the level
    and, from ``lo`` / ``hi``, outside its window too; eighths as values."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B - 1, (R, F))
    codes = np.where(rng.random((R, F)) < 0.1, B - 1, codes)
    local = rng.integers(lo, n_lv if hi is None else hi, (R,))
    vals = rng.integers(-32, 33, (R, V)).astype(np.float32) / 8.0
    return codes.astype(np.dtype(dtype)), local.astype(np.int32), vals


def _ref(codes, local, vals, n_lv, B):
    """(F, n_lv, B, V) in float64: every row inside the window adds its
    values at (f, node, code); rows outside add nothing."""
    F, V = codes.shape[1], vals.shape[1]
    h = np.zeros((F, n_lv, B, V))
    rows = np.nonzero((local >= 0) & (local < n_lv))[0]
    for f in range(F):
        np.add.at(h[f], (local[rows], codes[rows, f].astype(np.int64)),
                  vals[rows].astype(np.float64))
    return h


def _ref_groups(codes, local, vals, n_lv, B, groups):
    out = []
    for idxs, Bg, _mode in groups:
        cg = codes[:, list(idxs)].astype(np.int64)
        out.append(_ref(np.where(cg == B - 1, Bg - 1, cg), local, vals,
                        n_lv, Bg))
    return out


def _equal(got, want):
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


SHAPES = [(1, 21, jnp.int8), (28, 21, jnp.int16), (31, 65, jnp.int32)]


@pytest.mark.parametrize("F,B,dtype", SHAPES)
@pytest.mark.parametrize("V", [3, 4])
@pytest.mark.parametrize("n_lv", [1, 2, 16, 64])
def test_block_contribution_is_the_per_cell_sum(n_lv, V, F, B, dtype):
    codes, local, vals = _inputs(512, F, B, n_lv, V, dtype, seed=n_lv + V)
    c = hist._flat_contrib(jnp.asarray(codes), jnp.asarray(local),
                           jnp.asarray(vals), n_lv, B)
    assert c.shape == (n_lv * V, F, B)     # the contraction's own shape
    _equal(hist._unfold(c, n_lv), _ref(codes, local, vals, n_lv, B))


@pytest.mark.parametrize("n_lv,V,F,B,dtype", [
    (1, 3, 28, 21, jnp.int8), (16, 4, 31, 65, jnp.int16),
    (64, 3, 1, 21, jnp.int32), (2, 4, 28, 65, jnp.int8)])
def test_level_hist_blocks_flat(n_lv, V, F, B, dtype):
    codes, local, vals = _inputs(2048, F, B, n_lv, V, dtype, seed=3)
    h = hist.level_hist_blocks(
        jnp.asarray(codes), jnp.asarray(local), jnp.asarray(vals),
        n_lv=n_lv, nbins_tot=B, block=512)
    _equal(h, _ref(codes, local, vals, n_lv, B))


# a frame with categorical columns: a narrow segment-summed bucket and two
# one-hot buckets of different widths in ONE scan
GROUPS = (((0, 5, 9), 8, "segsum"),
          (tuple(i for i in range(31) if i not in (0, 3, 5, 9)), 21, "onehot"),
          ((3,), 65, "onehot"))


def _grouped_inputs(R, n_lv, V, dtype, seed, lo=0, hi=None):
    codes, local, vals = _inputs(R, 31, 65, n_lv, V, dtype, seed, lo, hi)
    for idxs, Bg, _mode in GROUPS:      # a group's codes fit its width
        sub = codes[:, list(idxs)]
        codes[:, list(idxs)] = np.where(sub == 64, 64, sub % (Bg - 1))
    return codes, local, vals


@pytest.mark.parametrize("n_lv,V,dtype", [(1, 3, jnp.int8), (16, 4, jnp.int16),
                                          (64, 3, jnp.int32)])
def test_level_hist_blocks_grouped_onehot_and_segsum(n_lv, V, dtype):
    codes, local, vals = _grouped_inputs(2048, n_lv, V, dtype, seed=5)
    hs = hist.level_hist_blocks(
        jnp.asarray(codes), jnp.asarray(local), jnp.asarray(vals),
        n_lv=n_lv, nbins_tot=65, block=512, groups=GROUPS)
    want = _ref_groups(codes, local, vals, n_lv, 65, GROUPS)
    assert len(hs) == len(want)
    for g, w in zip(hs, want):
        _equal(g, w)


def test_one_group_scan_matches_the_joint_scan():
    codes, local, vals = _grouped_inputs(2048, 16, 3, jnp.int16, seed=6)
    want = _ref_groups(codes, local, vals, 16, 65, GROUPS)
    for (idxs, Bg, mode), w in zip(GROUPS, want):
        g = hist.level_hist_one_group(
            jnp.asarray(codes[:, list(idxs)]), jnp.asarray(local),
            jnp.asarray(vals), Bg=Bg, mode=mode, n_lv=16, nbins_tot=65,
            block=512)
        _equal(g, w)


@pytest.mark.parametrize("n_lv,V,F,B,dtype", [
    (2, 3, 28, 21, jnp.int8), (16, 4, 31, 65, jnp.int16),
    (64, 4, 1, 21, jnp.int32), (1, 3, 31, 65, jnp.int8)])
def test_streamed_pass_zeroes_rows_outside_the_window(n_lv, V, F, B, dtype):
    """Node ids are global: rows below the level's offset (finished leaves)
    and above its last node add nothing, and their ids come back as they
    went in."""
    offset = n_lv - 1
    codes, local, vals = _inputs(2048, F, B, n_lv, V, dtype, seed=7,
                                 lo=-2, hi=n_lv + 3)
    assert (local < 0).any() and (local >= n_lv).any()
    node = local + offset
    (h,), node_out = hist.streamed_route_hist(
        jnp.asarray(codes), jnp.asarray(node), jnp.asarray(vals), None,
        offset=offset, n_lv=n_lv, nbins_tot=B, block=512)
    _equal(h, _ref(codes, local, vals, n_lv, B))
    np.testing.assert_array_equal(np.asarray(node_out), node)


@pytest.mark.parametrize("n_lv,V", [(2, 4), (16, 3)])
def test_streamed_pass_grouped(n_lv, V):
    offset = n_lv - 1
    codes, local, vals = _grouped_inputs(2048, n_lv, V, jnp.int16, seed=8,
                                         lo=-1, hi=n_lv + 2)
    hs, _ = hist.streamed_route_hist(
        jnp.asarray(codes), jnp.asarray(local + offset), jnp.asarray(vals),
        None, offset=offset, n_lv=n_lv, nbins_tot=65, block=512,
        groups=GROUPS)
    for g, w in zip(hs, _ref_groups(codes, local, vals, n_lv, 65, GROUPS)):
        _equal(g, w)


def test_three_trees_under_vmap():
    """K trees an iteration (multinomial): the codes are shared, node ids and
    statistics carry a leading tree axis, and the contraction gains a batch
    dimension."""
    n_lv, V, F, B, K = 4, 3, 28, 21, 3
    offset = n_lv - 1
    codes, _, _ = _inputs(1024, F, B, n_lv, V, jnp.int8, seed=9)
    per_tree = [_inputs(1024, F, B, n_lv, V, jnp.int8, seed=10 + k,
                        lo=-1, hi=n_lv + 1)[1:] for k in range(K)]
    local = np.stack([lv[0] for lv in per_tree])
    vals = np.stack([lv[1] for lv in per_tree])

    def one(node, v):
        (h,), nd = hist.streamed_route_hist(
            jnp.asarray(codes), node, v, None, offset=offset, n_lv=n_lv,
            nbins_tot=B, block=256)
        return h, nd

    h, nd = jax.vmap(one)(jnp.asarray(local + offset), jnp.asarray(vals))
    assert h.shape == (K, F, n_lv, B, V)
    for k in range(K):
        _equal(h[k], _ref(codes, local[k], vals[k], n_lv, B))
    np.testing.assert_array_equal(np.asarray(nd), local + offset)
