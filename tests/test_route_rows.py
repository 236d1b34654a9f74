"""`engine._route_rows` against a plain NumPy walk of ``xb[r, bf[lc[r]]]``.

The pipelined level program's routing reads a row's split parameters and its
code at the split feature through selects, not gathers; node ids are integer
work, so they must equal the walk exactly: over code dtypes, feature counts,
level widths, NA rows in both NA directions, rows outside the level's window,
nodes that do not split, and set splits on and off.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.models.tree.engine import _route_rows

ROWS = 1024


def _walk(xb, node, bf, bb, bnal, do_split, catd, isset, offset, n_lv, nbins):
    out = node.copy()
    for r in range(node.shape[0]):
        lc = node[r] - offset
        if not (0 <= lc < n_lv) or not do_split[lc]:
            continue
        x = int(xb[r, bf[lc]])
        if x == nbins:
            right = not bnal[lc]
        elif catd is not None and isset[lc]:
            right = catd[lc, x] > 0.5
        else:
            right = x > bb[lc]
        out[r] = 2 * node[r] + 1 + int(right)
    return out


@pytest.mark.parametrize("use_sets", [False, True], ids=["ordinal", "sets"])
@pytest.mark.parametrize("n_lv", [1, 2, 16])
@pytest.mark.parametrize("F", [1, 28, 31, 200])
@pytest.mark.parametrize("dtype,nbins", [(np.int8, 20), (np.int16, 300),
                                         (np.int32, 300)],
                         ids=["int8", "int16", "int32"])
def test_route_rows_equals_numpy_walk(dtype, nbins, F, n_lv, use_sets):
    rng = np.random.default_rng(1000 * F + 10 * n_lv + use_sets + nbins)
    offset = n_lv - 1
    xb = rng.integers(0, nbins, (ROWS, F)).astype(dtype)
    xb[rng.random((ROWS, F)) < 0.15] = nbins                   # the NA code
    # a third of the rows sit outside the window: stopped above the level
    # (ids below offset) or already routed below it
    node = rng.integers(max(offset - 2, 0), offset + n_lv + 3,
                        ROWS).astype(np.int32)
    bf = rng.integers(0, F, n_lv).astype(np.int32)
    bb = rng.integers(0, nbins - 1, n_lv).astype(np.int32)
    bnal = np.arange(n_lv) % 2 == 0                            # both NA directions
    if n_lv == 1:
        bnal[:] = F % 2 == 0
    do_split = rng.random(n_lv) < 0.8
    do_split[0] = True
    if n_lv > 1:
        do_split[-1] = False                                   # a node that stops
    catd = isset = None
    if use_sets:
        catd = (rng.random((n_lv, nbins)) < 0.5).astype(np.float32)
        isset = rng.random(n_lv) < 0.5
        isset[0] = True
    cfg = types.SimpleNamespace(nbins=nbins)

    def route(xb_, node_, bf_, bb_, bnal_, do_split_, catd_, isset_):
        return _route_rows(xb_, node_, (bf_, bb_, bnal_, do_split_, catd_,
                                        isset_, offset, n_lv), cfg)

    got = jax.jit(route)(jnp.asarray(xb), jnp.asarray(node), jnp.asarray(bf),
                         jnp.asarray(bb), jnp.asarray(bnal),
                         jnp.asarray(do_split),
                         None if catd is None else jnp.asarray(catd),
                         None if isset is None else jnp.asarray(isset))
    want = _walk(xb, node, bf, bb, bnal, do_split, catd, isset, offset, n_lv,
                 nbins)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    # the case is not vacuous: rows moved, rows stayed, NA rows were routed
    moved = want != node
    assert moved.any() and (~moved).any()
    active = (node >= offset) & (node < offset + n_lv)
    lc = np.clip(node - offset, 0, n_lv - 1)
    assert (moved & (xb[np.arange(ROWS), bf[lc]] == nbins)).any()
    assert (~active).any()
