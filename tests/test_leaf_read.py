"""A tree's leaf values as rows read them, against NumPy's ``v[node]``.

`engine._leaf_read` is how the train step turns a tree's ``n_nodes`` leaf
values into the margin's per-row change. It has to return the f32 element
ITSELF, as a gather does: a read that is good to 2^-18 (a table split into
two bf16 pieces and contracted with a one-hot on the MXU) is a different
margin on the chip, not a faster one. The oracle is NumPy indexing and
imports nothing of ``h2o_tpu/models/tree/``; equality is on the bits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROWS = 4096
F32_MAX = np.finfo(np.float32).max    # max_abs_leafnode_pred where it is set


def _table(rng, n):
    """Entries that would betray an inexact read: 24 significant bits, both
    signs, tiny beside huge, the clip's two ends, both zeros."""
    v = (rng.integers(1 << 23, 1 << 24, n).astype(np.float32)      # 24 bits
         * np.float32(2.0) ** rng.integers(-40, 20, n).astype(np.float32)
         * rng.choice(np.float32([-1, 1]), n))
    special = np.float32([F32_MAX, -F32_MAX, 0.0, -0.0, 1.1754944e-38, -3e38,
                          1 + 2.0 ** -23, -(1 + 2.0 ** -12 + 2.0 ** -23)])
    at = rng.permutation(n)[:len(special)]
    v[at] = special[:len(at)]
    return v


def _nodes(rng, n):
    node = rng.integers(0, n, ROWS).astype(np.int32)
    node[0], node[-1] = 0, n - 1            # the table's first and last entry
    return node


@pytest.mark.parametrize("batched", [False, True], ids=["plain", "vmap_k3"])
@pytest.mark.parametrize("n_nodes", [1, 3, 63, 64, 127, 511, 8191])
def test_leaf_read_returns_the_element_itself(n_nodes, batched):
    from h2o_tpu.models.tree.engine import _leaf_read

    rng = np.random.default_rng(n_nodes)
    if batched:                   # the K-class path: jax.vmap(leaf_delta)
        v = np.stack([_table(rng, n_nodes) for _ in range(3)])
        node = np.stack([_nodes(rng, n_nodes) for _ in range(3)])
        got = jax.jit(jax.vmap(_leaf_read))(jnp.asarray(v), jnp.asarray(node))
        want = np.take_along_axis(v, node, axis=1)
    else:
        v, node = _table(rng, n_nodes), _nodes(rng, n_nodes)
        got = jax.jit(_leaf_read)(jnp.asarray(v), jnp.asarray(node))
        want = v[node]
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == node.shape
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    # every entry was asked for, or the table's ends at least
    assert {0, n_nodes - 1} <= set(node.reshape(-1).tolist())


def test_a_non_finite_leaf_reaches_its_own_rows_only():
    """A select moves bits: an infinite or NaN entry comes back for the rows
    of its node and for no other (a one-hot contraction would multiply it by
    the other rows' zeros)."""
    from h2o_tpu.models.tree.engine import _leaf_read

    v = np.float32([1.5, np.inf, np.nan, -np.inf, 2.5, 0.0, -1.0])
    node = np.arange(ROWS, dtype=np.int32) % len(v)
    got = np.asarray(jax.jit(_leaf_read)(jnp.asarray(v), jnp.asarray(node)))
    assert np.array_equal(got.view(np.uint32), v[node].view(np.uint32))


@pytest.mark.parametrize("n_nodes,plan", [
    (1, (1, 1)), (3, (4, 1)), (63, (64, 1)), (127, (128, 1)),
    (255, (128, 2)), (8191, (128, 64))])
def test_leaf_tree_plan_comes_from_the_tables_length(n_nodes, plan):
    """One fused pass up to 128 entries (the cells' depth 5 and 6), 128
    entries a loop step beyond (depth 12, DRF's cap: 64 steps)."""
    from h2o_tpu.models.tree.engine import _leaf_tree_plan

    assert _leaf_tree_plan(n_nodes) == plan
