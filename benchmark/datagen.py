"""Data made on the device from ``--seed``: the HIGGS-shaped frame.

The arithmetic is ``bench._higgs_frame``'s (28 normals, every third mixed
0.3 with a latent, a binary label from a logistic in the latent, ``f0`` and
``f3``), drawn with ``jax.random`` in ONE jitted call, already row-sharded
and NaN-padded to the frame's padded length as ``Vec.from_numpy`` pads.
The columns belong to the benchmark, not to the program: the plain
references read these same device arrays once the window has closed.
"""

from __future__ import annotations

import functools

NCOL = 28
MIX, MIX_EVERY = 0.3, 3
FEATURES = tuple(f"f{j}" for j in range(NCOL))
RESPONSE = "response"
DOMAIN = ("b", "s")


def seed_key(seed: int):
    """A threefry key from any whole number up to 2**64 (the driver's seeds
    pass 2**31): both 32-bit halves are key data, none is dropped."""
    import jax
    import jax.numpy as jnp

    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.array([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32),
        impl="threefry2x32")


@functools.lru_cache(maxsize=4)
def _generator(nrow: int, plen: int, sharding):
    import jax
    import jax.numpy as jnp

    def gen(key):
        live = jnp.arange(plen) < nrow
        latent = jax.random.normal(jax.random.fold_in(key, 1000), (plen,),
                                   jnp.float32)
        cols = []
        for j in range(NCOL):
            x = jax.random.normal(jax.random.fold_in(key, j), (plen,),
                                  jnp.float32)
            if j % MIX_EVERY == 0:
                x = x + MIX * latent
            cols.append(x)
        logits = latent + 0.5 * cols[0] - 0.25 * cols[3]
        u = jax.random.uniform(jax.random.fold_in(key, 2000), (plen,),
                               jnp.float32)
        y = (u < jax.nn.sigmoid(logits)).astype(jnp.float32)
        nan = jnp.float32(jnp.nan)
        return tuple(jnp.where(live, c, nan) for c in cols + [y])

    return jax.jit(gen, out_shardings=(sharding,) * (NCOL + 1))


def higgs_columns(seed: int, nrow: int, plen: int, sharding, stream: int = 0):
    """(f0..f27, response) as device arrays of length ``plen``. ``stream``
    0 is the frame; another stream of the same seed is other rows by the
    same arithmetic (the rows that requests carry)."""
    key = seed_key(seed)
    if stream:
        import jax

        key = jax.random.fold_in(key, 7_000_000 + int(stream))
    return _generator(int(nrow), int(plen), sharding)(key)


def request_rows(seed: int, nrow: int):
    """(nrow, 28) float32 on the host: the rows that a cell's requests
    carry, by the frame's generator from another stream of the seed."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    plen = -(-int(nrow) // 1024) * 1024          # few distinct programs
    cols = higgs_columns(seed, plen, plen,
                         SingleDeviceSharding(jax.devices()[0]), stream=1)
    return np.stack([np.asarray(c) for c in cols[:NCOL]], axis=1)[:nrow]


def higgs_frame(seed: int, nrow: int):
    """The columns wrapped as the program's Frame, in its store."""
    from h2o_tpu.backend.kvstore import STORE
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.frame.vec import T_CAT, Vec
    from h2o_tpu.parallel import mesh as meshmod

    plen = meshmod.padded_len(nrow)
    cols = higgs_columns(seed, nrow, plen, meshmod.row_sharding())
    vecs = [Vec.from_device(c, nrow) for c in cols[:NCOL]]
    vecs.append(Vec.from_device(cols[NCOL], nrow, type=T_CAT,
                                domain=list(DOMAIN)))
    fr = Frame(list(FEATURES) + [RESPONSE], vecs)
    STORE.put_keyed(fr)
    return fr, cols


GENERATORS = {"higgs": higgs_frame}
