"""HBM Cleaner — the `water/Cleaner.java` / MemoryManager analog.

Budget pinned via H2O_TPU_HBM_LIMIT_BYTES so the LRU spill/rehydrate cycle is
deterministic on the virtual CPU mesh.
"""

import numpy as np
import pytest

from h2o_tpu.backend.memory import CLEANER
from h2o_tpu.frame.vec import Vec


@pytest.fixture()
def tight_budget(monkeypatch):
    # each Vec below is 1024 rows * 4 B = 4 KiB padded; budget fits ~3
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(3 * 4096))
    yield
    CLEANER.maybe_sweep()


def test_lru_spill_and_transparent_rehydrate(tight_budget):
    rng = np.random.default_rng(0)
    vals = [rng.normal(size=1000).astype(np.float32) for _ in range(5)]
    vecs = [Vec.from_numpy(v) for v in vals]
    CLEANER.maybe_sweep()
    spilled = [v for v in vecs if v._data is None and v._spill_path]
    assert spilled, "over-budget allocation must spill something"
    # the coldest (earliest-created) vecs go first
    assert vecs[0] in spilled
    assert vecs[-1] not in spilled  # the hottest stays resident
    # transparent rehydrate: .data access reloads and values survive
    v0 = vecs[0]
    np.testing.assert_allclose(np.asarray(v0.data)[:1000], vals[0],
                               rtol=1e-6)
    assert v0._data is not None and v0._spill_path is None
    # rollups still correct after a spill/reload cycle
    np.testing.assert_allclose(v0.rollups().mean, vals[0].mean(), rtol=1e-4)


def test_no_budget_means_no_spill(monkeypatch):
    monkeypatch.delenv("H2O_TPU_HBM_LIMIT_BYTES", raising=False)
    v = Vec.from_numpy(np.ones(1000, np.float32))
    CLEANER.maybe_sweep()
    assert v._data is not None


def test_touch_order_is_lru_not_creation_order(tight_budget):
    vecs = [Vec.from_numpy(np.full(1000, float(i), np.float32))
            for i in range(3)]
    _ = vecs[0].data  # re-touch the oldest: now vec[1] is coldest
    Vec.from_numpy(np.zeros(1000, np.float32))
    Vec.from_numpy(np.zeros(1000, np.float32))
    CLEANER.maybe_sweep()
    assert vecs[1]._data is None, "LRU must evict the coldest, not the oldest"
    assert vecs[0]._data is not None


class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.fixture()
def _fake_hw(monkeypatch):
    """Swap the local devices for fakes and clear the cached hardware
    lookup, so each test resolves the HBM budget fresh from the
    ``memory_stats()`` it chooses."""
    import jax

    from h2o_tpu.backend import memory

    monkeypatch.delenv("H2O_TPU_HBM_LIMIT_BYTES", raising=False)
    monkeypatch.setattr(memory, "_HW_BYTES", memory._UNRESOLVED)
    # fresh Cleaner: hbm_budget_bytes subtracts tracked resident bytes, and
    # vecs from other tests must not bleed into the budget assertions
    monkeypatch.setattr(memory, "CLEANER", memory.Cleaner())

    def install(*stats, backend="tpu"):
        monkeypatch.setattr(jax, "local_devices",
                            lambda: [_FakeDev(s) for s in stats])
        monkeypatch.setattr(jax, "default_backend", lambda: backend)

    yield memory, monkeypatch, install


def test_hbm_bytes_are_what_memory_stats_reports(_fake_hw):
    memory, _mp, install = _fake_hw
    gib16 = 16 << 30
    install({"bytes_limit": gib16, "bytes_in_use": 1 << 30},
            {"bytes_limit": gib16, "bytes_in_use": 5 << 30})
    # the FULLEST local device is the one a per-chip budget has to fit
    assert memory.hbm_stats()["bytes_in_use"] == 5 << 30
    assert memory.device_hbm_bytes() == gib16
    assert memory.hbm_budget_bytes() == int(gib16 * 0.85)
    assert memory.Cleaner().limit_bytes() == int(gib16 * 0.85)


@pytest.mark.parametrize("stats", [None, {"bytes_in_use": 0}])
def test_tpu_without_bytes_limit_is_an_error(_fake_hw, stats):
    """The hardware is asked, not assumed: no device_kind table, no 16 GiB
    last resort."""
    memory, _mp, install = _fake_hw
    install(stats)
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.hbm_budget_bytes()
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.Cleaner().limit_bytes()


def test_hbm_budget_env_pin_and_cpu_none(_fake_hw):
    memory, monkeypatch, install = _fake_hw
    install(None, backend="cpu")
    assert memory.hbm_budget_bytes() is None  # planners fall back
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", "123456")
    assert memory.hbm_budget_bytes() == 123456
    # the documented optargs contract: 0 means "backend resolution", never
    # a 0-byte budget that would spill every vec on sight
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", "0")
    assert memory.hbm_budget_bytes() is None
    assert memory.Cleaner().limit_bytes() is None


class _Resident:
    """Weakref-able stand-in for a device-resident Vec: its ``_data`` has
    the one thing the Cleaner's per-device ledger walks, the shards."""

    def __init__(self, **per_device):
        import types

        self._data = types.SimpleNamespace(addressable_shards=[
            types.SimpleNamespace(device=d, data=np.empty(b >> 20, "V1048576"))
            for d, b in per_device.items()])
        self.nbytes = sum(per_device.values())


def test_hbm_budget_is_live_minus_resident(_fake_hw):
    """Planners must see physical headroom MINUS what already sits in HBM —
    a 14 GB resident frame on a v5e leaves ~nothing for intermediates."""
    memory, _mp, install = _fake_hw
    install({"bytes_limit": 16 << 30, "bytes_in_use": 0})

    full = int((16 << 30) * 0.85)
    assert memory.hbm_budget_bytes() == full
    v = _Resident(chip0=4 << 30)
    memory.CLEANER.track(v, v.nbytes)
    assert memory.hbm_budget_bytes() == full - (4 << 30)
    # 13 GiB resident (still under the Cleaner's own 13.6 GiB sweep
    # threshold) leaves 0.6 GiB of headroom: the planner floor, 1/16 HBM
    u = _Resident(chip0=9 << 30)
    memory.CLEANER.track(u, u.nbytes)
    assert memory.hbm_budget_bytes() == (16 << 30) >> 4


def test_hbm_budget_debits_the_fullest_device_not_the_mesh_total(_fake_hw):
    """The limit is ONE device's: a frame row-sharded over four chips takes
    a quarter of its bytes from each chip's budget, a replicated table all
    of them from every chip, and host-resident payloads nothing."""
    memory, _mp, install = _fake_hw
    gib = 1 << 30
    install(*[{"bytes_limit": 16 * gib, "bytes_in_use": 0}] * 4)
    full = int(16 * gib * 0.85)
    frame = _Resident(chip0=gib, chip1=gib, chip2=gib, chip3=gib)
    memory.CLEANER.track(frame, frame.nbytes)
    assert memory.CLEANER.tracked_bytes() == 4 * gib
    assert memory.hbm_budget_bytes() == full - gib
    table = _Resident(chip0=gib >> 1, chip1=gib >> 1, chip2=gib >> 1,
                      chip3=gib >> 1)
    skewed = _Resident(chip2=2 * gib)
    host = _Resident(host=2 * gib)   # total 10 GiB: under the sweep's limit
    for r in (table, skewed, host):
        memory.CLEANER.track(r, r.nbytes)
    assert memory.CLEANER.fullest_device_bytes() == 3 * gib + (gib >> 1)
    assert memory.hbm_budget_bytes() == full - 3 * gib - (gib >> 1)
    del skewed
    import gc

    gc.collect()
    assert memory.hbm_budget_bytes() == full - gib - (gib >> 1)


def test_hbm_budget_of_a_row_sharded_vec_is_one_shards_bytes(_fake_hw):
    """A real column on the four-shard CPU mesh: the budget falls by the
    bytes ONE device holds; on a one-shard mesh by all of them."""
    import jax

    from h2o_tpu.parallel import mesh as meshmod

    memory, _mp, install = _fake_hw
    devs = jax.devices()
    install(*[{"bytes_limit": 16 << 30, "bytes_in_use": 0}] * 4)
    full = memory.hbm_budget_bytes()
    col = np.arange(1 << 16, dtype=np.float32)
    with meshmod.use_mesh(meshmod.make_mesh(devices=devs[:4])):
        v = Vec.from_numpy(col)
        assert len(v.data.sharding.device_set) == 4
        assert full - memory.hbm_budget_bytes() == col.nbytes // 4
    with meshmod.use_mesh(meshmod.make_mesh(devices=devs[:1])):
        w = Vec.from_numpy(col)
        assert full - memory.hbm_budget_bytes() == col.nbytes // 4 + col.nbytes
    del v, w
