"""The four-chip deployment (ISSUE 28) at a size the CPU mesh holds.

The configuration ``higgs_gbm_44m``'s own ``params`` train on a 60,000-row
frame from the benchmark's generator, once with the rows split over four
devices and once on one. The two forests are the same trees (the reduction
order may move a value by float32 rounding); each passes the plain reference
and the reference that works a shard at a time, and the two references
agree; the job's root span says how its rows lay on the mesh and the
``train.gbm.psum_bytes`` counter reads what the shapes give (nothing on one
shard); ``hbm_budget_bytes()`` under a row-sharded frame is in
tests/test_memory_cleaner.py, the four-chip compile in
tests/test_chip_compile.py. Counts and values, never a time.
"""

import jax
import numpy as np
import pytest

from benchmark import datagen, manifest
from benchmark.reference import gbm as ref_plain
from benchmark.reference import gbm_shards as ref_shards
from h2o_tpu.backend.kvstore import STORE
from h2o_tpu.parallel import mesh as meshmod
from h2o_tpu.utils import telemetry, timeline

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)
ROWS, SEED = 60_000, 2**31 + 2828
#: ``regret_gap`` reads the quantile sketch's noise, which falls with the
#: square root of the rows: its limit is for the cell's 44M
#: (benchmark/tests/test_correct.py leaves it out on the CPU likewise)
SIZE_DEPENDENT = ("regret_gap",)


@pytest.fixture(scope="module")
def config():
    return manifest.config_of(MAN, "higgs_gbm_44m", ROOT)


@pytest.fixture(scope="module")
def jobs(config):
    """{row shards: what one job of the configuration returned and
    recorded}, the frame's columns kept for the references."""
    from h2o_tpu.models.gbm import GBM, GBMParameters

    out = {}
    for shards in (4, 1):
        mesh = meshmod.make_mesh(devices=jax.devices()[:shards])
        with meshmod.use_mesh(mesh):
            fr, cols = datagen.higgs_frame(SEED, ROWS)
            seq0 = timeline.total_recorded()
            psum0 = telemetry.value("train.gbm.psum_bytes")
            model = GBM(GBMParameters(
                training_frame=fr, response_column=datagen.RESPONSE,
                seed=SEED % (1 << 31), **config["params"])).train_model()
            events = timeline.snapshot(since=seq0)
            (root,) = [e for e in events
                       if e["kind"] == "span" and e["what"] == "train.gbm"]
            forest = {k: np.asarray(model.forest[k])
                      for k in ("feat", "thr", "val", "gain", "nanL")}
            out[shards] = {
                **forest, "f0": float(np.asarray(model.f0)),
                "logloss": float(model.output.training_metrics.logloss),
                "auc": float(model.output.training_metrics.auc),
                "cols": cols, "plen": meshmod.padded_len(ROWS), "root": root,
                "psum_bytes": telemetry.value("train.gbm.psum_bytes") - psum0}
            STORE.remove(fr.key)
    return out


def test_the_deployment_trains_the_same_trees_on_four_shards_as_on_one(jobs):
    four, one = jobs[4], jobs[1]
    assert four["feat"].shape == (50, 63)
    np.testing.assert_array_equal(four["feat"], one["feat"])
    np.testing.assert_array_equal(four["thr"], one["thr"])
    np.testing.assert_array_equal(four["nanL"], one["nanL"])
    # sums reduced in another order: float32 rounding on values and gains
    np.testing.assert_allclose(four["val"], one["val"], rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(four["gain"], one["gain"], rtol=2e-3)
    assert abs(four["logloss"] - one["logloss"]) < 1e-5
    assert abs(four["auc"] - one["auc"]) < 1e-5


@pytest.mark.parametrize("shards", [4, 1])
@pytest.mark.parametrize("ref", [ref_plain, ref_shards],
                         ids=["gbm", "gbm_shards"])
def test_each_forest_passes_each_reference(jobs, config, ref, shards):
    job = jobs[shards]
    numbers = ref.compare(job, ref.Data(job["cols"], ROWS), config)
    limits = config["correct"]["limits"]
    over = [k for k, v in numbers.items()
            if k in limits and k not in SIZE_DEPENDENT and not v <= limits[k]]
    assert not over, numbers


def test_the_two_references_agree(jobs, config):
    """Same data, same forest: the float64 sums agree to rounding; the
    split search's histograms are float32 sums in row blocks, added in
    another order a shard at a time, so ``regret_gap`` agrees to that."""
    job = jobs[4]
    a = ref_plain.compare(job, ref_plain.Data(job["cols"], ROWS), config)
    b = ref_shards.compare(job, ref_shards.Data(job["cols"], ROWS), config)
    assert set(a) == set(b)
    for k in a:
        tol = 2e-5 if k == "regret_gap" else 1e-9
        assert abs(a[k] - b[k]) <= tol, (k, a[k], b[k])
    assert len(ref_shards.Data(job["cols"], ROWS).ranges) == 4


@pytest.mark.parametrize("shards", [4, 1])
def test_the_job_says_how_its_rows_lie_on_the_mesh(jobs, shards):
    job = jobs[shards]
    assert job["root"]["row_shards"] == shards
    assert job["root"]["rows_per_shard"] == job["plen"] // shards


def test_psum_bytes_reads_what_the_shapes_give(jobs):
    # every level's whole f32[28, n_lv, 21, 3]: 31 nodes x 7,056 B a tree
    assert jobs[4]["psum_bytes"] == 50 * 31 * 28 * 21 * 3 * 4
    assert jobs[1]["psum_bytes"] == 0
