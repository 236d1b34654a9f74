"""``correct`` has to be able to fail. At a size a test run can hold, on the
CPU: the control of each cell (the plain reference in the program's place,
in the precision below the one the configuration states) and each planted
fault come out as not correct by the limits the configuration files carry,
and a whole run of the harness, with the look for a chip skipped and the
timed path's result broken underneath, reports ``correct`` false. The
sound program passes every limit that does not depend on the frame's size
(``regret_gap`` reads the quantile sketch's noise, which falls with the
square root of the rows: its limit is for 11M rows).
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import datagen, manifest, run
from benchmark.reference import gbm as ref_gbm
from benchmark.reference import glm as ref_glm

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)
ROWS, SEED = 40_000, 2**31 + 12345
FAULTS = ("state_unchanged", "half_batch", "altered")
SIZE_DEPENDENT = ("regret_gap",)


def _config(name):
    return manifest.config_of(MAN, name, ROOT)


def _data(ref):
    import jax
    from jax.sharding import SingleDeviceSharding

    from h2o_tpu.parallel import mesh as meshmod

    cols = datagen.higgs_columns(SEED, ROWS, meshmod.padded_len(ROWS),
                                 SingleDeviceSharding(jax.devices()[0]))
    return ref.Data(cols, ROWS)


def _fails(numbers: dict, limits: dict) -> list:
    return [k for k, v in numbers.items()
            if k in limits and not v <= limits[k]]


def _over(compared: dict) -> list:
    """The numbers of a run that are over their limit (one that is printed
    without a limit is not compared)."""
    return [k for k, c in compared.items()
            if c["limit"] is not None and not c["value"] <= c["limit"]]


def _cell(name, tamper=None):
    """The rest of a run, with the look for a chip skipped: ``run.measure``
    at a size the CPU holds."""
    args = run.parse(["--workload", name, "--seed", str(SEED), "--seconds", "1",
                      "--trace", "0"])
    cell = manifest.cell(MAN, name)
    config = _config(cell["config"])
    config["data"]["rows"] = ROWS
    mix = manifest.traffic_of(MAN, cell["traffic"], ROOT)
    return run.measure(args, MAN, cell, config, mix, tamper=tamper)


# ------------------------------------------------------------------ GBM ---
@pytest.fixture(scope="module")
def gbm_data():
    return _data(ref_gbm)


@pytest.fixture(scope="module")
def gbm_cfg():
    return _config("higgs_gbm")


def _gbm_check(cand, data, cfg, trees):
    return ref_gbm.check(cand, data, cfg["params"]["learn_rate"],
                         range(trees), [0])


def test_gbm_reference_passes_its_own_check(gbm_data, gbm_cfg):
    own = ref_gbm.build(gbm_data, 3, 5, 0.1)
    assert _fails(_gbm_check(own, gbm_data, gbm_cfg, 3),
                  gbm_cfg["correct"]["limits"]) == []


def test_gbm_control_in_the_precision_below_is_not_correct(gbm_data, gbm_cfg):
    c = gbm_cfg["correct"]
    low = ref_gbm.build(gbm_data, 3, 5, 0.1, addend_dtype=c["control_dtype"],
                        metrics_dtype=c["control_metrics_dtype"])
    assert _fails(_gbm_check(low, gbm_data, gbm_cfg, 3), c["limits"])


@pytest.mark.parametrize("fault", FAULTS)
def test_gbm_fault_is_not_correct(gbm_data, gbm_cfg, fault):
    bad = ref_gbm.build(gbm_data, 3, 5, 0.1, fault=fault)
    assert _fails(_gbm_check(bad, gbm_data, gbm_cfg, 3),
                  gbm_cfg["correct"]["limits"])


def test_gbm_run_is_correct_but_for_the_size_dependent_numbers(gbm_cfg):
    result = _cell("higgs_gbm_train")
    bad = _over(result["compared"])
    assert set(bad) <= set(SIZE_DEPENDENT), result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def _trees_all_like_the_first(r):
    """A step that returns its state unchanged: every tree is fitted to
    the first tree's gradients."""
    out = dict(r)
    for k in ("feat", "thr", "val", "gain"):
        out[k] = np.repeat(np.asarray(r[k])[:1], len(r[k]), axis=0)
    return out


def _one_leaf_doubled(r):
    out = dict(r, val=np.array(r["val"], copy=True))
    t = out["val"].shape[0] - 1
    out["val"][t, np.argmax(np.abs(out["val"][t]))] *= 2.0
    return out


def _half_batch_forest(data):
    def tamper(r):
        return ref_gbm.build(data, int(np.asarray(r["feat"]).shape[0]), 5, 0.1,
                             fault="half_batch")
    return tamper


@pytest.mark.parametrize("fault", ["state_unchanged", "altered", "half_batch"])
def test_gbm_run_with_the_timed_path_broken_is_not_correct(gbm_data, fault):
    tamper = {"state_unchanged": _trees_all_like_the_first,
              "altered": _one_leaf_doubled,
              "half_batch": _half_batch_forest(gbm_data)}[fault]
    result = _cell("higgs_gbm_train", tamper=tamper)
    assert result["correct"] is False
    bad = _over(result["compared"])
    assert set(bad) - set(SIZE_DEPENDENT), result["compared"]


# ------------------------------------------------------------------ GLM ---
@pytest.fixture(scope="module")
def glm_data():
    return _data(ref_glm)


@pytest.fixture(scope="module")
def glm_cfg():
    return _config("higgs_glm")


def test_glm_reference_passes_its_own_check(glm_data, glm_cfg):
    c = glm_cfg["correct"]
    own = ref_glm.fit(glm_data, c["converge_iterations"])
    assert _fails(ref_glm.check(own, glm_data, c["converge_iterations"]),
                  c["limits"]) == []


def test_glm_control_in_the_precision_below_is_not_correct(glm_data, glm_cfg):
    c = glm_cfg["correct"]
    low = ref_glm.fit(glm_data, glm_cfg["params"]["max_iterations"],
                      dtype_name=c["control_dtype"],
                      metrics_dtype=c["control_metrics_dtype"])
    assert _fails(ref_glm.check(low, glm_data, c["converge_iterations"]),
                  c["limits"])


@pytest.mark.parametrize("fault", FAULTS)
def test_glm_fault_is_not_correct(glm_data, glm_cfg, fault):
    c = glm_cfg["correct"]
    bad = ref_glm.fit(glm_data, glm_cfg["params"]["max_iterations"], fault=fault)
    assert _fails(ref_glm.check(bad, glm_data, c["converge_iterations"]),
                  c["limits"])


def test_glm_run_is_correct():
    result = _cell("higgs_glm_train")
    assert result["correct"] is True, result["compared"]


def _glm_tamper(fault, data, cfg):
    names = [f"f{j}" for j in range(datagen.NCOL)] + ["Intercept"]

    def tamper(r):
        bad = ref_glm.fit(data, cfg["params"]["max_iterations"], fault=fault)
        return dict(r, coef=dict(zip(names, bad["coef"])))
    return tamper


@pytest.mark.parametrize("fault", FAULTS)
def test_glm_run_with_the_timed_path_broken_is_not_correct(glm_data, glm_cfg,
                                                          fault):
    result = _cell("higgs_glm_train",
                      tamper=_glm_tamper(fault, glm_data, glm_cfg))
    assert result["correct"] is False


def test_glm_run_with_a_wrong_reported_logloss_and_the_auc_intact_is_not_correct():
    """The reported logloss is held on its own: five parts in a million off,
    as a lower-precision reduction would leave it, fails ``logloss_gap`` and
    nothing else."""
    result = _cell("higgs_glm_train",
                   tamper=lambda r: dict(r, logloss=r["logloss"] * (1 + 5e-6)))
    assert result["correct"] is False
    assert _over(result["compared"]) == ["logloss_gap"]
