"""``correct`` of the regularisation-path cell has to be able to fail. At
40,000 rows on the CPU: the program's result is correct by the limits the
configuration file carries; the control (the reference's own walk of the
path with float8 Gram operands and bfloat16 metric probabilities) and each
of the five planted faults, put in the timed path's place underneath a
whole run of the harness (``run.measure`` with ``tamper``), are not."""

from __future__ import annotations

import pytest

from benchmark import datagen, manifest, run
from benchmark.reference import glm_path as ref

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)
CELL = "higgs_glm_path_train"
ROWS, SEED = 40_000, 2**31 + 12345
NAMES = [f"f{j}" for j in range(datagen.NCOL)] + ["Intercept"]


def _config():
    cfg = manifest.config_of(MAN, manifest.cell(MAN, CELL)["config"], ROOT)
    cfg["data"]["rows"] = ROWS
    return cfg


@pytest.fixture(scope="module")
def data():
    import jax
    from jax.sharding import SingleDeviceSharding

    from h2o_tpu.parallel import mesh as meshmod

    cols = datagen.higgs_columns(SEED, ROWS, meshmod.padded_len(ROWS),
                                 SingleDeviceSharding(jax.devices()[0]))
    return ref.Data(cols, ROWS)


def _over(compared: dict) -> list:
    return [k for k, c in compared.items()
            if c["limit"] is not None and not c["value"] <= c["limit"]]


def _cell(tamper=None):
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds",
                      "1", "--trace", "0"])
    cell = manifest.cell(MAN, CELL)
    mix = manifest.traffic_of(MAN, cell["traffic"], ROOT)
    return run.measure(args, MAN, cell, _config(), mix, tamper=tamper)


def _in_the_programs_place(data, **walk):
    def tamper(r):
        bad = ref.walk(data, _config(), **walk)
        return dict(r, coef=dict(zip(NAMES, bad["coef"])),
                    logloss=bad["logloss"], auc=bad["auc"])
    return tamper


def test_the_reference_s_own_walk_passes_its_own_check(data):
    cfg = _config()
    numbers = ref.check(ref.walk(data, cfg), data, cfg)
    lim = cfg["correct"]["limits"]
    assert [k for k, v in numbers.items() if k in lim and not v <= lim[k]] == []


def test_the_program_s_run_is_correct():
    result = _cell()
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_the_control_in_the_precision_below_is_not_correct(data):
    c = _config()["correct"]
    result = _cell(_in_the_programs_place(
        data, dtype_name=c["control_dtype"],
        metrics_dtype=c["control_metrics_dtype"]))
    assert result["correct"] is False and _over(result["compared"])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_run_with_the_timed_path_broken_is_not_correct(data, fault):
    result = _cell(_in_the_programs_place(data, fault=fault))
    assert result["correct"] is False and _over(result["compared"]), (
        fault, result["compared"])
