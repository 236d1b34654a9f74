"""``correct`` of the additive-model cell has to be able to fail. At 40,000
rows on the CPU: the control (the reference's own fit with float8 Gram
operands, bfloat16 basis values and bfloat16 metric probabilities) and each
of the six planted faults, put in the timed path's place underneath a whole
run of the harness (``run.measure`` with ``tamper``), are not correct; the
reference's own fit passes its own check. The limits are the chip's, for
11M rows: whether the PROGRAM's run is correct by them is the chip's to say
(at 40,000 rows its sketch's knots are a hundred times further from the
exact quantiles), and tests/test_gam_reference.py holds it on the CPU."""

from __future__ import annotations

import pytest

from benchmark import datagen, manifest, run
from benchmark.reference import gam as ref

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)
CELL = "higgs_gam_train"
ROWS, SEED = 40_000, 2**31 + 12345


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


def _in_the_programs_place(data, **fit):
    def tamper(r):
        return dict(r, **ref.fit(data, _config(), **fit))
    return tamper


def test_the_reference_s_own_fit_passes_its_own_check(data):
    cfg = _config()
    numbers = ref.check(ref.fit(data, cfg), data, cfg)
    lim = cfg["correct"]["limits"]
    assert [k for k, v in numbers.items() if k in lim and not v <= lim[k]] == []


def test_the_control_in_the_precision_below_is_not_correct(data):
    c = _config()["correct"]
    result = _cell(_in_the_programs_place(
        data, dtype_name=c["control_dtype"],
        basis_dtype=c["control_basis_dtype"],
        metrics_dtype=c["control_metrics_dtype"]))
    assert result["correct"] is False and _over(result["compared"])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_run_with_the_timed_path_broken_is_not_correct(data, fault):
    result = _cell(_in_the_programs_place(data, fault=fault))
    assert result["correct"] is False and _over(result["compared"]), (
        fault, result["compared"])
