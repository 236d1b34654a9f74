"""``correct`` of the XGBoost cell has to be able to fail. At a size a test
run can hold, on the CPU: the control (the plain reference in the program's
place, in the precision below the one the configuration states) and each
planted fault of ``reference/xgb.py`` come out as not correct by the limits
``configs/higgs_xgb.json`` carries, in the reference's own check and through
a whole run of the harness (``run.measure``) with the timed path's result
replaced underneath; an untampered rehearsal comes out correct but for the
numbers that read the quantile sketch's noise, which falls with the rows
(their limits are for 11M rows).
"""

from __future__ import annotations

import subprocess
import sys

import pytest

from benchmark import datagen, manifest, run
from benchmark.reference import xgb as ref

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)
CELL = "higgs_xgb_train"
ROWS, SEED = 20_000, 2**31 + 34567
#: at 20,000 rows the PROGRAM's sketch reads a cut to a few rows of 20,000
#: and its split search moves with it; at 11M rows both are under their limits
SIZE_DEPENDENT = ("edge_rank_gap", "regret_gap")
NAMES = ("control",) + ref.FAULTS


def _config():
    cfg = manifest.config_of(MAN, "higgs_xgb", ROOT)
    cfg["data"]["rows"] = ROWS
    return cfg


def _over(numbers: dict, limits: dict) -> list:
    return [k for k, v in numbers.items() if k in limits and not v <= limits[k]]


@pytest.fixture(scope="module")
def data():
    import jax
    from jax.sharding import SingleDeviceSharding

    from h2o_tpu.parallel import mesh as meshmod

    cols = datagen.higgs_columns(SEED, ROWS, meshmod.padded_len(ROWS),
                                 SingleDeviceSharding(jax.devices()[0]))
    return ref.Data(cols, ROWS)


@pytest.fixture(scope="module")
def made(data):
    """name -> (candidate, the parameters it was planted at)."""
    return {name: (cand, prm)
            for name, cand, prm in ref.candidates(_config(), data)}


def test_the_reference_passes_its_own_check(data):
    cfg = _config()
    prm = ref.params_of(cfg)
    own = ref.build(data, 2, prm)
    assert _over(ref.check(own, data, prm, range(2), [0]),
                 cfg["correct"]["limits"]) == []


@pytest.mark.parametrize("name", NAMES)
def test_control_and_each_fault_are_not_correct(data, made, name):
    cand, prm = made[name]
    numbers = ref.check(cand, data, prm, range(3), [0])
    assert _over(numbers, _config()["correct"]["limits"]), numbers


def _cell(tamper=None):
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds",
                      "1", "--trace", "0"])
    cell = manifest.cell(MAN, CELL)
    config = _config()
    mix = dict(manifest.traffic_of(MAN, cell["traffic"], ROOT), warmup_jobs=0)

    def swap(r):
        cand, prm = tamper
        # a fault planted at `xgb.binding_weight` is checked at it
        config["params"]["min_rows"] = prm["min_child_weight"]
        config["params"]["reg_lambda"] = prm["lam"]
        return cand

    return run.measure(args, MAN, cell, config, mix,
                       tamper=None if tamper is None else swap)


@pytest.mark.parametrize("name", NAMES)
def test_a_run_with_the_timed_path_replaced_is_not_correct(made, name):
    """The candidates are the reference's own forests, on its own exact
    cuts: a number of theirs over its limit reads the fault at any size,
    ``edge_rank_gap`` and ``regret_gap`` included."""
    result = _cell(tamper=made[name])
    assert result["correct"] is False
    bad = [k for k, c in result["compared"].items()
           if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert bad, result["compared"]


def test_an_untampered_run_is_correct_but_for_the_size_dependent_numbers():
    result = _cell()
    bad = [k for k, c in result["compared"].items()
           if c["limit"] is None or not c["value"] <= c["limit"]]
    assert set(bad) <= set(SIZE_DEPENDENT), result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_the_rehearsal_command_runs_to_its_end_and_never_passes():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0", "--rehearse-cpu",
         "--rows", "6000"], cwd=ROOT, capture_output=True, text=True)
    assert out.returncode == 2
    assert "REHEARSAL of higgs_xgb_train on platform=cpu" in out.stdout
    assert "compared leaf_gap" in out.stderr
