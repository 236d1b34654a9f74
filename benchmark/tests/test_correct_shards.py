"""``correct`` has to be able to fail for the four-chip deployment too:
``test_correct.py``'s GBM cases for the configuration ``higgs_gbm_44m`` and
its cell, decided by ``reference/gbm_shards.py``, at the same 40,000 rows
on however many CPU devices this process has (one beside the other tests
of this directory; the four-shard path runs in the repo's
tests/test_sharded_deployment.py). The helpers are ``test_correct.py``'s.
"""

from __future__ import annotations

import pytest
import test_correct as tc

from benchmark.reference import gbm_shards as ref

CONFIG, CELL = "higgs_gbm_44m", "higgs_gbm_train_4chip"


@pytest.fixture(scope="module")
def data():
    return tc._data(ref)


@pytest.fixture(scope="module")
def cfg():
    return tc._config(CONFIG)


def _check(cand, data, cfg):
    return ref.check(cand, data, cfg["params"]["learn_rate"], range(3), [0])


def test_the_configuration_is_decided_by_the_sharded_reference(cfg):
    assert cfg["reference"] == "gbm_shards" and cfg["reduced"] == []
    assert cfg["data"]["rows"] == 44_000_000 and cfg["params"]["ntrees"] == 50


def test_reference_passes_its_own_check(data, cfg):
    own = ref.build(data, 3, 5, 0.1)
    assert tc._fails(_check(own, data, cfg), cfg["correct"]["limits"]) == []


def test_control_in_the_precision_below_is_not_correct(data, cfg):
    c = cfg["correct"]
    low = ref.build(data, 3, 5, 0.1, addend_dtype=c["control_dtype"],
                    metrics_dtype=c["control_metrics_dtype"])
    assert tc._fails(_check(low, data, cfg), c["limits"])


@pytest.mark.parametrize("fault", tc.FAULTS)
def test_fault_is_not_correct(data, cfg, fault):
    bad = ref.build(data, 3, 5, 0.1, fault=fault)
    assert tc._fails(_check(bad, data, cfg), cfg["correct"]["limits"])


def test_run_is_correct_but_for_the_size_dependent_numbers():
    result = tc._cell(CELL)
    assert set(tc._over(result["compared"])) <= set(tc.SIZE_DEPENDENT), \
        result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "altered", "half_batch"])
def test_run_with_the_timed_path_broken_is_not_correct(data, fault):
    tamper = {"state_unchanged": tc._trees_all_like_the_first,
              "altered": tc._one_leaf_doubled,
              "half_batch": tc._half_batch_forest(data)}[fault]
    result = tc._cell(CELL, tamper=tamper)
    assert result["correct"] is False
    assert set(tc._over(result["compared"])) - set(tc.SIZE_DEPENDENT), \
        result["compared"]
