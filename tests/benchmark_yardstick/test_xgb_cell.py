"""The XGBoost cell (ISSUE 34), as files and entries: what the manifest
says of it, what its two new per-layer metrics read, and the work one of
its jobs counts. CPU, no socket, no JAX."""

from __future__ import annotations

import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import manifest, readers, work_counts

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)
CELL, CONFIG = "higgs_xgb_train", "higgs_xgb"


def test_the_manifest_checks_clean_with_the_new_entries():
    assert manifest.check(ROOT) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_back_to_back", 1)
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == ["ntrees"]
    assert cfg["published"]["ntrees"] == 50
    assert (cfg["algo"], cfg["reference"], cfg["estimator"]) == (
        "gbm", "xgb", "H2OXGBoostEstimator")
    assert cfg["data"] == {"generator": "higgs", "rows": 11_000_000,
                           "features": 28}
    mix = manifest.traffic_of(MAN, cell["traffic"], ROOT)
    assert mix["kind"] == "train_back_to_back" and mix["warmup_jobs"] == 1


def test_every_parameter_is_written_out_at_the_documented_value():
    """None left to a default: the file is the deployment whatever the
    program's defaults become. Only ``ntrees`` is cut (50 published)."""
    assert manifest.config_of(MAN, CONFIG, ROOT)["params"] == {
        "ntrees": 20, "max_depth": 6, "learn_rate": 0.3, "min_rows": 1,
        "max_bins": 256, "reg_lambda": 1, "reg_alpha": 0,
        "min_split_improvement": 0, "sample_rate": 1, "col_sample_rate": 1,
        "col_sample_rate_per_tree": 1, "booster": "gbtree",
        "tree_method": "hist", "distribution": "bernoulli",
        "score_tree_interval": 10}


@pytest.mark.parametrize("group,want", [
    ("end_to_end", ["train_job_s.gbm", "setup_s"]),
    ("per_layer", [
        "compiles_in_window.gbm", "program_replays_per_job.gbm",
        "uncached_compiles.setup", "chunk_s_per_tree", "train_mfu.gbm",
        "peak_hbm_gb.gbm", "device_idle_share.gbm", "sketch_s",
        "binnedview_s", "xgb_setup_s", "xgb_rest_overhead_s"])])
def test_the_cell_reports_the_tree_engine_s_metrics_and_its_own_two(group, want):
    """Every ``.gbm`` entry of the GBM cell but the two whose files read a
    span this job does not open (``train.gbm``, ``train.{algo}``: its root
    is ``train.xgboost``); in their place ``xgb_setup_s`` and
    ``xgb_rest_overhead_s``, which no other cell lists."""
    assert [m["name"] for m in manifest.metrics_of(MAN, CELL, group)] == want
    other = [m["name"] for m in manifest.metrics_of(MAN, "higgs_gbm_train", group)]
    swap = {"gbm_setup_s": "xgb_setup_s",
            "rest_overhead_s.gbm": "xgb_rest_overhead_s"}
    assert sorted(swap.get(n, n) for n in other) == sorted(want)
    for name in ("xgb_setup_s", "xgb_rest_overhead_s"):
        (m,) = [m for m in MAN["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_job_s.gbm"


def test_every_limit_has_a_number_the_reference_returns():
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    numbers = {"edge_rank_gap", "f0_gap", "leaf_gap", "gain_gap",
               "regret_gap", "child_weight_gap", "logloss_gap", "auc_gap"}
    c = cfg["correct"]
    assert set(c["limits"]) | set(c["not_compared"]) == numbers
    assert not set(c["limits"]) & set(c["not_compared"])
    # XGBoost's hist sketch promises a cut's rank to 1 / (kFactor x max_bins)
    assert c["limits"]["edge_rank_gap"] <= 1 / (8 * 256)
    assert (c["verify_trees"], c["regret_trees"]) == ([0, 1, 10, 19], [0])
    assert c["control_dtype"] == "float8_e4m3fn"


SPANS = [{"what": "train.xgboost", "dur_us": 8.5e6},
         {"what": "train.xgboost", "dur_us": 8.7e6},
         {"what": "train.gbm.chunk", "dur_us": 4.0e6},
         {"what": "train.gbm.chunk", "dur_us": 4.1e6},
         {"what": "train.gbm.chunk", "dur_us": 4.0e6},
         {"what": "train.gbm.chunk", "dur_us": 4.3e6},
         {"what": "train.gbm.sketch", "dur_us": 0.1e6}]
JOBS = [{"start": 10.0, "end": 18.53}, {"start": 18.53, "end": 27.27}]


def _reader(name):
    with open(manifest.layer_metric_file(MAN, name, ROOT)) as f:
        spec = json.load(f)
    return readers.READERS[spec["reader"]], spec["args"]


def test_xgb_setup_s_is_the_root_span_less_its_chunks():
    read, args = _reader("xgb_setup_s")
    obs = {"algo": "gbm", "spans": SPANS, "njobs": 2}
    assert read(obs, **args) == pytest.approx((17.2 - 16.4) / 2)
    # a job whose root is another span (a GBM's train.gbm): nothing, no error
    gbm = [dict(s, what="train.gbm") if s["what"] == "train.xgboost" else s
           for s in SPANS]
    assert read({"algo": "gbm", "spans": gbm, "njobs": 2}, **args) is None


def test_xgb_rest_overhead_s_is_the_client_s_wall_less_the_root_span():
    read, args = _reader("xgb_rest_overhead_s")
    obs = {"algo": "gbm", "spans": SPANS, "jobs": JOBS}
    assert read(obs, **args) == pytest.approx((8.53 + 8.74 - 17.2) / 2)
    assert read({"algo": "gbm", "spans": SPANS[2:], "jobs": JOBS},
                **args) is None


def test_a_job_s_work_is_the_tree_engine_s_at_one_byte_a_code():
    """At 256 bins a code is one byte of information (what XGBoost itself
    stores), so the algorithm's bytes stay `gbm_tree`'s; the program's
    int16 reads as distance from the roofline, not as work."""
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    ops, nbytes = work_counts.job_work(cfg)
    assert (ops, nbytes) == work_counts.gbm_job(11_000_000, 28, 6, 20)
    assert nbytes == 20 * 6 * 11_000_000 * (28 + 4 + 12)
    assert ops == 20 * 6 * 11_000_000 * 28 * 3
