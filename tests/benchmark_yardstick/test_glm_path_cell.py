"""The regularisation-path cell (ISSUE 32), as files and entries: what the
manifest says of it, what its one new per-layer metric reads, and the work
one of its jobs counts. CPU, no socket, no JAX."""

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
CELL, CONFIG = "higgs_glm_path_train", "higgs_glm_path"


def test_the_manifest_checks_clean_with_the_new_entries():
    assert manifest.check(ROOT) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_back_to_back_slice8s", 1)
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert cfg["source"] == entry["source"] and entry["reduced"] == []
    assert cfg["reduced"] == [] and cfg["reference"] == "glm_path"
    mix = manifest.traffic_of(MAN, cell["traffic"], ROOT)
    assert mix["kind"] == "train_back_to_back" and mix["warmup_jobs"] == 1
    assert mix["trace_slice"]["cap_s"] == 8.0


@pytest.mark.parametrize("group,want", [
    ("end_to_end", ["train_job_s.glm", "setup_s"]),
    ("per_layer", [
        "rest_overhead_s.glm", "compiles_in_window.glm",
        "program_replays_per_job.glm", "uncached_compiles.setup",
        "irls_program_s", "train_mfu.glm", "peak_hbm_gb.glm",
        "device_idle_share.glm", "glm_design_s", "glm_gram_s", "glm_solve_s",
        "glm_probe_s", "glm_metrics_s", "program_load_s.glm", "glm_path_s"])])
def test_the_cell_reports_the_glm_metrics_and_its_own(group, want):
    """Every ``.glm`` entry the other GLM cell reports, and ``glm_path_s``,
    which no other cell lists."""
    assert [m["name"] for m in manifest.metrics_of(MAN, CELL, group)] == want
    other = [m["name"] for m in manifest.metrics_of(MAN, "higgs_glm_train", group)]
    assert other == [n for n in want if n != "glm_path_s"]


def test_every_limit_has_a_number_the_reference_returns():
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    numbers = {"kkt_gap", "lambda_grid_gap", "stop_gap", "coef_gap",
               "support_gap", "logloss_gap", "auc_gap"}
    c = cfg["correct"]
    assert set(c["limits"]) | set(c["not_compared"]) == numbers
    assert not set(c["limits"]) & set(c["not_compared"])
    p = cfg["params"]
    assert (p["alpha"], p["nlambdas"], p["lambda_min_ratio"]) == (0.5, 100, 1e-4)
    assert p["lambda_search"] and p["standardize"] and p["early_stopping"]


SPANS = [{"what": "train.glm.path", "dur_us": 2.5e6},
         {"what": "train.glm.path", "dur_us": 3.5e6},
         {"what": "train.glm.gram", "dur_us": 1.0e6},
         {"what": "train.glm", "dur_us": 4.0e6}]


def test_glm_path_s_is_the_path_span_a_job():
    with open(manifest.layer_metric_file(MAN, "glm_path_s", ROOT)) as f:
        spec = json.load(f)
    read = readers.READERS[spec["reader"]]
    obs = {"algo": "glm", "spans": SPANS, "njobs": 2}
    assert read(obs, **spec["args"]) == pytest.approx(3.0)
    # a program without the span (the parent): nothing, and no error
    assert read({"algo": "glm", "njobs": 2,
                 "spans": [s for s in SPANS if s["what"] != "train.glm.path"]},
                **spec["args"]) is None
    assert read({"algo": "glm", "spans": SPANS, "njobs": 0},
                **spec["args"]) is None


@pytest.mark.parametrize("counted,iterations", [(None, 50), (116.5, 116.5)])
def test_a_path_job_s_work_is_that_of_its_iterations(counted, iterations):
    """Without a counted number the configuration's ``max_iterations``
    stands in; a traced run's count of IRLS dispatches a job replaces it."""
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    ops, nbytes = work_counts.job_work(cfg, counted)
    assert (ops, nbytes) == work_counts.glm_job(11_000_000, 29, iterations)
    assert nbytes == iterations * 4 * 11_000_000 * 30
