"""The additive-model cell (ISSUE 38), as files and entries: what the
manifest says of it, what its new per-layer metrics read, and the work one
of its jobs counts. CPU, no socket, no JAX."""

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
CELL, CONFIG = "higgs_gam_train", "higgs_gam"
NEW = {"gam_rest_overhead_s": "train.gam", "gam_knots_s": "train.gam.knots",
       "gam_design_s": "train.gam.design", "gam_gram_s": "train.gam.gram",
       "gam_solve_s": "train.gam.solve", "gam_metrics_s": "train.gam.metrics"}


def test_the_manifest_checks_clean_with_the_new_entries():
    assert manifest.check(ROOT) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_back_to_back_slice8s", 1)
    assert MAN["workloads"][-1] is cell and MAN["configs"][-1]["name"] == CONFIG
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    entry = MAN["configs"][-1]
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == []
    assert (cfg["algo"], cfg["reference"], cfg["estimator"]) == (
        "glm", "gam", "H2OGeneralizedAdditiveEstimator")
    p = cfg["params"]
    assert p["gam_columns"] == [f"f{j}" for j in range(21, 28)]
    assert (p["bs"], p["num_knots"], p["scale"]) == (
        [0] * 7, [10] * 7, [1.0] * 7)
    assert (p["family"], p["solver"], p["lambda_"], p["standardize"],
            p["max_iterations"]) == ("binomial", "IRLSM", 0.0, False, 10)
    assert cfg["data"] == {"generator": "higgs", "rows": 11_000_000,
                           "features": 84, "frame_features": 28}


@pytest.mark.parametrize("group,want", [
    ("end_to_end", ["train_job_s.glm", "setup_s"]),
    ("per_layer", [
        "compiles_in_window.glm", "program_replays_per_job.glm",
        "uncached_compiles.setup", "irls_program_s", "train_mfu.glm",
        "peak_hbm_gb.glm", "device_idle_share.glm", "metrics_device_s.glm",
        "gam_rest_overhead_s", "gam_knots_s", "gam_design_s", "gam_gram_s",
        "gam_solve_s", "gam_metrics_s", "gam_design_device_s"])])
def test_the_cell_reports_the_shared_metrics_and_its_own(group, want):
    """The GLM cells' metrics that read something in a GAM job (the step is
    the GLM's, module ``jit__core``), none of the ``train.glm.*`` span
    metrics (the job opens no such span), and seven of its own that no
    other cell lists."""
    got = [m["name"] for m in manifest.metrics_of(MAN, CELL, group)]
    # a later PR may list the cell under more metrics: these it must keep
    assert [n for n in got if n in want] == want
    for m in MAN["per_layer"]:
        if m["name"].startswith("gam_"):
            assert m["workloads"] == [CELL] and m["moves"] == "train_job_s.glm"
        if m["name"].startswith(("glm_", "program_load_s", "rest_overhead_s")):
            assert CELL not in m.get("workloads", [])


def test_every_limit_has_a_number_the_reference_returns():
    c = manifest.config_of(MAN, CONFIG, ROOT)["correct"]
    numbers = {"names_gap", "smooth_gap", "coef_gap", "kkt_gap",
               "zero_sum_gap", "logloss_gap", "auc_gap"}
    assert set(c["limits"]) | set(c["not_compared"]) == numbers
    assert not set(c["limits"]) & set(c["not_compared"])
    assert set(c["not_compared_why"]) == set(c["not_compared"])
    assert (c["control_dtype"], c["control_basis_dtype"]) == (
        "float8_e4m3fn", "bfloat16")


SPANS = [{"what": w, "dur_us": d} for w, d in (
    ("train.gam", 0.30e6), ("train.gam", 0.32e6),
    ("train.gam.knots", 0.03e6), ("train.gam.knots", 0.05e6),
    ("train.gam.design", 0.02e6), ("train.gam.design", 0.02e6),
    ("train.gam.gram", 0.04e6), ("train.gam.gram", 0.06e6),
    ("train.gam.solve", 0.001e6), ("train.gam.metrics", 0.05e6),
    ("train.glm.gram", 9e6))]
JOBS = [{"start": 0.0, "end": 0.35}, {"start": 0.35, "end": 0.72}]


@pytest.mark.parametrize("name,want", [
    ("gam_rest_overhead_s", 0.05), ("gam_knots_s", 0.04),
    ("gam_design_s", 0.02), ("gam_gram_s", 0.05), ("gam_solve_s", 0.0005),
    ("gam_metrics_s", 0.025)])
def test_a_span_metric_reads_its_span_a_job(name, want):
    with open(manifest.layer_metric_file(MAN, name, ROOT)) as f:
        spec = json.load(f)
    assert spec["args"]["span"] == NEW[name]
    read = readers.READERS[spec["reader"]]
    obs = {"algo": "glm", "spans": SPANS, "njobs": 2, "jobs": JOBS}
    assert read(obs, **spec["args"]) == pytest.approx(want)
    # a program without the span (the parent): nothing, and no error
    bare = dict(obs, spans=[s for s in SPANS if s["what"] != NEW[name]])
    assert read(bare, **spec["args"]) is None


def test_the_design_programs_device_seconds_sum_both_modules():
    with open(manifest.layer_metric_file(MAN, "gam_design_device_s", ROOT)) as f:
        spec = json.load(f)
    read = readers.READERS[spec["reader"]]
    mods = {"jit_gam_design": 0.03, "jit_gam_design_sums": 0.005,
            "jit__core": 0.1, "jit_glm_probe": 0.02}
    assert read({"trace": {"modules": mods}}, **spec["args"]) == pytest.approx(0.035)
    assert read({"trace": {"modules": {"jit__core": 0.1}}}, **spec["args"]) is None


@pytest.mark.parametrize("counted,iterations", [(None, 10), (4.5, 4.5)])
def test_a_job_s_work_is_that_of_its_passes_over_the_85_column_design(
        counted, iterations):
    """``data.features`` is the design's width less the intercept, so the
    IRLS passes are counted over the design the job really has."""
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    ops, nbytes = work_counts.job_work(cfg, counted)
    assert (ops, nbytes) == work_counts.glm_job(11_000_000, 85, iterations)
    assert nbytes == iterations * 4 * 11_000_000 * 86
