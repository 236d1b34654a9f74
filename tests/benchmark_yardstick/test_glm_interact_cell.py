"""The pairwise-interaction GLM cell (ISSUE 40), as files and entries: what
the manifest says of it, what its new per-layer metrics read, and the work
one of its jobs counts. CPU, no socket, no JAX. Written to hold when later
PRs append cells and metrics: nothing here asks to be last."""

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
CELL, CONFIG = "higgs_glm_interact_train", "higgs_glm_interact"
OWN = ["glm_interact_step_device_s", "glm_interact_eta_device_s",
       "glm_interact_moments_s"]


def test_the_manifest_checks_clean_with_the_new_entries():
    assert manifest.check(ROOT) == []
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_back_to_back_slice8s", 1)
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == []
    assert (cfg["algo"], cfg["reference"], cfg["estimator"]) == (
        "glm", "glm_interact", "H2OGeneralizedLinearEstimator")
    p = cfg["params"]
    assert p["interactions"] == [f"f{j}" for j in range(28)]
    assert (p["family"], p["solver"], p["lambda_"], p["standardize"],
            p["max_iterations"]) == ("binomial", "IRLSM", 0.0, True, 10)
    assert cfg["data"] == {"generator": "higgs", "rows": 11_000_000,
                           "features": 406, "frame_features": 28}
    assert cfg["precision"] and cfg["assumed"]["data.features"]


@pytest.mark.parametrize("group,want", [
    ("end_to_end", ["train_job_s.glm", "setup_s"]),
    ("per_layer", [
        "compiles_in_window.glm", "program_replays_per_job.glm",
        "uncached_compiles.setup", "train_mfu.glm", "peak_hbm_gb.glm",
        "device_idle_share.glm", "rest_overhead_s.glm", "glm_design_s",
        "glm_start_s", "glm_gram_s", "glm_solve_s", "glm_probe_s",
        "glm_metrics_s", "metrics_device_s.glm"] + OWN)])
def test_the_cell_reports_the_shared_metrics_and_its_own(group, want):
    """The GLM cells' metrics that read something in a streamed job, and
    three of its own that no other cell lists; not ``irls_program_s``
    (module ``jit__core``, which this job never runs), ``glm_probe_device_s``
    (``jit_glm_probe``, likewise) or ``program_load_s.glm`` (nothing to
    read in a steady window since PR 37)."""
    got = [m["name"] for m in manifest.metrics_of(MAN, CELL, group)]
    assert set(want) <= set(got), set(want) - set(got)
    assert not {"irls_program_s", "glm_probe_device_s",
                "program_load_s.glm"} & set(got)
    for m in MAN["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "train_job_s.glm"


def test_every_limit_has_a_number_the_reference_returns():
    c = manifest.config_of(MAN, CONFIG, ROOT)["correct"]
    numbers = {"names_gap", "coef_gap", "score_gap", "logloss_gap", "auc_gap"}
    assert set(c["limits"]) | set(c.get("not_compared", [])) == numbers
    assert not set(c["limits"]) & set(c.get("not_compared", []))
    assert (c["control_dtype"], c["control_metrics_dtype"]) == (
        "float8_e4m3fn", "bfloat16")


def test_the_moments_metric_reads_its_span_a_job():
    with open(manifest.layer_metric_file(MAN, "glm_interact_moments_s",
                                         ROOT)) as f:
        spec = json.load(f)
    read = readers.READERS[spec["reader"]]
    spans = [{"what": "train.glm.interact.moments", "dur_us": d}
             for d in (0.02e6, 0.04e6)] + [{"what": "train.glm.gram",
                                            "dur_us": 9e6}]
    obs = {"algo": "glm", "spans": spans, "njobs": 2}
    assert read(obs, **spec["args"]) == pytest.approx(0.03)
    # the parent opens no such span: nothing, and no error
    assert read(dict(obs, spans=spans[2:]), **spec["args"]) is None


@pytest.mark.parametrize("name,module", [
    ("glm_interact_step_device_s", "jit_glm_interact_step"),
    ("glm_interact_eta_device_s", "jit_glm_interact_eta")])
def test_a_program_metric_reads_its_module_alone(name, module):
    with open(manifest.layer_metric_file(MAN, name, ROOT)) as f:
        spec = json.load(f)
    read = readers.READERS[spec["reader"]]
    mods = {"jit_glm_interact_step": 0.16, "jit_glm_interact_eta": 0.012,
            "jit_glm_interact_moments": 0.004, "jit__core": 0.1}
    assert read({"trace": {"modules": mods}}, **spec["args"]) == mods[module]
    assert read({"trace": {"modules": {"jit__core": 0.1}}},
                **spec["args"]) is None


@pytest.mark.parametrize("counted,iterations", [(None, 10), (4, 4)])
def test_a_job_s_work_is_that_of_its_passes_over_the_407_column_design(
        counted, iterations):
    """``data.features`` is the design's width less the intercept, so the
    IRLS passes are counted over the (R, 407) design the step forms."""
    cfg = manifest.config_of(MAN, CONFIG, ROOT)
    ops, nbytes = work_counts.job_work(cfg, counted)
    assert (ops, nbytes) == work_counts.glm_job(11_000_000, 407, iterations)
    # 2 R P^2 = 3.644 TFLOP a pass, and the right-hand side and the solve
    assert ops == pytest.approx(iterations * 3.644e12, rel=5e-3)
