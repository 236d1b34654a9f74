"""CPU tests of the benchmark's own yardstick: the manifest's rules, the
generators, the window arithmetic, the work counts and peaks, the trace
reduction, and the runner's refusal to measure without a chip. No socket
and no chip: they run with the repo's tier-1 tests, and this directory is
one of BENCHMARK.json's ``paths``."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import (datagen, manifest, peaks, readers, trace_reduce, window,
                       work_counts)

ROOT = manifest.root_of()
MAN = manifest.load(ROOT)


# ------------------------------------------------------------- manifest ---
def test_manifest_is_sound():
    assert manifest.check(ROOT) == []


def _names():
    out = [("config", c["name"]) for c in MAN["configs"]]
    out += [("cell", w["name"]) for w in MAN["workloads"]]
    out += [("traffic", w["traffic"]) for w in MAN["workloads"]]
    out += [("metric", m["name"]) for m in MAN["end_to_end"] + MAN["per_layer"]]
    out += [("reduced", k) for c in MAN["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("kind,name", _names())
def test_name_characters(kind, name):
    assert manifest.NAME.match(name), (kind, name)
    assert name[0] not in ".-" and len(name) <= 64


@pytest.mark.parametrize("m", MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda m: m["name"])
def test_unit_characters(m):
    assert manifest.UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in manifest.SOURCES


@pytest.mark.parametrize("c", MAN["configs"], ids=lambda c: c["name"])
def test_source_is_printable_ascii(c):
    assert manifest.line_ok(c["source"]) and manifest.line_ok(c["why"])
    with open(os.path.join(ROOT, c["file"])) as f:
        body = json.load(f)
    assert body["source"] == c["source"]
    assert sorted(body["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(w):
    assert manifest.line_ok(w["why"]) and w["chips"] in (1, 4)
    assert os.path.isfile(manifest.traffic_file(MAN, w["traffic"], ROOT))
    manifest.config_of(MAN, w["config"], ROOT)
    for m in manifest.metrics_of(MAN, w["name"], "per_layer"):
        assert os.path.isfile(manifest.layer_metric_file(MAN, m["name"], ROOT))


@pytest.mark.parametrize("m", MAN["per_layer"], ids=lambda m: m["name"])
def test_moves_is_reported_by_every_cell_of_the_metric(m):
    e2e = {e["name"]: e for e in MAN["end_to_end"]}
    assert m["moves"] in e2e
    cells = [w["name"] for w in MAN["workloads"]
             if m in manifest.metrics_of(MAN, w["name"], "per_layer")]
    assert cells
    for c in cells:
        assert e2e[m["moves"]] in manifest.metrics_of(MAN, c, "end_to_end")
    with open(manifest.layer_metric_file(MAN, m["name"], ROOT)) as f:
        spec = json.load(f)
    assert spec["reader"] in readers.READERS


def test_a_split_quantity_shares_the_file_of_the_name_before_its_last_dot():
    f = manifest.layer_metric_file
    assert f(MAN, "peak_hbm_gb.gbm", ROOT) == f(MAN, "peak_hbm_gb.glm", ROOT)
    assert f(MAN, "peak_hbm_gb.gbm", ROOT).endswith("peak_hbm_gb.json")
    assert f(MAN, "uncached_compiles.setup", ROOT).endswith(
        "uncached_compiles.setup.json")
    with pytest.raises(FileNotFoundError):
        f(MAN, "no_such_metric.gbm", ROOT)


@pytest.mark.parametrize("bad_source", [
    "11,000,000 rows × 28", "a long dash — here", "tab\there", "",
    "x" * 201])
def test_check_refuses_a_bad_source(tmp_path, bad_source):
    root = _copy_benchmark(tmp_path)
    man = manifest.load(root)
    man["configs"][0]["source"] = bad_source
    _write(root, man)
    assert any("source" in b for b in manifest.check(root))


def _copy_benchmark(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def _write(root, man):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)


def test_a_later_pr_adds_one_of_each_as_files_only(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric are
    added by new files and new manifest entries; nothing that is there is
    edited, and the check passes."""
    root = _copy_benchmark(tmp_path)
    man = manifest.load(root)
    b = os.path.join(root, "benchmark")
    cfg = manifest.config_of(man, "higgs_gbm", root)
    cfg.update(name="higgs_gbm_d7", source=cfg["source"] + " (depth 7)")
    cfg["params"]["max_depth"] = 7
    with open(os.path.join(b, "configs", "higgs_gbm_d7.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "train_two_warmups.json"), "w") as f:
        json.dump({"kind": "train_back_to_back", "warmup_jobs": 2,
                   "trace_slice": {"from": "first job", "cap_s": 8.0}}, f)
    with open(os.path.join(b, "layer_metrics", "chunk_s_total.json"), "w") as f:
        json.dump({"reader": "span_sum_per",
                   "args": {"span": "train.gbm.chunk", "per": "njobs"}}, f)
    man["configs"].append({"name": "higgs_gbm_d7", "source": cfg["source"],
                           "file": "benchmark/configs/higgs_gbm_d7.json",
                           "reduced": ["ntrees"], "why": "deeper trees"})
    man["workloads"].append({"name": "higgs_gbm_d7_train",
                             "config": "higgs_gbm_d7",
                             "traffic": "train_two_warmups", "chips": 1,
                             "why": "two warm-up jobs"})
    man["per_layer"].append({"name": "chunk_s_total", "unit": "s",
                             "better": "lower", "source": "program_span",
                             "layer": "level program", "moves": "train_job_s.gbm",
                             "workloads": ["higgs_gbm_d7_train"]})
    for e in man["end_to_end"]:            # the cell joins its metric's list
        if e["name"] == "train_job_s.gbm":
            e["workloads"].append("higgs_gbm_d7_train")
    _write(root, man)
    assert manifest.check(root) == []
    names = [m["name"] for m in manifest.metrics_of(
        man, "higgs_gbm_d7_train", "per_layer")]
    assert "chunk_s_total" in names and "gbm_setup_s" not in names
    assert manifest.traffic_of(man, "train_two_warmups", root)["warmup_jobs"] == 2


@pytest.mark.parametrize("what,breaks", [
    ("moves", lambda man: man["per_layer"][0].update(moves="train_job_s")),
    ("does not report", lambda man: man["per_layer"][0].update(
        workloads=["higgs_glm_train"])),
    ("unknown cell", lambda man: man["per_layer"][0].update(workloads=["nope"])),
    ("unit", lambda man: man["end_to_end"][0].update(unit="tokens per second")),
    ("character rules", lambda man: man["workloads"][0].update(name="a b")),
    ("traffic", lambda man: man["workloads"][0].update(traffic="no_such_mix")),
    ("reader file", lambda man: man["per_layer"][0].update(name="unheard_of")),
])
def test_check_names_what_an_addition_broke(tmp_path, what, breaks):
    root = _copy_benchmark(tmp_path)
    man = manifest.load(root)
    breaks(man)
    _write(root, man)
    assert any(what in b for b in manifest.check(root)), manifest.check(root)


# ----------------------------------------------------------- generators ---
def _cols(seed, nrow=4096):
    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(jax.devices()[0])
    return [np.asarray(c) for c in datagen.higgs_columns(seed, nrow, nrow + 256, sh)]


def test_same_seed_same_columns_other_seed_other_columns():
    a, b, c = _cols(2**31 + 5), _cols(2**31 + 5), _cols(2**31 + 6)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][:4096], c[0][:4096])
    assert not np.array_equal(a[-1][:4096], c[-1][:4096])


def test_seeds_that_differ_only_above_32_bits_differ():
    a, b = _cols(7), _cols(7 + 2**32)
    assert not np.array_equal(a[0][:4096], b[0][:4096])


def test_columns_are_higgs_shaped_and_padded_with_nan():
    cols = _cols(11, nrow=4096)
    assert len(cols) == datagen.NCOL + 1
    for c in cols:
        assert c.dtype == np.float32 and c.shape == (4096 + 256,)
        assert np.isnan(c[4096:]).all() and not np.isnan(c[:4096]).any()
    y = cols[-1][:4096]
    assert set(np.unique(y)) == {0.0, 1.0}
    # every third column carries the latent: it correlates with the label
    assert abs(np.corrcoef(cols[0][:4096], y)[0, 1]) > 0.1
    assert abs(np.corrcoef(cols[1][:4096], y)[0, 1]) < 0.08


# --------------------------------------------------------------- window ---
def _fake_clock(durations):
    """A clock that a fake job advances by its duration."""
    now = [0.0]
    it = iter(durations)

    def job(_i):
        now[0] += next(it)
        return "ok"

    return (lambda: now[0]), job


def test_window_counts_all_time_and_all_jobs():
    clock, job = _fake_clock([20.0, 20.0, 20.0])
    t0, t1, jobs = window.run_back_to_back(job, 30.0, clock)
    assert len(jobs) == 2 and (t0, t1) == (0.0, 40.0)
    assert window.train_job_s(t0, t1, 2) == 20.0


def test_a_stall_moves_train_job_s():
    clock, job = _fake_clock([20.0, 27.0, 20.0])
    t0, t1, jobs = window.run_back_to_back(job, 30.0, clock)
    assert window.train_job_s(t0, t1, len(jobs)) == 23.5


def test_no_job_starts_after_the_window_has_run_out():
    clock, job = _fake_clock([31.0, 1.0])
    _, t1, jobs = window.run_back_to_back(job, 30.0, clock)
    assert len(jobs) == 1 and t1 == 31.0


def test_train_job_s_without_a_job_raises():
    with pytest.raises(ValueError):
        window.train_job_s(0.0, 10.0, 0)


# ---------------------------------------------------------- work counts ---
def test_gbm_work_at_higgs_shapes_by_hand():
    ops, nbytes = work_counts.gbm_job(11_000_000, 28, 5, 20)
    # per level: 11e6 rows x (28 codes + 4 node id + 12 statistics) bytes
    assert nbytes == 20 * 5 * 11_000_000 * 44 == 48_400_000_000
    # one add per (row, feature, statistic)
    assert ops == 20 * 5 * 11_000_000 * 28 * 3 == 92_400_000_000


def test_glm_work_at_higgs_shapes_by_hand():
    ops, nbytes = work_counts.glm_job(11_000_000, 29, 5)
    one = 2 * 11_000_000 * 29 * 29 + 2 * 11_000_000 * 29 + 2 / 3 * 29 ** 3
    assert ops == pytest.approx(5 * one, rel=1e-12)
    assert one == pytest.approx(19_140_016_259.3, rel=1e-9)
    assert nbytes == 5 * 4 * 11_000_000 * 30 == 6_600_000_000


def test_job_work_reads_the_config_file():
    cfg = manifest.config_of(MAN, "higgs_gbm", ROOT)
    assert work_counts.job_work(cfg) == work_counts.gbm_job(11_000_000, 28, 5, 20)
    with pytest.raises(KeyError):
        work_counts.job_work(dict(cfg, algo="kmeans"))


def test_peaks_table_raises_on_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 mega")
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "cpu")


def test_least_seconds_names_the_binding_bound():
    t, bound = peaks.least_seconds(92.4e9, 48.4e9, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(48.4e9 / 819e9)
    t, bound = peaks.least_seconds(197e12, 1.0, "TPU v5 lite", chips=4)
    assert bound == "flops" and t == pytest.approx(0.25)


def test_work_share_is_a_share_of_the_window_and_none_without_work():
    obs = {"work": (92.4e9 * 2, 48.4e9 * 2), "window_s": 40.0,
           "device_kind": "TPU v5 lite", "chips": 1, "_metric": "train_mfu.gbm"}
    share = readers.work_share(obs)
    assert share == pytest.approx(100 * 2 * 48.4e9 / 819e9 / 40.0)
    assert obs["binding"] == {"train_mfu.gbm": "bytes"}
    assert readers.work_share({"window_s": 40.0}) is None


# ------------------------------------------------------ trace reduction ---
DEV = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 2.0),
                         ("while.3", 0.0, 2.0), ("copy.4", 6.0, 7.0)]}
HOST = [("train.gbm", 0.0, 10.0), ("train.gbm.chunk", 0.0, 2.5),
        ("rest.request", 7.5, 9.0)]


def test_busy_is_the_union_of_device_intervals():
    assert trace_reduce.union_seconds([(0, 1), (0.5, 2), (6, 7)]) == 3.0
    assert trace_reduce.union_seconds([]) == 0.0


def test_idle_share_and_top_operations():
    r = trace_reduce.reduce_events(DEV, HOST, 0.0, 10.0)
    assert r["busy_s"] == 3.0 and r["window_s"] == 10.0
    assert r["idle_share"] == pytest.approx(0.7)
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion.2" and "while.3" not in names


def test_gaps_go_to_the_innermost_host_span_open_in_them():
    r = trace_reduce.reduce_events(DEV, HOST, 0.0, 10.0)
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    assert gaps["train.gbm"] == pytest.approx(4.0)       # 2..6
    assert gaps["rest.request"] == pytest.approx(3.0)    # 7..10, mid 8.5


def test_the_slice_clips_events_that_cross_its_ends():
    r = trace_reduce.reduce_events(DEV, HOST, 0.5, 6.5)
    assert r["busy_s"] == pytest.approx(1.5 + 0.5)
    assert r["window_s"] == 6.0


def test_no_device_events_reads_nothing():
    assert trace_reduce.reduce_events({}, HOST, 0.0, 1.0) == {}
    assert readers.trace_idle_share({"trace": {}}) is None
    assert readers.trace_idle_share({"trace": None}) is None


def test_idle_share_reader_is_a_percentage():
    assert readers.trace_idle_share(
        {"trace": {"busy_s": 3.0, "window_s": 10.0}}) == pytest.approx(70.0)


# -------------------------------------------------------------- readers ---
SPANS = [{"what": "train.gbm", "dur_us": 20e6}, {"what": "train.gbm", "dur_us": 22e6},
         {"what": "train.gbm.chunk", "dur_us": 7e6},
         {"what": "train.gbm.chunk", "dur_us": 7e6},
         {"what": "train.gbm.chunk", "dur_us": 8e6},
         {"what": "train.gbm.chunk", "dur_us": 8e6}]
OBS = {"algo": "gbm", "spans": SPANS, "trees": 40, "njobs": 2,
       "jobs": [{"start": 0.0, "end": 20.5}, {"start": 20.5, "end": 43.0}]}


def test_span_readers():
    assert readers.client_less_span(OBS, span="train.{algo}") == pytest.approx(0.5)
    assert readers.span_less_children(
        OBS, span="train.gbm", children="train.gbm.chunk") == pytest.approx(6.0)
    assert readers.span_sum_per(
        OBS, span="train.gbm.chunk", per="trees") == pytest.approx(0.75)


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = {"algo": "glm", "spans": [], "jobs": []}
    assert readers.client_less_span(empty, span="train.{algo}") is None
    assert readers.span_less_children(empty, span="a", children="b") is None
    assert readers.span_sum_per(empty, span="a", per="trees") is None
    assert readers.trace_module_s(empty, contains="jit__core") is None
    assert readers.meter(empty, which="compiles_in_window") is None
    assert readers.memory_peak_gb(empty) is None


# --------------------------------------------------------------- runner ---
def test_the_runner_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", MAN["workloads"][0]["name"], "--seed", str(2**31 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing to measure" in p.stderr


def test_the_runner_refuses_an_unknown_cell():
    from benchmark import run

    assert run.main(["--workload", "no_such_cell", "--seed", "1",
                     "--seconds", "1"]) == 2


def test_the_runner_refuses_a_traffic_kind_it_does_not_know():
    from benchmark import run

    args = run.parse(["--workload", "higgs_gbm_train", "--seed", "1",
                      "--seconds", "1"])
    with pytest.raises(ValueError, match="closed_loop"):
        run.measure(args, MAN, MAN["workloads"][0], {}, {"kind": "closed_loop"})


# ---------------------------------------------------------------- judge ---
@pytest.mark.parametrize("compared,not_compared,want", [
    ({"a": {"value": 1e-6, "limit": 1e-5}}, (), True),
    ({"a": {"value": 2e-5, "limit": 1e-5}}, (), False),
    ({"a": {"value": float("nan"), "limit": 1e-5}}, (), False),
    ({"a": {"value": 1e-6, "limit": None}}, (), False),        # a limit forgotten
    ({"a": {"value": 1e-6, "limit": None}}, ("a",), True),     # printed only
    ({"a": {"value": 1e-6, "limit": None}, "b": {"value": 1, "limit": 0}},
     ("a",), False),
])
def test_judge_holds_every_number_to_its_limit(compared, not_compared, want):
    from benchmark import drive_train

    assert drive_train.judge(compared, not_compared) is want
