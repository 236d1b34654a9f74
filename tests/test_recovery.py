"""Preemption-proof training + fault drills (the PR 5 acceptance pins).

- kill-at-EVERY-checkpoint-boundary, then `resume_training`: forest and
  predictions BIT-equal to the uninterrupted run (GBM and DRF; DL nets
  bit-equal at epoch granularity);
- atomic checkpoint writes: a crash injected BETWEEN temp-write and rename
  leaves the previous complete state resumable;
- checkpoint-restart prior replay runs in bin-code space (no stacked raw
  f32) and matches the raw path bit for bit;
- Cleaner rehydrate under injected device OOM emergency-spills and retries;
- the Python client retries connection errors and honors Retry-After from
  a LIVE flaky server (failpoint-injected 429/503 over a real socket).
"""

import os
import time

import numpy as np
import pytest

import h2o_tpu
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.drf import DRF, DRFParameters
from h2o_tpu.models.gbm import GBM, GBMParameters
from h2o_tpu.utils import failpoints as fp

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _fault_hygiene(monkeypatch):
    monkeypatch.delenv("H2O_TPU_FAILPOINTS", raising=False)
    monkeypatch.setenv("H2O_TPU_CHECKPOINT_SECS", "0")  # every boundary
    fp.reset()
    yield
    fp.reset()


_RNG = np.random.default_rng(7)
_N = 300
_COLS = {
    "x1": _RNG.normal(size=_N).astype(np.float32),
    "x2": _RNG.normal(size=_N).astype(np.float32),
    "c": _RNG.integers(0, 4, size=_N).astype(np.float32),
}
_Y = ((_COLS["x1"] - 0.4 * _COLS["x2"] + 0.3 * _COLS["c"]
       + _RNG.normal(scale=0.4, size=_N)) > 0.2).astype(np.float32)


def _frame():
    fr = Frame.from_dict({"x1": _COLS["x1"], "x2": _COLS["x2"]})
    fr.add("c", Vec.from_numpy(_COLS["c"], type=T_CAT,
                               domain=["a", "b", "c", "d"]))
    fr.add("y", Vec.from_numpy(_Y, type=T_CAT, domain=["0", "1"]))
    return fr


def _frame2():
    """A SECOND dataset, deliberately different from `_frame()` — the
    reused-recovery-dir drill must be able to tell them apart."""
    fr = Frame.from_dict({"x1": -_COLS["x1"], "x2": _COLS["x2"] + 2.0})
    fr.add("c", Vec.from_numpy(_COLS["c"], type=T_CAT,
                               domain=["a", "b", "c", "d"]))
    fr.add("y", Vec.from_numpy(1.0 - _Y, type=T_CAT, domain=["0", "1"]))
    return fr


def _forest_equal(a, b) -> bool:
    if set(a.forest) != set(b.forest):
        return False
    return all(np.array_equal(np.asarray(a.forest[k]), np.asarray(b.forest[k]))
               for k in a.forest)


def _params(cls, **kw):
    base = dict(training_frame=_frame(), response_column="y", ntrees=6,
                max_depth=3, score_tree_interval=2, seed=42)
    base.update(kw)
    return cls(**base)


# ---------------------------------------------------------------------------
# kill-resume bit parity, at every checkpoint boundary
# ---------------------------------------------------------------------------
def test_gbm_kill_resume_bit_parity_every_boundary(tmp_path):
    base = GBM(_params(GBMParameters)).train_model()
    base_pred = np.asarray(base.predict(_frame()).vec(2).data)
    n_chunks = 3  # ntrees=6 / interval=2
    for k in range(1, n_chunks + 1):
        rdir = str(tmp_path / f"gbm_k{k}")
        fp.reset()
        fp.arm("train.gbm.chunk", f"raise(preempt)@{k}")
        with pytest.raises(fp.InjectedPreemption):
            GBM(_params(GBMParameters,
                        auto_recovery_dir=rdir)).train_model()
        fp.reset()
        m = h2o_tpu.resume_training(rdir)
        assert m.ntrees == 6
        assert _forest_equal(m, base), f"forest diverged at kill point {k}"
        assert np.array_equal(
            np.asarray(m.predict(_frame()).vec(2).data), base_pred), \
            f"predictions diverged at kill point {k}"
        # the manifest now records completion — a second resume refuses
        with pytest.raises(ValueError, match="already completed"):
            h2o_tpu.resume_training(rdir)


def test_reused_recovery_dir_resumes_on_the_new_jobs_frame(tmp_path):
    """A recovery dir left behind by an abandoned job must not leak its
    frame into the next job that reuses the dir — init_for overwrites
    frame_<field>.npz unconditionally."""
    rdir = str(tmp_path / "reuse")
    # job A: killed before its first checkpoint, then abandoned
    fp.arm("train.gbm.chunk", "raise(preempt)@1")
    with pytest.raises(fp.InjectedPreemption):
        GBM(_params(GBMParameters, auto_recovery_dir=rdir)).train_model()
    fp.reset()
    # job B reuses the SAME dir with DIFFERENT training data
    base = GBM(_params(GBMParameters,
                       training_frame=_frame2())).train_model()
    fp.arm("train.gbm.chunk", "raise(preempt)@2")
    with pytest.raises(fp.InjectedPreemption):
        GBM(_params(GBMParameters, training_frame=_frame2(),
                    auto_recovery_dir=rdir)).train_model()
    fp.reset()
    m = h2o_tpu.resume_training(rdir)
    assert _forest_equal(m, base), \
        "resume trained on the abandoned job's stale frame"
    assert np.array_equal(np.asarray(m.predict(_frame2()).vec(2).data),
                          np.asarray(base.predict(_frame2()).vec(2).data))


def test_drf_kill_resume_bit_parity(tmp_path):
    base = DRF(_params(DRFParameters, ntrees=4, sample_rate=0.8)) \
        .train_model()
    base_pred = np.asarray(base.predict(_frame()).vec(2).data)
    rdir = str(tmp_path / "drf")
    fp.arm("train.gbm.chunk", "raise(preempt)@2")  # DRF rides the GBM loop
    with pytest.raises(fp.InjectedPreemption):
        DRF(_params(DRFParameters, ntrees=4, sample_rate=0.8,
                    auto_recovery_dir=rdir)).train_model()
    fp.reset()
    m = h2o_tpu.resume_training(rdir)
    assert m.ntrees == 4
    assert _forest_equal(m, base)
    assert np.array_equal(np.asarray(m.predict(_frame()).vec(2).data),
                          base_pred)
    # OOB training metrics survive the resume (state carries oob_sum/cnt)
    assert m.output.training_metrics.description == \
        base.output.training_metrics.description


def test_checkpoint_continuation_prior_survives_fresh_process(tmp_path):
    """A continuation job (params.checkpoint = prior model) killed BEFORE
    its first state write must still resume in a process whose STORE never
    saw the prior — init_for saves the prior model into the recovery dir
    and resume_training re-registers it."""
    from h2o_tpu.backend.kvstore import STORE

    prior = GBM(_params(GBMParameters, ntrees=2)).train_model()
    base = GBM(_params(GBMParameters, ntrees=6,
                       checkpoint=prior)).train_model()
    base_pred = np.asarray(base.predict(_frame()).vec(2).data)
    rdir = str(tmp_path / "cont")
    fp.arm("train.gbm.chunk", "raise(preempt)@1")  # before ANY state write
    with pytest.raises(fp.InjectedPreemption):
        GBM(_params(GBMParameters, ntrees=6, checkpoint=prior,
                    auto_recovery_dir=rdir)).train_model()
    fp.reset()
    STORE.remove(prior.key)  # simulate the fresh post-preemption process
    m = h2o_tpu.resume_training(rdir)
    assert m.ntrees == 6
    assert np.array_equal(np.asarray(m.predict(_frame()).vec(2).data),
                          base_pred)


def test_deeplearning_kill_resume_bit_parity(tmp_path):
    from h2o_tpu.models.deeplearning import (DeepLearning,
                                             DeepLearningParameters)

    def params(**kw):
        return DeepLearningParameters(
            training_frame=_frame(), response_column="y", hidden=[8],
            epochs=4, mini_batch_size=32, seed=5, **kw)

    base = DeepLearning(params()).train_model()
    rdir = str(tmp_path / "dl")
    fp.arm("train.dl.epoch", "raise(preempt)@3")
    with pytest.raises(fp.InjectedPreemption):
        DeepLearning(params(auto_recovery_dir=rdir)).train_model()
    fp.reset()
    m = h2o_tpu.resume_training(rdir)
    for lb, lm in zip(base.net, m.net):
        assert np.array_equal(np.asarray(lb["W"]), np.asarray(lm["W"]))
        assert np.array_equal(np.asarray(lb["b"]), np.asarray(lm["b"]))


def test_kill_before_first_checkpoint_resumes_from_scratch(tmp_path):
    base = GBM(_params(GBMParameters)).train_model()
    rdir = str(tmp_path / "early")
    fp.arm("train.gbm.chunk", "raise(preempt)@1")  # dies before any chunk
    with pytest.raises(fp.InjectedPreemption):
        GBM(_params(GBMParameters, auto_recovery_dir=rdir)).train_model()
    fp.reset()
    m = h2o_tpu.resume_training(rdir)  # state=None -> full replay
    assert _forest_equal(m, base)


# ---------------------------------------------------------------------------
# atomic writes: a crash mid-checkpoint must not lose the previous one
# ---------------------------------------------------------------------------
def test_crash_between_tempwrite_and_rename_keeps_previous_state(tmp_path):
    base = GBM(_params(GBMParameters)).train_model()
    rdir = str(tmp_path / "torn")
    # write sequence: init params(1) + manifest(2); ckpt1 state(3) +
    # manifest(4); ckpt2 state(5) — kill exactly in ckpt2's state write,
    # AFTER the temp bytes are durable but BEFORE the rename
    fp.arm("persist.checkpoint", "raise@5")
    with pytest.raises(fp.InjectedFault):
        GBM(_params(GBMParameters, auto_recovery_dir=rdir)).train_model()
    fp.reset()
    # the manifest still points at checkpoint 1's complete state (never a
    # torn/dangling reference), and resume lands bit-equal anyway
    from h2o_tpu.backend.persist import Recovery

    manifest = Recovery(rdir).read()
    assert manifest["checkpoints"] == 1 and not manifest["completed"]
    assert os.path.exists(os.path.join(rdir, "train_state.pkl.tmp"))
    m = h2o_tpu.resume_training(rdir)
    assert _forest_equal(m, base)


def test_recovery_state_unpickler_is_allowlisted(tmp_path):
    import pickle

    rdir = str(tmp_path / "evil")
    fp.arm("train.gbm.chunk", "raise(preempt)@2")
    with pytest.raises(fp.InjectedPreemption):
        GBM(_params(GBMParameters, auto_recovery_dir=rdir)).train_model()
    fp.reset()

    class Evil:
        def __reduce__(self):
            return (os.system, ("echo pwned",))

    with open(os.path.join(rdir, "train_state.pkl"), "wb") as f:
        pickle.dump({"algo": "gbm", "evil": Evil()}, f)
    with pytest.raises(pickle.UnpicklingError):
        h2o_tpu.resume_training(rdir)


# ---------------------------------------------------------------------------
# checkpoint-restart prior replay: bin-code space, no stacked raw f32
# ---------------------------------------------------------------------------
def test_checkpoint_restart_binned_replay_matches_raw(monkeypatch):
    from h2o_tpu.models import gbm as gbm_mod

    def continue_train():
        fr = _frame()
        prior = GBM(GBMParameters(training_frame=fr, response_column="y",
                                  ntrees=3, max_depth=3, seed=9)) \
            .train_model()
        m = GBM(GBMParameters(training_frame=fr, response_column="y",
                              ntrees=6, max_depth=3, seed=9,
                              checkpoint=prior)).train_model()
        return m, dict(gbm_mod.LAST_TRAIN_MATRIX_BYTES)

    monkeypatch.setenv("H2O_TPU_BINNED_STORE", "0")
    m_raw, mode_raw = continue_train()
    monkeypatch.delenv("H2O_TPU_BINNED_STORE")
    m_bin, mode_bin = continue_train()
    # the restart itself now trains (and replays) off the binned store
    assert mode_raw["mode"] == "stacked_f32"
    assert mode_bin["mode"] == "binned"
    assert mode_bin["binned_bytes"] < mode_raw["raw_bytes"]
    assert _forest_equal(m_raw, m_bin)
    pr = np.asarray(m_raw.predict(_frame()).vec(2).data)
    pb = np.asarray(m_bin.predict(_frame()).vec(2).data)
    assert np.array_equal(pr, pb)


def test_off_grid_prior_falls_back_to_raw_replay():
    from h2o_tpu.models.gbm import _prior_thr_codes

    fr = _frame()
    prior = GBM(GBMParameters(training_frame=fr, response_column="y",
                              ntrees=2, max_depth=3, seed=9)).train_model()
    # sabotage one numeric threshold off the bin grid: the mapper must
    # refuse (None) so build_impl's fallback re-stacks the raw matrix
    import jax.numpy as jnp

    thr = np.asarray(prior.forest["thr"]).copy()
    feat = np.asarray(prior.forest["feat"])
    node = np.argwhere(feat >= 0)[0]
    thr[tuple(node)] += 1e-3
    prior.forest["thr"] = jnp.asarray(thr)
    from h2o_tpu.models.tree.binning import compute_bin_edges_cols

    names = prior.output.names
    is_cat = np.array([fr.vec(n).is_categorical() for n in names])
    edges = compute_bin_edges_cols([fr.vec(n) for n in names], is_cat, 20,
                                   seed=9, histogram_type="AUTO")
    assert _prior_thr_codes(prior, edges) is None
    # and the end-to-end continuation still trains (via the raw fallback)
    m = GBM(GBMParameters(training_frame=fr, response_column="y", ntrees=4,
                          max_depth=3, seed=9, checkpoint=prior)) \
        .train_model()
    assert m.ntrees == 4


# ---------------------------------------------------------------------------
# Cleaner rehydrate under injected device OOM
# ---------------------------------------------------------------------------
def test_rehydrate_oom_emergency_spills_and_retries():
    from h2o_tpu.backend.memory import CLEANER

    data = np.arange(64, dtype=np.float32)
    v = Vec.from_numpy(data)
    bystander = Vec.from_numpy(np.ones(4096, dtype=np.float32))
    assert bystander._data is not None
    assert CLEANER._spill(v) > 0 and v._data is None
    spills_before = CLEANER.spills
    fp.arm("cleaner.rehydrate", "raise(oom)@1")  # first put fails, retry ok
    out = np.asarray(v.data)[:64]
    assert np.array_equal(out, data)
    # the emergency sweep spilled the (unpinned, unaliased) bystander
    assert CLEANER.spills > spills_before
    assert bystander._data is None and bystander._spill_path is not None
    assert np.array_equal(np.asarray(bystander.data)[:4096], np.ones(4096))


def test_rehydrate_persistent_oom_stays_typed():
    from h2o_tpu.backend.memory import CLEANER

    v = Vec.from_numpy(np.arange(16, dtype=np.float32))
    assert CLEANER._spill(v) > 0
    fp.arm("cleaner.rehydrate", "raise(oom)")  # every attempt fails
    with pytest.raises(fp.InjectedOOM):
        _ = v.data
    fp.reset()
    assert np.array_equal(np.asarray(v.data)[:16],
                          np.arange(16, dtype=np.float32))


def test_spill_failpoint_fires():
    from h2o_tpu.backend.memory import CLEANER

    v = Vec.from_numpy(np.arange(8, dtype=np.float32))
    fp.arm("cleaner.spill", "raise@1")
    with pytest.raises(fp.InjectedFault):
        with v._lock:
            CLEANER._spill_locked(v)
    fp.reset()
    assert v._data is not None  # the vec survived the failed spill


# ---------------------------------------------------------------------------
# client retry against a LIVE flaky server (real socket, injected 429/503)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cloud(worker_port):
    import h2o_tpu.api.client as h2o

    conn = h2o.init(port=worker_port(54671))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


def test_client_get_retries_503_honoring_retry_after(cloud, monkeypatch):
    monkeypatch.setenv("H2O_TPU_RETRY_JITTER", "0")
    fp.arm("rest.route", "http(503)*2")
    t0 = time.monotonic()
    out = cloud.request("GET", "/3/Cloud")
    elapsed = time.monotonic() - t0
    assert out["cloud_size"] >= 1
    assert fp.hits("rest.route") == 3          # 2 rejected + 1 success
    assert elapsed >= 2 * 0.05                 # slept the Retry-After twice


def test_client_connection_error_retries_and_gives_up_typed(monkeypatch):
    import h2o_tpu.api.client as h2o
    from h2o_tpu.utils.retry import RetryBudgetExceeded

    monkeypatch.setenv("H2O_TPU_RETRY_ATTEMPTS", "2")
    monkeypatch.setenv("H2O_TPU_RETRY_BASE_MS", "1")
    monkeypatch.setenv("H2O_TPU_RETRY_JITTER", "0")
    dead = h2o.H2OConnection("http://127.0.0.1:59999")
    with pytest.raises(RetryBudgetExceeded) as ei:
        dead.request("GET", "/3/Cloud")
    assert ei.value.attempts == 2
    assert isinstance(ei.value.last, h2o.H2OConnectionError)
    # POSTs never auto-retry: the same dead endpoint fails with the plain
    # connection error after ONE attempt
    with pytest.raises(h2o.H2OConnectionError):
        dead.request("POST", "/3/Shutdown")


def test_score_rows_retries_honor_retry_after(cloud, monkeypatch):
    import h2o_tpu.api.client as h2o
    from h2o_tpu.utils.retry import RetryBudgetExceeded

    fr = _frame()
    model = GBM(GBMParameters(training_frame=fr, response_column="y",
                              ntrees=3, max_depth=3, seed=4)).train_model()
    h2o.register_serving(model.key, serving_id="rec_flaky", buckets="1,8")
    try:
        row = {"x1": 0.3, "x2": -0.2, "c": "b"}
        baseline = h2o.score_rows("rec_flaky", row)  # warm, no injection
        fp.arm("rest.route", "http(429)*2")
        t0 = time.monotonic()
        out = h2o.score_rows("rec_flaky", row, retries=3)
        elapsed = time.monotonic() - t0
        assert out == baseline
        assert fp.hits("rest.route") == 3
        assert elapsed >= 2 * 0.05             # honored both Retry-After
        # default (retries=0) keeps the raw typed backpressure signal
        fp.arm("rest.route", "http(429)*1")
        with pytest.raises(h2o.H2OServingOverloadError) as ei:
            h2o.score_rows("rec_flaky", row)
        assert ei.value.retry_after_s > 0
        fp.reset()
        # a server that NEVER drains exhausts the budget, typed
        fp.arm("rest.route", "http(429)")
        with pytest.raises(RetryBudgetExceeded) as ei:
            h2o.score_rows("rec_flaky", row, retries=2)
        assert isinstance(ei.value.last, h2o.H2OServingOverloadError)
    finally:
        fp.reset()
        h2o.unregister_serving("rec_flaky")


# ---------------------------------------------------------------------------
# io.remote drill: typed retry without a network
# ---------------------------------------------------------------------------
def test_hdfs_request_retries_injected_connection_resets(monkeypatch):
    import http.server
    import threading

    class OK(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = b'{"FileStatus": {"length": 1}}'
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), OK)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        monkeypatch.setenv("H2O_TPU_RETRY_BASE_MS", "1")
        monkeypatch.setenv("H2O_TPU_RETRY_JITTER", "0")
        from h2o_tpu.io.hdfs import _request

        fp.arm("io.remote", "raise(conn)*2")
        url = f"http://127.0.0.1:{srv.server_port}/webhdfs/v1/x?op=GETFILESTATUS"
        with _request(url) as resp:
            assert b"FileStatus" in resp.read()
        assert fp.hits("io.remote") == 3
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# serving batcher fault fan-out
# ---------------------------------------------------------------------------
def test_serving_batch_injection_fans_out_typed():
    from h2o_tpu.serving import ServingRuntime

    fr = _frame()
    model = GBM(GBMParameters(training_frame=fr, response_column="y",
                              ntrees=2, max_depth=3, seed=4)).train_model()
    rt = ServingRuntime()
    rt.register_model(model, "fault_fanout", overrides={"buckets": [1, 8]})
    try:
        rows = [{"x1": 0.1, "x2": 0.2, "c": "a"}]
        ok = rt.score("fault_fanout", rows)  # warm path works
        assert len(ok) == 1
        fp.arm("serving.batch", "raise@1")
        with pytest.raises(Exception) as ei:
            rt.score("fault_fanout", rows)
        assert isinstance(ei.value, fp.InjectedFault)
        fp.reset()
        again = rt.score("fault_fanout", rows)  # the worker survived
        assert len(again) == 1
    finally:
        rt.shutdown()
