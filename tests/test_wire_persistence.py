"""Binary model save/load + file upload over the wire — the
`water/api/PostFileServlet` + ModelsHandler importModel/exportModel/
fetchBinaryModel routes and the h2o-py verbs `save_model`/`load_model`/
`download_model`/`upload_model`/`upload_file` (h2o-py/h2o/h2o.py:341,1490).

Everything here goes through HTTP only — no in-process object sharing on the
assertion paths; the load_model proof runs the loading server in a fresh
subprocess so no state can leak through the process-global DKV.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o

PORT = 54741


@pytest.fixture(scope="module")
def conn(worker_port):
    h2o.init(port=worker_port(PORT))
    yield h2o.connection()


def _df(n=300, seed=7):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "x1": rng.normal(size=n),
        "x2": rng.normal(size=n),
        "x3": rng.integers(0, 4, size=n),
        "y": rng.normal(size=n),
    })


def _train_gbm(fr):
    m = h2o.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=7)
    m.train(x=["x1", "x2", "x3"], y="y", training_frame=fr)
    return m


# ---------------------------------------------------------------------------
# upload_file
# ---------------------------------------------------------------------------
def test_upload_file_streams_local_csv(conn, tmp_path):
    df = _df()
    csv = tmp_path / "updata.csv"
    df.to_csv(csv, index=False)
    fr = h2o.upload_file(str(csv))
    assert fr.nrow == len(df) and fr.ncol == 4
    assert fr.columns == list(df.columns)
    got = fr.as_data_frame()
    np.testing.assert_allclose(got["x1"].to_numpy(), df["x1"].to_numpy(),
                               rtol=1e-6)


def test_upload_file_gzip_by_content_magic(conn, tmp_path):
    # a .gz pushed raw with no extension hint in the key: the server sniffs
    # the 1f8b magic and spools with the right suffix
    import gzip

    df = _df(80, seed=3)
    gz = tmp_path / "updata2.csv.gz"
    with gzip.open(gz, "wt") as f:
        df.to_csv(f, index=False)
    fr = h2o.upload_file(str(gz))
    assert fr.nrow == len(df)


def test_postfile_multipart_and_destination_frame(conn, tmp_path):
    # multipart/form-data push the way h2o-py's requests layer sends it
    df = _df(50, seed=5)
    payload = df.to_csv(index=False).encode()
    boundary = b"testBoundary42"
    body = (b"--" + boundary + b"\r\n"
            b'Content-Disposition: form-data; name="file"; '
            b'filename="mp.csv"\r\n'
            b"Content-Type: application/octet-stream\r\n\r\n"
            + payload + b"\r\n--" + boundary + b"--\r\n")
    req = urllib.request.Request(
        conn.url + "/3/PostFile?destination_frame=mp_upload.csv",
        data=body, method="POST",
        headers={"Content-Type":
                 "multipart/form-data; boundary=" + boundary.decode()})
    with urllib.request.urlopen(req) as resp:
        ret = json.loads(resp.read())
    assert ret["destination_frame"] == "mp_upload.csv"
    assert ret["total_bytes"] == len(payload)
    setup = conn.request("POST", "/3/ParseSetup",
                         data={"source_frames": ["mp_upload.csv"]})
    assert setup["number_columns"] == 4
    job = conn.request("POST", "/3/Parse",
                       data={"source_frames": ["mp_upload.csv"],
                             "destination_frame": "mp_parsed"})
    key = job["job"]["key"]["name"]
    import time
    for _ in range(200):
        j = conn.request("GET", f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.05)
    assert j["status"] == "DONE", j
    fr = h2o.get_frame("mp_parsed")
    assert fr.nrow == len(df)
    # delete_on_done: the raw upload key is spent after parse — a second
    # ParseSetup against it must fail (the spool file is gone from the DKV)
    with pytest.raises(h2o.H2OConnectionError):
        conn.request("POST", "/3/ParseSetup",
                     data={"source_frames": ["mp_upload.csv"]})


def test_upload_file_zip_archive(conn, tmp_path):
    # a real zip archive (PK magic, first member is the dataset) — the
    # reference reads it via ZipUtil; gzip-codec shortcuts would fail here
    import zipfile

    df = _df(60, seed=9)
    zpath = tmp_path / "arch.zip"
    with zipfile.ZipFile(zpath, "w") as zf:
        zf.writestr("inner.csv", df.to_csv(index=False))
    fr = h2o.upload_file(str(zpath))
    assert fr.nrow == len(df) and fr.ncol == 4


# ---------------------------------------------------------------------------
# save_model / load_model (server-side), download/upload (client-side)
# ---------------------------------------------------------------------------
def test_save_load_model_same_server(conn, tmp_path):
    df = _df()
    csv = tmp_path / "t.csv"
    df.to_csv(csv, index=False)
    fr = h2o.upload_file(str(csv))
    m = _train_gbm(fr)
    preds = m.predict(fr).as_data_frame()["predict"].to_numpy()

    saved = h2o.save_model(m, path=str(tmp_path), force=True)
    assert os.path.exists(saved)
    # unsaved duplicate without force → 400
    with pytest.raises(h2o.H2OConnectionError):
        h2o.save_model(m, path=str(tmp_path), force=False)

    h2o.remove(m.model_id)
    loaded = h2o.load_model(saved)
    assert loaded.model_id == m.model_id
    got = loaded.predict(fr).as_data_frame()["predict"].to_numpy()
    np.testing.assert_allclose(got, preds, rtol=1e-6)


def test_download_upload_model_roundtrip(conn, tmp_path):
    df = _df(seed=11)
    csv = tmp_path / "du.csv"
    df.to_csv(csv, index=False)
    fr = h2o.upload_file(str(csv))
    m = _train_gbm(fr)
    preds = m.predict(fr).as_data_frame()["predict"].to_numpy()

    local = h2o.download_model(m, path=str(tmp_path), filename="dl.bin")
    assert os.path.getsize(local) > 1000
    h2o.remove(m.model_id)
    up = h2o.upload_model(local)
    got = up.predict(fr).as_data_frame()["predict"].to_numpy()
    np.testing.assert_allclose(got, preds, rtol=1e-6)


def test_upload_model_rejects_pickle_gadget(conn, tmp_path):
    """Models.upload.bin is wire-facing: a crafted pickle whose __reduce__
    reaches os.system must be refused by the allowlisted unpickler, not
    executed (the reference's Iced deserializer is not exec-capable)."""
    import pickle

    marker = tmp_path / "pwned"

    class Evil:
        def __reduce__(self):
            return (os.system, (f"touch {marker}",))

    evil = tmp_path / "evil.bin"
    with open(evil, "wb") as f:
        pickle.dump({"class_module": "h2o_tpu.models.gbm",
                     "class_name": "GBM", "state": {"x": Evil()}}, f)
    with pytest.raises(h2o.H2OConnectionError, match="allowlist"):
        h2o.upload_model(str(evil))
    assert not marker.exists()
    # the same guard covers server-side load of a tampered file
    with pytest.raises(h2o.H2OConnectionError, match="allowlist"):
        h2o.load_model(str(evil))


_FRESH_SERVER = r"""
import json, sys
import h2o_tpu.api as h2o

model_path, csv_path, port = sys.argv[1], sys.argv[2], int(sys.argv[3])
h2o.init(port=port, name="fresh")
m = h2o.load_model(model_path)
fr = h2o.upload_file(csv_path)
preds = m.predict(fr).as_data_frame()["predict"].tolist()
print("PREDS::" + json.dumps(preds))
"""


def test_load_model_in_fresh_process(conn, tmp_path, worker_port):
    """train -> save_model -> FRESH server process -> load_model -> identical
    predictions, over HTTP only (the VERDICT #2 done-criterion)."""
    df = _df(seed=23)
    csv = tmp_path / "fresh.csv"
    df.to_csv(csv, index=False)
    fr = h2o.upload_file(str(csv))
    m = _train_gbm(fr)
    preds = m.predict(fr).as_data_frame()["predict"].to_numpy()
    saved = h2o.save_model(m, path=str(tmp_path), force=True)

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", _FRESH_SERVER, saved, str(csv),
         str(worker_port(PORT + 37))],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("PREDS::")][0]
    got = np.asarray(json.loads(line[len("PREDS::"):]))
    np.testing.assert_allclose(got, preds, rtol=1e-5, atol=1e-7)


def test_model_unpickler_optax_namedtuples_only():
    """The optax allowlist admits optimizer-state NamedTuples (what DL
    checkpoints actually carry) and nothing else from the package — a
    REDUCE resolving an optax callable is a code-execution gadget."""
    import io
    import pickle

    import optax
    from h2o_tpu.backend.persist import _ModelUnpickler

    state = optax.ScaleByAdamState(count=np.int32(3), mu=None, nu=None)
    out = _ModelUnpickler(io.BytesIO(pickle.dumps(state))).load()
    assert out == state

    for gadget in (optax.adam, optax.apply_updates):
        with pytest.raises(pickle.UnpicklingError, match="optax"):
            _ModelUnpickler(io.BytesIO(pickle.dumps(gadget))).load()
