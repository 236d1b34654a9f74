"""REST route tail (VERDICT r2 Missing #2): parameter validation without
training, Word2VecSynonyms, Capabilities, and the MOJO import/upload client
verbs."""

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o

PORT = 54771


@pytest.fixture(scope="module")
def fr(worker_port):
    h2o.init(port=worker_port(PORT))
    rng = np.random.default_rng(2)
    df = pd.DataFrame({"a": rng.normal(size=200),
                       "b": rng.normal(size=200)})
    df["y"] = 3 * df.a - df.b
    return h2o.H2OFrame(df)


def _req(method, path, body=None, params=None):
    return h2o.connection().request(method, path, data=body, params=params)


def test_parameters_validation_route(fr):
    """POST /3/ModelBuilders/{algo}/parameters: messages + error_count,
    nothing trains (`ModelBuilderHandler.validate_parameters`)."""
    ok = _req("POST", "/3/ModelBuilders/gbm/parameters",
              body={"training_frame": fr.frame_id, "response_column": "y",
                    "ntrees": 5})
    assert ok["error_count"] == 0 and ok["parameters"]
    n_models = len(_req("GET", "/3/Models")["models"])
    bad = _req("POST", "/3/ModelBuilders/gbm/parameters",
               body={"training_frame": fr.frame_id,
                     "response_column": "nope"})
    assert bad["error_count"] == 1
    assert "nope" in bad["messages"][0]["message"]
    unknown = _req("POST", "/3/ModelBuilders/gbm/parameters",
                   body={"bogus": 1})
    assert unknown["error_count"] == 1
    # validation never creates a model
    assert len(_req("GET", "/3/Models")["models"]) == n_models


def test_capabilities_route(fr):
    caps = _req("GET", "/3/Capabilities")["capabilities"]
    names = {c["name"] for c in caps}
    assert {"Algos", "AutoML", "API v3"} <= names
    core = _req("GET", "/3/Capabilities/Core")["capabilities"]
    assert all(c["extension_type"] == "core" for c in core)
    api = _req("GET", "/3/Capabilities/API")["capabilities"]
    assert all(c["extension_type"] == "rest" for c in api)


def test_word2vec_synonyms_route(fr):
    rng = np.random.default_rng(5)
    topics = {"fruit": ["apple", "banana", "cherry", "grape"],
              "tech": ["cpu", "gpu", "ram", "disk"]}
    words = []
    for _ in range(500):
        t = "fruit" if rng.random() < 0.5 else "tech"
        words.extend(rng.choice(topics[t], size=6).tolist())
        words.append(None)
    from h2o_tpu.backend.kvstore import STORE
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.frame.vec import T_STR, Vec

    v = Vec(None, len(words), type=T_STR,
            host_data=np.array(words, dtype=object))
    wf = Frame(["words"], [v], key="w2v_corpus")
    STORE.put_keyed(wf)
    job = _req("POST", "/3/ModelBuilders/word2vec",
               body={"training_frame": "w2v_corpus", "vec_size": 16,
                     "epochs": 8, "min_word_freq": 5, "window_size": 3,
                     "seed": 6})
    import time
    key = job["job"]["key"]["name"]
    for _ in range(600):
        j = _req("GET", f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] == "DONE":
            break
        assert j["status"] not in ("FAILED", "CANCELLED"), j
        time.sleep(0.1)
    mid = j["dest"]["name"]
    syn = _req("GET", "/3/Word2VecSynonyms",
               params={"model": mid, "word": "apple", "count": 3})
    assert len(syn["synonyms"]) == 3 and len(syn["scores"]) == 3
    assert set(syn["synonyms"]) <= {"banana", "cherry", "grape"}
    assert all(a >= b for a, b in zip(syn["scores"], syn["scores"][1:]))


def test_import_and_upload_mojo(fr, tmp_path):
    """h2o.import_mojo (server path) and h2o.upload_mojo (client push)
    both land a scoring Generic model."""
    m = h2o.H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=2)
    m.train(x=["a", "b"], y="y", training_frame=fr)
    mojo_path = m.download_mojo(str(tmp_path))
    preds = m.predict(fr).as_data_frame()["predict"].to_numpy()

    gen = h2o.import_mojo(mojo_path)
    got = gen.predict(fr).as_data_frame()["predict"].to_numpy()
    np.testing.assert_allclose(got, preds, rtol=1e-5)

    up = h2o.upload_mojo(mojo_path)
    got2 = up.predict(fr).as_data_frame()["predict"].to_numpy()
    np.testing.assert_allclose(got2, preds, rtol=1e-5)
