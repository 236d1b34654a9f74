"""REST route tail wave C: feature interactions (xgbfi), Friedman-Popescu H,
SignificantRules, Tabulate, DCT, sqlite SQL import, SVMLight parse route,
AES decryption setup (FIPS-197/SP800-38A-validated cipher), node persistent
storage, and the server-side Assembly pipeline with Java codegen."""

import os
import sqlite3
import time

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o

PORT = 54795


def _req(method, path, body=None, params=None, **kw):
    return h2o.connection().request(method, path, data=body, params=params,
                                    **kw)


def _wait(job_key):
    for _ in range(400):
        j = _req("GET", f"/3/Jobs/{job_key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            return j
        time.sleep(0.05)
    raise TimeoutError(job_key)


@pytest.fixture(scope="module")
def setup(worker_port):
    h2o.init(port=worker_port(PORT))
    rng = np.random.default_rng(21)
    n = 600
    df = pd.DataFrame({"x1": rng.normal(size=n), "x2": rng.normal(size=n),
                       "x3": rng.normal(size=n)})
    df["y_add"] = df.x1 + df.x2
    df["y_mul"] = df.x1 * df.x2
    fr = h2o.H2OFrame(df, destination_frame="wave_c.hex")
    from h2o_tpu.api.client import H2OGradientBoostingEstimator

    kw = dict(ntrees=20, max_depth=3, seed=1, learn_rate=0.3)
    add = H2OGradientBoostingEstimator(**kw)
    add.train(x=["x1", "x2", "x3"], y="y_add", training_frame=fr)
    mul = H2OGradientBoostingEstimator(**kw)
    mul.train(x=["x1", "x2", "x3"], y="y_mul", training_frame=fr)
    return fr, add.model_id, mul.model_id


# -- feature interactions ----------------------------------------------------

def test_feature_interaction_tables(setup):
    _, _, mul_id = setup
    out = _req("POST", "/3/FeatureInteraction", body={"model_id": mul_id})
    tables = out["feature_interaction"]
    names = [t["name"] for t in tables]
    assert "Interaction Depth 0" in names
    assert "Leaf Statistics" in names
    assert any(n.startswith("Split Value Histogram") for n in names)
    depth0 = tables[names.index("Interaction Depth 0")]
    feats = depth0["data"][0]
    assert set(feats) <= {"x1", "x2", "x3"}
    # the x1*x2 model splits overwhelmingly on x1 and x2
    gains = dict(zip(feats, depth0["data"][1]))
    assert gains.get("x1", 0) > gains.get("x3", 0)
    # depth-1 pairs exist for a depth-3 interactive model
    if "Interaction Depth 1" in names:
        pairs = tables[names.index("Interaction Depth 1")]["data"][0]
        assert any("|" in p for p in pairs)


def test_feature_interaction_unsupported_model(setup):
    fr, _, _ = setup
    from h2o_tpu.api.client import H2OGeneralizedLinearEstimator

    glm = H2OGeneralizedLinearEstimator(family="gaussian")
    glm.train(x=["x1", "x2"], y="y_add", training_frame=fr)
    with pytest.raises(Exception, match="does not support"):
        _req("POST", "/3/FeatureInteraction",
             body={"model_id": glm.model_id})


# -- friedman-popescu H ------------------------------------------------------

def test_friedman_h_separates_additive_from_interactive(setup):
    fr, add_id, mul_id = setup
    h_add = _req("POST", "/3/FriedmansPopescusH",
                 body={"model_id": add_id, "frame": "wave_c.hex",
                       "variables": ["x1", "x2"]})["h"]
    h_mul = _req("POST", "/3/FriedmansPopescusH",
                 body={"model_id": mul_id, "frame": "wave_c.hex",
                       "variables": ["x1", "x2"]})["h"]
    assert h_mul is not None and h_mul > 0.3, h_mul
    # additive target: interaction share near zero (or NaN -> None)
    assert h_add is None or h_add < 0.2, h_add
    with pytest.raises(Exception, match="not present"):
        _req("POST", "/3/FriedmansPopescusH",
             body={"model_id": mul_id, "frame": "wave_c.hex",
                   "variables": ["x1", "nope"]})


# -- significant rules -------------------------------------------------------

def test_significant_rules(setup):
    fr, _, _ = setup
    out = _req("POST", "/3/ModelBuilders/rulefit",
               body={"training_frame": "wave_c.hex",
                     "response_column": "y_mul", "seed": 1,
                     "max_num_rules": 20})
    j = _wait(out["job"]["key"]["name"])
    assert j["status"] == "DONE", j
    mid = j["dest"]["name"]
    t = _req("POST", "/3/SignificantRules",
             body={"model_id": mid})["significant_rules_table"]
    assert t and t["data"] and len(t["data"][0]) > 0
    with pytest.raises(Exception, match="does not support"):
        _req("POST", "/3/SignificantRules", body={"model_id": setup[1]})


# -- tabulate ----------------------------------------------------------------

def test_tabulate(setup):
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.frame.vec import T_CAT, Vec

    # categorical with a true NA code (upload would intern None as a level)
    codes = np.array([1.0, 1.0, 0.0, 0.0, 0.0, np.nan], dtype=np.float32)
    Frame.from_dict(
        {"color": Vec.from_numpy(codes, type=T_CAT,
                                 domain=["blue", "red"]),
         "v": np.array([1.0, 2.0, 3.0, 4.0, np.nan, 6.0],
                       dtype=np.float32)},
        key="tab.hex")
    out = _req("POST", "/99/Tabulate",
               body={"dataset": "tab.hex", "predictor": "color",
                     "response": "v", "nbins_response": 4})
    ct = out["count_table"]
    total = sum(ct["data"][2])
    assert total == 6.0
    rt = out["response_table"]
    labels = rt["data"][0]
    assert "missing(NA)" in labels
    means = dict(zip(labels, rt["data"][1]))
    assert means["red"] == pytest.approx(1.5)
    assert means["blue"] == pytest.approx(3.5)  # NaN response excluded
    assert means["missing(NA)"] == pytest.approx(6.0)


# -- DCT ---------------------------------------------------------------------

def test_dct_route_roundtrip(setup):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(40, 8)).astype(np.float32)
    h2o.H2OFrame(pd.DataFrame(X, columns=[f"c{i}" for i in range(8)]),
                 destination_frame="dct.hex")
    _req("POST", "/99/DCTTransformer",
         body={"dataset": "dct.hex", "dimensions": [8, 1, 1],
               "destination_frame": "dct_f.hex"})
    _req("POST", "/99/DCTTransformer",
         body={"dataset": "dct_f.hex", "dimensions": [8, 1, 1],
               "inverse": True, "destination_frame": "dct_b.hex"})
    from h2o_tpu.backend.kvstore import STORE

    back = np.stack([STORE.get("dct_b.hex").vec(n).to_numpy()
                     for n in STORE.get("dct_b.hex").names], axis=1)
    np.testing.assert_allclose(back, X, atol=1e-4)
    # constant row concentrates into the DC coefficient
    fwd = np.stack([STORE.get("dct_f.hex").vec(n).to_numpy()
                    for n in STORE.get("dct_f.hex").names], axis=1)
    const = np.ones((1, 8), dtype=np.float32)
    h2o.H2OFrame(pd.DataFrame(const), destination_frame="dct_c.hex")
    _req("POST", "/99/DCTTransformer",
         body={"dataset": "dct_c.hex", "dimensions": [8, 1, 1],
               "destination_frame": "dct_c_f.hex"})
    cf = np.stack([STORE.get("dct_c_f.hex").vec(n).to_numpy()
                   for n in STORE.get("dct_c_f.hex").names], axis=1)[0]
    assert cf[0] == pytest.approx(np.sqrt(8.0), rel=1e-5)
    np.testing.assert_allclose(cf[1:], 0, atol=1e-5)
    with pytest.raises(Exception, match="3 dimensions"):
        _req("POST", "/99/DCTTransformer",
             body={"dataset": "dct.hex", "dimensions": [8]})
    assert fwd.shape == X.shape


def test_dct_2d(setup):
    """2-D DCT = row transform then column transform of the W×H signal."""
    from h2o_tpu.ops.dct import _dct_matrix, dct_frame

    rng = np.random.default_rng(6)
    X = rng.normal(size=(10, 12))
    got = dct_frame(X, 4, 3, 1)
    C4, C3 = _dct_matrix(4), _dct_matrix(3)
    for r in range(10):
        sig = X[r].reshape(4, 3)
        want = C4 @ sig @ C3.T
        np.testing.assert_allclose(got[r].reshape(4, 3), want, atol=1e-4)


# -- SQL import --------------------------------------------------------------

def test_import_sql_table(setup, tmp_path):
    db = str(tmp_path / "t.db")
    con = sqlite3.connect(db)
    con.execute("CREATE TABLE citibike (trip INTEGER, gender TEXT, "
                "dur REAL)")
    rows = [(i, "MF"[i % 2], float(i) * 1.5) for i in range(50)]
    con.executemany("INSERT INTO citibike VALUES (?,?,?)", rows)
    con.commit()
    con.close()
    out = _req("POST", "/99/ImportSQLTable",
               body={"connection_url": f"jdbc:sqlite:{db}",
                     "table": "citibike", "username": "", "password": ""})
    fid = out["destination_frame"]["name"]
    got = _req("GET", f"/3/Frames/{fid}/summary")["frames"][0]
    assert got["rows"] == 50
    labels = [c["label"] for c in got["columns"]]
    assert labels == ["trip", "gender", "dur"]
    gender = got["columns"][labels.index("gender")]
    assert sorted(gender["domain"]) == ["F", "M"]
    # select_query form
    out2 = _req("POST", "/99/ImportSQLTable",
                body={"connection_url": f"jdbc:sqlite:{db}",
                      "select_query": "SELECT dur FROM citibike WHERE "
                                      "trip < 10",
                      "username": "", "password": ""})
    fid2 = out2["destination_frame"]["name"]
    assert _req("GET", f"/3/Frames/{fid2}/light")["frames"][0]["rows"] == 10
    with pytest.raises(Exception, match="sqlite3 only"):
        _req("POST", "/99/ImportSQLTable",
             body={"connection_url": "jdbc:postgresql://host/db",
                   "table": "t", "username": "u", "password": "p"})


def test_hive_gate(setup):
    with pytest.raises(Exception, match="Hive"):
        _req("POST", "/3/ImportHiveTable",
             body={"table_name": "t"})


# -- svmlight route ----------------------------------------------------------

def test_parse_svmlight_route(setup, tmp_path):
    p = tmp_path / "data.txt"  # extension does NOT say svmlight
    p.write_text("1.0 1:0.5 3:2.0\n-1.0 2:1.5\n")
    out = _req("POST", "/3/ParseSVMLight",
               body={"source_frames": [str(p)],
                     "destination_frame": "svm_c.hex"})
    _wait(out["job"]["key"]["name"])
    got = _req("GET", "/3/Frames/svm_c.hex/summary")["frames"][0]
    assert got["rows"] == 2
    labels = [c["label"] for c in got["columns"]]
    assert labels[0] == "target"
    assert len(labels) == 5  # target + C0..C3


# -- decryption --------------------------------------------------------------

def test_decryption_setup_end_to_end(setup, tmp_path):
    from h2o_tpu.io.crypto import aes_encrypt

    csv = "a,b\n1,2\n3,4\n5,6\n"
    key = bytes(range(16))
    enc_path = tmp_path / "secret.csv.aes"
    # a fixed IV: under a random one the wrong key below leaves a valid
    # PKCS5 pad once in 256 runs, and the refusal does not come
    enc_path.write_bytes(aes_encrypt(csv.encode(), key, mode="CBC",
                                     iv=bytes(range(16, 32))))
    key_path = tmp_path / "aes.key"
    key_path.write_text(key.hex())
    ds = _req("POST", "/3/DecryptionSetup",
              body={"keystore_id": str(key_path), "keystore_type": "hex",
                    "cipher_spec": "AES/CBC/PKCS5Padding"})
    tool = ds["decrypt_tool_id"]["name"]
    setup_out = _req("POST", "/3/ParseSetup",
                     body={"source_frames": [str(enc_path)],
                           "decrypt_tool": tool})
    assert setup_out["column_names"] == ["a", "b"]
    out = _req("POST", "/3/Parse",
               body={"source_frames": [str(enc_path)],
                     "decrypt_tool": tool,
                     "destination_frame": "decrypted.hex"})
    _wait(out["job"]["key"]["name"])
    got = _req("GET", "/3/Frames/decrypted.hex/summary")["frames"][0]
    assert got["rows"] == 3
    assert [c["label"] for c in got["columns"]] == ["a", "b"]
    # wrong key refuses via the PKCS5 check instead of shipping garbage
    bad_key_path = tmp_path / "bad.key"
    bad_key_path.write_text(bytes(range(1, 17)).hex())
    ds2 = _req("POST", "/3/DecryptionSetup",
               body={"keystore_id": str(bad_key_path),
                     "keystore_type": "hex"})
    with pytest.raises(Exception, match="padding|500"):
        _req("POST", "/3/ParseSetup",
             body={"source_frames": [str(enc_path)],
                   "decrypt_tool": ds2["decrypt_tool_id"]["name"]})


def test_aes_nist_vectors():
    """The cipher itself, pinned to published vectors (FIPS-197 app. C,
    NIST SP 800-38A F.2.2)."""
    from h2o_tpu.io.crypto import (_decrypt_block, _key_expansion,
                                   aes_decrypt)

    key = bytes.fromhex("000102030405060708090a0b0c0d0e0f")
    ct = bytes.fromhex("69c4e0d86a7b0430d8cdb78070b4c55a")
    assert _decrypt_block(ct, _key_expansion(key)) == \
        bytes.fromhex("00112233445566778899aabbccddeeff")
    key256 = bytes(range(32))
    ct256 = bytes.fromhex("8ea2b7ca516745bfeafc49904b496089")
    assert _decrypt_block(ct256, _key_expansion(key256)) == \
        bytes.fromhex("00112233445566778899aabbccddeeff")
    k = bytes.fromhex("2b7e151628aed2a6abf7158809cf4f3c")
    iv = bytes.fromhex("000102030405060708090a0b0c0d0e0f")
    ct = bytes.fromhex("7649abac8119b246cee98e9b12e9197d"
                       "5086cb9b507219ee95db113a917678b2")
    pt = aes_decrypt(ct, k, mode="CBC", iv=iv, padding="NoPadding")
    assert pt == bytes.fromhex("6bc1bee22e409f96e93d7e117393172a"
                               "ae2d8a571e03ac9c9eb76fac45af8e51")


# -- node persistent storage -------------------------------------------------

def test_nps_family(setup, tmp_path):
    from h2o_tpu.backend.nps import NPS

    NPS.root = str(tmp_path / "nps")
    assert _req("GET", "/3/NodePersistentStorage/configured")["configured"]
    assert not _req("GET", "/3/NodePersistentStorage/categories/notebook/"
                           "exists")["exists"]
    out = _req("POST", "/3/NodePersistentStorage/notebook/flow1",
               body={"value": "{\"cells\": []}"})
    assert out["name"] == "flow1"
    assert _req("GET", "/3/NodePersistentStorage/categories/notebook/"
                       "names/flow1/exists")["exists"]
    got = _req("GET", "/3/NodePersistentStorage/notebook/flow1", raw=True)
    assert got == "{\"cells\": []}"
    entries = _req("GET", "/3/NodePersistentStorage/notebook")["entries"]
    assert entries[0]["name"] == "flow1" and entries[0]["size"] == 13
    # anonymous put gets a uuid name
    anon = _req("POST", "/3/NodePersistentStorage/notebook",
                body={"value": "x"})
    assert anon["name"] and anon["name"] != "flow1"
    _req("DELETE", "/3/NodePersistentStorage/notebook/flow1")
    assert not _req("GET", "/3/NodePersistentStorage/categories/notebook/"
                           "names/flow1/exists")["exists"]
    # path escapes are refused
    with pytest.raises(Exception, match="bad"):
        _req("GET", "/3/NodePersistentStorage/notebook/..%2Fescape")
    # a missing entry is a 404, not a 500
    with pytest.raises(Exception, match="no NPS entry"):
        _req("GET", "/3/NodePersistentStorage/notebook/absent")
    # a name ending in .tmp is a legitimate entry (temp files are
    # dot-prefixed, outside the entry namespace)
    _req("POST", "/3/NodePersistentStorage/notebook/x.tmp",
         body={"value": "keep"})
    entries = _req("GET", "/3/NodePersistentStorage/notebook")["entries"]
    assert any(e["name"] == "x.tmp" for e in entries)
    assert _req("GET", "/3/NodePersistentStorage/notebook/x.tmp",
                raw=True) == "keep"


# -- assembly ----------------------------------------------------------------

def test_assembly_fit_and_java(setup):
    df = pd.DataFrame({"Sepal": [1.0, 2.0, 3.0, 4.0],
                       "Petal": [0.5, 1.0, 1.5, 2.0],
                       "Junk": [9.0, 9.0, 9.0, 9.0]})
    h2o.H2OFrame(df, destination_frame="asm.hex")
    steps = ('["col_select__H2OColSelect__(cols_py dummy '
             "['Sepal', 'Petal'])__False__|\","
             '"cos_Sepal__H2OColOp__(cos (cols_py dummy '
             "'Sepal'))__True__|\","
             '"plus1__H2OBinaryOp__(+ (cols_py dummy '
             "'Petal') 1)__False__Petal1\"]")
    out = _req("POST", "/99/Assembly",
               body={"steps": steps, "frame": "asm.hex"})
    rid = out["result"]["name"]
    aid = out["assembly"]["name"]
    from h2o_tpu.backend.kvstore import STORE

    res = STORE.get(rid)
    assert res.names == ["Sepal", "Petal", "Petal1"]
    np.testing.assert_allclose(res.vec("Sepal").to_numpy(),
                               np.cos([1, 2, 3, 4]), atol=1e-6)
    np.testing.assert_allclose(res.vec("Petal1").to_numpy(),
                               [1.5, 2.0, 2.5, 3.0], atol=1e-6)
    java = _req("GET", f"/99/Assembly.java/{aid}/MungingPojo", raw=True)
    assert "public class MungingPojo" in java
    assert "Math.cos" in java
    assert "retainAll" in java
