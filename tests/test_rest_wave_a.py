"""REST route tail wave A (toward `RegisterV3Api.java`'s 128 routes):
cloud/misc verbs (HEAD Cloud, KillMinus3, CloudLock, UnlockKeys,
SessionProperties, SteamMetrics, /99/Sample, /99/Rapids/help), frame-detail
routes (light, FrameChunks, per-column stats/domain/summary, GET export,
Frames save/load, delete-all), Find, ImportFilesMulti, Logs per-node files,
Metadata item views."""

import http.client
import os
import time

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o

PORT = 54773


@pytest.fixture(scope="module")
def fr(worker_port):
    h2o.init(port=worker_port(PORT))
    rng = np.random.default_rng(7)
    df = pd.DataFrame({
        "num": rng.normal(size=300),
        "cat": rng.choice(["red", "green", "blue"], size=300),
        "y": rng.normal(size=300)})
    return h2o.H2OFrame(df, destination_frame="wave_a.hex")


def _req(method, path, body=None, params=None, **kw):
    return h2o.connection().request(method, path, data=body, params=params,
                                    **kw)


# -- cloud / misc verbs ------------------------------------------------------

def test_head_cloud(fr, worker_port):
    """HEAD /3/Cloud answers 200 with headers and an empty body — and a GET
    on the SAME keep-alive connection still gets its body (the handler
    instance persists across requests; the suppress-body flag must not)."""
    conn = http.client.HTTPConnection("127.0.0.1", worker_port(PORT),
                                      timeout=10)
    conn.request("HEAD", "/3/Cloud")
    resp = conn.getresponse()
    body = resp.read()
    assert resp.status == 200
    assert body == b""
    assert int(resp.headers["Content-Length"]) > 0
    conn.request("GET", "/3/Cloud")
    resp2 = conn.getresponse()
    body2 = resp2.read()
    conn.close()
    assert resp2.status == 200
    assert b"cloud_name" in body2


def test_sample_alias_is_cloud_status(fr):
    sample = _req("GET", "/99/Sample")
    cloud = _req("GET", "/3/Cloud")
    assert sample["cloud_name"] == cloud["cloud_name"]
    assert sample["cloud_size"] == 1


def test_kill_minus_3_logs_stacks(fr):
    _req("GET", "/3/KillMinus3")
    log = _req("GET", "/3/Logs")["log"]
    assert "KillMinus3 thread" in log


def test_cloud_lock(fr):
    out = _req("POST", "/3/CloudLock", body={"reason": "pinned by test"})
    assert out["reason"] == "pinned by test"
    log = _req("GET", "/3/Logs")["log"]
    assert "pinned by test" in log


def test_unlock_keys_is_accepted(fr):
    assert _req("POST", "/3/UnlockKeys") == {}


def test_session_properties_roundtrip(fr):
    _req("POST", "/3/SessionProperties",
         params={"session_key": "s1", "key": "foo", "value": "bar"})
    got = _req("GET", "/3/SessionProperties",
               params={"session_key": "s1", "key": "foo"})
    assert got["value"] == "bar"
    # a different session does not see it
    other = _req("GET", "/3/SessionProperties",
                 params={"session_key": "s2", "key": "foo"})
    assert other["value"] is None


def test_steam_metrics_idle(fr):
    out = _req("GET", "/3/SteamMetrics")
    assert out["version"] == 1
    assert out["idle_millis"] >= 0


def test_rapids_help_lists_prims(fr):
    syntax = _req("GET", "/99/Rapids/help")["syntax"]
    names = {s["name"] for s in syntax}
    assert {"+", "sort", "merge", "cbind"} <= names
    assert len(names) > 150


def test_get_init_id_issues_session(fr):
    out = _req("GET", "/3/InitID")
    assert out["session_key"].startswith("_sid_")


# -- frame detail routes -----------------------------------------------------

def test_frames_light(fr):
    out = _req("GET", "/3/Frames/wave_a.hex/light")["frames"][0]
    assert out["rows"] == 300
    assert out["column_names"] == ["num", "cat", "y"]
    assert "columns" not in out  # light = no rollups payload


def test_frame_chunks(fr):
    out = _req("GET", "/3/FrameChunks/wave_a.hex")
    assert sum(c["row_count"] for c in out["chunks"]) == 300


def test_single_column_stats(fr):
    out = _req("GET", "/3/Frames/wave_a.hex/columns/num")["frames"][0]
    assert out["num_columns"] == 3
    [col] = out["columns"]
    assert col["label"] == "num"
    assert col["missing_count"] == 0


def test_column_domain(fr):
    out = _req("GET", "/3/Frames/wave_a.hex/columns/cat/domain")
    assert sorted(out["domain"][0]) == ["blue", "green", "red"]
    assert sum(out["counts"][0]) == 300


def test_column_summary_histogram(fr):
    out = _req("GET", "/3/Frames/wave_a.hex/columns/num/summary")
    [col] = out["frames"][0]["columns"]
    assert sum(col["histogram_bins"]) == 300
    assert len(col["percentiles"]) == len(col["default_percentiles"])
    # median must sit between min and max
    med = col["percentiles"][col["default_percentiles"].index(0.5)]
    assert col["mins"][0] <= med <= col["maxs"][0]


def test_column_routes_404(fr):
    with pytest.raises(Exception, match="nope"):
        _req("GET", "/3/Frames/wave_a.hex/columns/nope")


def test_get_export_route(fr, tmp_path):
    dest = str(tmp_path / "wave_a_export.csv")
    import urllib.parse

    quoted = urllib.parse.quote(dest, safe="")
    _req("GET", f"/3/Frames/wave_a.hex/export/{quoted}/overwrite/true")
    df = pd.read_csv(dest)
    assert len(df) == 300


def test_frames_save_load_roundtrip(fr, tmp_path):
    dest = str(tmp_path / "wave_a_frame")
    out = _req("POST", "/3/Frames/wave_a.hex/save", body={"dir": dest})
    assert os.path.exists(out["dir"])
    loaded = _req("POST", "/3/Frames/load", body={"dir": out["dir"]})
    fid = loaded["frame_id"]["name"]
    got = _req("GET", f"/3/Frames/{fid}/summary")["frames"][0]
    assert got["rows"] == 300
    assert [c["label"] for c in got["columns"]] == ["num", "cat", "y"]
    _req("DELETE", f"/3/Frames/{fid}")


def test_download_dataset_bin(fr):
    csv = _req("GET", "/3/DownloadDataset.bin",
               params={"frame_id": "wave_a.hex"}, raw=True)
    assert csv.splitlines()[0] == "num,cat,y"
    assert len(csv.splitlines()) == 301


def test_import_files_multi(fr, tmp_path):
    p1 = tmp_path / "a.csv"
    p2 = tmp_path / "b.csv"
    p1.write_text("x\n1\n")
    p2.write_text("x\n2\n")
    out = _req("POST", "/3/ImportFilesMulti",
               body={"paths": [str(p1), str(p2), str(tmp_path / "nope.csv")]})
    assert out["files"] == [str(p1), str(p2)]
    assert out["fails"] == [str(tmp_path / "nope.csv")]


# -- find --------------------------------------------------------------------

def test_find_numeric(fr):
    from h2o_tpu.backend.kvstore import STORE

    f2 = h2o.H2OFrame(pd.DataFrame({"v": [5.0, 1.0, 5.0, 2.0, 5.0]}),
                      destination_frame="find.hex")
    out = _req("GET", "/3/Find",
               params={"key": "find.hex", "column": "v", "row": 1,
                       "match": "5"})
    assert out["prev"] == 0 and out["next"] == 2
    # categorical match by level name
    out2 = _req("GET", "/3/Find",
                params={"key": "wave_a.hex", "column": "cat", "row": 0,
                        "match": "green"})
    assert out2["next"] >= 0
    STORE.remove("find.hex")


def test_find_missing_level_404(fr):
    with pytest.raises(Exception, match="not found"):
        _req("GET", "/3/Find",
             params={"key": "wave_a.hex", "column": "cat", "row": 0,
                     "match": "purple"})


# -- logs / metadata ---------------------------------------------------------

def test_logs_per_node_file(fr):
    h2o.log_and_echo("wave-a marker line")
    out = _req("GET", "/3/Logs/nodes/0/files/info")
    assert out["nodeidx"] == 0
    assert "wave-a marker line" in out["log"]
    err = _req("GET", "/3/Logs/nodes/0/files/error")
    assert "wave-a marker line" not in err["log"]


def test_metadata_item_views(fr):
    one = _req("GET", "/3/Metadata/endpoints/3")["routes"]
    assert len(one) == 1
    byname = _req("GET", "/3/Metadata/endpoints/Frames")["routes"]
    assert all("Frames" in r["url_pattern"] for r in byname)
    sch = _req("GET", "/3/Metadata/schemas/CloudV3")["schemas"]
    assert sch == [{"name": "CloudV3", "version": 3}]
    with pytest.raises(Exception, match="unknown schema"):
        _req("GET", "/3/Metadata/schemas/BogusV9")
    cls = _req("GET", "/3/Metadata/schemaclasses/CloudV3")["schemas"]
    assert cls[0]["name"] == "CloudV3"


# -- delete-all --------------------------------------------------------------

def test_delete_all_models_and_frames():
    """Runs last: DELETE /3/Models then DELETE /3/Frames clear the store."""
    df = pd.DataFrame({"x": np.arange(50.0),
                       "y": np.arange(50.0) * 2})
    h2o.H2OFrame(df, destination_frame="del_all.hex")
    from h2o_tpu.api.client import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(ntrees=2, max_depth=2)
    est.train(x=["x"], y="y", training_frame=h2o.get_frame("del_all.hex"))
    assert _req("GET", "/3/Models")["models"]
    _req("DELETE", "/3/Models")
    assert _req("GET", "/3/Models")["models"] == []
    assert _req("GET", "/3/Frames")["frames"]
    _req("DELETE", "/3/Frames")
    assert _req("GET", "/3/Frames")["frames"] == []
