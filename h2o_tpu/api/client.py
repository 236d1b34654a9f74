"""h2o-py-compatible client — the L10 surface (`h2o-py/h2o/h2o.py`,
`frame.py`, `estimators/`) speaking this package's REST API.

Mirrors the reference's client architecture: a connection object wrapping the
versioned JSON endpoints (`h2o-py/h2o/backend/connection.py:249,431`), module
functions (``init/connect/import_file/get_frame/remove/rapids/shutdown``), an
``H2OFrame`` handle whose operations compile to Rapids expressions posted to
`/99/Rapids` lazily (`h2o-py/h2o/expr.py:27-44` ExprNode DAG: frame ops hold
a pending expression, nested ops fuse, and one `(tmp= ...)` materializes on
first identity/data access), and estimator classes over
`/3/ModelBuilders/{algo}` (`h2o-py/h2o/estimators/`).

``init()`` with no running server boots an in-process `H2OServer` — the analog
of h2o.init() spawning a local JVM (`h2o-py/h2o/h2o.py:287`).
"""

from __future__ import annotations

import http.client
import json
import os
import threading
import time
import urllib.parse
import uuid

from ..utils.retry import RetryBudgetExceeded, retry_after_verdict

_conn = None


class H2OConnectionError(Exception):
    """REST-level failure. Carries ``status`` (HTTP code), ``headers`` and
    the parsed error ``payload`` when the server replied at all — typed
    helpers (serving's 429/408 mapping) key off those."""

    status: int | None = None
    headers: dict | None = None
    payload: dict | None = None


class H2OServingOverloadError(H2OConnectionError):
    """`POST /3/Serving/score` hit a full queue (HTTP 429): back off for
    ``retry_after_s`` (the server's Retry-After drain estimate)."""

    retry_after_s: float = 0.0


class H2OServingTimeoutError(H2OConnectionError):
    """`POST /3/Serving/score` missed its deadline while queued (408)."""


class H2ORetriesExhaustedError(H2OConnectionError, RetryBudgetExceeded):
    """The client's automatic transient retry gave up. Dual-typed on
    purpose: still an ``H2OConnectionError`` (every existing handler —
    e.g. ``remove()``'s frames-vs-models fallback — keeps working) AND a
    ``RetryBudgetExceeded`` (attempts/elapsed/cause attached). The
    transport fields mirror the FINAL underlying error."""

    def __init__(self, description: str, attempts: int, elapsed_s: float,
                 last: BaseException):
        RetryBudgetExceeded.__init__(self, description, attempts,
                                     elapsed_s, last)
        self.status = getattr(last, "status", None)
        self.headers = getattr(last, "headers", None)
        self.payload = getattr(last, "payload", None)
        self.no_server = getattr(last, "no_server", False)


class H2OConnection:
    """REST transport — `h2o-py/h2o/backend/connection.py` analog.

    The wire is POOLED: one persistent `http.client.HTTPConnection` per
    CLIENT THREAD (the server is HTTP/1.1 keep-alive; re-dialing TCP +
    rebuilding a urllib opener per request capped the wire at ~450 req/s
    while the batcher behind it does 37×). A stale pooled socket — server
    restarted, idle timeout, half-closed keep-alive — redials ONCE
    transparently when the request body is replayable; everything else
    keeps the pre-pool semantics: typed errors, Retry-After retries,
    streamed uploads/downloads. ``H2O_TPU_CLIENT_KEEPALIVE=0`` reverts to
    one connection per request (the serving_wire bench baseline)."""

    def __init__(self, url: str, username: str | None = None,
                 password: str | None = None,
                 verify_ssl_certificates: bool = True,
                 cacert: str | None = None):
        self.url = url.rstrip("/")
        self.session_id: str | None = None
        self.requests_count = 0  # h2o-py connection counter (lazy-op tests)
        self.connected = True
        self._pool = threading.local()  # .conn: this thread's keep-alive
        self._auth = None
        self._ssl_ctx = None
        if url.startswith("https"):
            import ssl

            self._ssl_ctx = ssl.create_default_context(cafile=cacert)
            if not verify_ssl_certificates:
                self._ssl_ctx.check_hostname = False
                self._ssl_ctx.verify_mode = ssl.CERT_NONE
        if username is not None:
            import base64

            self._auth = "Basic " + base64.b64encode(
                f"{username}:{password or ''}".encode()).decode()

    def request(self, method: str, path: str, data: dict | None = None,
                params: dict | None = None, raw: bool = False,
                filename: str | None = None,
                save_to: str | None = None,
                retry: bool | None = None) -> dict | str:
        """``raw=True`` returns the response body as text (non-JSON
        endpoints like DownloadDataset) through the same auth/SSL path.
        ``filename`` streams that local file as the request body (the h2o-py
        connection's file-upload mode — http.client reads file objects in
        8KB blocks, so large pushes never materialize in memory).
        ``save_to`` streams a binary response body to that local path and
        returns the path (the h2o-py save_to download mode).

        Transient-failure policy (`utils/retry.py`): idempotent methods
        (GET/HEAD/DELETE, no upload body) retry connection-level failures
        and 429/503 — honoring a server Retry-After — with jittered
        backoff, giving up with the typed ``RetryBudgetExceeded``.
        Non-idempotent requests never retry automatically (a replayed POST
        could double-train a model); ``retry`` overrides either way."""
        pathq = path
        if params:
            pathq += "?" + urllib.parse.urlencode(params)
        headers = {}
        if self._auth:
            headers["Authorization"] = self._auth
        if filename is not None:
            headers["Content-Type"] = "application/octet-stream"
        elif data is not None:
            headers["Content-Type"] = "application/json"

        def _attempt():
            # the body is built PER ATTEMPT: a retried upload must stream a
            # fresh file handle, never re-send the consumed one (which
            # http.client would transmit as an empty body)
            body = None
            if filename is not None:
                body = open(filename, "rb")
                headers["Content-Length"] = str(os.path.getsize(filename))
            elif data is not None:
                body = json.dumps(data).encode()
            try:
                return self._send(method, pathq, body, headers, raw,
                                  save_to)
            finally:
                if filename is not None and body is not None:
                    body.close()

        self.requests_count += 1
        if retry is None:
            retry = method in ("GET", "HEAD", "DELETE") and filename is None
        if not retry:
            return _attempt()
        from ..utils.retry import retry_call

        try:
            return retry_call(_attempt, retryable=_transient_rest,
                              description=f"{method} {path}")
        except RetryBudgetExceeded as e:
            raise H2ORetriesExhaustedError(
                f"{method} {path}", e.attempts, e.elapsed_s,
                e.last) from e.last

    # -- pooled transport ---------------------------------------------------
    def _new_conn(self) -> http.client.HTTPConnection:
        u = urllib.parse.urlsplit(self.url)
        if u.scheme == "https":
            return http.client.HTTPSConnection(
                u.hostname, u.port, timeout=600, context=self._ssl_ctx)
        return http.client.HTTPConnection(u.hostname, u.port, timeout=600)

    @staticmethod
    def _rewind(body) -> bool:
        """True when ``body`` can be re-sent on a redial (None/bytes
        always; a file only if it seeks back to 0)."""
        if body is None or isinstance(body, (bytes, bytearray)):
            return True
        try:
            body.seek(0)
            return True
        except (AttributeError, OSError):
            return False

    def _send(self, method: str, pathq: str, body, headers: dict,
              raw: bool, save_to: str | None):
        from ..utils import failpoints, knobs, telemetry

        failpoints.hit("client.request")
        keepalive = knobs.get_bool("H2O_TPU_CLIENT_KEEPALIVE")
        conn = getattr(self._pool, "conn", None) if keepalive else None
        pooled = conn is not None
        hdrs = dict(headers)
        # wire trace propagation: a request issued inside an open span
        # carries its W3C-style traceparent so the server roots the
        # request span under THIS caller's trace id — client→REST→job→
        # chunk spans merge into one Perfetto session across processes.
        # Outside any span no header is sent (no trace to continue).
        tp = telemetry.current_traceparent()
        if tp is not None:
            hdrs.setdefault("traceparent", tp)
        # tenant attribution: the H2O_TPU_TENANT knob stamps every request
        # so the server-side workload manager books admission, fair-share
        # tickets and per-tenant metrics against this client's tenant.
        tenant = knobs.get_str("H2O_TPU_TENANT")
        if tenant:
            hdrs.setdefault("X-H2O-TPU-Tenant", tenant)
        if not keepalive:
            hdrs["Connection"] = "close"
        try:
            if conn is None:
                conn = self._new_conn()
            try:
                conn.request(method, pathq, body=body, headers=hdrs)
                resp = conn.getresponse()
            except (http.client.HTTPException, OSError):
                # a POOLED socket gone stale (server restart, keep-alive
                # timeout, half-close — RemoteDisconnected, resets, EBADF)
                # redials ONCE on a fresh connection — transparent
                # reconnect, not a retry-policy attempt; a FRESH
                # connection failing is a real transport error
                conn.close()
                if keepalive:
                    self._pool.conn = None
                if not pooled or not self._rewind(body):
                    raise
                pooled = False
                conn = self._new_conn()
                conn.request(method, pathq, body=body, headers=hdrs)
                resp = conn.getresponse()
        except (http.client.HTTPException, OSError) as e:
            try:
                conn.close()
            except Exception:   # noqa: BLE001 — already broken
                pass
            if keepalive:
                self._pool.conn = None
            err = H2OConnectionError(f"no H2O server at {self.url}: {e}")
            err.no_server = True  # distinguishes "nothing listening" from
            raise err             # HTTP-level failures like 401
        try:
            return self._read_response(resp, raw, save_to)
        finally:
            # the body was fully read either way — the socket is clean for
            # the next request on this thread
            if keepalive:
                self._pool.conn = conn
            else:
                conn.close()

    def _read_response(self, resp, raw: bool, save_to: str | None):
        status = resp.status
        rheaders = {k: v for k, v in resp.getheaders()}
        if status >= 400:
            body = resp.read().decode(errors="replace")
            payload = None
            msg = f"HTTP Error {status}: {resp.reason}"
            try:
                payload = json.loads(body)
                if isinstance(payload, dict):
                    msg = payload.get("msg", msg)
            except ValueError:
                pass
            err = H2OConnectionError(msg)
            err.status = status
            err.headers = rheaders
            err.payload = payload if isinstance(payload, dict) else None
            raise err
        if save_to is not None:
            with open(save_to, "wb") as out:
                while True:
                    chunk = resp.read(1 << 20)
                    if not chunk:
                        break
                    out.write(chunk)
            return save_to
        text = resp.read().decode()
        return text if raw else json.loads(text)

    # session for rapids temp management
    def session(self) -> str:
        if self.session_id is None:
            self.session_id = self.request("POST", "/3/InitID")["session_key"]
        return self.session_id


def _transient_rest(e: BaseException):
    """Retry classifier for the REST transport: connection-level failures
    back off exponentially; 429/503 honor the server's Retry-After when it
    sent one (returning the float delegates the delay to retry_call)."""
    if isinstance(e, ConnectionError):
        return True  # failpoint-injected / OS-level resets before the wire
    if not isinstance(e, H2OConnectionError):
        return False
    if getattr(e, "no_server", False):
        return True
    if e.status in (429, 503):
        return retry_after_verdict((e.headers or {}).get("Retry-After"))
    return False


def connection() -> H2OConnection:
    if _conn is None:
        raise H2OConnectionError("not connected; call h2o.init() first")
    return _conn


# ---------------------------------------------------------------------------
# module surface (`h2o-py/h2o/h2o.py`)
# ---------------------------------------------------------------------------
def init(url: str | None = None, port: int = 54321, name: str = "h2o_tpu",
         strict_version_check: bool = False, username: str | None = None,
         password: str | None = None, hash_login: dict | str | None = None,
         verify_ssl_certificates: bool = True, cacert: str | None = None,
         **kw):
    """Connect to a running server, else boot one in-process
    (`h2o-py/h2o/h2o.py:137` connect-or-spawn). `username`/`password` send
    basic auth; `hash_login` configures it on a freshly booted server;
    `verify_ssl_certificates`/`cacert` control https trust."""
    global _conn
    if url is None:
        url = f"http://127.0.0.1:{port}"
    try:
        _conn = H2OConnection(url, username, password,
                              verify_ssl_certificates, cacert)
        # probe without retry: "nothing listening" here means "boot one
        # in-process", and that fallback must stay instant
        _conn.request("GET", "/3/Cloud", retry=False)
        return _conn
    except H2OConnectionError as e:
        if not getattr(e, "no_server", False):
            # a server IS listening but refused us (401, 5xx…) — surface it
            # rather than silently booting a fresh empty cluster beside it
            raise
    from ..utils import compile_cache
    from .server import H2OServer

    # boot-an-in-process-server path: this process will compile — place
    # the persistent XLA compile cache with the cloud (idempotent;
    # deploy_entry's server mode does the same)
    compile_cache.ensure()
    server = H2OServer(port=port, name=name, hash_login=hash_login).start()
    _conn = H2OConnection(server.url, username, password,
                          verify_ssl_certificates, cacert)
    _conn._server = server  # keep alive / allow shutdown
    cluster_status()
    return _conn


def connect(url: str, username: str | None = None,
            password: str | None = None,
            verify_ssl_certificates: bool = True, cacert: str | None = None,
            **kw):
    global _conn
    _conn = H2OConnection(url, username, password,
                          verify_ssl_certificates, cacert)
    _conn.request("GET", "/3/Cloud")
    return _conn


def cluster_status() -> dict:
    return connection().request("GET", "/3/Cloud")


def shutdown(prompt: bool = False):
    global _conn
    connection().request("POST", "/3/Shutdown")
    _conn = None


def _poll_job(job_json: dict) -> dict:
    key = job_json["job"]["key"]["name"]
    while True:
        j = connection().request("GET", f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] == "DONE":
            return j
        if j["status"] == "FAILED":
            raise RuntimeError(f"job failed: {j.get('exception')}\n"
                               f"{j.get('stacktrace', '')}")
        if j["status"] == "CANCELLED":
            raise RuntimeError(f"job {key} was cancelled")
        time.sleep(0.05)


def import_file(path: str, destination_frame: str | None = None) -> "H2OFrame":
    """`h2o.import_file` — ImportFiles → ParseSetup → Parse → poll job."""
    c = connection()
    imp = c.request("GET", "/3/ImportFiles", params={"path": path})
    if imp["fails"]:
        raise FileNotFoundError(f"import failed for {imp['fails']}")
    setup = c.request("POST", "/3/ParseSetup",
                      data={"source_frames": imp["files"]})
    dest = destination_frame or setup["destination_frame"]
    job = c.request("POST", "/3/Parse",
                    data={"source_frames": imp["files"],
                          "destination_frame": dest})
    done = _poll_job(job)
    return H2OFrame._by_id(done["dest"]["name"])


def _python_obj_to_pandas(obj, column_names=None, header=0):
    """h2o-py `H2OFrame(python_obj)` conversion semantics (`h2o-py/h2o/
    frame.py` _upload_python_object): a flat list/tuple is ONE column; a
    list/tuple of lists/tuples is a list of ROWS (jagged rows NA-pad to the
    widest); a dict maps names -> columns (jagged columns NA-pad); numpy
    1-D is one column, 2-D is rows; pandas passes through. Unnamed columns
    become C1..Cn."""
    import numpy as _np
    import pandas as pd

    if isinstance(obj, pd.DataFrame):
        df = obj.copy()
    elif isinstance(obj, dict):
        cols = {k: list(v) if hasattr(v, "__iter__")
                and not isinstance(v, str) else [v]
                for k, v in obj.items()}
        depth = max(len(v) for v in cols.values())
        cols = {k: v + [_np.nan] * (depth - len(v))
                for k, v in cols.items()}
        df = pd.DataFrame(cols)
    else:
        if isinstance(obj, _np.ndarray):
            rows = obj.tolist()
        elif isinstance(obj, (list, tuple)):
            rows = list(obj)
        else:
            rows = [obj]  # single scalar
        if rows and not any(isinstance(r, (list, tuple)) for r in rows):
            rows = [[r] for r in rows]  # flat sequence = one column
        if header == 1 and rows and column_names is None:
            # h2o-py header=1: the first row IS the column names
            column_names = [str(c) for c in rows[0]]
            rows = rows[1:]
        width = max((len(r) for r in rows), default=0)
        rows = [list(r) + [_np.nan] * (width - len(r)) for r in rows]
        names = list(column_names) if column_names else \
            [f"C{i+1}" for i in range(width)]
        df = pd.DataFrame(rows, columns=names)
    if column_names:
        df.columns = list(column_names)
    elif not isinstance(obj, dict):
        # positional pandas columns (0, 1, ...) get h2o's C1..Cn names
        df.columns = [f"C{i+1}" if isinstance(c, int) else str(c)
                      for i, c in enumerate(df.columns)]
    for c in df.columns:  # h2o uploads booleans as 0/1 numerics
        if df[c].dtype == bool:
            df[c] = df[c].astype(int)
    return df


def upload_frame(python_obj, destination_frame: str | None = None,
                 column_names=None, column_types=None,
                 na_strings=None, header: int = 0) -> "H2OFrame":
    """Build a frame from a python object via a CSV push through
    `POST /3/PostFile` — the h2o.H2OFrame(python_obj) upload path."""
    import os
    import tempfile

    df = _python_obj_to_pandas(python_obj, column_names=column_names,
                               header=header)
    fd, tmp = tempfile.mkstemp(suffix=".csv")
    os.close(fd)
    try:
        # QUOTE_NONNUMERIC like h2o-py's uploader: strings (incl. empty
        # ones) ride quoted so a lone "" row isn't dropped as a blank line;
        # the parser maps quoted "" to NA for numerics but keeps it as the
        # empty string for string/enum columns unless na_strings says so
        import csv as _csv

        df.to_csv(tmp, index=False, quoting=_csv.QUOTE_NONNUMERIC)
        parse_kw = {"column_names": list(df.columns), "check_header": 1}
        if column_types is not None:
            if isinstance(column_types, dict):
                parse_kw["column_types"] = {str(k): v for k, v
                                            in column_types.items()}
            else:
                parse_kw["column_types"] = list(column_types)
        if na_strings is not None:
            parse_kw["na_strings"] = list(na_strings)
        return upload_file(tmp, destination_frame=destination_frame,
                           **parse_kw)
    finally:
        os.unlink(tmp)


def upload_file(path: str, destination_frame: str | None = None,
                **parse_kw) -> "H2OFrame":
    """`h2o.upload_file` (`h2o-py/h2o/h2o.py:341`): push a LOCAL file to the
    server — `POST /3/PostFile` → ParseSetup → Parse on the raw upload key.
    The push streams in 8KB blocks; nothing loads into client memory."""
    c = connection()
    if path.startswith("~"):
        path = os.path.expanduser(path)
    ret = c.request("POST", "/3/PostFile",
                    params={"filename": os.path.basename(path)},
                    filename=path)
    rawkey = ret["destination_frame"]
    setup = c.request("POST", "/3/ParseSetup",
                      data={"source_frames": [rawkey]})
    dest = destination_frame or setup["destination_frame"]
    job = c.request("POST", "/3/Parse",
                    data={"source_frames": [rawkey],
                          "destination_frame": dest, **parse_kw})
    done = _poll_job(job)
    return H2OFrame._by_id(done["dest"]["name"])


def _model_id_of(model) -> str:
    return model if isinstance(model, str) else model.model_id


def save_model(model, path: str = "", force: bool = False,
               filename: str | None = None) -> str:
    """`h2o.save_model` (`h2o-py/h2o/h2o.py:1490`): the SERVER saves the
    binary model to ``path`` — `GET /99/Models.bin/{id}?dir=...`."""
    mid = _model_id_of(model)
    filename = filename or mid
    full = os.path.join(os.getcwd() if path == "" else path, filename)
    return connection().request(
        "GET", f"/99/Models.bin/{urllib.parse.quote(mid)}",
        params={"dir": full, "force": str(bool(force)).lower()})["dir"]


def load_model(path: str) -> "H2OModelClient":
    """`h2o.load_model` (`h2o-py/h2o/h2o.py:1578`): the SERVER loads a
    binary model from ``path`` — `POST /99/Models.bin`."""
    res = connection().request("POST", "/99/Models.bin",
                               data={"dir": path})
    return get_model(res["models"][0]["model_id"]["name"])


def download_model(model, path: str = "",
                   filename: str | None = None) -> str:
    """`h2o.download_model` (`h2o-py/h2o/h2o.py:1527`): stream the binary
    model to the CLIENT machine — `GET /3/Models.fetch.bin/{id}`."""
    mid = _model_id_of(model)
    filename = filename or mid
    full = os.path.join(os.getcwd() if path == "" else path, filename)
    return connection().request(
        "GET", f"/3/Models.fetch.bin/{urllib.parse.quote(mid)}",
        save_to=full)


def upload_custom_metric(func, func_file: str = "metrics.py",
                         func_name: str | None = None,
                         class_name: str | None = None) -> str:
    """`h2o.upload_custom_metric` (`h2o-py/h2o/h2o.py:2165`): push a metric
    class (a class object or its source string) to the server as a zipped
    module via PostFile; returns the ``python:{key}={module}.{Class}``
    reference any model's ``custom_metric_func`` can name. The class must
    implement ``map(pred, act, w, o, model)``, ``reduce(l, r)`` and
    ``metric(l)`` — the CMetricFunc triple."""
    import inspect
    import tempfile
    import zipfile

    if not func_file.endswith(".py"):
        raise ValueError("func_file must end with .py")
    module = func_file[:-3]
    if isinstance(func, str):
        if class_name is None:
            raise ValueError("class_name is required when func is a source "
                             "string")
        code = func
        derived = f"metrics_{class_name}"
        path = f"{module}.{class_name}"
    else:
        if not inspect.isclass(func):
            raise TypeError("func must be a class or a source string")
        for method in ("map", "reduce", "metric"):
            if method not in func.__dict__:
                raise ValueError(f"the class must define `{method}`")
        import textwrap

        code = textwrap.dedent(inspect.getsource(func))
        derived = f"metrics_{func.__name__}"
        path = f"{module}.{func.__name__}"
    key = func_name or derived
    fd, tmp = tempfile.mkstemp(suffix=".zip")
    os.close(fd)
    try:
        with zipfile.ZipFile(tmp, "w") as zf:
            zf.writestr(func_file, code)
        connection().request("POST", "/3/PostFile",
                             params={"destination_frame": key,
                                     "filename": f"{key}.zip"},
                             filename=tmp)
    finally:
        os.unlink(tmp)
    return f"python:{key}={path}"


def import_mojo(path: str) -> "H2OModelClient":
    """`h2o.import_mojo`: load a SERVER-side MOJO zip as a Generic model
    (`hex/generic/Generic` — first-class import)."""
    est = H2OGenericEstimator(path=path)
    est.train(training_frame=None)
    return est._model


def upload_mojo(path: str) -> "H2OModelClient":
    """`h2o.upload_mojo` (`h2o-py/h2o/h2o.py:2375`): push a CLIENT-side
    MOJO through PostFile, then import it as a Generic model."""
    c = connection()
    resp = c.request("POST", "/3/PostFile",
                     params={"filename": os.path.basename(path)},
                     filename=path)
    key = resp["destination_frame"]
    # the upload key resolves to its spool path server-side via Parse's
    # upload seam; Generic takes a filesystem path, so ask the server
    # where the bytes landed through the ImportFiles echo
    est = H2OGenericEstimator(path=key)
    est.train(training_frame=None)
    return est._model


def upload_model(path: str) -> "H2OModelClient":
    """`h2o.upload_model` (`h2o-py/h2o/h2o.py:1563`): push a CLIENT-side
    binary model to the server — PostFile.bin then Models.upload.bin."""
    c = connection()
    response = c.request("POST", "/3/PostFile.bin", filename=path)
    frame_key = response["destination_frame"]
    res = c.request("POST", "/99/Models.upload.bin",
                    data={"dir": frame_key})
    return get_model(res["models"][0]["model_id"]["name"])


def ls() -> list[str]:
    frames = connection().request("GET", "/3/Frames")["frames"]
    return [f["frame_id"]["name"] for f in frames]


def get_frame(frame_id: str) -> "H2OFrame":
    return H2OFrame._by_id(frame_id)


def get_model(model_id: str) -> "H2OModelClient":
    j = connection().request("GET", f"/3/Models/{urllib.parse.quote(model_id)}")
    return H2OModelClient(model_id, j["models"][0])


def remove(key):
    """`h2o.remove`: accepts a key string, an H2OFrame/model handle, or a
    list of either (h2o-py signature)."""
    if isinstance(key, (list, tuple)):
        for k in key:
            remove(k)
        return
    if not isinstance(key, str):
        key = getattr(key, "frame_id", None) or getattr(key, "model_id", key)
    c = connection()
    try:
        c.request("DELETE", f"/3/Frames/{urllib.parse.quote(key)}")
    except H2OConnectionError:
        c.request("DELETE", f"/3/Models/{urllib.parse.quote(key)}")


def as_list(frame: "H2OFrame", use_pandas: bool = True, header: bool = True):
    """`h2o.as_list` (`h2o-py/h2o/h2o.py`)."""
    return frame.as_data_frame(use_pandas=use_pandas, header=header)


def remove_all():
    """`h2o.remove_all` — `DELETE /3/DKV` (RemoveAllHandler)."""
    connection().request("DELETE", "/3/DKV")


def create_frame(rows: int = 10000, cols: int = 10, seed: int = -1,
                 real_fraction: float | None = None,
                 categorical_fraction: float | None = None,
                 integer_fraction: float | None = None,
                 binary_fraction: float | None = None,
                 time_fraction: float | None = None,
                 string_fraction: float | None = None,
                 missing_fraction: float = 0.0, factors: int = 100,
                 has_response: bool = False, response_factors: int = 2,
                 frame_id: str | None = None, **kw) -> "H2OFrame":
    """`h2o.create_frame` — `POST /3/CreateFrame` (CreateFrameHandler).

    Unset fractions share the remainder by the reference client's weights
    (real .5, cat .2, int .2, bin .1, time/string 0 — `h2o.py:1807-1837`),
    so `string_fraction=1.0` yields a pure string frame like h2o-py."""
    frcs = [real_fraction, categorical_fraction, integer_fraction,
            binary_fraction, time_fraction, string_fraction]
    wgts = [0.5, 0.2, 0.2, 0.1, 0.0, 0.0]
    explicit = sum(0 if f is None else f for f in frcs)
    if explicit >= 1 + 1e-10:
        raise ValueError("column-type fractions must add up to <= 1")
    if explicit < 1 - 1e-10:
        remainder = 1 - explicit
        sum_w = sum(wgts[i] for i in range(6) if frcs[i] is None)
        for i in range(6):
            if frcs[i] is not None:
                continue
            frcs[i] = remainder if sum_w == 0 else \
                remainder * wgts[i] / sum_w
            remainder -= frcs[i]
            sum_w -= wgts[i]
    frcs = [0.0 if f is None else f for f in frcs]
    body = dict(rows=rows, cols=cols, seed=seed,
                real_fraction=frcs[0],
                categorical_fraction=frcs[1],
                integer_fraction=frcs[2],
                binary_fraction=frcs[3],
                time_fraction=frcs[4],
                string_fraction=frcs[5],
                missing_fraction=missing_fraction, factors=factors,
                has_response=str(bool(has_response)).lower(),
                response_factors=response_factors, **kw)
    # always send a unique dest (h2o-py sends py_tmp_key when unset) — two
    # back-to-back create_frame calls must not overwrite each other under
    # the server's shared default key
    body["dest"] = frame_id or f"py_createframe_{uuid.uuid4().hex[:12]}"
    j = connection().request("POST", "/3/CreateFrame", data=body)
    return H2OFrame._by_id(j["key"]["name"])


def split_frame_rest(frame: "H2OFrame", ratios=(0.75,), seed: int = -1,
                     destination_frames=None) -> list["H2OFrame"]:
    """Server-side split — `POST /3/SplitFrame` (SplitFrameHandler); the
    H2OFrame.split_frame method is the rapids path, this is the REST one."""
    body = {"dataset": frame.frame_id,
            "ratios": list(ratios), "seed": seed}
    if destination_frames:
        body["destination_frames"] = list(destination_frames)
    j = connection().request("POST", "/3/SplitFrame", data=body)
    return [H2OFrame._by_id(d["name"]) for d in j["destination_frames"]]


def insert_missing_values(frame: "H2OFrame", fraction: float = 0.1,
                          seed: int = -1) -> "H2OFrame":
    """`h2o.insert_missing_values` — `POST /3/MissingInserter`."""
    connection().request("POST", "/3/MissingInserter",
                         data={"dataset": frame.frame_id,
                               "fraction": fraction, "seed": seed})
    return H2OFrame._by_id(frame.frame_id)


def download_csv(frame: "H2OFrame") -> str:
    """`h2o.download_csv` body — `GET /3/DownloadDataset` (raw CSV)."""
    return connection().request("GET", "/3/DownloadDataset",
                                params={"frame_id": frame.frame_id}, raw=True)


def log_and_echo(message: str) -> None:
    """`h2o.log_and_echo` — `POST /3/LogAndEcho`."""
    connection().request("POST", "/3/LogAndEcho", data={"message": message})


def rapids(expr: str) -> dict:
    c = connection()
    return c.request("POST", "/99/Rapids",
                     data={"ast": expr, "session_id": c.session()})


# ---------------------------------------------------------------------------
# online scoring (`/3/Serving/...` — the h2o_tpu/serving/ runtime)
# ---------------------------------------------------------------------------
def register_serving(model=None, serving_id: str | None = None,
                     mojo_file: str | None = None, **options) -> dict:
    """Register a model for online scoring and warm up its bucket scorers
    (`POST /3/Serving/models/{id}`). ``model`` is an in-STORE model (client
    handle, estimator, or key string); alternatively ``mojo_file`` names a
    MOJO zip on the server's filesystem (or a PostFile upload key).
    ``options`` forwards the serving overrides (buckets, max_batch,
    max_wait_us, queue_depth, deadline_ms, stats_window, strict_levels).
    Returns the registration info (buckets, warmup_compiles, ...)."""
    data = dict(options)
    if mojo_file is not None:
        data["mojo_file"] = mojo_file
        sid = serving_id or os.path.basename(mojo_file).rsplit(".", 1)[0]
    else:
        if model is None:
            raise ValueError("register_serving needs a model or a mojo_file")
        data["model_id"] = _model_id_of(model)
        sid = serving_id or data["model_id"]
    return connection().request(
        "POST", f"/3/Serving/models/{urllib.parse.quote(sid)}", data=data)


def score_rows(serving_id: str, rows, deadline_ms=None,
               retries: int = 0) -> list:
    """Score one row dict or a list of them through the micro-batched
    runtime (`POST /3/Serving/score`); returns one typed prediction dict
    per row. Raises `H2OServingOverloadError` (queue full, carries
    ``retry_after_s``) and `H2OServingTimeoutError` (deadline expired) so
    callers can back off / retry instead of parsing status codes.

    ``retries > 0`` does the backing off for you (`utils/retry.py`):
    overloads sleep exactly the server's Retry-After drain estimate and
    re-submit, up to ``retries`` extra attempts / the retry wall-clock
    budget — scoring is read-only, so replaying the POST is safe. The
    typed give-up is ``RetryBudgetExceeded`` with the final overload as
    ``__cause__``. Default 0 keeps the raw backpressure signal."""
    if retries > 0:
        from ..utils.retry import retry_call

        def _overloaded(e):
            if isinstance(e, H2OServingOverloadError):
                return max(float(e.retry_after_s), 0.001)
            return False

        return retry_call(
            lambda: score_rows(serving_id, rows, deadline_ms=deadline_ms),
            retryable=_overloaded, attempts=retries + 1,
            description=f"score_rows({serving_id})")
    if isinstance(rows, dict):
        rows = [rows]
    data: dict = {"model_id": serving_id, "rows": list(rows)}
    if deadline_ms is not None:
        data["deadline_ms"] = deadline_ms
    try:
        resp = connection().request("POST", "/3/Serving/score", data=data)
    except H2OConnectionError as e:
        if e.status == 429:
            err = H2OServingOverloadError(str(e))
            err.status, err.headers, err.payload = (e.status, e.headers,
                                                    e.payload)
            err.retry_after_s = float(
                (e.payload or {}).get("retry_after_s")
                or (e.headers or {}).get("Retry-After") or 0.0)
            raise err from None
        if e.status == 408:
            err = H2OServingTimeoutError(str(e))
            err.status, err.headers, err.payload = (e.status, e.headers,
                                                    e.payload)
            raise err from None
        raise
    return resp["predictions"]


def serving_stats(serving_id: str | None = None) -> dict:
    """`GET /3/Serving/stats[/{id}]` → {model_id: snapshot} (p50/p95/p99
    latency, rows/s, mean batch occupancy, queue depth, counters)."""
    path = "/3/Serving/stats"
    if serving_id is not None:
        path += f"/{urllib.parse.quote(serving_id)}"
    return connection().request("GET", path)["models"]


def unregister_serving(serving_id: str) -> dict:
    """`DELETE /3/Serving/models/{id}` — stop the model's batcher."""
    return connection().request(
        "DELETE", f"/3/Serving/models/{urllib.parse.quote(serving_id)}")


def create_route(endpoint: str, variants, seed: int | None = None) -> dict:
    """Map a logical serving endpoint onto weighted model variants
    (`POST /3/Serving/routes/{endpoint}`). ``variants`` is a list of
    ``{"model_id": ..., "weight": ..., "shadow": bool}`` dicts — or the
    ``{model_id: weight}`` shorthand. The split is deterministic in the
    route ``seed`` (fixed seed = replayable variant sequence); shadow
    variants score every request off the response path and feed the
    divergence stats in `route_stats`."""
    if isinstance(variants, dict):
        variants = [{"model_id": k, "weight": v}
                    for k, v in variants.items()]
    data: dict = {"variants": list(variants)}
    if seed is not None:
        data["seed"] = int(seed)
    return connection().request(
        "POST", f"/3/Serving/routes/{urllib.parse.quote(endpoint)}",
        data=data)


def route_score(endpoint: str, rows, deadline_ms=None,
                retries: int = 0) -> list:
    """Score through a routed endpoint (`POST /3/Serving/score` with
    ``endpoint``): the router picks the serving variant per request —
    champion/canary split — and shadow variants see the same rows without
    touching the response. Same typed 429/408 surface (and ``retries``
    semantics) as `score_rows`."""
    if retries > 0:
        from ..utils.retry import retry_call

        def _overloaded(e):
            if isinstance(e, H2OServingOverloadError):
                return max(float(e.retry_after_s), 0.001)
            return False

        return retry_call(
            lambda: route_score(endpoint, rows, deadline_ms=deadline_ms),
            retryable=_overloaded, attempts=retries + 1,
            description=f"route_score({endpoint})")
    if isinstance(rows, dict):
        rows = [rows]
    data: dict = {"endpoint": endpoint, "rows": list(rows)}
    if deadline_ms is not None:
        data["deadline_ms"] = deadline_ms
    try:
        resp = connection().request("POST", "/3/Serving/score", data=data)
    except H2OConnectionError as e:
        if e.status == 429:
            err = H2OServingOverloadError(str(e))
            err.status, err.headers, err.payload = (e.status, e.headers,
                                                    e.payload)
            err.retry_after_s = float(
                (e.payload or {}).get("retry_after_s")
                or (e.headers or {}).get("Retry-After") or 0.0)
            raise err from None
        if e.status == 408:
            err = H2OServingTimeoutError(str(e))
            err.status, err.headers, err.payload = (e.status, e.headers,
                                                    e.payload)
            raise err from None
        raise
    return resp["predictions"]


def route_stats(endpoint: str | None = None) -> dict:
    """`GET /3/Serving/routes[/{endpoint}]` — request counts, per-variant
    weights/serve counts, shadow rows and prediction-delta divergence."""
    if endpoint is not None:
        return connection().request(
            "GET", f"/3/Serving/routes/{urllib.parse.quote(endpoint)}")
    return connection().request("GET", "/3/Serving/routes")


def delete_route(endpoint: str) -> dict:
    """`DELETE /3/Serving/routes/{endpoint}`."""
    return connection().request(
        "DELETE", f"/3/Serving/routes/{urllib.parse.quote(endpoint)}")


def serving_control() -> dict:
    """`GET /3/Serving/control` — fleet quota, placements, routes."""
    return connection().request("GET", "/3/Serving/control")


# ---------------------------------------------------------------------------
# fleet observability plane (PR 13 — programs / fleet metrics / profiler
# capture / flight recorder)
# ---------------------------------------------------------------------------
def programs() -> dict:
    """`GET /3/Programs` → {program_id: record}: per compiled program,
    XLA cost-model flops / bytes accessed, memory assignment, its XLA
    module's name, host dispatch walls (enqueue times) and the module's
    device seconds from the last capture folded in (null before any)."""
    return connection().request("GET", "/3/Programs")["programs"]


def fleet_metrics(force: bool = False) -> dict:
    """`GET /3/Metrics?fleet=1` — the merged multi-process view: counters
    summed, gauges max'd, histogram quantiles count-weight-merged, every
    value also labeled per process. ``force`` bypasses the
    H2O_TPU_FLEET_INTERVAL_MS scrape cache."""
    path = "/3/Metrics?fleet=1" + ("&force=1" if force else "")
    return connection().request("GET", path)["fleet"]


def profiler_capture(ms: int = 1000) -> str:
    """`POST /3/Profiler/capture?ms=N` — bounded live jax.profiler device
    capture on the server process; returns the capture directory (load it
    in Perfetto / tensorboard-profile). The server folds the capture's
    device seconds by program into its registry first: `programs()` then
    shows them per record."""
    return connection().request(
        "POST", f"/3/Profiler/capture?ms={int(ms)}")["dir"]


def flight_bundles() -> dict:
    """`GET /3/Flight` — flight-recorder state: armed?, dir, bundles."""
    return connection().request("GET", "/3/Flight")


def flight_bundle(name: str) -> dict:
    """`GET /3/Flight/{name}` — one diagnostics bundle's full content."""
    return connection().request(
        "GET", f"/3/Flight/{urllib.parse.quote(name)}")["bundle"]


# ---------------------------------------------------------------------------
# causal observability plane (PR 15 — health / SLO burn / slow traces)
# ---------------------------------------------------------------------------
def health() -> dict:
    """`GET /3/Health` — liveness/readiness with typed degradation
    reasons (device visibility, Cleaner headroom vs the reservation
    ledger, serving queue saturation, job heartbeats, watchdog trips,
    SLO burn). ``ready`` is the poll target for autoscalers and rollout
    gates; ``degraded`` names exactly what is wrong."""
    return connection().request("GET", "/3/Health")


def workload() -> dict:
    """`GET /3/Workload` — the multi-tenant workload manager snapshot:
    tenants (weights, quota fractions, preempt/shed/reject counters),
    scheduler entries with their QUEUED/RUNNING/PARKED/FINISHED state,
    and the dispatch configuration (slots, seed)."""
    return connection().request("GET", "/3/Workload")


def workload_configure(tenant: str, weight: float | None = None,
                       quota_fraction: float | None = None) -> dict:
    """`POST /3/Workload` — configure a tenant's fair-share weight
    (lottery tickets relative to other tenants) and/or HBM quota
    fraction (share of the reservation ledger admission debits against).
    Returns the refreshed workload snapshot."""
    body: dict = {"tenant": tenant}
    if weight is not None:
        body["weight"] = float(weight)
    if quota_fraction is not None:
        body["quota_fraction"] = float(quota_fraction)
    return connection().request("POST", "/3/Workload", data=body)


def slow_traces(limit: int | None = None) -> list:
    """`GET /3/SlowTraces` — the tail-based capture ring: full span trees
    (+ program dispatch walls) of requests that breached their SLO p99
    target, newest last."""
    path = "/3/SlowTraces" + (f"?limit={int(limit)}" if limit else "")
    return connection().request("GET", path)["slow_traces"]


# ---------------------------------------------------------------------------
# H2OFrame handle (`h2o-py/h2o/frame.py` + the lazy `h2o-py/h2o/expr.py`
# ExprNode DAG: frame-producing ops build a pending rapids expression and
# only materialize — one `(tmp= name expr)` POST — when the frame's identity
# or data is actually needed. Nested ops fuse into a single server round-trip
# the way h2o-py's expression DAG does.)
# ---------------------------------------------------------------------------
import itertools as _itertools

_TMP_COUNTER = _itertools.count(1)  # atomic under the GIL (worker threads)


class H2OFrame:
    def __init__(self, python_obj=None, destination_frame: str | None = None,
                 header: int = 0, column_names=None, column_types=None,
                 na_strings=None):
        self._pending: str | None = None  # un-materialized rapids expression
        self._inlined = False  # pending expr already embedded somewhere once
        if python_obj is not None:
            other = upload_frame(python_obj, destination_frame,
                                 column_names=column_names,
                                 column_types=column_types,
                                 na_strings=na_strings, header=header)
            self._id = other.frame_id
            self._schema = other._schema
        else:
            self._id = None
            self._schema = None

    @classmethod
    def from_python(cls, python_obj, destination_frame=None, header=0,
                    separator=",", column_names=None, column_types=None,
                    na_strings=None) -> "H2OFrame":
        """`H2OFrame.from_python` (`h2o-py/h2o/frame.py:155`)."""
        return cls(python_obj, destination_frame=destination_frame,
                   header=header, column_names=column_names,
                   column_types=column_types, na_strings=na_strings)

    @classmethod
    def _by_id(cls, frame_id: str) -> "H2OFrame":
        fr = cls()
        fr._id = frame_id
        return fr

    @classmethod
    def _lazy(cls, expr: str) -> "H2OFrame":
        fr = cls()
        fr._pending = expr
        return fr

    @property
    def frame_id(self) -> str:
        """Materializes a pending expression on first identity access
        (h2o-py `ExprNode._eager_frame`)."""
        if self._id is None and self._pending is not None:
            name = f"py_{next(_TMP_COUNTER)}_{os.getpid()}"
            rapids(f"(tmp= {name} {self._pending})")
            self._id = name
            self._pending = None
        return self._id

    @frame_id.setter
    def frame_id(self, value: str):
        self._id = value
        self._pending = None

    def _ref(self) -> str:
        """Expression fragment for embedding in a larger rapids expression.

        A pending expression inlines ONCE (fusion, no round-trip); any later
        reference materializes and embeds the key instead — this caps the
        expression-string growth of reused/self-referencing lazy frames
        (h2o-py's ExprNode caches evaluated nodes for the same reason) and
        keeps repeated scalar reductions from re-evaluating the chain."""
        if self._id is None and self._pending is not None and \
                not self._inlined:
            self._inlined = True
            return self._pending
        return self.frame_id

    def _fr(self, expr: str) -> "H2OFrame":
        return H2OFrame._lazy(expr)

    # -- metadata ------------------------------------------------------------
    def _summary(self) -> dict:
        if self._schema is None:
            self._schema = connection().request(
                "GET", f"/3/Frames/{urllib.parse.quote(self.frame_id)}/summary"
            )["frames"][0]
        return self._schema

    def refresh(self):
        self._schema = None

    _meta: dict | None = None  # local dims/names/types for lazy frames

    @property
    def nrow(self) -> int:
        if self._id is None and self._meta:
            return self._meta["rows"]
        return self._summary()["rows"]

    @property
    def ncol(self) -> int:
        if self._id is None and self._meta:
            return self._meta["cols"]
        return self._summary()["num_columns"]

    @property
    def columns(self) -> list[str]:
        if self._id is None and self._meta:
            return list(self._meta["names"])
        return [c["label"] for c in self._summary()["columns"]]

    @property
    def names(self) -> list[str]:
        return self.columns

    @names.setter
    def names(self, value: list[str]) -> None:
        # h2o-py allows `fr.names = [...]` as a rename-in-place
        self.set_names(list(value))

    @property
    def types(self) -> dict:
        if self._id is None and self._meta:
            return dict(self._meta["types"])
        return {c["label"]: c["type"] for c in self._summary()["columns"]}

    def __len__(self):
        return self.nrow

    # -- rapids-backed ops ---------------------------------------------------
    def _exec(self, expr: str) -> "H2OFrame | float | str":
        res = rapids(expr)
        if res.get("key"):
            return H2OFrame._by_id(res["key"]["name"])
        if res.get("scalar") is not None:
            return res["scalar"]
        if res.get("values") is not None:
            return res["values"]
        return res.get("string")

    def _quoted(self) -> str:
        return self.frame_id

    def _slice_bounds(self, sl: slice, n: int) -> tuple[int, int]:
        start = 0 if sl.start is None else sl.start
        stop = n if sl.stop is None else min(sl.stop, n)
        if sl.step not in (None, 1):
            raise ValueError("h2o frame slices are contiguous (step 1)")
        return start, stop

    def _col_expr(self, ref: str, sel) -> str:
        if isinstance(sel, str):
            return f"(cols {ref} '{sel}')"
        if isinstance(sel, int):
            return f"(cols {ref} {sel})"
        if isinstance(sel, slice):
            a, b = self._slice_bounds(sel, self.ncol)
            return f"(cols {ref} [{a}:{b}])"
        if isinstance(sel, list):
            inner = " ".join(f"'{s}'" if isinstance(s, str) else str(s)
                             for s in sel)
            return f"(cols {ref} [{inner}])"
        raise TypeError(f"bad column selector {sel!r}")

    def __getitem__(self, sel):
        if isinstance(sel, tuple) and len(sel) == 2:
            rows, cols = sel
            scalar = isinstance(rows, int) and isinstance(cols, (int, str))
            # column select first (never changes row identity), rows second
            ref = self._ref()
            expr = ref if (isinstance(cols, slice) and cols == slice(None)) \
                else self._col_expr(ref, cols)
            if isinstance(rows, int):
                expr = f"(rows {expr} [{rows}:{rows + 1}])"
            elif isinstance(rows, slice):
                if rows != slice(None):
                    a, b = self._slice_bounds(rows, self.nrow)
                    expr = f"(rows {expr} [{a}:{b}])"
            elif isinstance(rows, list):
                inner = " ".join(str(int(r)) for r in rows)
                expr = f"(rows {expr} [{inner}])"
            elif isinstance(rows, H2OFrame):
                expr = f"(rows {expr} (cols {rows._ref()} 0))"
            else:
                raise TypeError(f"bad row selector {rows!r}")
            out = self._fr(expr)
            return out._scalar() if scalar else out
        if isinstance(sel, (str, int, slice, list)):
            return self._fr(self._col_expr(self._ref(), sel))
        if isinstance(sel, H2OFrame):  # boolean mask frame
            return self._fr(f"(rows {self._ref()} (cols {sel._ref()} 0))")
        raise TypeError(f"bad selector {sel!r}")

    def _scalar(self):
        """The single cell of a 1x1 frame as a python value (the h2o-py
        `flatten()` read used by `fr[r, c]`)."""
        j = connection().request(
            "GET", f"/3/Frames/{urllib.parse.quote(self.frame_id)}",
            params={"row_count": 1})["frames"][0]
        c = j["columns"][0]
        if c.get("string_data") is not None:
            return c["string_data"][0]
        v = (c["data"] or [None])[0]
        if v is None:
            return float("nan")
        if c["domain"]:
            return c["domain"][int(v)]
        return v

    @staticmethod
    def _src_expr(value) -> str:
        if isinstance(value, H2OFrame):
            return value.frame_id
        if isinstance(value, str):
            return f"'{value}'"
        if value is None:
            return "NA"
        return repr(float(value))

    def __setitem__(self, sel, value):
        """Column/slice update: `(append ...)` for a new column, `(:= ...)`
        rectangle assign otherwise (h2o-py `H2OFrame.__setitem__` →
        `AstAppend`/`AstRectangleAssign`). The result is bound to a FRESH key
        and this handle rebinds to it — outstanding lazy frames built from
        the old key keep seeing the pre-mutation data, matching h2o-py's
        immutable ExprNode-DAG semantics."""
        src = self._src_expr(value)
        fid = self.frame_id
        if isinstance(sel, tuple) and len(sel) == 2:
            rowsel, colsel = sel
            rows = (f"(cols {rowsel.frame_id} 0)"
                    if isinstance(rowsel, H2OFrame) else
                    "[]" if rowsel is None else
                    f"[{' '.join(str(int(r)) for r in rowsel)}]"
                    if isinstance(rowsel, (list, tuple)) else
                    str(int(rowsel)))
            cols = (f"'{colsel}'" if isinstance(colsel, str)
                    else str(int(colsel)))
            expr = f"(:= {fid} {src} {cols} {rows})"
        elif isinstance(sel, str) and sel not in self.columns:
            expr = f"(append {fid} {src} '{sel}')"
        else:
            col = sel if not isinstance(sel, str) else f"'{sel}'"
            expr = f"(:= {fid} {src} {col} [])"
        name = f"py_{next(_TMP_COUNTER)}_{os.getpid()}"
        rapids(f"(tmp= {name} {expr})")
        self._id = name
        self._pending = None
        self.refresh()

    def _binop(self, op, other, reverse=False):
        rhs = other._ref() if isinstance(other, H2OFrame) else repr(float(other))
        lhs = self._ref()
        if reverse:
            lhs, rhs = rhs, lhs
        return self._fr(f"({op} {lhs} {rhs})")

    def __add__(self, o):
        return self._binop("+", o)

    def __radd__(self, o):
        return self._binop("+", o, True)

    def __sub__(self, o):
        return self._binop("-", o)

    def __mul__(self, o):
        return self._binop("*", o)

    def __truediv__(self, o):
        return self._binop("/", o)

    def __gt__(self, o):
        return self._binop(">", o)

    def __ge__(self, o):
        return self._binop(">=", o)

    def __lt__(self, o):
        return self._binop("<", o)

    def __le__(self, o):
        return self._binop("<=", o)

    def __eq__(self, o):  # noqa: comparing frames builds a frame, like h2o-py
        return self._binop("==", o)

    def __ne__(self, o):
        return self._binop("!=", o)

    __hash__ = None

    def __and__(self, o):
        return self._binop("&", o)

    def __or__(self, o):
        return self._binop("|", o)

    def __pow__(self, o):
        return self._binop("^", o)

    def __rpow__(self, o):
        return self._binop("^", o, reverse=True)

    def __mod__(self, o):
        return self._binop("%", o)

    def __rmod__(self, o):
        return self._binop("%", o, reverse=True)

    def __floordiv__(self, o):
        return self._binop("intDiv", o)

    def __rtruediv__(self, o):
        return self._binop("/", o, reverse=True)

    def __rsub__(self, o):
        return self._binop("-", o, reverse=True)

    def __abs__(self):
        return self._unop("abs")

    def __neg__(self):
        return self._binop("*", -1)

    def __invert__(self):
        return self._unop("not")

    # unary math surface (h2o-py H2OFrame.cos/log/... — each compiles to
    # the matching rapids prim lazily)
    def _unop(self, op) -> "H2OFrame":
        return self._fr(f"({op} {self._ref()})")

    def cos(self): return self._unop("cos")          # noqa: E704
    def sin(self): return self._unop("sin")          # noqa: E704
    def tan(self): return self._unop("tan")          # noqa: E704
    def acos(self): return self._unop("acos")        # noqa: E704
    def asin(self): return self._unop("asin")        # noqa: E704
    def atan(self): return self._unop("atan")        # noqa: E704
    def cosh(self): return self._unop("cosh")        # noqa: E704
    def sinh(self): return self._unop("sinh")        # noqa: E704
    def tanh(self): return self._unop("tanh")        # noqa: E704
    def exp(self): return self._unop("exp")          # noqa: E704
    def expm1(self): return self._unop("expm1")      # noqa: E704
    def log(self): return self._unop("log")          # noqa: E704
    def log1p(self): return self._unop("log1p")      # noqa: E704
    def log2(self): return self._unop("log2")        # noqa: E704
    def log10(self): return self._unop("log10")      # noqa: E704
    def sqrt(self): return self._unop("sqrt")        # noqa: E704
    def abs(self): return self._unop("abs")          # noqa: E704
    def floor(self): return self._unop("floor")      # noqa: E704
    def ceil(self): return self._unop("ceiling")     # noqa: E704
    def trunc(self): return self._unop("trunc")      # noqa: E704
    def sign(self): return self._unop("sign")        # noqa: E704
    def gamma(self): return self._unop("gamma")      # noqa: E704
    def lgamma(self): return self._unop("lgamma")    # noqa: E704
    def digamma(self): return self._unop("digamma")  # noqa: E704
    def trigamma(self): return self._unop("trigamma")  # noqa: E704
    def logical_negation(self): return self._unop("not")  # noqa: E704

    def mean(self, na_rm=True):
        return self._exec(f"(mean {self._ref()} {'true' if na_rm else 'false'})")

    def sum(self, na_rm=True):
        return self._exec(f"(sum {self._ref()} {'true' if na_rm else 'false'})")

    def min(self):
        return self._exec(f"(min {self._ref()} true)")

    def max(self):
        return self._exec(f"(max {self._ref()} true)")

    def sd(self):
        return self._exec(f"(sd {self._ref()} true)")

    def prod(self, na_rm=True):
        return self._exec(f"(prod {self._ref()} "
                          f"{'true' if na_rm else 'false'})")

    def all(self) -> bool:
        return bool(self._exec(f"(all {self._ref()} true)"))

    def any(self) -> bool:
        return bool(self._exec(f"(any {self._ref()} true)"))

    def cumsum(self, axis=0): return self._unop("cumsum")    # noqa: E704
    def cumprod(self, axis=0): return self._unop("cumprod")  # noqa: E704
    def cummin(self, axis=0): return self._unop("cummin")    # noqa: E704
    def cummax(self, axis=0): return self._unop("cummax")    # noqa: E704

    @property
    def dim(self) -> list:
        return [self.nrow, self.ncol]

    @property
    def shape(self) -> tuple:
        return (self.nrow, self.ncol)

    def __iter__(self):
        return (self[i] for i in range(self.ncol))

    def show(self, use_pandas=False):
        print(self)

    def summary(self, return_data=False):
        """Per-column summary print (`H2OFrame.summary`)."""
        if return_data:
            return self._summary()
        self.describe()

    def insert_missing_values(self, fraction=0.1, seed=None) -> "H2OFrame":
        """In-place NA injection — `POST /3/MissingInserter` on this frame's
        key (h2o-py's method of the same name mutates server-side too)."""
        insert_missing_values(self, fraction=fraction,
                              seed=-1 if seed is None else seed)
        self.refresh()
        return self

    def flatten(self):
        return self._scalar()

    def asfactor(self) -> "H2OFrame":
        return self._fr(f"(as.factor {self._ref()})")

    def asnumeric(self) -> "H2OFrame":
        return self._fr(f"(as.numeric {self._ref()})")

    def unique(self) -> "H2OFrame":
        return self._fr(f"(unique {self._ref()})")

    def table(self) -> "H2OFrame":
        return self._fr(f"(table {self._ref()})")

    def cbind(self, other: "H2OFrame") -> "H2OFrame":
        return self._fr(f"(cbind {self._ref()} {other._ref()})")

    def rbind(self, other: "H2OFrame") -> "H2OFrame":
        return self._fr(f"(rbind {self._ref()} {other._ref()})")

    def skewness(self, na_rm=True):
        return self._exec(f"(skewness {self._ref()} true)")

    def kurtosis(self, na_rm=True):
        return self._exec(f"(kurtosis {self._ref()} true)")

    def cor(self, other: "H2OFrame" = None):
        if other is None:
            fid = self.frame_id  # one evaluation, embedded twice by key
            return self._exec(f"(cor {fid} {fid} 'everything' 'Pearson')")
        return self._exec(f"(cor {self._ref()} {other._ref()} "
                          f"'everything' 'Pearson')")

    def quantile(self, prob=(0.01, 0.1, 0.25, 0.333, 0.5, 0.667, 0.75, 0.9,
                             0.99)) -> "H2OFrame":
        ps = " ".join(str(p) for p in prob)
        return self._fr(f"(quantile {self._ref()} [{ps}] 'interpolate' _)")

    def impute(self, column=-1, method="mean"):
        return self._exec(f"(h2o.impute {self.frame_id} {column} '{method}' "
                          f"'interpolate' [] _ _)")

    def scale(self, center=True, scale=True) -> "H2OFrame":
        c = "true" if center else "false"
        s = "true" if scale else "false"
        return self._fr(f"(scale {self._ref()} {c} {s})")

    def na_omit(self) -> "H2OFrame":
        return self._fr(f"(na.omit {self._ref()})")

    def fillna(self, method="forward", axis=0, maxlen=1) -> "H2OFrame":
        return self._fr(f"(h2o.fillna {self._ref()} '{method}' {axis} "
                        f"{maxlen})")

    def match(self, table, nomatch=None) -> "H2OFrame":
        items = " ".join(f"'{t}'" if isinstance(t, str) else str(t)
                         for t in table)
        nm = "_" if nomatch is None else str(nomatch)
        return self._fr(f"(match {self._ref()} [{items}] {nm} 1)")

    def cut(self, breaks, labels=None, include_lowest=False,
            right=True) -> "H2OFrame":
        bs = " ".join(str(b) for b in breaks)
        lb = "_" if not labels else \
            "[" + " ".join(f"'{l}'" for l in labels) + "]"
        il = "true" if include_lowest else "false"
        r = "true" if right else "false"
        return self._fr(f"(cut {self._ref()} [{bs}] {lb} {il} {r} 3)")

    def difflag1(self) -> "H2OFrame":
        return self._fr(f"(difflag1 {self._ref()})")

    def kfold_column(self, n_folds=3, seed=-1) -> "H2OFrame":
        return self._exec(f"(kfold_column {self.frame_id} {n_folds} {seed})")

    def stratified_kfold_column(self, n_folds=3, seed=-1) -> "H2OFrame":
        return self._exec(
            f"(stratified_kfold_column {self.frame_id} {n_folds} {seed})")

    def stratified_split(self, test_frac=0.2, seed=-1) -> "H2OFrame":
        return self._exec(f"(h2o.random_stratified_split {self.frame_id} "
                          f"{test_frac} {seed})")

    def levels(self):
        return self._exec(f"(levels {self.frame_id})")

    def relevel(self, y: str) -> "H2OFrame":
        return self._exec(f"(relevel {self.frame_id} '{y}')")

    def pivot(self, index: str, column: str, value: str) -> "H2OFrame":
        return self._exec(f"(pivot {self.frame_id} '{index}' '{column}' "
                          f"'{value}')")

    def melt(self, id_vars, value_vars=None, var_name="variable",
             value_name="value", skipna=False) -> "H2OFrame":
        ids = " ".join(f"'{c}'" for c in id_vars)
        vv = "_" if not value_vars else \
            "[" + " ".join(f"'{c}'" for c in value_vars) + "]"
        sk = "true" if skipna else "false"
        return self._exec(f"(melt {self.frame_id} [{ids}] {vv} '{var_name}' "
                          f"'{value_name}' {sk})")

    def transpose(self) -> "H2OFrame":
        return self._exec(f"(t {self.frame_id})")

    def mult(self, other: "H2OFrame") -> "H2OFrame":
        return self._exec(f"(x*y {self.frame_id} {other.frame_id})")

    def topn(self, column=0, nPercent=10, grabTopN=-1) -> "H2OFrame":
        """grabTopN=-1 → top values; any other value → bottom (h2o-py
        `topNBottomN` convention routes both through this prim)."""
        bottom = "0" if grabTopN == -1 else "1"
        return self._exec(f"(topn {self.frame_id} {column} {nPercent} "
                          f"{bottom})")

    def mode(self):
        return self._exec(f"(mode {self.frame_id})")

    def hist(self, breaks="sturges") -> "H2OFrame":
        b = (f"'{breaks}'" if isinstance(breaks, str) else
             "[" + " ".join(map(str, breaks)) + "]"
             if isinstance(breaks, (list, tuple)) else str(breaks))
        return self._exec(f"(hist {self.frame_id} {b})")

    def distance(self, y: "H2OFrame", measure="l2") -> "H2OFrame":
        return self._exec(f"(distance {self.frame_id} {y.frame_id} "
                          f"'{measure}')")

    def drop_duplicates(self, columns, keep="first") -> "H2OFrame":
        cols = " ".join(f"'{c}'" if isinstance(c, str) else str(c)
                        for c in columns)
        return self._exec(f"(dropdup {self.frame_id} [{cols}] '{keep}')")

    def mad(self, combine_method="interpolate", constant=1.4826):
        return self._exec(f"(h2o.mad {self.frame_id} '{combine_method}' "
                          f"{constant})")

    def nlevels(self):
        return self._exec(f"(nlevels {self.frame_id})")

    def anyfactor(self):
        return bool(self._exec(f"(any.factor {self.frame_id})"))

    def isna(self) -> "H2OFrame":
        out = self._fr(f"(is.na {self._ref()})")
        # h2o-py's ExprNode knows the result's dims/names/types locally —
        # reading them must not force evaluation (pyunit_isna pins this)
        out._meta = {"rows": self.nrow, "cols": self.ncol,
                     "names": [f"isNA({n})" for n in self.columns],
                     "types": {f"isNA({n})": "int" for n in self.columns}}
        return out

    def columns_by_type(self, coltype="numeric"):
        return self._exec(f"(columnsByType {self.frame_id} '{coltype}')")

    def set_level(self, level: str) -> "H2OFrame":
        return self._exec(f"(setLevel {self.frame_id} '{level}')")

    def append_levels(self, levels) -> "H2OFrame":
        lv = " ".join(f"'{l}'" for l in levels)
        return self._exec(f"(appendLevels {self.frame_id} [{lv}])")

    def relevel_by_frequency(self, top_n=-1) -> "H2OFrame":
        return self._exec(f"(relevel.by.freq {self.frame_id} {top_n})")

    def as_date(self, format: str) -> "H2OFrame":
        return self._exec(f"(as.Date {self.frame_id} '{format}')")

    def week(self) -> "H2OFrame":
        return self._exec(f"(week {self.frame_id})")

    def isax(self, num_words, max_cardinality, optimize_card=False):
        oc = "1" if optimize_card else "0"
        return self._exec(f"(isax {self.frame_id} {num_words} "
                          f"{max_cardinality} {oc})")

    def apply(self, fun: str, axis=0) -> "H2OFrame":
        """Apply a reducer over rows (axis=1) or columns (axis=0). `fun` is
        a reducer name ('mean', 'sum', …) or a raw rapids lambda string."""
        lam = fun if fun.lstrip().startswith("{") else f"{{x . ({fun} x)}}"
        margin = 1 if axis == 1 else 2
        return self._exec(f"(apply {self.frame_id} {margin} {lam})")

    def entropy(self) -> "H2OFrame":
        return self._exec(f"(entropy {self.frame_id})")

    @property
    def nrows(self) -> int:
        return self.nrow

    @property
    def ncols(self) -> int:
        return self.ncol

    def merge(self, other: "H2OFrame", all_x: bool = False,
              all_y: bool = False, by_x=None, by_y=None,
              method: str = "auto") -> "H2OFrame":
        """`H2OFrame.merge` — `(merge x y all_x all_y [bx] [by])`
        (AstMerge); by_x/by_y are column names or indices."""
        ax = "TRUE" if all_x else "FALSE"
        ay = "TRUE" if all_y else "FALSE"

        def idxs(fr, by):
            if by is None:
                return "[]"
            cols = by if isinstance(by, list) else [by]
            return "[" + " ".join(
                str(fr.columns.index(c) if isinstance(c, str) else int(c))
                for c in cols) + "]"

        return self._fr(f"(merge {self._ref()} {other._ref()} {ax} {ay} "
                        f"{idxs(self, by_x)} {idxs(other, by_y)} "
                        f"'{method}')")

    def sort(self, by, ascending=None) -> "H2OFrame":
        cols = by if isinstance(by, list) else [by]
        inner = " ".join(f"'{c}'" if isinstance(c, str) else str(c)
                         for c in cols)
        if ascending is None:
            return self._fr(f"(sort {self._ref()} [{inner}])")
        asc = ascending if isinstance(ascending, list) else [ascending]
        flags = " ".join("1" if a else "0" for a in asc)
        return self._fr(f"(sort {self._ref()} [{inner}] [{flags}])")

    def strdistance(self, y: "H2OFrame", measure: str = "lv",
                    compare_empty: bool = True) -> "H2OFrame":
        """`H2OFrame.strdistance` — `(strDistance x y measure ce)`."""
        ce = "true" if compare_empty else "false"
        return self._fr(f"(strDistance {self._ref()} {y._ref()} "
                        f"'{measure}' {ce})")

    def strsplit(self, pattern: str) -> "H2OFrame":
        return self._exec(f"(strsplit {self.frame_id} '{pattern}')")

    def countmatches(self, pattern) -> "H2OFrame":
        pats = pattern if isinstance(pattern, list) else [pattern]
        items = " ".join(f"'{p}'" for p in pats)
        return self._exec(f"(countmatches {self.frame_id} [{items}])")

    def tokenize(self, split=" ") -> "H2OFrame":
        return self._exec(f"(tokenize {self.frame_id} '{split}')")

    def runif(self, seed=-1) -> "H2OFrame":
        return self._exec(f"(h2o.runif {self.frame_id} {seed})")

    def split_frame(self, ratios=(0.75,), seed=-1) -> list["H2OFrame"]:
        """Random frame split (h2o-py `split_frame`): cumulative ratio
        buckets over one uniform column."""
        r = self.runif(seed=seed)
        out = []
        lo = 0.0
        bounds = list(ratios) + [None]
        for frac in bounds:
            hi = lo + frac if frac is not None else 1.0
            mask = (r >= lo) & (r < hi) if frac is not None else (r >= lo)
            out.append(self[mask])
            lo = hi
        return out

    def drop(self, col) -> "H2OFrame":
        """Remove column(s) by name/index (h2o-py `drop`)."""
        cols = [col] if isinstance(col, (str, int)) else list(col)
        have = self.columns
        names = [c if isinstance(c, str) else have[c] for c in cols]
        missing = [n for n in names if n not in have]
        if missing:
            raise ValueError(f"drop: column(s) {missing} not in frame")
        keep = [n for n in have if n not in names]
        return self[keep]

    def ascharacter(self) -> "H2OFrame":
        return self._exec(f"(ascharacter {self.frame_id})")

    def group_by(self, by) -> "H2OGroupBy":
        """h2o-py GroupBy builder: chain aggregates, then `.get_frame()`."""
        return H2OGroupBy(self, [by] if isinstance(by, str) else list(by))

    def set_names(self, names: list[str]) -> "H2OFrame":
        """Rename columns in place (h2o-py semantics: the handle keeps
        pointing at the renamed frame)."""
        inner = " ".join(f"'{n}'" for n in names)
        idx = " ".join(str(i) for i in range(len(names)))
        out = self._exec(f"(colnames= {self.frame_id} [{idx}] [{inner}])")
        self.frame_id = out.frame_id
        self._schema = None
        return self

    # -- materialization -----------------------------------------------------
    def as_data_frame(self, use_pandas: bool = True, header: bool = True,
                      rows: int | None = None):
        """pandas DataFrame, or with ``use_pandas=False`` the h2o-py wire
        shape: a list of ROWS of strings, ``header`` controlling whether the
        first row is the column names (`h2o-py/h2o/frame.py as_data_frame`)."""
        j = connection().request(
            "GET", f"/3/Frames/{urllib.parse.quote(self.frame_id)}",
            params={"row_count": rows if rows is not None else self.nrow}
        )["frames"][0]
        cols = {}
        for c in j["columns"]:
            if c.get("string_data") is not None:
                cols[c["label"]] = c["string_data"]
            elif c["domain"]:
                dom = c["domain"]
                cols[c["label"]] = [None if v is None else dom[int(v)]
                                    for v in (c["data"] or [])]
            else:
                cols[c["label"]] = c["data"] or []
        if use_pandas:
            import pandas as pd

            return pd.DataFrame(cols)

        def cell(v):
            if v is None or (isinstance(v, float) and v != v):
                return ""
            if isinstance(v, float) and v == int(v):
                return str(int(v))
            return str(v)

        names = list(cols)
        out = [[cell(v) for v in row] for row in zip(*cols.values())]
        return ([names] + out) if header else out

    def describe(self, chunk_summary=False):
        """Print the per-column summary table (`H2OFrame.describe`)."""
        summ = self._summary()
        print(f"Rows:{summ['rows']}  Cols:{summ['num_columns']}")
        def first(v):  # schema emits mins/maxs as 1-element lists
            return v[0] if isinstance(v, list) and v else (
                None if isinstance(v, list) else v)

        rows = []
        for c in summ["columns"]:
            rows.append([c["label"], c["type"], first(c.get("mins")),
                         first(c.get("maxs")), c.get("mean"), c.get("sigma"),
                         c.get("missing_count")])
        try:
            import pandas as pd

            df = pd.DataFrame(rows, columns=["column", "type", "min", "max",
                                             "mean", "sigma", "missing"])
            print(df.to_string(index=False))
        except ImportError:
            for r in rows:
                print(r)
        return self

    def head(self, rows=10):
        # only the first `rows` rows cross the wire (server-side preview cap)
        return self.as_data_frame(rows=rows)

    def __repr__(self):
        return f"H2OFrame({self.frame_id}, {self.nrow}x{self.ncol})"


# ---------------------------------------------------------------------------
# estimators (`h2o-py/h2o/estimators/*` — thin generated layer)
# ---------------------------------------------------------------------------
class H2OGroupBy:
    """`h2o-py/h2o/group_by.py` surface over the rapids GB prim."""

    def __init__(self, fr: H2OFrame, by: list[str]):
        self._fr = fr
        self._by = by
        self._aggs: list[tuple[str, str, str]] = []

    def _add(self, agg, col, na):
        cols = [col] if isinstance(col, str) else list(col)
        for c in cols:
            self._aggs.append((agg, c, na))
        return self

    def sum(self, col, na="all"):
        return self._add("sum", col, na)

    def mean(self, col, na="all"):
        return self._add("mean", col, na)

    def min(self, col, na="all"):
        return self._add("min", col, na)

    def max(self, col, na="all"):
        return self._add("max", col, na)

    def sd(self, col, na="all"):
        return self._add("sd", col, na)

    def var(self, col, na="all"):
        return self._add("var", col, na)

    def count(self, na="all"):
        self._aggs.append(("nrow", self._by[0], na))
        return self

    def get_frame(self) -> H2OFrame:
        by = " ".join(f"'{c}'" for c in self._by)
        aggs = " ".join(f"'{a}' '{c}' '{na}'" for a, c, na in self._aggs)
        return self._fr._exec(f"(GB {self._fr.frame_id} [{by}] {aggs})")


def deep_copy(frame: H2OFrame, destination_frame: str) -> H2OFrame:
    """`h2o.deep_copy`: server-side materialized copy under a new key."""
    idx = " ".join(str(i) for i in range(frame.ncol))
    rapids(f"(tmp= {destination_frame} (cols {frame._ref()} [{idx}]))")
    return H2OFrame._by_id(destination_frame)


def assign(frame: H2OFrame, destination_frame: str) -> H2OFrame:
    """`h2o.assign`: bind the frame's data under a new key and rebind this
    handle to it. The old key stays alive so other handles and pending lazy
    expressions that captured it keep working (the client's snapshot
    contract — see __setitem__)."""
    idx = " ".join(str(i) for i in range(frame.ncol))
    rapids(f"(tmp= {destination_frame} (cols {frame._ref()} [{idx}]))")
    frame.frame_id = destination_frame
    frame.refresh()
    return frame


def list_timezones() -> "H2OFrame":
    return H2OFrame._lazy("(listTimeZones)")


def get_timezone() -> str:
    fr = H2OFrame._lazy("(getTimeZone)")
    return fr.as_data_frame().iloc[0, 0]


def set_timezone(tz: str) -> None:
    rapids(f"(setTimeZone '{tz}')")


def interaction(frame: H2OFrame, factors, pairwise=False, max_factors=100,
                min_occurrence=1, destination_frame=None) -> H2OFrame:
    """`h2o.interaction`: combined categorical columns from factor tuples."""
    items = " ".join(f"'{f}'" if isinstance(f, str) else str(f)
                     for f in factors)
    pw = "true" if pairwise else "false"
    return frame._exec(f"(interaction {frame.frame_id} [{items}] {pw} "
                       f"{max_factors} {min_occurrence})")


def export_file(frame: H2OFrame, path: str, force: bool = False) -> None:
    """`h2o.export_file`: write a frame to CSV/parquet server-side."""
    connection().request(
        "POST", f"/3/Frames/{urllib.parse.quote(frame.frame_id)}/export",
        params={"path": path, "force": "true" if force else "false"})


class _ClientMetrics(dict):
    """Metrics payload with h2o-py `ModelMetrics` getter methods, still a
    plain dict of the ModelMetricsBaseV3 wire fields."""

    def auc(self): return self.get("AUC")                    # noqa: E704
    def aucpr(self): return self.get("pr_auc")               # noqa: E704
    def mse(self): return self.get("MSE")                    # noqa: E704
    def rmse(self): return self.get("RMSE")                  # noqa: E704
    def mae(self): return self.get("mae")                    # noqa: E704
    def logloss(self): return self.get("logloss")            # noqa: E704
    def gini(self): return self.get("Gini")                  # noqa: E704
    def r2(self): return self.get("r2")                      # noqa: E704
    def null_deviance(self): return self.get("null_deviance")        # noqa: E704,E501
    def residual_deviance(self): return self.get("residual_deviance")  # noqa: E704,E501
    def mean_residual_deviance(self):                        # noqa: E704
        return self.get("mean_residual_deviance")

    def show(self):
        print(self)


class H2OModelClient:
    """Client handle on a trained server-side model."""

    def __init__(self, model_id: str, schema: dict):
        self.model_id = schema["model_id"]["name"] if schema else model_id
        self._schema = schema

    @property
    def key(self):
        return self.model_id

    def predict(self, frame: H2OFrame, **params) -> H2OFrame:
        j = connection().request(
            "POST",
            f"/3/Predictions/models/{urllib.parse.quote(self.model_id)}"
            f"/frames/{urllib.parse.quote(frame.frame_id)}", params=params)
        return H2OFrame._by_id(j["predictions_frame"]["name"])

    def predict_contributions(self, frame: H2OFrame) -> H2OFrame:
        return self.predict(frame, predict_contributions="true")

    def predict_leaf_node_assignment(self, frame: H2OFrame,
                                     type="Path") -> H2OFrame:
        return self.predict(frame, leaf_node_assignment="true",
                            leaf_node_assignment_type=type)

    def staged_predict_proba(self, frame: H2OFrame) -> H2OFrame:
        return self.predict(frame, predict_staged_proba="true")

    # -- GLM-family coefficient surface (`h2o-py` model.coef()) --------------
    def _coef_table(self) -> dict:
        tbl = ((self._schema or {}).get("output") or {}).get(
            "coefficients_table")
        if tbl is None:
            raise ValueError(f"model {self.model_id} has no coefficients")
        return tbl

    def coef(self) -> dict:
        t = self._coef_table()
        return dict(zip(t["names"], t["coefficients"]))

    def coef_norm(self) -> dict:
        t = self._coef_table()
        return dict(zip(t["names"], t["standardized_coefficients"]))

    def _coef_stat(self, col) -> dict:
        t = self._coef_table()
        if col not in t:
            raise ValueError(f"train with compute_p_values=True for {col}")
        return dict(zip(t["names"], t[col]))

    def std_errs(self) -> dict:
        return self._coef_stat("std_errs")

    def z_values(self) -> dict:
        return self._coef_stat("z_values")

    def p_values(self) -> dict:
        return self._coef_stat("p_values")

    def dispersion(self):
        return ((self._schema or {}).get("output") or {}).get("dispersion")

    def _metrics(self, kind="training_metrics") -> dict:
        return (self._schema or {}).get("output", {}).get(kind) or {}

    def model_performance(self, test_data: "H2OFrame | None" = None,
                          train=False, valid=False, xval=False) -> dict:
        """Recompute metrics on a frame — `GET /3/ModelMetrics/models/{m}/
        frames/{f}` (ModelMetricsHandler score-and-fetch); without a frame,
        the stored training/validation/xval metrics (h2o-py signature)."""
        if test_data is None:
            kind = ("cross_validation_metrics" if xval else
                    "validation_metrics" if valid else "training_metrics")
            return _ClientMetrics(self._metrics(kind))
        j = connection().request(
            "GET",
            f"/3/ModelMetrics/models/{urllib.parse.quote(self.model_id)}"
            f"/frames/{urllib.parse.quote(test_data.frame_id)}")
        return _ClientMetrics(j["model_metrics"][0])

    @property
    def _id(self) -> str:
        return self.model_id

    @property
    def _model_json(self) -> dict:
        return self._schema

    def show(self):
        print(self)

    def summary(self):
        return ((self._schema or {}).get("output") or {}).get("model_summary")

    @property
    def parms(self) -> dict:
        """h2o-py `ModelBase.parms`: {name: {actual_value, default_value}}
        off the model schema's parameters list."""
        return {p["name"]: p
                for p in (self._schema or {}).get("parameters", [])}

    @property
    def actual_params(self) -> dict:
        return {k: v.get("actual_value") for k, v in self.parms.items()}

    def cross_validation_models(self) -> list:
        """The N fold models (`ModelBase.cross_validation_models`)."""
        refs = ((self._schema or {}).get("output") or {}).get(
            "cross_validation_models") or []
        return [get_model(r["name"]) for r in refs]

    def cross_validation_fold_assignment(self) -> "H2OFrame":
        ref = ((self._schema or {}).get("output") or {}).get(
            "cross_validation_fold_assignment_frame_id")
        if not ref:
            raise ValueError("no fold assignment kept (train with "
                             "keep_cross_validation_fold_assignment=True)")
        return get_frame(ref["name"])

    def cross_validation_holdout_predictions(self) -> "H2OFrame":
        ref = ((self._schema or {}).get("output") or {}).get(
            "cross_validation_holdout_predictions_frame_id")
        if not ref:
            raise ValueError("no holdout predictions kept (train with "
                             "keep_cross_validation_predictions=True)")
        return get_frame(ref["name"])

    def cross_validation_predictions(self) -> list:
        refs = ((self._schema or {}).get("output") or {}).get(
            "cross_validation_predictions") or []
        return [get_frame(r["name"]) for r in refs]

    def to_frame(self) -> "H2OFrame":
        """Word2vec embeddings as a [Word, V1..VD] frame
        (`H2OWordEmbeddingModel.to_frame` → rapids word2vec.to.frame)."""
        j = rapids(f"(word2vec.to.frame {self.model_id})")
        return H2OFrame._by_id(j["key"]["name"])

    def weights(self, matrix_id: int = 0) -> "H2OFrame":
        """Layer weight frame (units_out × units_in) —
        `ModelBase.weights`, served when export_weights_and_biases=True."""
        refs = ((self._schema or {}).get("output") or {}).get("weights")
        if not refs:
            raise ValueError("no exported weights (train with "
                             "export_weights_and_biases=True)")
        return get_frame(refs[matrix_id]["name"])

    def biases(self, vector_id: int = 0) -> "H2OFrame":
        refs = ((self._schema or {}).get("output") or {}).get("biases")
        if not refs:
            raise ValueError("no exported biases (train with "
                             "export_weights_and_biases=True)")
        return get_frame(refs[vector_id]["name"])

    def num_iterations(self):
        """Clustering/GLM iteration count (`ModelBase.num_iterations`)."""
        return ((self._schema or {}).get("output") or {}).get(
            "num_iterations")

    def centers(self):
        """Cluster means in row-major form (`H2OClusteringModel.centers`)."""
        c = ((self._schema or {}).get("output") or {}).get("centers")
        if not c:
            return None
        cols = c["data"]
        return [[col[i] for col in cols] for i in range(len(cols[0]))]

    def auc(self, train=True, valid=False, xval=False):
        kind = ("cross_validation_metrics" if xval else
                "validation_metrics" if valid else "training_metrics")
        return self._metrics(kind).get("AUC")

    def rmse(self, train=True, valid=False, xval=False):
        kind = ("cross_validation_metrics" if xval else
                "validation_metrics" if valid else "training_metrics")
        return self._metrics(kind).get("RMSE")

    def logloss(self, **kw):
        return self._metrics().get("logloss")

    def aucpr(self, **kw):
        return self._metrics().get("pr_auc")

    def kolmogorov_smirnov(self, **kw):
        return self._metrics().get("ks")

    def gini(self, **kw):
        return self._metrics().get("Gini")

    def confusion_matrix(self, **kw):
        cm = self._metrics().get("cm")
        return cm and cm.get("table")

    def gains_lift(self, **kw):
        return self._metrics().get("gains_lift_table")

    def F1(self, thresholds=None, **kw):
        ts = self._metrics().get("thresholds_and_metric_scores") or {}
        return list(zip(ts.get("thresholds", []), ts.get("f1", [])))

    def find_threshold_by_max_metric(self, metric: str, **kw):
        t = self._metrics().get("max_criteria_and_metric_scores")
        if not t:
            return None
        names = t["data"][0]
        return t["data"][1][names.index(f"max {metric}")]

    def varimp(self, use_pandas=False):
        vi = (self._schema or {}).get("output", {}).get("variable_importances")
        if vi and use_pandas:
            import pandas as pd

            return pd.DataFrame(vi)
        return vi

    def partial_plot(self, frame: H2OFrame, cols=None, nbins: int = 20,
                     plot: bool = False, row_index: int = -1, targets=None):
        """Partial dependence tables (h2o-py `partial_plot` data surface);
        ``row_index >= 0`` returns the row's ICE curve instead."""
        params = {"model_id": self.model_id, "frame_id": frame.frame_id,
                  "nbins": nbins, "row_index": row_index}
        if cols:
            params["cols"] = ",".join(cols)
        if targets:
            params["targets"] = ",".join(
                [targets] if isinstance(targets, str) else list(targets))
        j = connection().request("POST", "/3/PartialDependence", params=params)
        return j["partial_dependence_data"]

    def fairness_metrics(self, frame: "H2OFrame", protected_columns,
                         reference, favorable_class) -> dict:
        """Intersectional fairness metrics (`h2o-py fairness_metrics` /
        the `fairnessMetrics` rapids prim): dict of H2OFrames keyed
        'overview' + per-group threshold tables."""
        def _sl(xs):
            return "[" + " ".join(f'"{x}"' for x in xs) + "]"

        expr = (f'(fairnessMetrics "{self.model_id}" {frame.frame_id} '
                f"{_sl(protected_columns)} "
                f"{_sl(reference) if reference else '[]'} "
                f'"{favorable_class}")')
        j = rapids(expr)
        return {name: H2OFrame._by_id(f["key"]["name"])
                for name, f in zip(j["map_keys"]["string"], j["frames"])}

    def scoring_history(self, use_pandas: bool = True):
        """The model's scoring-history table (`model.scoring_history()`)."""
        sh = ((self._schema or {}).get("output") or {}).get("scoring_history")
        if sh and use_pandas:
            import pandas as pd

            return pd.DataFrame(sh)
        return sh

    def permutation_importance(self, frame: H2OFrame, metric: str = "AUTO",
                               n_repeats: int = 1, seed: int = -1):
        j = connection().request(
            "POST", "/3/PermutationVarImp",
            params={"model_id": self.model_id, "frame_id": frame.frame_id,
                    "metric": metric, "n_repeats": n_repeats, "seed": seed})
        return j["permutation_varimp"]

    def download_mojo(self, path: str = ".") -> str:
        j = connection().request(
            "GET", f"/3/Models/{urllib.parse.quote(self.model_id)}/mojo",
            params={"dir": path})
        return j["dir"]

    def __repr__(self):
        return f"H2OModelClient({self.model_id})"


def _train_body(params: dict, x, y, training_frame, validation_frame,
                kw: dict) -> dict:
    """Assemble the training POST body shared by estimators and grid search:
    frame-valued params ride the wire as their keys (the server resolves them
    back to Frames), and ``x`` maps to ignored_columns h2o-py-style (names or
    integer indices)."""
    body = dict(params)
    body.update(kw)
    body = {k: (v.frame_id if isinstance(v, H2OFrame) else v)
            for k, v in body.items()}
    if training_frame is not None:
        body["training_frame"] = training_frame.frame_id
    if validation_frame is not None:
        body["validation_frame"] = validation_frame.frame_id
    frame_for_names = training_frame if training_frame is not None \
        else validation_frame
    if y is not None:
        if isinstance(y, int):  # h2o-py accepts a column index for y
            y = frame_for_names.columns[y]
        body["response_column"] = y
    if x is not None:
        all_cols = frame_for_names.columns
        keep = {all_cols[c] if isinstance(c, int) else c for c in x}
        body["ignored_columns"] = [c for c in all_cols
                                   if c not in keep and c != y]
    return body


#: algo -> parameter-name set for client-side validation (None = unknown,
#: keep trying). Module-level by ALGO, never a class attribute: a class-
#: level cache would leak through inheritance and poison every subclass.
_VALID_PARAMS: dict = {}


def _valid_param_names(algo: str) -> set | None:
    """Per-algo parameter names — the client-side validation surface
    h2o-py's generated estimators carry (`estimator_base.py` rejects
    unknown kwargs locally). Connected clients read the server's
    `/3/ModelBuilders/{algo}` metadata (no local modeling-stack import);
    in-process sessions fall back to the registry. None when neither is
    reachable — validation is skipped, the server still rejects."""
    if algo in _VALID_PARAMS:
        return _VALID_PARAMS[algo]
    names = None
    if _conn is not None:
        try:
            meta = _conn.request("GET", f"/3/ModelBuilders/{algo}")
            names = {p["name"] for p in meta.get("parameters", [])}
        except Exception:
            names = None
    if names is None:
        try:
            from ..models import registry

            entry = registry.lookup(algo)
            if entry is not None:
                import dataclasses

                names = {f.name for f in dataclasses.fields(entry[1])}
        except Exception:
            names = None
    if names:
        _VALID_PARAMS[algo] = names
    return names


class H2OEstimator:
    """Base estimator: collects kwargs, posts to /3/ModelBuilders/{algo},
    polls the job, exposes the trained model. Unknown keyword arguments
    fail at CONSTRUCTION with the valid-names list, like h2o-py's
    generated per-algo estimators."""

    algo = None

    def __init__(self, **params):
        cls = type(self)
        valid = _valid_param_names(self.algo) if self.algo else None
        if valid:
            unknown = sorted(k for k in params if k not in valid)
            if unknown:
                import difflib

                hints = {
                    k: difflib.get_close_matches(k, valid, n=1)
                    for k in unknown}
                hint_txt = "; ".join(
                    f"'{k}'" + (f" (did you mean '{m[0]}'?)" if m else "")
                    for k, m in hints.items())
                raise TypeError(
                    f"{cls.__name__} got unknown parameter(s) {hint_txt}. "
                    f"Valid parameters: {sorted(valid)}")
        self._params = params
        self._model: H2OModelClient | None = None

    def train(self, x=None, y=None, training_frame: H2OFrame | None = None,
              validation_frame: H2OFrame | None = None, **kw):
        from ..utils import telemetry

        body = _train_body(dict(self._params), x, y, training_frame,
                           validation_frame, kw)
        # the job's root span on the client: `_send` attaches it as
        # `traceparent`, so the server's rest.request and train.* spans
        # carry this trace id (an enclosing caller span stays the root)
        with telemetry.span("client.train", algo=self.algo):
            job = connection().request(
                "POST", f"/3/ModelBuilders/{self.algo}", data=body)
            done = _poll_job(job)
            self._model = get_model(done["dest"]["name"])
        return self

    # delegate model accessors
    def __getattr__(self, name):
        if self._model is None:
            raise AttributeError(f"train() first (no model for {name})")
        return getattr(self._model, name)

    @property
    def model_id(self):
        return self._model.model_id if self._model else None


def _estimator(algo: str, clsname: str) -> type:
    return type(clsname, (H2OEstimator,), {"algo": algo})


# the h2o-py estimator names (`h2o-py/h2o/estimators/__init__.py`)
H2OGradientBoostingEstimator = _estimator("gbm", "H2OGradientBoostingEstimator")
H2ORandomForestEstimator = _estimator("drf", "H2ORandomForestEstimator")
H2OXGBoostEstimator = _estimator("xgboost", "H2OXGBoostEstimator")


class H2OGeneralizedLinearEstimator(H2OEstimator):
    algo = "glm"

    @staticmethod
    def getGLMRegularizationPath(model) -> dict:
        """`H2OGeneralizedLinearEstimator.getGLMRegularizationPath`
        (h2o-py): per lambda fitted, the coefficients (natural and
        standardised scale, by name) and the explained deviance."""
        x = connection().request("GET", "/3/GetGLMRegPath",
                                 params={"model": _model_id_of(model)})
        ns = x["coefficient_names"]
        return {
            "lambdas": x["lambdas"], "alphas": x["alphas"],
            "explained_deviance_train": x["explained_deviance_train"],
            "explained_deviance_valid": x["explained_deviance_valid"],
            "coefficients": [dict(zip(ns, c)) for c in x["coefficients"]],
            "coefficients_std": [dict(zip(ns, c))
                                 for c in x["coefficients_std"]]}


H2OGeneralizedAdditiveEstimator = _estimator("gam", "H2OGeneralizedAdditiveEstimator")
H2ODeepLearningEstimator = _estimator("deeplearning", "H2ODeepLearningEstimator")
H2OKMeansEstimator = _estimator("kmeans", "H2OKMeansEstimator")
H2OPrincipalComponentAnalysisEstimator = _estimator("pca", "H2OPrincipalComponentAnalysisEstimator")
H2OSingularValueDecompositionEstimator = _estimator("svd", "H2OSingularValueDecompositionEstimator")
H2OGeneralizedLowRankEstimator = _estimator("glrm", "H2OGeneralizedLowRankEstimator")
H2ONaiveBayesEstimator = _estimator("naivebayes", "H2ONaiveBayesEstimator")
H2OIsolationForestEstimator = _estimator("isolationforest", "H2OIsolationForestEstimator")
H2OExtendedIsolationForestEstimator = _estimator("extendedisolationforest", "H2OExtendedIsolationForestEstimator")
H2OCoxProportionalHazardsEstimator = _estimator("coxph", "H2OCoxProportionalHazardsEstimator")
H2OIsotonicRegressionEstimator = _estimator("isotonicregression", "H2OIsotonicRegressionEstimator")
H2OStackedEnsembleEstimator = _estimator("stackedensemble", "H2OStackedEnsembleEstimator")
H2ORuleFitEstimator = _estimator("rulefit", "H2ORuleFitEstimator")
H2OSupportVectorMachineEstimator = _estimator("psvm", "H2OSupportVectorMachineEstimator")
H2OWord2vecEstimator = _estimator("word2vec", "H2OWord2vecEstimator")
H2OUpliftRandomForestEstimator = _estimator("upliftdrf", "H2OUpliftRandomForestEstimator")
H2ODecisionTreeEstimator = _estimator("decisiontree", "H2ODecisionTreeEstimator")
H2OAdaBoostEstimator = _estimator("adaboost", "H2OAdaBoostEstimator")
H2OANOVAGLMEstimator = _estimator("anovaglm", "H2OANOVAGLMEstimator")
H2OModelSelectionEstimator = _estimator("modelselection", "H2OModelSelectionEstimator")
H2OTargetEncoderEstimator = _estimator("targetencoder", "H2OTargetEncoderEstimator")
H2OAggregatorEstimator = _estimator("aggregator", "H2OAggregatorEstimator")
H2OInfogram = _estimator("infogram", "H2OInfogram")
H2OGenericEstimator = _estimator("generic", "H2OGenericEstimator")


# ---------------------------------------------------------------------------
# Grid search over REST (`h2o-py/h2o/grid/grid_search.py` surface over
# `POST /99/Grid/{algo}` + `GET /99/Grids/{id}`)
# ---------------------------------------------------------------------------
class H2OGridSearch:
    """h2o-py-compatible grid search: wraps an estimator (instance or class),
    posts the hyper space, polls the job, exposes ranked models."""

    def __init__(self, model, hyper_params: dict, grid_id: str | None = None,
                 search_criteria: dict | None = None, parallelism: int = 1):
        self.model = model() if isinstance(model, type) else model
        #: estimator class the contained models present as — h2o-py's grid
        #: yields estimator instances (`for m in grid: isinstance(m, Est)`)
        self._est_cls = model if isinstance(model, type) else type(model)
        self.hyper_params = hyper_params
        self.grid_id = grid_id
        self.search_criteria = search_criteria or {}
        self.parallelism = parallelism
        self._grid_json: dict | None = None

    def train(self, x=None, y=None, training_frame: "H2OFrame | None" = None,
              validation_frame: "H2OFrame | None" = None, **kw):
        import json as _json

        body = _train_body(dict(getattr(self.model, "_params", {})),
                           x, y, training_frame, validation_frame, kw)
        body["hyper_parameters"] = _json.dumps(self.hyper_params)
        if self.search_criteria:
            body["search_criteria"] = _json.dumps(self.search_criteria)
        if self.grid_id:
            body["grid_id"] = self.grid_id
        if self.parallelism != 1:
            body["parallelism"] = self.parallelism
        job = connection().request("POST", f"/99/Grid/{self.model.algo}",
                                   data=body)
        done = _poll_job(job)
        self.grid_id = done["dest"]["name"]
        self._fetch()
        return self

    def _fetch(self, sort_by: str | None = None, decreasing: bool | None = None):
        params = {}
        if sort_by:
            params["sort_by"] = sort_by
        if decreasing is not None:
            params["decreasing"] = str(bool(decreasing)).lower()
        self._grid_json = connection().request(
            "GET", f"/99/Grids/{urllib.parse.quote(self.grid_id)}",
            params=params)
        return self._grid_json

    @property
    def model_ids(self) -> list:
        return [k["name"] for k in self._grid_json["model_ids"]]

    @property
    def models(self) -> list:
        out = []
        for mid in self.model_ids:
            est = self._est_cls()
            est._model = get_model(mid)
            out.append(est)
        return out

    def __iter__(self):
        return iter(self.models)

    def __len__(self) -> int:
        return len(self.model_ids)

    def __getitem__(self, i):
        # one REST fetch for one model, not len(grid) of them
        est = self._est_cls()
        est._model = get_model(self.model_ids[i])
        return est

    def get_grid(self, sort_by: str | None = None, decreasing: bool = False):
        self._fetch(sort_by, decreasing)
        return self

    def summary_table(self):
        return self._grid_json.get("summary_table")

    @property
    def failure_details(self) -> list:
        return self._grid_json.get("failure_details", [])


def save_grid(grid: "H2OGridSearch", grid_directory: str) -> str:
    """`h2o.save_grid` — `POST /3/Grid.bin/{grid_id}/export`."""
    connection().request(
        "POST", f"/3/Grid.bin/{urllib.parse.quote(grid.grid_id)}/export",
        data={"grid_directory": grid_directory})
    return grid_directory


def load_grid(grid_directory: str) -> "H2OGridSearch":
    """`h2o.load_grid` — `POST /3/Grid.bin/import`. The rebuilt handle keeps
    the original algo (from the server-side manifest), so it can continue
    training with a fresh hyper space."""
    j = connection().request("POST", "/3/Grid.bin/import",
                             data={"grid_path": grid_directory})
    gid = j["name"]
    detail = connection().request(
        "GET", f"/99/Grids/{urllib.parse.quote(gid)}")
    est = H2OEstimator()
    est.algo = detail.get("algo")
    gs = H2OGridSearch(model=est, hyper_params={}, grid_id=gid)
    gs._grid_json = detail
    return gs


# ---------------------------------------------------------------------------
# AutoML over REST (`h2o-py/h2o/automl/_estimator.py` surface over
# `POST /99/AutoMLBuilder` + `GET /99/Leaderboards/{project}`)
# ---------------------------------------------------------------------------
class H2OAutoML:
    def __init__(self, max_models: int = 0, max_runtime_secs: float = 0.0,
                 max_runtime_secs_per_model: float = 0.0, nfolds: int = 5,
                 seed: int | None = None, project_name: str | None = None,
                 include_algos: list | None = None,
                 exclude_algos: list | None = None,
                 sort_metric: str | None = None,
                 stopping_rounds: int = 3, stopping_tolerance: float = 1e-3,
                 stopping_metric: str = "AUTO"):
        self.build_control = {
            "project_name": project_name,
            "nfolds": nfolds,
            "stopping_criteria": {
                "max_models": max_models,
                "max_runtime_secs": max_runtime_secs,
                "max_runtime_secs_per_model": max_runtime_secs_per_model,
                "seed": -1 if seed is None else seed,
                "stopping_rounds": stopping_rounds,
                "stopping_tolerance": stopping_tolerance,
                "stopping_metric": stopping_metric,
            },
        }
        self.build_models = {"include_algos": include_algos,
                             "exclude_algos": exclude_algos}
        self.sort_metric = sort_metric
        self.project_name = project_name
        self._leaderboard_json: dict | None = None

    def train(self, x=None, y=None,
              training_frame: "H2OFrame | None" = None, **kw):
        if kw:
            raise ValueError(
                f"unsupported H2OAutoML.train() arguments: {sorted(kw)} — "
                "supported: x, y, training_frame")
        spec = {"training_frame": training_frame.frame_id,
                "response_column": y}
        if self.sort_metric:
            spec["sort_metric"] = self.sort_metric
        if x is not None:
            all_cols = training_frame.columns
            keep = {all_cols[c] if isinstance(c, int) else c for c in x}
            spec["ignored_columns"] = [c for c in all_cols
                                       if c not in keep and c != y]
        resp = connection().request("POST", "/99/AutoMLBuilder", data={
            "input_spec": spec,
            "build_control": self.build_control,
            "build_models": self.build_models,
        })
        self.project_name = resp["build_control"]["project_name"]
        _poll_job(resp)
        self._fetch()
        return self

    def _fetch(self):
        self._leaderboard_json = connection().request(
            "GET", f"/99/Leaderboards/{urllib.parse.quote(self.project_name)}")
        return self._leaderboard_json

    @property
    def leaderboard(self):
        return self._leaderboard_json["table"]

    @property
    def leader(self) -> "H2OModelClient":
        models = self._leaderboard_json["models"]
        if not models:
            raise ValueError("no models trained")
        return get_model(models[0]["name"])

    def predict(self, test_data: "H2OFrame"):
        return self.leader.predict(test_data)

    def event_log(self) -> dict:
        j = connection().request(
            "GET", f"/99/AutoML/{urllib.parse.quote(self.project_name)}")
        return j["event_log_table"]
