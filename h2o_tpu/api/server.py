"""REST API server — analog of `water/api/RequestServer.java` (:56,80,157).

Routes follow the reference's versioned URL scheme (`/3/...`, `/99/Rapids`;
128 endpoints registered in `water/api/RegisterV3Api.java` — the subset here
covers the paths the Python client actually drives: cloud status, import/
parse, frames, model builders, models, predictions, jobs, rapids, logs,
timeline, shutdown). Built on the stdlib ThreadingHTTPServer: the control
plane is host-side Python; all bulk compute the handlers trigger runs on the
device mesh (SURVEY.md §2.5 — REST/job control on host CPUs, data plane on
ICI).

Request/response bodies are schema-v3-shaped JSON (see schemas.py). Errors
return the reference's H2OErrorV3 shape with http status codes
(`water/api/RequestServer.java` error handling).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import traceback
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from .. import __version__
from ..backend.jobs import Job
from ..backend.kvstore import STORE, make_key
from ..frame.frame import Frame
from ..frame.vec import Vec
from ..models import registry
from ..rapids.exec import Rapids, Session
from ..workload import WorkloadAdmissionError
from . import schemas

_SESSIONS: dict[str, Session] = {}
#: `/3/SessionProperties` store, keyed (session_key, property) —
#: `water/rapids/Session` attributes in the reference
_SESSION_PROPS: dict[tuple[str, str], str | None] = {}
#: on-frame metric recomputes, keyed (model_id, frame_id) — the reference
#: keeps ModelMetrics objects in the DKV under a model×frame checksum key;
#: the listing/fetch/DELETE ModelMetrics routes operate on this. Guarded by
#: a lock: ThreadingHTTPServer serves requests concurrently.
_METRICS_CACHE: dict[tuple[str, str], object] = {}
_METRICS_LOCK = threading.Lock()


def _metrics_cache_items() -> list:
    """Live cache entries; entries whose model or frame died since caching
    are purged here so a long-lived server can't accumulate garbage."""
    with _METRICS_LOCK:
        items = list(_METRICS_CACHE.items())
        dead = [(m, f) for (m, f), _ in items
                if STORE.get(m) is None or STORE.get(f) is None]
        for k in dead:
            del _METRICS_CACHE[k]
        return [(k, v) for k, v in items if k not in dead]


class H2OServer:
    """Server lifecycle — `water/H2O.main` + Jetty boot analog."""

    def __init__(self, port: int = 54321, name: str = "h2o_tpu",
                 hash_login: dict | str | None = None,
                 ssl_certfile: str | None = None,
                 ssl_keyfile: str | None = None,
                 auth_check=None, negotiate_auth=None):
        """`hash_login`: {user: sha256-hex-or-plain} dict or a realm file of
        `user:sha256hex` lines — the `-hash_login` basic-auth analog
        (`h2o-security`, `water/webserver/H2OHttpViewImpl` auth hook).
        `auth_check`: a callable `(user, password) -> bool` verifying Basic
        credentials against an external directory — pass
        `h2o_tpu.utils.ldap.LdapAuth(...)` for the `-ldap_login` role (the
        pluggable seam JAAS login modules fill in the reference).
        `ssl_certfile`/`ssl_keyfile` terminate TLS on the REST socket — the
        `-jks`/https role of `water/network/SSLSocketChannelFactory`."""
        if sum(x is not None and x != {} for x in
               (auth_check, hash_login, negotiate_auth)) > 1:
            raise ValueError("hash_login, auth_check and negotiate_auth are "
                             "mutually exclusive — one mechanism owns the "
                             "port, like the reference's login-module flags")
        self.auth_check = auth_check
        #: SPNEGO acceptor (`utils/krb.py` SpnegoAuth) — the `-spnego_login`
        #: role; when set, 401s advertise `WWW-Authenticate: Negotiate`
        self.negotiate_auth = negotiate_auth
        self.port = port
        self.name = name
        self.started_at = time.time()
        #: last REST activity stamp — `/3/SteamMetrics` idle_millis source
        self.last_activity = self.started_at
        #: `POST /3/CloudLock` reason (`water/Paxos.lockCloud`); the cloud
        #: here is born locked (single controller) so this is bookkeeping
        self.locked_reason: str | None = "new cloud"
        self.httpd: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        self.ssl_certfile = ssl_certfile
        self.ssl_keyfile = ssl_keyfile
        if isinstance(hash_login, str):
            creds = {}
            with open(hash_login) as f:
                for line in f:
                    if ":" in line:
                        u, _, h = line.strip().partition(":")
                        creds[u] = h
            hash_login = creds
        self.hash_login = hash_login

    def check_auth(self, header: str | None) -> bool:
        if self.negotiate_auth is not None:
            return self.negotiate_auth.check_header(header) is not None
        if not self.hash_login and self.auth_check is None:
            return True
        if not header or not header.startswith("Basic "):
            return False
        import base64
        import hashlib
        import hmac
        import re

        try:
            user, _, pw = base64.b64decode(
                header[6:]).decode().partition(":")
        except Exception:
            return False
        if self.auth_check is not None:
            return bool(self.auth_check(user, pw))
        expect = self.hash_login.get(user)
        if expect is None:
            return False
        # a 64-hex entry is a stored sha256 — compare digests only, so the
        # realm file never doubles as a usable credential (no pass-the-hash)
        if re.fullmatch(r"[0-9a-f]{64}", expect):
            digest = hashlib.sha256(pw.encode()).hexdigest()
            return hmac.compare_digest(digest, expect)
        return hmac.compare_digest(pw, expect)

    def start(self) -> "H2OServer":
        handler = _make_handler(self)
        # port scan upward like the reference (`NetworkInit` baseport search)
        last_err = None
        for port in range(self.port, self.port + 20):
            try:
                self.httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
                self.port = port
                break
            except OSError as e:
                last_err = e
        if self.httpd is None:
            raise last_err
        if self.ssl_certfile:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.ssl_certfile, self.ssl_keyfile)
            self.httpd.socket = ctx.wrap_socket(self.httpd.socket,
                                                server_side=True)
        # the acceptor owns no spans and serves EVERY request's context —
        # carrying the boot thread's trace into it would fabricate
        # causality
        self._thread = threading.Thread(  # graftlint: disable=thread-without-trace-context
            target=self.httpd.serve_forever, daemon=True, name="h2o-rest")
        self._thread.start()
        # arm the watchdog supervisor with the server (idempotent; no-op
        # unless H2O_TPU_WATCHDOG_MS > 0) — hung jobs / stalled dispatch /
        # Cleaner thrash / queue stalls become typed events + bundles
        from ..utils import watchdog

        watchdog.ensure_started()
        return self

    def stop(self):
        if self.httpd:
            self.httpd.shutdown()
            self.httpd.server_close()
            self.httpd = None
        if self._thread is not None:
            # serve_forever returns once shutdown() lands; drain the
            # acceptor thread so stop() means STOPPED (graftlint
            # unjoined-thread GL17-server-thread)
            self._thread.join(timeout=5.0)
            self._thread = None

    @property
    def url(self) -> str:
        scheme = "https" if self.ssl_certfile else "http"
        return f"{scheme}://127.0.0.1:{self.port}"


def _truthy(v) -> bool:
    return str(v).lower() in ("true", "1", "yes")


def _err(status: int, msg: str, **extra) -> tuple[int, dict]:
    return status, {"__meta": {"schema_type": "H2OError"},
                    "error_url": "", "msg": msg, "dev_msg": msg,
                    "http_status": status, "exception_msg": msg, **extra}


def _export_path(p: dict, default_name: str, what: str,
                 force_default: bool = False):
    """Shared dir-export contract (Models.bin / Models.mojo / Models json /
    frame export all follow `ModelsHandler`'s): strip file://, append
    `default_name` when the target is a directory, refuse an existing file
    unless force. Returns (path, None) on success or (None, error reply)."""
    path = p.get("dir", "")
    if not path:
        return None, _err(400, f"{what}: dir is required")
    if path.startswith("file://"):
        path = path[len("file://"):]
    if "://" not in path:  # remote URIs ride the Persist SPI untouched
        if os.path.isdir(path) or path.endswith(os.sep):
            os.makedirs(path, exist_ok=True)
            path = os.path.join(path, default_name)
        force = _truthy(p["force"]) if "force" in p else force_default
        if not force and os.path.exists(path):
            return None, _err(400, f"{what}: {path} exists (use force)")
    return path, None


_FRAME_PARAMS = ("training_frame", "validation_frame", "blending_frame",
                 "calibration_frame", "pre_trained")


def _resolve_params(params_cls, body: dict, extra_names=()) -> dict:
    """Validate body keys against the Parameters dataclass (the reference's
    412 on unknown params) and resolve frame keys to Frames. Shared by the
    ModelBuilders and Grid routes so frame-param handling can't drift."""
    import dataclasses

    valid = {f.name for f in dataclasses.fields(params_cls)}
    unknown = [k for k in body if k not in valid] + \
              [k for k in extra_names if k not in valid]
    if unknown:
        raise ValueError(f"unknown parameter(s) {unknown} for this algorithm")
    return {k: (STORE.get(v) if k in _FRAME_PARAMS else v)
            for k, v in body.items()}


def _jobs_of(algo_cls, params_cls, body: dict) -> tuple[int, dict]:
    kwargs = _resolve_params(params_cls, body)
    builder = algo_cls(params_cls(**kwargs))
    job = builder.train(background=True)
    return 200, {"job": schemas.job_schema(job),
                 "key": schemas.key_schema(job.key)}


def _make_handler(server: H2OServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # route to our logger, not stderr
            from ..utils.log import debug

            debug(f"REST {self.address_string()} {fmt % args}")

        # -- plumbing --------------------------------------------------------
        def _reply(self, status: int, payload: dict):
            filename = None
            extra_headers = payload.pop("__headers__", None)
            if "__html__" in payload:
                data = payload["__html__"].encode()
                ctype = "text/html; charset=utf-8"
            elif "__raw__" in payload:
                # non-JSON bodies (DownloadDataset's CSV, Models.fetch.bin)
                data = payload["__raw__"]
                if isinstance(data, str):
                    data = data.encode()
                ctype = payload.get("__ctype__", "text/plain")
                filename = payload.get("__filename__")
            else:
                data = json.dumps(payload).encode()
                ctype = "application/json"
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            for hk, hv in (extra_headers or {}).items():
                # route-supplied headers (Serving's Retry-After); values are
                # server-generated numbers/tokens, never client echoes
                self.send_header(hk, str(hv))
            if filename:
                # frame keys are client-controlled; anything outside a safe
                # charset could malform the header or inject CR/LF
                safe = re.sub(r"[^A-Za-z0-9._-]", "_", str(filename))
                self.send_header("Content-Disposition",
                                 f'attachment; filename="{safe}"')
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            if not getattr(self, "_suppress_body", False):
                self.wfile.write(data)

        def _body(self) -> dict:
            n = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(n).decode() if n else ""
            if not raw:
                return {}
            ctype = self.headers.get("Content-Type", "")
            if "json" in ctype:
                return json.loads(raw)
            return {k: v[0] if len(v) == 1 else v
                    for k, v in urllib.parse.parse_qs(raw).items()}

        def _route(self, method: str):
            # handler instances persist across keep-alive requests; a HEAD
            # must not leave the suppress-body flag set for the next request
            head_only = getattr(self, "_head_only", False)
            self._head_only = False
            self._suppress_body = head_only
            if not server.check_auth(self.headers.get("Authorization")):
                # drain any request body first — a keep-alive client will
                # reuse this socket for the re-authed retry, and leftover
                # body bytes would corrupt its next request line
                n = int(self.headers.get("Content-Length") or 0)
                if n:
                    self.rfile.read(n)
                self.send_response(401)
                challenge = ("Negotiate" if server.negotiate_auth is not None
                             else 'Basic realm="h2o_tpu"')
                self.send_header("WWW-Authenticate", challenge)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            parsed = urllib.parse.urlparse(self.path)
            parts = [p for p in parsed.path.split("/") if p]
            query = {k: v[0] if len(v) == 1 else v
                     for k, v in urllib.parse.parse_qs(parsed.query).items()}
            # monitoring polls don't count as activity for SteamMetrics'
            # idle clock (`water/api/SteamMetricsHandler` semantics);
            # Health rides the same exclusion — a 1s readiness prober
            # must not look like user traffic or cycle the timeline ring.
            # Timeline too: since the ?since= cursor exists precisely for
            # frequent polling, a timeline poll recording a timeline
            # event would FEED the ring it drains — every pull returns
            # the event of the previous pull and the cursor never
            # catches up (observed driving the cursor loop end-to-end)
            head = parts[1] if len(parts) > 1 else (parts[0] if parts else "")
            is_monitor_poll = head in ("Cloud", "Ping", "Jobs",
                                       "SteamMetrics", "Sample", "Health",
                                       "Timeline", "Workload")
            if not is_monitor_poll:
                server.last_activity = time.time()
            if method == "POST" and parts and \
                    parts[-1] in ("PostFile", "PostFile.bin"):
                # binary body — must not go through the text _body() path
                try:
                    status, payload = _post_file(self, query)
                except Exception as e:  # noqa: BLE001
                    status, payload = _err(500, repr(e),
                                           stacktrace=traceback.format_exc())
                self._reply(status, payload)
                return
            import contextlib

            from ..utils import slowtrace, telemetry

            t_route = time.perf_counter()
            # wire trace propagation: an incoming W3C-style traceparent
            # (attached by api/client.py _send) roots this request's span
            # under the REMOTE caller's trace — same trace id reused,
            # remote parent recorded — so client→REST→job→chunk spans
            # merge into one Perfetto session across processes. Non-
            # monitor requests also ride the tail-based slow-request
            # capture: the request span tree persists when the wall
            # breaches the rest.request SLO p99 (GET /3/SlowTraces).
            tp_header = self.headers.get("traceparent")
            capture = (contextlib.nullcontext() if is_monitor_poll
                       else slowtrace.request(
                           "rest.request", f"{method} {parsed.path}",
                           endpoint=head, remote=int(bool(tp_header))))
            with telemetry.remote_context(tp_header), capture as _cap:
                try:
                    from ..utils import failpoints

                    # read the body BEFORE the failpoint (or any other
                    # early reply) can short-circuit routing: on a
                    # keep-alive connection, unread body bytes would be
                    # parsed as the NEXT request's start line — a
                    # wire-protocol desync the pooled client turns from
                    # latent to immediate
                    body = (self._body() if method in ("POST", "PUT")
                            else {})
                    failpoints.hit("rest.route")
                    # tenant identity rides the request: every Job/quota
                    # decision under this route sees the caller's tenant
                    # (X-H2O-TPU-Tenant, attached by api/client.py) and
                    # requested priority lane
                    from ..workload import tenants as _tenants

                    with _tenants.request_scope(
                            self.headers.get("X-H2O-TPU-Tenant"),
                            self.headers.get("X-H2O-TPU-Priority")):
                        status, payload = route(server, method, parts,
                                                query, body)
                except failpoints.InjectedHTTPError as e:
                    # deterministic flaky-server injection: reply the
                    # injected status; 429/503 carry Retry-After so client
                    # retry paths can be driven end-to-end over a socket
                    status, payload = _err(e.status, str(e))
                    if e.status in (429, 503):
                        payload["__headers__"] = {
                            "Retry-After": f"{e.retry_after_s:g}"}
                except WorkloadAdmissionError as e:
                    # over-quota tenant submission — ONE central mapping
                    # covers every submitting route (model builds, grids,
                    # AutoML): retryable-later, other tenants untouched
                    status, payload = _err(
                        429, str(e), error_type="quota_rejected",
                        tenant=e.tenant,
                        retry_after_s=round(e.retry_after_s, 3),
                        cost_bytes=e.cost_bytes,
                        quota_bytes=e.quota_bytes)
                    payload["__headers__"] = {
                        "Retry-After": max(1, int(np.ceil(e.retry_after_s)))}
                except KeyError as e:
                    status, payload = _err(404, str(e))
                except (ValueError, TypeError) as e:
                    status, payload = _err(400, str(e))
                except Exception as e:  # noqa: BLE001 — as H2OError
                    status, payload = _err(500, repr(e),
                                           stacktrace=traceback.format_exc())
                    from ..utils.log import err as _log_err

                    # a 500 that only ever reached the wire was invisible
                    # to /3/Logs — now the ring keeps it
                    _log_err(f"{method} {parsed.path} -> 500: {e!r}")
                if _cap is not None and status >= 500:
                    # a 500 reply IS an SLO error even though no exception
                    # unwinds this handler
                    _cap.note_error()
            # every routed request lands in the registry (the reference
            # TimeLine records every RPC packet; the REST control plane is
            # this repo's packet stream) — but monitoring polls stay OUT of
            # the ring: a client polling /3/Jobs at 50ms would cycle the
            # 4096-event ring and evict the very training spans the
            # endpoint exists to show
            telemetry.inc("rest.request.count")
            if status >= 500:
                telemetry.inc("rest.error.count")
            telemetry.observe("rest.request.seconds",
                              time.perf_counter() - t_route)
            if not is_monitor_poll:
                from ..utils import timeline as _timeline

                _timeline.record("rest", f"{method} {parsed.path}",
                                 status=status)
            self._reply(status, payload)

        def do_GET(self):
            self._route("GET")

        def do_POST(self):
            self._route("POST")

        def do_DELETE(self):
            self._route("DELETE")

        def do_HEAD(self):
            # `HEAD /3/Cloud` (RegisterV3Api) — same handler as GET, no body
            self._head_only = True
            self._route("GET")

    return Handler


# ---------------------------------------------------------------------------
# routing table (`RequestServer.java:157` route registration)
# ---------------------------------------------------------------------------
def _post_file(handler, query: dict) -> tuple[int, dict]:
    """`POST /3/PostFile[.bin]` (`water/api/PostFileServlet.java:14`): spool
    the pushed bytes server-side and register them in the DKV under
    ``destination_frame``. A raw body streams to disk in 1MB chunks; a
    multipart/form-data body (what h2o-py's requests layer sends) is parsed
    with the stdlib email machinery."""
    from ..backend.kvstore import STORE, make_key
    from ..io import upload

    n = int(handler.headers.get("Content-Length") or 0)
    ctype = handler.headers.get("Content-Type", "")
    dest = query.get("destination_frame") or make_key("upload")
    fname = query.get("filename", "")
    # stream the request body to disk first — a multi-GB push (raw OR
    # multipart) must never materialize in server memory
    path, total = upload.spool_stream(handler.rfile, n)
    if ctype.startswith("multipart/"):
        raw = path
        try:
            path, total, part_name = upload.extract_multipart(raw, ctype)
        finally:
            os.unlink(raw)
        fname = fname or part_name
    with open(path, "rb") as fh:
        head = fh.read(8)
    suffix = upload.guess_suffix(fname, dest, head=head)
    if suffix != ".bin":
        os.replace(path, path[:-len(".bin")] + suffix)
        path = path[:-len(".bin")] + suffix
    uf = upload.UploadedFile(dest, path, total,
                             name=fname or os.path.basename(path))
    STORE.put(dest, uf)
    return 200, {"destination_frame": dest, "total_bytes": total}


#: ParseV3 wire type names → Vec type strings (`water/parser/ParseSetup`)
_PARSE_TYPES = {"numeric": "real", "real": "real", "double": "real",
                "float": "real", "int": "int", "enum": "enum",
                "categorical": "enum", "factor": "enum", "string": "string",
                "time": "time", "uuid": "string", "unknown": "real"}


def _parse_setup_of(p: dict):
    """Build a ParseSetup from a ParseV3 request body (`water/api/
    ParseHandler` applies the client's overrides the same way); returns None
    when the body carries no overrides so guessing stays in charge."""
    from ..io.parser import ParseSetup

    names = p.get("column_names") or None
    if isinstance(names, str):
        names = json.loads(names) if names.startswith("[") else [names]
    ctypes = p.get("column_types") or None
    if isinstance(ctypes, str):
        ctypes = json.loads(ctypes) if ctypes.startswith("[") else [ctypes]
    nas = p.get("na_strings") or None
    if isinstance(nas, str):
        nas = json.loads(nas) if nas.startswith("[") else [nas]
    check_header = p.get("check_header")
    sep = p.get("separator")
    if not any(v is not None for v in (names, ctypes, nas, check_header,
                                       sep)):
        return None
    tmap = None
    if isinstance(ctypes, dict):
        tmap = {k: _PARSE_TYPES.get(str(v).lower(), "real")
                for k, v in ctypes.items()}
    elif ctypes is not None and names:
        tmap = {n: _PARSE_TYPES.get(str(t).lower(), "real")
                for n, t in zip(names, ctypes) if t is not None}
    header = None
    if check_header is not None:
        check_header = int(check_header)
        header = True if check_header == 1 else \
            False if check_header == -1 else None
    if isinstance(sep, int):
        sep = chr(sep)
    return ParseSetup(separator=sep, header=header, column_names=names,
                      column_types=tmap, na_strings=nas)


def _csv_head_preview(path: str, setup) -> tuple[list, list]:
    """(column names, guessed types) from the first lines of a CSV — the
    ParseSetup preview. Transparent for gz/zip heads via pyarrow streams."""
    import csv as _csv
    import io as _io

    try:
        if path.endswith(".gz"):
            import pyarrow as pa

            with pa.input_stream(path, compression="gzip") as st:
                head = st.read(1 << 16)
        elif path.endswith(".zip"):
            import zipfile as _zipfile

            with _zipfile.ZipFile(path) as zf:
                with zf.open(zf.namelist()[0]) as st:
                    head = st.read(1 << 16)
        else:
            with open(path, "rb") as fh:
                head = fh.read(1 << 16)
    except Exception:  # noqa: BLE001 — preview is best-effort
        return None, None
    lines = head.decode("utf-8", errors="replace").splitlines()
    rows = list(_csv.reader(_io.StringIO("\n".join(lines[:50])),
                            delimiter=setup.separator or ","))
    rows = [r for r in rows if r]
    if not rows:
        return None, None
    ncol = len(rows[0])
    names = rows[0] if setup.header else [f"C{i+1}" for i in range(ncol)]
    data = rows[1:] if setup.header else rows
    types = []
    for j in range(ncol):
        vals = [r[j] for r in data[:30] if j < len(r) and r[j].strip()]
        numeric = vals and all(_is_float(v) for v in vals)
        types.append("Numeric" if numeric else "Enum")
    return names, types


def _is_float(tok: str) -> bool:
    try:
        float(tok)
        return True
    except ValueError:
        return tok.strip().upper() in ("NA", "NAN", "")


def _resolve_upload(source: str) -> tuple[str, str]:
    """A Parse source may be a filesystem path OR the key of a PostFile
    upload; returns (path to read, display name whose extension drives
    parse-type guessing)."""
    from ..backend.kvstore import STORE
    from ..io.upload import UploadedFile

    obj = STORE.get(source)
    if isinstance(obj, UploadedFile):
        return obj.path, obj.name
    return source, source


def _maybe_decrypt(path: str, name: str, p: dict) -> tuple[str, str, str | None]:
    """When the request names a decrypt_tool (`ParseSetupV3.decrypt_tool`),
    run the source bytes through it into a temp file the parser reads —
    `water/parser/DecryptionTool.decryptionOf` in the reference's 2-pass
    parse. The plaintext name drops a trailing .aes/.enc so extension-based
    type guessing sees the real format. The third return is the temp path
    when one was created — the CALLER must unlink it after parsing so
    plaintext never outlives the request."""
    tool_id = p.get("decrypt_tool") or p.get("decrypt_tool_id")
    if not tool_id:
        return path, name, None
    from ..io.crypto import DecryptionTool

    tool = STORE.get(tool_id)
    if not isinstance(tool, DecryptionTool):
        raise KeyError(f"decrypt tool {tool_id!r} not found")
    import tempfile

    with open(path, "rb") as fh:
        plain = tool.decrypt(fh.read())
    for ext in (".aes", ".enc", ".encrypted"):
        if name.endswith(ext):
            name = name[:-len(ext)]
            break
    suffix = os.path.splitext(name)[1] or ".csv"
    tf = tempfile.NamedTemporaryFile(suffix=suffix, delete=False)
    tf.write(plain)
    tf.close()
    return tf.name, name, tf.name


def _serving_route(method: str, rest: list[str], p: dict) -> tuple[int, dict]:
    """`/3/Serving/...` — the online scoring runtime (`h2o_tpu/serving/`).

    - ``POST /3/Serving/models/{id}``: register + warm up (in-STORE model
      key via ``model_id``, or a MOJO zip/dir via ``mojo_file`` — a local
      path or a PostFile upload key).
    - ``DELETE /3/Serving/models/{id}``: unregister, stop its batcher.
    - ``POST /3/Serving/score``: row-dict scoring (``row`` or ``rows``);
      queue-full → 429 + Retry-After, deadline expiry → 408 — typed,
      never hanging.
    - ``GET /3/Serving/stats[/{id}]``: latency percentiles, throughput,
      batch occupancy, queue depth, recompile/rejection counters.
    - ``POST /3/Serving/routes/{endpoint}``: map a logical endpoint onto
      weighted model variants (canary split + shadow traffic);
      ``GET /3/Serving/routes[/{endpoint}]`` surfaces per-variant
      divergence stats, ``DELETE`` drops the route.
    - ``GET /3/Serving/control``: fleet quota, placements, routes.
    """
    from .. import serving
    from ..serving.errors import (AdmissionError, DeadlineExceededError,
                                  ModelNotRegisteredError, QueueFullError,
                                  RouteNotFoundError, ServingShutdownError,
                                  UnsupportedModelError)

    rt = serving.get_runtime()
    sub = rest[1] if len(rest) > 1 else ""

    if sub == "models" and len(rest) >= 3:
        sid = urllib.parse.unquote(rest[2])
        if method == "GET":
            try:
                return 200, schemas.serving_model_schema(rt.model(sid).info())
            except ModelNotRegisteredError as e:
                return _err(404, str(e))
        if method == "DELETE":
            try:
                rt.unregister(sid)
            except ModelNotRegisteredError as e:
                return _err(404, str(e))
            return 200, {"model_id": sid, "unregistered": True}
        if method == "POST":
            overrides = {k: p[k] for k in
                         ("buckets", "max_batch", "max_wait_us",
                          "queue_depth", "deadline_ms", "stats_window",
                          "priority", "replicas")
                         if p.get(k) not in (None, "")}
            if isinstance(overrides.get("buckets"), str):
                overrides["buckets"] = [
                    int(t) for t in overrides["buckets"].split(",")
                    if t.strip()]
            strict = _truthy(p.get("strict_levels"))
            try:
                if p.get("mojo_file"):
                    path, _name = _resolve_upload(str(p["mojo_file"]))
                    if not os.path.exists(path):
                        return _err(404, f"no MOJO at '{p['mojo_file']}'")
                    info = rt.register_mojo(path, sid, overrides=overrides,
                                            strict_levels=strict)
                else:
                    mid = p.get("model_id") or sid
                    model = STORE.get(mid)
                    if model is None:
                        return _err(404, f"model {mid} not found")
                    info = rt.register_model(model, sid,
                                             overrides=overrides,
                                             strict_levels=strict)
            except (UnsupportedModelError, NotImplementedError) as e:
                # NotImplementedError: a model's score_raw declares the
                # matrix path unsupported at trace time (GLM interactions)
                # — a client-input problem, not a server fault
                return _err(400, str(e), error_type="unsupported_model")
            except ValueError as e:
                return _err(400, str(e))
            except AdmissionError as e:
                # over-quota (or placement-OOM) — retryable-later, and
                # co-registered models are untouched by construction
                status, payload = _err(
                    429, str(e), error_type="admission_rejected",
                    retry_after_s=round(e.retry_after_s, 3),
                    cost_bytes=e.cost_bytes,
                    budget_bytes=e.budget_bytes)
                payload["__headers__"] = {
                    "Retry-After": max(1, int(np.ceil(e.retry_after_s)))}
                return status, payload
            return 200, schemas.serving_model_schema(info)

    if sub == "score" and method == "POST":
        sid = p.get("model_id", "")
        endpoint = p.get("endpoint", "")
        rows = p.get("rows")
        if rows is None:
            row = p.get("row")
            rows = [row] if row is not None else None
        if not rows or not all(isinstance(r, dict) for r in rows):
            return _err(400, "score needs 'row' (dict) or 'rows' "
                             "(list of dicts)")
        deadline_ms = p.get("deadline_ms")
        deadline_ms = (None if deadline_ms in (None, "")
                       else float(deadline_ms))
        served_by = sid
        try:
            if endpoint:
                # routed scoring: the router picks the serving variant
                # (weighted deterministic split) and feeds shadow traffic
                preds, served_by = rt.router.score(endpoint, rows,
                                                   deadline_ms=deadline_ms)
            else:
                preds = rt.score(sid, rows, deadline_ms=deadline_ms)
        except (ModelNotRegisteredError, RouteNotFoundError) as e:
            return _err(404, str(e))
        except ServingShutdownError as e:
            # raced a DELETE / re-registration: the looked-up lane died
            # under the request — retryable conflict, not a server fault
            return _err(409, str(e), error_type="model_shutdown")
        except (QueueFullError, AdmissionError) as e:
            # AdmissionError here: a cold model's lazy re-placement lost
            # the quota race — same retryable-later shape as queue-full
            status, payload = _err(
                429, str(e),
                error_type=("queue_full" if isinstance(e, QueueFullError)
                            else "admission_rejected"),
                retry_after_s=round(e.retry_after_s, 3))
            payload["__headers__"] = {
                "Retry-After": max(1, int(np.ceil(e.retry_after_s)))}
            return status, payload
        except DeadlineExceededError as e:
            return _err(408, str(e), error_type="deadline_exceeded")
        out = {"model_id": served_by, "predictions": preds,
               "count": len(preds)}
        if endpoint:
            out["endpoint"] = endpoint
        return 200, out

    if sub == "stats" and method == "GET":
        if len(rest) > 2:
            sid = urllib.parse.unquote(rest[2])
            try:
                snap = rt.stats(sid)
            except ModelNotRegisteredError as e:
                return _err(404, str(e))
            return 200, schemas.serving_stats_schema({sid: snap})
        return 200, schemas.serving_stats_schema(rt.stats())

    if sub == "models" and method == "GET":
        infos = []
        for mid in rt.model_ids():
            try:
                infos.append(rt.model(mid).info())
            except ModelNotRegisteredError:
                pass  # unregistered between the listing and the lookup
        return 200, {"models": infos}

    if sub == "routes":
        if len(rest) > 2:
            endpoint = urllib.parse.unquote(rest[2])
            if method == "POST":
                variants = p.get("variants")
                if isinstance(variants, dict):
                    # {model_id: weight} shorthand
                    variants = [{"model_id": k, "weight": v}
                                for k, v in variants.items()]
                if not isinstance(variants, list):
                    return _err(400, "route needs 'variants': a list of "
                                     "{model_id, weight[, shadow]} dicts")
                seed = p.get("seed")
                try:
                    st = rt.router.create_route(
                        endpoint, variants,
                        seed=None if seed in (None, "") else int(seed))
                except ModelNotRegisteredError as e:
                    return _err(404, str(e))
                except ValueError as e:
                    return _err(400, str(e))
                return 200, schemas.serving_route_schema(st)
            if method == "DELETE":
                try:
                    rt.router.delete_route(endpoint)
                except RouteNotFoundError as e:
                    return _err(404, str(e))
                return 200, {"endpoint": endpoint, "deleted": True}
            if method == "GET":
                try:
                    return 200, schemas.serving_route_schema(
                        rt.router.stats(endpoint))
                except RouteNotFoundError as e:
                    return _err(404, str(e))
        if method == "GET":
            st = rt.router.stats()
            return 200, {"routes": [schemas.serving_route_schema(r)
                                    for r in st["routes"]]}

    if sub == "control" and method == "GET":
        return 200, schemas._clean(rt.control_snapshot())

    return _err(404, f"no serving route for {method} "
                     f"/{'/'.join(['3'] + rest)}")


def route(server: H2OServer, method: str, parts: list[str], query: dict,
          body: dict) -> tuple[int, dict]:
    if not parts or parts[0] in ("flow", "index.html"):
        # minimal interactive Flow over the JSON API: import/parse, frame
        # and model inspection, train form with live job progress (the
        # reference serves the h2o-flow notebook IDE here, `h2o-web/`)
        from .flow import FLOW_HTML

        return 200, {"__html__": FLOW_HTML}
    ver, rest = parts[0], parts[1:]
    if ver not in ("3", "99", "4"):
        return _err(404, f"unknown api version {ver}")
    p = dict(query)
    p.update(body)

    if not rest:
        return _err(404, "no route")
    head = rest[0]

    # -- cloud / about / shutdown -------------------------------------------
    if head in ("Cloud", "Sample"):
        # `GET /99/Sample` registers CloudHandler.status too
        # (`RegisterV3Api.java:495` — "example of an experimental endpoint")
        import jax

        from ..backend.memory import CLEANER, hbm_stats

        mem = hbm_stats() or {}
        return 200, {
            "version": __version__, "cloud_name": server.name,
            "cloud_size": 1, "cloud_healthy": True, "consensus": True,
            "locked": True,
            "nodes": [{"h2o": server.url, "healthy": True,
                       "num_cpus": len(jax.devices()),
                       "backend": jax.default_backend(),
                       # the free_mem/swap fields of NodeV3 — HBM here
                       # (0 is a REAL value at full utilization, not null)
                       "free_mem": (mem["bytes_limit"] - mem["bytes_in_use"]
                                    if "bytes_limit" in mem
                                    and "bytes_in_use" in mem else None),
                       "max_mem": mem.get("bytes_limit"),
                       "tracked_hbm_bytes": CLEANER.tracked_bytes(),
                       "swap_count": CLEANER.spills}],
        }
    if head == "About":
        return 200, {"entries": [{"name": "Build version", "value": __version__},
                                 {"name": "Backend", "value": "jax/tpu"}]}
    if head == "Shutdown" and method == "POST":
        # detached teardown thread — the process is ending, there is no
        # trace to continue
        threading.Thread(target=server.stop,  # graftlint: disable=thread-without-trace-context
                         daemon=True).start()
        return 200, {}

    # -- import / parse ------------------------------------------------------
    if head in ("ImportFiles", "ImportFilesMulti"):
        # `POST /3/ImportFilesMulti` (`ImportFilesHandler.importFilesMulti`)
        # is the same resolution over a `paths` array
        paths_in = ([p.get("path", "")] if head == "ImportFiles"
                    else p.get("paths") or [])
        if isinstance(paths_in, str):
            paths_in = [s.strip(" '\"") for s in
                        paths_in.strip("[]").split(",") if s.strip(" '\"")]
        import glob as _glob

        hits, fails = [], []
        for path in paths_in:
            if "://" in path:  # URI schemes resolve through the Persist SPI
                from ..io.persist import localize

                try:
                    hits.append(localize(path))
                except (OSError, ValueError, NotImplementedError):
                    fails.append(path)
            elif any(c in path for c in "*?["):
                got = sorted(_glob.glob(path))
                hits.extend(got)
                if not got:
                    fails.append(path)
            elif os.path.exists(path):
                hits.append(path)
            else:
                fails.append(path)
        return 200, {"files": hits, "destination_frames": hits,
                     "fails": fails, "dels": []}
    if head == "ParseSetup" and method == "POST":
        from ..io.parser import guess_setup

        paths = p.get("source_frames", [])
        if isinstance(paths, str):
            paths = [paths]
        paths = [s.strip('"') for s in paths]
        path0, name0, tmp0 = _maybe_decrypt(*_resolve_upload(paths[0]), p)
        try:
            setup = guess_setup(path0)
            ext = name0.rsplit(".", 1)[-1].lower()
            from ..io.parser import BINARY_FORMAT_EXTS

            if setup.column_names is None and \
                    "." + ext not in BINARY_FORMAT_EXTS:
                # sample the head for names/types the way ParseSetupHandler's
                # preview pass does (`water/parser/ParseSetup.java` guessSetup)
                names, types = _csv_head_preview(path0, setup)
                setup.column_names = names
                if setup.column_types is None:
                    setup.column_types = types
        finally:
            if tmp0:  # decrypted plaintext must not outlive the request
                os.unlink(tmp0)
        ptype = {"parquet": "PARQUET", "pq": "PARQUET", "orc": "ORC",
                 "xls": "XLS", "xlsx": "XLSX",
                 "svm": "SVMLight", "svmlight": "SVMLight"}.get(ext, "CSV")
        return 200, {
            "source_frames": [schemas.key_schema(s) for s in paths],
            "parse_type": ptype,
            "separator": ord(setup.separator or ","),
            "check_header": 1 if setup.header else -1,
            "column_names": setup.column_names,
            "column_types": setup.column_types,
            "number_columns": len(setup.column_names or []),
            "destination_frame": _dest_name(paths[0]),
        }
    if head == "Parse" and method == "POST":
        from ..io.parser import ParseSetup, parse_file

        paths = p.get("source_frames", [])
        if isinstance(paths, str):
            paths = [paths]
        paths = [s.strip('"') for s in paths]
        dest = p.get("destination_frame") or _dest_name(paths[0])
        job = Job(f"Parse {paths[0]}", work=1.0)
        # sources may be PostFile upload keys; resolve to their spool files
        # (and through the decrypt tool when the request names one)
        resolved = [_maybe_decrypt(*_resolve_upload(s), p) for s in paths]
        srcs = [r[0] for r in resolved]
        temps = [r[2] for r in resolved if r[2]]
        setup = _parse_setup_of(p)

        def run():
            try:
                fr = parse_file(srcs[0], setup=setup, dest_key=dest)
                if paths[1:]:  # multi-file import: rbind the remaining files
                    # the client's ParseV3 overrides apply to EVERY source
                    rest_frames = [parse_file(q, setup=setup)
                                   for q in srcs[1:]]
                    fr = fr.concat_rows(*rest_frames)
                    fr.key = dest
                    STORE.put(dest, fr)
            finally:
                for t in temps:  # decrypted plaintext dies with the parse
                    if os.path.exists(t):
                        os.unlink(t)
            from ..io.upload import UploadedFile

            for s in paths:  # delete_on_done: uploads are spent after parse
                if isinstance(STORE.get(s), UploadedFile):
                    STORE.remove(s)
            job.dest_key = fr.key
            return fr

        job.start(run, background=True)
        return 200, {"job": schemas.job_schema(job)}

    # -- frames --------------------------------------------------------------
    if head == "Frames":
        if method == "GET" and not rest[1:]:
            frames = STORE.values(Frame)
            return 200, {"frames": [schemas.frame_base(f) for f in frames]}
        if method == "DELETE" and not rest[1:]:
            # `DELETE /3/Frames` — remove ALL frames (`FramesHandler.deleteAll`)
            for k in STORE.keys(Frame):
                STORE.remove(k)
            return 200, {}
        if method == "POST" and rest[1:] and rest[1] == "load":
            # `POST /3/Frames/load` — binary frame import
            # (`water/fvec/persist/FramePersist.loadFrom`)
            from ..backend import persist

            d = p.get("dir", "")
            if not d:
                return _err(400, "Frames/load: dir is required")
            fr2 = persist.load_frame(d)
            job = Job(f"Load frame {fr2.key}", work=1.0)
            job.start(lambda: fr2, background=False)
            return 200, {"job": schemas.job_schema(job),
                         "frame_id": schemas.key_schema(fr2.key, "Key<Frame>")}
        fid = urllib.parse.unquote(rest[1]) if rest[1:] else None
        fr = STORE.get(fid)
        if not isinstance(fr, Frame):
            return _err(404, f"frame {fid} not found")
        if method == "DELETE":
            STORE.remove(fid)
            return 200, {}
        if rest[2:] and rest[2] == "save" and method == "POST":
            # `POST /3/Frames/{id}/save` — binary frame export
            # (`water/fvec/persist/FramePersist.saveTo`)
            from ..backend import persist

            d = p.get("dir", "")
            if not d:
                return _err(400, "Frames/save: dir is required")
            if not _truthy(p.get("force", True)) and os.path.exists(d):
                return _err(400, f"Frames/save: {d} exists (use force)")
            out = persist.save_frame(fr, d)
            job = Job(f"Save frame {fid}", work=1.0)
            job.start(lambda: out, background=False)
            return 200, {"job": schemas.job_schema(job), "dir": out}
        if rest[2:] and rest[2] == "light":
            # `GET /3/Frames/{id}/light` (`FramesHandler.fetchLight`) —
            # names/types only, no rollups and no row preview
            return 200, {"frames": [{
                "frame_id": schemas.key_schema(fr.key, "Key<Frame>"),
                "rows": fr.nrow, "num_columns": fr.ncol,
                "column_names": list(fr.names),
                "column_types": [fr.vec(n).type for n in fr.names]}]}
        if rest[2:] and rest[2] == "export" and \
                method == "GET" and rest[3:]:
            # `GET /3/Frames/{id}/export/{path}/overwrite/{force}`
            p["path"] = urllib.parse.unquote(rest[3])
            p["force"] = rest[5] if rest[5:] else "true"
            method = "POST"  # fall through to the POST export body below
        if rest[2:] and rest[2] == "export" and method == "POST":
            # `water/api/FramesHandler.export` — CSV/parquet by extension
            path = p.get("path", "")
            if not path:
                return _err(400, "export: path is required")
            if path.startswith("file://"):
                path = path[len("file://"):]
            remote = "://" in path
            if not remote and not _truthy(p.get("force")) \
                    and os.path.exists(path):
                return _err(400, f"export: {path} exists (use force)")
            df = fr.to_pandas()
            local = path
            if remote:  # s3://... / gs://... ride the Persist store SPI
                import tempfile as _tf

                suffix = os.path.splitext(path)[1] or ".csv"
                tf = _tf.NamedTemporaryFile(suffix=suffix, delete=False)
                tf.close()
                local = tf.name
            if path.endswith((".parquet", ".pq")):
                df.to_parquet(local)
            else:
                df.to_csv(local, index=False)
            if remote:
                from ..io.persist import store as _store

                try:
                    _store(path, local)
                finally:
                    os.unlink(local)
            return 200, {"job": {"status": "DONE", "dest": path}}
        if rest[2:] and rest[2] == "summary":
            return 200, {"frames": [schemas.frame_schema(fr, npreview=0)]}
        if rest[2:] and rest[2] == "columns":
            if rest[3:]:
                # `GET /3/Frames/{id}/columns/{column}[/domain|/summary]`
                col = urllib.parse.unquote(rest[3])
                if col not in fr.names:
                    return _err(404, f"column {col} not found in {fid}")
                v = fr.vec(col)
                if rest[4:] and rest[4] == "domain":
                    # `FramesHandler.columnDomain` — levels + per-level counts
                    if v.domain is None:
                        return 200, {"domain": [None], "map_keys": None,
                                     "num_levels": [0]}
                    codes = v.to_numpy()
                    counts = np.bincount(
                        codes[~np.isnan(codes)].astype(np.int64),
                        minlength=len(v.domain))
                    return 200, {"domain": [list(v.domain)],
                                 "map_keys": {"string": list(v.domain)},
                                 "num_levels": [int(len(v.domain))],
                                 "counts": [counts.tolist()]}
                summary = schemas.col_summary(col, v, npreview=0)
                if rest[4:] and rest[4] == "summary" and not v.is_string() \
                        and v.data is not None:
                    # `FramesHandler.columnSummary` — histogram + percentiles
                    x = v.to_numpy()
                    x = x[~np.isnan(x)]
                    if x.size:
                        counts, edges = np.histogram(x, bins=20)
                        summary["histogram_bins"] = counts.tolist()
                        summary["histogram_base"] = float(edges[0])
                        summary["histogram_stride"] = float(
                            edges[1] - edges[0])
                        probs = [0.001, 0.01, 0.1, 0.25, 0.333, 0.5,
                                 0.667, 0.75, 0.9, 0.99, 0.999]
                        summary["percentiles"] = np.quantile(
                            x, probs).tolist()
                        summary["default_percentiles"] = probs
                return 200, {"frames": [{
                    "frame_id": schemas.key_schema(fr.key, "Key<Frame>"),
                    "rows": fr.nrow, "num_columns": fr.ncol,
                    "columns": [summary]}]}
            # columns-only payload, no row preview (`FramesHandler.columns`)
            full = schemas.frame_schema(fr, npreview=0)
            return 200, {"frames": [{
                "frame_id": full["frame_id"],
                "rows": full["rows"],
                "num_columns": full["num_columns"],
                "columns": full["columns"]}]}
        n = int(p.get("row_count", 10) or 10)
        return 200, {"frames": [schemas.frame_schema(fr, npreview=n)]}

    # -- model builders ------------------------------------------------------
    if head == "ModelBuilders":
        if method == "GET" and not rest[1:]:
            return 200, {"model_builders": {
                a: {"algo": a, "visibility": "Stable"}
                for a in registry.algo_names()}}
        algo = rest[1]
        entry = registry.lookup(algo)
        if entry is None:
            return _err(404, f"unknown algorithm {algo}")
        if rest[2:] and rest[2] == "model_id" and method == "POST":
            # `POST /3/ModelBuilders/{algo}/model_id`
            # (`ModelBuildersHandler.calcModelId`) — a fresh unique id
            return 200, {"model_id": schemas.key_schema(
                make_key(f"{algo.upper()}_model"), "Key<Model>")}
        if rest[2:] and rest[2] == "parameters" and method == "POST":
            # validation-only pass (`ModelBuilderHandler.validate_parameters`
            # — POST /3/ModelBuilders/{algo}/parameters): construct the
            # builder, report messages, train NOTHING
            messages = []
            try:
                kwargs = _resolve_params(entry[1], p)
                entry[0](entry[1](**kwargs))
            except (ValueError, TypeError, KeyError) as e:
                messages.append({"message_type": "ERRR", "field_name": "",
                                 "message": str(e)})
            return 200, {"algo": algo,
                         "parameters": registry.param_metadata(algo),
                         "messages": messages,
                         "error_count": len(messages)}
        if method == "POST":
            return _jobs_of(entry[0], entry[1], p)
        return 200, {"algo": algo,
                     "parameters": registry.param_metadata(algo)}

    if head == "Word2VecSynonyms":
        # `hex/api/Word2VecHandler.findSynonyms` (Word2VecSynonymsV3)
        m = STORE.get(p.get("model", ""))
        if m is None:
            return _err(404, f"model {p.get('model')} not found")
        count = int(p.get("count", 10) or 10)
        syn = m.find_synonyms(p.get("word", ""), count=count)
        return 200, {"model": schemas.key_schema(m.key, "Key<Model>"),
                     "word": p.get("word", ""), "count": count,
                     "synonyms": list(syn.keys()),
                     "scores": [float(v) for v in syn.values()]}

    if head == "Capabilities":
        # `water/api/CapabilitiesHandler` — core/API/algo extension listing
        core = [{"name": n, "extension_type": "core"}
                for n in ("Algos", "AutoML", "TargetEncoder", "Infogram",
                          "MOJO", "Grid", "SegmentModels")]
        rest_caps = [{"name": "API v3", "extension_type": "rest"},
                     {"name": "Rapids", "extension_type": "rest"}]
        which = rest[1].lower() if rest[1:] else "all"
        caps = {"core": core, "api": rest_caps}.get(which, core + rest_caps)
        return 200, {"capabilities": caps}

    # -- models --------------------------------------------------------------
    if head == "Models":
        from ..models.model_base import Model

        if method == "GET" and not rest[1:]:
            return 200, {"models": [schemas.model_schema(m)
                                    for m in STORE.values(Model)]}
        if method == "DELETE" and not rest[1:]:
            # `DELETE /3/Models` — remove ALL models (`ModelsHandler.deleteAll`)
            for k in STORE.keys(Model):
                STORE.remove(k)
            return 200, {}
        mid = urllib.parse.unquote(rest[1]) if rest[1:] else None
        m = STORE.get(mid)
        if m is None:
            return _err(404, f"model {mid} not found")
        if method == "DELETE":
            STORE.remove(mid)
            return 200, {}
        if rest[2:] and rest[2] == "json":
            # `GET /99/Models/{id}/json` (`ModelsHandler.exportModelDetails`)
            # — full model detail; with dir=, also written server-side
            payload = schemas.model_schema(m)
            if p.get("dir"):
                path, err = _export_path(p, f"{mid}.json", "Models/json")
                if err:
                    return err
                with open(path, "w") as fh:
                    json.dump(payload, fh)
                return 200, {"dir": path, "models": [payload]}
            return 200, {"models": [payload]}
        if rest[2:] and rest[2] == "mojo":
            # force_default True: the download path always rewrites its temp
            # target (the `fetchMojo` contract h2o-py download_mojo relies on)
            path, err = _export_path({**p, "dir": p.get("dir") or "."},
                                     f"{mid}.zip", "Models/mojo",
                                     force_default=True)
            if err:
                return err
            return 200, {"dir": m.save_mojo(path)}
        return 200, {"models": [schemas.model_schema(m)]}

    # -- binary model persistence over the wire ------------------------------
    # (`water/api/ModelsHandler` importModel/exportModel + fetchBinaryModel;
    #  client verbs h2o.save_model/load_model/upload_model, h2o.py:1490-1602)
    if head == "Models.bin":
        from ..backend import persist

        if method == "GET" and rest[1:]:
            mid = urllib.parse.unquote(rest[1])
            m = STORE.get(mid)
            if m is None:
                return _err(404, f"model {mid} not found")
            path, err = _export_path(p, mid, "Models.bin")
            if err:
                return err
            return 200, {"dir": persist.save_model(m, path)}
        if method == "POST":
            path = p.get("dir", "")
            if not path:
                return _err(400, "Models.bin: dir is required")
            m = persist.load_model(path)
            return 200, {"models": [schemas.model_schema(m)]}
        return _err(404, "Models.bin: GET /{id}?dir= or POST with dir")
    if head == "Models.fetch.bin" and method == "GET" and rest[1:]:
        from ..backend import persist

        mid = urllib.parse.unquote(rest[1])
        m = STORE.get(mid)
        if m is None:
            return _err(404, f"model {mid} not found")
        return 200, {"__raw__": persist.model_bytes(m),
                     "__ctype__": "application/octet-stream",
                     "__filename__": mid}
    if head == "Models.java" and method == "GET" and rest[1:]:
        # `GET /3/Models.java/{id}[/preview]` (`ModelsHandler.fetchJavaCode`
        # / `fetchPreview`) — the POJO source as a java file download
        from ..mojo.pojo import pojo_source

        mid = urllib.parse.unquote(rest[1])
        m = STORE.get(mid)
        if m is None:
            return _err(404, f"model {mid} not found")
        cls_name = re.sub(r"[^A-Za-z0-9_]", "_", mid)
        if not cls_name or not (cls_name[0].isalpha() or cls_name[0] == "_"):
            # `JCodeGen.toJavaId`: a leading digit is not a Java identifier
            cls_name = "_" + cls_name
        try:
            src = pojo_source(m, class_name=cls_name)
        except NotImplementedError as e:
            return _err(400, str(e))
        if rest[2:] and rest[2] == "preview":
            # the reference truncates the preview to its first kilobytes
            lines = src.splitlines()
            src = "\n".join(lines[:1000])
            if len(lines) > 1000:
                src += "\n// ... truncated preview ..."
        return 200, {"__raw__": src, "__ctype__": "text/x-java",
                     "__filename__": f"{cls_name}.java"}
    if head == "Models.mojo" and method == "GET" and rest[1:]:
        # `GET /99/Models.mojo/{id}?dir=` (`ModelsHandler.exportMojo`) —
        # server-side MOJO export, returns the written path
        mid = urllib.parse.unquote(rest[1])
        m = STORE.get(mid)
        if m is None:
            return _err(404, f"model {mid} not found")
        path, err = _export_path(p, f"{mid}.zip", "Models.mojo")
        if err:
            return err
        return 200, {"dir": m.save_mojo(path)}
    if head == "Models.upload.bin" and method == "POST":
        from ..backend import persist
        from ..io.upload import UploadedFile

        src = p.get("dir", "")
        uf = STORE.get(src)
        if not isinstance(uf, UploadedFile):
            return _err(404, f"Models.upload.bin: no uploaded file '{src}'")
        m = persist.load_model(uf.path)
        STORE.remove(src)
        return 200, {"models": [schemas.model_schema(m)]}

    # -- online scoring (`h2o_tpu/serving/` runtime) -------------------------
    if head == "Serving":
        return _serving_route(method, rest, p)

    # -- predictions ---------------------------------------------------------
    if head == "Predictions" and method == "POST":
        # /3/Predictions/models/{model}/frames/{frame}
        mid = urllib.parse.unquote(rest[2])
        fid = urllib.parse.unquote(rest[4])
        model, fr = STORE.get(mid), STORE.get(fid)
        if model is None:
            return _err(404, f"model {mid} not found")
        if fr is None:
            return _err(404, f"frame {fid} not found")
        if _truthy(p.get("predict_contributions")):
            def score_fn():
                return model.predict_contributions(fr)
        elif _truthy(p.get("leaf_node_assignment")):
            def score_fn():
                return model.predict_leaf_node_assignment(
                    fr, type=p.get("leaf_node_assignment_type") or "Path")
        elif _truthy(p.get("predict_staged_proba")):
            def score_fn():
                return model.staged_predict_proba(fr)
        else:
            def score_fn():
                return model.predict(fr)
        dest = p.get("predictions_frame") or f"predictions_{mid}_{fid}"
        if ver == "4":
            # `POST /4/Predictions/...` — the async registration: EVERY
            # scoring mode runs in a background job the client polls
            # (water/api/RegisterV3Api's /4 route contract)
            job = Job(f"Prediction {mid} on {fid}", work=1.0)

            def run_predict():
                out = score_fn()
                out.key = dest
                STORE.put(dest, out)
                job.dest_key = dest
                return out

            job.start(run_predict, background=True)
            return 200, {"job": schemas.job_schema(job),
                         "predictions_frame": schemas.key_schema(dest)}
        pred = score_fn()
        pred.key = dest
        STORE.put(dest, pred)
        return 200, {"predictions_frame": schemas.key_schema(dest),
                     "model_metrics": [{}]}

    if head == "PartialDependence" and method == "POST":
        # `water/api/ModelMetricsHandler` PDP route (synchronous here: the
        # reference runs it as a job; ours is one batched rescore per bin)
        model = STORE.get(p.get("model_id", ""))
        fr = STORE.get(p.get("frame_id", ""))
        if model is None or fr is None:
            return _err(404, "model or frame not found")
        cols = p.get("cols")
        cols = cols.split(",") if isinstance(cols, str) and cols else None
        targets = p.get("targets")
        targets = targets.split(",") if isinstance(targets, str) and targets \
            else None
        tables = model.partial_dependence(
            fr, cols, nbins=int(p.get("nbins", 20) or 20),
            weight_column=p.get("weight_column") or None, targets=targets,
            row_index=int(p["row_index"]) if p.get("row_index")
            is not None else -1)
        dest = p.get("destination_key") or make_key("PartialDependence")
        payload = {"destination_key": schemas.key_schema(dest),
                   "partial_dependence_data":
                   [schemas.table_schema(t) for t in tables]}
        # keep the result fetchable by key — `GET /3/PartialDependence/{id}`
        STORE.put(dest, payload)
        return 200, payload
    if head == "PartialDependence" and method == "GET" and rest[1:]:
        # `ModelMetricsHandler.fetchPartialDependenceData`
        dest = urllib.parse.unquote(rest[1])
        payload = STORE.get(dest)
        if not isinstance(payload, dict) \
                or "partial_dependence_data" not in payload:
            return _err(404, f"no partial dependence result {dest}")
        return 200, payload

    if head == "PermutationVarImp" and method == "POST":
        model = STORE.get(p.get("model_id", ""))
        fr = STORE.get(p.get("frame_id", ""))
        if model is None or fr is None:
            return _err(404, "model or frame not found")
        t = model.permutation_importance(
            fr, metric=p.get("metric", "AUTO") or "AUTO",
            n_repeats=int(p.get("n_repeats", 1) or 1),
            seed=int(p.get("seed", -1) or -1))
        return 200, {"permutation_varimp": schemas.table_schema(t)}

    # -- model metrics (`water/api/ModelMetricsHandler`) --------------------
    if head == "ModelMetrics":
        from ..models.model_base import Model

        def _mm_entry(mid2, fid2, mm2):
            return {"model": schemas.key_schema(mid2),
                    "frame": schemas.key_schema(fid2) if fid2 else None,
                    **(schemas.metrics_schema(mm2) or {})}

        def _training_entries(models):
            return [
                _mm_entry(m.key,
                          (m.params.training_frame.key
                           if getattr(m.params, "training_frame", None)
                           is not None else None),
                          m.output.training_metrics)
                for m in models if m.output.training_metrics is not None]

        if not rest[1:]:
            if method == "DELETE":  # `DELETE /3/ModelMetrics` — drop cache
                with _METRICS_LOCK:
                    _METRICS_CACHE.clear()
                return 200, {}
            # listing: training metrics + every cached on-frame recompute
            return 200, {"model_metrics": _training_entries(
                STORE.values(Model)) + [
                _mm_entry(m2, f2, mm) for (m2, f2), mm
                in _metrics_cache_items()]}

        # `/3/ModelMetrics/predictions_frame/{p}/actuals_frame/{a}` — build
        # metrics from a predictions frame + an actuals frame with no model
        # (`ModelMetricsHandler.make`, h2o-py `h2o.make_metrics`)
        if rest[1] == "predictions_frame" and method == "POST":
            return _make_metrics_route(rest, p)

        # resolve the {models,frames} path pair in either order
        mid = fid = None
        seg = rest[1:]
        while seg:
            kind, val = seg[0], (seg[1] if seg[1:] else None)
            if val is None:
                break
            if kind == "models":
                mid = urllib.parse.unquote(val)
            elif kind == "frames":
                fid = urllib.parse.unquote(val)
            else:
                return _err(404, f"ModelMetrics: unknown segment {kind}")
            seg = seg[2:]
        if method == "DELETE":
            # scoped cache invalidation (`ModelMetricsHandler.delete`) —
            # runs BEFORE existence checks: entries for already-deleted
            # models/frames must stay deletable
            with _METRICS_LOCK:
                for k in [k for k in _METRICS_CACHE
                          if (mid is None or k[0] == mid)
                          and (fid is None or k[1] == fid)]:
                    del _METRICS_CACHE[k]
            return 200, {}
        model = STORE.get(mid) if mid else None
        if mid and model is None:
            return _err(404, f"model {mid} not found")
        if fid and not isinstance(STORE.get(fid), Frame):
            return _err(404, f"frame {fid} not found")
        if mid and fid:
            # always recompute: the model or frame may have been replaced
            # under the same key since the last score (the reference's
            # checksum-keyed DKV entry invalidates on replacement)
            if method == "POST" and p.get("predictions_frame"):
                # one scoring pass serves both outputs (BigScore semantics)
                pred, mm = model.score_with_metrics(STORE.get(fid))
                pred.key = str(p["predictions_frame"])
                STORE.put(pred.key, pred)
            else:
                mm = model.model_performance(STORE.get(fid))
            with _METRICS_LOCK:
                _METRICS_CACHE[(mid, fid)] = mm
            return 200, {"model_metrics": [_mm_entry(mid, fid, mm)]}
        if mid:  # all metrics known for one model
            entries = _training_entries([model]) + [
                _mm_entry(m2, f2, mm) for (m2, f2), mm
                in _metrics_cache_items() if m2 == mid]
            return 200, {"model_metrics": entries}
        # all metrics computed on one frame
        return 200, {"model_metrics": [
            _mm_entry(m2, f2, mm) for (m2, f2), mm
            in _metrics_cache_items() if f2 == fid]}

    # -- frame factory / munging routes -------------------------------------
    if head == "CreateFrame" and method == "POST":
        # `water/api/CreateFrameHandler` — synthetic random frame
        rows = int(p.get("rows", 10000) or 10000)
        cols = int(p.get("cols", 10) or 10)
        seed = int(p.get("seed", -1) or -1)
        rng = np.random.default_rng(None if seed in (-1, None) else seed)
        cat_frac = float(p.get("categorical_fraction", 0.2) or 0)
        int_frac = float(p.get("integer_fraction", 0.2) or 0)
        bin_frac = float(p.get("binary_fraction", 0.1) or 0)
        if cat_frac + int_frac + bin_frac > 1.0 + 1e-9:
            return _err(400, "categorical_fraction + integer_fraction + "
                             "binary_fraction must not exceed 1")
        miss_frac = float(p.get("missing_fraction", 0.0) or 0)
        factors = int(p.get("factors", 100) or 100)
        real_range = float(p.get("real_range", 100.0) or 100.0)
        int_range = int(p.get("integer_range", 100) or 100)
        from ..frame.vec import T_CAT, T_STR, T_TIME, Vec as _Vec

        str_frac = float(p.get("string_fraction", 0.0) or 0)
        time_frac = float(p.get("time_fraction", 0.0) or 0)
        real_frac = (float(p["real_fraction"])
                     if p.get("real_fraction") not in (None, "") else None)
        if (cat_frac + int_frac + bin_frac + str_frac + time_frac
                + (real_frac or 0.0)) > 1.0 + 1e-9:
            return _err(400, "column-type fractions must not exceed 1")
        # +0.1 before the floor absorbs 0.2999999997-style client rounding
        # (`OriginalCreateFrameRecipe.buildRecipe`'s comment)
        n_cat = int(cols * cat_frac + 0.1)
        n_int = int(cols * int_frac + 0.1)
        n_bin = int(cols * bin_frac + 0.1)
        n_str = int(cols * str_frac + 0.1)
        n_time = int(cols * time_frac + 0.1)
        if real_frac is not None:
            n_real = int(cols * real_frac + 0.1)
            # explicit fractions must account for every column
            total = n_cat + n_int + n_bin + n_str + n_time + n_real
            if total != cols:
                n_real += cols - total  # rounding slack goes to reals
        else:
            n_real = max(cols - n_cat - n_int - n_bin - n_str - n_time, 0)
        fr2 = Frame([], [])
        ci = 0
        _alpha = np.array(list("abcdefghijklmnopqrstuvwxyz"))
        for _ in range(n_str):
            lens = rng.integers(4, 9, rows)
            words = np.array(
                ["".join(rng.choice(_alpha, size=ln)) for ln in lens],
                dtype=object)
            words[rng.random(rows) < miss_frac] = None
            fr2.add(f"C{ci + 1}", _Vec(None, rows, type=T_STR,
                                       host_data=words)); ci += 1
        for _ in range(n_time):
            t = rng.integers(1_400_000_000_000, 1_700_000_000_000,
                             rows).astype(np.float64)
            t[rng.random(rows) < miss_frac] = np.nan
            fr2.add(f"C{ci + 1}", _Vec.from_numpy(t, type=T_TIME)); ci += 1
        for _ in range(n_real):
            x = rng.uniform(-real_range, real_range, rows).astype(np.float32)
            x[rng.random(rows) < miss_frac] = np.nan
            fr2.add(f"C{ci + 1}", _Vec.from_numpy(x)); ci += 1
        for _ in range(n_int):
            x = rng.integers(-int_range, int_range + 1, rows).astype(np.float32)
            x[rng.random(rows) < miss_frac] = np.nan
            fr2.add(f"C{ci + 1}", _Vec.from_numpy(x)); ci += 1
        for _ in range(n_bin):
            x = (rng.random(rows) < 0.5).astype(np.float32)
            x[rng.random(rows) < miss_frac] = np.nan
            fr2.add(f"C{ci + 1}", _Vec.from_numpy(x)); ci += 1
        for _ in range(n_cat):
            codes = rng.integers(0, factors, rows).astype(np.float32)
            codes[rng.random(rows) < miss_frac] = np.nan
            fr2.add(f"C{ci + 1}", _Vec.from_numpy(
                codes, type=T_CAT,
                domain=[f"c{ci}.l{j}" for j in range(factors)])); ci += 1
        if _truthy(p.get("has_response")):
            rf = int(p.get("response_factors", 2) or 2)
            if rf <= 1:
                y = rng.normal(size=rows).astype(np.float32)
                fr2.add("response", _Vec.from_numpy(y))
            else:
                y = rng.integers(0, rf, rows).astype(np.float32)
                fr2.add("response", _Vec.from_numpy(
                    y, type=T_CAT, domain=[f"r{j}" for j in range(rf)]))
        dest = p.get("dest") or p.get("destination_frame") or "createdFrame"
        fr2.key = dest
        STORE.put(dest, fr2)
        return 200, {"key": schemas.key_schema(dest),
                     "job": {"status": "DONE",
                             "dest": schemas.key_schema(dest)}}

    if head == "SplitFrame" and method == "POST":
        # `water/api/SplitFrameHandler`
        from ..frame.split import split_frame

        fid = p.get("dataset", "")
        fr2 = STORE.get(fid)
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {fid} not found")
        ratios = p.get("ratios") or [0.75]
        if isinstance(ratios, str):
            ratios = [float(r) for r in ratios.strip("[]").split(",") if r]
        seed = int(p.get("seed", -1) or -1)
        parts = split_frame(fr2, ratios=tuple(float(r) for r in ratios),
                            seed=None if seed == -1 else seed)
        dests = p.get("destination_frames") or [
            f"{fid}_part{i}" for i in range(len(parts))]
        if isinstance(dests, str):
            dests = [d.strip(" '\"") for d in dests.strip("[]").split(",")]
        if len(dests) < len(parts):
            return _err(400, f"destination_frames has {len(dests)} names "
                             f"but the split produces {len(parts)} parts")
        for part, dest in zip(parts, dests):
            part.key = dest
            STORE.put(dest, part)
        return 200, {"destination_frames": [schemas.key_schema(d)
                                            for d in dests[:len(parts)]],
                     "job": {"status": "DONE"}}

    if head == "Interaction" and method == "POST":
        # `water/api/InteractionHandler` — combined categorical columns
        from ..rapids import advmath

        fid = p.get("source_frame") or p.get("dataset") or ""
        fr2 = STORE.get(fid)
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {fid} not found")
        factors = p.get("factor_columns") or p.get("factors") or []
        if isinstance(factors, str):
            factors = [f.strip(" '\"") for f in factors.strip("[]").split(",")]
        out = advmath.interaction(
            fr2, factors, _truthy(p.get("pairwise")),
            int(p.get("max_factors", 100) or 100),
            int(p.get("min_occurrence", 1) or 1))
        dest = p.get("dest") or f"{fid}_interaction"
        out.key = dest
        STORE.put(dest, out)
        return 200, {"dest": schemas.key_schema(dest),
                     "job": {"status": "DONE"}}

    if head == "MissingInserter" and method == "POST":
        # `water/api/MissingInserterHandler` — corrupt a frame with NAs
        fid = p.get("dataset", "")
        fr2 = STORE.get(fid)
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {fid} not found")
        frac = float(p["fraction"]) if p.get("fraction") not in (None, "") \
            else 0.1
        seed = int(p["seed"]) if p.get("seed") not in (None, "") else -1
        rng = np.random.default_rng(None if seed == -1 else seed)
        from ..frame.vec import Vec as _Vec

        for name in fr2.names:
            v = fr2.vec(name)
            if v.is_string():
                continue
            # keep float64: from_numpy detects f32-lossy values (time/int64
            # columns) and retains the exact sidecar — an astype(f32) here
            # would corrupt every row, not just the NA-inserted ones
            x = v.to_numpy().astype(np.float64)
            x[rng.random(len(x)) < frac] = np.nan
            fr2.replace(name, _Vec.from_numpy(x, type=v.type,
                                              domain=v.domain))
        return 200, {"job": {"status": "DONE",
                             "dest": schemas.key_schema(fid)}}

    if head in ("DownloadDataset", "DownloadDataset.bin"):
        # `water/api/DownloadDataHandler` — raw CSV body, not JSON; the .bin
        # registration (`RegisterV3Api`) streams the same CSV for big frames
        fid = p.get("frame_id", "")
        fr2 = STORE.get(fid)
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {fid} not found")
        csv = fr2.to_pandas().to_csv(
            index=False, header=not _truthy(p.get("hex_string")))
        return 200, {"__raw__": csv, "__ctype__": "text/csv",
                     "__filename__": f"{fid}.csv"}

    if head == "Tree":
        # `hex/schemas/TreeV3` + `water/api/TreeHandler` — inspect one tree
        model = STORE.get(p.get("model", ""))
        if model is None or not hasattr(model, "forest"):
            return _err(404, "tree model not found")
        forest = model.forest
        if not isinstance(forest, dict) or not all(
                k in forest for k in ("feat", "thr", "val", "nanL")):
            return _err(400, f"model {model.key} does not store inspectable "
                             f"feat/thr/val trees (algo "
                             f"{getattr(model, 'algo_name', '?')})")
        t = int(p.get("tree_number", 0) or 0)
        feat = np.asarray(model.forest["feat"])
        if not (0 <= t < feat.shape[0]):
            return _err(400, f"tree_number {t} out of range "
                             f"[0, {feat.shape[0]})")
        if feat.ndim == 3:  # multinomial: per-class trees
            dom = model.output.response_domain or []
            cls_name = p.get("tree_class") or (dom[0] if dom else "0")
            k = dom.index(cls_name) if cls_name in dom else int(cls_name)
            sel = (t, k)
        else:
            cls_name = None
            sel = (t,)
        ft = feat[sel]
        thr = np.asarray(model.forest["thr"])[sel]
        val = np.asarray(model.forest["val"])[sel]
        nanl = np.asarray(model.forest["nanL"])[sel]
        N = ft.shape[0]
        names = model.output.names
        lefts = np.where(np.arange(N) * 2 + 1 < N,
                         np.arange(N) * 2 + 1, -1)
        rights = np.where(np.arange(N) * 2 + 2 < N,
                          np.arange(N) * 2 + 2, -1)
        is_leaf = ft < 0
        lefts[is_leaf] = -1
        rights[is_leaf] = -1
        # categorical set-split nodes report their LEFT level set
        # (`TreeHandler` fills levels from the split bitset)
        levels = [None] * N
        if (getattr(getattr(model, "cfg", None), "use_sets", False)
                and "catd" in forest):
            catd = np.asarray(forest["catd"])[sel]
            iscat = np.asarray(model.is_cat)
            ne = np.asarray(model.cat_nedges, dtype=np.int64)
            for j in range(N):
                f = int(ft[j])
                if f < 0 or not iscat[f]:
                    continue
                dom = model.output.domains.get(names[f]) or []
                lv = [d for li, d in enumerate(dom)
                      if catd[j, min(li, int(ne[f]))] <= 0.5]
                levels[j] = lv
        return 200, {
            "model_id": schemas.key_schema(str(model.key)),
            "tree_number": t,
            "tree_class": cls_name,
            "left_children": lefts.tolist(),
            "right_children": rights.tolist(),
            "features": [None if f < 0 else names[int(f)] for f in ft],
            "thresholds": [None if l or levels[i] is not None else float(x)
                           for i, (l, x) in enumerate(zip(is_leaf, thr))],
            "levels": levels,
            "predictions": [float(x) if l else None
                            for l, x in zip(is_leaf, val)],
            "nas": ["L" if nl else "R" for nl in nanl],
            "root_node_id": 0,
        }

    # -- key management / misc ----------------------------------------------
    if head == "DKV" and method == "DELETE":
        if rest[1:]:
            STORE.remove(urllib.parse.unquote(rest[1]))
            return 200, {}
        for k in STORE.keys():  # `removeAll` (`water/api/RemoveAllHandler`)
            STORE.remove(k, cascade=False)
        return 200, {}
    if head == "GarbageCollect" and method == "POST":
        import gc as _gc

        _gc.collect()
        return 200, {}
    if head == "LogAndEcho" and method == "POST":
        from ..utils.log import info as _log_info

        msg = p.get("message", "") or ""
        _log_info(f"LogAndEcho: {msg}")
        return 200, {"message": msg}
    if head == "Ping":
        import time as _time

        return 200, {"cloud_uptime_millis": int(
            (_time.time() - server.started_at) * 1000), "cloud_healthy": True}
    if head == "KillMinus3":
        # `GET /3/KillMinus3` (`water/util/JStackCollectorTask`) — the JVM
        # analog logs all stack traces to stdout; log the controller's here
        import sys
        import traceback as tb

        from ..utils.log import info as _log_info

        names = {t.ident: t.name for t in threading.enumerate()}
        for tid, frame in sys._current_frames().items():
            _log_info(f"KillMinus3 thread [{names.get(tid, tid)}]:\n"
                      + "".join(tb.format_stack(frame)))
        return 200, {}
    if head == "CloudLock" and method == "POST":
        # `water/api/CloudLockHandler` → Paxos.lockCloud(reason)
        from ..utils.log import info as _log_info

        reason = "requested via REST api." + (
            f" Reason: {p['reason']}" if p.get("reason") else "")
        server.locked_reason = reason
        _log_info(f"Cloud locked: {reason}")
        return 200, {"reason": p.get("reason")}
    if head == "UnlockKeys" and method == "POST":
        # `water/api/UnlockKeysHandler` → UnlockTask over all nodes; keys
        # here carry no write-locks (single controller), so this releases
        # nothing but keeps the verb for clients that call it defensively
        return 200, {}
    if head == "SessionProperties":
        # `RapidsHandler.{get,set}SessionProperty` (RegisterV3Api:483-487)
        sid = p.get("session_key", "default")
        key = p.get("key", "")
        if not key:
            return _err(400, "SessionProperties: key is required")
        if method == "POST":
            _SESSION_PROPS[(sid, key)] = p.get("value")
            return 200, {"session_key": sid, "key": key,
                         "value": p.get("value")}
        return 200, {"session_key": sid, "key": key,
                     "value": _SESSION_PROPS.get((sid, key))}
    if head == "SteamMetrics":
        # `water/api/SteamMetricsHandler` — cluster idle time for Steam's
        # auto-suspend decision
        import time as _time

        from ..backend.jobs import any_running

        idle = 0 if any_running() else int(
            (_time.time() - server.last_activity) * 1000)
        return 200, {"version": 1, "idle_millis": idle}
    if head == "Find":
        # `water/api/FindHandler` — scan forward from `row` for `match`,
        # reporting the previous and next hit row indices
        fr2 = STORE.get(p.get("key", ""))
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {p.get('key')} not found")
        names = [p["column"]] if p.get("column") else list(fr2.names)
        if p.get("column") and p["column"] not in fr2.names:
            return _err(404, f"column {p['column']} not found")
        row = int(p.get("row", 0) or 0)
        match = p.get("match")
        prev_hit, next_hit = -1, -1
        for name in names:
            v = fr2.vec(name)
            if v.is_string():
                vals = np.asarray([x == match for x in v.host_data])
            elif v.domain is not None:
                if match not in v.domain:
                    if len(names) == 1:
                        return _err(404, f"level {match!r} not found in "
                                         f"column {name}")
                    continue
                vals = fr2.vec(name).to_numpy() == v.domain.index(match)
            else:
                try:
                    target = float("nan") if match is None else float(match)
                except (TypeError, ValueError):
                    if len(names) == 1:
                        return _err(400, f"column {name} is numeric and the "
                                         f"find pattern is not: {match!r}")
                    continue
                x = v.to_numpy()
                vals = np.isnan(x) if np.isnan(target) else (x == target)
            hits = np.flatnonzero(vals)
            before = hits[hits < row]
            after = hits[hits >= row]
            if before.size:
                prev_hit = max(prev_hit, int(before[-1]))
            if after.size:
                next_hit = int(after[0]) if next_hit < 0 \
                    else min(next_hit, int(after[0]))
        return 200, {"prev": prev_hit, "next": next_hit}
    if head == "FrameChunks":
        # `water/api/FrameChunksHandler` — chunk layout of a frame; chunks
        # here are the row-shards of the device mesh
        fid2 = urllib.parse.unquote(rest[1]) if rest[1:] else ""
        fr2 = STORE.get(fid2)
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {fid2} not found")
        shards = 1
        if fr2.ncol and fr2.vecs[0].data is not None:
            try:
                shards = len(fr2.vecs[0].data.sharding.device_set)
            except (AttributeError, TypeError):
                shards = 1
        per = -(-fr2.nrow // shards)  # even padded shards (the ESPC analog)
        counts = [min(per, fr2.nrow - i * per) for i in range(shards)]
        return 200, {"frame_id": schemas.key_schema(fid2, "Key<Frame>"),
                     "chunks": [{"chunk_id": i, "row_count": max(c, 0),
                                 "node_idx": i % shards}
                                for i, c in enumerate(counts)]}

    # -- grid search (`POST /99/Grid/{algo}`, `GET /99/Grids[/{id}]`,
    #    `POST /3/Grid.bin/import`, `POST /3/Grid.bin/{id}/export` —
    #    `water/api/GridSearchHandler`/`GridsHandler`/`GridImportExportHandler`)
    if head == "Grid" and method == "POST" and rest[1:]:
        import json as _json

        from ..models.grid import GridSearch, SearchCriteria

        algo = rest[1]
        entry = registry.lookup(algo)
        if entry is None:
            return _err(404, f"unknown algorithm {algo}")
        algo_cls, params_cls = entry
        body2 = dict(p)
        hp = body2.pop("hyper_parameters", None) or {}
        if isinstance(hp, str):
            hp = _json.loads(hp)
        sc = body2.pop("search_criteria", None) or {}
        if isinstance(sc, str):
            sc = _json.loads(sc)
        grid_id = body2.pop("grid_id", None)
        parallelism = int(body2.pop("parallelism", 1) or 1)
        recovery_dir = body2.pop("recovery_dir", None)
        try:
            kwargs = _resolve_params(params_cls, body2, extra_names=list(hp))
        except ValueError as e:
            return _err(412, str(e))
        gs = GridSearch(algo_cls, params_cls(**kwargs), hp,
                        SearchCriteria(**sc), recovery_dir=recovery_dir,
                        parallelism=parallelism, grid_id=grid_id)
        job = gs.train(background=True)
        return 200, {"job": schemas.job_schema(job),
                     "key": schemas.key_schema(job.dest_key)}
    if head == "Grids":
        from ..models.grid import Grid

        if not rest[1:]:
            return 200, {"grids": [{"grid_id": schemas.key_schema(g.key)}
                                   for g in STORE.values(Grid)]}
        gid = urllib.parse.unquote(rest[1])
        g = STORE.get(gid)
        if not isinstance(g, Grid):
            return _err(404, f"grid {gid} not found")
        if method == "DELETE":
            # cascade like the reference's grid remove: the contained models
            # die with the grid (h2o.remove(grid) contract)
            for m in list(g.models):
                STORE.remove(m.key)
            STORE.remove(gid)
            return 200, {}
        by = p.get("sort_by") or None
        decr = _truthy(p["decreasing"]) if "decreasing" in p else None
        ms = g.sorted_models(by, decr)
        return 200, {
            "grid_id": schemas.key_schema(g.key),
            "algo": g.builder_cls.algo_name,
            "model_ids": [schemas.key_schema(m.key) for m in ms],
            "hyper_names": list(g.hyper_params),
            "failure_details": [f["error"] for f in g.failures],
            "failed_raw_params": [f["params"] for f in g.failures],
            "summary_table": schemas.table_schema(g.summary_table(by)),
        }
    # -- tree interaction statistics ----------------------------------------
    if head == "FeatureInteraction" and method == "POST":
        # `ModelsHandler.makeFeatureInteraction` (hex/FeatureInteractions,
        # the xgbfi algorithm)
        from ..models.interactions import feature_interactions_tables

        m = STORE.get(p.get("model_id", ""))
        if m is None:
            return _err(404, f"model {p.get('model_id')} not found")
        if not hasattr(m, "forest") or not hasattr(m, "_ensure_covers"):
            return _err(400, f"{getattr(m, 'algo_name', '?')} does not "
                             "support feature interactions calculation")
        tables = feature_interactions_tables(
            m, int(p.get("max_interaction_depth", 100) or 100),
            int(p.get("max_tree_depth", 100) or 100),
            int(p.get("max_deepening", -1) if p.get("max_deepening")
                not in (None, "") else -1))
        return 200, {"feature_interaction":
                     [schemas.table_schema(t) for t in tables]}
    if head == "FriedmansPopescusH" and method == "POST":
        # `ModelsHandler.makeFriedmansPopescusH` (hex/tree/FriedmanPopescusH)
        from ..models.interactions import friedman_popescu_h

        m = STORE.get(p.get("model_id", ""))
        fr2 = STORE.get(p.get("frame", "") or p.get("frame_id", ""))
        if m is None or not isinstance(fr2, Frame):
            return _err(404, "model or frame not found")
        if not hasattr(m, "forest") or not hasattr(m, "_ensure_covers"):
            return _err(400, f"{getattr(m, 'algo_name', '?')} does not "
                             "support Friedman Popescus H calculation")
        variables = p.get("variables") or []
        if isinstance(variables, str):
            variables = [v.strip(" '\"") for v in
                         variables.strip("[]").split(",") if v.strip(" '\"")]
        h = friedman_popescu_h(m, fr2, variables)
        return 200, {"h": None if np.isnan(h) else float(h)}
    if head == "SignificantRules" and method == "POST":
        # `ModelsHandler.makeSignificantRulesTable` (RuleFit)
        m = STORE.get(p.get("model_id", ""))
        if m is None:
            return _err(404, f"model {p.get('model_id')} not found")
        if not hasattr(m, "rule_importance"):
            return _err(400, f"{getattr(m, 'algo_name', '?')} does not "
                             "support significant rules collection")
        from ..utils.twodimtable import TwoDimTable

        rows = m.rule_importance()
        t = TwoDimTable.from_dict("Significant Rules", {
            "variable": [r["rule"] for r in rows],
            "coefficient": [float(r["coefficient"]) for r in rows],
            "support": [float(r["support"]) for r in rows]})
        return 200, {"significant_rules_table": schemas.table_schema(t)}

    if head == "GetGLMRegPath" and method == "GET":
        # `hex/api/MakeGLMModelHandler.extractRegularizationPath`
        # (`GLMRegularizationPathV3`): a model fitted without a search
        # answers with its one lambda
        m = STORE.get(p.get("model", ""))
        if m is None:
            return _err(404, f"model {p.get('model')} not found")
        o = m.output
        if getattr(o, "lambdas", None) is None:
            return _err(400, f"{getattr(m, 'algo_name', '?')} model "
                             f"{m.key} keeps no regularisation path")
        alpha = m.params.alpha if m.params.alpha is not None else 0.5
        return 200, {
            "model": schemas.key_schema(m.key, "Key<Model>"),
            "lambdas": [float(v) for v in o.lambdas],
            "alphas": [float(alpha)] * len(o.lambdas),
            "explained_deviance_train": [
                float(v) for v in o.explained_deviance_train],
            # the path is not scored on a validation frame
            "explained_deviance_valid": None,
            "coefficients": np.asarray(o.coefficients).tolist(),
            "coefficients_std": np.asarray(o.coefficients_std).tolist(),
            "coefficient_names": m.dinfo.expanded_names + ["Intercept"]}

    # -- tabulate / DCT / SQL import ----------------------------------------
    if head == "Tabulate" and method == "POST":
        # `water/api/TabulateHandler` → `water/util/Tabulate`
        from ..rapids.advmath import tabulate as _tabulate

        fr2 = STORE.get(p.get("dataset", ""))
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {p.get('dataset')} not found")
        count_t, resp_t = _tabulate(
            fr2, p.get("predictor", ""), p.get("response", ""),
            weight=p.get("weight") or None,
            nbins_predictor=int(p.get("nbins_predictor", 20) or 20),
            nbins_response=int(p.get("nbins_response", 10) or 10))
        return 200, {"count_table": schemas.table_schema(count_t),
                     "response_table": schemas.table_schema(resp_t)}
    if head == "DCTTransformer" and method == "POST":
        # `water/api/DCTTransformerHandler` → MathUtils.DCT, on the MXU
        from ..frame.vec import Vec as _Vec
        from ..ops.dct import dct_frame

        fr2 = STORE.get(p.get("dataset", ""))
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {p.get('dataset')} not found")
        dims = p.get("dimensions") or []
        if isinstance(dims, str):
            dims = [int(d) for d in dims.strip("[]").split(",") if d.strip()]
        if len(dims) != 3:
            return _err(400, "Need 3 dimensions (width/height/depth): "
                             "WxHxD (1D: Wx1x1, 2D: WxHx1, 3D: WxHxD)")
        X = np.stack([fr2.vec(n).to_numpy() for n in fr2.names], axis=1)
        Y = dct_frame(X, dims[0], dims[1], dims[2],
                      inverse=_truthy(p.get("inverse")))
        dest = p.get("destination_frame") or f"{p.get('dataset')}_dct"
        out = Frame([f"C{i + 1}" for i in range(Y.shape[1])],
                    [_Vec.from_numpy(Y[:, i]) for i in range(Y.shape[1])],
                    key=dest)
        STORE.put_keyed(out)
        return 200, {"key": schemas.key_schema(dest, "Key<Frame>"),
                     "job": {"status": "DONE",
                             "dest": schemas.key_schema(dest)}}
    if head == "ImportSQLTable" and method == "POST":
        # `water/jdbc/SQLManager` (`POST /99/ImportSQLTable`)
        from ..io.sqlimport import import_sql

        fr2 = import_sql(
            p.get("connection_url", ""), table=p.get("table", "") or "",
            select_query=p.get("select_query", "") or "",
            columns=p.get("columns", "*") or "*")
        job = Job(f"ImportSQLTable {fr2.key}", work=1.0)
        job.dest_key = fr2.key
        job.start(lambda: fr2, background=False)
        return 200, {"job": schemas.job_schema(job),
                     "destination_frame": schemas.key_schema(fr2.key)}
    if head in ("ImportHiveTable", "SaveToHiveTable") and method == "POST":
        # `water/hive/HiveTableImporter` — needs a live Hive metastore;
        # gate unless one is configured (the reference fails identically
        # without a Hive cluster on the classpath)
        from ..utils.knobs import raw as _knob_raw

        if not _knob_raw("H2O_TPU_HIVE_JDBC"):
            return _err(501, f"{head}: no Hive metastore configured "
                             "(set H2O_TPU_HIVE_JDBC to a reachable "
                             "HiveServer2 JDBC url)")
        return _err(501, f"{head}: Hive JDBC transport not implemented "
                         "in this build")
    if head == "ParseSVMLight" and method == "POST":
        # `POST /3/ParseSVMLight` (`ParseHandler.parseSVMLight`) — force the
        # SVMLight reader regardless of the source's extension
        from ..io.parser import _parse_svmlight

        paths = p.get("source_frames") or p.get("source_keys") or []
        if isinstance(paths, str):
            paths = [paths]
        paths = [s.strip('"') for s in paths]
        if not paths:
            return _err(400, "ParseSVMLight: source_frames is required")
        dest = p.get("destination_frame") or _dest_name(paths[0])
        src = _resolve_upload(paths[0])[0]
        job = Job(f"ParseSVMLight {paths[0]}", work=1.0)

        def run_svm():
            fr3 = _parse_svmlight(src, dest_key=dest)
            job.dest_key = fr3.key
            return fr3

        job.start(run_svm, background=True)
        return 200, {"job": schemas.job_schema(job),
                     "destination_frame": schemas.key_schema(dest)}

    # -- decryption setup ----------------------------------------------------
    if head == "DecryptionSetup" and method == "POST":
        # `water/api/DecryptionSetupHandler` → DecryptionTool; the keystore
        # is an uploaded key file (PostFile) or a server-side path
        from ..io.crypto import DecryptionTool, parse_key_material
        from ..io.upload import UploadedFile

        ks = p.get("keystore_id", "")
        obj = STORE.get(ks)
        if isinstance(obj, UploadedFile):
            with open(obj.path, "rb") as fh:
                raw = fh.read()
        elif ks and os.path.exists(ks):
            with open(ks, "rb") as fh:
                raw = fh.read()
        else:
            return _err(404, f"DecryptionSetup: keystore {ks!r} not found "
                             "(upload the key via PostFile first)")
        secret = parse_key_material(raw, p.get("keystore_type", "raw"))
        key = p.get("decrypt_tool_id") or make_key("decrypt_tool")
        tool = DecryptionTool(key, secret,
                              p.get("cipher_spec", "AES/CBC/PKCS5Padding"))
        STORE.put(key, tool)
        return 200, {"decrypt_tool_id": schemas.key_schema(key),
                     "decrypt_impl": "GenericDecryptionTool",
                     "cipher_spec": tool.cipher_spec}

    # -- node persistent storage --------------------------------------------
    if head == "NodePersistentStorage":
        from ..backend.nps import NPS

        if rest[1:] and rest[1] == "configured":
            return 200, {"configured": NPS.configured()}
        if rest[1:] and rest[1] == "categories":
            # /categories/{cat}/exists | /categories/{cat}/names/{n}/exists
            cat = rest[2] if rest[2:] else ""
            if rest[3:] and rest[3] == "names" and rest[4:]:
                return 200, {"exists": NPS.exists(cat, rest[4])}
            return 200, {"exists": NPS.exists(cat)}
        cat = rest[1] if rest[1:] else ""
        if not cat:
            return _err(404, "NodePersistentStorage: category required")
        name = rest[2] if rest[2:] else None
        if method == "GET" and name:
            return 200, {"__raw__": NPS.get(cat, name),
                         "__ctype__": "application/octet-stream",
                         "__filename__": name}
        if method == "GET":
            return 200, {"entries": NPS.list(cat)}
        if method == "POST":
            if name is None:
                import uuid

                name = str(uuid.uuid4())
            NPS.put(cat, name, p.get("value", ""))
            return 200, {"category": cat, "name": name}
        if method == "DELETE" and name:
            NPS.delete(cat, name)
            return 200, {}
        return _err(404, "NodePersistentStorage: bad request")

    # -- assembly (`POST /99/Assembly`, `GET /99/Assembly.java/...`) --------
    if head == "Assembly" and method == "POST":
        from .assembly_server import Assembly, parse_steps

        fr2 = STORE.get(p.get("frame", ""))
        if not isinstance(fr2, Frame):
            return _err(404, f"frame {p.get('frame')} not found")
        steps = parse_steps(p.get("steps"))
        asm = Assembly(steps)
        result = asm.fit(fr2)
        if result is fr2:  # empty pipeline: never rebind the input's key
            result = Frame(list(fr2.names), list(fr2.vecs))
        result.key = make_key("assembly_result")
        STORE.put_keyed(result)
        STORE.put(asm.key, asm)
        return 200, {"assembly": schemas.key_schema(asm.key, "Key<Assembly>"),
                     "result": schemas.key_schema(result.key, "Key<Frame>")}
    if head == "Assembly.java" and method == "GET" and rest[2:]:
        from .assembly_server import Assembly

        asm = STORE.get(urllib.parse.unquote(rest[1]))
        if not isinstance(asm, Assembly):
            return _err(404, f"assembly {rest[1]} not found")
        pojo_name = urllib.parse.unquote(rest[2])
        if pojo_name.endswith(".java"):
            pojo_name = pojo_name[:-5]
        return 200, {"__raw__": asm.to_java(pojo_name),
                     "__ctype__": "text/x-java",
                     "__filename__": f"{pojo_name}.java"}

    if head == "Recovery" and method == "POST" and rest[1:] \
            and rest[1] == "resume":
        # `POST /3/Recovery/resume` (`water/api/RecoveryHandler`, the
        # `-auto_recovery_dir` restart protocol): resume every incomplete
        # grid found under the recovery dir, skipping finished models
        from ..models.grid import GridSearch

        d = p.get("recovery_dir", "")
        if not d or not os.path.isdir(d):
            return _err(404, f"Recovery: no recovery dir at {d!r}")
        gs = GridSearch.resume(d)
        job = gs.train(background=True)
        return 200, {"job": schemas.job_schema(job),
                     "grid_id": schemas.key_schema(job.dest_key)}

    if head == "Grid.bin" and method == "POST":
        from ..models.grid import Grid, export_grid, import_grid

        if rest[1:] and rest[1] == "import":
            d = p.get("grid_path") or p.get("grid_directory") or ""
            if not os.path.isdir(d):
                return _err(404, f"no grid export at {d}")
            g = import_grid(d)
            return 200, {"name": g.key, "grid_id": schemas.key_schema(g.key)}
        if rest[2:] and rest[2] == "export":
            gid = urllib.parse.unquote(rest[1])
            g = STORE.get(gid)
            if not isinstance(g, Grid):
                return _err(404, f"grid {gid} not found")
            d = p.get("grid_directory") or p.get("grid_path") or ""
            if not d:
                return _err(400, "grid_directory is required")
            export_grid(g, d)
            return 200, {"grid_directory": d}
        return _err(404, "Grid.bin: use /import or /{grid_id}/export")

    # -- AutoML (`POST /99/AutoMLBuilder`, `GET /99/AutoML/{id}`,
    #    `GET /99/Leaderboards[/{project}]` — h2o-automl REST surface)
    if head == "AutoMLBuilder" and method == "POST":
        from ..models.automl import H2OAutoML as _AutoML

        spec = p.get("input_spec") or {}
        ctrl = p.get("build_control") or {}
        bm = p.get("build_models") or {}
        fr = STORE.get(spec.get("training_frame", ""))
        y = spec.get("response_column")
        if isinstance(y, dict):  # h2o-py sends {column_name: y}
            y = y.get("column_name")
        if fr is None or not y:
            return _err(404, "input_spec.training_frame and "
                             "input_spec.response_column are required")
        crit = ctrl.get("stopping_criteria") or {}
        aml = _AutoML(
            max_models=int(crit.get("max_models", 0) or 0),
            max_runtime_secs=float(crit.get("max_runtime_secs", 0) or 0),
            max_runtime_secs_per_model=float(
                crit.get("max_runtime_secs_per_model", 0) or 0),
            nfolds=int(ctrl.get("nfolds", 5) or 5),
            seed=(None if crit.get("seed") in (None, -1) else int(crit["seed"])),
            project_name=ctrl.get("project_name") or None,
            include_algos=bm.get("include_algos") or None,
            exclude_algos=bm.get("exclude_algos") or None,
            sort_metric=(spec.get("sort_metric") or None),
            stopping_rounds=int(crit.get("stopping_rounds", 3) or 3),
            stopping_tolerance=float(crit.get("stopping_tolerance", 1e-3)
                                     or 1e-3),
            stopping_metric=crit.get("stopping_metric", "AUTO") or "AUTO",
            ignored_columns=spec.get("ignored_columns") or None)
        job = Job("AutoML", work=1.0)
        job.dest_key = aml.key

        def run_automl():
            aml.train(y=y, training_frame=fr, job=job)
            return aml

        # managed dispatch: the AutoML run is tenant-stamped from the
        # request scope, lane-classed, and quota-checked like any build
        from .. import workload as _workload

        _workload.submit(job, run_automl, background=True,
                         cost_bytes=_workload.frame_cost(fr),
                         priority=aml.priority)
        return 200, {"job": schemas.job_schema(job),
                     "build_control": {"project_name": aml.key}}
    if head == "AutoML" and rest[1:]:
        from ..models.automl import H2OAutoML as _AutoML

        aml = STORE.get(urllib.parse.unquote(rest[1]))
        if not isinstance(aml, _AutoML):
            return _err(404, f"automl {rest[1]} not found")
        lb = aml.leaderboard
        return 200, {
            "automl_id": {"name": aml.key},
            "project_name": aml.key,
            "leader": schemas.key_schema(aml.leader.key) if aml.leader else None,
            "leaderboard_table": schemas.table_schema(
                lb.as_table()) if lb else None,
            "event_log_table": schemas.table_schema(aml.event_log.as_table()),
        }
    if head == "Leaderboards":
        from ..models.automl import H2OAutoML as _AutoML

        if not rest[1:]:
            return 200, {"projects": [a.key for a in STORE.values(_AutoML)]}
        aml = STORE.get(urllib.parse.unquote(rest[1]))
        if not isinstance(aml, _AutoML) or aml.leaderboard is None:
            return _err(404, f"no leaderboard for {rest[1]}")
        lb = aml.leaderboard
        return 200, {
            "project_name": aml.key,
            "table": schemas.table_schema(lb.as_table()),
            "models": [schemas.key_schema(m.key) for m in lb.sorted()],
            "sort_metric": lb.sort_metric,
        }

    # -- jobs ----------------------------------------------------------------
    if head == "Jobs":
        if rest[1:]:
            jid = urllib.parse.unquote(rest[1])
            job = STORE.get(jid)
            if not isinstance(job, Job):
                return _err(404, f"job {jid} not found")
            if rest[2:] and rest[2] == "cancel" and method == "POST":
                job.stop()
                return 200, {}
            return 200, {"jobs": [schemas.job_schema(job)]}
        return 200, {"jobs": [schemas.job_schema(j)
                              for j in STORE.values(Job)]}

    # -- rapids (`/99/Rapids`) ----------------------------------------------
    if head == "Rapids" and method == "GET" and rest[1:] \
            and rest[1] == "help":
        # `GET /99/Rapids/help` (`RapidsHandler.genHelp`) — the language's
        # registered primitives
        from ..rapids.exec import _PRIMS

        return 200, {"syntax": [
            {"name": n, "is_abstract": False} for n in sorted(_PRIMS)]}
    if head == "Rapids" and method == "POST":
        ast = p.get("ast", "")
        sid = p.get("session_id", "default")
        session = _SESSIONS.setdefault(sid, Session(sid))
        result = Rapids(session).exec(ast)
        return 200, _rapids_result(result)
    if head == "InitID":
        if method == "DELETE":
            sid = rest[1] if rest[1:] else "default"
            s = _SESSIONS.pop(sid, None)
            if s:
                s.end()
            for k in [k for k in _SESSION_PROPS if k[0] == sid]:
                del _SESSION_PROPS[k]  # props die with their session
            return 200, {}
        sid = f"_sid_{np.random.randint(1 << 30)}"
        _SESSIONS[sid] = Session(sid)
        return 200, {"session_key": sid}

    if head == "Typeahead":
        # `GET /3/Typeahead/files?src=...&limit=N` — path completion for the
        # import UI (`water/api/TypeaheadHandler`)
        src = p.get("src", "") or ""
        limit = int(p.get("limit", 100) or 100)
        import glob as _glob

        # escape glob metacharacters: src is a literal path prefix, not a
        # pattern ('/data/run[1]/' must match itself); non-positive limit
        # means unlimited (the H2O -1 convention)
        matches = sorted(_glob.glob(_glob.escape(src) + "*"))
        if limit > 0:
            matches = matches[:limit]
        return 200, {"src": src, "matches": matches}

    # -- observability -------------------------------------------------------
    if head == "JStack":
        # thread dumps — `water/api/JStackHandler` analog for the controller
        import sys
        import traceback as tb

        frames = sys._current_frames()
        threads = {t.ident: t.name for t in threading.enumerate()}
        traces = []
        for tid, frame in frames.items():
            traces.append({
                "thread": threads.get(tid, str(tid)),
                "stack": "".join(tb.format_stack(frame))})
        return 200, {"traces": traces}
    if head == "Logs":
        from ..utils.log import get_buffer, get_records

        limit = int(p.get("limit", 2000) or 0) or None
        if rest[1:] and rest[1] == "nodes" and len(rest) >= 5:
            # `GET /3/Logs/nodes/{nodeidx}/files/{name}`
            # (`water/api/LogsHandler`) — one controller, so every nodeidx
            # serves the same ring; `name` picks the per-level file, a
            # STRUCTURED level filter on the typed ring (friendly
            # spellings resolve via log._LEVEL_ALIASES); unknown names
            # serve the unfiltered ring, the old behavior
            from ..utils.log import _LEVEL_ALIASES

            want = (rest[4] if rest[4].upper() in _LEVEL_ALIASES else None)
            lines = get_buffer(limit=limit, level=want)
            return 200, {"log": "\n".join(lines),
                         "name": rest[4], "nodeidx": int(rest[2])}
        return 200, {"log": "\n".join(get_buffer(limit=limit)),
                     "records": get_records(limit=limit,
                                            level=p.get("level") or None)}
    if head == "Timeline":
        from ..utils import timeline as tl

        # full typed events (seq/ns/ms/kind/what + kind-specific detail),
        # newest-biased cap so a full 4096-event ring doesn't make every
        # poll serialize megabytes (`?limit=N`, `?kind=span` filter).
        # `?since=<seq>` is the incremental cursor: only events with a
        # larger seq return, OLDEST-first under `limit` (a >limit gap
        # drains losslessly across pulls — resume from `next_since`,
        # which echoes `since` when nothing new arrived); detect ring
        # overwrite by comparing the first returned seq against since+1
        limit = int(p.get("limit", 1000) or 0)
        # an EXPLICIT ?since= — including 0 (bootstrap from the start) —
        # selects cursor mode; absent = the newest-biased human view
        since_raw = p.get("since")
        since = (int(since_raw) if since_raw not in (None, "")
                 else None)
        events = tl.snapshot(limit=limit or None,
                             kind=p.get("kind") or None,
                             since=since)
        return 200, {"events": events,
                     "since": since,
                     "next_since": (events[-1]["seq"] if events
                                    else (since or 0)),
                     "total_recorded": tl.total_recorded(),
                     "capacity": tl.capacity()}
    if head == "Health":
        # liveness/readiness with typed degradation reasons — the signal
        # the autoscaling loop polls (utils/health.py); excluded from the
        # timeline ring like the monitoring polls above
        from ..utils import health as _health

        snap = _health.snapshot()
        return 200, schemas.health_schema(snap)
    if head == "Workload":
        # the multi-tenant scheduler surface (h2o_tpu/workload/): GET =
        # tenants/quotas/lanes/per-tenant burn + every live entry; POST =
        # configure a tenant's fair-share weight / quota fraction
        from .. import workload as _workload

        if method == "POST":
            name = p.get("tenant") or ""
            if not name:
                return _err(400, "POST /3/Workload needs 'tenant'")
            w = p.get("weight")
            q = p.get("quota_fraction")
            _workload.tenants.configure(
                name,
                weight=None if w in (None, "") else float(w),
                quota_fraction=None if q in (None, "") else float(q))
        return 200, schemas.workload_schema(_workload.snapshot())
    if head == "SlowTraces":
        # the tail-based capture ring (utils/slowtrace.py): full span
        # trees + program dispatch walls of requests that breached their
        # SLO p99 target
        from ..utils import slowtrace as _slowtrace

        if method == "DELETE":
            _slowtrace.clear()
            return 200, {}
        limit = int(p.get("limit", 0) or 0)
        return 200, schemas.slow_traces_schema(
            _slowtrace.snapshot(limit=limit or None),
            _slowtrace.total_captured())
    if head == "Metrics":
        # the unified telemetry registry — JSON by default, Prometheus
        # text exposition via ?format=prometheus (scrape-ready), and the
        # MERGED multi-process view via ?fleet=1 (utils/fleetobs.py
        # scrapes H2O_TPU_FLEET_PEERS + the spool dir and merges with
        # per-process labels — the observability substrate the
        # multi-process serving tier and multi-HOST ingest assume)
        from ..utils import telemetry

        from ..utils import slo as _slo

        # refresh the slo.worst_burn gauge before ANY metrics serve: a
        # Prometheus scraper that never polls /3/Health must still read
        # a current burn, not whatever the last health poll left behind
        _slo.burn_snapshot()
        if _truthy(p.get("fleet")):
            from ..utils import fleetobs

            return 200, {"fleet": fleetobs.collect(
                force=_truthy(p.get("force")))}
        if (p.get("format") or "").lower() in ("prometheus", "text"):
            return 200, {"__raw__": telemetry.prometheus(),
                         "__ctype__": "text/plain; version=0.0.4"}
        return 200, {"metrics": telemetry.snapshot(),
                     "trace_path": telemetry.trace_path(),
                     "pid": os.getpid(), "name": server.name,
                     "ts_ms": int(time.time() * 1000)}
    if head == "Programs":
        # the program cost registry (utils/programs.py): per compiled
        # program, XLA cost_analysis flops/bytes + memory_analysis
        # figures, the XLA module's name, host dispatch walls (enqueue
        # times) and the module's device seconds from the last capture
        # folded in (POST /3/Profiler/capture folds its own)
        from ..utils import programs as _programs
        from .schemas import programs_schema

        payload = programs_schema(_programs.snapshot(),
                                  _programs.last_fold())
        payload["ts_ms"] = int(time.time() * 1000)
        return 200, payload
    if head == "Flight":
        # flight-recorder bundles (utils/flightrec.py): listing, or one
        # bundle's full content by name
        from ..utils import flightrec as _flight

        if rest[1:]:
            return 200, {"bundle": _flight.read_bundle(rest[1])}
        return 200, {"dir": _flight.flight_dir(),
                     "armed": _flight.enabled(),
                     "bundles": _flight.list_bundles()}
    if head == "Profiler" and rest[1:] and rest[1] == "capture":
        # POST /3/Profiler/capture?ms=N — bounded LIVE device capture on
        # this process (the tool the real-v5e campaign points at a serving
        # replica); returns the capture directory. 400 when a session is
        # already running or ms is out of range.
        if method != "POST":
            return _err(405, "capture requires POST")
        from ..utils import telemetry as _telemetry

        ms = int(p.get("ms", 1000))
        path = _telemetry.capture(ms, out_dir=p.get("dir") or None)
        return 200, {"dir": path, "ms": ms}
    if head == "Profiler":
        # `water/api/ProfilerHandler`: cluster stack-sample aggregation; here
        # the controller process is sampled for `depth` rounds
        import sys
        import time as _time
        from collections import Counter

        depth = int(p.get("depth", 10))
        counts: Counter = Counter()
        for _ in range(max(depth, 1)):
            for frame in sys._current_frames().values():
                stack = []
                f = frame
                while f is not None and len(stack) < 20:
                    stack.append(f"{f.f_code.co_filename}:{f.f_lineno} "
                                 f"{f.f_code.co_name}")
                    f = f.f_back
                counts["\n".join(stack)] += 1
            _time.sleep(0.005)
        nodes = [{"node_name": server.name,
                  "entries": [{"stacktrace": s, "count": c}
                              for s, c in counts.most_common(50)]}]
        # per-task phase aggregation (`MRTask.profile()` records rolled up
        # process-wide) — the view that says WHERE task time went, next to
        # the stack samples that say where threads are right now
        from ..utils.profile import aggregate_snapshot

        return 200, {"nodes": nodes, "task_profiles": aggregate_snapshot()}
    if head == "WaterMeterCpuTicks":
        # `water/api/WaterMeterCpuTicksHandler` — /proc/stat per-core ticks
        ticks = []
        try:
            with open("/proc/stat") as f:
                for line in f:
                    if line.startswith("cpu") and line[3:4].isdigit():
                        vals = [int(x) for x in line.split()[1:5]]
                        ticks.append(vals)  # user, nice, sys, idle
        except OSError:
            pass
        return 200, {"cpu_ticks": ticks}
    if head == "WaterMeterIo":
        # `water/api/WaterMeterIoHandler` — process I/O counters
        io = {}
        try:
            with open("/proc/self/io") as f:
                for line in f:
                    k, _, v = line.partition(":")
                    io[k.strip()] = int(v)
        except OSError:
            pass
        return 200, {"persist_stats": [{
            "backend": "ice", "store_count": 0,
            "load_bytes": io.get("read_bytes", 0),
            "store_bytes": io.get("write_bytes", 0)}]}
    if head == "NetworkTest":
        from ..utils.devicebench import network_test

        return 200, network_test()
    if head == "Metadata":
        # `/3/Metadata/endpoints` + `/3/Metadata/schemas` — the
        # schema-metadata surface that drives client codegen
        # (`water/api/MetadataHandler`, consumed by h2o-bindings)
        sub = rest[1] if rest[1:] else "endpoints"
        if sub == "endpoints":
            if rest[2:]:
                # `GET /3/Metadata/endpoints/{path}` — one route, by index
                # or by url fragment (`MetadataHandler.fetchRoute`)
                which = urllib.parse.unquote("/".join(rest[2:]))
                if which.isdigit() and int(which) < len(_ROUTES_DOC):
                    return 200, {"routes": [_ROUTES_DOC[int(which)]]}
                hits = [r for r in _ROUTES_DOC
                        if which in r["url_pattern"]]
                if not hits:
                    return _err(404, f"no endpoint matching {which}")
                return 200, {"routes": hits}
            return 200, {"routes": _ROUTES_DOC}
        if sub == "schemas":
            # reference schema-class naming (`hex/schemas/*V3`): acronym
            # algos keep their acronym, the rest camel-case
            special = {"gbm": "GBM", "drf": "DRF", "glm": "GLM",
                       "gam": "GAM", "psvm": "PSVM", "svd": "SVD",
                       "pca": "PCA", "glrm": "GLRM", "coxph": "CoxPH",
                       "anovaglm": "ANOVAGLM", "dt": "DT",
                       "kmeans": "KMeans", "deeplearning": "DeepLearning",
                       "naivebayes": "NaiveBayes",
                       "isolationforest": "IsolationForest",
                       "extendedisolationforest": "ExtendedIsolationForest",
                       "upliftdrf": "UpliftDRF",
                       "targetencoder": "TargetEncoder",
                       "stackedensemble": "StackedEnsemble",
                       "rulefit": "RuleFit", "isotonic": "IsotonicRegression",
                       "modelselection": "ModelSelection",
                       "adaboost": "AdaBoost", "word2vec": "Word2Vec",
                       "aggregator": "Aggregator", "infogram": "Infogram",
                       "generic": "Generic", "xgboost": "XGBoost"}
            names = sorted(
                {f"{special.get(a, a.capitalize())}ParametersV3"
                 for a in registry.algo_names()}
                | {"CloudV3", "FramesV3", "FrameV3", "JobsV3", "JobV3",
                   "ModelsV3", "ModelSchemaV3", "ModelBuildersV3",
                   "RapidsSchemaV3", "ImportFilesV3", "ParseV3",
                   "ParseSetupV3", "InitIDV3", "ShutdownV3", "LogsV3",
                   "TimelineV3", "MetricsV3", "ProfilerV3", "NetworkTestV3",
                   "HealthV3", "SlowTracesV3",
                   "PartialDependenceV3", "PermutationVarImpV3",
                   "TwoDimTableV3", "KeyV3", "H2OErrorV3"})
            if rest[2:]:
                # `GET /3/Metadata/schemas/{schemaname}`
                want = urllib.parse.unquote(rest[2])
                if want not in names:
                    return _err(404, f"unknown schema {want}")
                return 200, {"schemas": [{"name": want, "version": 3}]}
            return 200, {"schemas": [{"name": n, "version": 3}
                                     for n in names]}
        if sub == "schemaclasses" and rest[2:]:
            # `GET /3/Metadata/schemaclasses/{classname}` — the schema-class
            # view is the schema view here (no Java class layer)
            want = urllib.parse.unquote(rest[2])
            return 200, {"schemas": [{"name": want, "version": 3}]}
        return _err(404, f"unknown metadata view {sub}")

    return _err(404, f"no route for {method} /{'/'.join(parts)}")


_ROUTES_DOC = [
    {"http_method": m, "url_pattern": u, "summary": s}
    for m, u, s in [
        ("GET", "/3/Cloud", "cluster status"),
        ("HEAD", "/3/Cloud", "cluster liveness, headers only"),
        ("GET", "/99/Sample", "cluster status (experimental alias)"),
        ("GET", "/3/About", "version info"),
        ("GET", "/3/KillMinus3", "log all stack traces"),
        ("POST", "/3/CloudLock", "lock the cloud with a reason"),
        ("POST", "/3/UnlockKeys", "unlock all write-locked keys"),
        ("GET", "/3/SessionProperties", "read a session property"),
        ("POST", "/3/SessionProperties", "set a session property"),
        ("GET", "/3/SteamMetrics", "cluster idle time for Steam"),
        ("POST", "/3/Shutdown", "shut the cluster down"),
        ("GET", "/3/ImportFiles", "import files by path/URI"),
        ("POST", "/3/PostFile", "upload raw bytes for parsing"),
        ("POST", "/3/PostFile.bin", "upload a binary artifact"),
        ("GET", "/99/Models.bin/{id}", "save a binary model server-side"),
        ("POST", "/99/Models.bin", "load a binary model server-side"),
        ("GET", "/3/Models.fetch.bin/{id}", "download a binary model"),
        ("POST", "/99/Models.upload.bin", "import an uploaded binary model"),
        ("POST", "/3/ParseSetup", "guess parse setup"),
        ("POST", "/3/Parse", "parse files into a Frame"),
        ("GET", "/3/Frames", "list frames"),
        ("GET", "/3/Frames/{id}", "frame detail with row preview"),
        ("GET", "/3/Frames/{id}/summary", "frame summary with column stats"),
        ("GET", "/3/Frames/{id}/light", "frame names/types only"),
        ("GET", "/3/Frames/{id}/columns", "frame columns"),
        ("GET", "/3/Frames/{id}/columns/{column}", "one column's stats"),
        ("GET", "/3/Frames/{id}/columns/{column}/domain",
         "categorical levels + counts"),
        ("GET", "/3/Frames/{id}/columns/{column}/summary",
         "column histogram + percentiles"),
        ("GET", "/3/FrameChunks/{id}", "chunk/shard layout of a frame"),
        ("POST", "/3/Frames/{id}/export", "export a frame to csv/parquet"),
        ("GET", "/3/Frames/{id}/export/{path}/overwrite/{force}",
         "export a frame (GET form)"),
        ("POST", "/3/Frames/{id}/save", "save a frame in binary form"),
        ("POST", "/3/Frames/load", "load a binary-saved frame"),
        ("DELETE", "/3/Frames/{id}", "remove a frame"),
        ("DELETE", "/3/Frames", "remove all frames"),
        ("GET", "/3/Find", "find a value in a frame"),
        ("POST", "/3/ImportFilesMulti", "import many paths/patterns"),
        ("GET", "/3/ModelBuilders", "list algorithms"),
        ("GET", "/3/ModelBuilders/{algo}", "algorithm parameter metadata"),
        ("POST", "/3/ModelBuilders/{algo}", "launch a training job"),
        ("POST", "/3/ModelBuilders/{algo}/parameters",
         "validate parameters without training"),
        ("GET", "/3/Word2VecSynonyms", "nearest words in a w2v embedding"),
        ("GET", "/3/Capabilities", "core + REST extension listing"),
        ("GET", "/3/Models", "list models"),
        ("GET", "/3/Models/{id}", "model detail"),
        ("GET", "/3/Models/{id}/mojo", "export MOJO"),
        ("GET", "/3/Models.java/{id}", "POJO scoring source"),
        ("GET", "/3/Models.java/{id}/preview", "POJO source preview"),
        ("GET", "/99/Models.mojo/{id}", "export MOJO server-side"),
        ("GET", "/99/Models/{id}/json", "model detail as exportable JSON"),
        ("POST", "/3/ModelBuilders/{algo}/model_id", "fresh unique model id"),
        ("DELETE", "/3/Models/{id}", "remove a model"),
        ("DELETE", "/3/Models", "remove all models"),
        ("POST", "/3/Serving/models/{id}",
         "register + warm up a model (or MOJO) for online scoring"),
        ("DELETE", "/3/Serving/models/{id}", "unregister a served model"),
        ("POST", "/3/Serving/score",
         "micro-batched row-dict scoring (429/408 on overload/deadline)"),
        ("GET", "/3/Serving/stats",
         "serving latency/throughput/occupancy/queue stats"),
        ("POST", "/3/Serving/routes/{endpoint}",
         "map an endpoint onto weighted variants (canary + shadow)"),
        ("GET", "/3/Serving/routes",
         "route table with per-variant divergence stats"),
        ("DELETE", "/3/Serving/routes/{endpoint}", "drop a route"),
        ("GET", "/3/Serving/control",
         "fleet placement/quota snapshot (admission control plane)"),
        ("POST", "/3/Predictions/models/{m}/frames/{f}", "score a frame"),
        ("POST", "/4/Predictions/models/{m}/frames/{f}",
         "score a frame asynchronously (job)"),
        ("POST", "/3/PartialDependence", "partial dependence"),
        ("GET", "/3/PartialDependence/{name}",
         "fetch a stored partial dependence result"),
        ("POST", "/3/Recovery/resume", "resume grids from a recovery dir"),
        ("POST", "/3/PermutationVarImp", "permutation importance"),
        ("GET", "/3/Jobs", "list jobs"),
        ("GET", "/3/Jobs/{id}", "poll a job"),
        ("POST", "/3/Jobs/{id}/cancel", "cancel a job"),
        ("POST", "/99/Rapids", "execute a rapids expression"),
        ("GET", "/99/Rapids/help", "rapids language primitives"),
        ("POST", "/3/InitID", "open a session"),
        ("GET", "/3/InitID", "open a session"),
        ("DELETE", "/3/InitID", "end a session"),
        ("GET", "/3/JStack", "thread stack dump"),
        ("GET", "/3/Logs", "node log ring"),
        ("GET", "/3/Logs/nodes/{nodeidx}/files/{name}",
         "one node's log file, filtered by level"),
        ("GET", "/3/Timeline",
         "typed event timeline ring (limit/kind; ?since=<seq> is the "
         "incremental poll cursor)"),
        ("GET", "/3/Health",
         "liveness/readiness with typed degradation reasons + SLO burn "
         "(devices, Cleaner headroom, serving queues, job heartbeats, "
         "watchdog trips)"),
        ("GET", "/3/Workload",
         "multi-tenant workload manager snapshot: tenants (weights, "
         "quotas, preempt/shed counters), scheduler entries and dispatch "
         "configuration"),
        ("POST", "/3/Workload",
         "configure a tenant: weight (fair-share tickets) and "
         "quota_fraction (share of the HBM reservation ledger)"),
        ("GET", "/3/SlowTraces",
         "tail-based slow-request capture ring: span trees + program "
         "dispatch walls of SLO p99 breachers"),
        ("DELETE", "/3/SlowTraces", "clear the slow-trace ring"),
        ("GET", "/3/Metrics",
         "unified telemetry registry (JSON; ?format=prometheus; "
         "?fleet=1 merges peer processes with per-process labels)"),
        ("GET", "/3/Programs",
         "program cost registry: per-executable XLA flops/bytes/memory, "
         "measured walls, roofline fraction"),
        ("GET", "/3/Flight", "flight-recorder bundle listing"),
        ("GET", "/3/Flight/{name}", "one flight bundle's content"),
        ("POST", "/3/Profiler/capture",
         "bounded live jax.profiler device capture (?ms=N)"),
        ("GET", "/3/Profiler", "stack samples + task phase aggregation"),
        ("GET", "/3/WaterMeterCpuTicks/{node}", "cpu tick counters"),
        ("GET", "/3/WaterMeterIo", "io counters"),
        ("GET", "/3/WaterMeterIo/{nodeidx}", "one node's io counters"),
        ("GET", "/3/NetworkTest", "device microbenchmarks"),
        ("GET", "/3/Typeahead/files", "path completion for import"),
        ("GET", "/3/Metadata/endpoints", "this listing"),
        ("GET", "/3/Metadata/endpoints/{path}", "one endpoint's doc"),
        ("GET", "/3/Metadata/schemas", "schema catalog"),
        ("GET", "/3/Metadata/schemas/{schemaname}", "one schema's doc"),
        ("GET", "/3/Metadata/schemaclasses/{classname}",
         "one schema class's doc"),
        ("GET", "/3/ModelMetrics", "list stored model metrics"),
        ("DELETE", "/3/ModelMetrics", "drop all cached metrics"),
        ("GET", "/3/ModelMetrics/models/{m}", "all metrics of one model"),
        ("DELETE", "/3/ModelMetrics/models/{m}",
         "drop one model's cached metrics"),
        ("GET", "/3/ModelMetrics/frames/{f}", "metrics computed on a frame"),
        ("DELETE", "/3/ModelMetrics/frames/{f}",
         "drop one frame's cached metrics"),
        ("GET", "/3/ModelMetrics/models/{m}/frames/{f}",
         "compute metrics of a model on a frame"),
        ("POST", "/3/ModelMetrics/models/{m}/frames/{f}",
         "recompute metrics, optionally storing predictions"),
        ("DELETE", "/3/ModelMetrics/models/{m}/frames/{f}",
         "drop one cached model-on-frame metric"),
        ("GET", "/3/ModelMetrics/frames/{f}/models/{m}",
         "metrics of a model on a frame (frame-first form)"),
        ("DELETE", "/3/ModelMetrics/frames/{f}/models/{m}",
         "drop one cached metric (frame-first form)"),
        ("POST",
         "/3/ModelMetrics/predictions_frame/{p}/actuals_frame/{a}",
         "make metrics from a predictions frame + actuals"),
        ("POST", "/3/CreateFrame", "synthesize a random frame"),
        ("POST", "/3/SplitFrame", "random-split a frame"),
        ("POST", "/3/Interaction", "combined categorical interaction columns"),
        ("POST", "/3/MissingInserter", "inject NAs into a frame"),
        ("GET", "/3/DownloadDataset", "frame as raw CSV"),
        ("GET", "/3/DownloadDataset.bin", "frame as raw CSV (streaming)"),
        ("GET", "/3/Tree", "inspect one tree of a tree model"),
        ("DELETE", "/3/DKV/{key}", "remove one key"),
        ("DELETE", "/3/DKV", "remove all keys"),
        ("POST", "/3/GarbageCollect", "force a gc cycle"),
        ("POST", "/3/LogAndEcho", "log and echo a message"),
        ("GET", "/3/Ping", "liveness + uptime"),
        ("POST", "/99/Grid/{algo}", "launch a grid search"),
        ("GET", "/99/Grids", "list grids"),
        ("GET", "/99/Grids/{id}", "grid detail with ranked models"),
        ("DELETE", "/99/Grids/{id}", "remove a grid"),
        ("POST", "/3/Grid.bin/import", "import an exported grid"),
        ("POST", "/3/Grid.bin/{id}/export", "export a grid and its models"),
        ("POST", "/3/FeatureInteraction",
         "xgbfi feature-interaction tables for a tree model"),
        ("POST", "/3/FriedmansPopescusH",
         "Friedman-Popescu H interaction statistic"),
        ("POST", "/3/SignificantRules", "RuleFit rule-importance table"),
        ("GET", "/3/GetGLMRegPath",
         "a GLM's regularisation path: per lambda, coefficients and "
         "explained deviance"),
        ("POST", "/99/Tabulate", "co-occurrence tabulation of two columns"),
        ("POST", "/99/DCTTransformer", "row-wise discrete cosine transform"),
        ("POST", "/99/ImportSQLTable", "import a SQL table (sqlite3)"),
        ("POST", "/3/ImportHiveTable", "import a Hive table (gated)"),
        ("POST", "/3/SaveToHiveTable", "export to a Hive table (gated)"),
        ("POST", "/3/ParseSVMLight", "parse SVMLight files directly"),
        ("POST", "/3/DecryptionSetup", "register an AES decryption tool"),
        ("GET", "/3/NodePersistentStorage/configured", "NPS availability"),
        ("GET", "/3/NodePersistentStorage/categories/{category}/exists",
         "category existence"),
        ("GET", "/3/NodePersistentStorage/categories/{category}"
                "/names/{name}/exists", "entry existence"),
        ("GET", "/3/NodePersistentStorage/{category}", "list a category"),
        ("GET", "/3/NodePersistentStorage/{category}/{name}",
         "fetch an entry"),
        ("POST", "/3/NodePersistentStorage/{category}",
         "store under a fresh uuid name"),
        ("POST", "/3/NodePersistentStorage/{category}/{name}",
         "store under a name"),
        ("DELETE", "/3/NodePersistentStorage/{category}/{name}",
         "delete an entry"),
        ("POST", "/99/Assembly", "fit a munging pipeline"),
        ("GET", "/99/Assembly.java/{assembly_id}/{file_name}",
         "pipeline as a self-contained Java class"),
        ("POST", "/99/AutoMLBuilder", "launch an AutoML run"),
        ("GET", "/99/AutoML/{id}", "AutoML run detail + event log"),
        ("GET", "/99/Leaderboards", "list AutoML projects"),
        ("GET", "/99/Leaderboards/{project}", "project leaderboard"),
    ]
]


def _make_metrics_route(rest: list[str], p: dict) -> tuple[int, dict]:
    """`POST /3/ModelMetrics/predictions_frame/{p}/actuals_frame/{a}`
    (`ModelMetricsHandler.make`, `ModelMetricsBinomial.make` et al.): build
    metrics straight from a predictions frame and an actuals column, no
    model involved. `domain` (class labels) picks the category: absent →
    regression (1 prediction column), 2 labels → binomial (1 column of
    class-1 probabilities), >2 → multinomial (k probability columns)."""
    import jax.numpy as jnp

    from ..models import metrics as M

    pid = urllib.parse.unquote(rest[2])
    if not (rest[3:] and rest[3] == "actuals_frame" and rest[4:]):
        return _err(404, "ModelMetrics make: "
                         "/predictions_frame/{p}/actuals_frame/{a}")
    aid = urllib.parse.unquote(rest[4])
    pred, act = STORE.get(pid), STORE.get(aid)
    if not isinstance(pred, Frame):
        return _err(404, f"predictions frame {pid} not found")
    if not isinstance(act, Frame):
        return _err(404, f"actuals frame {aid} not found")
    domain = p.get("domain")
    if isinstance(domain, str) and domain:
        domain = [d.strip(" '\"") for d in domain.strip("[]").split(",")]
    av = act.vecs[0]
    y = av.to_numpy()
    if domain:
        if av.domain and list(av.domain) != list(domain):
            # actuals encoded against their own domain: remap to the
            # caller's label order (the reference adapts via the domain)
            remap = {lv: i for i, lv in enumerate(domain)}
            codes = np.array([remap.get(av.domain[int(c)], -1)
                              if not np.isnan(c) else np.nan for c in y])
            y = codes
        if len(domain) == 2:
            if pred.ncol != 1:
                return _err(400, "binomial make: predictions_frame must "
                                 "have exactly one class-1 probability "
                                 "column")
            mm = M.make_binomial_metrics(jnp.asarray(y),
                                         jnp.asarray(pred.vecs[0].to_numpy()))
        else:
            if pred.ncol != len(domain):
                return _err(400, f"multinomial make: predictions_frame must "
                                 f"have {len(domain)} probability columns")
            probs = np.stack([v.to_numpy() for v in pred.vecs], axis=1)
            mm = M.make_multinomial_metrics(jnp.asarray(y),
                                            jnp.asarray(probs))
    else:
        if pred.ncol != 1:
            return _err(400, "regression make (domain=null): "
                             "predictions_frame must have exactly 1 column")
        mm = M.make_regression_metrics(jnp.asarray(y),
                                       jnp.asarray(pred.vecs[0].to_numpy()))
    return 200, {"model_metrics": [schemas.metrics_schema(mm) or {}]}


def _dest_name(path: str) -> str:

    base = os.path.basename(path)
    for ext in (".csv", ".gz", ".zip", ".parquet"):
        base = base.replace(ext, "")
    return base.replace(".", "_") + ".hex"


def _rapids_result(result) -> dict:
    """ValFrame/ValNum/ValStr serialization (`water/rapids/val/*`)."""
    if isinstance(result, dict) and result and all(
            isinstance(v, Frame) for v in result.values()):
        # ValMapFrame (`RapidsMapFrameV3`): named frames, DKV-published
        frames = []
        for v in result.values():
            STORE.put_keyed(v)
            frames.append({"key": schemas.key_schema(v.key)})
        return {"key": None, "string": None, "scalar": None,
                "map_keys": {"string": list(result.keys())},
                "frames": frames}
    if isinstance(result, Frame):
        STORE.put_keyed(result)
        return {"key": schemas.key_schema(result.key),
                "string": None, "scalar": None}
    if isinstance(result, Vec):
        fr = Frame([result.key or "C1"], [result])
        STORE.put_keyed(fr)
        return {"key": schemas.key_schema(fr.key),
                "string": None, "scalar": None}
    if isinstance(result, str):
        return {"key": None, "string": result, "scalar": None}
    if isinstance(result, (list, tuple)):
        return {"key": None, "string": None, "scalar": None,
                "values": schemas._clean(list(result))}
    if result is None:
        return {"key": None, "string": None, "scalar": None}
    return {"key": None, "string": None, "scalar": schemas._clean(result)}
