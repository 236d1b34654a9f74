"""graftlint v2 — interprocedural concurrency analysis (rules 14-17),
the incremental cache, and the machine-readable output modes.

Four layers:

1. per-rule fixture TRIPLES — each new rule fires on a violating snippet,
   stays quiet on the clean twin, and honors an inline suppression;
2. project-model unit pins — call-graph resolution (self./name/dotted/
   unique-method), the thread-entry map (Thread targets, nested closures,
   REST do_* handlers, `.start(fn)` dispatches), and guarded-by inference
   through one level of private helpers;
3. incremental cache — cold scan populates `.graftlint_cache/`-style
   entries, the warm scan is all hits with byte-identical results, a
   content edit invalidates exactly the edited file, and `--jobs N`
   parallel scans agree with serial;
4. output modes — SARIF 2.1.0 validates and carries rule/region data,
   `--format=github` emits ::error workflow commands, and
   `tools/ci_gate.sh` exists as the one exit-coded CI gate.

No jax import in the analyzer — these tests run in milliseconds.
"""

import json
import os
import stat
import time

import pytest

from tools.graftlint import (ALL_RULES, PROJECT_RULES, REPO_ROOT, Violation,
                             lint_paths, lint_project, render_github,
                             render_sarif)
from tools.graftlint.concurrency import (BlockingUnderLock, LockOrderCycle,
                                         UnguardedSharedField,
                                         UnjoinedThread)
from tools.graftlint.project import ProjectModel, extract_summary

pytestmark = pytest.mark.graftlint

FIXTURE_PATH = "h2o_tpu/serving/_fixture.py"


def _rules_hit(source: str, relpath: str = FIXTURE_PATH) -> list:
    return [(v.rule, v.line) for v in lint_project({relpath: source})]


def _ids(source: str, relpath: str = FIXTURE_PATH) -> set:
    return {r for r, _ in _rules_hit(source, relpath)}


# ---------------------------------------------------------------------------
# fixture triples
# ---------------------------------------------------------------------------
UNGUARDED_VIOLATING = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        while True:
            self.count += 1

    def read(self):
        return self.count

    def stop(self):
        self._t.join()
"""

UNGUARDED_CLEAN = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        while True:
            with self._lock:
                self.count += 1

    def read(self):
        with self._lock:
            return self.count

    def stop(self):
        self._t.join()
"""

CYCLE_VIOLATING = """
import threading

class TwoLocks:
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()

    def forward(self):
        with self._alock:
            with self._block:
                return 1

    def backward(self):
        with self._block:
            with self._alock:
                return 2
"""

CYCLE_CLEAN = """
import threading

class TwoLocks:
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()

    def forward(self):
        with self._alock:
            with self._block:
                return 1

    def backward(self):
        with self._alock:
            with self._block:
                return 2
"""

BLOCKING_VIOLATING = """
import threading
import time

class Poller:
    def __init__(self):
        self._lock = threading.Lock()

    def tick(self):
        with self._lock:
            time.sleep(0.1)
"""

BLOCKING_CLEAN = """
import threading
import time

class Poller:
    def __init__(self):
        self._lock = threading.Lock()

    def tick(self):
        with self._lock:
            n = 1
        time.sleep(0.1)
        return n
"""

UNJOINED_VIOLATING = """
import threading

class Svc:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        pass
"""

UNJOINED_CLEAN = """
import threading

class Svc:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        pass

    def stop(self):
        self._t.join(timeout=5.0)
"""

TRIPLES = {
    "unguarded-shared-field": (UNGUARDED_VIOLATING, UNGUARDED_CLEAN),
    "lock-order-cycle": (CYCLE_VIOLATING, CYCLE_CLEAN),
    "blocking-under-lock": (BLOCKING_VIOLATING, BLOCKING_CLEAN),
    "unjoined-thread": (UNJOINED_VIOLATING, UNJOINED_CLEAN),
}


@pytest.mark.parametrize("rule_id", sorted(TRIPLES))
def test_rule_fires_on_violating_fixture(rule_id):
    violating, _ = TRIPLES[rule_id]
    assert rule_id in _ids(violating)


@pytest.mark.parametrize("rule_id", sorted(TRIPLES))
def test_rule_quiet_on_clean_fixture(rule_id):
    _, clean = TRIPLES[rule_id]
    assert rule_id not in _ids(clean)


@pytest.mark.parametrize("rule_id", sorted(TRIPLES))
def test_rule_suppressed_inline(rule_id):
    violating, _ = TRIPLES[rule_id]
    flagged = [ln for r, ln in _rules_hit(violating) if r == rule_id]
    assert flagged
    lines = violating.splitlines()
    for ln in flagged:
        lines[ln - 1] += f"  # graftlint: disable={rule_id}"
    assert rule_id not in _ids("\n".join(lines))


# ---------------------------------------------------------------------------
# rule semantics pins
# ---------------------------------------------------------------------------
def test_guarded_by_inference_through_private_helper():
    """A private helper only ever called under the lock inherits the
    guard — the `_rows_per_s_locked` shape stays clean."""
    src = UNGUARDED_CLEAN.replace(
        """    def read(self):
        with self._lock:
            return self.count
""",
        """    def read(self):
        with self._lock:
            return self._read_locked()

    def _read_locked(self):
        return self.count
""")
    assert "unguarded-shared-field" not in _ids(src)


def test_unguarded_field_public_helper_does_not_inherit():
    """A PUBLIC method reading the field is externally callable — call
    sites holding the lock do not cover it, so the field stays flagged."""
    src = UNGUARDED_CLEAN.replace(
        """    def read(self):
        with self._lock:
            return self.count
""",
        """    def read(self):
        with self._lock:
            return self.peek()

    def peek(self):
        return self.count
""")
    assert "unguarded-shared-field" in _ids(src)


def test_init_only_fields_never_flagged():
    src = """
import threading

class Cfg:
    def __init__(self):
        self.window = 16
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        return self.window

    def read(self):
        return self.window

    def stop(self):
        self._t.join()
"""
    assert "unguarded-shared-field" not in _ids(src)


def test_lock_order_cycle_through_call_graph():
    """The inversion hides one call deep: forward holds A and calls a
    helper that takes B; backward holds B and calls a helper that takes
    A — the edge propagation through the call graph finds it."""
    src = """
import threading

class TwoLocks:
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()

    def _take_b(self):
        with self._block:
            return 1

    def _take_a(self):
        with self._alock:
            return 2

    def forward(self):
        with self._alock:
            return self._take_b()

    def backward(self):
        with self._block:
            return self._take_a()
"""
    assert "lock-order-cycle" in _ids(src)


def test_blocking_rule_exempts_wait_on_held_condition():
    src = """
import threading

class Q:
    def __init__(self):
        self._cv = threading.Condition()

    def take(self):
        with self._cv:
            self._cv.wait()
"""
    assert "blocking-under-lock" not in _ids(src)


def test_blocking_rule_sees_one_level_through_calls():
    src = """
import threading
import time

class Poller:
    def __init__(self):
        self._lock = threading.Lock()

    def _nap(self):
        time.sleep(0.5)

    def tick(self):
        with self._lock:
            self._nap()
"""
    hits = _rules_hit(src)
    assert ("blocking-under-lock" in {r for r, _ in hits})


def test_unjoined_thread_list_comprehension_pattern_is_clean():
    """The bench.py fan-out shape: a comprehension-built thread list
    joined through the loop variable drains every member."""
    src = """
import threading

def work(k):
    return k

def fan_out():
    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
"""
    assert "unjoined-thread" not in _ids(src)


def test_unjoined_fire_and_forget_is_flagged():
    src = """
import threading

def kick(fn):
    threading.Thread(target=fn, daemon=True).start()
"""
    assert "unjoined-thread" in _ids(src)


def test_project_rules_scope_excludes_tests():
    assert _ids(UNJOINED_VIOLATING, relpath="tests/test_x.py") == set()


# ---------------------------------------------------------------------------
# project-model unit pins (pass 1)
# ---------------------------------------------------------------------------
def _model(sources: dict) -> ProjectModel:
    return ProjectModel({p: extract_summary(p, s)
                         for p, s in sources.items()})


def test_thread_entry_map_covers_the_root_kinds():
    sources = {
        "h2o_tpu/a.py": """
import threading

class Batcher:
    def __init__(self):
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def _run(self):
        pass

def dispatch(job):
    job.start(run_build)

def run_build():
    pass
""",
        "h2o_tpu/h.py": """
from http.server import BaseHTTPRequestHandler

class Handler(BaseHTTPRequestHandler):
    def do_GET(self):
        pass
""",
    }
    roots = _model(sources).thread_roots()
    names = {k.split("::")[-1] for k in roots}
    assert "Batcher._run" in names          # Thread target
    assert "run_build" in names             # .start(fn) worker dispatch
    assert "Handler.do_GET" in names        # REST handler thread


def test_call_graph_resolution_forms():
    sources = {
        "h2o_tpu/a.py": """
from h2o_tpu.b import helper

class C:
    def m(self):
        return self.n() + helper() + only_here()

    def n(self):
        return 1

def only_here():
    return 2
""",
        "h2o_tpu/b.py": """
def helper():
    return 3

class Unique:
    def very_unique_method(self):
        return 4

class Caller:
    def go(self, obj):
        return obj.very_unique_method()
""",
    }
    m = _model(sources)
    key = "h2o_tpu/a.py::C.m"
    assert m.resolve_call(key, "self", "n", None) == "h2o_tpu/a.py::C.n"
    assert m.resolve_call(key, "name", "only_here",
                          None) == "h2o_tpu/a.py::only_here"
    assert m.resolve_call(key, "dotted", "h2o_tpu.b.helper",
                          None) == "h2o_tpu/b.py::helper"
    # unique-method-name index resolves obj.very_unique_method()
    caller = "h2o_tpu/b.py::Caller.go"
    assert m.resolve_call(caller, "attr", "very_unique_method",
                          None) == "h2o_tpu/b.py::Unique.very_unique_method"
    # blocklisted / ambiguous names do NOT resolve (no wrong edges)
    assert m.resolve_call(caller, "attr", "get", None) is None


def test_nested_closure_inherits_class_context():
    """The Job.start._run shape: a worker closure capturing self writes
    class fields from a thread root."""
    src = """
import threading

class JobLike:
    def start(self):
        def _run():
            self.status = "RUNNING"

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()

    def poll(self):
        return self.status

    def join(self):
        self._thread.join()
"""
    assert "unguarded-shared-field" in _ids(src)


# ---------------------------------------------------------------------------
# incremental cache + --jobs
# ---------------------------------------------------------------------------
def _write_tree(tmp_path, n=6):
    for i in range(n):
        (tmp_path / f"mod{i}.py").write_text(
            "import threading\n"
            f"def fn{i}():\n"
            f"    return {i}\n")
    return [f"mod{i}.py" for i in range(n)]


def test_cache_cold_then_warm_hits_and_identical_results(tmp_path):
    files = _write_tree(tmp_path)
    cache = str(tmp_path / ".cache")
    stats_cold, stats_warm = {}, {}
    t0 = time.perf_counter()
    cold = lint_paths(files, root=str(tmp_path), cache_dir=cache,
                      stats=stats_cold)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = lint_paths(files, root=str(tmp_path), cache_dir=cache,
                      stats=stats_warm)
    warm_s = time.perf_counter() - t0
    assert stats_cold["misses"] == len(files) and stats_cold["hits"] == 0
    assert stats_warm["hits"] == len(files) and stats_warm["misses"] == 0
    assert [v.key() for v in cold] == [v.key() for v in warm]
    # the whole point: a warm scan does no parsing (generous CI slack)
    assert warm_s <= max(cold_s * 1.5, 0.5), (cold_s, warm_s)


def test_cache_invalidates_only_the_edited_file(tmp_path):
    files = _write_tree(tmp_path)
    cache = str(tmp_path / ".cache")
    lint_paths(files, root=str(tmp_path), cache_dir=cache)
    (tmp_path / "mod0.py").write_text("def fn0():\n    return 99\n")
    stats = {}
    lint_paths(files, root=str(tmp_path), cache_dir=cache, stats=stats)
    assert stats["misses"] == 1 and stats["hits"] == len(files) - 1


def test_cached_violations_round_trip(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental.shard_map import shard_map\n")
    cache = str(tmp_path / ".cache")
    first = lint_paths(["bad.py"], root=str(tmp_path), cache_dir=cache)
    second = lint_paths(["bad.py"], root=str(tmp_path), cache_dir=cache)
    assert [v.key() for v in first] == [v.key() for v in second]
    assert any(v.rule == "direct-shard-map" for v in second)


def test_jobs_parallel_scan_matches_serial(tmp_path):
    files = _write_tree(tmp_path, n=8)
    serial = lint_paths(files, root=str(tmp_path), cache=False)
    parallel = lint_paths(files, root=str(tmp_path), cache=False, jobs=4)
    assert [v.key() for v in serial] == [v.key() for v in parallel]


def test_warm_repo_gate_stays_fast():
    """The repo gate claim: with a warm cache the full default-scope scan
    (per-file replay + the live interprocedural pass) stays ~1 s class.
    Generous bound for loaded CI boxes."""
    stats = {}
    lint_paths(stats=stats)             # populate/refresh the cache
    t0 = time.perf_counter()
    stats2 = {}
    lint_paths(stats=stats2)
    warm_s = time.perf_counter() - t0
    assert stats2["misses"] == 0
    assert warm_s < 5.0, f"warm full scan took {warm_s:.2f}s"


# ---------------------------------------------------------------------------
# output modes + ci gate
# ---------------------------------------------------------------------------
def _fake_violation():
    return Violation(rule="blocking-under-lock", path="h2o_tpu/x.py",
                     line=12, col=4, message='sleep while holding "_lock"',
                     snippet="time.sleep(1)")


def test_sarif_output_validates():
    doc = json.loads(render_sarif([_fake_violation()]))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    res = run["results"][0]
    assert res["ruleId"] == "blocking-under-lock"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "h2o_tpu/x.py"
    assert loc["region"]["startLine"] == 12
    assert loc["region"]["snippet"]["text"] == "time.sleep(1)"
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "blocking-under-lock" in rules


def test_github_output_shape():
    out = render_github([_fake_violation()])
    assert out.startswith("::error file=h2o_tpu/x.py,line=12,col=5,")
    assert "title=graftlint blocking-under-lock" in out


def test_cli_format_flags(tmp_path, capsys):
    from tools.graftlint import main

    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental.shard_map import shard_map\n")
    assert main([str(bad), "--no-baseline", "--format", "sarif",
                 "--no-cache"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["runs"][0]["results"]
    assert main([str(bad), "--no-baseline", "--format", "github",
                 "--no-cache"]) == 1
    assert "::error " in capsys.readouterr().out


def test_cli_select_accepts_project_rules(capsys):
    from tools.graftlint import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("unguarded-shared-field", "lock-order-cycle",
                "blocking-under-lock", "unjoined-thread"):
        assert rid in out


def test_ci_gate_script_exists_and_is_executable():
    path = os.path.join(REPO_ROOT, "tools", "ci_gate.sh")
    assert os.path.exists(path)
    assert os.stat(path).st_mode & stat.S_IXUSR
    text = open(path).read()
    assert "tools.graftlint" in text
    assert "pytest" in text


def test_rule_catalog_is_twenty_three():
    from tools.graftlint import DATAFLOW_RULES

    ids = ([cls.id for cls in ALL_RULES]
           + [cls.id for cls in PROJECT_RULES]
           + [cls.id for cls in DATAFLOW_RULES])
    assert len(ids) == len(set(ids)) == 23
    assert {"unguarded-shared-field", "lock-order-cycle",
            "blocking-under-lock", "unjoined-thread",
            "unscoped-profiler-capture",
            "thread-without-trace-context"} <= set(ids)


def test_rules_docs_name_real_constructs():
    for cls in PROJECT_RULES:
        assert cls.doc and cls.id
