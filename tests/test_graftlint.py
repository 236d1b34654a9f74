"""graftlint — the repo-native static analyzer (tools/graftlint/).

Three layers:

1. per-rule fixtures — each of the 8 rules demonstrably fires on a
   violating snippet, stays quiet on the clean twin, and honors an inline
   ``# graftlint: disable=<rule>`` suppression (the acceptance triple);
2. framework mechanics — baseline matching survives line drift, regeneration
   is byte-deterministic, the --fix rewrites are behavior-preserving text
   edits, the knob registry accessors enforce registration;
3. the repo-wide gate — `h2o_tpu/ tests/ bench.py` lints clean against the
   checked-in baseline (tier-1: a new violation fails this test, not a
   reviewer's patience).

No jax import in the linter itself — these tests run in milliseconds.
"""

import json
import subprocess
import sys

import pytest

from tools.graftlint import (apply_baseline, lint_paths, lint_source,
                             load_baseline, main, write_baseline)
from tools.graftlint.core import REPO_ROOT, Violation, iter_py_files
from tools.graftlint.fixes import fix_source
from tools.graftlint.rules import ALL_RULES, registered_knobs

pytestmark = pytest.mark.graftlint

#: relpath under which fixtures lint (frame/ scope so untracked-resident
#: engages; harmless for every other rule)
FIXTURE_PATH = "h2o_tpu/frame/_fixture.py"

#: rule id -> (violating, clean) snippet pair. The suppressed variant is
#: derived mechanically: the violating line gains an inline disable.
FIXTURES = {
    "direct-shard-map": (
        """
from jax.experimental.shard_map import shard_map

fn = shard_map(lambda x: x, mesh=None)
""",
        """
from h2o_tpu.parallel.mesh import shard_map

fn = shard_map(lambda x: x, mesh=None)
""",
    ),
    "pspec-concat": (
        """
from jax.sharding import PartitionSpec as P

spec = P("rows") + P(None)
""",
        """
from jax.sharding import PartitionSpec as P

spec = P("rows", None)
""",
    ),
    "narrow-int-accumulate": (
        """
import jax.numpy as jnp

def hist(x):
    codes = x.astype(jnp.int8)
    return jnp.sum(codes)
""",
        """
import jax.numpy as jnp

def hist(x):
    codes = x.astype(jnp.int8)
    return jnp.sum(codes.astype(jnp.int32))
""",
    ),
    "untracked-resident": (
        """
import jax.numpy as jnp

class Holder:
    def __init__(self, x):
        self.buf = jnp.asarray(x)
""",
        """
import jax.numpy as jnp
from ..backend.memory import CLEANER

class Holder:
    def __init__(self, x):
        self.buf = jnp.asarray(x)
        CLEANER.track(self, self.buf.size * self.buf.dtype.itemsize)
""",
    ),
    "timing-without-sync": (
        """
import time
import jax.numpy as jnp

def bench(x):
    t0 = time.time()
    y = jnp.sum(x * 2)
    return time.time() - t0
""",
        """
import time
import jax
import jax.numpy as jnp

def bench(x):
    t0 = time.time()
    y = jax.block_until_ready(jnp.sum(x * 2))
    return time.time() - t0
""",
    ),
    "host-sync-in-trace": (
        """
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    return float(jnp.sum(x))
""",
        """
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    return jnp.sum(x)
""",
    ),
    "nondeterminism-in-trace": (
        """
import jax
import numpy as np

@jax.jit
def f(x):
    return x + np.random.rand()
""",
        """
import jax

@jax.jit
def f(x, key):
    return x + jax.random.uniform(key)
""",
    ),
    "unregistered-knob": (
        """
import os

v = os.environ.get("H2O_TPU_TOTALLY_NEW_KNOB", "1")
""",
        """
import os

v = os.environ.get("H2O_TPU_BINNED_STORE", "1")
""",
    ),
    "unregistered-failpoint": (
        """
from h2o_tpu.utils import failpoints

failpoints.hit("totally.new.site")
""",
        """
from h2o_tpu.utils import failpoints

failpoints.hit("parser.parse")
""",
    ),
    "swallowed-retryable": (
        """
from h2o_tpu.utils import failpoints


def read():
    try:
        failpoints.hit("io.remote")
        return 1
    except Exception:
        pass
""",
        """
from h2o_tpu.utils import failpoints


def read():
    try:
        failpoints.hit("io.remote")
        return 1
    except Exception as e:
        raise RuntimeError("read failed") from e
""",
    ),
    "unregistered-metric": (
        """
from h2o_tpu.utils import telemetry

telemetry.inc("totally.new.metric")
""",
        """
from h2o_tpu.utils import telemetry

telemetry.inc("mrtask.dispatch.count")
""",
    ),
    "direct-device-put": (
        """
import jax
from h2o_tpu.parallel.mesh import default_mesh, replicated

arr = jax.device_put([1.0], replicated(default_mesh()))
""",
        """
from h2o_tpu.parallel.mesh import put_replicated

arr = put_replicated([1.0])
""",
    ),
    "use-after-donate": (
        """
import jax

step = jax.jit(lambda a, b: a + b, donate_argnums=(1,))

def run(x, f):
    out = step(x, f)
    return out + f
""",
        """
import jax

step = jax.jit(lambda a, b: a + b, donate_argnums=(1,))

def run(x, f):
    f = step(x, f)
    return f + 1.0
""",
    ),
    "unscoped-profiler-capture": (
        """
import jax

def grab(workdir):
    jax.profiler.start_trace(workdir)
    do_work()
    jax.profiler.stop_trace()
""",
        """
from h2o_tpu.utils import telemetry

def grab(workdir):
    with telemetry.device_profile("grab", out_dir=workdir):
        do_work()
""",
    ),
    "thread-without-trace-context": (
        """
import threading
from concurrent.futures import ThreadPoolExecutor

from h2o_tpu.utils import telemetry

def work():
    with telemetry.span("worker.op"):
        pass

def spawn(items):
    t = threading.Thread(target=work, daemon=True)
    t.start()
    with ThreadPoolExecutor(max_workers=2) as ex:
        list(ex.map(work, items))
    return t
""",
        """
import threading
from concurrent.futures import ThreadPoolExecutor

from h2o_tpu.utils import telemetry

def work():
    with telemetry.span("worker.op"):
        pass

def spawn(items):
    t = threading.Thread(target=telemetry.carry_context(work),
                         daemon=True)
    t.start()
    with ThreadPoolExecutor(max_workers=2) as ex:
        list(ex.map(telemetry.carry_context(work), items))
    return t
""",
    ),
}


def _rules_hit(source: str, relpath: str = FIXTURE_PATH) -> list[str]:
    return [v.rule for v in lint_source(source, relpath=relpath)]


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_fires_on_violating_fixture(rule_id):
    violating, _ = FIXTURES[rule_id]
    assert rule_id in _rules_hit(violating)


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_quiet_on_clean_fixture(rule_id):
    _, clean = FIXTURES[rule_id]
    assert rule_id not in _rules_hit(clean)


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_suppressed_inline(rule_id):
    violating, _ = FIXTURES[rule_id]
    vs = lint_source(violating, relpath=FIXTURE_PATH)
    flagged = {v.line for v in vs if v.rule == rule_id}
    lines = violating.splitlines()
    for ln in flagged:
        lines[ln - 1] += f"  # graftlint: disable={rule_id}"
    assert rule_id not in _rules_hit("\n".join(lines))


def test_thread_without_trace_context_positional_form():
    """Rule 24 on positional Thread(...) style: args[0] is GROUP — the
    callable is args[1] (a carried positional target must stay clean, an
    uncarried one must flag)."""
    carried = """
import threading

from h2o_tpu.utils import telemetry

def work():
    with telemetry.span("w"):
        pass

def spawn():
    threading.Thread(None, telemetry.carry_context(work)).start()
"""
    assert "thread-without-trace-context" not in _rules_hit(carried)
    bare = carried.replace("telemetry.carry_context(work)", "work")
    assert "thread-without-trace-context" in _rules_hit(bare)


def test_use_after_donate_factory_and_ifexp_forms():
    """Rule 18 also tracks donating FACTORIES (make_train_fn(...,
    donate=True) donates the returned trainer's argument 3) and
    IfExp-wrapped jit bindings (review catch: the literal-jax.jit-only
    form missed the exact donation sites PR 12 introduces)."""
    factory = """
from h2o_tpu.models.tree.engine import make_train_fn

def run(cfg, grad, Xb, y, w, f, rest):
    step = make_train_fn(cfg, grad, donate=True)
    out = step(Xb, y, w, f, rest)
    return out, f
"""
    assert "use-after-donate" in _rules_hit(factory)
    rebound = factory.replace("out = step(Xb, y, w, f, rest)\n    return out, f",
                              "f = step(Xb, y, w, f, rest)\n    return f")
    assert "use-after-donate" not in _rules_hit(rebound)
    ifexp = """
import jax

def build(fn, donate):
    return jax.jit(fn, donate_argnums=(0,)) if donate else jax.jit(fn)

def run(fn, x):
    step = jax.jit(fn, donate_argnums=(0,)) if True else jax.jit(fn)
    y = step(x)
    return y + x
"""
    assert "use-after-donate" in _rules_hit(ifexp)


def test_swallowed_retryable_catches_tuple_and_dotted_forms():
    # `except (ValueError, Exception):` and `except builtins.Exception:`
    # swallow exactly as much as the bare spelling
    violating = FIXTURES["swallowed-retryable"][0]
    tupled = violating.replace("except Exception:",
                               "except (ValueError, Exception):")
    assert "swallowed-retryable" in _rules_hit(tupled)
    dotted = violating.replace("except Exception:",
                               "except builtins.Exception:")
    assert "swallowed-retryable" in _rules_hit(dotted)
    narrow = violating.replace("except Exception:",
                               "except (ValueError, KeyError):")
    assert "swallowed-retryable" not in _rules_hit(narrow)


def test_suppression_works_on_continuation_lines():
    # the disable comment may sit on ANY physical line of the flagged
    # statement — the natural spot when the first line is already long
    src = """
import jax.numpy as jnp

def f(x):
    codes = x.astype(jnp.int8)
    return jnp.sum(codes,
                   axis=0)  # graftlint: disable=narrow-int-accumulate
"""
    assert "narrow-int-accumulate" not in _rules_hit(src)


def test_fix_import_insertion_precedes_mid_prelude_use():
    # conftest.py-shaped module: an env read EXECUTES between import groups;
    # the inserted knobs import must land before it, not after the file's
    # last import (which would NameError at import time)
    src = ('"""Doc."""\n'
           "import os\n"
           "\n"
           'cache = os.environ.get("H2O_TPU_KEY_STRICT")\n'
           "\n"
           "import json\n")
    fixed = fix_source(src, "h2o_tpu/models/new.py")
    assert 'knobs.raw("H2O_TPU_KEY_STRICT")' in fixed
    compile(fixed, "<fixed>", "exec")
    knobs_at = fixed.splitlines().index("from h2o_tpu.utils import knobs")
    use_at = next(i for i, ln in enumerate(fixed.splitlines())
                  if "knobs.raw" in ln)
    assert knobs_at < use_at


def test_bare_disable_suppresses_all_rules():
    src = ('import os\n'
           'v = os.environ.get("H2O_TPU_NOT_A_KNOB")  # graftlint: disable\n')
    assert _rules_hit(src) == []


def test_direct_shard_map_attribute_form_flagged_once():
    src = ("import jax\n"
           "fn = jax.experimental.shard_map.shard_map(lambda x: x)\n")
    vs = [v for v in lint_source(src, relpath=FIXTURE_PATH)
          if v.rule == "direct-shard-map"]
    assert len(vs) == 1


def test_direct_shard_map_two_uses_one_line_both_flagged():
    # span CONTAINMENT dedup, not same-line dedup: two disjoint chains on
    # one line are two real occurrences
    src = ("import jax\n"
           "a, b = (jax.experimental.shard_map.shard_map(min),\n"
           "        jax.experimental.shard_map.shard_map(max))\n")
    one = ("import jax\n"
           "a, b = (jax.experimental.shard_map.shard_map(min),\n"
           "        jax.experimental.shard_map.shard_map(max))\n"
           ).replace("\n        jax", " jax")  # same two calls, one line
    for variant in (src, one):
        vs = [v for v in lint_source(variant, relpath=FIXTURE_PATH)
              if v.rule == "direct-shard-map"]
        assert len(vs) == 2, variant


def test_fix_import_insertion_respects_shebang():
    src = ("#!/usr/bin/env python\n"
           "# -*- coding: utf-8 -*-\n"
           "def f():\n"
           "    import os\n"
           '    return os.environ.get("H2O_TPU_BENCH_ROWS", "1")\n')
    fixed = fix_source(src, "h2o_tpu/models/script.py")
    lines = fixed.splitlines()
    assert lines[0] == "#!/usr/bin/env python"
    assert lines[1] == "# -*- coding: utf-8 -*-"
    assert "from h2o_tpu.utils import knobs" in lines[2:]
    compile(fixed, "<fixed>", "exec")


def test_mesh_module_itself_is_exempt():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert _rules_hit(src, relpath="h2o_tpu/parallel/mesh.py") == []


def test_timing_rule_window_is_positional():
    # sync BEFORE the timer restart must not launder the second window
    src = """
import time
import jax
import jax.numpy as jnp

def bench(x):
    t0 = time.time()
    jax.block_until_ready(jnp.sum(x))
    warm = time.time() - t0
    t0 = time.time()
    y = jnp.sum(x * 3)
    return warm, time.time() - t0
"""
    vs = [v for v in lint_source(src, relpath=FIXTURE_PATH)
          if v.rule == "timing-without-sync"]
    assert len(vs) == 1
    assert vs[0].line == src.splitlines().index(
        "    return warm, time.time() - t0") + 1


def test_narrow_accumulate_dtype_kwarg_is_clean():
    src = """
import jax.numpy as jnp

def f(x):
    h = jnp.zeros((4,), dtype=jnp.int16)
    return jnp.sum(h, dtype=jnp.int32)
"""
    assert "narrow-int-accumulate" not in _rules_hit(src)


def test_untracked_resident_scope_is_frame_and_models_only():
    violating, _ = FIXTURES["untracked-resident"]
    assert _rules_hit(violating, relpath="h2o_tpu/rapids/x.py") == []


# ---------------------------------------------------------------------------
# Baseline mechanics
# ---------------------------------------------------------------------------
def _fake_violation(line: int = 3) -> Violation:
    return Violation(rule="unregistered-knob", path="h2o_tpu/x.py",
                     line=line, col=0, message="m",
                     snippet='v = os.environ.get("H2O_TPU_Z")')


def test_baseline_matches_on_snippet_not_line(tmp_path):
    bl = tmp_path / "baseline.json"
    write_baseline([_fake_violation(line=3)], path=str(bl))
    drifted = _fake_violation(line=99)  # same code, new line number
    assert apply_baseline([drifted], load_baseline(str(bl))) == []
    other = Violation(rule="unregistered-knob", path="h2o_tpu/x.py", line=3,
                      col=0, message="m", snippet="something_else()")
    assert apply_baseline([other], load_baseline(str(bl))) == [other]


def test_baseline_update_is_deterministic(tmp_path):
    vs = [_fake_violation(line=9), _fake_violation(line=3),
          Violation(rule="pspec-concat", path="h2o_tpu/a.py", line=1, col=0,
                    message="m", snippet="s = a + b")]
    p1, p2 = tmp_path / "b1.json", tmp_path / "b2.json"
    write_baseline(vs, path=str(p1))
    write_baseline(list(reversed(vs)), path=str(p2))
    assert p1.read_bytes() == p2.read_bytes()
    entries = json.loads(p1.read_text())["entries"]
    assert [e["path"] for e in entries] == sorted(e["path"] for e in entries)


def test_baseline_update_preserves_reasons(tmp_path):
    bl = tmp_path / "baseline.json"
    write_baseline([_fake_violation()], path=str(bl))
    data = json.loads(bl.read_text())
    data["entries"][0]["reason"] = "legacy knob, removed in PR 9"
    bl.write_text(json.dumps(data))
    write_baseline([_fake_violation(line=50)], path=str(bl))
    assert (json.loads(bl.read_text())["entries"][0]["reason"]
            == "legacy knob, removed in PR 9")


# ---------------------------------------------------------------------------
# --fix rewrites
# ---------------------------------------------------------------------------
def test_fix_shard_map_import():
    src = ("from jax.experimental.shard_map import shard_map\n"
           "fn = shard_map(lambda x: x, mesh=None)\n")
    fixed = fix_source(src, "h2o_tpu/models/new.py")
    assert "from h2o_tpu.parallel.mesh import shard_map" in fixed
    assert "jax.experimental" not in fixed
    assert lint_source(fixed, relpath="h2o_tpu/models/new.py") == []


def test_fix_shard_map_attribute_call():
    src = ("import jax\n"
           "fn = jax.experimental.shard_map.shard_map(lambda x: x)\n")
    fixed = fix_source(src, "h2o_tpu/models/new.py")
    assert "from h2o_tpu.parallel.mesh import shard_map" in fixed
    assert "fn = shard_map(lambda x: x)" in fixed


def test_fix_leaves_module_form_shard_map_import_alone():
    # `from jax.experimental import shard_map` imports the MODULE; its call
    # sites spell shard_map.shard_map(...) — a function import would break
    # them, so the fixer must leave this form to the lint (still flagged)
    src = ("from jax.experimental import shard_map\n"
           "fn = shard_map.shard_map(lambda x: x)\n")
    assert fix_source(src, "h2o_tpu/models/new.py") == src
    assert "direct-shard-map" in _rules_hit(src)


def test_fix_knob_read_is_behavior_preserving():
    src = ('import os\n'
           'rows = int(os.environ.get("H2O_TPU_BENCH_ROWS", 11_000_000))\n')
    fixed = fix_source(src, "h2o_tpu/models/new.py")
    assert 'knobs.raw("H2O_TPU_BENCH_ROWS", 11_000_000)' in fixed
    assert "from h2o_tpu.utils import knobs" in fixed


def test_fix_leaves_unregistered_knob_alone():
    src = 'import os\nv = os.environ.get("H2O_TPU_NOT_DECLARED")\n'
    assert fix_source(src, "h2o_tpu/models/new.py") == src
    assert "unregistered-knob" in _rules_hit(src)


def test_pspec_nested_chain_flagged_once():
    src = """
from jax.sharding import PartitionSpec as P

spec = (P("a") + P("b")) + P("c")
"""
    vs = [v for v in lint_source(src, relpath=FIXTURE_PATH)
          if v.rule == "pspec-concat"]
    assert len(vs) == 1


def test_shipped_tree_is_a_fix_fixed_point():
    """The README tells contributors to run `--fix`; on a clean checkout it
    must be a no-op, or every contributor gets an unrelated dirty diff."""
    import os

    from tools.graftlint.core import DEFAULT_PATHS
    from tools.graftlint.rules import registered_knobs

    registry = registered_knobs()
    dirty = []
    for ap in iter_py_files(DEFAULT_PATHS):
        with open(ap, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(ap, REPO_ROOT)
        if fix_source(src, rel, registry=registry) != src:
            dirty.append(rel)
    assert not dirty, f"--fix would rewrite: {dirty}"


def test_fix_paths_roundtrip(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text("from jax.experimental.shard_map import shard_map\n")
    from tools.graftlint.fixes import fix_paths

    changed = fix_paths([str(mod)], root=str(tmp_path))
    assert changed == ["mod.py"]
    assert ("from h2o_tpu.parallel.mesh import shard_map"
            in mod.read_text())
    assert fix_paths([str(mod)], root=str(tmp_path)) == []  # idempotent


# ---------------------------------------------------------------------------
# Knob registry
# ---------------------------------------------------------------------------
def test_registry_covers_every_knob_the_tree_reads():
    names = registered_knobs()
    # the knobs the satellite explicitly migrates
    for knob in ("H2O_TPU_BINNED_STORE", "H2O_TPU_HIST_SEG_WIDTH",
                 "H2O_TPU_BENCH_ROWS", "H2O_TPU_BENCH_SIDECAR",
                 "H2O_TPU_HBM_LIMIT_BYTES"):
        assert knob in names


def test_knob_accessors(monkeypatch):
    from h2o_tpu.utils import knobs

    # the asserts below exercise unset-knob fallbacks — scrub any ambient
    # values a dev/CI shell may have exported
    for var in ("H2O_TPU_BENCH_SIDECAR", "H2O_TPU_BENCH_WORKLOADS",
                "H2O_TPU_HIST_SEG_WIDTH", "H2O_TPU_BINNED_STORE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("H2O_TPU_HIST_SEG_WIDTH", "4")
    assert knobs.get_int("H2O_TPU_HIST_SEG_WIDTH") == 4
    monkeypatch.delenv("H2O_TPU_HIST_SEG_WIDTH")
    assert knobs.get_int("H2O_TPU_HIST_SEG_WIDTH") == 8
    monkeypatch.setenv("H2O_TPU_BINNED_STORE", "off")
    assert knobs.get_bool("H2O_TPU_BINNED_STORE") is False
    monkeypatch.delenv("H2O_TPU_BINNED_STORE")
    assert knobs.get_bool("H2O_TPU_BINNED_STORE") is True
    # set-but-EMPTY bool reads as UNSET: a stale `export VAR=` line must not
    # flip the binned store (or wire UDFs) off — matches the pre-registry
    # per-site defaults
    monkeypatch.setenv("H2O_TPU_BINNED_STORE", "")
    assert knobs.get_bool("H2O_TPU_BINNED_STORE") is True
    assert knobs.raw("H2O_TPU_BENCH_SIDECAR", "dflt") == "dflt"
    with pytest.raises(KeyError):
        knobs.raw("H2O_TPU_NEVER_DECLARED")
    assert "H2O_TPU_BINNED_STORE" in knobs.describe()
    # set-but-EMPTY string knob means "nothing", not "the default" —
    # H2O_TPU_BENCH_WORKLOADS= must run zero bench legs, not all of them
    monkeypatch.setenv("H2O_TPU_BENCH_WORKLOADS", "")
    assert knobs.get_str("H2O_TPU_BENCH_WORKLOADS") == ""
    monkeypatch.delenv("H2O_TPU_BENCH_WORKLOADS")
    assert "gbm" in knobs.get_str("H2O_TPU_BENCH_WORKLOADS")
    # ...while an empty INT knob falls back (there is no int reading of "")
    monkeypatch.setenv("H2O_TPU_HIST_SEG_WIDTH", "")
    assert knobs.get_int("H2O_TPU_HIST_SEG_WIDTH") == 8


def test_registry_and_module_agree():
    from h2o_tpu.utils import knobs

    assert registered_knobs() == set(knobs.KNOBS)


# ---------------------------------------------------------------------------
# CLI + repo gate
# ---------------------------------------------------------------------------
def test_cli_list_rules_and_select(capsys):
    assert main(["--list-rules"]) == 0
    assert "direct-shard-map" in capsys.readouterr().out
    assert main(["--select", "no-such-rule"]) == 2


def test_cli_baseline_update_refuses_narrowed_scope(tmp_path, capsys):
    # a --select/explicit-path run sees only a slice of the violations;
    # regenerating the baseline from it would drop every other entry
    bl = tmp_path / "b.json"
    assert main(["--select", "pspec-concat", "--baseline-update",
                 "--baseline", str(bl)]) == 2
    assert main(["h2o_tpu/parallel", "--baseline-update",
                 "--baseline", str(bl)]) == 2
    assert not bl.exists()


def test_cli_fails_on_violating_file(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental.shard_map import shard_map\n")
    assert main([str(bad), "--no-baseline"]) == 1
    out = capsys.readouterr().out
    assert "direct-shard-map" in out
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert main([str(good), "--no-baseline"]) == 0


def test_cli_module_entrypoint_runs():
    # the documented invocation shape; rules restricted to the cheap ones so
    # the subprocess stays fast even on a loaded CI box
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--select",
         "direct-shard-map", "h2o_tpu/parallel"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_scan_set_includes_the_advertised_tree():
    files = {p.replace("\\", "/").rsplit("/", 1)[-1]
             for p in iter_py_files(("h2o_tpu", "tests", "bench.py"))}
    assert {"bench.py", "engine.py", "mesh.py", "conftest.py"} <= files


def test_every_rule_registered_exactly_once():
    from tools.graftlint import PROJECT_RULES

    ids = [cls.id for cls in ALL_RULES]
    assert len(ids) == len(set(ids)) == 15  # per-file rules
    both = ids + [cls.id for cls in PROJECT_RULES]
    assert len(both) == len(set(both)) == 19  # + interprocedural (v2)


def test_direct_device_put_forms():
    """Rule 13: every mesh-sharded device_put spelling outside the
    sanctioned placement sites fires — via-variable shardings included —
    while device-object placement (serving replica pinning) stays clean."""
    named = """
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

arr = jax.device_put(x, NamedSharding(mesh, P("rows")))
"""
    assert "direct-device-put" in _rules_hit(named)
    via_var = """
import jax
from h2o_tpu.parallel.mesh import default_mesh, row_sharding

rs = row_sharding(default_mesh())
arr = jax.device_put(x, rs)
"""
    assert "direct-device-put" in _rules_hit(via_var)
    kw = """
import jax
from h2o_tpu.parallel.mesh import replicated

arr = jax.device_put(x, device=replicated())
"""
    assert "direct-device-put" in _rules_hit(kw)
    # frame layer + mesh module are the sanctioned sites
    for ok_path in ("h2o_tpu/parallel/mesh.py", "h2o_tpu/frame/vec.py",
                    "h2o_tpu/frame/chunks.py"):
        assert "direct-device-put" not in _rules_hit(named, relpath=ok_path)
    # placing onto a bare Device (replica pinning) is device selection,
    # not frame-data partitioning
    dev = """
import jax

arr = jax.device_put(x, jax.devices()[0])
"""
    assert "direct-device-put" not in _rules_hit(dev)


def test_failpoint_registry_covers_every_site_the_tree_hits():
    """Dynamic twin of unregistered-failpoint: every literal site name in
    the shipped tree resolves against the registry module itself."""
    from h2o_tpu.utils import failpoints as fp
    from tools.graftlint.rules import registered_failpoints

    assert registered_failpoints() == set(fp.FAILPOINTS)
    assert set(fp.FAILPOINTS)  # the registry is not empty


def test_metric_registry_and_module_agree():
    """Dynamic twin of unregistered-metric: the AST parse of telemetry.py
    sees exactly the metrics the module declares at import."""
    from h2o_tpu.utils import telemetry
    from tools.graftlint.rules import registered_metrics

    assert registered_metrics() == set(telemetry.METRICS)
    assert set(telemetry.METRICS)  # the registry is not empty


def test_unregistered_metric_span_kwarg():
    """The span/lap `metric=` keyword is checked too, not just the
    positional accessor surface."""
    src = """
from h2o_tpu.utils import telemetry

with telemetry.span("anything", metric="not.a.metric"):
    pass
"""
    assert "unregistered-metric" in _rules_hit(src)
    ok = src.replace("not.a.metric", "mrtask.dispatch.seconds")
    assert "unregistered-metric" not in _rules_hit(ok)


def test_repo_gate_zero_nonbaselined_violations():
    """THE gate: the PR tree lints clean (fixed or baselined). A failure
    here prints the exact violations — fix them or (for pre-existing code
    under active refactor) add them to tools/graftlint/baseline.json with
    a reason via --baseline-update."""
    vs = apply_baseline(lint_paths(), load_baseline())
    assert not vs, "\n".join(v.render() for v in vs)
