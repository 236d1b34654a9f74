"""graftlint v3 — array-provenance dataflow analysis (rules 20-23).

Four layers, mirroring the v2 concurrency test plan:

1. per-rule fixture TRIPLES — each rule fires on a violating snippet,
   stays quiet on the clean twin (the sanctioned spelling: explicit
   `jax.device_get`, `mesh.put_*` re-placement, hoisted jit, rebind-on-
   dispatch), and honors an inline suppression;
2. provenance-propagation pins on the call graph — placement tags
   resolve through function returns, host ops hide one call below a hot
   root, donating factories resolve across functions, donation rides
   tuple packs and `f(*args)` star-dispatch (the GBM chunk-loop shape)
   and lexical closures, and a param-forwarding helper summarizes as
   donating;
3. scope/exemption semantics — hot-path locality for rule 20, traced-
   body exemption for rule 21, tests/ exclusion;
4. machine output + cache — findings carry column spans end to end
   (SARIF endColumn / ::error endColumn), provenance events round-trip
   through the incremental summary cache, and the rule catalog counts
   all three passes.

No jax import in the analyzer — these tests run in milliseconds.
"""

import json

import pytest

from tools.graftlint import (ALL_RULES, DATAFLOW_RULES, PROJECT_RULES,
                             Violation, lint_paths, lint_project,
                             render_github, render_sarif)
from tools.graftlint.dataflow import HOT_ROOTS, ProvInfo
from tools.graftlint.project import ProjectModel, extract_summary

pytestmark = pytest.mark.graftlint

#: rule-20 fixtures live at a HOT_ROOTS path/function; the others at a
#: neutral in-scope path
HOT_PATH = "h2o_tpu/parallel/mrtask.py"
FIXTURE_PATH = "h2o_tpu/models/_fixture.py"


def _violations(source: str, relpath: str = FIXTURE_PATH):
    return lint_project({relpath: source})


def _rules_hit(source: str, relpath: str = FIXTURE_PATH) -> list:
    return [(v.rule, v.line) for v in _violations(source, relpath)]


def _ids(source: str, relpath: str = FIXTURE_PATH) -> set:
    return {r for r, _ in _rules_hit(source, relpath)}


# ---------------------------------------------------------------------------
# fixture triples
# ---------------------------------------------------------------------------
HOST_VIOLATING = """
import jax.numpy as jnp

def _dispatch(fn, arrays):
    out = jnp.sum(arrays)
    return float(out)
"""

HOST_CLEAN = """
import jax
import jax.numpy as jnp

def _dispatch(fn, arrays):
    out = jnp.sum(arrays)
    host = jax.device_get(out)
    return float(host)
"""

COMBINE_VIOLATING = """
from h2o_tpu.parallel import mesh

def merge(x, y):
    rows = mesh.put_row_sharded(x)
    meta = mesh.put_replicated(y)
    return rows * meta
"""

COMBINE_CLEAN = """
from h2o_tpu.parallel import mesh

def merge(x, y):
    rows = mesh.put_row_sharded(x)
    meta = mesh.put_row_sharded(y)
    return rows * meta
"""

RECOMPILE_VIOLATING = """
import jax

def train(step, xs):
    for x in xs:
        fn = jax.jit(step)
        fn(x)
"""

RECOMPILE_CLEAN = """
import jax

def train(step, xs):
    fn = jax.jit(step)
    for x in xs:
        fn(x)
"""

DONATE_VIOLATING = """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def loop(fn, x, m):
    step = make_step(fn)
    out = step(x, m)
    return m + out
"""

DONATE_CLEAN = """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def loop(fn, x, m):
    step = make_step(fn)
    m = step(x, m)
    return m
"""

TRIPLES = {
    "host-transfer-in-hot-path": (HOST_VIOLATING, HOST_CLEAN, HOT_PATH),
    "mixed-sharding-combine": (COMBINE_VIOLATING, COMBINE_CLEAN,
                               FIXTURE_PATH),
    "recompile-hazard": (RECOMPILE_VIOLATING, RECOMPILE_CLEAN,
                         FIXTURE_PATH),
    "donate-across-calls": (DONATE_VIOLATING, DONATE_CLEAN, FIXTURE_PATH),
}


@pytest.mark.parametrize("rule_id", sorted(TRIPLES))
def test_rule_fires_on_violating_fixture(rule_id):
    violating, _, relpath = TRIPLES[rule_id]
    assert rule_id in _ids(violating, relpath)


@pytest.mark.parametrize("rule_id", sorted(TRIPLES))
def test_rule_quiet_on_clean_fixture(rule_id):
    _, clean, relpath = TRIPLES[rule_id]
    assert rule_id not in _ids(clean, relpath)


@pytest.mark.parametrize("rule_id", sorted(TRIPLES))
def test_rule_suppressed_inline(rule_id):
    violating, _, relpath = TRIPLES[rule_id]
    flagged = [ln for r, ln in _rules_hit(violating, relpath)
               if r == rule_id]
    assert flagged
    lines = violating.splitlines()
    for ln in flagged:
        lines[ln - 1] += f"  # graftlint: disable={rule_id}"
    assert rule_id not in _ids("\n".join(lines), relpath)


# ---------------------------------------------------------------------------
# rule 20 semantics — hot closure, lookthrough, implicit bool
# ---------------------------------------------------------------------------
def test_host_transfer_seen_through_hot_call_graph():
    """The hot label propagates over the call graph: the host sync lives
    in a helper the dispatch root calls, not in the root itself."""
    src = """
import jax.numpy as jnp

def _dispatch(fn, arrays):
    return _drain(arrays)

def _drain(arrays):
    out = jnp.sum(arrays)
    return float(out)
"""
    hits = _rules_hit(src, HOT_PATH)
    assert ("host-transfer-in-hot-path" in {r for r, _ in hits})


def test_host_transfer_hidden_one_call_below_is_flagged_at_site():
    """A device value handed to a helper that .item()s its parameter is
    flagged AT THE CALL SITE (the helper itself sees only an untagged
    param)."""
    src = """
import jax.numpy as jnp

def _dispatch(fn, arrays):
    out = jnp.sum(arrays)
    return _log_scalar(out)

def _log_scalar(v):
    return v.item()
"""
    hits = _rules_hit(src, HOT_PATH)
    flagged = [ln for r, ln in hits if r == "host-transfer-in-hot-path"]
    assert flagged == [6]   # the _log_scalar(out) call, not line 9


def test_implicit_bool_of_device_value_is_flagged():
    src = """
import jax.numpy as jnp

def _dispatch(fn, arrays):
    mask = jnp.any(arrays)
    if mask:
        return 1
    return 0
"""
    assert "host-transfer-in-hot-path" in _ids(src, HOT_PATH)


def test_same_sync_outside_hot_sections_is_quiet():
    """The rule is about hot paths, not np. usage in general — the same
    implicit sync in a non-root function at a non-root path is fine."""
    src = """
import jax.numpy as jnp

def summarize(arrays):
    out = jnp.sum(arrays)
    return float(out)
"""
    assert "host-transfer-in-hot-path" not in _ids(src)


def test_hot_roots_name_real_functions():
    """Every hot root must point at code that exists — a renamed root
    would silently turn the rule (and the runtime twin's coverage story)
    off."""
    import os

    from tools.graftlint import REPO_ROOT

    for suffix, name, _desc in HOT_ROOTS:
        path = os.path.join(REPO_ROOT, suffix)
        if not os.path.exists(path):
            continue  # serving/runtime.py score lives on the class
        src = open(path).read()
        assert f"def {name}" in src, (suffix, name)


# ---------------------------------------------------------------------------
# rule 21 semantics — interprocedural tags, traced exemption
# ---------------------------------------------------------------------------
def test_mixed_sharding_tags_resolve_through_returns():
    src = """
from h2o_tpu.parallel import mesh

def _rows(x):
    return mesh.put_row_sharded(x)

def _meta(y):
    return mesh.put_replicated(y)

def merge(x, y):
    rows = _rows(x)
    meta = _meta(y)
    return rows - meta
"""
    assert "mixed-sharding-combine" in _ids(src)


def test_mixed_sharding_exempt_inside_traced_body():
    """Inside a jit/shard_map-traced body the row+rep mix is the
    sanctioned shape (per-shard compute + replicated metadata)."""
    src = """
import jax
from h2o_tpu.parallel import mesh

@jax.jit
def fused(x, y):
    rows = mesh.put_row_sharded(x)
    meta = mesh.put_replicated(y)
    return rows * meta
"""
    assert "mixed-sharding-combine" not in _ids(src)


def test_mixed_sharding_replacement_clears_the_tag():
    """mesh.put_* re-placement is the sanctioned fix: the re-placed
    binding carries the NEW tag."""
    src = """
from h2o_tpu.parallel import mesh

def merge(x, y):
    rows = mesh.put_row_sharded(x)
    meta = mesh.put_replicated(y)
    meta = mesh.put_row_sharded(meta)
    return rows * meta
"""
    assert "mixed-sharding-combine" not in _ids(src)


# ---------------------------------------------------------------------------
# rule 22 semantics — static churn, non-hashable, comprehension args
# ---------------------------------------------------------------------------
def test_per_iteration_value_in_static_position_flagged():
    src = """
import jax

def train(step, x, widths):
    fn = jax.jit(step, static_argnums=(1,))
    for w in widths:
        fn(x, w)
"""
    assert "recompile-hazard" in _ids(src)


def test_loop_invariant_static_argument_is_quiet():
    src = """
import jax

def train(step, x, width, xs):
    fn = jax.jit(step, static_argnums=(1,))
    for _ in xs:
        fn(x, width)
"""
    assert "recompile-hazard" not in _ids(src)


def test_nonhashable_literal_in_static_position_flagged():
    src = """
import jax

def train(step, x):
    fn = jax.jit(step, static_argnums=(1,))
    return fn(x, [1, 2])
"""
    assert "recompile-hazard" in _ids(src)


def test_per_iteration_comprehension_argument_flagged():
    src = """
import jax

def train(step, parts):
    fn = jax.jit(step)
    for p in parts:
        fn([q for q in p])
"""
    assert "recompile-hazard" in _ids(src)


def test_aot_lower_in_loop_flagged_and_hoisted_clean():
    bad = """
import jax

def warm(fn, specs):
    for s in specs:
        exe = fn.lower(s).compile()
        exe(s)
"""
    good = """
import jax

def warm(fn, spec, xs):
    exe = fn.lower(spec).compile()
    for x in xs:
        exe(x)
"""
    assert "recompile-hazard" in _ids(bad)
    assert "recompile-hazard" not in _ids(good)


# ---------------------------------------------------------------------------
# rule 23 semantics — the interprocedural donation shapes
# ---------------------------------------------------------------------------
def test_donation_rides_star_dispatch_through_packer():
    """The GBM chunk-loop shape end to end: a cross-function packer
    returns (x, m), the donating step is dispatched `step(*args)`, and a
    later read of m is flagged."""
    src = """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def _step_args(x, m):
    return (x, m)

def chunk_loop(fn, x, m):
    step = make_step(fn)
    args = _step_args(x, m)
    out = step(*args)
    return m
"""
    hits = _rules_hit(src)
    assert ("donate-across-calls", 15) in hits   # the `return m` read


def test_donation_rides_local_tuple_pack():
    src = """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def chunk_loop(fn, x, m):
    step = make_step(fn)
    args = (x, m)
    out = step(*args)
    return m
"""
    assert "donate-across-calls" in _ids(src)


def test_param_forwarding_helper_summarizes_as_donating():
    """A helper that forwards its parameter into a donated position is
    itself donating — the caller's read-after-call is flagged."""
    src = """
import jax

def _f(a, b):
    return a + b

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def helper(x, m):
    step = make_step(_f)
    return step(x, m)

def outer(x, m):
    helper(x, m)
    return m
"""
    hits = _rules_hit(src)
    assert ("donate-across-calls", 17) in hits


def test_donating_binding_visible_to_lexical_closure():
    """The gbm `_dispatch` shape: a nested closure dispatches the
    enclosing scope's donating callable."""
    src = """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def outer(fn, x, m):
    step = make_step(fn)

    def run(m2):
        out = step(x, m2)
        return m2

    return run(m)
"""
    assert "donate-across-calls" in _ids(src)


def test_loop_carried_rebind_is_the_sanctioned_idiom():
    """`m = step(x, m)` inside a loop — the rebind kills the donated
    state each iteration (RHS evaluates before the target binds)."""
    src = """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(1,))
    return step

def loop(fn, x, m, xs):
    step = make_step(fn)
    for _ in xs:
        m = step(x, m)
    return m
"""
    assert "donate-across-calls" not in _ids(src)


# ---------------------------------------------------------------------------
# provenance model pins (pass-1 extraction feeding pass 3)
# ---------------------------------------------------------------------------
def _model(sources: dict) -> ProjectModel:
    return ProjectModel({p: extract_summary(p, s)
                         for p, s in sources.items()})


def test_ret_tag_resolves_across_modules():
    sources = {
        "h2o_tpu/a.py": """
from h2o_tpu.parallel import mesh

def rows(x):
    return mesh.put_row_sharded(x)
""",
        "h2o_tpu/b.py": """
from h2o_tpu.a import rows

def use(x):
    r = rows(x)
    return r
""",
    }
    m = _model(sources)
    info = ProvInfo.of(m)
    assert info.ret_tag("h2o_tpu/a.py::rows") == "row"


def test_donating_factory_summary_across_modules():
    sources = {
        "h2o_tpu/eng.py": """
import jax

def make_step(fn):
    step = jax.jit(fn, donate_argnums=(3,))
    return step
""",
    }
    info = ProvInfo.of(_model(sources))
    assert info.returns_donating("h2o_tpu/eng.py::make_step") \
        == frozenset([3])


def test_ambiguous_return_tag_is_unknown():
    """Two branches returning different placements — ambiguity must give
    None (no finding), never a guess."""
    src = """
from h2o_tpu.parallel import mesh

def either(x, flag):
    if flag:
        return mesh.put_row_sharded(x)
    return mesh.put_replicated(x)
"""
    info = ProvInfo.of(_model({"h2o_tpu/a.py": src}))
    assert info.ret_tag("h2o_tpu/a.py::either") is None


def test_dataflow_scope_excludes_tests():
    assert _ids(DONATE_VIOLATING, relpath="tests/test_x.py") == set()


def test_bare_name_resolution_never_crosses_class_scope():
    """Python does not resolve bare names through the enclosing class
    body: `helper(x)` inside C.method must reach the MODULE `helper`,
    never C.helper — a class-scope edge would fabricate call-graph facts
    (hot closures, donation summaries) downstream."""
    src = """
def helper(x):
    return x

class C:
    def helper(self):
        return 1

    def method(self):
        return helper(2)
"""
    m = _model({"h2o_tpu/a.py": src})
    assert m.resolve_call("h2o_tpu/a.py::C.method", "name", "helper",
                          None) == "h2o_tpu/a.py::helper"


def test_multiline_bind_keeps_its_provenance_tag():
    """A wrapped `v = mesh.put_*(\\n x)` must carry its tag exactly like
    the single-line spelling — the rebind-unbind anchors at the
    statement's first line, before the bind, not after it."""
    src = """
import jax.numpy as jnp

def _dispatch(fn, arrays):
    out = jnp.sum(
        arrays)
    return float(out)
"""
    assert "host-transfer-in-hot-path" in _ids(src, HOT_PATH)


def test_static_argnums_survive_static_argnames():
    """Both static spellings on one jit: the argnames keyword must not
    erase the argnums positions."""
    src = """
import jax

def train(step, x, widths):
    fn = jax.jit(step, static_argnums=(1,), static_argnames=('w',))
    for w in widths:
        fn(x, w)
"""
    assert "recompile-hazard" in _ids(src)


# ---------------------------------------------------------------------------
# column spans in machine output
# ---------------------------------------------------------------------------
def test_dataflow_findings_carry_column_spans():
    v = [x for x in _violations(HOST_VIOLATING, HOT_PATH)
         if x.rule == "host-transfer-in-hot-path"]
    assert v and v[0].col_end > v[0].col >= 0


def test_sarif_region_carries_end_column():
    v = Violation(rule="host-transfer-in-hot-path", path="h2o_tpu/x.py",
                  line=7, col=11, message="m", snippet="float(out)",
                  line_end=7, col_end=21)
    region = json.loads(render_sarif([v]))["runs"][0]["results"][0][
        "locations"][0]["physicalLocation"]["region"]
    assert region["startColumn"] == 12
    assert region["endLine"] == 7
    assert region["endColumn"] == 22      # 1-based exclusive


def test_sarif_region_omits_end_when_unknown():
    v = Violation(rule="host-transfer-in-hot-path", path="h2o_tpu/x.py",
                  line=7, col=0, message="m", snippet="s")
    region = json.loads(render_sarif([v]))["runs"][0]["results"][0][
        "locations"][0]["physicalLocation"]["region"]
    assert "endColumn" not in region


def test_github_annotation_carries_end_column():
    v = Violation(rule="host-transfer-in-hot-path", path="h2o_tpu/x.py",
                  line=7, col=11, message="m", snippet="float(out)",
                  line_end=7, col_end=21)
    out = render_github([v])
    assert "endLine=7" in out and "endColumn=22" in out


# ---------------------------------------------------------------------------
# incremental cache — provenance events round-trip
# ---------------------------------------------------------------------------
def test_provenance_findings_survive_the_summary_cache(tmp_path):
    """A warm scan replays pass-1 summaries from cache; the pass-3
    findings must be byte-identical to the cold scan's (the provenance
    event stream round-trips through the cache)."""
    (tmp_path / "mod.py").write_text(DONATE_VIOLATING)
    cache = str(tmp_path / ".cache")
    cold = lint_paths(["mod.py"], root=str(tmp_path), cache_dir=cache)
    stats = {}
    warm = lint_paths(["mod.py"], root=str(tmp_path), cache_dir=cache,
                      stats=stats)
    assert stats["hits"] == 1 and stats["misses"] == 0
    assert [v.key() for v in cold] == [v.key() for v in warm]
    assert any(v.rule == "donate-across-calls" for v in warm)


# ---------------------------------------------------------------------------
# catalog
# ---------------------------------------------------------------------------
def test_rule_catalog_counts_all_three_passes():
    ids = ([cls.id for cls in ALL_RULES]
           + [cls.id for cls in PROJECT_RULES]
           + [cls.id for cls in DATAFLOW_RULES])
    assert len(ids) == len(set(ids)) == 23
    assert {"host-transfer-in-hot-path", "mixed-sharding-combine",
            "recompile-hazard", "donate-across-calls"} <= set(ids)


def test_dataflow_rules_in_cli_catalog(capsys):
    from tools.graftlint import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("host-transfer-in-hot-path", "mixed-sharding-combine",
                "recompile-hazard", "donate-across-calls"):
        assert rid in out
