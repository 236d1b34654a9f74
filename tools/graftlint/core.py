"""graftlint core — the rule framework, file runner, cache, baseline.

A repo-native static analyzer: 15 per-file AST rules plus 8 interprocedural
rules — 4 concurrency (pass 2) and 4 array-provenance dataflow (pass 3) —
encoding hazard classes this codebase has actually hit (see
`tools/graftlint/rules.py`, `tools/graftlint/concurrency.py`, and
`tools/graftlint/dataflow.py` for the catalogs and ISSUE/README for the
history). Deliberately
dependency-free — stdlib ``ast`` only, no jax import, so the lint gate
costs ~a second cold and much less warm, and runs identically on a dev
laptop and in the tier-1 pytest tier.

Mechanics:

- every per-file rule is a `Rule` subclass with a stable kebab-case
  ``id``; a run parses each file once and hands the tree + a per-file
  `FileContext` (import-alias map, traced-scope set, suppression table)
  to every rule;
- the interprocedural rules run over per-file summaries (`project.py`
  pass 1 → `concurrency.py` pass 2 → `dataflow.py` pass 3);
- **incremental cache**: per-file results (violations + project summary)
  persist under ``.graftlint_cache/`` keyed on (content hash, rule-set
  version, selected rules). The rule-set version hashes every
  tools/graftlint source — including `dataflow.py` and the provenance
  event shapes in `project.py`, so a stale cache can never hide a
  new-rule finding — AND the three registry files (knobs / failpoints /
  telemetry) the registry rules read, so editing a registry invalidates
  every cached file. The pass-2/3 project analyses re-run every time
  from the (cached) summaries — they are repo-global by nature and cost
  ~0.1 s;
- ``--jobs N`` scans cache misses in parallel;
- inline suppressions: ``# graftlint: disable=<rule>[,<rule>...]`` (or
  bare ``disable`` for all rules) on any physical line of the flagged
  statement (interprocedural findings: on the flagged line);
- the checked-in ``tools/graftlint/baseline.json`` grandfathers
  pre-existing violations: entries match on (rule, path, stripped source
  line), so line drift from unrelated edits does not resurrect them;
- ``--baseline-update`` regenerates the file deterministically (sorted,
  path-relative, reasons preserved) so baseline diffs stay reviewable;
- ``--format sarif|github`` emit machine-readable findings (SARIF 2.1.0 /
  GitHub workflow commands) for CI annotation; `tools/ci_gate.sh` runs
  the lint and the tier-1 pytest line as one exit-coded gate.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
import re
import sys

#: repo root = two levels above this file (tools/graftlint/core.py)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")
#: default scan set — the CLI and the pytest gate lint the same tree
DEFAULT_PATHS = ("h2o_tpu", "tests", "chip_smoke.py")

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?:=([A-Za-z0-9_\-, ]+))?")


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str          # repo-relative, forward slashes
    line: int          # 1-based line of the flagged node
    col: int
    message: str
    snippet: str       # stripped source of the flagged line (baseline key)
    severity: str = "error"
    line_end: int = 0  # last physical line of the flagged node (0 = line)
    col_end: int = 0   # 0-based end column (ast end_col_offset; 0 = unknown)
                       # — SARIF regions carry it so GitHub annotations
                       # underline the expression, not just its first char

    def span(self) -> range:
        return range(self.line, max(self.line_end, self.line) + 1)

    def key(self) -> tuple:
        return (self.rule, self.path, self.snippet)

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col + 1}: "
                f"{self.rule}: {self.message}")


class Rule:
    """One lint rule. Subclasses set ``id``/``doc`` and implement
    ``check(tree, ctx) -> list[Violation]``."""

    id: str = ""
    severity: str = "error"
    doc: str = ""

    def check(self, tree: ast.Module, ctx: "FileContext") -> list[Violation]:
        raise NotImplementedError

    def violation(self, ctx: "FileContext", node: ast.AST,
                  message: str) -> Violation:
        line = getattr(node, "lineno", 1)
        return Violation(rule=self.id, path=ctx.relpath, line=line,
                         col=getattr(node, "col_offset", 0), message=message,
                         snippet=ctx.line_text(line), severity=self.severity,
                         line_end=getattr(node, "end_lineno", line) or line,
                         col_end=getattr(node, "end_col_offset", 0) or 0)


# ---------------------------------------------------------------------------
# Shared AST analyses (computed once per file, consumed by several rules).
# ---------------------------------------------------------------------------
def dotted_name(node: ast.AST) -> str | None:
    """'jax.experimental.shard_map.shard_map' for an Attribute/Name chain;
    None for anything rooted elsewhere (calls, subscripts, ...)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def collect_aliases(tree: ast.Module) -> dict[str, str]:
    """Import-alias map: local name -> canonical dotted module. Covers the
    repo conventions (``import jax.numpy as jnp``, ``from jax import lax``,
    ``from jax.sharding import PartitionSpec as P``...)."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def normalize(dotted: str | None, aliases: dict[str, str]) -> str | None:
    """Rewrite the first segment through the alias map, then collapse the
    well-known jax module spellings to canonical roots (jax.numpy -> jnp,
    jax.lax -> lax, numpy -> np) so rules match one spelling."""
    if dotted is None:
        return None
    head, _, rest = dotted.partition(".")
    full = aliases.get(head, head)
    out = f"{full}.{rest}" if rest else full
    for prefix, canon in (("jax.numpy", "jnp"), ("jax.lax", "lax"),
                          ("numpy", "np")):
        if out == prefix or out.startswith(prefix + "."):
            out = canon + out[len(prefix):]
    return out


#: call entry points whose function arguments are traced by jax
_TRACING_ENTRY_SUFFIXES = ("shard_map",)
_TRACING_ENTRY_NAMES = {
    "jax.jit", "jit", "lax.scan", "lax.fori_loop", "lax.while_loop",
    "lax.cond", "lax.switch", "lax.map", "lax.associative_scan",
    "jax.vmap", "vmap", "jax.pmap", "jax.checkpoint", "jax.remat",
    "jax.grad", "jax.value_and_grad",
}


def _is_tracing_entry(norm: str | None) -> bool:
    if norm is None:
        return False
    return (norm in _TRACING_ENTRY_NAMES
            or norm.endswith(_TRACING_ENTRY_SUFFIXES))


def traced_scopes(tree: ast.Module,
                  aliases: dict[str, str]) -> set[ast.AST]:
    """Function/lambda nodes whose bodies run under a jax trace: decorated
    with jit (bare, called, or partial(jax.jit, ...)), passed by name or
    inline to a tracing entry point (jit/scan/fori_loop/shard_map/vmap/...),
    or lexically nested inside such a function."""
    traced: set[ast.AST] = set()
    defs_by_name: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)

    def decorator_traces(dec: ast.AST) -> bool:
        if _is_tracing_entry(normalize(dotted_name(dec), aliases)):
            return True
        if isinstance(dec, ast.Call):
            fn = normalize(dotted_name(dec.func), aliases)
            if _is_tracing_entry(fn):
                return True
            if fn in ("functools.partial", "partial") and dec.args:
                return _is_tracing_entry(
                    normalize(dotted_name(dec.args[0]), aliases))
        return False

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(decorator_traces(d) for d in node.decorator_list):
                traced.add(node)
        elif isinstance(node, ast.Call):
            if not _is_tracing_entry(
                    normalize(dotted_name(node.func), aliases)):
                continue
            cands = list(node.args) + [kw.value for kw in node.keywords
                                       if kw.arg in (None, "f", "fun", "body",
                                                     "body_fun", "cond_fun")]
            for arg in cands:
                if isinstance(arg, ast.Lambda):
                    traced.add(arg)
                elif isinstance(arg, ast.Name) and arg.id in defs_by_name:
                    traced.add(defs_by_name[arg.id][-1])

    # propagate: nested defs/lambdas inside a traced function are traced
    grew = True
    while grew:
        grew = False
        for fn in list(traced):
            for sub in ast.walk(fn):
                if (sub is not fn
                        and isinstance(sub, (ast.FunctionDef,
                                             ast.AsyncFunctionDef,
                                             ast.Lambda))
                        and sub not in traced):
                    traced.add(sub)
                    grew = True
    return traced


def function_scopes(tree: ast.Module) -> list[ast.AST]:
    """All function-like scopes plus the module itself."""
    out: list[ast.AST] = [tree]
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            out.append(node)
    return out


def scope_statements(scope: ast.AST):
    """Walk a scope WITHOUT descending into nested function scopes (each
    nested scope is analyzed on its own)."""
    body = scope.body if not isinstance(scope, ast.Lambda) else [scope.body]
    stack = list(body) if isinstance(body, list) else [body]
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue  # nested scope — analyzed on its own
        stack.extend(ast.iter_child_nodes(node))


def suppression_table(source: str) -> dict:
    """1-based line -> set of rule ids suppressed there (None = all) —
    shared by per-file FileContexts and the pass-2 project runner."""
    table: dict[int, set | None] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        if m.group(1) is None:
            table[i] = None
        else:
            table[i] = {r.strip() for r in m.group(1).split(",")
                        if r.strip()}
    return table


class FileContext:
    def __init__(self, relpath: str, source: str, tree: ast.Module):
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self.aliases = collect_aliases(tree)
        self.traced = traced_scopes(tree, self.aliases)
        # suppression table: 1-based line -> set of rule ids (None = all)
        self.suppressions = suppression_table(source)

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def is_suppressed(self, rule_id: str, line: int) -> bool:
        if line not in self.suppressions:
            return False
        ids = self.suppressions[line]
        return ids is None or rule_id in ids


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------
def _all_rules() -> list[Rule]:
    from . import rules as rules_mod

    return [cls() for cls in rules_mod.ALL_RULES]


def lint_source(source: str, relpath: str = "<memory>.py",
                rules: list[Rule] | None = None) -> list[Violation]:
    """Lint one source string (fixture/test entry point). Suppressions
    apply; baseline does not."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation(rule="syntax-error", path=relpath,
                          line=e.lineno or 1, col=(e.offset or 1) - 1,
                          message=str(e.msg), snippet="")]
    ctx = FileContext(relpath, source, tree)
    out: list[Violation] = []
    for rule in (rules if rules is not None else _all_rules()):
        for v in rule.check(tree, ctx):
            # a disable comment counts on ANY physical line of the flagged
            # statement (the natural place is often a continuation line)
            if not any(ctx.is_suppressed(v.rule, ln) for ln in v.span()):
                out.append(v)
    return sorted(out, key=lambda v: (v.path, v.line, v.col, v.rule))


def iter_py_files(paths, root: str = REPO_ROOT):
    """Yield absolute paths of .py files under ``paths`` (files or dirs,
    relative to ``root``), skipping __pycache__ and hidden dirs."""
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap):
            if ap.endswith(".py"):
                yield ap
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__" and not d.startswith(".")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


# ---------------------------------------------------------------------------
# incremental cache
# ---------------------------------------------------------------------------
CACHE_DIR = os.path.join(REPO_ROOT, ".graftlint_cache")

#: registry files whose content changes the RESULTS of per-file rules
#: (unregistered-knob/-failpoint/-metric read them) — they invalidate the
#: whole cache exactly like editing a rule does
_REGISTRY_FILES = ("h2o_tpu/utils/knobs.py", "h2o_tpu/utils/failpoints.py",
                   "h2o_tpu/utils/telemetry.py")

_RULESET_VERSIONS: dict[str, str] = {}


def ruleset_version(root: str = REPO_ROOT) -> str:
    """Hash of every tools/graftlint source plus the three registry files
    — the cache key component that invalidates on any rule change. Memo
    is keyed per ``root``: the registry files live under it, so a run
    against a fixture tree must not decide the version for the repo."""
    if root in _RULESET_VERSIONS:
        return _RULESET_VERSIONS[root]
    h = hashlib.sha1()
    tooldir = os.path.dirname(os.path.abspath(__file__))
    for fn in sorted(os.listdir(tooldir)):
        if fn.endswith(".py"):
            h.update(fn.encode())
            with open(os.path.join(tooldir, fn), "rb") as f:
                h.update(f.read())
    for rel in _REGISTRY_FILES:
        ap = os.path.join(root, rel)
        h.update(rel.encode())
        if os.path.exists(ap):
            with open(ap, "rb") as f:
                h.update(f.read())
    _RULESET_VERSIONS[root] = h.hexdigest()
    return _RULESET_VERSIONS[root]


def _cache_path(rel: str, cache_dir: str) -> str:
    return os.path.join(cache_dir, rel.replace("/", "__") + ".json")


def _cache_load(rel: str, content_key: str, rules_sig: str,
                cache_dir: str, version: str):
    """(violations, summary) on a hit, None on any miss/mismatch."""
    try:
        with open(_cache_path(rel, cache_dir), encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if (data.get("content") != content_key
            or data.get("version") != version
            or data.get("rules") != rules_sig):
        return None
    vs = [Violation(**v) for v in data.get("violations", [])]
    return vs, data.get("summary")


def _cache_store(rel: str, content_key: str, rules_sig: str,
                 cache_dir: str, version: str, violations, summary) -> None:
    payload = {"content": content_key, "version": version,
               "rules": rules_sig,
               "violations": [dataclasses.asdict(v) for v in violations],
               "summary": summary}
    path = _cache_path(rel, cache_dir)
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f)
        os.replace(tmp, path)  # atomic: parallel runs never read a torn file
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass


#: per-process state for the --jobs worker pool (set by _worker_init)
_WORKER_STATE: dict = {}


def _worker_init(rule_ids, cache, cache_dir, version, rules_sig) -> None:
    from . import rules as rules_mod

    _WORKER_STATE["rules"] = [cls() for cls in rules_mod.ALL_RULES
                              if cls.id in set(rule_ids)]
    _WORKER_STATE.update(cache=cache, cache_dir=cache_dir,
                         version=version, rules_sig=rules_sig)


def _worker_scan(item):
    """One file's per-file scan inside a --jobs worker process."""
    rel, source, key = item
    from .concurrency import in_scope
    from .project import extract_summary

    st = _WORKER_STATE
    vs = lint_source(source, relpath=rel, rules=st["rules"])
    summary = extract_summary(rel, source) if in_scope(rel) else None
    if st["cache"]:
        _cache_store(rel, key, st["rules_sig"], st["cache_dir"],
                     st["version"], vs, summary)
    return rel, vs, summary


def lint_paths(paths=DEFAULT_PATHS, root: str = REPO_ROOT,
               rules: list[Rule] | None = None, *,
               project_rules=None, jobs: int | None = None,
               cache: bool = True, cache_dir: str | None = None,
               stats: dict | None = None) -> list[Violation]:
    """Two-pass repo lint. Per-file rules run (or replay from cache) per
    file — in parallel when ``jobs`` > 1; the interprocedural pass runs
    over the per-file summaries every time (repo-global by nature).

    ``project_rules``: None = all interprocedural rules (pass-2
    concurrency + pass-3 dataflow); [] = skip both passes.
    ``stats`` (optional dict) is filled with files/hits/misses counts.
    """
    from .concurrency import (check_project, default_project_rules,
                              in_scope)

    rules = rules if rules is not None else _all_rules()
    if project_rules is None:
        project_rules = list(default_project_rules())
    cache_dir = cache_dir or CACHE_DIR
    version = ruleset_version(root)
    rules_sig = ",".join(sorted(r.id for r in rules))

    files: list[tuple[str, str]] = []   # (relpath, source)
    out: list[Violation] = []
    for ap in iter_py_files(paths, root):
        rel = os.path.relpath(ap, root).replace(os.sep, "/")
        try:
            with open(ap, encoding="utf-8") as f:
                files.append((rel, f.read()))
        except OSError as e:
            out.append(Violation(rule="io-error", path=rel, line=1, col=0,
                                 message=str(e), snippet=""))

    summaries: dict[str, dict | None] = {}
    sources = dict(files)
    hits = 0
    misses: list[tuple[str, str, str]] = []  # (rel, source, content_key)
    for rel, source in files:
        key = hashlib.sha1(source.encode("utf-8")).hexdigest()
        got = (_cache_load(rel, key, rules_sig, cache_dir, version)
               if cache else None)
        if got is not None:
            vs, summary = got
            out.extend(vs)
            summaries[rel] = summary
            hits += 1
        else:
            misses.append((rel, source, key))

    def _scan(item):
        rel, source, key = item
        from .project import extract_summary

        vs = lint_source(source, relpath=rel, rules=rules)
        summary = extract_summary(rel, source) if in_scope(rel) else None
        if cache:
            _cache_store(rel, key, rules_sig, cache_dir, version, vs,
                         summary)
        return rel, vs, summary

    if misses:
        results = None
        # the scan is GIL-bound pure-python AST work, so real parallelism
        # needs PROCESSES (a thread pool measures SLOWER than serial);
        # spawn context keeps the children free of the parent's jax/XLA
        # state. Only stock rules survive reconstruction in a child —
        # custom rule instances fall back to the serial path.
        from . import rules as rules_mod

        known = {cls.id for cls in rules_mod.ALL_RULES}
        if jobs and jobs > 1 and len(misses) > 1 \
                and all(r.id in known for r in rules):
            try:
                import multiprocessing
                from concurrent.futures import ProcessPoolExecutor

                ctx = multiprocessing.get_context("spawn")
                init_args = (sorted(r.id for r in rules), cache, cache_dir,
                             version, rules_sig)
                with ProcessPoolExecutor(max_workers=jobs, mp_context=ctx,
                                         initializer=_worker_init,
                                         initargs=init_args) as ex:
                    results = list(ex.map(_worker_scan, misses,
                                          chunksize=max(
                                              len(misses) // (jobs * 4),
                                              1)))
            except (OSError, ValueError, ImportError):
                results = None   # sandboxed env without fork/sem: serial
        if results is None:
            results = [_scan(m) for m in misses]
        for rel, vs, summary in results:
            out.extend(vs)
            summaries[rel] = summary

    if project_rules:
        out.extend(check_project(summaries, sources, rules=project_rules))

    if stats is not None:
        stats.update(files=len(files), hits=hits, misses=len(misses))
    return sorted(out, key=lambda v: (v.path, v.line, v.col, v.rule))


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------
def load_baseline(path: str = BASELINE_PATH) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return data.get("entries", [])


def baseline_keys(entries: list[dict]) -> set[tuple]:
    return {(e["rule"], e["path"], e["snippet"]) for e in entries}


def apply_baseline(violations: list[Violation],
                   entries: list[dict]) -> list[Violation]:
    keys = baseline_keys(entries)
    return [v for v in violations if v.key() not in keys]


def write_baseline(violations: list[Violation], path: str = BASELINE_PATH,
                   old_entries: list[dict] | None = None) -> None:
    """Deterministic regeneration: sorted by (path, line, rule), repo-
    relative paths, one entry per distinct (rule, path, snippet), reasons
    carried over from the previous baseline when the key survives."""
    reasons = {(e["rule"], e["path"], e["snippet"]): e.get("reason", "")
               for e in (old_entries if old_entries is not None
                         else load_baseline(path))}
    seen: set[tuple] = set()
    entries = []
    for v in sorted(violations, key=lambda v: (v.path, v.line, v.rule)):
        if v.key() in seen:
            continue
        seen.add(v.key())
        entries.append({"rule": v.rule, "path": v.path, "line": v.line,
                        "snippet": v.snippet,
                        "reason": reasons.get(v.key(), "baselined")})
    payload = {"version": 1,
               "comment": ("pre-existing violations grandfathered out of the "
                           "gate; match on (rule, path, snippet) so line "
                           "drift does not resurrect them. Regenerate with "
                           "python -m tools.graftlint --baseline-update"),
               "entries": entries}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# machine-readable output (--format sarif|github)
# ---------------------------------------------------------------------------
def _rule_catalog() -> list:
    from . import rules as rules_mod
    from .concurrency import default_project_rules

    return [cls() for cls in
            tuple(rules_mod.ALL_RULES) + default_project_rules()]


def render_sarif(violations: list[Violation]) -> str:
    """SARIF 2.1.0 — one run, one result per violation, rules carried in
    the tool component so CI annotators can show the doc line."""
    docs = {r.id: r.doc for r in _rule_catalog()}
    rule_ids = sorted({v.rule for v in violations})
    sarif = {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftlint",
                "informationUri": "tools/graftlint/",
                "rules": [{"id": rid,
                           "shortDescription": {"text": docs.get(rid, rid)}}
                          for rid in rule_ids],
            }},
            "results": [{
                "ruleId": v.rule,
                "level": "error" if v.severity == "error" else "warning",
                "message": {"text": v.message},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {"uri": v.path},
                    # endLine/endColumn make GitHub underline the flagged
                    # expression instead of a zero-width caret at its
                    # start (endColumn is 1-based exclusive, so the
                    # 0-based-exclusive ast end_col_offset maps via +1)
                    "region": {**{"startLine": v.line,
                                  "startColumn": v.col + 1,
                                  "snippet": {"text": v.snippet}},
                               **({"endLine": max(v.line_end, v.line),
                                   "endColumn": v.col_end + 1}
                                  if v.col_end > 0 else {})},
                }}],
            } for v in violations],
        }],
    }
    return json.dumps(sarif, indent=1, sort_keys=True)


def render_github(violations: list[Violation]) -> str:
    """GitHub Actions workflow commands — one ::error per violation, so a
    CI run annotates the diff inline with no extra tooling."""
    lines = []
    for v in violations:
        msg = v.message.replace("%", "%25").replace("\n", "%0A")
        span = (f",endLine={max(v.line_end, v.line)},"
                f"endColumn={v.col_end + 1}" if v.col_end > 0 else "")
        lines.append(f"::error file={v.path},line={v.line},"
                     f"col={v.col + 1}{span},title=graftlint "
                     f"{v.rule}::{msg}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def main(argv: list[str] | None = None) -> int:
    import argparse

    from . import rules as rules_mod
    from .concurrency import default_project_rules

    ap = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="repo-native static analysis for the JAX and "
                    "concurrency hazard classes this codebase keeps "
                    "re-fixing")
    ap.add_argument("paths", nargs="*", default=list(DEFAULT_PATHS),
                    help="files/dirs to lint (default: %(default)s)")
    ap.add_argument("--fix", action="store_true",
                    help="auto-rewrite the mechanical rules (shard_map "
                         "imports -> parallel.mesh, registered knob env "
                         "reads -> knobs.raw)")
    ap.add_argument("--baseline-update", action="store_true",
                    help="regenerate baseline.json from the current tree "
                         "(deterministic: sorted, path-relative)")
    ap.add_argument("--baseline", default=BASELINE_PATH,
                    help="baseline file (default: %(default)s)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report baselined violations too")
    ap.add_argument("--select",
                    help="comma list of rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="parallel workers for the per-file scan")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore and don't write .graftlint_cache/")
    ap.add_argument("--format", choices=("text", "sarif", "github"),
                    default="text",
                    help="finding output format (default: %(default)s)")
    args = ap.parse_args(argv)

    rules = [cls() for cls in rules_mod.ALL_RULES]
    proj_rules = [cls() for cls in default_project_rules()]
    if args.list_rules:
        for r in rules + proj_rules:
            print(f"{r.id:24} [{r.severity}] {r.doc}")
        return 0
    if args.select:
        wanted = {s.strip() for s in args.select.split(",")}
        known = {r.id for r in rules} | {r.id for r in proj_rules}
        unknown = wanted - known
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))}",
                  file=sys.stderr)
            return 2
        rules = [r for r in rules if r.id in wanted]
        proj_rules = [r for r in proj_rules if r.id in wanted]

    if args.fix:
        from . import fixes

        changed = fixes.fix_paths(args.paths, root=REPO_ROOT)
        for path in changed:
            print(f"fixed: {path}")

    if args.baseline_update and (args.select
                                 or args.paths != list(DEFAULT_PATHS)):
        # a narrowed run sees only a slice of the violations; writing the
        # baseline from it would silently drop every other entry (and its
        # hand-written reason)
        print("--baseline-update requires a full default-scope run "
              "(no --select, no explicit paths)", file=sys.stderr)
        return 2

    violations = lint_paths(args.paths, rules=rules,
                            project_rules=proj_rules, jobs=args.jobs,
                            cache=not args.no_cache)
    if args.baseline_update:
        write_baseline(violations, path=args.baseline)
        print(f"baseline: {len(violations)} entr"
              f"{'y' if len(violations) == 1 else 'ies'} -> {args.baseline}")
        return 0
    if not args.no_baseline:
        violations = apply_baseline(violations, load_baseline(args.baseline))
    if args.format == "sarif":
        print(render_sarif(violations))
    elif args.format == "github":
        if violations:
            print(render_github(violations))
    else:
        for v in violations:
            print(v.render())
        n = len(violations)
        print(f"graftlint: {n} violation{'s' if n != 1 else ''} "
              f"({'FAIL' if n else 'ok'})")
    return 1 if violations else 0
