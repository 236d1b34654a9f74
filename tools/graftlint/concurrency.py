"""graftlint pass 2 — interprocedural concurrency rules (14-17).

The platform became a genuinely concurrent system (serving batcher/shadow
workers, REST handler threads, background jobs, the Cleaner reservation
ledger) and the per-file rules 1-13 cannot see the bug class that hurts
next: a field raced between a request thread and a worker, a lock-order
inversion between two subsystems, a device sync on the batch path while a
lock is held. These rules run on the repo-wide :class:`ProjectModel`
(tools/graftlint/project.py — symbol table, call graph, thread-entry map)
instead of a single file's AST:

14. unguarded-shared-field — a ``self.*`` field written outside
    ``__init__`` and touched from ≥2 thread roots (spawned workers, REST
    handler threads, the public entry surface) must be accessed under ONE
    consistent inferred guard. Guarded-by inference reads ``with
    self._lock:`` scopes and propagates through one level of private
    helper methods (a ``*_locked`` helper only ever called under the lock
    inherits it).
15. lock-order-cycle — the static lock-acquisition graph (lock A held
    while B is acquired → edge A→B, propagated through the call graph)
    must be acyclic; any cycle is a deadlock candidate. The runtime twin
    (`h2o_tpu/utils/sanitizer.py`) raises on *observed* inversions; this
    rule flags *possible* ones.
16. blocking-under-lock — no ``time.sleep`` / ``block_until_ready`` /
    ``device_get`` / HTTP / thread-or-job join / ``Event.wait`` while
    holding a lock (waiting on the HELD condition is exempt — that
    releases it). One level of interprocedural lookthrough: calling a
    helper that blocks counts. This is the serving-p99 killer class.
17. unjoined-thread — a ``threading.Thread``/``Timer`` created with no
    join on any path (``self.X`` spawn with no ``self.X.join()`` anywhere
    in the class; a local spawn with no join in the function;
    fire-and-forget anonymous threads) leaks workers past shutdown.

All four are deliberately under-approximate where resolution is
ambiguous (no edge beats a wrong edge); everything they DO flag is either
fixed or baselined with a written reason — the gate ships at 0
non-baselined violations, the rules 1-13 discipline.

Scope: everything scanned except the test tree — on the default scan set
that is ``h2o_tpu/``, whose race-freedom the MapReduce determinism
story depends on, and the ``chip_smoke.py`` driver. Tests spawn
threads with their own lifecycles and stay per-file-linted only.
"""

from __future__ import annotations

import os

from .core import Violation, suppression_table
from .project import ProjectModel, extract_summary

#: call-graph BFS bound for closure queries (lock closures); the repo's
#: real chains are < 10 deep, this is a runaway guard, not a tuning knob
_CLOSURE_DEPTH = 12


def in_scope(relpath: str) -> bool:
    """Interprocedural scope: everything scanned EXCEPT the test tree —
    tests spawn threads with their own lifecycles and stay per-file-
    linted only. On the default scan set this means h2o_tpu/ +
    chip_smoke.py;
    an explicit out-of-tree path gets the full analysis too."""
    p = relpath.replace(os.sep, "/")
    return not (p.startswith("tests/") or "/tests/" in p)


class ProjectRule:
    """One interprocedural rule: ``check(model) -> [(path, line, msg)]``."""

    id: str = ""
    severity: str = "error"
    doc: str = ""

    def check(self, model: ProjectModel) -> list[tuple]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# rule 14 — unguarded-shared-field
# ---------------------------------------------------------------------------
class UnguardedSharedField(ProjectRule):
    id = "unguarded-shared-field"
    doc = ("self.* field written from >=2 thread roots without one "
           "consistent inferred guard (with self._lock scopes, incl. one "
           "level of private helper methods)")

    def _class_functions(self, model: ProjectModel, path: str,
                         cls: str) -> dict:
        return {k: fn for k, fn in model.functions.items()
                if fn["path"] == path and fn.get("cls") == cls}

    @staticmethod
    def _helper_guards(fns: dict) -> dict:
        """{fnkey: extra guard set} for private helpers whose every
        intra-class call site holds a common lock (one inference level)."""
        call_guards: dict[str, list] = {}
        by_name = {}
        for key, fn in fns.items():
            # only direct methods (not nested closures) are addressable
            # through self.m() — qual "Class.m" has exactly one dot
            if fn["qual"].count(".") == 1:
                by_name[fn["name"]] = key
        for key, fn in fns.items():
            for kind, name, _recv, guards, _line in fn.get("calls", []):
                if kind == "self" and name in by_name:
                    call_guards.setdefault(by_name[name],
                                           []).append(set(guards))
        out: dict[str, set] = {}
        for key, sites in call_guards.items():
            fn = fns[key]
            if fn.get("public"):
                continue  # externally callable — call sites don't cover it
            common = set.intersection(*sites) if sites else set()
            if common:
                out[key] = common
        return out

    def check(self, model: ProjectModel) -> list[tuple]:
        out: list[tuple] = []
        reachable = model.thread_reachable()
        for (path, cls), crec in sorted(model.classes.items()):
            if not in_scope(path):
                continue
            fns = self._class_functions(model, path, cls)
            if not fns:
                continue
            extra = self._helper_guards(fns)
            # field -> [(root label, mode, guards, line, fnkey)]
            fields: dict[str, list] = {}
            for key, fn in sorted(fns.items()):
                if "__init__" in fn["qual"]:
                    continue  # construction happens-before publication
                root = reachable.get(key, "entry")
                bonus = extra.get(key, set())
                for fld, guards, line in fn.get("writes", []):
                    fields.setdefault(fld, []).append(
                        (root, "w", set(guards) | bonus, line, key))
                for fld, guards, line in fn.get("reads", []):
                    fields.setdefault(fld, []).append(
                        (root, "r", set(guards) | bonus, line, key))
            for fld in sorted(fields):
                accesses = fields[fld]
                if fld.isupper():
                    continue  # module-constant convention
                roots = {a[0] for a in accesses}
                writes = [a for a in accesses if a[1] == "w"]
                if len(roots) < 2 or not writes:
                    continue
                common = set.intersection(*(a[2] for a in accesses))
                if common:
                    continue  # one consistent guard covers every access
                # inferred guard = the most used lock across accesses
                counts: dict[str, int] = {}
                for a in accesses:
                    for gkey in a[2]:
                        counts[gkey] = counts.get(gkey, 0) + 1
                if counts:
                    inferred = sorted(counts.items(),
                                      key=lambda kv: (-kv[1], kv[0]))[0][0]
                    offenders = [a for a in accesses
                                 if inferred not in a[2]]
                    detail = (f"this access misses the inferred guard "
                              f"'{inferred}' the other accesses hold")
                else:
                    offenders = writes
                    detail = "no access holds any lock"
                anchor = sorted(offenders,
                                key=lambda a: (a[1] != "w", a[3]))[0]
                other = sorted(roots)[:3]
                out.append((path, anchor[3],
                            f"field '{cls}.{fld}' is shared between "
                            f"thread roots ({'; '.join(other)}) and "
                            f"written outside __init__, but {detail} — "
                            f"guard every access with one lock (or "
                            f"baseline with a reason if the race is "
                            f"benign)"))
        return out


# ---------------------------------------------------------------------------
# rule 15 — lock-order-cycle
# ---------------------------------------------------------------------------
class LockOrderCycle(ProjectRule):
    id = "lock-order-cycle"
    doc = ("cycle in the static lock-acquisition graph (lock A held while "
           "acquiring B, across the call graph) — a deadlock candidate")

    def _closure_locks(self, model: ProjectModel, start: str,
                       memo: dict) -> set:
        """Lock ids acquired anywhere in ``start``'s call closure."""
        if start in memo:
            return memo[start]
        memo[start] = set()  # cycle guard
        acc: set = set()
        seen = {start}
        frontier = [start]
        depth = 0
        while frontier and depth < _CLOSURE_DEPTH:
            nxt = []
            for key in frontier:
                fn = model.functions.get(key)
                if fn is None:
                    continue
                for tok, _held, _line, _blocking in fn.get("acquires", []):
                    lid = model.lock_id(key, tok)
                    if lid is not None:
                        acc.add(lid)
                for kind, name, recv, _g, _line in fn.get("calls", []):
                    tgt = model.resolve_call(key, kind, name, recv)
                    if tgt is not None and tgt not in seen:
                        seen.add(tgt)
                        nxt.append(tgt)
            frontier = nxt
            depth += 1
        memo[start] = acc
        return acc

    def check(self, model: ProjectModel) -> list[tuple]:
        edges: dict[tuple, tuple] = {}  # (a, b) -> (path, line)

        def note(a: str, b: str, path: str, line: int) -> None:
            if a != b and (a, b) not in edges:
                edges[(a, b)] = (path, line)

        memo: dict = {}
        for key, fn in sorted(model.functions.items()):
            if not in_scope(fn["path"]):
                continue
            for tok, held, line, _blocking in fn.get("acquires", []):
                b = model.lock_id(key, tok)
                if b is None:
                    continue
                for h in held:
                    a = model.lock_id(key, h)
                    if a is not None:
                        note(a, b, fn["path"], line)
            for kind, name, recv, guards, line in fn.get("calls", []):
                if not guards:
                    continue
                tgt = model.resolve_call(key, kind, name, recv)
                if tgt is None:
                    continue
                for b in self._closure_locks(model, tgt, memo):
                    for h in guards:
                        a = model.lock_id(key, h)
                        if a is not None:
                            note(a, b, fn["path"], line)

        # cycle detection over the edge set (iterative DFS per SCC seed)
        graph: dict[str, list] = {}
        for (a, b) in edges:
            graph.setdefault(a, []).append(b)
            graph.setdefault(b, [])
        sccs = _sccs(graph)
        out: list[tuple] = []
        for comp in sccs:
            if len(comp) < 2:
                continue
            comp = sorted(comp)
            cyc_edges = sorted((a, b) for (a, b) in edges
                               if a in comp and b in comp)
            path, line = edges[cyc_edges[0]]
            sites = "; ".join(
                f"{a.split('::')[-1]}->{b.split('::')[-1]} at "
                f"{edges[(a, b)][0]}:{edges[(a, b)][1]}"
                for a, b in cyc_edges[:4])
            out.append((path, line,
                        f"lock-order cycle between "
                        f"{', '.join(c.split('::')[-1] for c in comp)} — "
                        f"a deadlock candidate ({sites}); pick one global "
                        f"order or drop a lock from one path"))
        return out


def _sccs(graph: dict) -> list:
    """Tarjan strongly-connected components, iterative (deterministic:
    nodes visited in sorted order)."""
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set = set()
    stack: list = []
    out: list = []
    counter = [0]

    for root in sorted(graph):
        if root in index:
            continue
        work = [(root, iter(sorted(graph.get(root, []))))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(sorted(graph.get(nxt, [])))))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                out.append(comp)
    return out


# ---------------------------------------------------------------------------
# rule 16 — blocking-under-lock
# ---------------------------------------------------------------------------
#: dotted spellings that block the calling thread
_BLOCKING_DOTTED = {
    "time.sleep", "jax.device_get", "jax.block_until_ready",
    "urllib.request.urlopen", "subprocess.run", "subprocess.check_output",
    "subprocess.check_call", "select.select",
}
#: attribute spellings that block regardless of receiver
_BLOCKING_ATTRS = {"block_until_ready", "device_get", "communicate",
                   "getresponse", "urlopen", "serve_forever", "result"}


class BlockingUnderLock(ProjectRule):
    id = "blocking-under-lock"
    doc = ("blocking call (sleep/block_until_ready/device_get/HTTP/"
           "thread-or-job join/Event.wait) while holding a lock — the "
           "serving p99 killer; waiting on the HELD condition is exempt")

    def _direct_blocking(self, fn: dict, thread_attrs: set) -> list:
        """[(line, what, guards)] of blocking calls in one function."""
        out = []
        for kind, name, recv, guards, line in fn.get("calls", []):
            what = None
            if kind == "dotted" and (name in _BLOCKING_DOTTED
                                     or name.endswith(".sleep")
                                     and name.startswith("time")):
                what = name
            elif kind in ("attr", "dotted"):
                last = name.rsplit(".", 1)[-1]
                if last in _BLOCKING_ATTRS:
                    what = last
                elif last == "wait" and recv is not None \
                        and recv not in guards:
                    # Event/Future .wait under a lock blocks WITH the lock;
                    # cv.wait on a held condition releases it — exempt
                    what = f"{recv}.wait"
                elif last == "join" and recv in thread_attrs:
                    what = f"{recv}.join"
            elif kind == "name" and name in ("sleep", "urlopen"):
                what = name
            if what is not None:
                out.append((line, what, guards))
        return out

    def check(self, model: ProjectModel) -> list[tuple]:
        # class -> attrs that store spawned threads (join targets)
        thread_attrs: dict[tuple, set] = {}
        for key, fn in model.functions.items():
            for _ref, store, _line, kind in fn.get("spawns", []):
                if kind == "thread" and store and store.startswith("self."):
                    thread_attrs.setdefault(
                        (fn["path"], fn.get("cls")), set()).add(store)
        out: list[tuple] = []
        direct: dict[str, list] = {}
        for key, fn in model.functions.items():
            tattrs = thread_attrs.get((fn["path"], fn.get("cls")), set())
            direct[key] = self._direct_blocking(fn, tattrs)
        for key, fn in sorted(model.functions.items()):
            if not in_scope(fn["path"]):
                continue
            for line, what, guards in direct[key]:
                if guards:
                    held = ", ".join(sorted(set(guards)))
                    out.append((fn["path"], line,
                                f"blocking call {what} while holding "
                                f"{held} — every other thread contending "
                                f"on that lock stalls behind it; move the "
                                f"wait outside the lock"))
            # one level of lookthrough: a call under a lock to a helper
            # that blocks directly
            for kind, name, recv, guards, line in fn.get("calls", []):
                if not guards:
                    continue
                tgt = model.resolve_call(key, kind, name, recv)
                if tgt is None or not direct.get(tgt):
                    continue
                whats = sorted({w for _l, w, _g in direct[tgt]})
                held = ", ".join(sorted(set(guards)))
                out.append((fn["path"], line,
                            f"call to {name}() while holding {held} — the "
                            f"callee blocks ({', '.join(whats[:3])}); "
                            f"move the call outside the lock"))
        return out


# ---------------------------------------------------------------------------
# rule 17 — unjoined-thread
# ---------------------------------------------------------------------------
class UnjoinedThread(ProjectRule):
    id = "unjoined-thread"
    doc = ("threading.Thread/Timer created with no join on the shutdown "
           "path (self.X spawn with no self.X.join() in the class; local "
           "spawn with no join in the function; fire-and-forget)")

    def check(self, model: ProjectModel) -> list[tuple]:
        # joins per (path, cls) and per function
        class_joins: dict[tuple, set] = {}
        for key, fn in model.functions.items():
            cj = class_joins.setdefault((fn["path"], fn.get("cls")), set())
            cj.update(j for j in fn.get("joins", [])
                      if j.startswith("self."))
        out: list[tuple] = []
        for key, fn in sorted(model.functions.items()):
            if not in_scope(fn["path"]):
                continue
            local_joins = {j for j in fn.get("joins", [])
                           if j.startswith("local:")}
            for _ref, store, line, kind in fn.get("spawns", []):
                if kind != "thread":
                    continue
                if store and store.startswith("self."):
                    if store in class_joins.get(
                            (fn["path"], fn.get("cls")), set()):
                        continue
                    what = (f"thread stored on {store} is never joined "
                            f"anywhere in class {fn.get('cls')}")
                elif store and store.startswith("local:"):
                    if store in local_joins:
                        continue
                    what = (f"thread '{store[6:]}' is never joined in "
                            f"{fn['qual']}")
                else:
                    what = "fire-and-forget thread (no handle kept)"
                out.append((fn["path"], line,
                            f"{what} — shutdown cannot drain it; keep the "
                            f"handle and join on the stop path (or "
                            f"baseline with a reason if detaching is the "
                            f"design)"))
        return out


PROJECT_RULES = (UnguardedSharedField, LockOrderCycle, BlockingUnderLock,
                 UnjoinedThread)


def default_project_rules() -> tuple:
    """Pass 2 (concurrency) + pass 3 (dataflow) rule classes — the full
    interprocedural rule set a default run executes. Lazy import: dataflow
    imports from this module."""
    from .dataflow import DATAFLOW_RULES

    return tuple(PROJECT_RULES) + tuple(DATAFLOW_RULES)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------
def check_project(summaries: dict, sources: dict,
                  rules=None) -> list[Violation]:
    """Run the interprocedural rules over pre-extracted summaries.

    ``sources`` maps relpath -> source text (for snippets/suppressions);
    files without a summary (syntax errors, out of scope) are skipped.
    Rules yield ``(path, line, msg)`` or ``(path, line, msg, col,
    col_end)`` — the dataflow rules carry column spans so SARIF/GitHub
    annotations underline the exact expression.
    """
    model = ProjectModel({p: s for p, s in summaries.items()
                          if s is not None and in_scope(p)})
    out: list[Violation] = []
    suppress_cache: dict[str, dict] = {}
    lines_cache: dict[str, list] = {}
    for cls in (rules if rules is not None else default_project_rules()):
        rule = cls() if isinstance(cls, type) else cls
        for finding in rule.check(model):
            path, line, message = finding[0], finding[1], finding[2]
            col = finding[3] if len(finding) > 3 else 0
            col_end = finding[4] if len(finding) > 4 else 0
            src = sources.get(path)
            if src is None:
                snippet, suppressed = "", False
            else:
                if path not in lines_cache:
                    lines_cache[path] = src.splitlines()
                    suppress_cache[path] = suppression_table(src)
                lines = lines_cache[path]
                snippet = (lines[line - 1].strip()
                           if 1 <= line <= len(lines) else "")
                tab = suppress_cache[path]
                ids = tab.get(line, "absent")
                suppressed = (ids is None
                              or (ids != "absent" and rule.id in ids))
            if suppressed:
                continue
            out.append(Violation(rule=rule.id, path=path, line=line,
                                 col=col, message=message, snippet=snippet,
                                 severity=rule.severity, col_end=col_end))
    return sorted(out, key=lambda v: (v.path, v.line, v.col, v.rule))


def lint_project(sources: dict, rules=None) -> list[Violation]:
    """Fixture/test entry point: interprocedural lint over in-memory
    sources ({relpath: source}). Suppressions apply; baseline does not."""
    sources = {p.replace(os.sep, "/"): s for p, s in sources.items()}
    summaries = {p: extract_summary(p, s) for p, s in sources.items()}
    return check_project(summaries, sources, rules=rules)
