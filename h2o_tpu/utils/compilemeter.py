"""Process-global XLA compile counter — the serving warmup contract's meter.

The serving runtime promises "zero recompiles in steady state": every bucket
executable is AOT-compiled at model registration (`serving/scorer.py`), so a
compile observed during request serving is a bug (a shape that escaped the
buckets, a donated-buffer retrace, ...). That promise is only assertable if
compiles are *countable*, which jax exposes through `jax.monitoring`: the
dispatch layer records one `/jax/core/compile/backend_compile_duration`
event per program that reaches the compile path.

One measured caveat: that event wraps
``compiler.compile_or_get_cached``, so it fires for PERSISTENT-CACHE HITS
too — a program replayed from the `utils/compile_cache.py` disk cache
counts as a "compile" even though no XLA compilation ran. The cache layer
emits its own `/jax/compilation_cache/cache_hits` event per replay, so
this module tracks both and exposes the number that actually costs wall:

- ``count()``       — programs through the compile path (builds + replays);
- ``cache_hits()``  — persistent-cache replays among them;
- ``uncached_count()`` — real XLA compilations (count − cache_hits), the
  cold-start acceptance metric ``chip_smoke.py`` prints on its second run.

`count()` deltas remain the right meter where NO compile activity at all
is the contract (serving steady state: both numbers are zero). Callers
measure deltas around the region they care about::

    before = compilemeter.count()
    ...serve traffic...
    assert compilemeter.count() - before == 0

The listeners are registered once per process (jax.monitoring offers no
unregister, so install() is idempotent by module flag) and cost one dict
lookup per monitoring event — nothing on the request path.
"""

from __future__ import annotations

import contextlib
import threading

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_installed = False
_count = 0
_cache_hits = 0


#: thread-local steady-state guard (H2O_TPU_SANITIZE=recompiles): jax
#: compiles synchronously on the dispatching thread, so a per-thread
#: scope stack attributes every compile event to the section that
#: dispatched it — concurrent training/registration on OTHER threads
#: never trips a serving scope (the blame problem the global counter's
#: docstring warns about, solved by construction)
_STEADY = threading.local()


def _steady_stack() -> list:
    stack = getattr(_STEADY, "stack", None)
    if stack is None:
        stack = _STEADY.stack = []
    return stack


def _listener(name: str, secs: float, **kw) -> None:
    global _count
    if name == _COMPILE_EVENT:
        with _lock:
            _count += 1
        # every backend compile is also a registry counter and a timeline
        # event, so /3/Metrics and the bench sidecar deltas carry compile
        # counts per leg and cold-start cost is visible in /3/Timeline
        # (compiles are rare by contract — recording one is not hot-path)
        from . import telemetry, timeline

        telemetry.inc("xla.compile.count")
        # jax passes the compiled function's name with the event. A
        # persistent-cache replay fires its cache_hits event INSIDE
        # compile_or_get_cached, i.e. BEFORE this duration event on the
        # same thread — a pending hit pairs them: the event says it was a
        # replay, and replays (zero XLA wall) never raise under a
        # steady-state scope. Zeroed, not decremented: a hit whose
        # duration event never came (an exception between the two) is
        # spent on the next event and cannot pile up
        cached = getattr(_STEADY, "hits", 0) > 0
        _STEADY.hits = 0
        timeline.record("compile", str(kw.get("fun_name") or "backend_compile"),
                        secs=round(float(secs), 4), cached=cached)
        stack = _steady_stack()
        if stack:
            if cached:
                return
            from . import sanitizer

            err = sanitizer.SteadyStateCompileError(stack[-1])
            sanitizer._emit_violation("steady_compile", err,
                                      section=stack[-1])
            raise err


def _event_listener(name: str, **kw) -> None:
    global _cache_hits
    if name == _CACHE_HIT_EVENT:
        with _lock:
            _cache_hits += 1
        _STEADY.hits = getattr(_STEADY, "hits", 0) + 1


def install() -> None:
    """Register the monitoring listeners (idempotent, lazy jax import)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_listener)
    jax.monitoring.register_event_listener(_event_listener)


def count() -> int:
    """Total programs through the XLA compile path in this process so far
    (real compilations AND persistent-cache replays — see module doc).

    Process-global by nature: steady-state serving accounting must NOT
    diff this around individual device calls (a concurrent registration
    or training job would be blamed on the serving path) — the stats
    ``recompiles`` gauge counts the scorer's own bucket-miss fallbacks
    instead (`serving/scorer.py`). This counter is for delta assertions
    in controlled regions: warmup cost reporting and the zero-recompile
    tests."""
    install()
    with _lock:
        return _count


def cache_hits() -> int:
    """Persistent compile-cache replays observed so far."""
    install()
    with _lock:
        return _cache_hits


def uncached_count() -> int:
    """Real XLA compilations so far: ``count() − cache_hits()``, floored
    at 0 (the floor defends against event-ordering races; the two events
    fire from the same dispatch stack, so in practice it never engages)."""
    install()
    with _lock:
        return max(_count - _cache_hits, 0)


class CompileScope:
    """Compile-count delta over one region — the context-LOCAL reading the
    global counter's docstring warns against misusing: a scope pins its own
    start, so two concurrent scopes each see every compile in their window
    (attribution of a shared backend is inherently shared; per-cause
    blame stays with `serving/scorer.py`'s own bucket-miss gauge)."""

    __slots__ = ("start", "start_hits", "_end", "_end_hits")

    def __init__(self, start: int, start_hits: int):
        self.start = start
        self.start_hits = start_hits
        self._end: int | None = None
        self._end_hits: int | None = None

    @property
    def compiles(self) -> int:
        """Programs through the compile path since the scope opened
        (frozen at exit) — builds plus persistent-cache replays."""
        return (count() if self._end is None else self._end) - self.start

    @property
    def hits(self) -> int:
        """Persistent-cache replays in the window."""
        return ((cache_hits() if self._end_hits is None else self._end_hits)
                - self.start_hits)

    @property
    def uncached(self) -> int:
        """Real XLA compilations in the window (compiles − hits, ≥ 0)."""
        return max(self.compiles - self.hits, 0)


@contextlib.contextmanager
def scoped():
    """``with compilemeter.scoped() as sc: ... ; sc.compiles`` — the delta
    pattern made first-class (bench legs, per-train cold-start metering),
    mirroring PR 4's bucket-miss fix: read a scope, not the global."""
    sc = CompileScope(count(), cache_hits())
    try:
        yield sc
    finally:
        sc._end = count()
        sc._end_hits = cache_hits()


@contextlib.contextmanager
def no_compile_scope(section: str):
    """Declare the enclosed dispatches steady-state: under
    ``H2O_TPU_SANITIZE=recompiles`` any UNCACHED compile inside raises the
    typed :class:`~h2o_tpu.utils.sanitizer.SteadyStateCompileError` at the
    dispatching call site, naming ``section``. Persistent-cache replays
    are paired off against their cache-hit events and never raise (they
    cost no XLA wall). Thread-local: a concurrent registration or
    training job compiling on another thread is its own business.

    No-op (one cached env read) when the mode is off — the hot-path
    wiring sites (GBM chunk dispatch, serving _score_bucket) pay nothing
    in production."""
    from . import sanitizer

    if not sanitizer.enabled("recompiles"):
        yield
        return
    install()
    stack = _steady_stack()
    if not stack:
        _STEADY.hits = 0
    stack.append(section)
    try:
        yield
    finally:
        stack.pop()
