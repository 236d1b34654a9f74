"""HBM memory manager — the `water.Cleaner` / MemoryManager analog.

The reference runs a background Cleaner that, under memory pressure, swaps
least-recently-used values out of the K/V store to disk ("ice") and drops
cached POJOs (`water/Cleaner.java`, `water/MemoryManager.java`). The TPU
analog: bulk data lives in HBM as sharded `jax.Array`s hanging off Vecs, so
the Cleaner tracks every device-resident Vec (weakly), and when tracked bytes
exceed the budget it spills the coldest Vecs' device buffers to disk; the Vec
rehydrates transparently on next `.data` access (`frame/vec.py`).

Budget resolution order:
- ``H2O_TPU_HBM_LIMIT_BYTES`` env (tests pin this for determinism),
- ``memory_stats()['bytes_limit']`` × 0.85 as the backend reports it —
  resolved once and cached. A TPU backend that reports none is an ERROR
  (``hbm_stats``): real allocations are sized from this number and nothing
  here guesses it from a device name,
- otherwise (the CPU test mesh) unlimited: the Cleaner only observes.

``hbm_budget_bytes()`` exposes the same resolution to compute planners —
the tree engine's histogram row blocks, the binning sketch's column blocks,
and the frame rollup batcher all size their intermediates from it instead
of hardcoded constants.

Accounting is a running counter (track/spill/rehydrate/GC adjust it), not a
per-call scan; spill files are removed on rehydrate, on overwrite, and by a
weakref finalizer when a spilled Vec is garbage-collected.

The tracked protocol is duck-typed on Vec's fields (``_data``, ``_lock``,
``_spill_path``, ``_last_access``, ``key``), so the chunk store's coded
columns and binned views (`frame/chunks.py` CodedVec/BinnedView — Vec
subclasses whose ``_data`` holds CODES, not f32) ride the same ledger:
their coded bytes debit ``hbm_budget_bytes()`` while alive, and they
spill/rehydrate by LRU like raw columns (reload sharding comes from
``Vec._put_sharding`` — const/sparse payloads replicate).
"""

from __future__ import annotations

import os
import tempfile
import threading
import weakref

import numpy as np

from ..utils import knobs, telemetry
from ..utils.sanitizer import guarded_by

_UNRESOLVED = object()


def hbm_stats() -> dict | None:
    """``memory_stats()`` of the local device with the MOST bytes in use —
    the chip a per-device budget has to fit on a multi-chip host — or None
    where the backend reports none (the CPU mesh).

    On a TPU backend missing stats (or stats without ``bytes_limit``) raise:
    the hardware is asked, never assumed."""
    import jax

    per_dev = [d.memory_stats() for d in jax.local_devices()]
    if all(s and s.get("bytes_limit") for s in per_dev):
        return dict(max(per_dev, key=lambda s: s.get("bytes_in_use", 0)))
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "TPU backend reports no memory_stats()['bytes_limit'] — the "
            "HBM budget cannot be resolved (set H2O_TPU_HBM_LIMIT_BYTES "
            "to pin one explicitly)")
    return None


def hbm_span_attrs() -> dict:
    """``hbm_in_use_gb`` and ``hbm_peak_gb`` of the fullest device, now: what
    a span that may set the HBM peak carries at its close (the sketch, the
    coded view, the GLM's design, a job's root), so that a job's own spans
    show the first one at whose close the peak stands and whether the job
    before left memory behind. One ``memory_stats()`` call; {} where the
    backend reports none (the CPU mesh)."""
    stats = hbm_stats()
    if not stats:
        return {}
    return {"hbm_in_use_gb": stats.get("bytes_in_use", 0) / 1e9,
            "hbm_peak_gb": stats.get("peak_bytes_in_use", 0) / 1e9}


_HW_BYTES = _UNRESOLVED  # cached device_hbm_bytes result


def device_hbm_bytes() -> int | None:
    """Physical per-device HBM as the backend reports it
    (``memory_stats()['bytes_limit']``); None on backends without memory
    stats (CPU). Resolved once."""
    global _HW_BYTES
    if _HW_BYTES is _UNRESOLVED:
        stats = hbm_stats()
        _HW_BYTES = int(stats["bytes_limit"]) if stats else None
    return _HW_BYTES


def hbm_budget_bytes() -> int | None:
    """LIVE HBM planning budget for compute intermediates, PER DEVICE: 85%
    of one device's physical HBM (the Cleaner's headroom) minus the bytes
    the Cleaner currently tracks as resident on the FULLEST device (a
    row-sharded frame debits each chip its own slice, not the total over
    the mesh; one chip reads as before) and minus outstanding serving
    reservations (:func:`reserve_bytes`), floored at 1/16 of physical so
    planners always get a workable (if small) budget under pressure.
    ``H2O_TPU_HBM_LIMIT_BYTES`` pins the PRE-reservation value exactly (no
    residency adjustment — tests mock budgets with it); None when no
    accelerator budget is resolvable (planners fall back to their own
    conservative defaults)."""
    env = knobs.raw("H2O_TPU_HBM_LIMIT_BYTES")
    if env and int(env) > 0:  # 0 = backend resolution (optargs contract)
        return int(env)
    hw = device_hbm_bytes()
    if not hw:
        return None
    return max(int(hw * 0.85) - CLEANER.fullest_device_bytes()
               - reserved_bytes(), hw >> 4)


# ---------------------------------------------------------------------------
# reservation ledger — the serving control plane's quota hook
# ---------------------------------------------------------------------------
#: owner -> bytes reserved out of the shared HBM pool. The serving control
#: plane (serving/control.py) reserves each PLACED model's estimated
#: residency here, so training planners (hbm_budget_bytes) and the Cleaner's
#: sweep threshold (Cleaner.limit_bytes) both see serving occupancy through
#: the ONE existing accounting instead of a parallel serving-only ledger.
_RESERVATIONS: dict[str, int] = {}
_RES_LOCK = threading.Lock()


def reserve_bytes(owner: str, nbytes: int) -> None:
    """Reserve ``nbytes`` of the shared HBM pool under ``owner`` (replacing
    any prior reservation for the same owner)."""
    with _RES_LOCK:
        _RESERVATIONS[owner] = max(int(nbytes), 0)


def release_bytes(owner: str) -> int:
    """Drop ``owner``'s reservation; returns the bytes freed (0 if none)."""
    with _RES_LOCK:
        return _RESERVATIONS.pop(owner, 0)


def reserved_bytes() -> int:
    """Total outstanding reservations (0 when serving placed nothing)."""
    with _RES_LOCK:
        return sum(_RESERVATIONS.values())


def _vec_nbytes(arr) -> int:
    return 0 if arr is None else arr.size * arr.dtype.itemsize


def _arr_device_bytes(arr) -> dict:
    """Per-DEVICE footprint — the distinction the process-global counter
    cannot see and a per-chip HBM budget lives or dies by. Shared with the
    bench accounting via the one mesh-layer implementation."""
    from ..parallel.mesh import device_nbytes

    return device_nbytes(arr)


class Cleaner:
    def __init__(self):
        import itertools

        from ..utils.sanitizer import make_lock

        self._vecs: "weakref.WeakValueDictionary[int, object]" = \
            weakref.WeakValueDictionary()
        self._lock = make_lock("Cleaner._lock", rlock=True)
        # atomic in CPython — Vec.data reads must not contend on a lock
        self._clock = itertools.count(1)
        # ledger keys are per-vec monotonic tokens, NOT id(vec): CPython can
        # reuse a dead vec's address for a new vec before the old finalizer
        # fires, and a stale _on_dead must never pop the new vec's bytes
        self._token_ctr = itertools.count(1)
        self._resident_bytes = 0
        self._sizes: dict[int, int] = {}  # vec token -> its resident bytes
        # per-DEVICE residency (the multi-chip split of the live-bytes
        # gauge): token -> {device label -> bytes}, plus the live totals
        # and process-lifetime peaks the prometheus provider exposes as
        # h2o_tpu_cleaner_device_live_bytes{device="..."}
        self._dev_by_tok: dict[int, dict] = {}
        self._dev_live: dict[str, int] = {}
        self._dev_peak: dict[str, int] = {}
        self._stats_limit = _UNRESOLVED  # memory_stats-based limit, cached
        self.spill_dir = None            # lazy tempdir
        self.spills = 0                  # observability (`/3/Cloud` swap ctr)

    # -- budget ---------------------------------------------------------------
    def limit_bytes(self) -> int | None:
        """Sweep threshold for tracked Vec residency: the resolved HBM
        budget minus outstanding serving reservations (floored at 1/16 of
        the base so a quota-heavy fleet can't drive the Cleaner into a
        spill storm) — frames yield HBM to placed serving models through
        the same ledger planners read."""
        base = self._base_limit_bytes()
        if base is None:
            return None
        res = reserved_bytes()
        return max(base - res, base >> 4) if res else base

    def _base_limit_bytes(self) -> int | None:
        env = knobs.raw("H2O_TPU_HBM_LIMIT_BYTES")
        if env and int(env) > 0:  # 0 = backend resolution (optargs contract)
            telemetry.set_gauge("cleaner.hbm.limit.bytes", int(env))
            return int(env)
        if self._stats_limit is not _UNRESOLVED:
            # resolved: lock-free read of an immutable value — planner
            # budget queries must not contend with a sweep holding the
            # ledger lock
            return self._stats_limit
        with self._lock:
            # first resolve under the ledger lock: serving admission and a
            # training planner racing it must not both resolve (and
            # double-emit the gauge)
            return self._resolve_stats_limit_locked()

    @guarded_by("_lock")
    def _resolve_stats_limit_locked(self) -> int | None:
        if self._stats_limit is _UNRESOLVED:
            hw = device_hbm_bytes()
            limit = int(hw * 0.85) if hw else None
            self._stats_limit = limit
            telemetry.set_gauge("cleaner.hbm.limit.bytes", limit or 0)
        return self._stats_limit

    # -- tracking -------------------------------------------------------------
    def touch(self, vec) -> int:
        """Record an access; returns the new LRU clock stamp (lock-free)."""
        return next(self._clock)

    def _token(self, vec) -> int:
        tok = getattr(vec, "_cleaner_token", None)
        if tok is None:
            tok = next(self._token_ctr)
            vec._cleaner_token = tok
        return tok

    def track(self, vec, nbytes: int) -> None:
        """Register a newly device-resident Vec (construction / rehydrate /
        setter). The caller holds the vec's own lock if one exists."""
        with self._lock:
            tok = self._token(vec)
            if tok not in self._vecs:
                self._vecs[tok] = vec
                weakref.finalize(vec, self._on_dead, tok,
                                 getattr(vec, "key", None))
            self._resident_bytes += nbytes
            self._sizes[tok] = self._sizes.get(tok, 0) + nbytes
            dm = _arr_device_bytes(getattr(vec, "_data", None))
            if dm:
                per = self._dev_by_tok.setdefault(tok, {})
                for d, b in dm.items():
                    per[d] = per.get(d, 0) + b
                    live = self._dev_live.get(d, 0) + b
                    self._dev_live[d] = live
                    if live > self._dev_peak.get(d, 0):
                        self._dev_peak[d] = live
            telemetry.set_gauge("cleaner.hbm.live.bytes",
                                max(self._resident_bytes, 0))
        self.maybe_sweep(exclude=tok)

    def note_freed(self, vec, nbytes: int,
                   spill_path: str | None = None) -> None:
        """A device buffer went away outside a sweep (setter overwrite)."""
        self._debit(vec, nbytes)
        if spill_path:
            self._remove_ice(spill_path)

    def _debit(self, vec, nbytes: int) -> None:
        with self._lock:
            self._resident_bytes -= nbytes
            tok = getattr(vec, "_cleaner_token", None)
            if tok in self._sizes:
                before = self._sizes[tok]
                after = max(before - nbytes, 0)
                self._sizes[tok] = after
                self._dev_release(tok, after / before if before > 0 else 0.0)
            telemetry.set_gauge("cleaner.hbm.live.bytes",
                                max(self._resident_bytes, 0))

    @guarded_by("_lock")
    def _dev_release(self, tok, keep_frac: float) -> None:
        """Scale a token's per-device residency by ``keep_frac`` (0 drops
        it) and debit the live per-device totals. Lock held by caller
        (asserted under H2O_TPU_SANITIZE=guards)."""
        per = self._dev_by_tok.get(tok)
        if per is None:
            return
        if keep_frac <= 0:
            self._dev_by_tok.pop(tok, None)
            for d, b in per.items():
                self._dev_live[d] = max(self._dev_live.get(d, 0) - b, 0)
            return
        for d, b in list(per.items()):
            kept = int(b * keep_frac)
            per[d] = kept
            self._dev_live[d] = max(self._dev_live.get(d, 0) - (b - kept), 0)

    def _on_dead(self, tok, key):
        # a spilled vec's ice file dies with it, and whatever bytes it still
        # held resident leave the counter — otherwise churned temporaries
        # drift the counter upward and every construction pays a recount
        with self._lock:
            self._resident_bytes -= self._sizes.pop(tok, 0)
            self._dev_release(tok, 0.0)
            telemetry.set_gauge("cleaner.hbm.live.bytes",
                                max(self._resident_bytes, 0))
        if key and self.spill_dir:
            self._remove_ice(os.path.join(self.spill_dir, f"{key}.npy"))

    @staticmethod
    def _remove_ice(path: str) -> None:
        try:
            os.remove(path)
        except OSError:
            pass

    def tracked_bytes(self) -> int:
        with self._lock:
            return max(self._resident_bytes, 0)

    def device_bytes(self) -> dict:
        """Live tracked bytes PER DEVICE ({device label: bytes}) — the
        multi-chip residency split (a replicated array books its full
        nbytes on every device; a row-sharded one ~1/n_shards each)."""
        with self._lock:
            return {d: b for d, b in self._dev_live.items() if b > 0}

    def fullest_device_bytes(self) -> int:
        """Live tracked bytes on the device that holds the most: what a
        per-device budget is debited (host-resident payloads hold no HBM)."""
        with self._lock:
            return max((b for d, b in self._dev_live.items() if d != "host"),
                       default=0)

    def device_peak_bytes(self) -> dict:
        """Process-lifetime per-device residency peaks (the per-chip HBM
        watermark the bench `sharded` leg and /3/Metrics report)."""
        with self._lock:
            return dict(self._dev_peak)

    def _recount(self) -> tuple[int, dict]:
        """Exact resync against live vecs, DEDUPED by device buffer: several
        Vecs may wrap the same jax array (as_factor views etc.) — it holds
        HBM once and spilling one alias frees nothing. Returns (total bytes,
        {buffer id: alias count}); corrects drift from GC'd arrays."""
        with self._lock:
            vecs = [v for v in self._vecs.values()
                    if getattr(v, "_data", None) is not None]
            seen: dict = {}
            total = 0
            for v in vecs:
                bid = id(v._data)
                if bid not in seen:
                    total += _vec_nbytes(v._data)
                seen[bid] = seen.get(bid, 0) + 1
            # a shared buffer's bytes are SPLIT across its alias tokens so the
            # per-token ledger sums to _resident_bytes: when one alias dies,
            # _on_dead debits only its share, not the whole still-live buffer
            sizes: dict[int, int] = {}
            dev_by_tok: dict[int, dict] = {}
            dev_live: dict[str, int] = {}
            for v in vecs:
                tok = self._token(v)
                aliases = seen[id(v._data)]
                sizes[tok] = _vec_nbytes(v._data) // aliases
                dm = {d: b // aliases
                      for d, b in _arr_device_bytes(v._data).items()}
                dev_by_tok[tok] = dm
                for d, b in dm.items():
                    dev_live[d] = dev_live.get(d, 0) + b
            self._resident_bytes = total
            self._sizes = sizes
            self._dev_by_tok = dev_by_tok
            self._dev_live = dev_live
            for d, b in dev_live.items():
                if b > self._dev_peak.get(d, 0):
                    self._dev_peak[d] = b
            return total, seen

    # -- the sweep (Cleaner.run's store_clean pass) ---------------------------
    def emergency_sweep(self, exclude: int | None = None) -> int:
        """Spill EVERYTHING spillable except ``exclude`` — the rehydrate
        path's response to a device OOM (`frame/vec.py`): free the maximum
        HBM regardless of budget, so the failed device_put can retry."""
        from ..utils import timeline

        telemetry.inc("cleaner.emergency_sweep.count")
        freed = self.maybe_sweep(exclude=exclude, target_bytes=0)
        timeline.record("cleaner", "emergency_sweep", freed_bytes=freed)
        return freed

    def maybe_sweep(self, exclude: int | None = None,
                    target_bytes: int | None = None) -> int:
        from ..utils import sanitizer

        limit = self.limit_bytes() if target_bytes is None else target_bytes
        if limit is None:
            return 0
        if self.tracked_bytes() <= limit:
            return 0
        used, aliases = self._recount()
        if used <= limit:
            return 0
        with self._lock:
            vecs = sorted((v for v in self._vecs.values()
                           if getattr(v, "_data", None) is not None
                           and getattr(v, "_cleaner_token", None) != exclude
                           # pinned views (a BinnedView mid-train) stay: the
                           # trainer holds the buffer anyway, so spilling
                           # would debit the ledger while freeing no HBM
                           and not getattr(v, "_pinned", False)
                           # spilling an aliased buffer frees no HBM
                           and aliases.get(id(v._data), 1) == 1),
                          key=lambda v: getattr(v, "_last_access", 0))
        freed = 0
        # H2O_TPU_SANITIZE=transfers: the sweep's only sanctioned
        # device->host move is the spill's explicit device_get — an
        # implicit conversion anywhere in the loop raises typed
        with sanitizer.transfer_scope("cleaner.sweep"):
            for v in vecs:
                if used - freed <= limit:
                    break
                freed += self._spill(v)
        return freed

    def _spill(self, vec) -> int:
        # non-blocking: a vec whose lock is held is in active use — skip it
        # (this also prevents lock-order inversion against a rehydrating
        # reader that holds its vec lock while sweeping others)
        if not vec._lock.acquire(blocking=False):
            return 0
        try:
            return self._spill_locked(vec)
        finally:
            vec._lock.release()

    def _spill_locked(self, vec) -> int:
        import jax

        from ..utils import failpoints

        failpoints.hit("cleaner.spill")
        arr = vec._data
        if arr is None:
            return 0
        nbytes = _vec_nbytes(arr)
        if self.spill_dir is None:
            self.spill_dir = tempfile.mkdtemp(prefix="h2o_tpu_ice_")
        path = os.path.join(self.spill_dir, f"{vec.key}.npy")
        # EXPLICIT device->host fetch (not np.asarray): the spill is a
        # declared sync point, so it stays silent under the sweep's
        # transfer guard and the graftlint host-transfer-in-hot-path rule
        np.save(path, jax.device_get(arr))  # device -> host -> ice
        vec._spill_path = path
        vec._data = None                # HBM buffer becomes collectable
        self._debit(vec, nbytes)
        with self._lock:
            self.spills += 1
        telemetry.inc("cleaner.spill.count")
        telemetry.inc("cleaner.spill.bytes", nbytes)
        return nbytes


#: process-global Cleaner (the `H2O.CLEANER` role)
CLEANER = Cleaner()


def _prometheus_device_lines() -> list:
    """Per-device label dimension for the Prometheus exposition (the PR 6
    residual unblocked by multi-chip sharding): the process-global
    ``h2o_tpu_cleaner_hbm_live_bytes`` stays in the registry — one
    accounting — and these ``{device="..."}`` families split it per chip,
    straight off the Cleaner's per-device ledger (the serving per-model
    provider pattern)."""
    live = CLEANER.device_bytes()
    peak = CLEANER.device_peak_bytes()
    if not live and not peak:
        return []
    esc = telemetry.prom_label_escape
    lines = [
        "# HELP h2o_tpu_cleaner_device_live_bytes tracked device-resident "
        "bytes per device (replicated arrays count on every device)",
        "# TYPE h2o_tpu_cleaner_device_live_bytes gauge",
    ]
    for d, b in sorted(live.items()):
        lines.append(
            f'h2o_tpu_cleaner_device_live_bytes{{device="{esc(d)}"}} {b:g}')
    lines += [
        "# HELP h2o_tpu_cleaner_device_peak_bytes process-lifetime peak "
        "of tracked bytes per device (the per-chip HBM watermark)",
        "# TYPE h2o_tpu_cleaner_device_peak_bytes gauge",
    ]
    for d, b in sorted(peak.items()):
        lines.append(
            f'h2o_tpu_cleaner_device_peak_bytes{{device="{esc(d)}"}} {b:g}')
    return lines


telemetry.add_prometheus_provider(_prometheus_device_lines)


def base_hbm_limit_bytes() -> int | None:
    """The resolved HBM budget BEFORE reservation subtraction — the number
    the serving control plane takes its quota fraction of (taking it from
    the post-reservation limit would shrink serving's own quota as serving
    places models: a feedback loop, not an accounting)."""
    return CLEANER._base_limit_bytes()
