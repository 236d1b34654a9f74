"""From a profiler trace to numbers: device busy time, idle share, the
device operations that took most time, and the longest idle gaps with the
host span that was open in them. The reduction works on plain event lists
so that it can be checked on a hand-built one; ``load_xplane`` makes those
lists from the ``.xplane.pb`` that ``jax.profiler`` writes."""

from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: container events on the ops line: they span their bodies' events and
#: would hide them in a list of the most expensive operations
CONTAINERS = ("while", "conditional", "call")


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return busy + ((cur_e - cur_s) if cur_e is not None else 0.0)


def gaps(intervals, t0: float, t1: float) -> list:
    """The idle (start, end) stretches of [t0, t1] that no interval covers."""
    out, at = [], t0
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return [(a, b) for a, b in out if b > a]


def host_span_at(host_events, t: float) -> str:
    """Name of the innermost host annotation open at instant t."""
    best, best_len = "(no host span)", None
    for name, s, e in host_events:
        if s <= t <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def is_container(name: str) -> bool:
    base = name.lstrip("%").split(".")[0].split(" ")[0]
    return base in CONTAINERS


_HASH = re.compile(r"\(\d+\)$")
_KIND = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")


def short_name(name: str, limit: int = 120) -> str:
    """An HLO instruction's text cut to its name, result shapes, operation
    and operand shapes: ``fusion.3 s8[8192] = fusion(s8[8192,28], s32[8192])``.
    A name that is no instruction text is kept, cut to ``limit``."""
    lhs, eq, rhs = name.partition(" = ")
    m = _KIND.search(" " + rhs) if eq else None
    if not m:
        return name[:limit]
    rhs = " " + rhs
    result = ", ".join(_SHAPE.findall(rhs[: m.start()]))
    operands = ", ".join(_SHAPE.findall(rhs[m.end():].split("), ")[0]))
    return f"{lhs.lstrip('%')} {result} = {m.group(1)}({operands})"[:limit]


def reduce_events(device_ops: dict, host_events: list, t0: float, t1: float,
                  top: int = 10, modules: dict | None = None) -> dict:
    """``device_ops``: {device name: [(op name, start s, end s), ...]}.
    ``host_events``: [(annotation name, start s, end s), ...] on the same
    clock. ``modules``: {device name: [(program name, start s, end s), ...]},
    the whole programs the device ran. Times are averaged over the devices."""
    if not device_ops:
        return {}
    busy, by_op, gap_list = [], {}, []
    for ops in device_ops.values():
        iv = [(max(s, t0), min(e, t1)) for _, s, e in ops if e > t0 and s < t1]
        busy.append(union_seconds(iv))
        for name, s, e in ops:
            if e > t0 and s < t1 and not is_container(name):
                by_op[name] = by_op.get(name, 0.0) + (min(e, t1) - max(s, t0))
        gap_list += gaps(iv, t0, t1)
    n = len(device_ops)
    by_gap = {}
    for a, b in gap_list:
        name = host_span_at(host_events, (a + b) / 2)
        by_gap[name] = by_gap.get(name, 0.0) + (b - a) / n
    window = t1 - t0
    busy_s = sum(busy) / n
    by_mod = {}
    for mods in (modules or {}).values():
        for name, s, e in mods:
            if e > t0 and s < t1:
                key = _HASH.sub("", name)
                by_mod[key] = by_mod.get(key, 0.0) + (min(e, t1) - max(s, t0)) / n
    return {
        "modules": by_mod,
        "busy_s": busy_s, "window_s": window,
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "device_ops": sorted(([short_name(k), v / n] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in by_gap.items()),
                            key=lambda kv: -kv[1])[:top],
        "events": sum(len(v) for v in device_ops.values()),
    }


def find_xplane(trace_dir: str) -> str | None:
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load_xplane(path: str, annotations=None) -> tuple[dict, list, dict]:
    """(device_ops, host_events, modules) in seconds on the trace's own clock.
    ``annotations``: keep only host events with these names (None: keep
    those that look like program spans, dotted lower-case names)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_events, modules = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = device_ops if line.name == OPS_LINE else modules
                    into[plane.name] = [
                        (ev.name, ev.start_ns / 1e9,
                         (ev.start_ns + ev.duration_ns) / 1e9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    keep = (name in annotations) if annotations is not None \
                        else ("." in name and name == name.lower()
                              and " " not in name and "/" not in name
                              and ":" not in name)
                    if keep:
                        host_events.append(
                            (name, ev.start_ns / 1e9,
                             (ev.start_ns + ev.duration_ns) / 1e9))
    return device_ops, host_events, modules


def reduce_slice(trace_dir: str | None, slice_s: float) -> dict | None:
    """The newest trace under ``trace_dir`` reduced over the slice: it opens
    with the trace's first event and lasts as long as the host measured.
    None where there is no trace or no device operation in it."""
    xp = find_xplane(trace_dir) if trace_dir else None
    if not xp:
        return None
    dev_ops, host, mods = load_xplane(xp)
    starts = [s for ops in dev_ops.values() for _, s, _ in ops]
    if not starts:
        return None
    t_open = min(starts + [s for _, s, _ in host])
    return reduce_events(dev_ops, host, t_open, t_open + slice_s, modules=mods)


def peek(path: str) -> None:
    """Print what a trace holds: planes, lines, event counts, first names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            span = ((evs[0].start_ns, evs[-1].start_ns + evs[-1].duration_ns)
                    if evs else None)
            print(f"  line {line.name!r}: {len(evs)} events, span {span}, "
                  f"top {[(k[:60], round(v / 1e9, 4)) for k, v in top]}")


if __name__ == "__main__":
    p = sys.argv[1]
    peek(p if p.endswith(".pb") else find_xplane(p))
