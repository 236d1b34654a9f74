"""Device seconds per declared scope (``telemetry.SCOPES``) and per program
(``telemetry.PROGRAMS``) in a capture:
``python tools/trace_scopes.py <capture dir or .xplane.pb>``.

An op's scope is the innermost declared name on the ``jax.named_scope`` path
in its metadata. The trace keeps the path in a stat of the event or of its
event-metadata entry, which ``ProfileData`` does not expose, so the file is
read as plain protobuf fields (tsl's xplane.proto). Prints, per device, seconds
by scope and which stat held the path in how many events (None: none); for a
capture of several chips also the mean plane, with each scope's least and
most over the chips. Under each plane's scope table, from the ``XLA Modules``
line of the same protobuf (`programs.capture_modules`): device seconds and
executions by declared program, then the undeclared programs by name (the
eager primitives a later PR fuses).
Imports four names of ``benchmark.trace_reduce``: keep them stable."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace_reduce import (DEVICE_PLANE, OPS_LINE,  # noqa: E402
                                    find_xplane, is_container)
from h2o_tpu.utils.programs import (DECLARED_MODULES,  # noqa: E402
                                    capture_modules)
from h2o_tpu.utils.telemetry import SCOPES  # noqa: E402


def _varint(buf, i):
    v, shift = buf[i] & 0x7F, 7
    while buf[i] >= 0x80:
        i += 1
        v |= (buf[i] & 0x7F) << shift
        shift += 7
    return v, i + 1


def _fields(buf):
    """(field number, int | memoryview) of a message; fixed-width skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        if key & 7 == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif key & 7 == 2:
            n, i = _varint(buf, i)
            yield key >> 3, buf[i:i + n]
            i += n
        else:
            i += 8 if key & 7 == 1 else 4


def reduce_plane(plane) -> tuple[dict, dict]:
    """({scope: seconds}, {stat name: events}) over one XPlane's XLA ops. An
    XStat's text is its str_value (5) or its ref_value (7) into the names."""
    names, emeta, secs, held = {}, {}, {}, {}
    plane = list(_fields(plane))
    for num, v in plane:
        if num in (4, 5):                   # map entries: key 1, message 2
            body = list(_fields(dict(_fields(v))[2]))
            ident, name = dict(body).get(1, 0), dict(body).get(2, b"")
            if num == 5:
                names[ident] = str(name, "utf8")
            else:                           # XEventMetadata: its stats are 5
                emeta[ident] = (str(name, "utf8", "replace"),
                                [x for n, x in body if n == 5])
    for line in (list(_fields(v)) for n, v in plane if n == 3):
        if str(dict(line).get(2, b""), "utf8") != OPS_LINE:
            continue
        for ev in (list(_fields(v)) for n, v in line if n == 4):
            d = {n: v for n, v in ev if n != 4}     # XEvent: id 1, ps 3
            name, mstats = emeta.get(d.get(1, 0), ("", []))
            if is_container(name):
                continue
            scope, stat = "unscoped", None
            for f in (dict(_fields(m))
                      for m in [v for n, v in ev if n == 4] + mstats):
                text = (str(f[5], "utf8", "replace") if 5 in f
                        else names.get(f.get(7), ""))
                hit = [p for p in text.split("/") if p in SCOPES]
                if hit:
                    scope, stat = hit[-1], names.get(f.get(1), "?")
                    break
            secs[scope] = secs.get(scope, 0.0) + d.get(3, 0) / 1e12
            held[stat] = held.get(stat, 0) + 1
    return secs, held


def _table(secs: dict, spread: dict | None = None) -> None:
    total = sum(secs.values()) or 1.0
    for k, v in sorted(secs.items(), key=lambda kv: -kv[1]):
        lo_hi = "" if spread is None else "  (%.4f .. %.4f)" % spread[k]
        print(f"  {k:12s} {v:9.4f} s {100 * v / total:5.1f}%{lo_hi}")


def _program_table(mods: dict) -> None:
    """One plane's modules: the declared programs, then the rest by name."""
    for title, keep in (("declared programs", True), ("undeclared", False)):
        rows = sorted(((k, v) for k, v in mods.items()
                       if (k in DECLARED_MODULES) == keep),
                      key=lambda kv: -kv[1][0])
        print(f"  {title}: {sum(v[0] for _, v in rows):.4f} s of "
              f"{sum(v[0] for v in mods.values()):.4f} s of XLA modules")
        for k, (secs, runs) in rows:
            print(f"    {k:32s} {secs:9.4f} s {runs:5d} x")


def main(path: str) -> None:
    xp = path if path.endswith(".pb") else find_xplane(path)
    with open(xp or sys.exit(f"no .xplane.pb under {path}"), "rb") as f:
        space = memoryview(f.read())
    modules = capture_modules(xp)
    planes = []
    for plane in (v for n, v in _fields(space) if n == 1):
        name = str(dict(_fields(plane)).get(2, b""), "utf8")
        if name.startswith(DEVICE_PLANE):
            secs, held = reduce_plane(plane)
            planes.append(secs)
            print(f"{name}: {sum(secs.values()):.4f} s of XLA ops; "
                  f"scope held in {held}")
            if set(held) == {None}:     # the cache key leaves out op metadata
                print("  NO scope: executables replayed from a cache written "
                      "before the scopes? Empty JAX_COMPILATION_CACHE_DIR")
            _table(secs)
            _program_table(modules.get(name, {}))
    if len(planes) > 1:
        # several chips: the mean plane, and each scope's least and most
        # over the chips (the straggler a collective waits for)
        scopes = sorted({k for p in planes for k in p})
        per = {k: [p.get(k, 0.0) for p in planes] for k in scopes}
        print(f"mean of {len(planes)} device planes (least .. most):")
        _table({k: sum(v) / len(v) for k, v in per.items()},
               {k: (min(v), max(v)) for k, v in per.items()})


if __name__ == "__main__":
    main(sys.argv[1])
