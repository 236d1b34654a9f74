"""The readers a per-layer metric's file can name. Each takes the run's
observations and the file's own arguments and returns a number, or None
where it finds nothing to read: the harness then leaves the metric out."""

from __future__ import annotations

import json
import sys

from . import manifest, peaks


def _spans(obs, name):
    return [e for e in obs.get("spans", []) if e["what"] == name]


def client_less_span(obs, span: str, **_):
    """Per job: the wall at the client less the server's span of that job."""
    jobs, spans = obs.get("jobs", []), _spans(obs, span.format(**obs))
    if not jobs or len(spans) != len(jobs):
        return None
    client = sum(j["end"] - j["start"] for j in jobs)
    return (client - sum(e["dur_us"] for e in spans) / 1e6) / len(jobs)


def span_less_children(obs, span: str, children: str, **_):
    """Per job: a span's time less the sum of its child spans."""
    top = _spans(obs, span.format(**obs))
    kids = _spans(obs, children.format(**obs))
    if not top or not kids:
        return None
    return (sum(e["dur_us"] for e in top)
            - sum(e["dur_us"] for e in kids)) / 1e6 / len(top)


def span_sum_per(obs, span: str, per: str, **_):
    """A span's summed time over a count of the window (``trees``, ``jobs``)."""
    evs = _spans(obs, span.format(**obs))
    n = obs.get(per)
    if not evs or not n:
        return None
    return sum(e["dur_us"] for e in evs) / 1e6 / n


def meter(obs, which: str, **_):
    return obs.get("meters", {}).get(which)


def memory_peak_gb(obs, **_):
    b = obs.get("memory_peak_bytes")
    return None if not b else b / 1e9


def work_share(obs, **_):
    """The least time the chips could take for the work the algorithm
    requires in the window, over the window's seconds, in percent."""
    work, window = obs.get("work"), obs.get("window_s")
    if not work or not window:
        return None
    least, bound = peaks.least_seconds(work[0], work[1], obs["device_kind"],
                                       obs.get("chips", 1))
    obs.setdefault("binding", {})[obs.get("_metric", "work")] = bound
    return 100.0 * least / window


def trace_idle_share(obs, **_):
    tr = obs.get("trace") or {}
    if not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def trace_module_s(obs, contains: str, **_):
    """Device seconds, inside the traced slice, of the whole programs whose
    name contains ``contains``."""
    mods = (obs.get("trace") or {}).get("modules") or {}
    hit = [v for k, v in mods.items() if contains in k]
    return sum(hit) if hit else None


READERS = {f.__name__: f for f in (
    client_less_span, span_less_children, span_sum_per,
    meter, memory_peak_gb, work_share, trace_idle_share, trace_module_s)}


def read_all(man: dict, cell: dict, root: str, obs: dict, on_chip: bool) -> dict:
    """Every per-layer metric of a cell, by the reader its file names.
    Off the chip (a rehearsal) a reader that lacks a peak is skipped."""
    out = {}
    for m in manifest.metrics_of(man, cell["name"], "per_layer"):
        with open(manifest.layer_metric_file(man, m["name"], root)) as f:
            spec = json.load(f)
        obs["_metric"] = m["name"]
        try:
            out[m["name"]] = READERS[spec["reader"]](obs, **spec.get("args", {}))
        except KeyError as e:
            if on_chip:
                raise
            print(f"rehearsal: {m['name']} not read: {e}", file=sys.stderr)
    return out
