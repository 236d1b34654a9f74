"""Run ONE cell of BENCHMARK.json once, in one process that holds the chip:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (importing the program, ``h2o.init``, the frame made on the device
from the seed, warm-up) is timed from the moment JAX has found its devices;
what comes before (the interpreter, ``import jax``, the TPU runtime's start:
12 s that no code of this repo runs, and that grow with a machine's age) is
printed as ``platform`` and is not part of ``setup_s``. Then the window;
then, with the program's state freed, the plain reference decides ``correct``.
The last line of standard output is the result. Without a TPU the run
refuses: exit 2, no result line. ``--rehearse-cpu [--rows N]`` drives the
same path on whatever JAX finds, prints the platform and no result line,
and still exits non-zero.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

DRIVERS = {"train_back_to_back": "benchmark.drive_train"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="drive the path on any backend; never a result line")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows of the frame (rehearsal only)")
    return ap.parse_args(argv)


def measure(args, man: dict, cell: dict, config: dict, mix: dict, *,
            t_setup: float | None = None, tamper=None) -> dict:
    """The rest of a run, once a device has been found: set-up, window,
    reference, and the result as the last line carries it. The tests under
    benchmark/tests call this on the CPU with ``tamper``, which breaks what
    the timed path returned before the reference sees it."""
    import importlib

    import jax

    t_setup = time.perf_counter() if t_setup is None else t_setup
    if mix.get("kind") not in DRIVERS:
        raise ValueError(f"traffic kind {mix.get('kind')!r} is not one of "
                         f"{sorted(DRIVERS)}")
    driver = importlib.import_module(DRIVERS[mix["kind"]])
    out = driver.run(man=man, cell=cell, config=config, mix=mix, args=args,
                     t_setup=t_setup, root=ROOT, tamper=tamper)
    dev0 = jax.devices()[0]
    group = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in manifest.metrics_of(
        man, cell["name"], group)}
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in out["metrics"].items()
               if k in units and v is not None}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        tr = out.get("trace") or {}
        device["busy_s"], device["window_s"] = tr.get("busy_s"), tr.get("window_s")
        result["breakdown"] = {"device_ops": tr.get("device_ops", []),
                               "idle_gaps": tr.get("idle_gaps", [])}
        result["trace_slice"] = out.get("trace_slice")
    result["binding"] = out.get("binding")
    result["compared"] = out["compared"]
    return result


def main(argv=None) -> int:
    args = parse(argv)
    man = manifest.load(ROOT)
    try:
        cell = manifest.cell(man, args.workload)
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if args.rows is not None and not args.rehearse_cpu:
        print("run.py: --rows is for --rehearse-cpu only", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    t_setup = time.perf_counter()        # set-up is timed from here
    print(f"platform (s): {t_setup - T_PROCESS:.2f} to start Python, import "
          f"jax and find {len(devs)} x {devs[0].platform}", file=sys.stderr)
    on_chip = devs[0].platform == "tpu" and len(devs) >= cell["chips"]
    if not args.rehearse_cpu and not on_chip:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} x {devs[0].platform}: refusing to "
              f"measure (--rehearse-cpu rehearses, and still does not pass)",
              file=sys.stderr)
        return 2
    config = manifest.config_of(man, cell["config"], ROOT)
    if args.rows is not None:
        config["data"]["rows"] = args.rows
    mix = manifest.traffic_of(man, cell["traffic"], ROOT)
    result = measure(args, man, cell, config, mix, t_setup=t_setup)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        print(f"run.py: REHEARSAL of {cell['name']} on platform="
              f"{devs[0].platform} x{len(devs)} at {config['data']['rows']} "
              f"rows, correct={result['correct']}: this is not a measurement",
              flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
