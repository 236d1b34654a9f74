"""The readings a limit is set from, taken on the chip at the cell's own
size, one process a seed (the program does not give a frame's memory back
inside a process, so a dozen seeds do not fit in one):

    python3 benchmark/tools/readings.py --workload <cell> --seeds 12 \
        --controls 3 [--first-seed N] [--out chiprun_out/readings.jsonl]

For each seed the cell's own run (``run.measure``, a one-job window and no
warm-up job) gives the program's numbers: the lower readings. For the first
``--controls`` seeds the plain reference is put in the program's place in
the precision below the one the configuration states, and with each planted
fault, and its numbers are read by the same check: the upper readings. One
JSON line per reading. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import datagen, manifest, run  # noqa: E402

FAULTS = ("state_unchanged", "half_batch", "altered")


def candidates(ref, config, data):
    """(name, candidate) for the control and each fault, as the reference
    makes them."""
    c = config["correct"]
    if config["algo"] == "gbm":
        p = config["params"]
        n = int(c["control_trees"])
        yield "control", ref.build(data, n, p["max_depth"], p["learn_rate"],
                                   addend_dtype=c["control_dtype"],
                                   metrics_dtype=c["control_metrics_dtype"])
        for f in FAULTS:
            yield f, ref.build(data, n, p["max_depth"], p["learn_rate"],
                               fault=f)
    else:
        its = int(config["params"]["max_iterations"])
        yield "control", ref.fit(data, its, dtype_name=c["control_dtype"],
                                 metrics_dtype=c["control_metrics_dtype"])
        for f in FAULTS:
            yield f, ref.fit(data, its, fault=f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--rows", type=int, default=None,
                    help="a rehearsal's rows (any backend)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "readings.jsonl"))
    ap.add_argument("--one-seed", type=int, default=None,
                    help="(the child) this seed only")
    ap.add_argument("--with-controls", action="store_true")
    args = ap.parse_args(argv)
    if args.one_seed is None:
        # the parent never touches JAX: each seed is a process of its own
        for i in range(args.seeds):
            cmd = [sys.executable, os.path.abspath(__file__), "--workload",
                   args.workload, "--one-seed", str(args.first_seed + 7919 * i),
                   "--out", args.out]
            if args.rows:
                cmd += ["--rows", str(args.rows)]
            if i < args.controls:
                cmd += ["--with-controls"]
            rc = subprocess.run(cmd).returncode
            if rc:
                print(f"seed {cmd[5]}: exit {rc}", flush=True)
        return 0

    import jax
    from jax.sharding import SingleDeviceSharding

    from h2o_tpu.parallel import mesh as meshmod

    seed = args.one_seed
    man = manifest.load(ROOT)
    cell = manifest.cell(man, args.workload)
    config = manifest.config_of(man, cell["config"], ROOT)
    if args.rows:
        config["data"]["rows"] = args.rows
    elif jax.devices()[0].platform != "tpu":
        print("readings.py: no TPU; give --rows to rehearse", file=sys.stderr)
        return 2
    nrow = int(config["data"]["rows"])
    mix = dict(manifest.traffic_of(man, cell["traffic"], ROOT), warmup_jobs=0)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def emit(**kw):
        line = json.dumps({"workload": args.workload, "rows": nrow,
                           "seed": seed, **kw})
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    t = time.perf_counter()
    result = run.measure(
        run.parse(["--workload", args.workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", "0"]), man, cell, config, mix)
    emit(what="program", seconds=time.perf_counter() - t,
         numbers={k: v["value"] for k, v in result["compared"].items()})
    if not args.with_controls:
        return 0
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    c = config["correct"]
    cols = datagen.higgs_columns(seed, nrow, meshmod.padded_len(nrow),
                                 SingleDeviceSharding(jax.devices()[0]))
    data = ref.Data(cols, nrow)
    for name, cand in candidates(ref, config, data):
        t = time.perf_counter()
        if config["algo"] == "gbm":
            nums = ref.check(cand, data, config["params"]["learn_rate"],
                             range(int(c["control_trees"])), [0])
        else:
            nums = ref.check(cand, data, int(c["converge_iterations"]))
        emit(what=name, seconds=time.perf_counter() - t, numbers=nums)
    return 0


if __name__ == "__main__":
    sys.exit(main())
