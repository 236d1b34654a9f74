"""The readings the XGBoost cell's limits are set from, by ``readings.py``'s
method (that tool bakes in ``reference/gbm.py``'s faults and ``build``
signature): on the chip at the cell's own size, one process a seed, the
program's numbers on every seed (``run.measure``, a one-job window and no
warm-up job) and, on the first ``--controls`` seeds, the plain reference put
in the program's place: in the precision below the one the configuration
states, and with each planted fault of ``reference/xgb.py``
(`xgb.candidates`). Each candidate is built when its turn comes and
checked under the parameters it was planted at. One JSON line per reading:

    python3 benchmark/tools/readings_xgb.py --seeds 6 --controls 1 \
        [--first-seed N] [--rows N] [--out chiprun_out/readings_xgb.jsonl]

Not part of a benchmark run."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import datagen, manifest, run  # noqa: E402

CELL = "higgs_xgb_train"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=3_400_000_111)
    ap.add_argument("--rows", type=int, default=None,
                    help="a rehearsal's rows (any backend)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "readings_xgb.jsonl"))
    ap.add_argument("--one-seed", type=int, default=None,
                    help="(the child) this seed only")
    ap.add_argument("--with-controls", action="store_true")
    ap.add_argument("--controls-only", action="store_true",
                    help="skip the program's run (its numbers are in hand)")
    args = ap.parse_args(argv)
    if args.one_seed is None:
        # the parent never touches JAX: each seed is a process of its own
        for i in range(args.seeds):
            cmd = [sys.executable, os.path.abspath(__file__), "--one-seed",
                   str(args.first_seed + 7919 * i), "--out", args.out]
            cmd += ["--rows", str(args.rows)] if args.rows else []
            cmd += ["--with-controls"] if i < args.controls else []
            cmd += ["--controls-only"] if args.controls_only else []
            rc = subprocess.run(cmd).returncode
            if rc:
                print(f"seed {cmd[3]}: exit {rc}", flush=True)
        return 0

    import jax
    from jax.sharding import SingleDeviceSharding

    from benchmark.reference import xgb as ref
    from h2o_tpu.parallel import mesh as meshmod

    seed = args.one_seed
    man = manifest.load(ROOT)
    cell = manifest.cell(man, CELL)
    config = manifest.config_of(man, cell["config"], ROOT)
    if args.rows:
        config["data"]["rows"] = args.rows
    elif jax.devices()[0].platform != "tpu":
        print("readings_xgb.py: no TPU; give --rows to rehearse",
              file=sys.stderr)
        return 2
    nrow = int(config["data"]["rows"])
    mix = dict(manifest.traffic_of(man, cell["traffic"], ROOT), warmup_jobs=0)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def emit(**kw):
        line = json.dumps({"workload": CELL, "rows": nrow, "seed": seed, **kw})
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    if not args.controls_only:
        t = time.perf_counter()
        result = run.measure(
            run.parse(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0"]),
            man, cell, config, mix)
        emit(what="program", seconds=time.perf_counter() - t,
             numbers={k: float(v["value"])
                      for k, v in result["compared"].items()})
    if not args.with_controls:
        return 0
    c = config["correct"]
    cols = datagen.higgs_columns(seed, nrow, meshmod.padded_len(nrow),
                                 SingleDeviceSharding(jax.devices()[0]))
    data = ref.Data(cols, nrow)
    t = time.perf_counter()
    for name, cand, prm in ref.candidates(config, data):
        built = time.perf_counter() - t
        t = time.perf_counter()
        nums = ref.check(cand, data, prm, range(int(c["control_trees"])), [0])
        emit(what=name, build_seconds=built,
             seconds=time.perf_counter() - t,
             numbers={k: float(v) for k, v in nums.items()})
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
