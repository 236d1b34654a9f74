"""The readings the additive-model cell's limits are set from, by
``readings_path.py``'s method: on the chip at the cell's own size, one
process a seed, the program's numbers on every seed (``run.measure``, a
one-job window and no warm-up job) and, on the first ``--controls`` seeds,
the plain reference's own fit put in the program's place: in the precision
below the one the configuration states, and with each planted fault of
``reference/gam.py``. On those seeds also the rank error of the knots the
program's quantile sketch places (``hist_quantile_sketch_cols`` on the
smooth columns, as ``models/gam.py`` calls it) against the exact
quantiles. One JSON line per reading:

    python3 benchmark/tools/readings_gam.py --seeds 6 --controls 2 \
        [--first-seed N] [--rows N] [--out chiprun_out/readings_gam.jsonl]

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

CELL = "higgs_gam_train"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3_800_000_011)
    ap.add_argument("--rows", type=int, default=None,
                    help="a rehearsal's rows (any backend)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "readings_gam.jsonl"))
    ap.add_argument("--one-seed", type=int, default=None,
                    help="(the child) this seed only")
    ap.add_argument("--with-controls", action="store_true")
    args = ap.parse_args(argv)
    if args.one_seed is None:
        # the parent never touches JAX: each seed is a process of its own
        for i in range(args.seeds):
            cmd = [sys.executable, os.path.abspath(__file__), "--one-seed",
                   str(args.first_seed + 7919 * i), "--out", args.out]
            cmd += ["--rows", str(args.rows)] if args.rows else []
            cmd += ["--with-controls"] if i < args.controls else []
            rc = subprocess.run(cmd).returncode
            if rc:
                print(f"seed {cmd[3]}: exit {rc}", flush=True)
        return 0

    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from benchmark.reference import gam as ref
    from h2o_tpu.parallel import mesh as meshmod

    seed = args.one_seed
    man = manifest.load(ROOT)
    cell = manifest.cell(man, CELL)
    config = manifest.config_of(man, cell["config"], ROOT)
    if args.rows:
        config["data"]["rows"] = args.rows
    elif jax.devices()[0].platform != "tpu":
        print("readings_gam.py: no TPU; give --rows to rehearse",
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

    t = time.perf_counter()
    result = run.measure(
        run.parse(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", "0"]), man, cell, config, mix)
    emit(what="program", seconds=time.perf_counter() - t,
         numbers={k: v["value"] for k, v in result["compared"].items()})
    if not args.with_controls:
        return 0
    c, p = config["correct"], config["params"]
    cols = datagen.higgs_columns(seed, nrow, meshmod.padded_len(nrow),
                                 SingleDeviceSharding(jax.devices()[0]))
    # the knots as the program places them, against the exact quantiles
    from h2o_tpu.models.tree.binning import hist_quantile_sketch_cols

    smooth = [datagen.FEATURES.index(n) for n in p["gam_columns"]]
    qs = np.linspace(0.0, 1.0, int(p["num_knots"][0]))[1:-1]
    cuts = hist_quantile_sketch_cols([cols[j] for j in smooth],
                                     tuple(float(q) for q in qs))
    worst_rank, worst_x = 0.0, 0.0
    for k, j in enumerate(smooth):
        x = np.sort(np.asarray(cols[j], np.float64)[:nrow])
        rank = np.searchsorted(x, cuts[:, k].astype(np.float64)) / (nrow - 1)
        worst_rank = max(worst_rank, float(np.max(np.abs(rank - qs))))
        worst_x = max(worst_x, float(np.max(np.abs(
            cuts[:, k] - np.quantile(x, qs)))))
    emit(what="knots", rank_error=worst_rank, value_error=worst_x)
    data = ref.Data(cols, nrow)
    fits = [("control", {"dtype_name": c["control_dtype"],
                         "basis_dtype": c["control_basis_dtype"],
                         "metrics_dtype": c["control_metrics_dtype"]})]
    fits += [(f, {"fault": f}) for f in ref.FAULTS]
    for name, kw in fits:
        t = time.perf_counter()
        cand = ref.fit(data, config, **kw)
        emit(what=name, numbers=ref.check(cand, data, config),
             seconds=time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
