#!/usr/bin/env python3
"""Readings that set a cell's output limit: the program's and the controls'.

    python3 chipbench/control.py --workload gcn-paper.arxiv --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, and then, over the same compared requests, the verdict
of ``harness.check`` at the configuration's limit, with its ``out_gap``,
for

* ``program``: the served answers (what a run checks);
* one control per stage, the reference in the program's place with that
  stage one step below its stated precision (``combination_float8_e4m3fn``,
  ``aggregation_bfloat16`` for a bfloat16/float32 configuration); each has
  to come out not correct;
* ``exact``: the float64 reference in the program's place; it has to
  come out correct.

Beside each, the gaps against the stated-precision reference alone
(``vs_stated``) and against the exact one alone (``vs_exact``).  The
benchmark's own runs do not run this.  Needs a TPU.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402
from run import enable_compile_cache  # noqa: E402


def readings(work, win) -> dict:
    ref_mod = harness.reference_module(work.config)
    stated = work.config["precision"]

    def as_served(precision):
        return lambda w, adj, x: ref_mod.forward(w, adj, x, precision)

    cases = {"program": None, **ref_mod.controls(stated), "exact": harness.exact(stated)}
    out = {}
    for name, prec in cases.items():
        v = harness.check(win, work, outs=None if prec is None else as_served(prec))
        c = v["checks"]
        out[name] = {"correct": v["correct"], "out_gap": c["out_gap"]["value"],
                     "limit": c["out_gap"]["limit"], "vs_stated": v["vs_stated"],
                     "vs_exact": v["vs_exact"]}
    out["compared"] = c["compared"]["value"]
    out["missing"] = c["missing"]["value"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell, config, traffic = harness.cell_spec(bench, args.workload)
    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        harness.log("control: no TPU; refusing to run")
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        work = harness.Workload(config, traffic, seed, args.seconds, config["model"]["backend"])
        work.warm_up()
        win = work.window()
        work.stop()
        r = readings(work, win)
        r.update(workload=args.workload, seed=seed, seconds=time.monotonic() - t0)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
