"""Benchmark harness: one function per paper table/figure (+ the dry-run
roofline table when artifacts exist).

Prints ``name,us_per_call,derived`` CSV rows (simulated cycles at 1 GHz ->
us) and writes the full row dumps to results/benchmarks.json.
"""
from __future__ import annotations

import json
import os
import sys
import time


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    from benchmarks.figures import ALL_FIGURES

    all_rows = []
    print("name,us_per_call,derived")
    for name, fn in ALL_FIGURES.items():
        if only and only not in (name,):
            continue
        t0 = time.time()
        rows = fn()
        dt = time.time() - t0
        all_rows.extend(rows)
        # emit the headline geomean rows as CSV
        for r in rows:
            if str(r.get("dataset", "")).startswith("geomean"):
                key = [str(r.get(k)) for k in ("baseline", "ours", "height", "width",
                                               "processors", "format", "block")
                       if r.get(k) is not None]
                metric = next((r[k] for k in ("speedup", "reduction",
                                              "improvement_vs_csr",
                                              "speedup_vs_128", "slowdown_vs_w1")
                               if k in r), "")
                us = r.get("total_scv_cycles", r.get("cycles_scv", ""))
                us = f"{us/1e3:.1f}" if us else ""
                print(f"{name}:{r['dataset']}:{':'.join(key)},{us},{metric:.3f}"
                      if metric != "" else f"{name}:{r['dataset']},{us},")
        print(f"# {name} done in {dt:.1f}s ({len(rows)} rows)", flush=True)

    # roofline table from dry-run artifacts, if present
    path = "results/dryrun_single_pod.json"
    if (only is None or only == "roofline") and os.path.exists(path):
        from benchmarks.roofline import build_table, format_table

        table = build_table(path)
        print(format_table(table))
        for r in table:
            print(f"roofline:{r['arch']}:{r['shape']},,"
                  f"{r['bottleneck']}:{100*r['roofline_fraction']:.1f}%")
        all_rows.extend({"figure": "roofline", **r} for r in table)

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as fh:
        json.dump(all_rows, fh, indent=1, default=str)
    print(f"# wrote results/benchmarks.json ({len(all_rows)} rows)")


if __name__ == "__main__":
    main()
