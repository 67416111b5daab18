#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload gcn-paper.arxiv --seed 7 --seconds 30 --trace 0

Builds the cell's inputs and weights from ``--seed``, warms up every
shape the cell's traffic uses (through the persistent compile cache at
``JAX_COMPILATION_CACHE_DIR``, or ``<checkout>/.jax_cache``), measures for
``--seconds``, checks the answers served in the window against the plain
reference, and prints one JSON line as the last line of stdout.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and it reports the per-layer metrics,
the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import cost  # noqa: E402
import harness  # noqa: E402
from harness import log  # noqa: E402


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    set (JAX reads it itself), else a fixed directory in the checkout.
    Every program is cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(HERE.parent / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.benchmark()
    cell, config, traffic = harness.cell_spec(bench, args.workload)
    cache = enable_compile_cache()
    import jax

    visible = jax.devices()
    if visible[0].platform != "tpu" or len(visible) < cell["chips"]:
        log(f"chipbench: cell {args.workload} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(visible)} {visible[0].platform} device(s); refusing to run")
        return 1
    devices = visible[: cell["chips"]]
    peak = cost.peaks(devices[0].device_kind)
    log(f"device: {devices[0].device_kind} x{len(devices)}  compile cache: {cache}")

    line = harness.execute(cell, config, traffic, harness.metrics_for(bench, args.workload, bool(args.trace)),
                           seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                           devices=devices, peak=peak, t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
