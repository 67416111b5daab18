#!/usr/bin/env bash
# Fast CI tier: lint, then the whole suite minus the multi-minute
# `slow`-marked modules.  Pass extra pytest args through, e.g.
# `scripts/ci.sh -k serve`.  The full tier-1 command (ROADMAP.md) remains
# `PYTHONPATH=src python -m pytest -x -q`.
#
# CI checks counts, bit-exactness and retraces; it times nothing.  Speed
# is judged on the chip by `chipbench/` (BENCHMARK.json).  The jitted-
# forward equivalence checks (the whole GNN forward under one jax.jit
# must match the unjitted path for all model kinds) always run: when
# "$@" filters might have deselected them they run again explicitly.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
scripts/lint.sh
python -m pytest -q -m "not slow" "$@"
if [ "$#" -gt 0 ]; then
  python -m pytest -q tests/test_scv_plan.py -k "jit" --no-header
fi
