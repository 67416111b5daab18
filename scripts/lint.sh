#!/usr/bin/env bash
# Lint gate: generic style (ruff, when the image has it) + the
# repo-specific scvlint rules (tools/scvlint — np-in-traced-body, magic
# kernel constants, nondiff_argnums over plan leaves, jax-shim pin
# hygiene, fori_loop unroll).  New violations fail the run; pre-existing
# ones live in tools/scvlint/baseline.txt.
#
# Run directly (`scripts/lint.sh`) or via scripts/ci.sh, which runs it
# before the pytest tier.
set -euo pipefail
cd "$(dirname "$0")/.."

if command -v ruff >/dev/null 2>&1; then
  ruff check src tools benchmarks
else
  # The container does not bake ruff in (and installing deps is out of
  # scope for CI); the repo-specific rules below still run.
  echo "lint.sh: ruff not installed — skipping generic style pass"
fi

python -m tools.scvlint src/ tools/ benchmarks/
