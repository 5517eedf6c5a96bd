#!/usr/bin/env bash
# dtpu-lint wrapper — THE lint command, from anywhere in the repo:
#
#   ./scripts/lint.sh                 # whole tree, default rules
#   ./scripts/lint.sh --rules event-schema
#   ./scripts/lint.sh --write-baseline
#
# Runs the repo-aware static analyzer (distributed_tpu/analysis/,
# docs/ANALYSIS.md) over the package: jax-free-at-import, writer-thread
# collective discipline, trace purity, event-schema agreement, thread
# hygiene. Exit status is dtpu-lint's: 0 clean, 1 findings, 2 usage.
# scripts/tier1.sh runs this same gate before pytest — a lint regression
# fails in seconds, not somewhere inside the suite.
#
# JAX_PLATFORMS=cpu: the linter never initializes jax, but importing the
# package's CLI module pulls the top-level __init__; pin CPU so a box
# with an accelerator plugin doesn't pay device discovery for a lint.
set -o pipefail
cd "$(dirname "$0")/.." || exit 1
exec env JAX_PLATFORMS=cpu python -m distributed_tpu.analysis.cli "$@"
