#!/usr/bin/env bash
# Canonical tier-1 test runner: the lint gate, then the one pytest command
# of ROADMAP.md's "Tier-1 verify" (the driver's), so builders, CI and
# humans all invoke the same thing. Run from anywhere:
#
#   ./scripts/tier1.sh
#
# - XLA:CPU only (JAX_PLATFORMS=cpu; conftest.py simulates 8 devices),
#   non-slow tests, six xdist workers that each take whole files.
# - dtpu-lint first (docs/ANALYSIS.md): a broken tree invariant fails in
#   about 2 s, with exit 4, not somewhere inside the suite.
# - DOTS_PASSED=<n> at the end: the tally survives a timeout kill, which
#   pytest's own summary would not.
# - Exit status is pytest's, or 124 when the 1470 s limit cut the run.
#
# To iterate on one subsystem, run its file: pytest tests/test_precision.py

set -o pipefail
cd "$(dirname "$0")/.." || exit 1

echo "dtpu-lint: checking tree invariants (scripts/lint.sh)"
if ! env JAX_PLATFORMS=cpu python -m distributed_tpu.analysis.cli; then
    echo "tier-1: dtpu-lint gate failed (fix the findings, allowlist at" \
         "the source line, or baseline with --write-baseline)" >&2
    exit 4
fi

log=$(mktemp)
trap 'rm -f "$log"' EXIT
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" | tr -cd . | wc -c)"
exit "$rc"
