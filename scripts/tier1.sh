#!/usr/bin/env bash
# Canonical tier-1 test runner — THE command from ROADMAP.md "Tier-1
# verify", wrapped once so builders, CI, and humans all invoke the same
# thing instead of each re-typing (and drifting from) the incantation.
#
#   ./scripts/tier1.sh            # run from the repo root
#
# Behavior, matching the ROADMAP contract exactly:
#   - XLA:CPU only (JAX_PLATFORMS=cpu; conftest.py simulates 8 devices)
#   - quiet, non-slow tests, collection errors don't abort the run
#   - hard timeout (870 s + 10 s kill grace): a hung suite still reports
#   - DOTS_PASSED=<n> printed at the end: the per-test tally survives a
#     timeout kill (pytest's own summary would not), and the incremental
#     ledger .pytest_progress.txt names every completed test either way
#   - --durations=15 prints the slowest tests so a PR that bloats the
#     suite names its own culprits
#   - TIER1_WALL_SECONDS=<n> printed at the end; a PASSING run that takes
#     longer than 850 s FAILS anyway (exit 3): the hard timeout is 870 s,
#     and a suite that creeps past 850 s leaves the next PR no room to
#     add a single test — fail loud here, not mysteriously there
#   - exit status is pytest's (or 124 on timeout, 3 on budget), NOT tee's

set -o pipefail
cd "$(dirname "$0")/.." || exit 1

# TIER1_PRECISION_SMOKE=1: pre-push fast path for mixed-precision work —
# runs ONLY tests/test_precision.py (~50 s vs the full ~800 s suite) so a
# policy/step-body/strategy-cast change can iterate without paying for
# tier-1 each round. NOT a tier-1 substitute: the full suite still gates.
if [ -n "${TIER1_PRECISION_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_precision.py -q \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_SERVE_SMOKE=1: same idea for the serving runtime — runs ONLY
# tests/test_serving.py (+ the bench serve smoke) so engine/scheduler/
# paged-cache changes iterate fast. NOT a tier-1 substitute.
if [ -n "${TIER1_SERVE_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
        "tests/test_bench.py::test_bench_serve_smoke" -q \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_QUANT_SMOKE=1: same idea for the raw-speed tier — runs ONLY the
# int8-quantization + fused-optimizer tests and their bench smokes
# (~60 s) so quant/kernel changes iterate fast. NOT a tier-1 substitute.
if [ -n "${TIER1_QUANT_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_quant.py \
        tests/test_fused_update.py \
        "tests/test_bench.py::test_bench_quant_smoke" \
        "tests/test_bench.py::test_bench_fused_update_smoke" \
        -q --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_AUTOSHARD_SMOKE=1: same idea for the auto-shard planner — runs
# ONLY tests/test_autoshard.py (+ the bench autoshard smoke, ~35 s) so
# planner/cost-model/strategy-seam changes iterate fast. The measured-
# shortlist path stays @slow (run it with -m slow when touching the
# measure machinery). NOT a tier-1 substitute.
if [ -n "${TIER1_AUTOSHARD_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_autoshard.py \
        "tests/test_bench.py::test_bench_autoshard_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_ELASTIC_SMOKE=1: same idea for the elastic-gang subsystem — runs
# the elastic policy/supervisor/cluster/pipeline units plus the N->N'
# sharded-restore tests (~15 s). The real-gang shrink/grow fault matrix
# stays @slow (run it explicitly with -m slow when touching the gang
# paths). NOT a tier-1 substitute.
if [ -n "${TIER1_ELASTIC_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py \
        "tests/test_sharded_checkpoint.py::TestElasticRestore" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_DATA_SMOKE=1: same idea for the streaming-input subsystem — runs
# the record-shard + pipeline + file-pipeline tests and the bench input
# smoke (~20 s) so records/decode-pool/shuffle changes iterate fast. The
# decode-bound W-curve itself runs via `python bench.py input`. NOT a
# tier-1 substitute.
if [ -n "${TIER1_DATA_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_records.py \
        tests/test_pipeline.py tests/test_file_pipeline.py \
        "tests/test_bench.py::test_bench_input_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_FLEET_SMOKE=1: same idea for the serving fleet — runs the
# router/autoscaler/handoff/fleet tests, the serving runtime they build
# on, and the bench fleet smoke (~30 s) so fleet/router/replica changes
# iterate fast. The replica-count x fault matrix stays @slow (run it
# with -m slow when touching the kill/requeue paths). NOT a tier-1
# substitute.
if [ -n "${TIER1_FLEET_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py \
        tests/test_serving.py \
        "tests/test_bench.py::test_bench_fleet_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_RL_SMOKE=1: same idea for online post-training — runs the rl
# loop tests, the serving runtime they ride on (logprob capture, RNG
# determinism, the update_weights hot-swap), and the bench rl smoke
# (~60 s) so PostTrainer/engine-swap changes iterate fast. NOT a tier-1
# substitute.
if [ -n "${TIER1_RL_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_rl.py \
        tests/test_serving.py \
        "tests/test_bench.py::test_bench_rl_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_RECOVERY_SMOKE=1: same idea for the diskless-recovery tier —
# runs the buddy-store/tier-selection/in-process-recovery tests, the
# sharded-checkpoint CRC+async satellites they build on, and the bench
# recovery schema smoke (~20 s) so redundancy/restore-path changes
# iterate fast. The real supervised-gang fault matrix stays @slow (run
# it with -m slow when touching the gang/invalidation paths; `python
# bench.py recovery` drives the measured artifact). NOT a tier-1
# substitute.
if [ -n "${TIER1_RECOVERY_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_redundancy.py \
        tests/test_sharded_checkpoint.py \
        "tests/test_bench.py::test_bench_recovery_schema_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_ANALYSIS_SMOKE=1: same idea for the static analyzer — runs the
# dtpu-lint rule/runner tests plus the full-tree lint gate (~10 s) so
# rule/schema/manifest changes iterate fast. NOT a tier-1 substitute.
if [ -n "${TIER1_ANALYSIS_SMOKE:-}" ]; then
    env JAX_PLATFORMS=cpu python -m distributed_tpu.analysis.cli || exit 1
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_OBS_SMOKE=1: same idea for the observability runtime — runs the
# registry/span/flight/aggregation/exporter/CLI tests plus the bench obs
# schema smoke (~25 s) so obs/telemetry-surface changes iterate fast.
# The real supervised straggler gang runs via `python bench.py obs`
# (BENCH_obs.json). NOT a tier-1 substitute.
if [ -n "${TIER1_OBS_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py \
        "tests/test_bench.py::test_bench_obs_schema_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_PREFIX_SMOKE=1: same idea for the serving memory-economy stack —
# runs the prefix-cache / int8-KV / speculative-decode tests plus the
# bench prefix smoke (~45 s) so kv_cache/engine/handoff changes iterate
# fast. The full gated measurement runs via `python bench.py prefix`
# (BENCH_prefix.json). NOT a tier-1 substitute.
if [ -n "${TIER1_PREFIX_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_prefix.py \
        "tests/test_bench.py::test_bench_prefix_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_SPEC_SMOKE=1: same idea for the speculation-that-pays stack —
# runs the draft-distillation / adaptive-spec_k tests, the cross-replica
# prefix-gossip tests (index, pack/adopt, transport stamp, fleet TTFT,
# the real-process shm payload — no slow filter, ~60 s total), and the
# bench spec smoke so distill/gossip/engine-spec changes iterate fast.
# The full gated measurement runs via `python bench.py spec`
# (BENCH_spec.json). NOT a tier-1 substitute.
if [ -n "${TIER1_SPEC_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_distill.py \
        tests/test_gossip.py \
        "tests/test_bench.py::test_bench_spec_smoke" \
        -q --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_SERVICE_SMOKE=1: same idea for the multi-process serving
# service — runs the framing/transport/quota units, the single-worker
# real-process end-to-end, the router/fleet tests it builds on, and the
# bench service schema smoke (~45 s; worker spin-up is ~3 s/process) so
# serve_service changes iterate fast. The multi-process matrix (shm
# handoff, kill-a-replica, pool mismatch, live autoscale) stays @slow
# (run it with -m slow when touching worker/service paths; `python
# bench.py fleet --clock wall` drives the measured BENCH_service.json).
# NOT a tier-1 substitute.
if [ -n "${TIER1_SERVICE_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_serve_service.py \
        tests/test_fleet.py \
        "tests/test_bench.py::test_bench_service_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_KERNEL_SMOKE=1: same idea for the raw-speed round-2 tier — runs
# the fused paged-attention kernel parity matrix + engine token-exact
# tests, the FSDP gather-overlap tests, and the bench overlap2 smoke
# (~60 s) so decode-kernel/overlap changes iterate fast. The measured
# artifacts come from `python bench.py overlap2 decode_kernel`
# (BENCH_overlap2.json / BENCH_decode_kernel.json; docs/PERF.md "Overlap
# round 2" / "Fused paged attention"). NOT a tier-1 substitute.
if [ -n "${TIER1_KERNEL_SMOKE:-}" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/test_paged_kernel.py \
        tests/test_fsdp_overlap.py \
        "tests/test_bench.py::test_bench_overlap2_smoke" \
        -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

# TIER1_PIPELINE_SMOKE=1: same idea for the pipeline third axis — runs
# the PipelinedBlocks schedule/parity tests and the planner's DP x TP x
# PP rows in-tier (~45 s), then the bench pipeline smoke WITHOUT the
# slow filter (it is @slow: ~8 shard_map compiles) so schedule/planner/
# stacked-serving changes iterate fast. The measured artifact comes from
# `python bench.py pipeline` (BENCH_pipeline.json). NOT a tier-1
# substitute.
if [ -n "${TIER1_PIPELINE_SMOKE:-}" ]; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_pipeline_parallel.py \
        tests/test_autoshard.py -q -m 'not slow' \
        --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly \
        || exit 1
    exec env JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_bench.py::test_bench_pipeline_smoke" \
        -q --durations=5 -p no:cacheprovider -p no:xdist -p no:randomly
fi

LOG="${TIER1_LOG:-/tmp/_t1.log}"
BUDGET="${TIER1_BUDGET_SECONDS:-850}"
rm -f "$LOG"

# Lint gate BEFORE pytest: the repo-aware invariants (jax-free imports,
# writer-thread discipline, trace purity, event schema, thread hygiene —
# docs/ANALYSIS.md) fail in ~2 s instead of surfacing as a runtime
# regression 13 minutes in. Exit 4 distinguishes a lint failure from
# pytest's own statuses (124 timeout / 3 budget).
echo "dtpu-lint: checking tree invariants (scripts/lint.sh)"
if ! env JAX_PLATFORMS=cpu python -m distributed_tpu.analysis.cli; then
    echo "tier-1: dtpu-lint gate failed (fix the findings, allowlist at" \
         "the source line, or baseline with --write-baseline)" >&2
    exit 4
fi

start=$(date +%s)
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors --durations=15 \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
elapsed=$(( $(date +%s) - start ))
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"
echo "TIER1_WALL_SECONDS=$elapsed"
if [ "$rc" -eq 0 ] && [ "$elapsed" -gt "$BUDGET" ]; then
    echo "tier-1 wall time ${elapsed}s exceeds the ${BUDGET}s budget" \
         "(hard timeout is 870s; trim or @slow-mark tests)" >&2
    rc=3
fi
exit "$rc"
