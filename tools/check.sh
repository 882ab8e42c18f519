#!/usr/bin/env bash
# tools/check.sh — the one tier-1 static-analysis entry point.
#
#   tools/check.sh            yblint (all eleven passes, repo-clean vs the
#                             committed baseline, incl. the metric-name
#                             lint and the kernel-contracts pass) + the
#                             kernel-manifest drift check (committed
#                             JSON vs source fingerprints; seconds, no
#                             jax import) + the yblint framework suite,
#                             which carries the lock-rank acyclicity
#                             gate and the baseline/justification gates
#   tools/check.sh --changed  same, but yblint reports only files changed
#                             vs HEAD (index still whole-program), and
#                             the manifest is only REGENERATED (verified
#                             byte-identical; ~10s of device-free
#                             eval_shape/lower under JAX_PLATFORMS=cpu)
#                             when the change set touches the kernel
#                             surface: yugabyte_tpu/ops/,
#                             yugabyte_tpu/parallel/, or
#                             storage/offload_policy.py. The drift gate
#                             itself always runs and always reads the
#                             committed JSON.
#   tools/check.sh --sanitize the ybsan lane: re-run the concurrency-
#                             heavy tier-1 suites with the race
#                             sanitizer armed (YBSAN=1); any race
#                             report not justified in
#                             tools/analysis/baseline.txt exits 1
#   tools/check.sh --full     all of the above (including --sanitize),
#                             the manifest regeneration verify, then
#                             the full tier-1 pytest suite
#                             (tests/ -m 'not slow')
set -euo pipefail
cd "$(dirname "$0")/.."

YBLINT_ARGS=()
RUN_FULL=0
RUN_SANITIZE=0
CHANGED=0
for a in "$@"; do
    case "$a" in
        --changed)  YBLINT_ARGS+=(--changed); CHANGED=1 ;;
        --sanitize) RUN_SANITIZE=1 ;;
        --full)     RUN_FULL=1; RUN_SANITIZE=1 ;;
        *) echo "usage: tools/check.sh [--changed] [--sanitize] [--full]" >&2
           exit 2 ;;
    esac
done

echo "== yblint (all passes) =="
python -m tools.analysis "${YBLINT_ARGS[@]+"${YBLINT_ARGS[@]}"}"

echo "== no offload_calibration references (PR 16 deleted the file) =="
# the static calibration loader is gone — the bucket-health board
# (storage/bucket_health.py) is the only device-vs-native authority;
# any source reference means a dispatch site regressed to the dead API
if grep -rn --include='*.py' --include='*.sh' --include='*.md' \
        -l 'offload_calibration' \
        yugabyte_tpu/ tools/ tests/ README.md 2>/dev/null \
        | grep -v '^tools/check.sh$'; then
    echo "check.sh: FAIL — offload_calibration is deleted; route through" \
         "the bucket-health board (storage/bucket_health.py)" >&2
    exit 1
fi

echo "== kernel-manifest drift check (committed JSON) =="
python -m tools.analysis.kernel_manifest --check

REGEN=0
if [ "$RUN_FULL" = 1 ]; then
    REGEN=1
elif [ "$CHANGED" = 1 ]; then
    # regenerate only when the change set touches the kernel compile
    # surface; everything else keeps the --changed path seconds-fast.
    # (buffered into a variable: `git | grep -q` would SIGPIPE git on
    # the first match, which pipefail turns into a false condition)
    CHANGED_FILES=$( { git diff --name-only HEAD --; \
                       git ls-files --others --exclude-standard; } || true )
    if grep -qE '^yugabyte_tpu/(ops|parallel)/|^yugabyte_tpu/storage/offload_policy\.py$' \
            <<<"$CHANGED_FILES"; then
        REGEN=1
    fi
fi
if [ "$REGEN" = 1 ]; then
    echo "== kernel-manifest regeneration verify (device-free) =="
    JAX_PLATFORMS=cpu python -m tools.analysis.kernel_manifest --verify
fi

echo "== yblint framework + lock-rank acyclicity + baseline gates =="
python -m pytest tests/test_yblint.py -q

echo "== 8-host-device mesh smoke lane (compaction pool differential) =="
# mesh regressions must surface in tier-1, not only on TPU rounds: the
# pool differential test runs on an 8-virtual-device CPU mesh and
# asserts pooled outputs are byte-identical to sequential runs
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
JAX_PLATFORMS=cpu python -m pytest \
    tests/test_compaction_pool.py::test_pool_differential_byte_identical \
    -q -p no:cacheprovider

if [ "$RUN_SANITIZE" = 1 ]; then
    echo "== ybsan race-sanitizer lane (concurrency-heavy suites, armed) =="
    # The session gate in tests/conftest.py flips the exit code to 1 on
    # any race report whose fingerprint is not baseline-justified.
    # test_ybsan.py is excluded by design: its positive fixtures are
    # races by construction (its own skipif also enforces this). Two
    # invocations: the cluster-heavy batch runs apart from the rest so
    # leftover daemon threads don't compound the armed slowdown into
    # election-timing flakes on a 1-core runner.
    JAX_PLATFORMS=cpu YBSAN=1 python -m pytest \
        tests/test_bucket_health.py tests/test_compaction_pool.py \
        tests/test_multi_raft_and_compression.py tests/test_consensus.py \
        tests/test_txn_coordinator.py tests/test_sync_interleavings.py \
        tests/test_observability.py tests/test_telemetry.py \
        -q -m 'not slow' -p no:cacheprovider -p no:randomly
    # xcluster runs FIRST: its two-cluster election timing is the most
    # sensitive to accumulated daemon threads under armed overhead
    JAX_PLATFORMS=cpu YBSAN=1 python -m pytest \
        tests/test_xcluster.py tests/test_mini_cluster.py \
        tests/test_tablet_split.py tests/test_replica_movement.py \
        -q -m 'not slow' -p no:cacheprovider -p no:randomly
fi

if [ "$RUN_FULL" = 1 ]; then
    echo "== tier-1 =="
    python -m pytest tests/ -m 'not slow' -q
fi
echo "check.sh: OK"
