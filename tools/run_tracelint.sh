#!/bin/sh
# tracelint self-check: lint mxnet_tpu/ for trace-safety hazards, failing
# on error-severity findings. Part of the tier-1 gate (also run from
# tests/test_analysis.py under the `lint` pytest marker).
#
# The per-file mtime cache keeps repeat runs well under the 10 s budget —
# only files that changed since the last run are re-parsed (the
# whole-program project digest folds every file's mtime in, so editing a
# helper re-lints its callers too).
#
# Usage: tools/run_tracelint.sh [extra tracelint args...]
#        tools/run_tracelint.sh --ci
#
# --ci is the findings gate: any NEW warning-or-worse finding fails; the
# findings fingerprinted in tools/tracelint_baseline.json pass. Refresh
# the baseline after a reviewed change with:
#   python -m mxnet_tpu.analysis mxnet_tpu tools/mxtop.py \
#       --baseline tools/tracelint_baseline.json --update-baseline
set -e
cd "$(dirname "$0")/.."
# rewrite a --ci token into the baseline-gate argument set (plain-flag
# word splitting is fine here: tracelint args carry no spaces)
ci=0
rest=""
for a in "$@"; do
    if [ "$a" = "--ci" ]; then
        ci=1
    else
        rest="$rest $a"
    fi
done
# shellcheck disable=SC2086
set -- $rest
if [ "$ci" = 1 ]; then
    set -- --baseline tools/tracelint_baseline.json --fail-on warning "$@"
fi
# --cache uses the CLI's uid-scoped default path under $TMPDIR;
# MXNET_TPU_TRACELINT_CACHE overrides it explicitly
if [ -n "${MXNET_TPU_TRACELINT_CACHE:-}" ]; then
    set -- --cache-file "$MXNET_TPU_TRACELINT_CACHE" "$@"
else
    set -- --cache "$@"
fi
# tools/mxtop.py and tools/prebake_cache.py ride along: the dashboard
# spawns no traces itself but shares the telemetry thread model the
# TPU006 rule audits, and the pre-bake tool drives the serve warmup
# path. The package root covers mxnet_tpu/serve/ AND mxnet_tpu/compiler/
# — the serving scheduler/replica threads are TPU006-clean with zero
# suppressions (tests/test_serve.py asserts it under the lint marker),
# and the whole-graph compiler package is tracelint-clean with zero
# suppressions (tests/test_compiler.py asserts it the same way). The
# linter also lints its own runtime guards: mxnet_tpu/analysis/guard.py
# and lockguard.py sit under the package root, so the lock-order guard
# must itself pass TPU009/TPU010 (its _GRAPH_LOCK is the one lock the
# guard holds while checking, and nothing blocking happens under it).
exec python -m mxnet_tpu.analysis mxnet_tpu tools/mxtop.py \
    tools/prebake_cache.py \
    --fail-on=error "$@"
