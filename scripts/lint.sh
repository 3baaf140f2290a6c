#!/usr/bin/env bash
# graftlint convenience runner: one rule (or all) against one path.
#
#   scripts/lint.sh                      # all rules, whole package
#   scripts/lint.sh R1                   # one rule, whole package
#   scripts/lint.sh R1 deeplearning4j_tpu/nn   # one rule, one tree
#   scripts/lint.sh all tests/test_x.py  # all rules, one file
#   scripts/lint.sh all deeplearning4j_tpu --diff HEAD   # pre-commit:
#       analyse the whole tree (project rules need it) but only REPORT
#       findings on lines changed vs the ref — extra args pass through
#
# Runs WITHOUT the baseline (every finding prints) — the gating CI run
# with the baseline applied lives in scripts/tier1.sh. Like tier1.sh it
# pins the CPU backend: lint must never take a chip another process holds.
set -o pipefail
cd "$(dirname "$0")/.."
RULE="${1:-}"
PATH_ARG="${2:-deeplearning4j_tpu}"
shift $(( $# > 2 ? 2 : $# ))
ARGS=(--no-baseline)
if [ -n "$RULE" ] && [ "$RULE" != "all" ]; then
  ARGS+=(--rules "$RULE")
fi
exec env JAX_PLATFORMS=cpu \
  python -m deeplearning4j_tpu lint "${ARGS[@]}" "$@" "$PATH_ARG"
