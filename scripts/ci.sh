#!/usr/bin/env bash
# One CI entrypoint: static analysis first (cheap, catches the perf/race
# hazards pytest can't see), then the tier-1 test suite from ROADMAP.md.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tpulint =="
make lint

echo "== tpulint whole-program JSON artifact =="
# machine-readable findings (schema v4: incl. suppressed + baselined,
# per-finding SHP/SPD witness chains, and per-pass wall times) for CI
# consumers; the baseline gate itself already ran inside `make lint`, so an
# unbaselined SPD/SHP/WPA/TPU finding has already failed the build by now
mkdir -p artifacts
python -m tools.tpulint githubrepostorag_tpu tests \
    --exclude tests/lint_fixtures --baseline tools/tpulint/baseline.json \
    --format json > artifacts/tpulint.json \
    || { echo "tpulint JSON pass failed (exit $?)"; exit 1; }

echo "== tpulint SARIF artifact =="
# SARIF 2.1.0 for code-scanning upload; suppressions ride along as SARIF
# suppression records instead of being dropped
python -m tools.tpulint githubrepostorag_tpu tests \
    --exclude tests/lint_fixtures --baseline tools/tpulint/baseline.json \
    --format sarif > artifacts/tpulint.sarif \
    || { echo "tpulint SARIF pass failed (exit $?)"; exit 1; }

echo "== tpulint artifact schema gate =="
# pin the v4 JSON shape (witness field, pass_seconds stats) and the SARIF
# ruleIndex invariants the code-scanning upload depends on
python scripts/check_tpulint_schema.py artifacts/tpulint.json artifacts/tpulint.sarif

echo "== /debug/traces schema =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python scripts/check_traces_schema.py

echo "== /debug/slo + /debug/fleet schema =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python scripts/check_slo_schema.py

echo "== /debug/timeline + /debug/hbm schema =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python scripts/check_timeline_schema.py

echo "== tier-1 tests =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly
