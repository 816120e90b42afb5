#!/usr/bin/env bash
# CI smoke entry point: full test suite + fast machine-readable benchmarks.
#
# Usage: scripts/smoke.sh [output.json]
#   output.json — where the benchmark JSON lands (default: results/smoke_bench.json)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
OUT="${1:-results/smoke_bench.json}"
mkdir -p "$(dirname "$OUT")"

python -m pytest -q
python scripts/check_docs.py
python scripts/check_deprecated.py
python -m benchmarks.run --fast --only table2,noise --json "$OUT"

echo "smoke OK -> $OUT"
