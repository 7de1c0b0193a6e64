#!/usr/bin/env bash
# CI entry point (the reference's .travis.yml + scripts/travis/travis_script.sh
# role): one command that runs every tier the suite ships.
#
#   scripts/ci.sh            # lint + native (incl. sanitizers) + pytest + bench smoke
#   scripts/ci.sh quick      # lint + native unit + pytest (no sanitizers/bench)
#
# Exit non-zero on the first failing tier. CPU-only safe: jax tests run on a
# virtual device mesh (tests/conftest.py); the bench smoke prints its JSON
# line from whatever device exists.

set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-full}"

echo "== lint =="
python scripts/lint.py

echo "== native build + unit tier =="
make -C cpp
make -C cpp test

if [ "$MODE" = "full" ]; then
  echo "== native sanitizer tiers (ASan+UBSan, TSan) =="
  make -C cpp test_asan
  make -C cpp test_tsan
fi

echo "== python suite =="
python -m pytest tests/ -q -x

if [ "$MODE" = "full" ]; then
  echo "== bench smoke (one JSON line) =="
  # CI asserts the bench MACHINERY (one parseable line): off the chip the
  # device tiers do not run and the record says "not measured"
  python bench.py
fi

echo "CI OK"
