#!/bin/sh
# Chunked full-suite runner: one pytest process per test file.
#
# Why: a monolithic 285-test process trips an XLA:CPU compiler segfault
# on the pipeline train-step compile after ~150 prior compilations
# (r05, jax 0.9; crash is in-process-state dependent — every file is
# green standalone). conftest.py also clears jax caches between modules,
# which mitigates the monolithic run; this runner is the isolation-
# guaranteed form. The persistent compile cache (<checkout>/.jax_cache,
# or JAX_COMPILATION_CACHE_DIR) keeps the chunked wall time close to the
# monolithic one. CPU only (tests/conftest.py names the platform).
#
# Usage: sh tools/run_suite.sh [extra pytest args]
set -u
cd "$(dirname "$0")/.."
PY="${PYTHON:-/opt/venv/bin/python}"
[ -x "$PY" ] || PY=python
fail=0
for f in tests/test_*.py; do
  echo "== $f"
  "$PY" -m pytest "$f" -q --no-header "$@" || fail=1
done
exit $fail
