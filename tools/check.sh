#!/usr/bin/env bash
# Pre-PR gate: byte-compile the tree, run kukelint (strict baseline mode —
# stale suppressions fail too), verify the guarded-by contract is not
# stale, and run mypy on the strictly-annotated modules when mypy is
# installed. Exits non-zero on any new finding.
#
#   ./tools/check.sh               # static gates (seconds, no jax import)
#   ./tools/check.sh --sanitize    # + the kukesan fixture/stress tests
#                                  #   under KUKEON_SANITIZE=1 (needs jax)
#
# The full dynamic gate is the whole tier-1 suite under KUKEON_SANITIZE=1
# (see README "Concurrency model"); --sanitize is the fast slice of it.
#
# This is the same set of checks tier-1 runs via
# tests/test_static_analysis.py, packaged for the editing loop.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "check.sh: compileall"
python -m compileall -q kukeon_tpu tests

echo "check.sh: kukelint (python -m kukeon_tpu.analysis)"
python -m kukeon_tpu.analysis --strict-baseline

echo "check.sh: guarded-by contract drift"
python - <<'EOF'
from kukeon_tpu.analysis import (
    default_contracts_path, guarded_contracts, load_sources,
    render_contracts,
)
import os, sys
import kukeon_tpu

root = os.path.dirname(os.path.abspath(kukeon_tpu.__file__))
want = render_contracts(guarded_contracts(load_sources(root), root))
with open(default_contracts_path(), encoding="utf-8") as f:
    have = f.read()
if have != want:
    sys.exit("analysis/guarded_by.json is stale — regenerate with "
             "`python -m kukeon_tpu.analysis --write-contracts`")
print("analysis/guarded_by.json matches the tree")
EOF

if python -c "import mypy" >/dev/null 2>&1; then
    echo "check.sh: mypy (strict modules)"
    python -m mypy kukeon_tpu/obs/registry.py kukeon_tpu/serving/kv_pages.py \
        kukeon_tpu/gateway/router.py kukeon_tpu/sanitize
else
    echo "check.sh: mypy not installed — skipping the strict-module check"
fi

if [[ "${1:-}" == "--sanitize" ]]; then
    echo "check.sh: kukesan fixture/stress tests (KUKEON_SANITIZE=1)"
    JAX_PLATFORMS=cpu KUKEON_SANITIZE=1 python -m pytest \
        tests/test_concurrency_sanitizer.py -q -p no:cacheprovider
fi

echo "check.sh: all gates green"
