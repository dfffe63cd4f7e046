#!/usr/bin/env bash
# Preflight gate: run before ANY end-of-round / milestone commit.
#
# Round 3 shipped a half-finished refactor that broke 1F1B for every model
# because nothing gated the snapshot commit.  This script is the gate: a
# fast pytest subset plus the dry run of every parallelism stage on a
# virtual 8-device CPU mesh.  It says nothing about the chip — that is
# `python chip_smoke.py`.  ~5 minutes; refuse to commit if red.
#
# Usage: scripts/preflight.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== preflight: fast pytest subset =="
python -m pytest \
    tests/test_pipeline.py \
    tests/test_train_step.py \
    tests/test_deferred_init.py \
    tests/test_materialize_jax.py \
    -x -q "$@"

echo "== preflight: parallelism dry run (8 virtual CPU devices) =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== preflight: entry() compile check (default backend) =="
python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn).lower(*args).compile()
print("entry() compiles:", out is not None)
EOF

echo "preflight OK"
