#!/usr/bin/env bash
# Build the native core into torchdistx_tpu/lib/ (where _native.py looks).
#
# Usage: scripts/build_native.sh [--sanitizers "asan;ubsan"]
set -euo pipefail
cd "$(dirname "$0")/.."

SANS=""
if [[ "${1:-}" == "--sanitizers" ]]; then
  SANS="$2"
fi

mkdir -p build torchdistx_tpu/lib
cmake -S src/cc -B build -G Ninja \
  -DCMAKE_BUILD_TYPE=Release \
  -DTDX_SANITIZERS="${SANS}" >/dev/null
cmake --build build >/dev/null
cp build/libtdx_core.so torchdistx_tpu/lib/
# The source-hash stamp _native.py compares before trusting a .so.
cat src/cc/tdx_core/graph.cc src/cc/tdx_core/graph.h | sha256sum \
  | cut -d' ' -f1 > torchdistx_tpu/lib/libtdx_core.so.srchash
echo "built torchdistx_tpu/lib/libtdx_core.so"
