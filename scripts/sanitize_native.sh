#!/usr/bin/env bash
# Build the native core with ASan+UBSan and run the native-path tests under
# it.  The reference treats sanitizer lanes as first-class CI
# (/root/reference/cmake/Helpers.cmake:287-316, .github/workflows/
# _test_wheel.yaml:49-89); this is the analog for the two tdx_core
# artifacts.  Leak detection is disabled (libtorch_python/numpy hold known
# suppressed leaks — the reference ships LSan.supp for the same reason);
# the lane's oracle is heap-corruption/UB errors in tdx_core frames.

set -euo pipefail
cd "$(dirname "$0")/.."

SAN="-fsanitize=address,undefined -fno-omit-frame-pointer"
LIBDIR=torchdistx_tpu/lib
mkdir -p "$LIBDIR"

g++ -std=c++17 -O1 -g -fPIC -shared $SAN \
  -o "$LIBDIR/libtdx_core.so" src/cc/tdx_core/graph.cc
PY_INCLUDE=$(python -c "import sysconfig; print(sysconfig.get_paths()['include'])")
g++ -std=c++17 -O1 -g -fPIC -shared $SAN -I"$PY_INCLUDE" -Isrc/cc/tdx_core \
  -o "$LIBDIR/_tdx_stack.so" src/cc/tdx_core/stack.cc src/cc/tdx_core/graph.cc

# Stamp the libs with their sources' hash (what _native.py compares) so
# the loaders don't rebuild over the sanitized artifacts.
stamp_libs() {
  local S=src/cc/tdx_core
  cat $S/graph.cc $S/graph.h | sha256sum | cut -d' ' -f1 \
    > "$LIBDIR/libtdx_core.so.srchash"
  cat $S/stack.cc $S/graph.cc $S/graph.h | sha256sum | cut -d' ' -f1 \
    > "$LIBDIR/_tdx_stack.so.srchash"
}
stamp_libs

ASAN_LIB=$(g++ -print-file-name=libasan.so)
UBSAN_LIB=$(g++ -print-file-name=libubsan.so)

LD_PRELOAD="$ASAN_LIB $UBSAN_LIB" \
ASAN_OPTIONS=detect_leaks=0:abort_on_error=1 \
UBSAN_OPTIONS=halt_on_error=1:print_stacktrace=1 \
python -m pytest tests/test_native_tape.py tests/test_fake.py \
  tests/test_deferred_init.py tests/test_data_interception.py -q

# Rebuild un-sanitized so later local runs aren't preloaded-dependent.
g++ -std=c++17 -O2 -fPIC -shared \
  -o "$LIBDIR/libtdx_core.so" src/cc/tdx_core/graph.cc
g++ -std=c++17 -O2 -fPIC -shared -I"$PY_INCLUDE" -Isrc/cc/tdx_core \
  -o "$LIBDIR/_tdx_stack.so" src/cc/tdx_core/stack.cc src/cc/tdx_core/graph.cc
stamp_libs
echo "sanitizer lane: OK"
