#!/bin/bash
# Sanitizer lane for the native runtime (reference: SURVEY §5 — the
# reference CI runs its Rust core under miri/sanitizer-class checks; the
# C/C++ here gets the ASAN/UBSAN + TSAN equivalents).
#
#   ./scripts/sanitize_native.sh          # ASAN+UBSAN over the native tests
#   ./scripts/sanitize_native.sh tsan     # TSAN over the threaded executor
#
# The extensions are rebuilt with the chosen sanitizer into a scratch
# build dir, injected via PATHWAY_NATIVE_BUILD_DIR, and the native test
# batteries run with the runtime library preloaded. Leak checking is off:
# CPython interns/arenas are not leaks.

set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-asan}"
PYINC=$(python -c "import sysconfig; print(sysconfig.get_paths()['include'])")
EXT=$(python -c "import sysconfig; print(sysconfig.get_config_var('EXT_SUFFIX'))")
BUILD="/tmp/pathway_native_${MODE}"
mkdir -p "$BUILD"

# graceful skip when the toolchain lacks sanitizer support (ci_lanes.sh
# runs this lane everywhere; a container without libasan must not fail
# the pipeline, it must say so and move on)
PROBE_SAN="-fsanitize=address,undefined"
[ "$MODE" = "tsan" ] && PROBE_SAN="-fsanitize=thread"
if ! echo 'int main(){return 0;}' | \
     g++ -x c++ $PROBE_SAN -o "$BUILD/san_probe" - 2>/dev/null; then
    echo "== sanitizer lane SKIPPED: g++ lacks $PROBE_SAN support =="
    exit 0
fi
rm -f "$BUILD/san_probe"

if [ "$MODE" = "tsan" ]; then
    SAN="-fsanitize=thread"
    RUNTIME=$(gcc -print-file-name=libtsan.so)
    # allocator_may_return_null: same story as the ASan lane below — the
    # differential fuzz asks CPython for astronomically large ints, and
    # CPython's own malloc of that size must return NULL (-> clean
    # MemoryError) instead of tripping the sanitizer's allocation cap.
    # The suppressions file silences fd-interceptor noise from the
    # UNINSTRUMENTED stdlib _socket module (see its comments); the
    # instrumented native worker threads run unsuppressed.
    export TSAN_OPTIONS="report_bugs=1 halt_on_error=1 allocator_may_return_null=1 suppressions=$PWD/scripts/tsan_suppressions.txt"
else
    SAN="-fsanitize=address,undefined -fno-sanitize-recover=undefined"
    RUNTIME=$(gcc -print-file-name=libasan.so)
    # allocator_may_return_null: the differential fuzz asks CPython for
    # astronomically large ints (2**70 ** 2**70); CPython's own malloc
    # of that size must return NULL (-> clean MemoryError) instead of
    # tripping ASan's hard allocation cap
    export ASAN_OPTIONS="detect_leaks=0 abort_on_error=1 allocator_may_return_null=1"
    export UBSAN_OPTIONS="halt_on_error=1 print_stacktrace=1"
fi

echo "== building native extensions with $MODE =="
g++ -O1 -g -std=c++20 -shared -fPIC -pthread $SAN \
    -I"$PYINC" -o "$BUILD/pwexec$EXT" native/exec.cpp
gcc -O1 -g -shared -fPIC $SAN \
    -I"$PYINC" -o "$BUILD/fastpath$EXT" native/fastpath.c
g++ -O1 -g -std=c++20 -shared -fPIC $SAN \
    -o "$BUILD/libpathway_native.so" native/bm25.cpp native/hnsw.cpp

echo "== running native batteries under $MODE =="
# PATHWAY_THREADS=4 exercises the GIL-released shard threads (the TSAN
# target); the batteries cover groupby/join/minmax incl. fallbacks, plus
# the exchange NATIVE surface (shard_partition_nb parity, nb/deltas wire
# codecs, nb_concat, procgroup framing). The real-fork 2-rank exchange
# tests stay OUT of the sanitized process: they exercise no additional
# native code, and the LD_PRELOADed ASan runtime cannot intercept C++
# exceptions thrown inside the prebuilt (uninstrumented) jaxlib those
# pipelines import — a known false abort, not a finding.
LD_PRELOAD="$RUNTIME" \
PATHWAY_NATIVE_BUILD_DIR="$BUILD" \
PATHWAY_THREADS=4 \
JAX_PLATFORMS=cpu \
python -m pytest tests/test_native_groupby.py tests/test_native_join.py \
    tests/test_native_minmax.py tests/test_native.py \
    tests/test_native_chain.py tests/test_native_join_chain.py \
    tests/test_join_battery.py \
    tests/test_native_exchange.py \
    tests/test_consistency_fuzz.py tests/test_native_stress.py \
    -m 'not slow' -k 'not two_rank and not smoke_2rank' -x -q

echo "== $MODE lane clean =="
