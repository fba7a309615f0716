#!/bin/bash
# ThreadSanitizer gate for ALL THREE native engines (ISSUE 12 tentpole).
#
# The ASan/UBSan gate (sanitize.sh) proves memory safety; this one proves
# the CONCURRENCY the repo leans on since PRs 8-11: the LSM's WAL
# writer/flusher/compactor threads, the subtrie merkle workers driving
# lt_keccak256_batch, the threaded lt_g1_mul_batch / lt_pairing_check_mt
# fan-outs, and the pipelined-era driver over the consensus engine.
#
# TSan-instrumented builds of libllsm.so, libconsensus_rt.so and
# libbls381.so are loaded into a NON-instrumented CPython via the loader
# override envs (LACHAIN_LSM_LIB / LACHAIN_CONSENSUS_LIB /
# LACHAIN_BLS_LIB) with libtsan preloaded, then driven by the real
# multi-threaded test slices: storage, trie, exec, pipeline (non-slow) —
# the same selections `make test-storage` etc. run in CI. Races in
# UNinstrumented code (CPython, JAX) are invisible by construction, which
# is exactly the scoping we want: the gate watches the C++ we own.
#
# Suppression policy (tsan.supp): ONLY interpreter/runtime-side noise —
# an entry must name an uninstrumented-runtime frame and carry a comment
# explaining why it is noise. Engine frames are NEVER suppressed; a race
# in lsm.cpp / consensus_rt.cpp / bls381.cpp gets fixed, not silenced.
#
# Any report fails the gate: TSan exits 66 at process exit when races
# were recorded (halt_on_error=0 lets one run surface every report), and
# we additionally fail if any report file landed in the build dir.
set -euo pipefail
cd "$(dirname "$0")"
REPO="$(cd ../.. && pwd)"
BUILD=./.tsan-build
mkdir -p "$BUILD"
rm -f "$BUILD"/tsan_report*

SAN="-fsanitize=thread -fno-omit-frame-pointer"
# -O1: keep stacks readable; -pthread everywhere (TSan needs it anyway)
CXXFLAGS="-O1 -g -march=native -std=c++17 -pthread $SAN -fPIC -shared"

echo "== building TSan-instrumented engines =="
g++ $CXXFLAGS -o "$BUILD/libllsm_tsan.so" \
    "$REPO/lachain_tpu/storage/native/lsm.cpp"
g++ $CXXFLAGS -o "$BUILD/libconsensus_rt_tsan.so" \
    "$REPO/lachain_tpu/consensus/native/consensus_rt.cpp"
g++ $CXXFLAGS -o "$BUILD/libbls381_tsan.so" \
    "$REPO/lachain_tpu/crypto/native/bls381.cpp" \
    "$REPO/lachain_tpu/crypto/native/secp256k1.cpp" \
    "$REPO/lachain_tpu/crypto/native/trie.cpp"

TSAN_RT="$(gcc -print-file-name=libtsan.so)"
ABS_BUILD="$(cd "$BUILD" && pwd)"

echo "== storage/trie/exec/pipeline slices over TSan engines =="
# One combined pytest invocation: TSan's per-run startup (shadow mapping)
# is expensive on the one-core box, and the slices share fixtures. The
# marker expression is the union of make test-storage/-trie/-exec/-pipeline.
(cd "$REPO" && \
    LD_PRELOAD="$TSAN_RT" \
    TSAN_OPTIONS="exitcode=66,halt_on_error=0,report_thread_leaks=0,suppressions=$ABS_BUILD/../tsan.supp,log_path=$ABS_BUILD/tsan_report" \
    LACHAIN_LSM_LIB="$ABS_BUILD/libllsm_tsan.so" \
    LACHAIN_CONSENSUS_LIB="$ABS_BUILD/libconsensus_rt_tsan.so" \
    LACHAIN_BLS_LIB="$ABS_BUILD/libbls381_tsan.so" \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q \
        -m "(storage or trie or exec or pipeline) and not slow" \
        -p no:cacheprovider)

if compgen -G "$BUILD/tsan_report*" > /dev/null; then
    echo "== TSAN REPORTS =="
    cat "$BUILD"/tsan_report*
    echo "TSAN RED: unsuppressed reports above"
    exit 1
fi
echo "TSAN GREEN"
