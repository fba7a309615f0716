#!/bin/bash
# ASan+UBSan gate for the native engines (VERDICT r4 #6 / SURVEY §5).
# Builds the crypto + consensus TUs with sanitizers and runs:
#   1. the MSM/pairing differential harness (check_msm.cpp, beside this script)
#   2. a time-boxed decoder fuzzer (structured + random mutations)
#   3. a time-boxed consensus-engine fuzzer (hostile shards, live engines)
#   4. a time-boxed LSM corruption fuzzer
#   5. the Python storage test slice against a SANITIZED libllsm.so —
#      the real multi-threaded engine (WAL pipeline, flusher, compactor)
#      under ASan/UBSan, driven by the same tests CI runs
#   6. the Python native-engine slices against SANITIZED builds of
#      libconsensus_rt.so and libbls381.so (loader override envs
#      LACHAIN_CONSENSUS_LIB / LACHAIN_BLS_LIB) — the consensus router
#      and BLS backend under the same pytest drivers
# Any sanitizer report aborts with a non-zero exit (no recover).
# The sibling tsan.sh runs the ThreadSanitizer leg over the same three
# engines (make sanitize-tsan).
set -euo pipefail
cd "$(dirname "$0")"
FUZZ_SECONDS="${FUZZ_SECONDS:-20}"
SAN="-fsanitize=address,undefined -fno-sanitize-recover=all -fno-omit-frame-pointer"
CXXFLAGS="-O1 -g -march=native -std=c++17 -pthread $SAN"
BUILD=./.sanitize-build
mkdir -p "$BUILD"

echo "== building sanitized harnesses =="
g++ $CXXFLAGS -o "$BUILD/check_msm" check_msm.cpp
g++ $CXXFLAGS -o "$BUILD/fuzz_decoders" fuzz_decoders.cpp
g++ $CXXFLAGS -o "$BUILD/fuzz_consensus" fuzz_consensus.cpp
g++ $CXXFLAGS -o "$BUILD/fuzz_lsm" fuzz_lsm.cpp
g++ $CXXFLAGS -fPIC -shared -o "$BUILD/libllsm_san.so" \
    ../../lachain_tpu/storage/native/lsm.cpp
g++ $CXXFLAGS -fPIC -shared -o "$BUILD/libconsensus_rt_san.so" \
    ../../lachain_tpu/consensus/native/consensus_rt.cpp
g++ $CXXFLAGS -fPIC -shared -o "$BUILD/libbls381_san.so" \
    ../../lachain_tpu/crypto/native/bls381.cpp \
    ../../lachain_tpu/crypto/native/secp256k1.cpp \
    ../../lachain_tpu/crypto/native/trie.cpp

echo "== differential (sanitized) =="
"$BUILD/check_msm"
echo "== fuzz decoders (${FUZZ_SECONDS}s) =="
"$BUILD/fuzz_decoders" "$FUZZ_SECONDS"
echo "== fuzz consensus (${FUZZ_SECONDS}s) =="
"$BUILD/fuzz_consensus" "$FUZZ_SECONDS"
echo "== fuzz lsm corruption (${FUZZ_SECONDS}s) =="
"$BUILD/fuzz_lsm" "$FUZZ_SECONDS"

echo "== storage slice over sanitized libllsm.so =="
# python itself is not ASan-instrumented: the runtime must be preloaded,
# and leak checking disabled (the interpreter's arenas never free).
# LACHAIN_LSM_LIB makes lsm.py load the sanitized build verbatim (no
# mtime-rebuild). Slow campaigns excluded: the gate stays time-boxed.
ASAN_RT="$(gcc -print-file-name=libasan.so)"
UBSAN_RT="$(gcc -print-file-name=libubsan.so)"
ABS_BUILD="$(cd "$BUILD" && pwd)"
(cd ../.. && \
    LD_PRELOAD="$ASAN_RT $UBSAN_RT" \
    ASAN_OPTIONS="detect_leaks=0,abort_on_error=1,verify_asan_link_order=0" \
    LACHAIN_LSM_LIB="$ABS_BUILD/libllsm_san.so" \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/test_lsm.py -q -m "not slow" -p no:cacheprovider)

echo "== native-engine slices over sanitized libconsensus_rt.so + libbls381.so =="
# same preload discipline; the consensus router (pipelined-era driver,
# flood protocols, trace rings), the BLS backend (threaded batch muls,
# grand multi-pairing) and the trie's native walks (node table, loader
# callback, threaded level hashing) under the pytest slices that
# exercise them
(cd ../.. && \
    LD_PRELOAD="$ASAN_RT $UBSAN_RT" \
    ASAN_OPTIONS="detect_leaks=0,abort_on_error=1,verify_asan_link_order=0" \
    LACHAIN_CONSENSUS_LIB="$ABS_BUILD/libconsensus_rt_san.so" \
    LACHAIN_BLS_LIB="$ABS_BUILD/libbls381_san.so" \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/test_native_rt.py tests/test_native_backend.py \
        tests/test_trie_native.py tests/test_trie_parallel.py \
        -q -m "not slow" -k "not share_block" -p no:cacheprovider)
echo "SANITIZE GREEN"
