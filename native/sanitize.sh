#!/usr/bin/env bash
# ASan/UBSan job for the native libraries (SURVEY §5.3). Builds each with its
# self-test under sanitizers and runs it: the storage engine's full exercise
# (CRUD, compaction, reopen recovery, torn-tail sweep), and the msm epilogue's
# field and point arithmetic in scalar_ops.cpp against known answers from the
# integer reference, on limbs as loose as int32 holds.
set -euo pipefail
cd "$(dirname "$0")"
out=$(mktemp -d)
trap 'rm -rf "$out"' EXIT
san=(g++ -O1 -g -std=c++17 -fsanitize=address,undefined -fno-sanitize-recover=undefined -fno-omit-frame-pointer)
"${san[@]}" -o "$out/engine_selftest" engine_selftest.cpp storage_engine.cpp -lz
"$out/engine_selftest" "$out"
"${san[@]}" -o "$out/scalar_selftest" scalar_selftest.cpp scalar_ops.cpp
"$out/scalar_selftest"
echo "sanitizers clean"
