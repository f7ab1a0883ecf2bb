#!/usr/bin/env bash
# Sanitizer gate for the native C++ runtime (the TSAN analog of the
# reference's `go test -race` CI discipline, tests.mk:56).
#
#   scripts/sanitize_native.sh            # thread, then address + undefined
#   scripts/sanitize_native.sh thread     # one sanitizer only
#
# Builds csrc/{cometbft_native,native_stress}.cpp into a standalone
# binary per sanitizer and runs the concurrent stress driver (WAL
# appends; the batch packer's old entry point and, into tables of exactly
# the padded size, the in-place one; the validator set's root over random
# sets of 0 to 10,240 validators; the signature cache's key pass over
# random triples, and its store under concurrent gets and puts and against
# an LRU of its own); any data race / out-of-bounds / UB report fails
# the script via the sanitizer's nonzero exit.
set -euo pipefail
cd "$(dirname "$0")/../cometbft_tpu/native/csrc"

SANS=${1:-"thread address,undefined"}
for SAN in $SANS; do
  out="/tmp/native_stress_${SAN}"
  echo "== build -fsanitize=${SAN} =="
  g++ -O1 -g -std=c++17 -fsanitize="${SAN}" -fno-sanitize-recover=all \
      -fno-omit-frame-pointer \
      cometbft_native.cpp native_stress.cpp -o "${out}" -lpthread
  echo "== run (${SAN}) =="
  "${out}" "/tmp/native_stress_${SAN}.wal"

  blsout="/tmp/bls_stress_${SAN}"
  echo "== build bls -fsanitize=${SAN} =="
  g++ -O1 -g -std=c++17 -fsanitize="${SAN}" -fno-omit-frame-pointer \
      bls12381.cpp bls_stress.cpp -o "${blsout}" -lpthread
  echo "== run bls (${SAN}) =="
  "${blsout}"
done
echo "sanitize_native: ALL CLEAN"
