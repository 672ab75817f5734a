#!/usr/bin/env bash
# Sanitizer-hardened native pipeline gate (make native-sanitize).
#
# Rebuilds native/celestia_native.cpp under ThreadSanitizer and under
# AddressSanitizer+UBSan, then re-runs the thread-scaling byte-identity
# tests against each instrumented build: the multi-threaded overlapped
# extend->roots pipeline must produce byte-identical output AND be free
# of data races / memory errors the byte comparison alone cannot see.
#
# Environment-gated like the Go golden-vector cross-check: when the
# toolchain cannot build the sanitizer runtime this prints a loud
# SKIP(...) line and exits 0 — it never silently passes.  The moment the
# toolchain supports -fsanitize=..., the same invocation becomes a hard
# gate (any sanitizer report or test failure exits non-zero).
#
# Usage: tools/native_sanitize.sh [tsan|asan|all]   (default: all)

set -u

REPO_ROOT="$(cd "$(dirname "$0")/.." && pwd)"
SRC="$REPO_ROOT/native/celestia_native.cpp"
CXX="${CXX:-g++}"
PY="${PY:-python}"

# the thread-scaling byte-identity suite: pooled native pipeline at
# nthreads 1/2/4 pins identical extension, roots, data root and repair,
# and the blob commitment pass at 1 and 8 threads
TESTS=(
  "tests/test_leopard_codec.py::test_threaded_host_pipeline_byte_identical"
  "tests/test_leopard_codec.py::test_golden_parity_vectors_pin_leopard_bytes"
  "tests/test_host_pipeline.py::test_threaded_extend_repair_dah_smoke"
  "tests/test_golden_vectors.py::test_native_blob_commitments_batch"
)

# sanitizer-instrumented code needs frame pointers for usable reports;
# everything else matches the production build flags
COMMON_FLAGS=(-O2 -g -fno-omit-frame-pointer -march=native -shared -fPIC -pthread)

probe() { # probe <flags...>: can the toolchain link this sanitizer at all?
  local tmp
  tmp="$(mktemp -d)"
  echo 'int main(){return 0;}' > "$tmp/p.cpp"
  if "$CXX" "$@" "$tmp/p.cpp" -o "$tmp/p" >/dev/null 2>&1; then
    rm -rf "$tmp"; return 0
  fi
  rm -rf "$tmp"; return 1
}

run_leg() { # run_leg <name> <sanitize-flags> <runtime-lib> <env...>
  local name="$1" sanflag="$2" runtime="$3"; shift 3
  if ! command -v "$CXX" >/dev/null 2>&1 || ! probe "$sanflag"; then
    echo "SKIP(native-sanitize/$name): $CXX cannot build $sanflag — toolchain gate, NOT a pass"
    return 0
  fi
  local so="$REPO_ROOT/native/celestia_native.$name.so"
  echo "== native-sanitize/$name: building $so"
  if ! "$CXX" "${COMMON_FLAGS[@]}" "$sanflag" "$SRC" -o "$so"; then
    echo "FAIL(native-sanitize/$name): instrumented build failed" >&2
    return 1
  fi
  # ASan/TSan runtimes must own the process from startup: the .so is
  # dlopen'd into an uninstrumented python, so the runtime is preloaded
  local preload
  preload="$("$CXX" -print-file-name="$runtime")"
  if [ ! -e "$preload" ]; then
    echo "SKIP(native-sanitize/$name): $runtime not shipped with $CXX — toolchain gate, NOT a pass"
    return 0
  fi
  echo "== native-sanitize/$name: re-running thread-scaling byte-identity tests"
  if LD_PRELOAD="$preload" \
     CELESTIA_TPU_NATIVE_SO="$so" \
     JAX_PLATFORMS=cpu \
     "$@" "$PY" -m pytest "${TESTS[@]}" -q -p no:cacheprovider; then
    echo "PASS(native-sanitize/$name)"
    return 0
  fi
  echo "FAIL(native-sanitize/$name): sanitizer report or byte-identity failure" >&2
  return 1
}

cd "$REPO_ROOT"
mode="${1:-all}"
rc=0
case "$mode" in
  tsan|all)
    # exitcode=66 makes any detected race fail the pytest process even
    # when the race is outside an assertion's line of sight
    run_leg tsan -fsanitize=thread libtsan.so \
      env TSAN_OPTIONS="exitcode=66 halt_on_error=0 history_size=4" || rc=1
    ;;&
  asan|all)
    # CPython itself "leaks" interned objects at exit: leak checking off,
    # every other ASan/UBSan check fatal
    run_leg asan -fsanitize=address,undefined libasan.so \
      env ASAN_OPTIONS="detect_leaks=0 abort_on_error=0" \
          UBSAN_OPTIONS="halt_on_error=1 print_stacktrace=1" || rc=1
    ;;&
  tsan|asan|all) ;;
  *)
    echo "usage: tools/native_sanitize.sh [tsan|asan|all]" >&2
    exit 2
    ;;
esac
exit $rc
