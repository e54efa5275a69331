#!/usr/bin/env bash
# Build the native host-ops shared library (native/hivemall_native.cpp) into
# hivemall_tpu/native/libhivemall_native.so. Pure C ABI, consumed via ctypes.
#
# --sanitize=MODE builds an instrumented variant next to the optimized one:
#   --sanitize=address,undefined -> libhivemall_native.asan.so  (ASan+UBSan)
#   --sanitize=thread            -> libhivemall_native.tsan.so  (TSan)
# Suffixed outputs so a sanitizer or -O0 build can never be mistaken for the
# optimized library; hivemall_tpu.native selects a variant at load via
# HIVEMALL_TPU_NATIVE_SANITIZE= (see scripts/test.sh gate 11). Sanitizer
# runtimes are NOT linked into a -shared .so — run with
# LD_PRELOAD="$(g++ -print-file-name=libasan.so) $(g++ -print-file-name=libubsan.so)".
#
# --if-stale: rebuild only when the .so is missing, its build stamp (compiler
# version + flags + target CPU + source sha256) mismatches, or — plain
# variant only — it is unloadable on THIS host (the PR 11 GLIBCXX-mismatch
# pathology) or predates the newest required symbol. The stamp is what makes
# flag changes count as staleness: before it, `--if-stale` only compared
# mtimes, so a stray -O0 or sanitizer build of the same source looked
# "fresh" forever. The cpu line is what makes a copied tree safe: the plain
# build is -march=native, and a library built on an AVX-512 host dies with
# SIGILL inside hm_pack_block on a host without it (seen on the TPU VM,
# PR 21) — hivemall_tpu.native checks the same line before it loads.
# Exits 0 WITHOUT building when no C++ compiler is present —
# hivemall_tpu.native then reports unavailability loudly (warnings +
# load_error()) and the native bench gates skip with the reason in-artifact.
# A present compiler that fails to build is a hard error: scripts/test.sh
# runs this un-guarded so a broken toolchain fails tier-1 instead of
# shipping a stale library.
set -euo pipefail
cd "$(dirname "$0")/.."

SRC=native/hivemall_native.cpp
# bumped with the plan ABI (ops/scatter.py PLAN_ABI_VERSION): a loadable
# .so missing this symbol predates the current ABI and must be rebuilt
# (the loader also calls it at runtime and refuses on version mismatch)
PROBE_SYMBOL=hm_plan_abi_version

IF_STALE=0
SANITIZE=""
for arg in "$@"; do
  case "$arg" in
    --if-stale) IF_STALE=1 ;;
    --sanitize=*) SANITIZE="${arg#--sanitize=}" ;;
    *) echo "build_native.sh: unknown argument: $arg" >&2; exit 2 ;;
  esac
done

case "$SANITIZE" in
  "")
    SO=hivemall_tpu/native/libhivemall_native.so
    FLAGS="-O3 -march=native"
    PROBE_LOAD=1 ;;  # the optimized .so must CDLL cleanly standalone
  address|undefined|address,undefined|undefined,address)
    SO=hivemall_tpu/native/libhivemall_native.asan.so
    FLAGS="-O1 -g -fno-omit-frame-pointer -fsanitize=address,undefined -fno-sanitize-recover=all"
    PROBE_LOAD=0 ;;  # needs LD_PRELOADed runtimes; CDLL probe would lie
  thread)
    SO=hivemall_tpu/native/libhivemall_native.tsan.so
    FLAGS="-O1 -g -fno-omit-frame-pointer -fsanitize=thread"
    PROBE_LOAD=0 ;;
  *)
    echo "build_native.sh: unknown --sanitize mode: $SANITIZE" \
         "(expected address,undefined | thread)" >&2
    exit 2 ;;
esac
STAMP="$SO.stamp"

stamp_content() {
  # compiler identity + exact flags + target CPU + source hash: any drift in
  # any of the four means the binary on disk is not the binary these inputs
  # produce HERE. The cpu id (machine + sha256 of the kernel's ISA-flags
  # line) is recomputed by hivemall_tpu/native/__init__.py::_host_cpu_id —
  # keep the two derivations identical.
  echo "compiler: $(g++ --version 2>/dev/null | head -n 1)"
  echo "flags: $FLAGS -fPIC -shared -std=c++17"
  echo "cpu: $(uname -m) $(grep -m1 -E '^(flags|Features)' /proc/cpuinfo 2>/dev/null | sha256sum | cut -d' ' -f1)"
  echo "source: $(sha256sum "$SRC" | cut -d' ' -f1)"
}

if [[ "$IF_STALE" == 1 ]]; then
  fresh=0
  if [[ -f "$SO" && -f "$STAMP" ]] && command -v g++ >/dev/null 2>&1 \
      && [[ "$(stamp_content)" == "$(cat "$STAMP")" ]]; then
    if [[ "$PROBE_LOAD" == 1 ]]; then
      if python - "$SO" "$PROBE_SYMBOL" <<'EOF'
import ctypes, sys
try:
    lib = ctypes.CDLL(sys.argv[1])
except OSError:
    sys.exit(1)  # present but unloadable on this host: stale
sys.exit(0 if hasattr(lib, sys.argv[2]) else 1)
EOF
      then fresh=1; fi
    else
      fresh=1  # stamp match is the whole check for sanitizer variants
    fi
  fi
  if [[ "$fresh" == 1 ]]; then
    if [[ "$PROBE_LOAD" == 1 ]]; then
      echo "native: $SO is fresh (stamp matches, loads, exports $PROBE_SYMBOL)"
    else
      echo "native: $SO is fresh (stamp matches)"
    fi
    exit 0
  fi
  if ! command -v g++ >/dev/null 2>&1; then
    echo "native: $SO is stale/missing and no g++ is available;" \
         "skipping build — hivemall_tpu.native will report the" \
         "load failure loudly and native gates skip with the reason" >&2
    exit 0
  fi
fi

mkdir -p hivemall_tpu/native
# shellcheck disable=SC2086  # FLAGS is a deliberate word-split flag list
# compile beside the target and rename over it: concurrent first-use builds
# (several processes importing hivemall_tpu.native at once) each publish a
# complete library, never a half-written one
g++ $FLAGS -fPIC -shared -std=c++17 \
    "$SRC" \
    -o "$SO.tmp.$$"
mv -f "$SO.tmp.$$" "$SO"
stamp_content > "$STAMP.tmp.$$"
mv -f "$STAMP.tmp.$$" "$STAMP"
echo "built $SO (stamp: $STAMP)"
