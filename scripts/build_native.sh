#!/bin/sh
# Build the native shared libraries:
#   * lightgbm_tpu/lib/liblgbt_native.so — fast text parsing/binning
#     (src/native/loader.cpp) + the dependency-free C INFERENCE ABI
#     (src/native/c_api.cpp).  Picked up automatically by
#     lightgbm_tpu/native.py; everything falls back to NumPy when absent.
#   * lightgbm_tpu/lib/liblgbt_train.so — the full LGBM_* TRAINING ABI
#     (src/native/c_api_train.cpp), which embeds CPython and delegates to
#     lightgbm_tpu.capi (the JAX compute path lives there).  Requires
#     libpython at build and run time; skipped with a notice when
#     python3-config is unavailable.
set -e
cd "$(dirname "$0")/.."
mkdir -p lightgbm_tpu/lib
# no -march=native: lib/ is gitignored but copied with the checkout as it
# stands on disk, so a library built here must run on another CPU
g++ -O3 -std=c++17 -shared -fPIC \
    -o lightgbm_tpu/lib/liblgbt_native.so \
    src/native/loader.cpp src/native/c_api.cpp
echo "built lightgbm_tpu/lib/liblgbt_native.so"

# Derive embed flags from the RUNNING interpreter (sysconfig), not from
# whichever python3-config is first on PATH — a mismatch would link a
# different libpython than the one that later loads this library.
PY=${PYTHON:-python3}
if command -v "$PY" >/dev/null 2>&1; then
    PY_CFLAGS="$("$PY" -c 'import sysconfig; print("-I"+sysconfig.get_path("include"))')"
    PY_LDFLAGS="$("$PY" -c 'import sysconfig as s; v=s.get_config_var; print("-L"+(v("LIBDIR") or "")+" -lpython"+v("LDVERSION"))')"
    g++ -O3 -std=c++17 -shared -fPIC \
        -o lightgbm_tpu/lib/liblgbt_train.so \
        src/native/c_api_train.cpp ${PY_CFLAGS} ${PY_LDFLAGS}
    echo "built lightgbm_tpu/lib/liblgbt_train.so"
else
    echo "python3 not found: skipping liblgbt_train.so"
fi
