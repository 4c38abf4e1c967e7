#!/usr/bin/env bash
# Build the native runtime components (dsi_tpu/native/*.cpp) into build/.
# The framework works without them (pure-Python fallbacks); when present
# they accelerate the host-side data plane.
set -eu
REPO=$(cd "$(dirname "$0")/.." && pwd)
SRCS=("$REPO/dsi_tpu/native/kvcodec.cpp" "$REPO/dsi_tpu/native/wcjob.cpp"
      "$REPO/dsi_tpu/native/docread.cpp"
      "$REPO/dsi_tpu/native/mergeruns.cpp")
mkdir -p "$REPO/build"
# Build to a temp name + atomic rename: concurrent workers may trigger the
# lazy first-use build simultaneously, and no process may ever dlopen a
# half-written .so.
TMP="$REPO/build/.libkvcodec.$$.tmp"
# The source hash recorded beside the library is what dsi_tpu/native
# trusts (never file times).  Taken BEFORE compiling, and renamed into
# place after the library: a reader can see an old hash beside a new
# library (it rebuilds), never a new hash beside an old library.
HASH=$(cat "${SRCS[@]}" | sha256sum | cut -d' ' -f1)
g++ -O2 -Wall -shared -fPIC -std=c++17 -o "$TMP" "${SRCS[@]}"
mv -f "$TMP" "$REPO/build/libkvcodec.so"
echo "$HASH" > "$TMP.sha256"
mv -f "$TMP.sha256" "$REPO/build/libkvcodec.so.sha256"
echo "built $REPO/build/libkvcodec.so"
