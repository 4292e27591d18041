#!/bin/sh
# Build the native host library (see native/acg_host.cpp) for the host it
# runs on.  Written to a temporary name and renamed, so processes that
# build at the same time never load a half-written file.
set -e
cd "$(dirname "$0")"
tmp="libacg_host.so.tmp.$$"
g++ -O3 -std=c++17 -shared -fPIC -pthread -o "$tmp" acg_host.cpp
mv -f "$tmp" libacg_host.so
echo "built $(pwd)/libacg_host.so"
