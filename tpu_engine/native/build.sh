#!/usr/bin/env bash
# The build of libtpucore.so: plain g++ over the tracked sources. Run by
# tpu_engine.core.native whenever the library is missing or was built
# from other sources (it records the sources' hash beside the library).
set -euo pipefail
cd "$(dirname "$0")"
out="${1:-libtpucore.so}"
g++ -std=c++17 -O3 -Wall -Wextra -fPIC -shared -pthread core_api.cc -o "$out"
echo "built $out"
