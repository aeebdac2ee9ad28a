#!/bin/sh
# Build the native libraries (vector search + WordPiece tokenizer) ahead
# of first use, through the loader that keys them on source, flags and
# host CPU (generativeaiexamples_tpu/utils/native_build.py).
set -e
cd "$(dirname "$0")/.."
python -c "
from generativeaiexamples_tpu.utils.native_build import load_native_library
for name in ('vecsearch', 'wordpiece'):
    load_native_library(name)
print('built native/build/libvecsearch-*.so and libwordpiece-*.so')
"
