#!/usr/bin/env python3
"""Record tests/benchmarks/data/scopes.toy_hybrid.xplane.pb ON THE CHIP: the
profiler's trace of one chunk tick and the width-1 ticks behind it, of a toy
hybrid (one gated-delta layer, one full-attention layer, the kernels' own
head sizes, two heads) served by a `ContinuousGenerator`.

    chiprun -- python3 tools/record_scopes_fixture.py

writes the profiler's file under chiprun_out/scopes_fixture/plugins/ and,
beside it, `scopes.toy_hybrid.xplane.pb`: the same bytes less the planes no
reader opens (`/host:metadata`, the programs' HLO, is three quarters of the
file). Copy that over the fixture after a change to `STEP_PARTS` or to a
step's scopes (tests/benchmarks/test_benchmark_xplane_scopes.py reads the
parts and the program names off it). Needs a TPU: the Pallas kernels' names
and the compiler's `flops` / `bytes_accessed` are what a CPU trace lacks.
"""

import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))   # lib.xplane_scopes
OUT = os.path.join(ROOT, "chiprun_out", "scopes_fixture")
TOY = dict(layer_types=("linear_attention", "full_attention"), d_model=256,
           n_heads=2, head_dim=128, d_ff=512, lin_heads=2, lin_key_dim=96,
           lin_value_dim=192, vocab=512, max_seq=512)
KEPT_PLANES = ("/device:TPU:", "/host:CPU")


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def strip(space):
    """An XSpace's bytes without the planes (field 1) whose name (field 2)
    starts with none of KEPT_PLANES; its other fields (host names, errors:
    all length-delimited) as they were."""
    from lib.xplane_scopes import fields

    out = bytearray()
    for number, _, value in fields(memoryview(space)):
        name = (bytes(next(v for n, _, v in fields(value) if n == 2)).decode()
                if number == 1 else None)
        if name is None or name.startswith(KEPT_PLANES):
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return bytes(out)


def main():
    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    if jax.default_backend() != "tpu":
        print("the fixture is a TPU's trace; this backend is "
              f"{jax.default_backend()}", file=sys.stderr)
        return 1
    _ensure_builtin_models_imported()
    gen = ContinuousGenerator(create_model("olmo_hybrid", **TOY), n_slots=4,
                              dtype="bfloat16", kv_block_size=16,
                              prefill_chunk=64, prefix_sharing=False)
    prompt = [(7 * k) % 500 + 1 for k in range(40)]
    try:
        # Both widths compile outside the trace.
        gen.generate([prompt], max_new_tokens=3)
        shutil.rmtree(OUT, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(OUT, profiler_options=options)
        try:
            tokens = gen.generate([prompt[::-1]], max_new_tokens=3)
        finally:
            jax.profiler.stop_trace()
    finally:
        gen.stop()
    recorded, = glob.glob(os.path.join(OUT, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    with open(recorded, "rb") as f:
        kept = strip(f.read())
    with open(os.path.join(OUT, "scopes.toy_hybrid.xplane.pb"), "wb") as f:
        f.write(kept)
    print({"tokens": tokens, "stats": gen.stats()["mixed"],
           "bytes": len(kept)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
