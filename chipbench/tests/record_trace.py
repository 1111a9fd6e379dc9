#!/usr/bin/env python3
"""Record the small trace ``test_reduce_trace.py`` checks the reduction on.

Run on the chip (it is how ``tests/data/small.xplane.pb`` was made):
12 ``jit_tile_gemm`` programs on 256 x 256 f32 tiles issued inside an
``insert`` span with 2 ms of host sleep after every fourth, then a ``wait``
span around ``block_until_ready``, then 3 ``jit_tile_syrk`` programs inside
a ``refill`` span. Writes ``<out>/small.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import time


def main(out):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tile_gemm(c, a, b):
        return c + jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def tile_syrk(a, c):
        return c - jnp.dot(a, a.T, precision=jax.lax.Precision.HIGHEST)

    a = jnp.ones((256, 256), jnp.float32)
    c = tile_syrk(a, tile_gemm(a, a, a)).block_until_ready()
    tmp = os.path.join(out, "trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("insert"):
        for i in range(12):
            c = tile_gemm(c, a, a)
            if i % 4 == 3:
                time.sleep(0.002)
    with jax.profiler.TraceAnnotation("wait"):
        c.block_until_ready()
    with jax.profiler.TraceAnnotation("refill"):
        for _ in range(3):
            c = tile_syrk(a, c)
        c.block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(found[0], os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print("recorded", os.path.getsize(os.path.join(out, "small.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
