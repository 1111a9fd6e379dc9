#!/usr/bin/env python3
"""chipbench/raw_xla.py — what raw XLA does with the same problem, no runtime.

    python3 chipbench/raw_xla.py --workload <cell> [--n N] [--reps K]

One dense ``jnp.dot`` (GEMM cells) or ``jnp.linalg.cholesky`` (POTRF cells)
at the cell's N (or ``--n``, where the dense problem does not fit the chip:
a dense f32 Cholesky needs its input and its output, 2 x 4 N^2 bytes), same
dtype and precision, operands made on the device from the seed, timed with
``block_until_ready`` after one warm-up call. Run once for ``PERF.md``'s
``vs_raw`` column; it is not a cell and prints no contract line.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from chipbench.run import Cell
    cell = Cell(args.workload, rehearsal=False)
    n = args.n or cell.traffic["n"]

    import jax
    import jax.numpy as jnp
    from parsec_tpu.utils import compile_cache
    compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"raw_xla: JAX found no accelerator ({dev.platform!r})",
              file=sys.stderr)
        return 3
    key = jax.random.PRNGKey(args.seed)
    precision = jax.lax.Precision.HIGHEST \
        if cell.config["precision"] == "highest" else None

    if cell.config["graph"] == "gemm":
        ka, kb = jax.random.split(key)
        operands = (jax.random.normal(ka, (n, n), jnp.float32),
                    jax.random.normal(kb, (n, n), jnp.float32))
        op = jax.jit(lambda a, b: jnp.dot(a, b, precision=precision))
    else:
        @jax.jit
        def spd(k):
            # S + 3I, S symmetric Gaussian with off-diagonal variance 1/n:
            # the spectrum of reference/potrf.py's matrix
            g = jax.random.normal(k, (n, n), jnp.float32) / jnp.sqrt(2.0 * n)
            return g + g.T + 3.0 * jnp.eye(n, dtype=jnp.float32)
        operands = (spd(key),)

        @jax.jit
        def op(a):
            with jax.default_matmul_precision("highest"):
                return jnp.linalg.cholesky(a)
    flops = cell.graph.flops({**cell.traffic, "n": n})

    t = time.perf_counter()
    op(*operands).block_until_ready()
    first_s = time.perf_counter() - t
    secs = []
    for _ in range(args.reps):
        t = time.perf_counter()
        op(*operands).block_until_ready()
        secs.append(time.perf_counter() - t)
    med = statistics.median(secs)
    print("RAW " + json.dumps({
        "cell": cell.name, "graph": cell.config["graph"], "n": n,
        "dtype": cell.config["dtype"], "precision": cell.config["precision"],
        "device_kind": dev.device_kind, "first_call_s": first_s,
        "seconds": secs, "median_s": med, "tflops": flops / med / 1e12,
        "memory_peak_bytes": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
