"""Tile GEMM kernels and DTD/PTG algorithm builders.

The compute path for the headline tiled-GEMM benchmark (the reference's
harness: tests/dsl/dtd/dtd_test_simple_gemm.c, gflops = 2MNK/1e9/t at
:1143-1161). Tile bodies are jittable functions dispatched by the device
layer; XLA maps the dots onto the MXU, so the kernels stay simple and large
(tile sizes should be multiples of 128).

``insert_gemm_tasks`` builds the classic tile-DAG (one RW chain per C tile
over k) through the DTD frontend; ``gemm_flops`` mirrors the reference's
FLOP accounting.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ..data.matrix import TiledMatrix
from ..dsl.dtd import AFFINITY, DTDTaskpool, READ, RW


def tile_gemm(c, a, b):
    """C += A @ B on one tile triple; f32 accumulation even for bf16 inputs
    (MXU-native mixed precision)."""
    import jax.numpy as jnp
    from .pallas_kernels import dot_precision
    return c + jnp.dot(a, b, precision=dot_precision(),
                       preferred_element_type=jnp.float32).astype(c.dtype)


def tile_gemm_chain(c, a_stack, b_stack):
    """Fused k-chain: C += sum_k A[k] @ B[k] in one dispatch.

    The task-batching analogue (ref: parsec_gpu_task_collect_batch,
    device_gpu.c:2229): a whole k-chain of compatible GEMM tasks collapses
    into one device call. Backed by the Pallas kernel
    (:func:`parsec_tpu.ops.pallas_kernels.gemm_chain`) which keeps C in
    VMEM across all k steps.
    """
    from .pallas_kernels import gemm_chain
    return gemm_chain(c, a_stack, b_stack)


def insert_gemm_tasks(tp: DTDTaskpool, A: TiledMatrix, B: TiledMatrix,
                      C: TiledMatrix, alpha: float = 1.0,
                      batch_k: bool = False, batch: bool = False) -> int:
    """Insert the tile-GEMM DAG: C[m,n] += alpha * sum_k A[m,k] B[k,n].

    With ``batch_k`` the whole k-chain per C tile becomes ONE task using the
    fused scan body — fewer, bigger device dispatches (the TPU-first answer
    to per-tile task overhead). ``batch`` additionally marks the class as
    grouped on the device whatever its manager observes: up to
    device_tpu_batch_max pending tasks leave as one flat program (their
    operands side by side in, one output per task out; nothing is stacked).
    Without it the manager groups only a class it sees the host pace.
    Returns the number of inserted tasks.
    """
    mt, nt, kt = C.mt, C.nt, A.nt
    assert A.mt == mt and B.nt == nt and B.mt == kt
    n0 = tp.inserted

    if batch_k:
        gemm_k = _gemm_chain_body(kt)
        for m in range(mt):
            for n in range(nt):
                args = [(tp.tile_of(C, m, n), RW | AFFINITY)]
                args += [(tp.tile_of(A, m, k), READ) for k in range(kt)]
                args += [(tp.tile_of(B, k, n), READ) for k in range(kt)]
                tp.insert_task(gemm_k, *args, name="GEMM_K", batch=batch)
    else:
        for m in range(mt):
            for n in range(nt):
                tc = tp.tile_of(C, m, n)
                for k in range(kt):
                    tp.insert_task(tile_gemm, (tc, RW | AFFINITY),
                                   (tp.tile_of(A, m, k), READ),
                                   (tp.tile_of(B, k, n), READ),
                                   name="GEMM", batch=batch)
    return tp.inserted - n0


@functools.lru_cache(maxsize=None)
def _gemm_chain_body(kt: int):
    """One body function object per k-chain length: jit traces/compiles once
    per (kt, tile shape) across all taskpools and benchmark repetitions.

    Short chains unroll the dots directly (no stacking copies: XLA chains
    the MXU calls on the accumulator); long chains stack once and ride the
    Pallas VMEM-resident kernel."""
    def gemm_k(c, *abs_):
        import jax.numpy as jnp
        from .pallas_kernels import dot_precision
        if kt <= 16:
            for k in range(kt):
                c = c + jnp.dot(abs_[k], abs_[kt + k], precision=dot_precision(),
                                preferred_element_type=jnp.float32
                                ).astype(c.dtype)
            return c
        a_stack = jnp.stack(abs_[:kt])
        b_stack = jnp.stack(abs_[kt:])
        return tile_gemm_chain(c, a_stack, b_stack)
    return gemm_k


def gemm_flops(M: int, N: int, K: int) -> float:
    """2·M·N·K (ref: dtd_test_simple_gemm.c gflops computation)."""
    return 2.0 * M * N * K


def gemm_reference(a, b, c0, solves: int = 1):
    """The plain reference of the tiled GEMM configurations (DTD and PTG):
    ``c0 + solves * a @ b`` in float32 ``jax.numpy`` at the highest matmul
    precision; no tiles, no runtime. ``solves`` is how often the graph ran
    over an accumulating C."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        prod = jnp.dot(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
    return jnp.asarray(c0, jnp.float32) + jnp.float32(solves) * prod
