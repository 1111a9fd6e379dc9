"""Pallas TPU kernels for the hot tile operations.

Where the reference hand-writes CUDA kernels for its GPU task bodies
(tests/runtime/cuda/*.cu), this module supplies Pallas kernels for the TPU
chore path:

* :func:`gemm_chain` — the fused k-chain  C += Σ_k A[k]·B[k]  as ONE kernel:
  the C block stays in VMEM across the whole k grid (the task-batching
  analogue at kernel level), each step is an MXU dot; Pallas double-buffers
  the A/B block streams from HBM automatically.
* :func:`matmul` — classic blocked matmul with a (M/bm, N/bn, K/bk) grid and
  VMEM accumulation, for large single dots.
* :func:`stencil1d` — fused 3-point stencil with halo columns (one VPU pass,
  no intermediate materialization).
* :func:`flash_attention` — blockwise attention with the online-softmax
  accumulation fused into one kernel: scores, running max/sum and the
  weighted-V accumulation never leave VMEM (the HBM-bandwidth win that
  motivates flash attention), grid over (batch·heads, query blocks), k/v
  resident per head. Positional offsets make it usable on rotated ring
  blocks (`parallel/ring_attention.py`) and sequence-sharded shards.

On an accelerator backend every kernel compiles through Mosaic or raises —
there is no quiet XLA route. Interpreter mode is used only when the backend
is ``cpu`` (tests, rehearsal). :func:`verify_lowering` is the compile gate.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ..utils import mca

mca.register("tile_dot_precision", "highest",
             "MXU pass count for float32 tile dots: 'default' (fast bf16 "
             "passes), 'high' (3-pass), 'highest' (6-pass, dgemm-accuracy "
             "f32). bf16 inputs are always single-pass native.", type=str)


def dot_precision():
    """The lax.Precision for f32 tile dots. On TPU the MXU multiplies in
    bf16; 'highest' recovers f32 accuracy via 6 passes — the semantics a
    dgemm-parity runtime must default to. bf16 tiles ignore this (native)."""
    import jax
    name = str(mca.get("tile_dot_precision", "highest")).lower()
    return {"default": jax.lax.Precision.DEFAULT,
            "high": jax.lax.Precision.HIGH,
            "highest": jax.lax.Precision.HIGHEST}.get(
                name, jax.lax.Precision.HIGHEST)


def _interpret() -> bool:
    """Interpreter mode only on the CPU backend; anywhere else a kernel is
    a real Mosaic compile."""
    import jax
    return jax.default_backend() == "cpu"


def _mxu_precision(dtype):
    """``dot_precision()`` for f32 operands; None for narrower dtypes, which
    the MXU multiplies natively in one pass — Mosaic refuses an fp32
    contract precision on bf16 operands ("Bad lhs type")."""
    import jax.numpy as jnp
    return dot_precision() if jnp.dtype(dtype) == jnp.float32 else None


def verify_lowering(shapes=((256, 256, 256), ), kt: int = 4,
                    dtypes=("float32",)) -> dict:
    """Compile-only gate: lower and compile every kernel for the CURRENT
    backend (real Mosaic on an accelerator, the interpreter on cpu) at each
    (m, k, n) of ``shapes`` and each dtype, without executing. Raises with
    the compiler's own messages if any kernel fails; returns
    {kernel: 'ok'} otherwise."""
    import jax
    results = {}
    interp = _interpret()
    for dtype in dtypes:
        prec = _mxu_precision(dtype)

        def sds(*shape):
            return jax.ShapeDtypeStruct(shape, dtype)

        for m, k, n in shapes:
            checks = {
                f"gemm_chain[{kt}x{m}x{k}x{n},{dtype}]": (
                    _gemm_chain_call(kt, m, k, n, dtype, interp, prec),
                    (sds(m, n), sds(kt, m, k), sds(kt, k, n))),
                f"matmul[{m}x{k}x{n},{dtype}]": (
                    _matmul_call(m, n, k, min(m, 256), min(n, 256),
                                 min(k, 256), dtype, interp, prec),
                    (sds(m, k), sds(k, n))),
                f"stencil1d[8x{n},{dtype}]": (
                    _stencil_call(8, n, (0.25, 0.5, 0.25), dtype, interp),
                    (sds(8, n), sds(8, n), sds(8, n))),
                f"flash_attention[2x{m}x128,{dtype}]": (
                    _flash_attn_call(2, m, m, 128, min(m, 128), min(m, 128),
                                     True, 0.088388, 0, 0, dtype, interp,
                                     None),
                    (sds(2, m, 128), sds(2, m, 128), sds(2, m, 128))),
            }
            for name, (call, args) in checks.items():
                try:
                    call.lower(*args).compile()
                    results[name] = "ok"
                except Exception as e:  # noqa: BLE001 - collected, re-raised
                    results[name] = f"{type(e).__name__}: {e}"
    errors = {k: v for k, v in results.items() if v != "ok"}
    if errors:
        raise RuntimeError(f"pallas lowering FAILED: {errors}")
    return results


# ---------------------------------------------------------------------------
# fused GEMM k-chain
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _gemm_chain_call(kt: int, ts_m: int, ts_k: int, ts_n: int, dtype: str,
                     interpret: bool, prec=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(c_ref, a_ref, b_ref, out_ref):
        k = pl.program_id(0)

        @pl.when(k == 0)
        def _():
            out_ref[:] = c_ref[:]

        out_ref[:] += jnp.dot(a_ref[0], b_ref[0], precision=prec,
                              preferred_element_type=jnp.float32
                              ).astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        grid=(kt,),
        in_specs=[
            pl.BlockSpec((ts_m, ts_n), lambda k: (0, 0)),          # C
            pl.BlockSpec((1, ts_m, ts_k), lambda k: (k, 0, 0)),    # A[k]
            pl.BlockSpec((1, ts_k, ts_n), lambda k: (k, 0, 0)),    # B[k]
        ],
        out_specs=pl.BlockSpec((ts_m, ts_n), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ts_m, ts_n), dtype),
        interpret=interpret,
    )
    return jax.jit(call)


def gemm_chain(c, a_stack, b_stack):
    """C += sum_k A[k] @ B[k]; one kernel, C resident in VMEM throughout."""
    kt, ts_m, ts_k = a_stack.shape
    ts_n = b_stack.shape[2]
    dtype = str(c.dtype)
    return _gemm_chain_call(kt, ts_m, ts_k, ts_n, dtype, _interpret(),
                            _mxu_precision(dtype))(c, a_stack, b_stack)


# ---------------------------------------------------------------------------
# blocked matmul
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _matmul_call(m: int, n: int, k: int, bm: int, bn: int, bk: int,
                 dtype: str, interpret: bool, prec=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, out_ref):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        out_ref[:] += jnp.dot(a_ref[:], b_ref[:], precision=prec,
                              preferred_element_type=jnp.float32
                              ).astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        interpret=interpret,
    )
    return jax.jit(call)


def matmul(a, b, block: Tuple[int, int, int] = (256, 256, 256)):
    """Blocked A @ B. Shapes the blocks do not divide are the caller's
    property, not a kernel failure: they take the plain ``jnp.dot``."""
    import jax.numpy as jnp
    m, k = a.shape
    n = b.shape[1]
    bm, bn, bk = (min(block[0], m), min(block[1], n), min(block[2], k))
    if m % bm or n % bn or k % bk:
        return jnp.dot(a, b, precision=dot_precision(),
                       preferred_element_type=jnp.float32).astype(a.dtype)
    dtype = str(a.dtype)
    return _matmul_call(m, n, k, bm, bn, bk, dtype, _interpret(),
                        _mxu_precision(dtype))(a, b)


# ---------------------------------------------------------------------------
# fused 1D stencil
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _stencil_call(rows: int, cols: int, w: Tuple[float, float, float],
                  dtype: str, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    w0, w1, w2 = w

    def kernel(x_ref, l_ref, r_ref, out_ref):
        x = x_ref[:]
        xm = jnp.concatenate([l_ref[:, -1:], x[:, :-1]], axis=1)
        xp = jnp.concatenate([x[:, 1:], r_ref[:, :1]], axis=1)
        out_ref[:] = (w0 * xm + w1 * x + w2 * xp).astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        interpret=interpret,
    )
    return jax.jit(call)


def stencil1d(x, left, right, weights=(0.25, 0.5, 0.25)):
    """Fused 3-point stencil; ``left``/``right`` are the neighbor tiles
    (pass zero tiles at the domain boundary)."""
    return _stencil_call(x.shape[0], x.shape[1], tuple(weights),
                         str(x.dtype), _interpret())(x, left, right)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _flash_attn_call(bh: int, sq: int, sk: int, d: int, bq: int, bk: int,
                     causal: bool, scale: float, q_off: int, k_off: int,
                     dtype: str, interpret: bool, vma=None):
    """Grid (bh, sq//bq, sk//bk): k/v STREAM through VMEM one block per
    step (so sequence length is HBM-bounded, not VMEM-bounded) while the
    online-softmax state (running max ``m``, rescaled sum ``l``,
    accumulator ``acc``) lives in VMEM scratch across the k dimension —
    scores and probabilities are never written to HBM.

    ``q_off``/``k_off`` are the GLOBAL positions of row/col 0, so the
    causal mask is correct on sequence shards and rotated ring blocks;
    fully-masked rows produce ZERO output (ring-fold convention).
    ``vma`` types the output as varying over those mesh axes so the kernel
    can sit inside a ``shard_map`` with the VMA checker on."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nk = sk // bk
    neg = -1e30

    def kernel(q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref):
        iq = pl.program_id(1)
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, neg)
            l_ref[:] = jnp.zeros_like(l_ref)

        # blocks entirely above the causal diagonal contribute nothing
        intersects = True
        if causal:
            intersects = (k_off + kk * bk) <= (q_off + (iq + 1) * bq - 1)

        @pl.when(intersects)
        def _():
            q = q_ref[0].astype(jnp.float32) * scale      # (bq, d)
            kb = k_ref[0].astype(jnp.float32)             # (bk, d)
            vb = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if causal:
                q_pos = q_off + iq * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                k_pos = k_off + kk * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(k_pos <= q_pos, s, neg)
            m = jnp.max(m_ref[...], axis=1, keepdims=True)   # lanes equal
            l = jnp.max(l_ref[...], axis=1, keepdims=True)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            # a masked score must carry ZERO weight even when the whole
            # row is masked (s == m_new == neg would give p = 1)
            p = jnp.where(s > 0.5 * neg, p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[...] * corr + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(l, l_ref.shape)

        @pl.when(kk == nk - 1)
        def _():
            l = jnp.max(l_ref[...], axis=1, keepdims=True)
            out_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)
                          ).astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, iq, kk: (b, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, iq, kk: (b, kk, 0)),
            pl.BlockSpec((1, bk, d), lambda b, iq, kk: (b, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, iq, kk: (b, iq, 0)),
        out_shape=(jax.ShapeDtypeStruct((bh, sq, d), dtype, vma=set(vma))
                   if vma else jax.ShapeDtypeStruct((bh, sq, d), dtype)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),     # acc
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (lanes equal)
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum (lanes equal)
        ],
        interpret=interpret,
    )
    return jax.jit(call)


def flash_attention(q, k, v, causal: bool = False, scale: float = None,
                    q_offset: int = 0, k_offset: int = 0,
                    block_q: int = 256, block_k: int = 512, vma=None):
    """Fused softmax(q·kᵀ·scale)·v over (..., seq, head_dim) operands.

    Accepts (B, H, S, D) or (BH, S, D); k/v may have a different sequence
    length than q (cross-attention, ring blocks, sequence shards —
    ``q_offset``/``k_offset`` give the global position of element 0 so the
    causal mask stays correct; fully-masked rows return zeros). Inside a
    ``shard_map``, pass ``vma=(axis, ...)`` so the output is typed as
    device-varying. Sequence lengths not divisible by the block sizes
    shrink the blocks to the largest divisor (a caller-shape property,
    handled here); only a degenerate divisor takes the dense XLA
    expression, chosen from the shapes. A Pallas failure raises."""
    import jax.numpy as jnp
    q4 = q.reshape((-1,) + q.shape[-2:])
    k4 = k.reshape((-1,) + k.shape[-2:])
    v4 = v.reshape((-1,) + v.shape[-2:])
    bhn, sq, d = q4.shape
    sk = k4.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    # block sizes must divide the sequence lengths — that is a property of
    # the CALLER's shapes, not a Pallas failure, so resolve it here by
    # shrinking to the largest divisor (never silently fall back over it):
    # an odd length degrades the block size, not the numerics
    def _divisor_block(s: int, b: int) -> int:
        b = min(b, s)
        while s % b:
            b -= 1
        return b

    bq = _divisor_block(sq, block_q)
    bk = _divisor_block(sk, block_k)

    def _dense(q4, k4, v4):
        import jax
        s = jnp.einsum("bqd,bkd->bqk", q4.astype(jnp.float32),
                       k4.astype(jnp.float32),
                       precision=jax.lax.Precision.DEFAULT) * scale
        if causal:
            qp = q_offset + jnp.arange(sq)[:, None]
            kp = k_offset + jnp.arange(sk)[None, :]
            s = jnp.where(kp <= qp, s, -jnp.inf)
        # explicit guarded softmax: fully-masked rows give ZERO output
        # (jax.nn.softmax would return uniform weights there)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - jnp.where(
            jnp.isfinite(m), m, 0.0)), 0.0)
        l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("bqk,bkd->bqd", p / l, v4.astype(jnp.float32)
                          ).astype(q.dtype)

    # A prime/odd sequence length degrades the largest divisor toward 1,
    # which is below TPU tile granularity — a severe Pallas perf cliff or a
    # Mosaic trace failure. Below _MIN_BLOCK (unless the block IS the whole
    # sequence), the dense XLA path is the better program.
    _MIN_BLOCK = 8
    if (bq < _MIN_BLOCK < sq) or (bk < _MIN_BLOCK < sk):
        return _dense(q4, k4, v4).reshape(q.shape)
    out = _flash_attn_call(bhn, sq, sk, d, bq, bk, bool(causal),
                           float(scale), int(q_offset), int(k_offset),
                           str(q.dtype), _interpret(),
                           tuple(vma) if vma else None)(q4, k4, v4)
    return out.reshape(q.shape)
