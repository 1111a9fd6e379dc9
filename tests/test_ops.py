"""Algorithm builders through the DTD runtime: tiled GEMM and Cholesky."""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.dtd import DTDTaskpool
from parsec_tpu.ops.gemm import insert_gemm_tasks
from parsec_tpu.ops.potrf import insert_potrf_tasks, make_spd


@pytest.fixture()
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


def _tiled_from(dense: np.ndarray, ts: int, name: str) -> TiledMatrix:
    n = dense.shape[0]
    M = TiledMatrix(name, n, dense.shape[1], ts, ts)
    M.fill(lambda m, k: dense[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    return M


@pytest.mark.parametrize("batch_k", [False, True])
def test_gemm_builder(ctx, batch_k):
    n, ts = 96, 32
    rng = np.random.default_rng(5)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = _tiled_from(a, ts, "A")
    B = _tiled_from(b, ts, "B")
    C = _tiled_from(np.zeros((n, n), np.float32), ts, "C")
    tp = DTDTaskpool(ctx, "gemm")
    ntasks = insert_gemm_tasks(tp, A, B, C, batch_k=batch_k)
    assert ntasks == (9 if batch_k else 27)
    tp.wait()
    tp.close()
    ctx.wait()
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-3, atol=1e-3)


def test_potrf_builder(ctx):
    """Tiled Cholesky DAG vs numpy (BASELINE config 3: DTD dpotrf)."""
    n, ts = 128, 32
    spd = make_spd(n, seed=6)
    A = _tiled_from(spd, ts, "A")
    tp = DTDTaskpool(ctx, "potrf")
    T = n // ts
    ntasks = insert_potrf_tasks(tp, A)
    # POTRF: T diag + T(T-1)/2 trsm + T(T-1)/2 syrk + T(T-1)(T-2)/6 gemm
    assert ntasks == T + T*(T-1) + T*(T-1)*(T-2)//6
    tp.wait()
    tp.close()
    ctx.wait()
    L = np.tril(A.to_dense())
    np.testing.assert_allclose(L @ L.T, spd, rtol=1e-2, atol=1e-2)


def test_potrf_larger_grid(ctx):
    n, ts = 160, 32  # 5x5 tile grid exercises deeper DAG
    spd = make_spd(n, seed=7)
    A = _tiled_from(spd, ts, "A")
    tp = DTDTaskpool(ctx, "potrf5")
    insert_potrf_tasks(tp, A)
    tp.wait()
    tp.close()
    ctx.wait()
    L = np.tril(A.to_dense())
    np.testing.assert_allclose(L @ L.T, spd, rtol=1e-2, atol=1e-2)


def test_getrf_builder(ctx):
    """Tiled LU (no pivoting) on a diagonally-dominant matrix."""
    from parsec_tpu.ops.getrf import (getrf_flops, insert_getrf_tasks,
                                      make_dd, unpack_lu)
    n, ts = 96, 32
    a = make_dd(n, seed=8)
    A = _tiled_from(a, ts, "LU")
    tp = DTDTaskpool(ctx, "getrf")
    T = n // ts
    ntasks = insert_getrf_tasks(tp, A)
    assert ntasks == T + 2 * (T * (T - 1) // 2) + (T*(T-1)*(2*T-1))//6
    tp.wait()
    tp.close()
    ctx.wait()
    packed = A.to_dense()
    L, U = unpack_lu(packed)
    np.testing.assert_allclose(L @ U, a, rtol=2e-2, atol=2e-2)
    assert getrf_flops(10) == 2000.0 / 3.0


def test_geqrf_builder(ctx):
    """Tiled QR: R^T R must equal A^T A (Q orthogonal), and the reflectors
    stored below the diagonal (V2 in the sub-diagonal tiles, T beside)
    rebuild A = QR."""
    from parsec_tpu.ops.geqrf import insert_geqrf_tasks
    from test_geqrf import apply_q
    n, ts = 64, 16
    nt = n // ts
    rng = np.random.default_rng(9)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = _tiled_from(a, ts, "QR")
    T = TiledMatrix("QRT", n, n, ts, ts)
    tp = DTDTaskpool(ctx, "geqrf")
    insert_geqrf_tasks(tp, A, T)
    tp.wait()
    tp.close()
    ctx.wait()
    R = np.triu(A.to_dense())
    np.testing.assert_allclose(R.T @ R, a.T @ a, rtol=5e-2, atol=5e-2)
    # the sub-diagonal tiles hold V2 by design: with T they give back A
    tile = lambda M, m, k: np.asarray(M.data_of(m, k).newest_copy().payload)
    qr = np.vstack(apply_q(lambda k, m: tile(A, m, k),
                           lambda m, k: tile(T, m, k),
                           [R[i * ts:(i + 1) * ts] for i in range(nt)], nt))
    assert np.linalg.norm(qr - a) / np.linalg.norm(a) < 1e-5


def test_dtd_gemm_bf16_tiles(ctx):
    """bf16 tile GEMM with per-step f32 dots (the MXU-native mixed
    precision the real-chip bench flips to): the DTD DAG over bf16
    payloads matches the f32 product within bf16 tolerance."""
    import jax.numpy as jnp
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    N, TS = 128, 32
    rng = np.random.default_rng(21)
    a = rng.standard_normal((N, N)).astype(np.float32)
    b = rng.standard_normal((N, N)).astype(np.float32)

    def mk(name, src):
        M = TwoDimBlockCyclic(name, N, N, TS, TS, P=1, Q=1,
                              dtype=jnp.bfloat16)
        M.fill(lambda m, n: jnp.asarray(src[m*TS:(m+1)*TS, n*TS:(n+1)*TS],
                                        dtype=jnp.bfloat16))
        return M

    A, B = mk("BFA", a), mk("BFB", b)
    C = TwoDimBlockCyclic("BFC", N, N, TS, TS, P=1, Q=1, dtype=jnp.bfloat16)
    C.fill(lambda m, n: jnp.zeros((TS, TS), jnp.bfloat16))
    tp = DTDTaskpool(ctx, "bf16gemm")
    insert_gemm_tasks(tp, A, B, C, batch_k=True)
    assert tp.wait(timeout=60)
    tp.close()
    assert ctx.wait(timeout=60) == 0
    got = np.zeros((N, N), np.float32)
    for m in range(N // TS):
        for n in range(N // TS):
            got[m*TS:(m+1)*TS, n*TS:(n+1)*TS] = np.asarray(
                C.data_of(m, n).newest_copy().payload, dtype=np.float32)
    ref = (a.astype(np.float32) @ b.astype(np.float32))
    # bf16 storage of inputs/outputs: ~3 decimal digits
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.5 * np.sqrt(N))
