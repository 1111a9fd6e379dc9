"""Tiled Cholesky (POTRF) kernels and DAG builder.

The second headline benchmark (BASELINE.md: tiled dPOTRF). Right-looking
tiled Cholesky — the canonical PaRSEC/DPLASMA example (the reference ships it
as dplasma's dpotrf and exercises the same DAG shape in its DTD tests):

    for k in range(T):
        A[k,k] = POTRF(A[k,k])
        for m > k:    A[m,k] = TRSM(A[k,k], A[m,k])
        for m > k:    A[m,m] = SYRK(A[m,k], A[m,m])
        for m > n > k: A[m,n] = GEMM(A[m,k], A[n,k], A[m,n])

Tile bodies are jittable; XLA lowers cholesky/triangular_solve natively on
TPU. The DAG (RAW on panels, WAW on trailing updates) is discovered by the
DTD tile chains, exactly like the insert-task Cholesky of the reference
(BASELINE.json config 3: "DTD Cholesky (dpotrf)").
"""

from __future__ import annotations

import functools

import numpy as np

from ..data.matrix import TiledMatrix
from ..dsl.dtd import AFFINITY, DTDTaskpool, READ, RW


def tile_potrf(a):
    """Cholesky of the diagonal tile (lower)."""
    import jax
    import jax.numpy as jnp
    # cholesky's internal dots have no precision arg; scope the default so
    # f32 factorization keeps f32 accuracy on the MXU
    with jax.default_matmul_precision("highest"):
        return jnp.linalg.cholesky(a)


def tile_trsm(akk, amk):
    """A[m,k] <- A[m,k] · L(k,k)^{-T}  (right, lower, transposed)."""
    import jax
    import jax.numpy as jnp
    # solve L X^T = A^T  =>  X = A L^{-T}
    with jax.default_matmul_precision("highest"):
        return jax.scipy.linalg.solve_triangular(akk, amk.T, lower=True).T


def tile_syrk(amk, amm):
    """A[m,m] <- A[m,m] - A[m,k] · A[m,k]^T."""
    import jax.numpy as jnp
    from .pallas_kernels import dot_precision
    return amm - jnp.dot(amk, amk.T, precision=dot_precision(),
                         preferred_element_type=jnp.float32).astype(amm.dtype)


def tile_gemm_update(amk, ank, amn):
    """A[m,n] <- A[m,n] - A[m,k] · A[n,k]^T."""
    import jax.numpy as jnp
    from .pallas_kernels import dot_precision
    return amn - jnp.dot(amk, ank.T, precision=dot_precision(),
                         preferred_element_type=jnp.float32).astype(amn.dtype)


def insert_potrf_tasks(tp: DTDTaskpool, A: TiledMatrix) -> int:
    """Insert the right-looking tiled Cholesky DAG (lower). Returns task count.

    Priorities follow the critical path (panel first), the standard trick the
    reference relies on priority-aware schedulers for.
    """
    T = A.mt
    assert A.mt == A.nt, "POTRF needs a square tile grid"
    n0 = tp.inserted
    for k in range(T):
        prio = (T - k) * 10000
        tp.insert_task(tile_potrf, (tp.tile_of(A, k, k), RW | AFFINITY),
                       priority=prio + 3000, name="POTRF")
        for m in range(k + 1, T):
            tp.insert_task(tile_trsm,
                           (tp.tile_of(A, k, k), READ),
                           (tp.tile_of(A, m, k), RW | AFFINITY),
                           priority=prio + 2000, name="TRSM")
        for m in range(k + 1, T):
            tp.insert_task(tile_syrk,
                           (tp.tile_of(A, m, k), READ),
                           (tp.tile_of(A, m, m), RW | AFFINITY),
                           priority=prio + 1000, name="SYRK")
            for n in range(k + 1, m):
                tp.insert_task(tile_gemm_update,
                               (tp.tile_of(A, m, k), READ),
                               (tp.tile_of(A, n, k), READ),
                               (tp.tile_of(A, m, n), RW | AFFINITY),
                               priority=prio, name="GEMM")
    return tp.inserted - n0


#: the same DAG as a PTG: DPLASMA's ``src/zpotrf_L.jdf`` (dpotrf, lower,
#: right-looking), its four classes ``potrf_zpotrf(k)``, ``potrf_ztrsm(m, k)``,
#: ``potrf_zherk(k, m)``, ``potrf_zgemm(m, n, k)`` over their triangular
#: task space, ranges declared in DPLASMA's order (``GEMM``'s ``k`` first:
#: a bound reads the locals declared above it). The bodies call the tile
#: functions above by name, handed in as globals by :func:`potrf_taskpool`;
#: the priorities are :func:`insert_potrf_tasks`'s (DPLASMA's own formulas
#: are not in this tree).
POTRF_JDF = """
%global NT
%global descA

POTRF(k)
  k = 0 .. NT-1
  : descA(k, k)
  priority = (NT - k) * 10000 + 3000
  RW T <- (k == 0) ? descA(k, k) : T SYRK(k-1, k)
       -> T TRSM(k+1 .. NT-1, k)
       -> descA(k, k)
BODY [type=TPU]
  T = tile_potrf(T)
END

TRSM(m, k)
  m = 1 .. NT-1
  k = 0 .. m-1
  : descA(m, k)
  priority = (NT - k) * 10000 + 2000
  READ T <- T POTRF(k)
  RW C <- (k == 0) ? descA(m, k) : C GEMM(m, k, k-1)
       -> A SYRK(k, m)
       -> A GEMM(m, k+1 .. m-1, k)
       -> B GEMM(m+1 .. NT-1, m, k)
       -> descA(m, k)
BODY [type=TPU]
  C = tile_trsm(T, C)
END

SYRK(k, m)
  k = 0 .. NT-2
  m = k+1 .. NT-1
  : descA(m, m)
  priority = (NT - k) * 10000 + 1000
  READ A <- C TRSM(m, k)
  RW T <- (k == 0) ? descA(m, m) : T SYRK(k-1, m)
       -> (m == k+1) ? T POTRF(m) : T SYRK(k+1, m)
BODY [type=TPU]
  T = tile_syrk(A, T)
END

GEMM(m, n, k)
  k = 0 .. NT-3
  m = k+2 .. NT-1
  n = k+1 .. m-1
  : descA(m, n)
  priority = (NT - k) * 10000
  READ A <- C TRSM(m, k)
  READ B <- C TRSM(n, k)
  RW C <- (k == 0) ? descA(m, n) : C GEMM(m, n, k-1)
       -> (n == k+1) ? C TRSM(m, n) : C GEMM(m, n, k+1)
BODY [type=TPU]
  C = tile_gemm_update(A, B, C)
END
"""


@functools.lru_cache(maxsize=None)
def potrf_program():
    """:data:`POTRF_JDF` compiled, once a process: its pools share one
    flatten, one fusion plan and one set of region executables."""
    from ..dsl.ptg.compiler import compile_ptg
    return compile_ptg(POTRF_JDF, "potrf")


def potrf_taskpool(ctx, A: TiledMatrix):
    """A new taskpool of :data:`POTRF_JDF` over ``A`` (lower), as DPLASMA
    creates one per call of ``dplasma_dpotrf``; the caller adds it to
    ``ctx``. ``A.mt * (A.mt + 1) * (A.mt + 2) // 6`` tasks."""
    assert A.mt == A.nt, "POTRF needs a square tile grid"
    return potrf_program().instantiate(
        ctx, globals={"NT": A.mt, "tile_potrf": tile_potrf,
                      "tile_trsm": tile_trsm, "tile_syrk": tile_syrk,
                      "tile_gemm_update": tile_gemm_update},
        collections={"descA": A})


def potrf_flops(N: int) -> float:
    """N^3/3 (+ lower order), the standard dpotrf count."""
    return N ** 3 / 3.0 + N ** 2 / 2.0


def make_spd(n: int, seed: int = 0, dtype=np.float32) -> np.ndarray:
    """A well-conditioned SPD matrix for tests/benchmarks."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float64) / np.sqrt(n)
    spd = a @ a.T + np.eye(n) * n * 0.05
    return spd.astype(dtype)


def spd_tile(n: int, ts: int, m: int, k: int, seed: int = 0,
             dtype=np.float32) -> np.ndarray:
    """Tile (m, k) of a seeded, well-conditioned n x n SPD matrix, made in
    O(ts^2) by whoever needs it: S + 3I, S a symmetric Gaussian (Wigner)
    matrix with off-diagonal variance 1/n, so the spectrum lies in about
    [1, 5]. :func:`make_spd` costs an n^3 float64 host matmul and cannot
    reach sizes that fill a chip; here every rank builds exactly its own
    tiles, and a reference builds the same matrix from the same seed."""
    lo, hi = max(m, k), min(m, k)
    g = np.random.default_rng((seed, lo, hi)).standard_normal(
        (ts, ts), dtype=np.float32) / np.float32(np.sqrt(n))
    if m == k:
        g = (g + g.T) / np.float32(np.sqrt(2.0)) \
            + 3.0 * np.eye(ts, dtype=np.float32)
    elif m < k:
        g = g.T
    return g.astype(dtype, copy=False)


# --------------------------------------------------------------- SPD solve

def tile_trsv_l(lkk, bk):
    """B[k] <- L(k,k)^{-1} B[k] (forward substitution step)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.scipy.linalg.solve_triangular(lkk, bk, lower=True)


def tile_trsv_lt(lkk, bk):
    """B[k] <- L(k,k)^{-T} B[k] (backward substitution step)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.scipy.linalg.solve_triangular(lkk, bk, lower=True,
                                                 trans=1)


def tile_gemv_sub(lmk, yk, bm):
    """B[m] <- B[m] - L(m,k) Y[k]."""
    import jax.numpy as jnp
    from .pallas_kernels import dot_precision
    return bm - jnp.dot(lmk, yk, precision=dot_precision(),
                        preferred_element_type=jnp.float32).astype(bm.dtype)


def tile_gemv_sub_t(lkm, xk, ym):
    """Y[m] <- Y[m] - L(k,m)^T X[k]."""
    import jax.numpy as jnp
    from .pallas_kernels import dot_precision
    return ym - jnp.dot(lkm.T, xk, precision=dot_precision(),
                        preferred_element_type=jnp.float32).astype(ym.dtype)


def insert_posv_tasks(tp: DTDTaskpool, A: TiledMatrix,
                      B: TiledMatrix) -> int:
    """Solve A X = B for SPD A (the DPLASMA dposv shape): Cholesky
    factorization followed by tiled forward and backward substitution, one
    taskpool — the solves chain onto the factorization through the tile
    dependencies, so panels start solving while trailing updates still run.
    B is a (T x 1)-tile right-hand-side collection, overwritten with X.
    Works under both execution modes (scheduler and capture)."""
    T = A.mt
    assert A.mt == A.nt and B.mt == T and B.nt == 1
    n0 = tp.inserted
    insert_potrf_tasks(tp, A)
    # forward: L Y = B
    for k in range(T):
        tp.insert_task(tile_trsv_l, (tp.tile_of(A, k, k), READ),
                       (tp.tile_of(B, k, 0), RW | AFFINITY), name="TRSV_L")
        for m in range(k + 1, T):
            tp.insert_task(tile_gemv_sub, (tp.tile_of(A, m, k), READ),
                           (tp.tile_of(B, k, 0), READ),
                           (tp.tile_of(B, m, 0), RW | AFFINITY),
                           name="GEMV_SUB")
    # backward: L^T X = Y
    for k in reversed(range(T)):
        tp.insert_task(tile_trsv_lt, (tp.tile_of(A, k, k), READ),
                       (tp.tile_of(B, k, 0), RW | AFFINITY), name="TRSV_LT")
        for m in range(k):
            tp.insert_task(tile_gemv_sub_t, (tp.tile_of(A, k, m), READ),
                           (tp.tile_of(B, k, 0), READ),
                           (tp.tile_of(B, m, 0), RW | AFFINITY),
                           name="GEMV_SUB_T")
    return tp.inserted - n0
