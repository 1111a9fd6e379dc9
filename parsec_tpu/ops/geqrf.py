"""Tiled QR factorization (dgeqrf) DAG builder, in compact-WY form.

The DPLASMA dgeqrf of BASELINE config 5: the tile QR of Buttari, Langou,
Kurzak and Dongarra ("A class of parallel tiled linear algebra algorithms
for multicore architectures", 2009) with its four kernels, on the flat TS
tree, storing its factors as ``dplasma_dgeqrf(A, T)`` does:

    for k:
      GEQRT(k):     A[k,k] -> R (upper) and V (strictly lower, unit
                    diagonal implied); T[k,k] <- its triangular factor
      UNMQR(k,n):   A[k,n] <- Q(k)^T A[k,n]                       (n > k)
      for m > k:
        TSQRT(k,m):   [R[k,k]; A[m,k]] -> new R into A[k,k] (its lower part,
                      GEQRT's V, kept); V2 into A[m,k]; T[m,k] <- its T
        TSMQR(k,m,n): [A[k,n]; A[m,n]] <- Q(k,m)^T [A[k,n]; A[m,n]]  (n > k)

Every reflector block is ``Q = I - V T V^T`` with ``T`` upper triangular,
``T^{-1} = striu(V^T V) + diag(1/tau)`` (a reflector with ``tau = 0`` is the
identity, and its row and column of ``T`` are 0). ``T`` is written by GEQRT
and TSQRT and never read first: a WRITE flow, which the device lane gives
room on the device without moving a byte (docs/device_lane.md).

Departures from DPLASMA, each for the MXU:

* ``T`` is one upper-triangular TS x TS tile per panel, as if ``ib = TS``.
  UNMQR and TSMQR read only its ib x ib diagonal blocks: the panel's
  ``Q = H_1 H_2 ... H_b``, ``H_j = I - V_j T_jj V_j^T`` with ``T_jj`` T's
  j-th diagonal block (T is built as ``[[T11, -T11 V1^T V2 T22], [0, T22]]``,
  so its off-diagonal blocks only encode the product), and they apply it one
  ib-wide block of reflectors at a time: TSMQR 4 TS^3 + 2 ib TS^2 FLOP,
  UNMQR 2 TS^3 + 4 ib TS^2, where the whole T in three dense products is
  6 TS^3. The same Q is applied, up to rounding. T is still stored whole:
  it is the factor the caller is handed, from which anyone may apply Q. ib
  is 256 from a tile of 1024 up (:func:`_inner_block`), the width at which
  TSMQR runs fastest on the MXU; DPLASMA's ib ~ 32 tunes for a CPU's
  caches. A smaller tile is one block: the dense form.
* TSQRT factors the stacked ``[triu(R); A[m,k]]`` with the dense Householder
  panel, jax's ``geqrf`` (the ``Qr`` custom call on the TPU), about twice
  the structured kernel's panel FLOP. The top of its V is exactly the
  identity (the stack's top block is upper triangular, so no reflector has a
  component below its own row there), and only V2 is stored, in A[m,k].

The panel runs under ``default_matmul_precision("highest")``; the products
take the ``tile_dot_precision`` of every other tile body."""

from __future__ import annotations

from ..data.matrix import TiledMatrix
from ..dsl.dtd import AFFINITY, DTDTaskpool, READ, RW, WRITE


#: the width of the reflector blocks UNMQR and TSMQR apply one at a time,
#: and the smallest tile they are applied to in blocks. On a TPU v5e at f32
#: HIGHEST a TSMQR of 2048 takes 1.24 ms at ib = 256 (1.28 at 128, 1.30 at
#: 512, 1.69 dense); blocks gain at 1024 and nothing at 512 (PERF.md)
INNER_BLOCK = 256
BLOCKED_FROM = 1024


def _inner_block(ts):
    """ib for a TS x TS tile: ``INNER_BLOCK`` where the tile is at least
    ``BLOCKED_FROM`` and a multiple of it, else TS (one block: three dense
    products, 6 TS^3)."""
    return INNER_BLOCK if ts >= BLOCKED_FROM and ts % INNER_BLOCK == 0 \
        else ts


def _dot(a, b):
    import jax.numpy as jnp
    from .pallas_kernels import dot_precision
    return jnp.dot(a, b, precision=dot_precision(),
                   preferred_element_type=jnp.float32).astype(a.dtype)


def _panel(a):
    """Householder QR of ``a`` (m x n, m >= n): LAPACK's packed output (R on
    and above the diagonal, the reflectors below it) and ``tau``."""
    import jax
    # jax exports only the explicit-Q ``qr``; its Householder half is the
    # primitive ``geqrf`` (the ``Qr`` custom call on the TPU)
    from jax._src.lax.linalg import geqrf
    with jax.default_matmul_precision("highest"):
        return geqrf(a)


def _t_factor(gram, tau):
    """The upper-triangular T of ``Q = I - V T V^T`` from ``V^T V`` and the
    reflectors' ``tau``: the inverse of ``striu(V^T V) + diag(1/tau)``, with
    the row and column of a ``tau = 0`` reflector (the identity) set to 0."""
    import jax
    import jax.numpy as jnp
    nz = tau != 0
    keep = nz[:, None] & nz[None, :]
    inv_tau = jnp.where(nz, 1.0 / jnp.where(nz, tau, 1.0), 1.0)
    m = jnp.where(keep, jnp.triu(gram, 1), 0.0) + jnp.diag(inv_tau)
    eye = jnp.eye(gram.shape[0], dtype=gram.dtype)
    with jax.default_matmul_precision("highest"):
        t = jax.scipy.linalg.solve_triangular(m, eye, lower=False)
    return jnp.where(keep, t, 0.0).astype(gram.dtype)


def _unit_lower(a):
    import jax.numpy as jnp
    return jnp.tril(a, -1) + jnp.eye(a.shape[0], dtype=a.dtype)


def tile_geqrt(akk, t):
    """QR of the diagonal tile. ``t`` is write-only (``None`` on the device
    lane). Returns (R above and V below the diagonal, T)."""
    a, tau = _panel(akk)
    v = _unit_lower(a)
    return a, _t_factor(_dot(v.T, v), tau)


def tile_unmqr(akk, t, akn):
    """A[k,n] <- Q^T A[k,n], Q = I - V T V^T, V the unit lower part of A[k,k],
    applied one ib-wide block of reflectors at a time. Block j's columns of
    V are zero above row j ib, so it reads and writes only the rows below:
    2 TS^3 + 4 ib TS^2 FLOP."""
    import jax.numpy as jnp
    v = _unit_lower(akk)
    ib = _inner_block(akk.shape[0])
    done, rest = [], akn
    for j in range(0, akk.shape[0], ib):
        vj, s = v[j:, j:j + ib], slice(j, j + ib)
        w = _dot(t[s, s].T, _dot(vj.T, rest))
        rest = rest - _dot(vj, w)
        done.append(rest[:ib])
        rest = rest[ib:]
    return jnp.concatenate(done)


def tile_tsqrt(akk, amk, t):
    """QR of the stack [triu(A[k,k]); A[m,k]]. Returns (new R above
    A[k,k]'s kept lower part, V2, T); ``t`` is write-only."""
    import jax.numpy as jnp
    ts = akk.shape[0]
    a, tau = _panel(jnp.concatenate([jnp.triu(akk), amk], axis=0))
    v2 = a[ts:]
    # V = [I; V2], so striu(V^T V) = striu(V2^T V2)
    return (jnp.triu(a[:ts]) + jnp.tril(akk, -1), v2,
            _t_factor(_dot(v2.T, v2), tau))


def tile_tsmqr(akn, amn, v2, t):
    """[A[k,n]; A[m,n]] <- Q^T [A[k,n]; A[m,n]], Q = I - [I; V2] T [I; V2]^T,
    applied one ib-wide block of reflectors at a time: block j touches
    A[k,n]'s rows j ib : (j+1) ib and all of A[m,n], 4 TS^3 + 2 ib TS^2
    FLOP."""
    import jax.numpy as jnp
    ib = _inner_block(akn.shape[0])
    top = []
    for j in range(0, akn.shape[0], ib):
        v2j, s = v2[:, j:j + ib], slice(j, j + ib)
        w = _dot(t[s, s].T, akn[s] + _dot(v2j.T, amn))
        top.append(akn[s] - w)
        amn = amn - _dot(v2j, w)
    return jnp.concatenate(top), amn


def insert_geqrf_tasks(tp: DTDTaskpool, A: TiledMatrix,
                       T: TiledMatrix) -> int:
    """The flat-tree tile QR of the square tiled ``A``; the triangular
    factors go to ``T``'s tiles (k, k) and (m, k), m > k, which GEQRT and
    TSQRT write without reading. Priorities put the panel first and the
    earlier step ahead of the later. Returns the task count,
    NT + 2 NT(NT-1)/2 + sum_{j<NT} j^2."""
    nt = A.mt
    if A.mt != A.nt or (T.mt, T.nt, T.mb, T.nb) != (A.mt, A.nt, A.mb, A.nb):
        raise ValueError("the tile QR takes a square A of square tiles and "
                         "a T of A's tiling")
    n0 = tp.inserted
    for k in range(nt):
        prio = (nt - k) * 10000
        akk = tp.tile_of(A, k, k)
        tp.insert_task(tile_geqrt, (akk, RW | AFFINITY),
                       (tp.tile_of(T, k, k), WRITE),
                       priority=prio + 3000, name="GEQRT")
        for n in range(k + 1, nt):
            tp.insert_task(tile_unmqr, (akk, READ), (tp.tile_of(T, k, k), READ),
                           (tp.tile_of(A, k, n), RW | AFFINITY),
                           priority=prio + 2000, name="UNMQR")
        for m in range(k + 1, nt):
            tmk = tp.tile_of(T, m, k)
            tp.insert_task(tile_tsqrt, (akk, RW | AFFINITY),
                           (tp.tile_of(A, m, k), RW), (tmk, WRITE),
                           priority=prio + 1500, name="TSQRT")
            for n in range(k + 1, nt):
                tp.insert_task(tile_tsmqr, (tp.tile_of(A, k, n), RW),
                               (tp.tile_of(A, m, n), RW | AFFINITY),
                               (tp.tile_of(A, m, k), READ), (tmk, READ),
                               priority=prio, name="TSMQR")
    return tp.inserted - n0


def geqrf_flops(N: int) -> float:
    return 4.0 * N ** 3 / 3.0
