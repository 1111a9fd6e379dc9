"""Reference for the tiled Cholesky cells: the seeded SPD matrix, tile by
tile, and the residual that decides ``correct``.

``spd_tile`` is a copy of ``parsec_tpu/ops/potrf.py:spd_tile`` (the program
may change its own; the benchmark's operands may not move with it).
"""

import numpy as np


def spd_tile(n, ts, m, k, seed=0):
    """Tile (m, k) of a seeded, well-conditioned n x n SPD matrix in f32:
    S + 3I, S a symmetric Gaussian (Wigner) matrix with off-diagonal
    variance 1/n, so the spectrum lies in about [1, 5]."""
    lo, hi = max(m, k), min(m, k)
    g = np.random.default_rng((seed, lo, hi)).standard_normal(
        (ts, ts), dtype=np.float32) / np.float32(np.sqrt(n))
    if m == k:
        g = (g + g.T) / np.float32(np.sqrt(2.0)) \
            + 3.0 * np.eye(ts, dtype=np.float32)
    elif m < k:
        g = g.T
    return g


def sample_tiles(nt, seed, at_least=64):
    """The lower tiles the residual is taken over: every diagonal tile plus
    seeded off-diagonal ones, ``at_least`` in all (or the whole triangle)."""
    diag = [(m, m) for m in range(nt)]
    off = [(m, k) for m in range(nt) for k in range(m)]
    rng = np.random.default_rng((seed, 0x5A))
    want = min(len(off), max(0, at_least - len(diag)))
    picks = rng.choice(len(off), size=want, replace=False) if want else []
    return diag + [off[i] for i in sorted(picks)]


def residual(factor_tile, n, ts, seed, sample):
    """||(L L^T - A)[S]||_F / ||A[S]||_F over the tiles S = ``sample``, on
    the default device, tile by tile: (L L^T)[m, k] = sum_{j <= k}
    L[m, j] L[k, j]^T. ``factor_tile(m, j)`` returns tile (m, j) of L as an
    array (host or device); the diagonal tiles' upper halves are ignored.
    The dense pair never exists: at N = 49152 it would not fit."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def accumulate(c, lm, lk):
        return c + jnp.dot(lm, lk.T, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def sq_err(c, a):
        return jnp.sum((c - a) ** 2), jnp.sum(a ** 2)

    tril = jax.jit(jnp.tril)
    held = {}

    def tile(m, j):
        if (m, j) not in held:
            t = jnp.asarray(factor_tile(m, j))
            held[m, j] = tril(t) if m == j else t
        return held[m, j]

    num = den = 0.0
    for m, k in sample:
        c = jnp.zeros((ts, ts), jnp.float32)
        for j in range(k + 1):
            c = accumulate(c, tile(m, j), tile(k, j))
        e, a2 = sq_err(c, jnp.asarray(spd_tile(n, ts, m, k, seed)))
        num += float(e)
        den += float(a2)
    return (num / den) ** 0.5
