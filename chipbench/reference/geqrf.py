"""Reference for the tile QR cell: the seeded matrix, tile by tile, and the
two comparisons behind ``correct``, in plain ``jax.numpy`` at
``default_matmul_precision("highest")`` on the default device, block by block
(the dense N x N pair never exists).

The factor is read as ``dplasma_dgeqrf(A, T)`` leaves it: R on and above the
diagonal of A; below it, in tile (k, k) the unit-lower V of the diagonal
panel and in tile (m, k), m > k, the V2 of the stacked panel
``[R(k,k); A(m,k)]`` (its top block the identity); T's tile (k, k) and
(m, k) the upper-triangular T of ``Q = I - V T V^T`` for those panels.
"""

import numpy as np


def operand_tile(n, ts, m, k, seed):
    """Tile (m, k) of the seeded n x n matrix: standard normals / sqrt(n),
    f32 (its columns have norm ~1; a Gaussian square matrix)."""
    return np.random.default_rng((seed, m, k)).standard_normal(
        (ts, ts), dtype=np.float32) / np.float32(np.sqrt(n))


def _ops():
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    @jax.jit
    def ts_apply(v2, t, xk, xm):
        """[xk; xm] <- (I - [I; V2] T [I; V2]^T) [xk; xm]"""
        w = dot(t, xk + dot(v2.T, xm))
        return xk - w, xm - dot(v2, w)

    @jax.jit
    def ge_apply(a, t, xk):
        """xk <- (I - V T V^T) xk, V the unit lower part of ``a``"""
        v = jnp.tril(a, -1) + jnp.eye(a.shape[0], dtype=a.dtype)
        return xk - dot(v, dot(t, dot(v.T, xk)))

    @jax.jit
    def sq(x, y):
        return jnp.sum((x - y) ** 2), jnp.sum(y ** 2)

    return ts_apply, ge_apply, sq


def backward_errors(a_tile, t_tile, orig, n, ts):
    """||A[:, j] - Q R[:, j]||_F / ||A[:, j]||_F for every column block j,
    one block at a time: Q applied from the program's own V and T tiles to
    the program's R, last panel first (the panels after j touch only rows
    past j, which are zero in R[:, j]). A column's R and Q R stand on their
    own, so an update lost in any column shows in its block. ``a_tile(m, k)``
    / ``t_tile(m, k)`` return the factored A's and T's tiles (host or device
    arrays), ``orig(m, k)`` the operand's (``operand_tile``)."""
    import jax.numpy as jnp

    ts_apply, ge_apply, sq = _ops()
    nt = n // ts
    out = []
    for j in range(nt):
        x = [jnp.triu(jnp.asarray(a_tile(i, j))) if i == j
             else jnp.asarray(a_tile(i, j)) if i < j
             else jnp.zeros((ts, ts), jnp.float32) for i in range(nt)]
        for k in reversed(range(j + 1)):
            for m in reversed(range(k + 1, nt)):
                x[k], x[m] = ts_apply(jnp.asarray(a_tile(m, k)),
                                      jnp.asarray(t_tile(m, k)), x[k], x[m])
            x[k] = ge_apply(jnp.asarray(a_tile(k, k)),
                            jnp.asarray(t_tile(k, k)), x[k])
        num = den = 0.0
        for i in range(nt):
            e, a2 = sq(x[i], jnp.asarray(orig(i, j)))
            num += float(e)
            den += float(a2)
        out.append((num / den) ** 0.5)
    return out


def leading_r_error(a_tile, orig, n, ts, c):
    """R[:c, :c] (c tile columns) against the R of ``jnp.linalg.qr`` of A's
    first c tile columns alone, each with its rows' signs set so that the
    diagonal is >= 0: ||R - R_ref||_F / ||R_ref||_F."""
    import jax
    import jax.numpy as jnp

    nt = n // ts
    cols = np.concatenate([np.concatenate(
        [orig(m, k) for k in range(c)], axis=1) for m in range(nt)], axis=0)
    with jax.default_matmul_precision("highest"):
        ref = jnp.linalg.qr(jnp.asarray(cols), mode="r")
    del cols
    got = jnp.triu(jnp.concatenate([jnp.concatenate(
        [jnp.asarray(a_tile(i, j)) if i <= j
         else jnp.zeros((ts, ts), jnp.float32) for j in range(c)], axis=1)
        for i in range(c)], axis=0))

    @jax.jit
    def rel(got, ref):
        def pos(r):
            s = jnp.where(jnp.diag(r) < 0, -1.0, 1.0).astype(r.dtype)
            return s[:, None] * r
        d = pos(got) - pos(ref)
        return jnp.sqrt(jnp.sum(d ** 2) / jnp.sum(ref ** 2))

    return float(rel(got, ref))
