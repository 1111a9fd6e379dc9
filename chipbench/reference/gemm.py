"""Reference for the tiled GEMM cells: seeded operands, tile by tile, and
the max-abs comparison with raw XLA that decides ``correct``
(``chip_smoke.py``'s check and tolerance, on a sample)."""

import numpy as np


def operand_tile(which, ts, m, k, seed):
    """Tile (m, k) of operand ``which`` (0 = A, 1 = B): unit normals, f32."""
    return np.random.default_rng((seed, which, m, k)).standard_normal(
        (ts, ts), dtype=np.float32)


def sample_rows(nt, seed, at_least=64):
    """The C tile rows that are checked, whole: enough seeded rows to hold
    ``at_least`` tiles (so at least one whole tile row, as the issue asks)."""
    rows = min(nt, -(-at_least // nt))
    rng = np.random.default_rng((seed, 0x6E))
    return sorted(int(r) for r in rng.choice(nt, size=rows, replace=False))


def tolerance(n, solves, per_solve=1e-4):
    """f32 sums of N products of unit normals, taken in two different
    orders: 1e-4 * sqrt(N) is ~800 ulp of the result's magnitude and far
    below the sqrt(TS) a single missing tile product would cost
    (``chip_smoke.py``); C accumulates ``solves`` products, so it scales."""
    return solves * per_solve * n ** 0.5


def max_abs_err(c_tile, a_host, b_host, nt, rows, scale):
    """max |C[m, n] - scale * A[m, :] B[:, n]| over the tile rows ``rows``
    and every column, against ``jnp.dot(..., HIGHEST)`` of the matching
    slabs, on the default device. ``a_host``/``b_host`` are this
    benchmark's own host tiles, staged here and not read from the
    program's copies; ``c_tile(m, n)`` returns the program's result."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tile_err(c, a_row, b_col, k):
        ref = jnp.dot(a_row, b_col, precision=jax.lax.Precision.HIGHEST)
        return jnp.max(jnp.abs(c - k * ref))

    k = jnp.float32(scale)     # traced: one program whatever the count
    a_rows = {m: jnp.concatenate([jnp.asarray(a_host[m, k])
                                  for k in range(nt)], axis=1) for m in rows}
    worst = 0.0
    for n in range(nt):
        b_col = jnp.concatenate([jnp.asarray(b_host[k, n])
                                 for k in range(nt)], axis=0)
        errs = [tile_err(jnp.asarray(c_tile(m, n)), a_rows[m], b_col, k)
                for m in rows]
        worst = max(worst, float(jnp.max(jnp.stack(errs))))
    return worst
