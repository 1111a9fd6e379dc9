"""Reference for ``ptg_gemm_f32``: C = A B, once, from zero. The seeded
operands are ``reference/gemm.py``'s (the DAG is ``dtd_gemm_f32``'s; only the
front end and the lanes differ), the comparison is this configuration's own:
C does not accumulate over solves, so the bound is the f32 summation error of
ONE product and is tight enough that a reference at ``Precision.HIGH`` (three
bf16 passes for six) falls outside it. Independent of ``parsec_tpu``."""

from chipbench.reference.gemm import operand_tile, sample_rows  # noqa: F401


def tolerance(n, value):
    """``value * sqrt(N)``: the error of an f32 sum of N products of unit
    normals grows like sqrt(N) whichever order the sum is taken in."""
    return value * n ** 0.5


def max_abs_err(c_tile, a_host, b_host, nt, rows, precision="highest"):
    """max |C[m, n] - A[m, :] B[:, n]| over the tile rows ``rows`` and every
    column, against one ``jnp.dot`` of the matching slabs at ``precision``
    on the default device. ``a_host``/``b_host`` are this benchmark's own
    host tiles, staged here and not read from the program's copies;
    ``c_tile(m, n)`` returns the program's result."""
    import jax
    import jax.numpy as jnp

    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH}[precision]

    @jax.jit
    def tile_err(c, a_row, b_col):
        return jnp.max(jnp.abs(c - jnp.dot(a_row, b_col, precision=prec)))

    a_rows = {m: jnp.concatenate([jnp.asarray(a_host[m, k])
                                  for k in range(nt)], axis=1) for m in rows}
    worst = 0.0
    for n in range(nt):
        b_col = jnp.concatenate([jnp.asarray(b_host[k, n])
                                 for k in range(nt)], axis=0)
        errs = [tile_err(jnp.asarray(c_tile(m, n)), a_rows[m], b_col)
                for m in rows]
        worst = max(worst, float(jnp.max(jnp.stack(errs))))
    return worst
