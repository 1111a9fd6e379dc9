"""Reference for ``dtd_gemm_f32_ooc``: C = K A B after K solves over an
accumulating C, at a size whose three matrices do not fit the chip. The
seeded operands are ``reference/gemm.py``'s (the graph is ``dtd_gemm_f32``'s;
only the scale differs); the comparison is this configuration's own.

It runs beside a full residency budget, so it is computed in blocks: one C
tile row of A against one tile column of B at a time, each slab put
together on the host and staged in one transfer, and freed before the next.
Two slabs of N x TS f32 (288 MiB each at N = 36864) and a few tiles are all
it holds on the device. A C tile is read from wherever its newest copy is:
a device array as it is, a written-back numpy array staged for the
comparison.

The bound is K * value * sqrt(N): the f32 summation error of one product of
unit normals grows like sqrt(N) whichever order the sum is taken in, and the
K accumulated solves add the same product K times. ``value`` sits between
two readings on the chip (the configuration's ``tolerance.readings``), so a
run one precision down (three bf16 passes for six) is not correct. What a
missed write-back would cost: a C tile short of one solve's product is off
by entries of size ~sqrt(N) = 192, 10^4 / K times the bound. Independent of
``parsec_tpu``."""

import numpy as np

from chipbench.reference.gemm import operand_tile, sample_rows  # noqa: F401


def tolerance(n, solves, value):
    return solves * value * n ** 0.5


def max_abs_err(c_tile, a_host, b_host, nt, rows, scale):
    """(max |C[m, n] - scale * A[m, :] B[:, n]| at ``HIGHEST``, the same
    against a reference at ``Precision.HIGH``) over the tile rows ``rows``
    and every column, on the default device. ``a_host``/``b_host`` are this
    benchmark's own host tiles, not read from the program's copies;
    ``c_tile(m, n)`` returns the program's result, on either side. The
    second value is the second reading of the tolerance: it has to lie
    above the bound."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tile_errs(c, a_row, b_col, k):
        def err(precision):
            return jnp.max(jnp.abs(
                c - k * jnp.dot(a_row, b_col, precision=precision)))
        return jnp.stack([err(jax.lax.Precision.HIGHEST),
                          err(jax.lax.Precision.HIGH)])

    k = jnp.float32(scale)     # traced: one program whatever the count
    worst = np.zeros(2)
    for m in rows:
        a_row = jnp.asarray(np.concatenate(
            [a_host[m, i] for i in range(nt)], axis=1))
        for n in range(nt):
            b_col = jnp.asarray(np.concatenate(
                [b_host[i, n] for i in range(nt)], axis=0))
            errs = tile_errs(jnp.asarray(c_tile(m, n)), a_row, b_col, k)
            worst = np.maximum(worst, np.asarray(errs))
            del b_col, errs     # the slab leaves before the next is staged
        del a_row
    return float(worst[0]), float(worst[1])
