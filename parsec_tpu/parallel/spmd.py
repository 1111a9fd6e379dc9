"""SPMD execution paths over TPU meshes.

This is the TPU-native embodiment of the reference's distribution machinery
(SURVEY §2.8): where PaRSEC pairs owner-computes collections
(two_dim_rectangle_cyclic.c) with per-dep multicast trees
(remote_dep.c:322-411, chain-pipeline/binomial over rank-bit masks), the TPU
framework lays the P×Q process grid directly over the ICI mesh axes and lets
XLA collectives carry the dataflow:

* :func:`distributed_gemm` — Cannon's algorithm under ``shard_map``:
  pre-skew, then T steps of (local MXU dot, neighbor ``ppermute``). All
  traffic is nearest-neighbor on the torus — the moral equivalent of the
  reference's chain-pipelined broadcast, with zero host involvement.
* :func:`distributed_gemm_allgather` — the bandwidth-optimal 2-collective
  variant (all_gather row/col panels, one local dot); XLA overlaps the
  gathers with compute.
* :func:`distributed_potrf` — right-looking blocked Cholesky: per-k jitted
  shard_map step (panel factor + broadcast + trailing SYRK/GEMM update),
  host loop over k. The broadcast of the panel is an ``all_gather`` along
  one mesh axis = the reference's multicast tree ridden by the torus.

These functions double as the driver's multi-chip dry-run payload
(``__graft_entry__.dryrun_multichip``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import numpy as np


def _jax():
    import jax
    return jax


def best_grid(n: int) -> Tuple[int, int]:
    """Most-square P×Q factorization of n (grid helper, ref grid_2Dcyclic.c)."""
    p = int(math.sqrt(n))
    while n % p:
        p -= 1
    return p, n // p


def make_1d_mesh(axis_name: str, n_devices: Optional[int] = None):
    """A 1D mesh over the first n devices (the seq/pipeline/expert axis
    builder shared by ring_attention/pipeline/moe)."""
    jax = _jax()
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices for axis {axis_name!r}, "
                         f"have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]), (axis_name,))


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Tuple[str, str] = ("p", "q")):
    """Build a 2D device mesh over the available chips.

    On a real pod the default device order follows the ICI torus so that
    adjacent mesh coordinates are physical neighbors.
    """
    jax = _jax()
    devs = jax.devices()
    n = n_devices or len(devs)
    P, Q = best_grid(n)
    arr = np.array(devs[:n]).reshape(P, Q)
    return jax.sharding.Mesh(arr, axis_names)


def distributed_gemm(A, B, mesh=None, dtype=None):
    """C = A @ B via Cannon's algorithm on a P×P mesh slice.

    Per step: one local tile dot (MXU) + one neighbor ppermute per operand
    (ICI). Requires a square grid; falls back to the all-gather variant
    otherwise.
    """
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if mesh is None:
        mesh = make_mesh()
    Pm, Qm = mesh.devices.shape
    if Pm != Qm:
        return distributed_gemm_allgather(A, B, mesh, dtype)
    T = Pm

    # pre-skew permutations over the flattened (p, q) rank space: block (p, j)
    # moves to (p, (j - p) % T); (i, q) to ((i - q) % T, q). Static — the
    # compiler schedules them as one collective-permute each.
    skew_a = [(p * T + j, p * T + (j - p) % T)
              for p in range(T) for j in range(T)]
    skew_b = [(i * T + q, ((i - q) % T) * T + q)
              for i in range(T) for q in range(T)]

    def body(a_blk, b_blk):
        a = jax.lax.ppermute(a_blk, ("p", "q"), skew_a)
        b = jax.lax.ppermute(b_blk, ("p", "q"), skew_b)

        def step(carry, _):
            a, b, acc = carry
            acc = acc + jnp.dot(a, b, preferred_element_type=jnp.float32)
            a = jax.lax.ppermute(a, "q", [(j, (j - 1) % T) for j in range(T)])
            b = jax.lax.ppermute(b, "p", [(i, (i - 1) % T) for i in range(T)])
            return (a, b, acc), None

        acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
        # type the replicated zeros as device-varying for the VMA checker
        acc = jax.lax.pcast(acc, ("p", "q"), to="varying")
        (_, _, acc), _ = jax.lax.scan(step, (a, b, acc), None, length=T)
        return acc.astype(a_blk.dtype if dtype is None else dtype)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P("p", "q"), P("p", "q")),
                   out_specs=P("p", "q"))
    return jax.jit(fn)(A, B)


def distributed_gemm_allgather(A, B, mesh=None, dtype=None):
    """C = A @ B with row/col panel all_gathers + one local dot.

    C[p,q] = (gather_q A[p,:]) @ (gather_p B[:,q]) — two collectives total;
    XLA overlaps the gathers with the dot's first steps.
    """
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if mesh is None:
        mesh = make_mesh()

    def body(a_blk, b_blk):
        a_row = jax.lax.all_gather(a_blk, "q", axis=1, tiled=True)
        b_col = jax.lax.all_gather(b_blk, "p", axis=0, tiled=True)
        out = jnp.dot(a_row, b_col, preferred_element_type=jnp.float32)
        return out.astype(a_blk.dtype if dtype is None else dtype)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P("p", "q"), P("p", "q")),
                   out_specs=P("p", "q"))
    return jax.jit(fn)(A, B)


def distributed_potrf(A, mesh=None, block: Optional[int] = None):
    """Blocked right-looking Cholesky (lower) over the mesh.

    Layout: A is ("p", "q")-sharded. Each outer step k:
      1. the owner block row factors the diagonal block (replicated cholesky
         of a small gathered block — the panel),
      2. panel broadcast = all_gather along the mesh axes (the multicast
         tree of the reference, ridden by the torus),
      3. trailing update A22 -= L21 L21^T runs fully sharded (MXU + psum).

    The per-k step is one jitted shard_map program; the k loop stays on host
    exactly like the reference's task DAG unrolls over k. Returns the lower
    Cholesky factor with the strict upper triangle zeroed.
    """
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    if mesh is None:
        mesh = make_mesh()
    n = A.shape[0]
    nb = block or max(A.shape[0] // (mesh.devices.shape[0] * 4), 128)
    nb = min(nb, n)

    sharding = jax.sharding.NamedSharding(mesh, P("p", "q"))
    A = jax.device_put(A, sharding)

    @partial(jax.jit, static_argnames=("nb",))
    def step(A, k, nb: int):
        # panel column [*, k:k+nb] is small (n x nb); k is a traced scalar so
        # one executable serves every outer iteration
        panel = jax.lax.dynamic_slice(A, (0, k), (n, nb))
        akk = jax.lax.dynamic_slice(panel, (k, 0), (nb, nb))
        lkk = jnp.linalg.cholesky(akk)
        l21 = jax.scipy.linalg.solve_triangular(lkk, panel.T, lower=True).T
        rows = jnp.arange(n)[:, None]
        l21 = jnp.where(rows >= k + nb, l21, 0.0)   # only rows below the block
        newpanel = jax.lax.dynamic_update_slice(l21, lkk, (k, 0))
        A = jax.lax.dynamic_update_slice(A, newpanel, (0, k))
        # trailing update: A -= l21 @ l21^T restricted to the trailing block
        upd = jnp.dot(l21, l21.T, preferred_element_type=jnp.float32).astype(A.dtype)
        cols = jnp.arange(n)[None, :]
        mask = (rows >= k + nb) & (cols >= k + nb)
        A = A - jnp.where(mask, upd, 0.0)
        return A

    nsteps = n // nb
    for i in range(nsteps):
        A = step(A, i * nb, nb)
    tail = n - nsteps * nb
    if tail:
        A = A.at[nsteps * nb:, nsteps * nb:].set(
            jnp.linalg.cholesky(A[nsteps * nb:, nsteps * nb:]))
    return jnp.tril(A)


def training_step(A, B, C, mesh=None):
    """One flagship 'step': C += A@B then Cholesky-factor a diagonal block.

    This is the driver-facing composite (the framework's unit of useful work:
    the GEMM+POTRF mix of the headline benchmarks) expressed fully SPMD.
    """
    jax = _jax()
    import jax.numpy as jnp

    C2 = distributed_gemm_allgather(A, B, mesh)
    C2 = C + C2
    # SPD-ify the result then factor: exercises cholesky + triangular solves
    sym = C2 @ C2.T / C2.shape[0] + jnp.eye(C2.shape[0], dtype=C2.dtype) * 2.0
    L = jnp.linalg.cholesky(sym)
    return C2, L
