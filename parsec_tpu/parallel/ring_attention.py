"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The long-context half of the framework (first-class here even though the
reference has no sequence dimension at all — SURVEY §5 "Long-context":
absent; its chain-pipeline broadcasts + neighbor deps are the moral
pattern, stencil_1D.jdf). Two TPU-native schemes over one
``jax.sharding.Mesh`` axis:

* :func:`ring_attention` — the sequence axis stays sharded; K/V blocks
  rotate around the ring via ``lax.ppermute`` (ICI neighbor hops, fully
  overlapped by XLA with the per-step matmuls) while each device folds
  every block into a numerically-stable online softmax (the
  flash/blockwise accumulation: running max + rescaled sum). Memory per
  chip stays O(S/P · S/P); no materialized S×S attention matrix, ever.
  Causal masking works on global positions reconstructed from the ring
  step, and fully-masked early blocks contribute nothing.
* :func:`ulysses_attention` — the all-to-all scheme: resharding seq→heads
  via ``lax.all_to_all``, dense per-head attention locally, then
  heads→seq back. Two A2As instead of P-1 neighbor hops; wins when
  H >= P and the sequence blocks are small.

Both are pure ``shard_map`` programs: pick the mesh, annotate the
shardings, let XLA insert the collectives (the scaling-book recipe).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np


def _seq_mesh(n_devices: Optional[int] = None):
    """A 1D mesh over the sequence-parallel axis ``sp``."""
    from .spmd import make_1d_mesh
    return make_1d_mesh("sp", n_devices)


def _fold_block(acc, k, v, src, q, scale, causal, q_pos, k_pos0, block):
    """Fold the resident K/V block into the (o, m, l) online softmax."""
    import jax.numpy as jnp
    o, m, l = acc
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        kp = src * block + k_pos0                      # global key positions
        mask = kp[None, None, None, :] <= q_pos[None, None, :, None]
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # exp(-inf - -inf) guards: a fully-masked row keeps m=-inf, p=0
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m_new, l


@functools.lru_cache(maxsize=None)
def _ring_call(mesh, causal: bool, block: int, scale: float):
    """One compiled shard_map program per (mesh, causal, block, scale) —
    every attention layer / training step reuses it (jax.Mesh is
    hashable; jit's own cache handles the remaining shape signature)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    nP = mesh.devices.size
    perm = [(i, (i + 1) % nP) for i in range(nP)]

    def local(qb, kb, vb):
        idx = jax.lax.axis_index(axis)
        q_pos = idx * block + jnp.arange(block)
        k_pos0 = jnp.arange(block)
        o = jnp.zeros_like(qb)
        # derive from qb so the carry is device-varying from step 0 (the
        # shard_map manual-axes type system requires carry-in == carry-out)
        m = qb[..., 0] * 0.0 - jnp.inf
        l = qb[..., 0] * 0.0
        fold = functools.partial(_fold_block, q=qb, scale=scale,
                                 causal=causal, q_pos=q_pos, k_pos0=k_pos0,
                                 block=block)
        # fold the resident block, then P-1 x (rotate, fold): exactly the
        # P-1 neighbor hops the ring needs, none wasted
        acc = fold((o, m, l), kb, vb, idx)

        def step(carry, _):
            acc, k, v, src = carry
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
            src = jax.lax.ppermute(src, axis, perm)
            return (fold(acc, k, v, src), k, v, src), None

        if nP > 1:
            (acc, _, _, _), _ = jax.lax.scan(
                step, (acc, kb, vb, idx), None, length=nP - 1)
        o, m, l = acc
        safe_l = jnp.where(l > 0, l, 1.0)
        return o / safe_l[..., None]

    spec = P(None, None, axis, None)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec))


def ring_attention(q, k, v, mesh=None, causal: bool = False,
                   scale: Optional[float] = None):
    """Multi-head attention with the sequence axis sharded over the mesh.

    ``q``/``k``/``v``: (batch, heads, seq, head_dim) global arrays (host or
    device); the mesh size must divide seq. Returns the attention output
    with the same global shape and sharding.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh if mesh is not None else _seq_mesh()
    nP = mesh.devices.size
    B, H, S, D = q.shape
    assert S % nP == 0, f"the {nP}-device mesh must divide seq {S}"
    block = S // nP
    sc = scale if scale is not None else 1.0 / float(np.sqrt(D))
    fn = _ring_call(mesh, causal, block, sc)
    sharding = NamedSharding(mesh, P(None, None, mesh.axis_names[0], None))
    qd, kd, vd = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(qd, kd, vd)


@functools.lru_cache(maxsize=None)
def _ulysses_call(mesh, causal: bool, scale: float):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    sc = scale

    def local(qb, kb, vb):
        # (B, H, S/P, D) -> all_to_all -> (B, H/P, S, D)
        def a2a(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)
        qh, kh, vh = a2a(qb), a2a(kb), a2a(vb)
        # full sequence per device after the A2A: the fused flash kernel
        # streams k/v blocks through VMEM (falls back to the XLA
        # expression of the same math off-TPU); vma types the output as
        # device-varying for the shard_map checker
        from ..ops.pallas_kernels import flash_attention
        oh = flash_attention(qh, kh, vh, causal=causal, scale=sc,
                             vma=(axis,))
        # back: (B, H/P, S, D) -> (B, H, S/P, D)
        return jax.lax.all_to_all(oh, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    spec = P(None, None, axis, None)
    # check_vma=False: pallas interpret mode cannot yet discharge a
    # vma-typed pallas_call (jax raises "dynamic_slice requires varying
    # manual axes to match ... as a temporary workaround pass
    # check_vma=False"); the kernel still declares vma on its output so
    # re-enabling the checker is a one-line change when jax supports it.
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False))


def ulysses_attention(q, k, v, mesh=None, causal: bool = False,
                      scale: Optional[float] = None):
    """All-to-all (Ulysses) sequence parallelism: reshard seq->heads, run
    dense attention per device on full sequences of H/P heads, reshard
    back. The mesh size must divide both heads and seq."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh if mesh is not None else _seq_mesh()
    nP = mesh.devices.size
    B, H, S, D = q.shape
    assert H % nP == 0, f"the {nP}-device mesh must divide heads {H}"
    assert S % nP == 0, f"the {nP}-device mesh must divide seq {S}"
    sc = scale if scale is not None else 1.0 / float(np.sqrt(D))
    fn = _ulysses_call(mesh, causal, sc)
    sharding = NamedSharding(mesh, P(None, None, mesh.axis_names[0], None))
    qd, kd, vd = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(qd, kd, vd)


def dense_attention_reference(q, k, v, causal: bool = False,
                              scale: Optional[float] = None):
    """Single-device reference for the tests."""
    import jax.numpy as jnp
    D = q.shape[-1]
    sc = scale if scale is not None else 1.0 / float(np.sqrt(D))
    s = jnp.einsum("bhqd,bhkd->bhqk", jnp.asarray(q), jnp.asarray(k)) * sc
    if causal:
        S = s.shape[-1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    import jax
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", a, jnp.asarray(v))
