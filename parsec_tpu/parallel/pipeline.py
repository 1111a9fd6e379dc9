"""Pipeline parallelism: GPipe-style microbatch streaming over a mesh axis.

Each device owns ONE stage's parameters (stage-major pytrees sharded over
``pp``); microbatches enter stage 0, activations hop one neighbor per tick
via ``lax.ppermute`` (the ICI ring), and after the P-1 fill ticks every
device computes every tick — the classic (M + P - 1)-tick GPipe schedule
expressed as one ``lax.scan`` inside ``shard_map``. The task runtime
expresses the same pattern as cross-rank chain deps (examples/ex03); this
is the compiler-scheduled, jittable form.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import numpy as np


def make_pp_mesh(n_devices: Optional[int] = None):
    from .spmd import make_1d_mesh
    return make_1d_mesh("pp", n_devices)


def init_pipeline_params(seed: int, n_stages: int, d: int,
                         dtype=np.float32):
    """Stage-major weights: one (W, b) per stage, leading axis = stage."""
    rng = np.random.default_rng(seed)
    s = np.sqrt(1.0 / d)
    return {
        "w": (rng.standard_normal((n_stages, d, d)) * s).astype(dtype),
        "b": np.zeros((n_stages, d), dtype),
    }


def stage_apply(w, b, x):
    """One pipeline stage: x -> gelu(x W + b) + x."""
    import jax
    return x + jax.nn.gelu(x @ w + b)


def reference_forward(params, x):
    """Sequential application of all stages (the single-device truth)."""
    import jax.numpy as jnp
    out = jnp.asarray(x)
    for i in range(params["w"].shape[0]):
        out = stage_apply(jnp.asarray(params["w"][i]),
                          jnp.asarray(params["b"][i]), out)
    return out


def _mlp_stage(sp, x):
    """The simple-MLP stage as a stage-pytree fn (the original pipeline)."""
    return stage_apply(sp["w"], sp["b"], x)


@functools.lru_cache(maxsize=None)
def _pipe_stages_call(mesh, n_micro: int, stage_fn: Callable,
                      replicate_out: bool = True):
    """The (M + P - 1)-tick GPipe schedule for an ARBITRARY stage pytree
    (leading axis = stage) and stage function
    ``stage_fn(stage_params, act) -> act`` — e.g. a group of transformer
    blocks. ``stage_fn`` must be jit-traceable and shape-preserving.
    Returns a ``run(sp, xs)`` whose jitted shard_map program is built ONCE
    per stage-pytree structure (jax's own trace cache handles shapes)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    nP = mesh.devices.size
    perm = [(i, (i + 1) % nP) for i in range(nP)]

    def local(sp, xs):
        idx = jax.lax.axis_index(axis)
        p0 = jax.tree_util.tree_map(lambda l: l[0], sp)   # my stage's slice
        # derive the zero bubble from a device-varying leaf so the scan
        # carry is varying from step 0 (manual-axes typing)
        zv = jax.tree_util.tree_leaves(p0)[0].ravel()[0] * 0.0
        act = jnp.zeros(xs.shape[1:], xs.dtype) + zv   # the in-flight bubble
        out = jnp.zeros_like(xs) + zv       # filled on the LAST stage

        def tick(carry, t):
            act, out = carry
            # stage 0 ingests microbatch t (while t < n_micro)
            feed = jnp.where(t < n_micro, 1.0, 0.0).astype(xs.dtype)
            mb = xs[jnp.minimum(t, n_micro - 1)]
            act = jnp.where(idx == 0, feed * mb, act)
            act = stage_fn(p0, act)
            # the LAST stage retires microbatch t-(P-1)
            done = t - (nP - 1)
            is_out = jnp.logical_and(idx == nP - 1, done >= 0)
            slot = jnp.maximum(done, 0)
            out = jnp.where(is_out, out.at[slot].set(act), out)
            act = jax.lax.ppermute(act, axis, perm)
            return (act, out), None

        (act, out), _ = jax.lax.scan(tick, (act, out),
                                     jnp.arange(n_micro + nP - 1))
        if replicate_out:
            # outputs live on the last stage only: everyone else holds
            # zeros, one psum replicates them. O(P·B·S·D) redundant ICI
            # traffic — acceptable for validation shapes, NOT at LM scale;
            # pass replicate_out=False to keep them resident where the
            # last stage computed them
            return jax.lax.psum(jnp.where(idx == nP - 1, out, 0.0), axis)
        return out          # stage-local: only the last stage's block is real

    def spec_of(leaf):
        return P(axis, *([None] * (leaf.ndim - 1)))

    jitted = {}     # one compiled wrapper per stage-pytree structure

    def run(sp, xs):
        key = (jax.tree_util.tree_structure(sp),
               tuple(l.ndim for l in jax.tree_util.tree_leaves(sp)))
        fn = jitted.get(key)
        if fn is None:
            in_specs = (jax.tree_util.tree_map(spec_of, sp), P())
            out_spec = P() if replicate_out else P(axis)
            fn = jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_spec))
            jitted[key] = fn
        return fn(sp, xs)

    return run


def pipeline_forward_stages(stage_params, x, stage_fn, mesh=None,
                            n_micro: Optional[int] = None,
                            replicate_out: bool = True):
    """GPipe over an arbitrary stage pytree: every leaf of
    ``stage_params`` has leading axis P (stage-major); device i runs
    ``stage_fn(stage_i_params, act)``. ``x``: (n_micro, B, ...)
    microbatches; returns the same shape. ``stage_fn`` must be a STABLE
    function object (module-level or cached) — it keys the compiled
    program cache.

    ``replicate_out=True`` (default) replicates the result to every stage
    with a psum — O(P·activations) ICI traffic, fine for validation
    shapes. ``replicate_out=False`` keeps the result SHARDED over the
    stage axis (only the last stage's shard is live), so downstream
    consumers (the LM head) read it where it was produced instead of
    paying a full replication every forward."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh if mesh is not None else make_pp_mesh()
    axis = mesh.axis_names[0]
    nP = mesh.devices.size
    leaves = jax.tree_util.tree_leaves(stage_params)
    assert leaves and all(l.shape[0] == nP for l in leaves), \
        f"every stage-params leaf needs leading axis {nP} (the stage axis)"
    xs = np.asarray(x) if not hasattr(x, "dtype") else x
    m = int(n_micro) if n_micro is not None else xs.shape[0]
    assert m <= xs.shape[0], \
        f"n_micro={m} exceeds the {xs.shape[0]} provided microbatches"
    xs = xs[:m]        # honor the (n_micro, B, ...) return contract exactly
    run = _pipe_stages_call(mesh, m, stage_fn, replicate_out)
    sp = jax.tree_util.tree_map(
        lambda l: jax.device_put(
            l, NamedSharding(mesh, P(axis, *([None] * (l.ndim - 1))))),
        stage_params)
    xd = jax.device_put(xs, NamedSharding(mesh, P()))
    res = run(sp, xd)
    if not replicate_out:
        # global shape (P·m, B, ...): block s is stage s's residue; only
        # the LAST block carries the pipeline's output. The slice is lazy
        # over the sharded array — it addresses the last stage's shard
        # without replicating the others
        res = res[(nP - 1) * m:]
    return res


def pipeline_forward(params, x, mesh=None, n_micro: Optional[int] = None):
    """Run (n_micro, B, d) microbatches through the P-stage MLP pipeline
    (the :func:`pipeline_forward_stages` schedule with the simple-MLP
    stage). ``params['w']``: (P, d, d) — stage i's weights live on
    device i. Returns (n_micro, B, d), matching :func:`reference_forward`
    applied per microbatch within float32 tolerance."""
    mesh = mesh if mesh is not None else make_pp_mesh()
    nP = mesh.devices.size
    assert params["w"].shape[0] == nP, \
        f"{params['w'].shape[0]} stages need a {params['w'].shape[0]}-device" \
        f" mesh (have {nP})"
    return pipeline_forward_stages(
        {"w": params["w"], "b": params["b"]}, x, _mlp_stage, mesh=mesh,
        n_micro=n_micro)
