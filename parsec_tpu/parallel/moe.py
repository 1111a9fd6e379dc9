"""Expert parallelism: a top-k routed MoE layer over a mesh axis.

The GShard/Switch dispatch pattern, TPU-native: tokens are data-sharded
over ``ep``; a router scores every local token, the top-k experts per token
are packed into fixed-capacity per-expert buffers (one-hot dispatch einsum
— static shapes, MXU-friendly), ``lax.all_to_all`` ships each expert's
slice to the device that OWNS that expert, the expert MLPs run local and
dense, and a second all_to_all brings results home where the combine
einsum unpacks and gate-weights them. Capacity >= local tokens means no
drops, which makes the layer bit-comparable to its dense equivalent (the
tests' invariant); tighter capacities drop overflow tokens with the drop
COUNT reported, and the Switch-style auxiliary load-balancing loss is
computed over the global batch (psum across the mesh).

Routing follows the standard recipes: top-1 gates with the raw router
probability (Switch); top-k>=2 renormalizes the k gates to sum to one
(GShard/Mixtral). Slot assignment is choice-major — every token's first
choice claims buffer slots before any second choice — so under pressure
drops hit lower-priority routes first, as in GShard.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np


def make_ep_mesh(n_devices: Optional[int] = None):
    from .spmd import make_1d_mesh
    return make_1d_mesh("ep", n_devices)


def init_moe_params(seed: int, n_experts: int, d: int, d_ff: int,
                    dtype=np.float32):
    """Router + per-expert 2-layer MLPs (expert-major leading axis)."""
    rng = np.random.default_rng(seed)

    def g(*shape, fan):
        return (rng.standard_normal(shape) / np.sqrt(fan)).astype(dtype)

    return {
        "router": g(d, n_experts, fan=d),
        "w1": g(n_experts, d, d_ff, fan=d),
        "w2": g(n_experts, d_ff, d, fan=d_ff),
    }


def _expert_mlp(w1, w2, x):
    import jax
    return jax.nn.gelu(x @ w1) @ w2


def _topk_gates(probs, k: int):
    """(gates, expert ids), both (T, k): raw top-1 prob for k=1 (Switch),
    renormalized over the k winners for k>=2 (GShard/Mixtral)."""
    import jax
    import jax.numpy as jnp
    gate_k, eid_k = jax.lax.top_k(probs, k)
    if k > 1:
        gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)
    return gate_k, eid_k


def dense_reference(params, x, k: int = 1):
    """Every token through its top-k routed experts, no parallelism (the
    truth the expert-parallel layer must match when nothing is dropped)."""
    import jax.numpy as jnp
    xt = jnp.asarray(x)
    logits = xt @ params["router"]
    import jax
    gate_k, eid_k = _topk_gates(jax.nn.softmax(logits, axis=-1), k)
    E = params["w1"].shape[0]
    out = jnp.zeros_like(xt)
    for e in range(E):
        y = _expert_mlp(jnp.asarray(params["w1"][e]),
                        jnp.asarray(params["w2"][e]), xt)
        w = (gate_k * (eid_k == e)).sum(-1)          # this expert's gate
        out = out + y * w[:, None]
    return out


@functools.lru_cache(maxsize=None)
def _moe_call(mesh, capacity: int, experts_per_dev: int, k: int):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    nP = mesh.devices.size

    def local(router, w1, w2, xb):
        # xb: (T_loc, D) this device's tokens; w1/w2: this device's experts
        T, D = xb.shape
        E = nP * experts_per_dev
        logits = xb @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_k, eid_k = _topk_gates(probs, k)                  # (T, k)
        # choice-major slot assignment: flatten (k, T) so every token's
        # 1st choice claims capacity before any 2nd choice (GShard
        # priority); cumsum over that order numbers the slots
        oh = jax.nn.one_hot(eid_k, E, dtype=xb.dtype)          # (T, k, E)
        ohf = jnp.moveaxis(oh, 1, 0).reshape(k * T, E)         # (kT, E)
        posf = (jnp.cumsum(ohf, axis=0) - 1.0) * ohf
        keepf = ohf * (posf < capacity).astype(xb.dtype)
        dropped = ohf.sum() - keepf.sum()                      # local drops
        dispf = keepf[..., None] * jax.nn.one_hot(
            posf.astype(jnp.int32), capacity, dtype=xb.dtype)  # (kT, E, C)
        disp = jnp.moveaxis(dispf.reshape(k, T, E, capacity), 0, 1)
        dispatch = disp.sum(1)                   # (T, E, C) raw packing
        combine = jnp.einsum("tkec,tk->tec", disp, gate_k)   # gate-weighted
        # pack per global expert, grouped by owning device
        buf = jnp.einsum("td,tec->ecd", xb, dispatch)          # (E, C, D)
        buf = buf.reshape(nP, experts_per_dev, capacity, D)
        # ship slice [dst] to device dst; recv[s, e] = source s's tokens
        # for MY local expert e
        recv = jax.lax.all_to_all(buf, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        work = jnp.moveaxis(recv, 0, 1).reshape(
            experts_per_dev, nP * capacity, D)
        done = jnp.stack([_expert_mlp(w1[e], w2[e], work[e])
                          for e in range(experts_per_dev)])
        done = done.reshape(experts_per_dev, nP, capacity, D)
        # return trip: slice [src] goes home to device src; ret[d, e] =
        # device d's local expert e results for MY tokens — which is
        # exactly the (global expert, capacity) layout dispatch used
        ret = jax.lax.all_to_all(jnp.moveaxis(done, 1, 0), axis,
                                 split_axis=0, concat_axis=0, tiled=True)
        y = jnp.einsum("ecd,tec->td", ret.reshape(E, capacity, D), combine)
        # Switch aux load-balancing loss over the GLOBAL batch:
        # E * sum_e f_e * p_e, f_e = fraction of tokens whose TOP-1 is e,
        # p_e = mean router prob for e (both psum-averaged over the mesh)
        top1 = jax.nn.one_hot(eid_k[:, 0], E, dtype=jnp.float32)
        f = jax.lax.psum(top1.sum(0), axis) / (T * nP)
        p = jax.lax.psum(probs.astype(jnp.float32).sum(0), axis) / (T * nP)
        aux = E * jnp.sum(f * p)
        return y, aux, jax.lax.psum(dropped, axis)

    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None, None),
                  P(axis, None)),
        out_specs=(P(axis, None), P(), P())))


def moe_forward(params, x, mesh=None, capacity: Optional[int] = None,
                k: int = 1, capacity_factor: Optional[float] = None,
                return_aux: bool = False):
    """Expert-parallel forward of the top-k routed MoE layer.

    ``x``: (tokens, d) global; tokens must divide the mesh size, experts
    must divide the mesh size (``experts_per_dev`` each), ``k`` <= experts.
    Per-expert buffer capacity, in priority order:

    * ``capacity`` — explicit slots per (expert, source device);
    * ``capacity_factor`` — ``ceil(cf * k * T_loc / E)`` slots, the GShard
      convention (cf=1.0 is "fair share", cf>1 headroom);
    * default — ``T_loc`` slots: no token can be dropped, and the result
      matches :func:`dense_reference` exactly.

    ``return_aux=True`` also returns ``{"aux_loss", "dropped"}`` — the
    Switch load-balancing loss over the global batch (add
    ``lambda * aux_loss`` to the training objective) and the global count
    of routed (token, choice) pairs that overflowed capacity.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh if mesh is not None else make_ep_mesh()
    axis = mesh.axis_names[0]
    nP = mesh.devices.size
    T, D = x.shape
    E = params["w1"].shape[0]
    assert T % nP == 0 and E % nP == 0
    assert 1 <= k <= E, f"top-{k} routing needs k in [1, {E}]"
    t_loc = T // nP
    if capacity is not None:
        cap = int(capacity)
    elif capacity_factor is not None:
        cap = max(1, math.ceil(capacity_factor * k * t_loc / E))
    else:
        cap = t_loc
    fn = _moe_call(mesh, cap, E // nP, k)
    import jax.core
    leaves = [params["router"], params["w1"], params["w2"], x]
    if any(isinstance(v, jax.core.Tracer) for v in leaves):
        # under an outer jit/grad trace: no host-side placement — the
        # shard_map in_specs become sharding constraints and gradients
        # flow through dispatch/combine (the MoE-LM training path)
        y, aux, dropped = fn(params["router"], params["w1"],
                             params["w2"], x)
    else:
        ns = lambda spec: NamedSharding(mesh, spec)
        rd = jax.device_put(params["router"], ns(P()))
        w1 = jax.device_put(params["w1"], ns(P(axis, None, None)))
        w2 = jax.device_put(params["w2"], ns(P(axis, None, None)))
        xd = jax.device_put(np.asarray(x), ns(P(axis, None)))
        y, aux, dropped = fn(rd, w1, w2, xd)
    if return_aux:
        return y, {"aux_loss": aux, "dropped": dropped}
    return y
