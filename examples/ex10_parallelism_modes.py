"""Ex10: the five parallelism modes on one virtual mesh.

Runs each of dp/tp (transformer training step), pp (GPipe pipeline),
ep (routed MoE), and sp (ring attention) against its single-device
reference — the scaling-book recipe end to end: pick a mesh, annotate
shardings, let XLA insert the collectives.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/ex10_parallelism_modes.py
"""
from _common import setup


def main():
    setup()
    import numpy as np

    from parsec_tpu.parallel.moe import (dense_reference, init_moe_params,
                                         make_ep_mesh, moe_forward)
    from parsec_tpu.parallel.pipeline import (init_pipeline_params,
                                              make_pp_mesh, pipeline_forward,
                                              reference_forward)
    from parsec_tpu.parallel.ring_attention import (
        dense_attention_reference, ring_attention)
    from parsec_tpu.parallel.transformer import (
        init_block_params, make_tp_mesh, make_train_step)

    import jax
    n = len(jax.devices())
    rng = np.random.default_rng(0)

    # dp x tp: train a transformer block
    mesh = make_tp_mesh(tp_must_divide=4)
    dpn, tpn = mesh.devices.shape
    step, place_p, place_x = make_train_step(mesh, lr=5e-2)
    p = place_p(init_block_params(0, d_model=16, d_ff=32, n_heads=4))
    x = place_x(rng.standard_normal((2 * dpn, 8, 16)).astype(np.float32))
    y = place_x(rng.standard_normal((2 * dpn, 8, 16)).astype(np.float32))
    losses = []
    for _ in range(5):
        p, loss = step(p, x, y)
        losses.append(float(loss))
    print(f"dp{dpn} x tp{tpn} train step: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0]

    # pp: GPipe pipeline
    pparams = init_pipeline_params(0, n, 8)
    px = rng.standard_normal((4, 2, 8)).astype(np.float32)
    pout = pipeline_forward(pparams, px)
    pref = np.stack([np.asarray(reference_forward(pparams, px[i]))
                     for i in range(4)])
    np.testing.assert_allclose(np.asarray(pout), pref, rtol=2e-5, atol=2e-5)
    print(f"pp: {n}-stage pipeline == sequential")

    # ep: top-2 routed MoE with the Switch aux load-balance loss
    mp = init_moe_params(0, n, 8, 16)
    mx = rng.standard_normal((4 * n, 8)).astype(np.float32)
    mout, maux = moe_forward(mp, mx, k=2, return_aux=True)
    np.testing.assert_allclose(np.asarray(mout),
                               np.asarray(dense_reference(mp, mx, k=2)),
                               rtol=2e-4, atol=2e-5)
    print(f"ep: {n} experts over {n} devices, top-2 == dense routing "
          f"(aux={float(maux['aux_loss']):.2f}, "
          f"dropped={int(maux['dropped'])})")

    # sp: causal ring attention
    q, k, v = (rng.standard_normal((1, 2, 8 * n, 8)).astype(np.float32)
               for _ in range(3))
    r = ring_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(r),
        np.asarray(dense_attention_reference(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)
    print(f"sp: causal ring attention seq={8*n} over {n} devices == dense")


if __name__ == "__main__":
    main()
