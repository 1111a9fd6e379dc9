"""Pallas kernel tests (interpret mode on the CPU backend)."""

import numpy as np
import pytest

from parsec_tpu.ops import pallas_kernels as PK


def test_gemm_chain_matches_numpy():
    rng = np.random.default_rng(30)
    kt, ts = 4, 32
    c = rng.standard_normal((ts, ts)).astype(np.float32)
    a = rng.standard_normal((kt, ts, ts)).astype(np.float32)
    b = rng.standard_normal((kt, ts, ts)).astype(np.float32)
    out = np.asarray(PK.gemm_chain(c, a, b))
    ref = c + sum(a[k] @ b[k] for k in range(kt))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_blocked_matmul():
    rng = np.random.default_rng(31)
    a = rng.standard_normal((128, 64)).astype(np.float32)
    b = rng.standard_normal((64, 128)).astype(np.float32)
    out = np.asarray(PK.matmul(a, b, block=(64, 64, 32)))
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)


def test_blocked_matmul_odd_shapes_fallback():
    rng = np.random.default_rng(32)
    a = rng.standard_normal((100, 60)).astype(np.float32)
    b = rng.standard_normal((60, 90)).astype(np.float32)
    out = np.asarray(PK.matmul(a, b))
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)


def test_stencil_kernel_matches_reference():
    from parsec_tpu.ops.stencil import reference_stencil1d
    rng = np.random.default_rng(33)
    x = rng.standard_normal((1, 64)).astype(np.float32)
    z = np.zeros_like(x)
    out = np.asarray(PK.stencil1d(x, z, z))
    ref = reference_stencil1d(x, 1)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_stencil_kernel_with_halos():
    rng = np.random.default_rng(34)
    x = rng.standard_normal((1, 32)).astype(np.float32)
    l = rng.standard_normal((1, 32)).astype(np.float32)
    r = rng.standard_normal((1, 32)).astype(np.float32)
    out = np.asarray(PK.stencil1d(x, l, r))
    xm = np.concatenate([l[:, -1:], x[:, :-1]], axis=1)
    xp = np.concatenate([x[:, 1:], r[:, :1]], axis=1)
    np.testing.assert_allclose(out, 0.25 * xm + 0.5 * x + 0.25 * xp,
                               rtol=1e-5, atol=1e-5)


def test_verify_lowering_gate():
    """The compile-only gate lowers every kernel for the current backend,
    f32 and bf16, and returns ok for all (it RAISES on a lowering break —
    there is no XLA route to fall back to)."""
    from parsec_tpu.ops.pallas_kernels import verify_lowering
    results = verify_lowering(shapes=((128, 128, 128),), kt=2,
                              dtypes=("float32", "bfloat16"))
    assert len(results) == 8
    assert all(v == "ok" for v in results.values()), results


def test_pallas_failure_raises(monkeypatch):
    """A Pallas failure propagates: no entry point swaps in the XLA
    expression of the same math behind the caller's back."""
    import jax.numpy as jnp
    from parsec_tpu.ops import pallas_kernels as pk

    def boom(*a, **k):
        raise RuntimeError("mosaic lowering exploded")

    monkeypatch.setattr(pk, "_gemm_chain_call", boom)
    monkeypatch.setattr(pk, "_stencil_call", boom)
    c = jnp.zeros((8, 8), jnp.float32)
    a = jnp.ones((2, 8, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="mosaic lowering exploded"):
        pk.gemm_chain(c, a, a)
    with pytest.raises(RuntimeError, match="mosaic lowering exploded"):
        pk.stencil1d(c, c, c)


def _dense_attn(q, k, v, causal=False, q_off=0, k_off=0):
    d = q.shape[-1]
    s = np.einsum("bqd,bkd->bqk", q.astype(np.float64),
                  k.astype(np.float64)) / np.sqrt(d)
    if causal:
        qp = q_off + np.arange(q.shape[1])[:, None]
        kp = k_off + np.arange(k.shape[1])[None, :]
        s = np.where(kp <= qp, s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = np.where(np.isfinite(s), p, 0.0)
    a = p / np.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return np.einsum("bqk,bkd->bqd", a, v.astype(np.float64))


def test_flash_attention_matches_dense():
    rng = np.random.default_rng(34)
    q = rng.standard_normal((2, 128, 64)).astype(np.float32)
    k = rng.standard_normal((2, 128, 64)).astype(np.float32)
    v = rng.standard_normal((2, 128, 64)).astype(np.float32)
    out = np.asarray(PK.flash_attention(q, k, v, block_q=64, block_k=64))
    np.testing.assert_allclose(out, _dense_attn(q, k, v), rtol=2e-4,
                               atol=2e-4)


def test_flash_attention_causal():
    rng = np.random.default_rng(35)
    q = rng.standard_normal((1, 128, 32)).astype(np.float32)
    k = rng.standard_normal((1, 128, 32)).astype(np.float32)
    v = rng.standard_normal((1, 128, 32)).astype(np.float32)
    out = np.asarray(PK.flash_attention(q, k, v, causal=True, block_q=32,
                                        block_k=32))
    np.testing.assert_allclose(out, _dense_attn(q, k, v, causal=True),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bhsd_layout_and_rect_kv():
    """(B, H, S, D) input, cross-attention k/v longer than q."""
    rng = np.random.default_rng(36)
    q = rng.standard_normal((2, 3, 64, 32)).astype(np.float32)
    k = rng.standard_normal((2, 3, 192, 32)).astype(np.float32)
    v = rng.standard_normal((2, 3, 192, 32)).astype(np.float32)
    out = np.asarray(PK.flash_attention(q, k, v, block_q=32, block_k=64))
    assert out.shape == q.shape
    ref = _dense_attn(q.reshape(6, 64, 32), k.reshape(6, 192, 32),
                      v.reshape(6, 192, 32)).reshape(q.shape)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_ring_block_offsets():
    """Causal masking with global offsets: a later q shard attending a
    rotated k block must equal the same slice of full dense attention."""
    rng = np.random.default_rng(37)
    S, D = 256, 32
    q = rng.standard_normal((1, S, D)).astype(np.float32)
    k = rng.standard_normal((1, S, D)).astype(np.float32)
    v = rng.standard_normal((1, S, D)).astype(np.float32)
    full = _dense_attn(q, k, v, causal=True)
    # q shard [128:256) attending k block [0:128) then [128:256): fold the
    # two flash outputs with their stats replicated by calling on the
    # concatenated blocks (order must not matter for the final row sums)
    qs = q[:, 128:, :]
    out = np.asarray(PK.flash_attention(
        qs, k, v, causal=True, q_offset=128, k_offset=0,
        block_q=64, block_k=64))
    np.testing.assert_allclose(out, full[:, 128:, :], rtol=2e-4, atol=2e-4)
    # an entirely-above-diagonal k block contributes nothing: q shard 0
    # against k shard [128:) is all-masked -> uniform-of-nothing guard path
    out0 = np.asarray(PK.flash_attention(
        q[:, :128, :], k[:, 128:, :], v[:, 128:, :], causal=True,
        q_offset=0, k_offset=128, block_q=64, block_k=64))
    assert np.all(np.abs(out0) < 1e-6)


def test_flash_attention_bf16():
    import jax.numpy as jnp
    rng = np.random.default_rng(38)
    q = jnp.asarray(rng.standard_normal((1, 64, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 64, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 64, 64)), jnp.bfloat16)
    out = PK.flash_attention(q, k, v, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attn(np.asarray(q, np.float32), np.asarray(k, np.float32),
                      np.asarray(v, np.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, rtol=0.05,
                               atol=0.05)


def test_flash_attention_unaligned_offset_masked_rows():
    """k_offset-q_offset not a multiple of block_q: rows of a q block that
    are fully masked must output ZEROS, not uniform attention (regression:
    p = exp(s - m_new) = 1 when the whole row sits at the mask floor)."""
    rng = np.random.default_rng(40)
    q = rng.standard_normal((1, 64, 32)).astype(np.float32)
    k = rng.standard_normal((1, 64, 32)).astype(np.float32)
    v = rng.standard_normal((1, 64, 32)).astype(np.float32)
    out = np.asarray(PK.flash_attention(
        q, k, v, causal=True, q_offset=0, k_offset=32,
        block_q=64, block_k=32))
    ref = _dense_attn(q, k, v, causal=True, q_off=0, k_off=32)
    assert np.all(np.abs(out[:, :32]) < 1e-6)          # fully masked rows
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_prime_seq_routes_to_dense():
    """ADVICE r4: a prime sequence length (257) degrades the largest
    divisor block toward 1 — below tile granularity the dense XLA path is
    taken DELIBERATELY (not via the exception fallback) and must still be
    numerically correct."""
    rng = np.random.default_rng(41)
    q = rng.standard_normal((1, 257, 16)).astype(np.float32)
    k = rng.standard_normal((1, 257, 16)).astype(np.float32)
    v = rng.standard_normal((1, 257, 16)).astype(np.float32)
    out = np.asarray(PK.flash_attention(q, k, v, causal=True))
    ref = _dense_attn(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_small_seq_still_uses_pallas_path():
    """A short sequence (s < MIN_BLOCK) is a single whole-sequence block —
    viable, so the deliberate-routing gate must NOT trip."""
    rng = np.random.default_rng(42)
    q = rng.standard_normal((1, 4, 16)).astype(np.float32)
    k = rng.standard_normal((1, 4, 16)).astype(np.float32)
    v = rng.standard_normal((1, 4, 16)).astype(np.float32)
    out = np.asarray(PK.flash_attention(q, k, v))
    ref = _dense_attn(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
