"""flash_xla (fwd + custom VJP), KV caches, MLA absorbed decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests degrade to skips
from hypothesis import given, settings, strategies as st

from repro.models.attention import (
    AttnConfig,
    MLAConfig,
    attention_ref,
    attn_apply,
    attn_defs,
    decode_attention,
    flash_xla,
    init_cache,
    init_mla_cache,
    mla_apply,
    mla_defs,
)
from repro.models.params import init_params


def _qkv(s=96, h=4, kv=2, d=16, b=2):
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, kv, d))
    v = jax.random.normal(jax.random.key(2), (b, s, kv, d))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return q, k, v, pos


@given(
    s=st.sampled_from([17, 64, 100]),
    chunk=st.sampled_from([16, 32, 512]),
    causal=st.booleans(),
    window=st.sampled_from([None, 13]),
)
@settings(max_examples=16, deadline=None)
def test_flash_vs_ref_sweep(s, chunk, causal, window):
    q, k, v, pos = _qkv(s=s)
    got = flash_xla(q, k, v, pos, None, causal, window, chunk)
    want = attention_ref(q, k, v, pos, causal=causal, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_flash_custom_vjp_matches_autodiff():
    q, k, v, pos = _qkv(s=64)

    def f_flash(q, k, v):
        return jnp.sum(flash_xla(q, k, v, pos, None, True, None, 16) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, pos, causal=True) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_flash_kv_length_mask():
    q, k, v, pos = _qkv(s=64)
    got = flash_xla(q, k, v, pos, jnp.asarray(40), True, None, 16)
    want = attention_ref(q, k, v, pos, kv_length=jnp.asarray(40), causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("length", [11, 16], ids=["partial", "full"])
@pytest.mark.parametrize("window", [None, 4])
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (8, 1)])
def test_decode_attention_matches_ref(h, kv, window, length, dtype, tol):
    """One query token on a 16-position cache, each KV head serving its
    whole query group, equals the oracle over heads repeated to H."""
    b, skv, d = 3, 16, 8
    q = jax.random.normal(jax.random.key(0), (b, 1, h, d)).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (b, skv, kv, d)).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (b, skv, kv, d)).astype(dtype)
    pos = jnp.full((b, 1), length - 1, jnp.int32)
    kv_length = jnp.asarray(length, jnp.int32)
    got = decode_attention(q, k, v, pos, kv_length, window)
    want = attention_ref(q, k, v, pos, kv_length=kv_length, causal=False,
                         window=window)
    assert got.shape == q.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_gqa_cache_decode_matches_full():
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, chunk=16)
    params = init_params(attn_defs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    full, _ = attn_apply(params, x, pos, cfg)
    cache = init_cache(2, 16, 2, 8, jnp.float32)
    y, cache = attn_apply(params, x[:, :6], pos[:, :6], cfg, cache)
    np.testing.assert_allclose(y, full[:, :6], atol=1e-5, rtol=1e-4)
    for t in range(6, 12):
        y, cache = attn_apply(params, x[:, t : t + 1], pos[:, t : t + 1], cfg, cache)
    np.testing.assert_allclose(y[:, 0], full[:, -1], atol=1e-5, rtol=1e-4)


def test_sliding_window_cache_decode():
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                     sliding_window=4, chunk=8)
    params = init_params(attn_defs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 10, 32))
    pos = jnp.broadcast_to(jnp.arange(10)[None], (1, 10))
    full, _ = attn_apply(params, x, pos, cfg)
    cache = init_cache(1, 16, 2, 8, jnp.float32)
    y, cache = attn_apply(params, x[:, :9], pos[:, :9], cfg, cache)
    y, cache = attn_apply(params, x[:, 9:10], pos[:, 9:10], cfg, cache)
    np.testing.assert_allclose(y[:, 0], full[:, -1], atol=1e-5, rtol=1e-4)


def test_mla_decode_absorbed_matches_expanded():
    """The absorbed decode path must equal prefill-style expanded attention."""
    cfg = MLAConfig(d_model=32, n_heads=2, q_lora_rank=16, kv_lora_rank=16,
                    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, chunk=8)
    params = init_params(mla_defs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 9, 32))
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    full, _ = mla_apply(params, x, pos, cfg)
    cache = init_mla_cache(2, 16, cfg, jnp.float32)
    y, cache = mla_apply(params, x[:, :8], pos[:, :8], cfg, cache)
    np.testing.assert_allclose(y, full[:, :8], atol=1e-5, rtol=1e-4)
    # decode one token through the absorbed path
    y, cache = mla_apply(params, x[:, 8:9], pos[:, 8:9], cfg, cache)
    np.testing.assert_allclose(y[:, 0], full[:, 8], atol=1e-4, rtol=1e-3)


def test_mla_grads_flow():
    cfg = MLAConfig(d_model=32, n_heads=2, q_lora_rank=16, kv_lora_rank=16,
                    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    params = init_params(mla_defs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 8, 32))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (1, 8))

    def loss(p):
        y, _ = mla_apply(p, x, pos, cfg)
        return jnp.sum(y**2)

    g = jax.grad(loss)(params)
    gn = sum(float(jnp.sum(v**2)) for v in jax.tree.leaves(g))
    assert gn > 0 and np.isfinite(gn)
