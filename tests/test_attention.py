"""Chunked attention + decode attention vs the naive oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.models.attention import (chunked_attention, combine_partial,
                                    decode_attention, finalize_partial)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [0, 16])
def test_chunked_vs_ref(hq, hkv, window):
    k = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(k, 1), (2, hq, 64, 32))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (2, hkv, 64, 32))
    v = jax.random.normal(jax.random.fold_in(k, 3), (2, hkv, 64, 32))
    o = chunked_attention(q, kk, v, causal=True, window=window,
                          q_chunk=16, kv_chunk=16)
    o_ref = ref.attention_ref(q, kk, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(st.sampled_from([17, 33, 48, 96]), st.sampled_from([8, 16, 32]))
def test_chunked_odd_seq_lengths(s, chunk):
    """_fit chunking handles non-power-of-two sequence lengths."""
    k = jax.random.key(1)
    q = jax.random.normal(jax.random.fold_in(k, 1), (1, 2, s, 16))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (1, 2, s, 16))
    v = jax.random.normal(jax.random.fold_in(k, 3), (1, 2, s, 16))
    o = chunked_attention(q, kk, v, causal=True, q_chunk=chunk, kv_chunk=chunk)
    o_ref = ref.attention_ref(q, kk, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_matches_full_row():
    """decode at position p == row p of full causal attention."""
    k = jax.random.key(2)
    b, hq, hkv, s, d = 2, 4, 2, 32, 16
    q_all = jax.random.normal(jax.random.fold_in(k, 1), (b, hq, s, d))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (b, hkv, s, d))
    v = jax.random.normal(jax.random.fold_in(k, 3), (b, hkv, s, d))
    full = ref.attention_ref(q_all, kk, v, causal=True)
    p = 20
    o, m, l = decode_attention(q_all[:, :, p, :], kk, v,
                               jnp.arange(s), p + 1)
    o = finalize_partial(o, m, l)
    np.testing.assert_allclose(np.asarray(o), np.asarray(full[:, :, p]),
                               rtol=2e-5, atol=2e-5)


def test_split_kv_combine_equals_single_shard():
    """Partial-softmax combine over KV splits == direct attention (the
    move-compute decode path's math)."""
    k = jax.random.key(3)
    b, hq, hkv, s, d = 1, 2, 2, 64, 16
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, hq, d))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (b, hkv, s, d))
    v = jax.random.normal(jax.random.fold_in(k, 3), (b, hkv, s, d))
    cache_len = 50
    o_ref_, m_, l_ = decode_attention(q, kk, v, jnp.arange(s), cache_len)
    o_ref_ = finalize_partial(o_ref_, m_, l_)
    # simulate 4 shards, combine manually with the same math
    parts = []
    for i in range(4):
        sl = slice(i * 16, (i + 1) * 16)
        o, m, l = decode_attention(q, kk[:, :, sl], v[:, :, sl],
                                   jnp.arange(s)[sl], cache_len)
        parts.append((o, m, l))
    m_g = jnp.max(jnp.stack([p[1] for p in parts]), 0)
    o_sum = sum(p[0] * jnp.exp(p[1] - m_g)[..., None] for p in parts)
    l_sum = sum(p[2] * jnp.exp(p[1] - m_g) for p in parts)
    o_comb = o_sum / jnp.maximum(l_sum, 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(o_comb), np.asarray(o_ref_),
                               rtol=2e-5, atol=2e-5)


def test_ring_buffer_decode_window():
    """Ring-buffer cache slot/position math for local attention decode."""
    from repro.configs.base import ModelConfig
    from repro.models.decode import _ring_positions
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=8,
                      num_heads=1, num_kv_heads=1, head_dim=8, d_ff=8,
                      vocab_size=16, attn_window=4)
    pos = jnp.asarray(6)  # positions 3,4,5,6 live in the ring
    kv_pos = _ring_positions(cfg, pos, 4)
    assert sorted(np.asarray(kv_pos).tolist()) == [3, 4, 5, 6]
