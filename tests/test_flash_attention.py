"""Pallas flash-attention kernel vs the jnp chunked-attention oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.models.layers import chunked_attention

def _qkv(seed, b, hq, hkv, s, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,hq,hkv,s,d,bq,bk", [
    (2, 6, 2, 256, 64, 64, 64),
    (1, 4, 4, 128, 32, 32, 64),   # MHA
    (2, 8, 1, 128, 64, 64, 32),   # MQA
    (1, 2, 2, 192, 16, 64, 64),   # non-power-of-two seq
])
def test_flash_matches_oracle(causal, b, hq, hkv, s, d, bq, bk):
    q, k, v = _qkv(b + s, b, hq, hkv, s, d)
    got = flash_attention(q, k, v, causal, 1.0 / d**0.5, bq, bk, True)
    want = chunked_attention(q, k, v, causal=causal, q_chunk=bq, kv_chunk=bk,
                             scale=1.0 / d**0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_dtypes(dtype):
    q, k, v = _qkv(0, 2, 4, 2, 128, 64, dtype)
    got = flash_attention(q, k, v, True, 0.125, 64, 64, True)
    want = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64,
                             scale=0.125)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_gradients_match_oracle():
    q, k, v = _qkv(3, 1, 4, 2, 128, 32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0.2, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(chunked_attention(q, k, v, causal=True, q_chunk=64,
                                         kv_chunk=64, scale=0.2) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_model_forward_with_flash_matches_chunked():
    import dataclasses
    from repro.configs import get_config
    from repro.models import forward, init_params, model_specs

    cfg = get_config("qwen2-7b", smoke=True)
    cfg_flash = dataclasses.replace(cfg, attn_impl="flash")
    params = init_params(model_specs(cfg), jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    a = forward(cfg, params, tokens=toks).logits.astype(jnp.float32)
    b = forward(cfg_flash, params, tokens=toks).logits.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2)
