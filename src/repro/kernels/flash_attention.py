"""Pallas TPU kernel: fused flash attention (GQA-native, causal-aware).

The dry-run memory profile (EXPERIMENTS.md §Perf) shows the pure-jnp chunked
attention dominating the HBM roofline term: every (q_block × kv_block) score
tile is a dot result that XLA materializes to HBM (~10–200 TB/step at 32k
context). This kernel keeps the score tile, running max/sum, and output
accumulator in VMEM across the KV loop — HBM traffic collapses to
Q + O + nq·(K + V) streams, the standard flash-attention budget.

Layout: grid (batch, kv_head, q_block); the KV loop runs *inside* the kernel
body (fori_loop) so (m, l, acc) never leave VMEM. GQA is native: the q tile
carries the `rep = Hq/Hkv` group dim; K/V tiles are shared across the group.
Causal masking skips fully-masked KV blocks via the loop upper bound
`(qi+1)·bq / bk` — the triangular schedule, which also halves FLOPs vs the
jnp path's full rectangle.

`ref.py` oracle: ``repro.models.layers.chunked_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, block_q: int,
            block_k: int, seq_kv: int, scale: float):
    # q_ref: (1, 1, rep, block_q, d); k_ref/v_ref: (1, 1, seq_kv, d)
    rep = q_ref.shape[2]
    d = q_ref.shape[-1]
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (rep, bq, d)

    num_k = seq_kv // block_k
    if causal:
        # triangular schedule: only blocks overlapping the causal frontier
        num_live = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k, num_k)
    else:
        num_live = num_k

    def body(kj, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(
            jnp.float32)  # (bk, d)
        v_blk = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (rep,bq,bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            mask = rows >= cols
            s = jnp.where(mask, s, NEG_INF)
        m2 = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m2)
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        c1 = jnp.exp(m - m_new)
        l_new = l * c1 + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v_blk, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_new = acc * c1[..., None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((rep, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep, block_q), jnp.float32)
    acc0 = jnp.zeros((rep, block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_live, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                    scale: float, block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D), Hq % Hkv == 0.
    Returns (B, Hq, Sq, D), same dtype as q."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    rep = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq ({sq},{skv}) not divisible by ({block_q},{block_k})")
    qg = q.reshape(b, hkv, rep, sq, d)
    grid = (b, hkv, sq // block_q)
    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, seq_kv=skv, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, block_q, d), lambda bi, hi, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((1, 1, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, block_q, d),
                               lambda bi, hi, qi: (bi, hi, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, sq, d), q.dtype),
        interpret=interpret,
    )(qg, k, v)
    return out.reshape(b, hq, sq, d)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    """Backward via the jnp oracle (recompute-from-inputs, flash-style).

    A dedicated Pallas backward kernel has the same structure as the forward
    (streaming KV blocks, dq/dk/dv accumulators in VMEM) and the same HBM
    budget; the roofline substitution in EXPERIMENTS.md §Perf models the
    fwd+bwd kernel pair. Functionally, recomputing through the chunked-jnp
    path yields exact gradients.
    """
    from ..models.layers import chunked_attention

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_attention(
            q_, k_, v_, causal=causal, q_chunk=block_q, kv_chunk=block_k,
            scale=scale), q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
