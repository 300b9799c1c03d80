"""Pallas TPU kernel: local-field init from packed signed bit-planes.

TPU-native analogue of the paper's Hamming-weight accumulator (§IV-B2a): the
FPGA's 64-bit popcount trees become `lax.population_count` on the VPU over
`uint32` lanes. For B planes the couplings cost 2·B bits each — at the paper's
B=2 that is 8× less HBM traffic than an int8 J and 16× less than f32, which
directly scales the memory-roofline term of the init (see EXPERIMENTS.md §Perf).

Layout: planes (B, N, W) uint32 packed 32 couplers/word; spin words (R, W).
Grid: (N/bn, R/br); each program produces a (br × bn) tile of u by looping
planes in-register. The plane tile (B, bn, W) streams once per N-block and is
reused across the replica axis by the pipeline (index_map ignores r).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common


def _kernel(pos_ref, neg_ref, x_ref, out_ref, *, num_planes: int):
    x = x_ref[...]  # (br, W) uint32
    popc = jax.lax.population_count
    acc = jnp.zeros(out_ref.shape, jnp.float32)  # (br, bn)
    for b in range(num_planes):  # static unroll: B is small (≤ 16)
        pos = pos_ref[b]  # (bn, W)
        neg = neg_ref[b]
        m_p = popc(pos).astype(jnp.int32).sum(-1)  # (bn,)
        m_n = popc(neg).astype(jnp.int32).sum(-1)
        o_p = popc(pos[None, :, :] & x[:, None, :]).astype(jnp.int32).sum(-1)  # (br, bn)
        o_n = popc(neg[None, :, :] & x[:, None, :]).astype(jnp.int32).sum(-1)
        contrib = (2 * o_p - m_p[None, :]) - (2 * o_n - m_n[None, :])
        acc = acc + jnp.float32(1 << b) * contrib.astype(jnp.float32)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_r", "block_n", "interpret"))
def bitplane_field_init(pos: jax.Array, neg: jax.Array, spin_words: jax.Array,
                        *, block_r: int = 8, block_n: int = 256,
                        interpret: bool = False) -> jax.Array:
    """u^(J)[r, i] from packed planes (Eq. 14-16). Returns (R, N) f32.

    ``block_r`` resolves through ``common.replica_block``; ``block_n`` clamps
    to N and need not divide it — the edge block computes rows past N, whose
    results are never stored (each output row depends on its own plane row
    only). Past N, a legal TPU block is a multiple of 128.
    """
    num_planes, n, w = pos.shape
    assert neg.shape == pos.shape
    r = spin_words.shape[0]
    assert spin_words.shape == (r, w)
    br = common.replica_block(r, block_r)
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn), r // br)
    plane_blk = common.vmem_bytes((num_planes, bn, w), jnp.uint32)
    nbytes = 2 * (2 * plane_blk + common.vmem_bytes((br, w), jnp.uint32)
                  + common.vmem_bytes((br, bn), jnp.float32)) \
        + 4 * br * common.vmem_bytes((bn, w), jnp.uint32)
    return pl.pallas_call(
        functools.partial(_kernel, num_planes=num_planes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((num_planes, bn, w), lambda i, j: (0, i, 0)),
            pl.BlockSpec((num_planes, bn, w), lambda i, j: (0, i, 0)),
            pl.BlockSpec((br, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, bn), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=common.vmem_limit(nbytes)),
        interpret=interpret,
        name="bitplane_field_init",
    )(pos, neg, spin_words)
