"""Pallas TPU kernel: batched local-field initialization u = s Jᵀ + h.

TPU adaptation of the paper's row-major streaming init (§IV-B2a): on an FPGA
the dense init is a popcount pipeline; on TPU the roofline-optimal engine for
a dense (R, N) × (N, N) contraction is the MXU, so the init is a tiled matmul
with f32 accumulation. Tiles are chosen MXU-aligned (multiples of 128 on the
contracting/lane dims, 8 on sublanes) and triple-buffered through VMEM by the
Pallas pipeline.

Grid: (R/br, N/bn, K/bk) with the K axis innermost ("arbitrary") so each
(br × bn) output tile accumulates in a VMEM scratch across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common


def _kernel(s_ref, j_ref, h_ref, out_ref, acc_ref, *, num_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s_blk = s_ref[...].astype(jnp.float32)  # (br, bk)
    j_blk = j_ref[...].astype(jnp.float32)  # (bn, bk) — row-block of J
    acc_ref[...] += jax.lax.dot_general(
        s_blk, j_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == num_k - 1)
    def _done():
        out_ref[...] = acc_ref[...] + h_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_r", "block_n", "block_k", "interpret"))
def local_field_init(spins: jax.Array, couplings: jax.Array, bias: jax.Array,
                     *, block_r: int = 8, block_n: int = 256, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """u[r] = J @ s[r] + h for a replica batch. spins (R,N) ±1 (any int/float
    dtype), couplings (N,N), bias (N,). Returns (R,N) f32. ``block_r`` and
    ``block_k`` must divide R and N; ``block_n`` need not — the edge block of
    output columns reads rows past N, whose results are never stored."""
    r, n = spins.shape
    assert couplings.shape == (n, n) and bias.shape == (n,)
    br = min(block_r, r)
    bn = min(block_n, n)
    bk = min(block_k, n)
    if r % br or n % bk:
        raise ValueError(f"shape ({r},{n}) not divisible by blocks ({br},{bk})")
    num_k = n // bk
    grid = (r // br, pl.cdiv(n, bn), num_k)
    j_blk = common.vmem_bytes((bn, bk), couplings.dtype)
    out_blk = common.vmem_bytes((br, bn), jnp.float32)
    # Double-buffered blocks, the accumulator, and the f32 copy of the J tile.
    nbytes = (2 * (common.vmem_bytes((br, bk), spins.dtype) + j_blk
                   + common.vmem_bytes((1, bn), bias.dtype) + out_blk)
              + out_blk + common.vmem_bytes((bn, bk), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, num_k=num_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bk), lambda i, j, k: (i, k)),     # spins
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),     # J row-block
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),      # bias (2D for TPU layout)
        ],
        out_specs=pl.BlockSpec((br, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=common.vmem_limit(nbytes)),
        interpret=interpret,
        name="local_field_init",
    )(spins, couplings, bias.reshape(1, n))
