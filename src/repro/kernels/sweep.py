"""Pallas TPU kernel: fused multi-step dual-mode MCMC sweep (production backend).

TPU analogue of the paper's on-chip local-field memory (§IV-B2b): the FPGA
keeps u in BRAM and read-modify-writes it after every flip. A literal
one-flip-per-XLA-op loop would round-trip u, s through HBM every step; this
kernel keeps the coupling tile J, the local fields u, and the spins s resident
in VMEM across ``T`` consecutive MCMC steps, so per-step HBM traffic drops to
zero while J fits VMEM (f32 J: 16 MiB at N=2000, of a v5e core's 128 MiB)
— the same "compute-bound, not memory-bound" crossover the paper
demonstrates in Fig. 14.

Per-step work is O(br·N) (DESIGN.md §Backends): the incremental update
u ← u − 2 J[j,:] s_j_old (Eq. 27/31) fetches row J[j] with one per-replica
``pl.ds`` row read of the VMEM-resident J. The historical one-hot × J
MXU gather — an O(br·N²) contraction per step — survives only as the opt-in
``gather="onehot"`` heuristic for tiny N, where a single small matmul beats
``br`` sequential DMA-issued row reads.

Coupling storage is selectable (``coupling="dense"|"bitplane"|"bitplane_hbm"``):
the dense path holds J as (N, N) f32 — 16 MiB of VMEM at N=2048, the f32 wall
— while the bit-plane path (paper §IV-B1, Eq. 13) holds the (B, N, W) uint32
``pos``/``neg`` planes of an integer J, 2·B bits per coupler instead of 32.
At the paper's B=2 that is 8× smaller, moving the VMEM wall from N≈2000 to
N≈5–11k (DESIGN.md §Backends). Row j is fetched as a (B, 1, W) ``pl.ds``
slice per sign — O(B·N/32) word reads — and decoded in-register by
``common.decode_bitplane_rows`` (shift-and-mask expansion + unrolled plane
sum, O(B·N) VPU work, no ``dot_general``); the O(N) FMA into u is unchanged,
so the O(N)/step contract and the no-``dot_general`` jaxpr pin both hold.
Local-field *initialization* from planes is the separate popcount kernel
(``kernels/bitplane_field.py``); this kernel only consumes u₀.

``coupling="bitplane_hbm"`` breaks even the packed-VMEM wall (N ≈ 8–11k):
the planes stay in HBM (``memory_space=ANY`` — never blocked into the
pipeline) and each step's selected row streams into a 2-slot VMEM scratch via
``pltpu.make_async_copy`` DMAs, double-buffered across the replica apply
loop — while replica r's row is decoded and FMA'd, the DMA for replica
r+1's row is already in flight. A DMA moves whole (8, 128) tiles of the HBM
layout, so each fetch copies the aligned (B, 8, W) row group and the decode
reads its row out of VMEM. VMEM then holds only the sweep state plus two row
groups, so the N-ceiling is set by HBM capacity, not VMEM: N=16384 at B=1 is
a 64 MiB plane store streamed at 2·B·8·W words/step against the same O(N)
VPU work. The decoded row goes through the identical
``common.decode_bitplane_rows`` expansion, so streamed trajectories are
exactly equal to the VMEM-bitplane and dense paths (the parity tier asserts
``assert_array_equal``). The DMA pattern runs under interpret mode too (the
interpreter emulates ``make_async_copy`` + semaphores), so the tested path
on CPU is the compiled path on TPU.

Feature parity with ``core.mcmc``: both modes (RSA random-scan, RWA
roulette-wheel with hierarchical lane-scan selection), the uniformized-RWA
null-transition variant, the PWL LUT flip probability (passed as a small VMEM
table), per-replica temperature ladders (``temps`` is (T, R) — parallel
tempering runs a constant ladder, annealing a broadcast schedule), and
``num_flips`` tracking.

Asynchronous single-spin semantics are preserved exactly: each step selects at
most one spin per replica, flips it, and applies the incremental update before
the next selection. Randomness is supplied as a precomputed (T, R, 4) tensor
of uniforms — (site, accept, roulette, uniformize) streams — from the
stateless threefry RNG, so the kernel stays deterministic and replayable.

Grid: replica blocks ("parallel"). A VMEM-resident store is a whole-array
VMEM operand — copied in once, single-buffered — and each ``pallas_call``
requests the scoped VMEM its own buffers need (``common.vmem_limit``).

Mosaic lowering rules the kernels follow: the sweep state (u, s, best s)
lives in VMEM refs and is read and written by row (``ref[pl.ds(r, 1), :]``);
per-replica scalars (site, coefficient) are taken from (br, 1) columns by
masked reduction; per-step scalars of the colored schedule come from SMEM;
the roulette's prefix sums are ``common.prefix_sum``, a statically unrolled
log-depth doubling scan (no ``cumsum``, no loop); the PWL segment sweep is a
static unroll; no value-level ``dynamic_slice``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import coupling as coupling_store
from . import common

#: Coupling-store modes of the fused sweep (the single-device slice of the
#: ``core.coupling`` format registry; the sharded tier has its own driver).
COUPLING_MODES = coupling_store.KERNEL_COUPLING_MODES
#: Modes that consume a packed ``BitPlanes`` instead of a dense (N, N) J.
PLANE_MODES = coupling_store.KERNEL_PLANE_MODES


#: Rows per HBM→VMEM plane DMA: the sublane tile of the HBM layout.
ROW_GROUP = common.SUBLANE_TILE


def _dense_layout(couplings, n, br, coalesce):
    """VMEM-resident (N, N) J, copied in once for all replica blocks (a
    whole-array VMEM operand is single-buffered; a grid-invariant block
    would be double-buffered for nothing)."""
    return ([pl.BlockSpec(memory_space=pltpu.VMEM)], [couplings], [],
            common.vmem_bytes(couplings.shape, couplings.dtype))


def _bitplane_layout(couplings, n, br, coalesce):
    """VMEM-resident packed planes: pos/neg (B, N, W), copied in once."""
    vm = pl.BlockSpec(memory_space=pltpu.VMEM)
    return ([vm, vm], [couplings.pos, couplings.neg], [],
            2 * common.vmem_bytes(couplings.pos.shape, jnp.uint32))


def _bitplane_hbm_layout(couplings, n, br, coalesce):
    """HBM-resident planes: never enter the block pipeline (ANY pins them to
    HBM); the kernel streams row tiles into a 2-slot VMEM scratch
    double-buffer with one DMA semaphore per (slot, sign) in-flight copy.
    HBM arrays are tiled in (8, 128) blocks and a DMA moves whole row tiles,
    so each fetch copies the aligned (B, 8, W) group holding the row (rows
    padded to a multiple of 8 first when N is not). With coalescing, a
    (br, N) f32 row cache holds the step's decoded unique rows so duplicate
    selections replay a VMEM read instead of a second DMA."""
    bp, rows, w = couplings.pos.shape
    planes = [couplings.pos, couplings.neg]
    if rows % ROW_GROUP:
        pad = ((0, 0), (0, common.round_up(rows, ROW_GROUP) - rows), (0, 0))
        planes = [jnp.pad(x, pad) for x in planes]
    tile = (2, bp, ROW_GROUP, w)
    scratch = [pltpu.VMEM(tile, jnp.uint32),          # pos row groups
               pltpu.VMEM(tile, jnp.uint32),          # neg row groups
               pltpu.SemaphoreType.DMA((2, 2))]       # (slot, sign) DMAs
    nbytes = 2 * common.vmem_bytes(tile, jnp.uint32)
    if coalesce:
        scratch.append(pltpu.VMEM((br, n), jnp.float32))  # decoded row cache
        nbytes += common.vmem_bytes((br, n), jnp.float32)
    return ([pl.BlockSpec(memory_space=pl.ANY),
             pl.BlockSpec(memory_space=pl.ANY)], planes, scratch, nbytes)


#: Kernel-side half of the coupling-store contract: resolved format name →
#: (in_specs, operands, scratch_shapes, VMEM bytes) for the J store. The
#: host-side half is ``core.coupling.CouplingStore.build``.
_STORE_LAYOUTS = {
    "dense": _dense_layout,
    "bitplane": _bitplane_layout,
    "bitplane_hbm": _bitplane_hbm_layout,
}


def _pick(col: jax.Array, i) -> jax.Array:
    """Scalar ``col[i, 0]`` of an (br, 1) column for a traced row ``i`` —
    masked reduction (one term plus zeros, exact), the lowerable form of a
    per-replica scalar read."""
    rows = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0)
    return jnp.sum(jnp.where(rows == i, col, jnp.zeros((), col.dtype)))


def _row_store(j_refs, coupling: str, n: int, scratch=()):
    """The kernel's coupling-row reader: ``fetch_row(jr)`` → the (1, N) f32
    row ``jr`` off the VMEM-resident store, and for the HBM tier
    ``stream_start(slot, jr)`` / ``stream_wait_decode(slot, jr)`` around the
    2-slot DMA double buffer. One decode for every tier ⇒ identical rows."""

    def fetch_row(jr):
        if coupling == "bitplane":
            pos_ref, neg_ref = j_refs
            return common.decode_bitplane_rows(pos_ref[:, pl.ds(jr, 1), :],
                                               neg_ref[:, pl.ds(jr, 1), :], n)
        return j_refs[0][pl.ds(jr, 1), :].astype(jnp.float32)

    def stream_dmas(slot, jr):
        """The two (B, 8, W) HBM→VMEM copies of site jr's row group into
        double-buffer ``slot`` (descriptors are rebuilt for wait() — the
        canonical make_async_copy pattern)."""
        pos_ref, neg_ref = j_refs
        pos_scr, neg_scr, sems = scratch
        rows = pl.ds(pl.multiple_of(jr // ROW_GROUP * ROW_GROUP, ROW_GROUP),
                     ROW_GROUP)
        return (pltpu.make_async_copy(pos_ref.at[:, rows, :],
                                      pos_scr.at[slot], sems.at[slot, 0]),
                pltpu.make_async_copy(neg_ref.at[:, rows, :],
                                      neg_scr.at[slot], sems.at[slot, 1]))

    def stream_start(slot, jr):
        for dma in stream_dmas(slot, jr):
            dma.start()

    def stream_wait_decode(slot, jr):
        """Block on slot's row DMAs, then the same in-register bit expansion
        as the VMEM path — identical decode ⇒ identical trajectories."""
        pos_scr, neg_scr, _ = scratch
        for dma in stream_dmas(slot, jr):
            dma.wait()
        row = pl.ds(jr % ROW_GROUP, 1)
        return common.decode_bitplane_rows(pos_scr[slot, :, row, :],
                                           neg_scr[slot, :, row, :], n)

    return fetch_row, stream_start, stream_wait_decode


def _kernel(*refs, num_steps: int, mode: str, uniformized: bool,
            gather: str, lane: int, has_pwl: bool, coupling: str,
            coalesce: bool):
    streamed = coupling == "bitplane_hbm"
    num_j = 2 if coupling in PLANE_MODES else 1
    j_refs = refs[:num_j]
    u0_ref, s0_ref, e0_ref, unif_ref, temp_ref = refs[num_j:num_j + 5]
    rest = refs[num_j + 5:]
    tbl = rest[0][...].astype(jnp.float32) if has_pwl else None
    rest = rest[int(has_pwl):]
    u_out, s_out, e_out, be_out, bs_out, nf_out, rf_out = rest[:7]
    # State scratch: the f32 spins and best spins (u lives in u_out), then
    # the HBM tier's tiles + semaphores and (coalesced) decoded-row cache.
    s_scr, bs_scr = rest[7:9]
    store_scr = rest[9:12] if streamed else ()
    cache_scr = rest[12] if streamed and coalesce else None
    br, n = u0_ref.shape
    fetch_row, stream_start, stream_wait_decode = _row_store(
        j_refs, coupling, n, store_scr)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (br, n), 1)

    def gather_sites(x, j):
        """x[r, j[r]] as an (br, 1) column by masked row reduction (exact)."""
        return jnp.sum(jnp.where(lanes == j, x, 0.0), axis=1, keepdims=True)

    def flip_probability(de, temp):
        return common.flip_probability(de, temp, tbl, pwl_select="select")

    u_out[...] = u0_ref[...].astype(jnp.float32)
    s_scr[...] = s0_ref[...].astype(jnp.float32)
    bs_scr[...] = s_scr[...]

    def step(t, carry):
        e, be, nf, rf = carry                # (br, 1) columns
        unif = unif_ref[t]                   # (br, 4) (site, acc, rou, uni)
        u_site, u_acc = unif[:, 0:1], unif[:, 1:2]
        u_rou, u_uni = unif[:, 2:3], unif[:, 3:4]
        temp = temp_ref[t]                   # (br, 1) per-replica rung
        u = u_out[...]
        s = s_scr[...]
        if mode == "rsa":
            j = common.site_from_uniform(u_site, n)
            s_old = gather_sites(s, j)
            de = 2.0 * s_old * gather_sites(u, j)
            accept_b = u_acc < flip_probability(de, temp)
        else:
            de_all = 2.0 * s * u
            p_all = flip_probability(de_all, temp)
            j_rw, total, degenerate = common.roulette_pick(p_all, u_rou, lane)
            if uniformized:
                # Null transition with prob 1 − W/W*, W* = N (§IV-B3c).
                accept_b = ~degenerate & (u_uni * jnp.float32(n) < total)
                j = j_rw
            else:
                # Degenerate-W fallback: one random-scan update (Alg. 1 l. 10-14).
                j_fb = common.site_from_uniform(u_site, n)
                p_fb = gather_sites(p_all, j_fb)
                accept_b = ~degenerate | (u_acc < p_fb)
                j = jnp.where(degenerate, j_fb, j_rw)
            de = gather_sites(de_all, j)
            s_old = gather_sites(s, j)
        accept = accept_b.astype(jnp.float32)
        e = e + accept * de
        nf = nf + accept_b.astype(jnp.int32)
        better = e < be
        be = jnp.where(better, e, be)
        coef = 2.0 * accept * s_old          # u ← u − coef·J[j] (Eq. 27/31)
        s_scr[...] = jnp.where((lanes == j) & accept_b, -s, s)
        if gather == "onehot":
            rf = rf + 1                      # one row materialized per replica
            onehot = (lanes == j).astype(jnp.float32)
            rows = jax.lax.dot_general(
                onehot, j_refs[0][...].astype(jnp.float32),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            u_out[...] = u - coef * rows
        else:
            # Asynchronous apply, one replica at a time: an O(N) row FMA
            # into that replica's field row.
            def apply_row(rix, row):
                u_out[pl.ds(rix, 1), :] = (u_out[pl.ds(rix, 1), :]
                                           - _pick(coef, rix) * row)

            if streamed and coalesce:
                # Reuse-aware streaming: DMA each *unique* selected row
                # exactly once — still double-buffered across the fetch
                # loop — into the (br, N) decoded-row cache, then apply
                # replicas reading the cache. The decoded row depends only on
                # the site, so the trajectory cannot move; only rf (rows
                # fetched) drops from br to nu per step.
                nu, usite, uo, fetched = common.coalesce_rows(j)
                rf = rf + fetched

                def fetch_one(m, c):
                    slot = jax.lax.rem(m, 2)

                    @pl.when(m + 1 < nu)
                    def _():
                        stream_start(1 - slot, _pick(usite, m + 1))

                    cache_scr[pl.ds(m, 1), :] = stream_wait_decode(
                        slot, _pick(usite, m))
                    return c

                stream_start(0, _pick(usite, 0))
                jax.lax.fori_loop(0, nu, fetch_one, 0)

                def apply_one(rix, c):
                    apply_row(rix, cache_scr[pl.ds(_pick(uo, rix), 1), :])
                    return c
            elif streamed:
                rf = rf + 1
                # Double-buffered HBM streaming: replica r+1's row tiles are
                # DMA'd into the other scratch slot while replica r's row is
                # decoded and applied (sites are all known before the apply
                # loop, and replicas are independent).
                def apply_one(rix, c):
                    slot = jax.lax.rem(rix, 2)

                    @pl.when(rix + 1 < br)
                    def _():
                        stream_start(1 - slot, _pick(j, rix + 1))

                    apply_row(rix, stream_wait_decode(slot, _pick(j, rix)))
                    return c

                stream_start(0, _pick(j, 0))
            else:
                rf = rf + 1

                def apply_one(rix, c):
                    apply_row(rix, fetch_row(_pick(j, rix)))
                    return c

            jax.lax.fori_loop(0, br, apply_one, 0)
        bs_scr[...] = jnp.where(better, s_scr[...], bs_scr[...])
        return e, be, nf, rf

    e = e0_ref[...].astype(jnp.float32)
    zeros = jnp.zeros((br, 1), jnp.int32)
    e, be, nf, rf = jax.lax.fori_loop(0, num_steps, step, (e, e, zeros, zeros))
    s_out[...] = s_scr[...].astype(s_out.dtype)
    bs_out[...] = bs_scr[...].astype(bs_out.dtype)
    e_out[...] = e
    be_out[...] = be
    nf_out[...] = nf
    rf_out[...] = rf


def _colored_kernel(*refs, num_steps: int, has_pwl: bool, coupling: str,
                    n: int):
    """Graph-colored block sweep: per step, every spin of the scheduled color
    class accepts an independent heat-bath flip off the live local fields,
    then the accepted subset's rank-1 field updates are applied through the
    same per-row fetch/decode the single-flip kernel uses. Same-color spins
    share no coupling, so the ΔE computed at step start stays valid at every
    member site regardless of apply order — exact block Gibbs (DESIGN.md
    §Graph-colored parallel flips). The selection-mode knob (rsa/rwa/
    uniformized) does not enter: class membership replaces spin selection,
    so colored trajectories are mode-independent by construction.

    The driver hands the class schedule as a (T, 3) int32 ``sched`` table in
    SMEM — per step the window start ``w`` (a multiple of 128), the class
    offset, and the class size in the color-sorted (permuted) spin order —
    so the kernel reads one static-width window per step at a lane-aligned
    dynamic offset and masks it to the live class. The state is padded to a
    lane multiple (``n`` is the real spin count; the pad is never in a class).
    """
    streamed = coupling == "bitplane_hbm"
    num_j = 2 if coupling in PLANE_MODES else 1
    j_refs = refs[:num_j]
    (u0_ref, s0_ref, e0_ref, unif_ref, temp_ref,
     sched_ref) = refs[num_j:num_j + 6]
    rest = refs[num_j + 6:]
    tbl = rest[0][...].astype(jnp.float32) if has_pwl else None
    rest = rest[int(has_pwl):]
    u_out, s_out, e_out, be_out, bs_out, nf_out, rf_out = rest[:7]
    s_scr, bs_scr = rest[7:9]
    store_scr = rest[9:12] if streamed else ()
    br = u0_ref.shape[0]
    win = unif_ref.shape[2]
    fetch_row, stream_start, stream_wait_decode = _row_store(
        j_refs, coupling, n, store_scr)
    if streamed:
        # The colored fetch is gated per slot (no double-buffer overlap):
        # one slot, started and waited back to back.
        def fetch_row(jr):
            stream_start(0, jr)
            return stream_wait_decode(0, jr)

    ids = jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    u_out[...] = u0_ref[...].astype(jnp.float32)
    s_scr[...] = s0_ref[...].astype(jnp.float32)
    bs_scr[...] = s_scr[...]
    rf_out[...] = jnp.zeros_like(rf_out)

    def step(t, carry):
        e, be, nf = carry                        # (br, 1) columns
        temp = temp_ref[t]                       # (br, 1)
        w = pl.multiple_of(sched_ref[3 * t], common.LANE_TILE)
        off = sched_ref[3 * t + 1]
        size = sched_ref[3 * t + 2]
        u_win = u_out[:, pl.ds(w, win)]
        s_win = s_scr[:, pl.ds(w, win)]
        de = 2.0 * s_win * u_win
        p = common.flip_probability(de, temp, tbl, pwl_select="select")
        idx = jax.lax.broadcasted_iota(jnp.int32, (br, win), 1) + w
        valid = (idx >= off) & (idx < off + size)
        accept = (unif_ref[t] < p) & valid
        acc_f = accept.astype(jnp.float32)
        e = e + jnp.sum(acc_f * de, axis=1, keepdims=True)
        nf = nf + jnp.sum(accept.astype(jnp.int32), axis=1, keepdims=True)
        s_scr[:, pl.ds(w, win)] = s_win * (1.0 - 2.0 * acc_f)

        def apply_slot(k, c):
            # One class member per iteration: fetch its row once — the fetch
            # is shared by every replica, cross-replica coalescing for free —
            # and FMA it into all br field rows, gated so idle slots cost
            # nothing (and the streamed tier skips the DMA entirely).
            acc_k = common.take_lane(acc_f, k)                  # (br, 1)
            s_old_k = common.take_lane(s_win, k)

            @pl.when(jnp.sum(acc_k) > 0.0)
            def _():
                row = fetch_row(w + k)                           # (1, N)
                u_out[:, :n] = u_out[:, :n] - (2.0 * acc_k * s_old_k) * row
                # Attribute the single shared fetch to the lowest-index
                # accepting replica (the coalesce_rows convention), so the
                # block sum of rf is the true unique-row traffic.
                first = jnp.min(jnp.where(acc_k > 0.0, ids, br))
                rf_out[...] += (ids == first).astype(jnp.int32)

            return c

        lo = off - w
        jax.lax.fori_loop(lo, lo + size, apply_slot, 0)
        better = e < be
        be = jnp.where(better, e, be)
        bs_scr[...] = jnp.where(better, s_scr[...], bs_scr[...])
        return e, be, nf

    e = e0_ref[...].astype(jnp.float32)
    e, be, nf = jax.lax.fori_loop(0, num_steps, step,
                                  (e, e, jnp.zeros((br, 1), jnp.int32)))
    s_out[...] = s_scr[...].astype(s_out.dtype)
    bs_out[...] = bs_scr[...].astype(bs_out.dtype)
    e_out[...] = e
    be_out[...] = be
    nf_out[...] = nf


def _state_specs(br: int, n: int, t: int, stream_width: int):
    """Blocks of the per-replica operands — u0, s0, e0, the (T, R, ·)
    uniforms and the (T, R, 1) temperatures — and of the 7 outputs (u, s,
    e, best_e, best_s, flips, rows fetched): replica block ``i`` of each."""
    row = pl.BlockSpec((br, n), lambda i: (i, 0))
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    ins = [row, row, col,
           pl.BlockSpec((t, br, stream_width), lambda i: (0, i, 0)),
           pl.BlockSpec((t, br, 1), lambda i: (0, i, 0))]
    return ins, [row, row, col, col, row, col, col]


def _state_vmem_bytes(br: int, n: int, t: int, stream_width: int) -> int:
    """VMEM of the pipelined state blocks (double-buffered) + the f32 spin
    scratch + headroom for the step's (br, N) temporaries."""
    row = common.vmem_bytes((br, n), jnp.float32)
    col = common.vmem_bytes((br, 1), jnp.float32)
    stream = (common.vmem_bytes((t, br, stream_width), jnp.float32)
              + common.vmem_bytes((t, br, 1), jnp.float32))
    return 2 * (5 * row + 6 * col + stream) + 2 * row + 8 * row


def _out_shapes(r: int, n: int, spin_dtype):
    return [
        jax.ShapeDtypeStruct((r, n), jnp.float32),
        jax.ShapeDtypeStruct((r, n), spin_dtype),
        jax.ShapeDtypeStruct((r, 1), jnp.float32),
        jax.ShapeDtypeStruct((r, 1), jnp.float32),
        jax.ShapeDtypeStruct((r, n), spin_dtype),
        jax.ShapeDtypeStruct((r, 1), jnp.int32),
        jax.ShapeDtypeStruct((r, 1), jnp.int32),
    ]


def _compiler_params(vmem_limit_bytes: int):
    """Replica blocks are independent ("parallel"); the scoped-VMEM request
    is derived from the kernel's own buffers (``common.vmem_limit``)."""
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=vmem_limit_bytes)


def sweep_vmem_limit(couplings, r: int, n: int, t: int, *, coupling: str,
                     block_r: int = 8, coalesce: bool = True) -> int:
    """The scoped-VMEM request (``vmem_limit_bytes``) of the
    :func:`mcmc_sweep` ``pallas_call`` for R replicas of N spins over T
    steps: the store layout's bytes plus the state blocks', through
    ``common.vmem_limit``. It reads the coupling operand's shapes only
    (the layout runs under ``jax.eval_shape``: nothing is padded or
    copied), once per shape: the supervisor asks at every runner build."""
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          couplings)
    coalesce = coalesce and coupling_store.FORMATS[coupling].coalescable
    return _sweep_vmem_limit(shapes, r, n, t, coupling,
                             common.replica_block(r, block_r), coalesce)


@functools.lru_cache(maxsize=64)
def _sweep_vmem_limit(shapes, r, n, t, coupling, br, coalesce) -> int:
    store = []
    jax.eval_shape(lambda c: store.append(
        _STORE_LAYOUTS[coupling](c, n, br, coalesce)[3]), shapes)
    return common.vmem_limit(store[0] + _state_vmem_bytes(br, n, t, 4))


@functools.partial(jax.jit, static_argnames=("coupling", "block_r",
                                             "interpret"))
def colored_sweep(couplings, fields0: jax.Array, spins0: jax.Array,
                  energy0: jax.Array, uniforms: jax.Array, temps: jax.Array,
                  sched: jax.Array, pwl_table: Optional[jax.Array] = None, *,
                  coupling: str = "dense", block_r: int = 8,
                  interpret: bool = False):
    """T graph-colored block-update steps for R replicas.

    The colored counterpart of :func:`mcmc_sweep`: state and coupling-store
    contracts are identical (same 7 outputs, same ``_STORE_LAYOUTS`` tiers,
    same decode, no ``dot_general``), but each step updates the whole
    scheduled color class instead of selecting one spin. Spins must already
    be in color-sorted (permuted) order — ``kernels.ops.colored_anneal``
    owns the permutation. ``uniforms`` is (T, R, S) with S the static class
    window, a multiple of 128; ``sched`` is (T, 3) int32 rows of
    ``(window_start, class_offset, class_size)`` per step, window starts
    multiples of 128 with ``start + S ≤ roundup(N, 128)`` (the
    ``ColoredPlan`` window math). ``rows_fetched`` counts each fetched
    coupling row once, attributed to the lowest-index accepting replica (the
    row fetch is shared across replicas — colored mode is coalesced by
    construction on every tier).
    """
    r, n = fields0.shape
    t = uniforms.shape[0]
    win = uniforms.shape[2]
    assert spins0.shape == (r, n)
    assert uniforms.shape == (t, r, win) and temps.shape == (t, r)
    assert sched.shape == (t, 3)
    coupling_store.validate_kernel_operand(coupling, couplings, n, "dynamic")
    n_pad = common.round_up(n, common.LANE_TILE)
    if win % common.LANE_TILE or win > n_pad:
        raise ValueError(f"class window {win} must be a multiple of "
                         f"{common.LANE_TILE} and ≤ {n_pad}")
    br = common.replica_block(r, block_r)
    in_specs, j_args, store_scratch, nbytes = _STORE_LAYOUTS[coupling](
        couplings, n, br, False)
    if coupling == "bitplane_hbm":
        store_scratch = store_scratch[:3]  # tiles + semaphores, no row cache
    state_in, out_specs = _state_specs(br, n_pad, t, win)
    in_specs = in_specs + state_in + [pl.BlockSpec(memory_space=pltpu.SMEM)]
    # The state is padded to a lane multiple so every window is a
    # lane-aligned slice; pad spins never join a class.
    pad = ((0, 0), (0, n_pad - n))
    args = j_args + [jnp.pad(fields0.astype(jnp.float32), pad),
                     jnp.pad(spins0, pad, constant_values=1),
                     energy0.reshape(r, 1), uniforms, temps.reshape(t, r, 1),
                     sched.astype(jnp.int32).reshape(3 * t)]
    if pwl_table is not None:
        in_specs.append(pl.BlockSpec(pwl_table.shape, lambda i: (0, 0)))
        args.append(pwl_table)
    nbytes += _state_vmem_bytes(br, n_pad, t, win)
    outs = pl.pallas_call(
        functools.partial(_colored_kernel, num_steps=t,
                          has_pwl=pwl_table is not None, coupling=coupling,
                          n=n),
        grid=(r // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=_out_shapes(r, n_pad, spins0.dtype),
        scratch_shapes=[pltpu.VMEM((br, n_pad), jnp.float32),
                        pltpu.VMEM((br, n_pad), jnp.float32)] + store_scratch,
        compiler_params=_compiler_params(common.vmem_limit(nbytes)),
        interpret=interpret,
        name="colored_sweep",
    )(*args)
    u, s, e, be, bs, nf, rf = outs
    return (u[:, :n], s[:, :n], e[:, 0], be[:, 0], bs[:, :n], nf[:, 0],
            rf[:, 0])


@functools.partial(jax.jit, static_argnames=(
    "mode", "uniformized", "gather", "coupling", "block_r", "lane",
    "coalesce", "interpret"))
def mcmc_sweep(couplings, fields0: jax.Array, spins0: jax.Array,
               energy0: jax.Array, uniforms: jax.Array, temps: jax.Array,
               pwl_table: Optional[jax.Array] = None, *, mode: str = "rsa",
               uniformized: bool = False, gather: str = "dynamic",
               coupling: str = "dense", block_r: int = 8,
               lane: Optional[int] = None, coalesce: bool = True,
               interpret: bool = False):
    """T fused MCMC steps for R replicas.

    couplings: (N, N) f32 with ``coupling="dense"``, or a packed
    ``core.bitplane.BitPlanes`` of an integer J with ``coupling="bitplane"``
    (2·B bits per coupler in VMEM instead of 32 — the N≈2000 → N≈11k wall
    move) or ``coupling="bitplane_hbm"`` (planes stay in HBM, selected rows
    stream through a double-buffered VMEM scratch — the past-the-packed-wall
    tier, DESIGN.md §Backends). fields0/spins0 (R, N); energy0 (R,);
    uniforms (T, R, 4) [site, accept, roulette, uniformize] in [0,1); temps
    (T, R) per-replica temperatures; pwl_table optional (S+1, 3) LUT from
    ``core.pwl.pwl_table`` (None = exact sigmoid). ``gather``: "dynamic"
    (default, O(N)/step row fetch) or "onehot" (opt-in O(N²)/step MXU
    contraction for tiny N; dense-only). ``block_r`` is resolved by
    ``common.replica_block`` (a multiple of 8 dividing R, else all of R).
    ``coalesce`` (default on; only the HBM-streamed tier is affected —
    VMEM-resident fetches are free) DMAs each step's *unique* selected rows
    once and broadcasts the decoded row to every replica that picked it
    (``common.coalesce_rows``) — bit-identical trajectories, up to br× less
    row traffic. Returns (fields, spins, energy, best_energy, best_spins,
    num_flips, rows_fetched) where rows_fetched is the (R,) int32 count of
    coupling-row fetches each replica block attributed to that replica
    (uncoalesced paths count one per replica per step; the coalesced stream
    attributes each unique row to the lowest-index replica selecting it, so
    the block sum is the unique-row traffic); see ``ref.mcmc_sweep`` for the
    exact-semantics oracle.
    """
    r, n = fields0.shape
    t = uniforms.shape[0]
    assert spins0.shape == (r, n)
    assert uniforms.shape == (t, r, 4) and temps.shape == (t, r)
    if gather not in ("dynamic", "onehot"):
        raise ValueError(f"gather must be 'dynamic' or 'onehot', got {gather!r}")
    coupling_store.validate_kernel_operand(coupling, couplings, n, gather)
    br = common.replica_block(r, block_r)
    lane = common.default_lane(n) if lane is None else lane
    if n % lane:
        raise ValueError(f"N={n} not divisible by lane={lane}")
    # Coalescing only changes behavior where the row fetch is real data
    # movement (the registry's coalescable tiers); VMEM-resident stores keep
    # their direct per-replica reads so the flag never perturbs their layout.
    coalesce = coalesce and coupling_store.FORMATS[coupling].coalescable
    in_specs, j_args, store_scratch, _ = _STORE_LAYOUTS[coupling](
        couplings, n, br, coalesce)
    state_in, out_specs = _state_specs(br, n, t, 4)
    in_specs = in_specs + state_in
    args = j_args + [fields0, spins0, energy0.reshape(r, 1), uniforms,
                     temps.reshape(t, r, 1)]
    if pwl_table is not None:
        in_specs.append(pl.BlockSpec(pwl_table.shape, lambda i: (0, 0)))
        args.append(pwl_table)
    outs = pl.pallas_call(
        functools.partial(_kernel, num_steps=t, mode=mode,
                          uniformized=uniformized, gather=gather, lane=lane,
                          has_pwl=pwl_table is not None, coupling=coupling,
                          coalesce=coalesce),
        grid=(r // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=_out_shapes(r, n, spins0.dtype),
        scratch_shapes=[pltpu.VMEM((br, n), jnp.float32),
                        pltpu.VMEM((br, n), jnp.float32)] + store_scratch,
        compiler_params=_compiler_params(sweep_vmem_limit(
            couplings, r, n, t, coupling=coupling, block_r=block_r,
            coalesce=coalesce)),
        interpret=interpret,
        name="mcmc_sweep",
    )(*args)
    u, s, e, be, bs, nf, rf = outs
    return u, s, e[:, 0], be[:, 0], bs, nf[:, 0], rf[:, 0]
