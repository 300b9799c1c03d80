"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; kernel tests sweep shapes/dtypes
and ``assert_allclose`` against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def local_field_init(spins: jax.Array, couplings: jax.Array, bias: jax.Array) -> jax.Array:
    """u[r, i] = Σ_j J_ij s[r, j] + h_i  (paper Eq. 11 batched over replicas)."""
    s = spins.astype(jnp.float32)
    J = couplings.astype(jnp.float32)
    return s @ J.T + bias.astype(jnp.float32)[None, :]


def bitplane_field_init(pos: jax.Array, neg: jax.Array, spin_words: jax.Array,
                        num_spins: int) -> jax.Array:
    """Hamming-weight accumulation (paper Eq. 14-16) over packed planes.

    pos/neg: (B, N, W) uint32; spin_words: (R, W) uint32; -> (R, N) f32.
    """
    popc = jax.lax.population_count
    x = spin_words[:, None, None, :]  # (R, 1, 1, W)
    m_p = popc(pos).astype(jnp.int32).sum(-1)  # (B, N)
    m_n = popc(neg).astype(jnp.int32).sum(-1)
    o_p = popc(pos[None] & x).astype(jnp.int32).sum(-1)  # (R, B, N)
    o_n = popc(neg[None] & x).astype(jnp.int32).sum(-1)
    contrib = (2 * o_p - m_p[None]) - (2 * o_n - m_n[None])  # (R, B, N)
    w = jnp.float32(2.0) ** jnp.arange(pos.shape[0], dtype=jnp.float32)
    return jnp.einsum("b,rbn->rn", w, contrib.astype(jnp.float32))


def colored_sweep(couplings, fields0: jax.Array, spins0: jax.Array,
                  energy0: jax.Array, uniforms: jax.Array, temps: jax.Array,
                  sched: jax.Array, pwl_table: jax.Array | None = None, *,
                  block_r: int = 8):
    """Exact-semantics oracle for ``kernels.sweep.colored_sweep``.

    Same contract: spins in color-sorted order, ``sched`` (T, 3) int32 rows of
    (window_start, class_offset, class_size), ``uniforms`` (T, R, S) accept
    streams over the static class window S; windows index the state padded
    to a lane multiple, as in the kernel. Per step every member of the
    scheduled class takes an independent heat-bath flip off the live local
    fields (exact block Gibbs — same-color spins share no coupling), then the
    accepted subset's rank-1 row updates are applied slot by slot through the
    same row decode as the single-flip oracle. The kernel gates each slot's
    fetch+FMA on "any replica in the *block* accepted", so the oracle takes
    ``block_r`` and reproduces the identical block-shaped select — parity
    tests require trajectory-exact agreement on all 7 outputs, including the
    coalesced ``rows_fetched`` attribution (one count per fetched row, on the
    block's lowest-index accepting replica). Returns (fields, spins, energy,
    best_energy, best_spins, num_flips, rows_fetched).
    """
    from . import common  # local import: ref stays importable standalone
    from ..core.bitplane import BitPlanes

    if isinstance(couplings, BitPlanes):
        n = couplings.num_spins
        pos, neg = couplings.pos, couplings.neg

        def fetch_row(jr):  # scalar site -> (1, N) f32 decoded coupling row
            return common.decode_bitplane_rows(
                jax.lax.dynamic_slice_in_dim(pos, jr, 1, axis=1),
                jax.lax.dynamic_slice_in_dim(neg, jr, 1, axis=1), n)
    else:
        n = couplings.shape[0]
        J = couplings.astype(jnp.float32)

        def fetch_row(jr):
            return jax.lax.dynamic_slice_in_dim(J, jr, 1, axis=0)

    r = fields0.shape[0]
    br = common.replica_block(r, block_r)
    g = r // br
    win = uniforms.shape[2]
    ids = jnp.arange(br, dtype=jnp.int32)
    # The kernel's lane-padded state: windows are lane-aligned slices of it.
    n_pad = common.round_up(n, common.LANE_TILE)
    pad = ((0, 0), (0, n_pad - n))

    def body(carry, xs):
        u, s, e, be, bs, nf, rf = carry
        u01, temp, row_sched = xs            # (R, S), (R,), (3,)
        w, off, size = row_sched[0], row_sched[1], row_sched[2]
        u_win = jax.lax.dynamic_slice(u, (0, w), (r, win))
        s_win = jax.lax.dynamic_slice(s, (0, w), (r, win))
        de = 2.0 * s_win * u_win
        p = common.flip_probability(de, temp[:, None], pwl_table)
        idx = jax.lax.broadcasted_iota(jnp.int32, (r, win), 1) + w
        valid = (idx >= off) & (idx < off + size)
        accept = (u01 < p) & valid
        acc_f = accept.astype(jnp.float32)
        e = e + jnp.sum(acc_f * de, axis=1)
        nf = nf + jnp.sum(accept.astype(jnp.int32), axis=1)
        s = jax.lax.dynamic_update_slice(s, s_win * (1.0 - 2.0 * acc_f),
                                         (0, w))

        def apply_slot(k, carry):
            u, rf = carry
            acc_k = jax.lax.dynamic_slice(acc_f, (0, k), (r, 1))   # (R, 1)
            s_old_k = jax.lax.dynamic_slice(s_win, (0, k), (r, 1))
            acc_b = acc_k.reshape(g, br)
            anyacc = jnp.sum(acc_b, axis=1) > 0.0                  # (G,)
            row = jnp.pad(fetch_row(w + k), pad)                   # (1, N_pad)
            gate = jnp.repeat(anyacc, br)[:, None]
            u = jnp.where(gate, u - (2.0 * acc_k * s_old_k) * row, u)
            first = jnp.min(jnp.where(acc_b > 0.0, ids[None, :], br), axis=1)
            hit = anyacc[:, None] & (ids[None, :] == first[:, None])
            return u, rf + hit.reshape(r).astype(jnp.int32)

        lo = off - w
        u, rf = jax.lax.fori_loop(lo, lo + size, apply_slot, (u, rf))
        better = e < be
        be = jnp.where(better, e, be)
        bs = jnp.where(better[:, None], s, bs)
        return (u, s, e, be, bs, nf, rf), None

    u0 = jnp.pad(fields0.astype(jnp.float32), pad)
    s0 = jnp.pad(spins0.astype(jnp.float32), pad, constant_values=1.0)
    init = (u0, s0, energy0.astype(jnp.float32), energy0.astype(jnp.float32),
            s0, jnp.zeros((r,), jnp.int32), jnp.zeros((r,), jnp.int32))
    (u, s, e, be, bs, nf, rf), _ = jax.lax.scan(
        body, init, (uniforms, temps, sched.astype(jnp.int32)))
    return (u[:, :n], s[:, :n].astype(spins0.dtype), e, be,
            bs[:, :n].astype(spins0.dtype), nf, rf)


def mcmc_sweep(couplings, fields0: jax.Array, spins0: jax.Array,
               energy0: jax.Array, uniforms: jax.Array, temps: jax.Array,
               pwl_table: jax.Array | None = None, *, mode: str = "rsa",
               uniformized: bool = False, lane: int | None = None):
    """T-step dual-mode sweep over R replicas (paper Alg. 1 inner loop).

    Exact-semantics oracle for ``kernels.sweep.mcmc_sweep``: identical
    signature (minus blocking knobs) and identical per-step arithmetic via the
    shared ``kernels.common`` selection math, so parity tests can require
    trajectory-exact agreement. couplings (N, N) dense — or a packed
    ``core.bitplane.BitPlanes``, mirroring the kernel's
    ``coupling="bitplane"`` path: rows are gathered from the planes and
    decoded through the same ``common.decode_bitplane_rows`` bit expansion,
    so the bit-plane trajectories are exact too. fields0/spins0 (R, N);
    energy0 (R,); uniforms (T, R, 4) f32 in [0,1) — (site, accept, roulette,
    uniformize) streams; temps (T, R) f32 per-replica temperatures;
    ``pwl_table`` optional (S+1, 3) LUT (None = exact sigmoid). Returns
    (fields, spins, energy, best_energy, best_spins, num_flips).
    """
    from . import common  # local import: ref stays importable standalone
    from ..core.bitplane import BitPlanes

    if isinstance(couplings, BitPlanes):
        n = couplings.num_spins
        pos, neg = couplings.pos, couplings.neg

        def fetch_rows(j):  # (R,) sites -> (R, N) f32 decoded coupling rows
            return common.decode_bitplane_rows(
                jnp.take(pos, j, axis=1), jnp.take(neg, j, axis=1), n)
    else:
        n = couplings.shape[0]
        J = couplings.astype(jnp.float32)

        def fetch_rows(j):
            return jnp.take(J, j, axis=0)
    lane = common.default_lane(n) if lane is None else lane

    def body(carry, xs):
        u, s, e, be, bs, nf = carry
        u01, temp = xs                       # (R, 4), (R,)
        temp = temp[:, None]
        sf = s.astype(jnp.float32)
        # Per-replica quantities are (R, 1) columns, as in the kernel.
        if mode == "rsa":
            j = common.site_from_uniform(u01[:, 0:1], n)
            u_j = jnp.take_along_axis(u, j, axis=1)
            s_j = jnp.take_along_axis(sf, j, axis=1)
            de = 2.0 * s_j * u_j
            p_j = common.flip_probability(de, temp, pwl_table)
            accept = u01[:, 1:2] < p_j
        else:
            de_all = 2.0 * sf * u            # (R, N)
            p_all = common.flip_probability(de_all, temp, pwl_table)
            j_rw, total, degenerate = common.roulette_pick(
                p_all, u01[:, 2:3], lane)
            if uniformized:
                accept = ~degenerate & (u01[:, 3:4] * jnp.float32(n) < total)
                j = j_rw
            else:
                j_fb = common.site_from_uniform(u01[:, 0:1], n)
                p_fb = jnp.take_along_axis(p_all, j_fb, axis=1)
                accept = ~degenerate | (u01[:, 1:2] < p_fb)
                j = jnp.where(degenerate, j_fb, j_rw)
            de = jnp.take_along_axis(de_all, j, axis=1)
        s_old = jnp.take_along_axis(sf, j, axis=1)
        acc_f = accept.astype(jnp.float32)
        rows = fetch_rows(j[:, 0])  # (R, N)
        u = u - (2.0 * acc_f * s_old) * rows
        onehot = jax.nn.one_hot(j[:, 0], n, dtype=s.dtype)
        s = jnp.where(accept, (s * (1 - 2 * onehot)).astype(s.dtype), s)
        e = e + (acc_f * de)[:, 0]
        nf = nf + accept[:, 0].astype(jnp.int32)
        better = e < be
        be = jnp.where(better, e, be)
        bs = jnp.where(better[:, None], s, bs)
        return (u, s, e, be, bs, nf), None

    r = fields0.shape[0]
    init = (fields0.astype(jnp.float32), spins0, energy0.astype(jnp.float32),
            energy0.astype(jnp.float32), spins0, jnp.zeros((r,), jnp.int32))
    (u, s, e, be, bs, nf), _ = jax.lax.scan(body, init, (uniforms, temps))
    return u, s, e, be, bs, nf
