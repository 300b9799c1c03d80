"""Spin-parallel distributed Snowball: the ``bitplane_sharded`` coupling tier.

Where ``solver_dist`` shards *replicas* (independent chains, J replicated),
this driver shards the **problem itself** across the mesh — the HETRI-style
partition of one Ising instance over multiple compute units, applied to the
plane store the reuse-aware near-memory literature makes the central design
axis. Device d owns coupling-plane rows [d·N/D, (d+1)·N/D) plus the matching
slice of the local fields u and spins s, so J capacity scales with
*aggregate* HBM — D× past the single-device ``bitplane_hbm`` wall — while
every replica still runs one global chain.

Per asynchronous MCMC step (paper Alg. 1, collectivized):

* **selection** — each device evaluates flip probabilities for its own spin
  slice; the hierarchical roulette's level-1 block sums (G = N/lane values,
  i.e. N/128 floats, not N) are ``all_gather``-ed so every device runs the
  identical block pick, and the winning block's lane weights are
  ``psum``-combined from their owner (``kernels.common`` supplies both levels
  — the same arithmetic the kernel and oracle run, so trajectories stay
  *exactly* equal to every single-device tier).
* **flip update** — the owner of the selected row contributes its packed
  (B, 1, W) pos/neg row tiles to a ``psum`` broadcast (masked zeros from
  everyone else add exactly), every device decodes the full row through the
  shared ``common.decode_bitplane_rows`` expansion and FMAs its own u-slice.
  The replica-apply loop is software-pipelined: replica r+1's row-tile psum
  is issued before replica r's decode+FMA consumes its tiles (the
  cross-device analogue of the HBM tier's DMA double-buffer), so the
  broadcast overlaps the previous replica's compute instead of blocking the
  step. Per-step traffic is O(B·N/32) words of row tiles + O(N/lane) block
  sums — never the O(N²) store, never O(N) f32 fields.

The solve is **dense-J-free end to end**: replica init runs inside the
shard_map, plane-natively per device (u₀ from the device's own plane slab,
e₀ via the shared ``ising.energy_from_fields`` einsum on the all_gather'd
u^(J)), and edge-list problems encode each device's slab straight from the
O(nnz) edges (:func:`shard_planes_from_edges`) — neither the full (B, N, W)
store nor any (N, N) f32 exists on any single host or device at any point.

RNG, chunk cadence (``kernels.ops.anneal_chunk_plan``), and the best-so-far
merge are shared with ``kernels.ops.fused_anneal`` statement for statement,
so ``solve_sharded`` returns **bit-identical** ``SolveResult``s to the fused
driver on every coupling tier (the parity test in
``tests/test_solver_sharded.py`` asserts ``assert_array_equal`` across
dense / bitplane / bitplane_hbm / bitplane_sharded / sharded_2d).

**2-D meshes — rows × replica groups** (the ``bitplane_sharded_2d`` tier):
on a multi-axis mesh the **last** axis row-shards the planes exactly as
above, while the leading axes form replica *groups*: planes are replicated
across groups, and each group runs an independent contiguous block of
``R / G`` replicas with **global** replica indices. All hot-path collectives
(the row-tile psums, the block-sum all_gathers, the masked psum gathers)
are scoped to the group's rows sub-axis only — no cross-group traffic per
step — so per-device J bytes are ``total / rows_per_group`` while replica
throughput scales with the group count. Every replica's RNG (``Salt.REPLICA``
keys, per-chunk ``Salt.SWEEP`` uniforms drawn at the full (T, R, 4) shape
and sliced to the group's block) is computed at its global index, so the
concatenation of the group blocks reproduces the full-R fused trajectory
bit for bit — the 1-D tier is the degenerate single-group case of the same
code path.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import coupling as coupling_store
from ..core import ising, rng
from ..core.bitplane import (WORD_BITS, BitPlanes, edge_plane_words,
                             local_fields_from_planes)
from ..core.solver import SolveResult, SolverConfig
from ..kernels import common
from ..kernels import ops as _ops
from .shmap import shard_map_compat


def _mesh_size(mesh: Mesh, axes) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _flat_shard_index(mesh: Mesh, axes):
    """Linear device index over all mesh axes (row-major in axis order —
    the same flattening ``PartitionSpec((axes...))`` uses to lay out the
    sharded dimension, and the one ``solver_dist`` derives replica ids from)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _mesh_axes_split(mesh: Mesh):
    """Split a sharded-tier mesh into ``(group_axes, row_axes)``.

    The **last** mesh axis always row-shards the plane store (J capacity);
    any leading axes are replica-group axes — planes replicated across them,
    each group running an independent contiguous block of replicas
    (throughput). A 1-D mesh is the degenerate no-group case
    (``group_axes == ()``), so the 1-D tier is exactly this path."""
    axes = tuple(mesh.axis_names)
    return axes[:-1], axes[-1:]


def _mesh_desc(mesh: Mesh) -> str:
    return "(" + ", ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names) + ")"


def nearest_row_shard_counts(n: int, near: int, limit: int = 3):
    """The row-shard counts d closest to ``near`` that split N evenly into
    lane-aligned shards (``N % d == 0 and (N // d) % default_lane(N) == 0``)
    — the actionable half of the sharded tier's divisibility errors."""
    lane = common.default_lane(n)
    valid = [d for d in range(1, max(n // lane, 1) + 1)
             if n % d == 0 and (n // d) % lane == 0]
    return tuple(sorted(valid, key=lambda d: (abs(d - near), d))[:limit])


def _check_row_shardable(n: int, mesh: Mesh) -> int:
    """Validate that N rows split evenly (and lane-aligned) over the mesh's
    row axis; returns the row-shard count. The error names N, the mesh
    shape, and the nearest valid row-shard counts — both the 1-D and 2-D
    paths route through here, so neither can silently mis-shard."""
    grp_axes, row_axes = _mesh_axes_split(mesh)
    num_rows = _mesh_size(mesh, row_axes)
    lane = common.default_lane(n)
    where = (f"row axis {row_axes[0]!r}" if grp_axes else "mesh")
    if n % num_rows:
        raise ValueError(
            f"N={n} spin rows cannot shard evenly over the {num_rows} "
            f"shard(s) of the {where} of mesh {_mesh_desc(mesh)} "
            f"(N % {num_rows} == {n % num_rows}); nearest valid row-shard "
            f"counts for N={n}: {nearest_row_shard_counts(n, num_rows)}")
    if (n // num_rows) % lane:
        raise ValueError(
            f"per-shard spin count {n // num_rows} is not a multiple of the "
            f"roulette lane {lane} (N={n} over the {num_rows} shard(s) of "
            f"the {where} of mesh {_mesh_desc(mesh)}): shard boundaries "
            f"must align with selection blocks; nearest valid row-shard "
            f"counts for N={n}: {nearest_row_shard_counts(n, num_rows)}")
    return num_rows


def _check_group_replicas(config: SolverConfig, mesh: Mesh) -> int:
    """Validate that the replica count splits evenly over the mesh's replica
    groups; returns the group count (1 on a 1-D mesh)."""
    grp_axes, _ = _mesh_axes_split(mesh)
    num_groups = _mesh_size(mesh, grp_axes)
    r = config.num_replicas
    if r % num_groups:
        valid = tuple(g for g in range(1, r + 1) if r % g == 0)
        raise ValueError(
            f"num_replicas={r} cannot split evenly over the {num_groups} "
            f"replica group(s) of mesh {_mesh_desc(mesh)} (group axes "
            f"{grp_axes}); use a replica count divisible by {num_groups} "
            f"or a group count in {valid}")
    return num_groups


def _psum_gather(x, j, lo, axes):
    """x[r, j[r]] with x row-sharded over the spin axis: the owner contributes
    the value, everyone else exact zeros, and the ``psum`` combine restores
    the global gather (v + 0 + … + 0 is exact in f32, so this is
    value-identical to the single-device ``take``)."""
    n_loc = x.shape[1]
    jl = jnp.clip(j - lo, 0, n_loc - 1)
    v = jnp.take_along_axis(x, jl[:, None], axis=1)[:, 0]
    own = (j >= lo) & (j < lo + n_loc)
    return jax.lax.psum(jnp.where(own, v, jnp.zeros((), x.dtype)), axes)


def _sharded_roulette(p_loc, u_roulette, lane, g0, axes):
    """``common.roulette_pick`` with the (R, N) wheel row-sharded.

    Level 1: local (R, G_loc) block sums, ``all_gather`` to the full (R, G)
    block weights (G = N/lane — N/128 f32s per replica, not N), then the
    *shared* ``common.roulette_block_pick`` replicated on every device.
    Level 2: the selected block's lane weights are psum-combined from the
    owner (masked zeros elsewhere) into the *shared*
    ``common.roulette_lane_pick``. Both levels therefore run the identical
    arithmetic of the single-device pick on identical values — the exactness
    argument of the four-way parity tier.
    """
    blk_loc = common.block_sums(p_loc, lane)             # (R, G_loc)
    blk = jax.lax.all_gather(blk_loc, axes, axis=1, tiled=True)  # (R, G)
    g, residual, total, degenerate = common.roulette_block_pick(
        blk, u_roulette[:, None])
    sel = jax.lax.psum(common.select_block(p_loc, g, lane, g0), axes)
    l = common.roulette_lane_pick(sel, residual)
    return ((g * lane + l)[:, 0].astype(jnp.int32), total[:, 0],
            degenerate[:, 0])


def _sharded_sweep(planes_loc: BitPlanes, fields0, spins0, energy0, uniforms,
                   temps, pwl_table, *, mode: str, uniformized: bool, n: int,
                   lane: int, axes, lo, g0, coalesce: bool = True):
    """T spin-sharded MCMC steps for R replicas — ``kernels.ref.mcmc_sweep``
    statement for statement, with every global op replaced by its collective
    counterpart (gathers → masked ``psum``, row fetch → psum row-tile
    broadcast + shared decode + local column slice). fields0/spins0 are the
    (R, N/D) local slices; energy0 and the uniforms/temps tensors are
    replicated. ``coalesce`` (default on) combines duplicate per-step row
    selections into one psum broadcast per *unique* row. Returns the
    local-slice analogue of the kernel's 7-tuple — the trailing (R,) int32
    counts row-tile broadcasts attributed per replica.
    """
    pos, neg = planes_loc.pos, planes_loc.neg            # (B, N/D, W) rows
    r, n_loc = fields0.shape
    col = lo + jnp.arange(n_loc)                         # global column ids

    num_planes = pos.shape[0]
    num_words = pos.shape[2]

    def issue(site_l, is_own):
        """One (2B, 1, W) stacked pos∥neg row-tile psum broadcast: the owner
        contributes its packed words, everyone else exact integer zeros."""
        tiles = jnp.concatenate(
            [jnp.take(pos, site_l, axis=1),
             jnp.take(neg, site_l, axis=1)], axis=0)[:, None, :]
        tiles = jnp.where(is_own, tiles, jnp.uint32(0))  # (2B, 1, W)
        return jax.lax.psum(tiles, axes)

    def decode(tiles):
        pr, nr = tiles[:num_planes], tiles[num_planes:]
        if n_loc % WORD_BITS == 0:
            w_lo = lo // WORD_BITS                   # lo % 32 == 0 too
            w_loc = n_loc // WORD_BITS
            pr = jax.lax.dynamic_slice_in_dim(pr, w_lo, w_loc, axis=2)
            nr = jax.lax.dynamic_slice_in_dim(nr, w_lo, w_loc, axis=2)
            return common.decode_bitplane_rows(pr, nr, n_loc)[0]  # (N/D,)
        rows = common.decode_bitplane_rows(pr, nr, n)[0]  # shared decode
        return jax.lax.dynamic_slice_in_dim(rows, lo, n_loc, axis=0)

    def fetch_rows(j):
        """(R,) global sites → ((R, N/D) decoded local row columns, (R,)
        int32 broadcast counts): the owner broadcasts its packed (B, 1, W)
        row tiles via masked psum (integer zeros add exactly), every device
        runs the identical ``decode_bitplane_rows`` expansion on its own
        slice. When the shard boundary is word-aligned (N/D % 32 == 0 —
        every lane-128 size) the packed words are sliced *before* decoding,
        keeping the per-device expansion O(B·N/D) instead of O(B·N); bit
        expansion is per-word, so slice-then-decode equals decode-then-slice
        value for value.

        The replica-apply loop is **software-pipelined** — the cross-device
        analogue of the HBM tier's DMA double-buffer: replica r+1's row-tile
        psum is *issued* before replica r's decode+FMA consumes its tiles
        (replicas are independent, so the prefetch is always safe), letting
        XLA's async collectives run the broadcast under the previous decode
        instead of blocking the step on a synchronous (B, R, W) combine. One
        psum per replica moves the stacked (2B, 1, W) pos∥neg tiles; uint32
        adds are exact, per-replica decode is the per-row expansion the
        batched form ran, and the stack keeps replica order — so the
        trajectory is bit-identical to the un-overlapped formulation (the
        four-way parity tier asserts it end to end).

        With ``coalesce`` the pipeline runs over the step's **unique** sites
        (``common.coalesce_rows``): slot m's psum is ``lax.cond``-gated on
        ``m < nu`` — the predicate is replicated (computed from the
        replicated j), so every device takes the same branch and the
        collective is jointly skipped, cutting interconnect traffic from R
        to nu broadcasts — and the decoded unique rows are gathered back to
        replica order with ``jnp.take``. The decoded row is a function of
        the site alone, so the broadcast-back is byte-identical to
        fetch-per-replica and the trajectory cannot move."""
        if coalesce:
            nu, usite, uo, fetched = common.coalesce_rows(j[:, None])
            usite, uo, fetched = usite[:, 0], uo[:, 0], fetched[:, 0]
            jl = jnp.clip(usite - lo, 0, n_loc - 1)
            own = (usite >= lo) & (usite < lo + n_loc)
            zeros = jnp.zeros((2 * num_planes, 1, num_words), jnp.uint32)

            def issue_unique(mi):
                return jax.lax.cond(mi < nu,
                                    lambda: issue(jl[mi], own[mi]),
                                    lambda: zeros)

            in_flight = issue_unique(0)
            rows = []
            for mi in range(r):           # static unroll: R is small
                tiles = in_flight
                if mi + 1 < r:
                    in_flight = issue_unique(mi + 1)
                rows.append(decode(tiles))
            # Broadcast the unique rows back to every selecting replica
            # (slots ≥ nu hold zeros and are never referenced by uo < nu).
            return jnp.take(jnp.stack(rows, axis=0), uo, axis=0), fetched

        jl = jnp.clip(j - lo, 0, n_loc - 1)
        own = (j >= lo) & (j < lo + n_loc)
        in_flight = issue(jl[0], own[0])
        rows = []
        for ri in range(r):               # static unroll: R is small
            tiles = in_flight
            if ri + 1 < r:
                # next broadcast under this decode
                in_flight = issue(jl[ri + 1], own[ri + 1])
            rows.append(decode(tiles))
        return jnp.stack(rows, axis=0), jnp.ones((r,), jnp.int32)

    def body(carry, xs):
        u, s, e, be, bs, nf, rf = carry
        u01, temp = xs                                   # (R, 4), (R,)
        sf = s.astype(jnp.float32)
        if mode == "rsa":
            j = common.site_from_uniform(u01[:, 0], n)
            u_j = _psum_gather(u, j, lo, axes)
            s_old = _psum_gather(sf, j, lo, axes)
            de = 2.0 * s_old * u_j
            p_j = common.flip_probability(de, temp, pwl_table)
            accept = u01[:, 1] < p_j
        else:
            de_all = 2.0 * sf * u                        # (R, N/D)
            p_all = common.flip_probability(de_all, temp[:, None], pwl_table)
            j_rw, total, degenerate = _sharded_roulette(
                p_all, u01[:, 2], lane, g0, axes)
            if uniformized:
                accept = jnp.where(degenerate, False,
                                   u01[:, 3] * jnp.float32(n) < total)
                j = j_rw
            else:
                j_fb = common.site_from_uniform(u01[:, 0], n)
                p_fb = _psum_gather(p_all, j_fb, lo, axes)
                accept = jnp.where(degenerate, u01[:, 1] < p_fb, True)
                j = jnp.where(degenerate, j_fb, j_rw)
            de = _psum_gather(de_all, j, lo, axes)
            s_old = _psum_gather(sf, j, lo, axes)
        acc_f = accept.astype(jnp.float32)
        rows, fetched = fetch_rows(j)                    # (R, N/D), (R,)
        rf = rf + fetched
        u = u - (2.0 * acc_f * s_old)[:, None] * rows
        onehot = (col[None, :] == j[:, None]).astype(sf.dtype)
        s = jnp.where(accept[:, None], (sf * (1 - 2 * onehot)).astype(s.dtype), s)
        e = e + acc_f * de
        nf = nf + accept.astype(jnp.int32)
        better = e < be
        be = jnp.where(better, e, be)
        bs = jnp.where(better[:, None], s, bs)
        return (u, s, e, be, bs, nf, rf), None

    init = (fields0.astype(jnp.float32), spins0,
            energy0.astype(jnp.float32), energy0.astype(jnp.float32),
            spins0, jnp.zeros((r,), jnp.int32), jnp.zeros((r,), jnp.int32))
    (u, s, e, be, bs, nf, rf), _ = jax.lax.scan(body, init, (uniforms, temps))
    return u, s, e, be, bs, nf, rf


def _sharded_init(planes_loc: BitPlanes, fields, base, *, r: int, n: int,
                  n_loc: int, lo, axes, r0=0):
    """Plane-native per-device replica init — ``ops.fused_init_state`` with
    every full-width touch replaced by its sharded counterpart, so neither
    the full (B, N, W) planes nor any dense J is ever needed on one device.

    Key derivation (``Salt.REPLICA`` → ``Salt.INIT``) and the spin draw are
    replicated computation — byte-for-byte the fused init's, O(R·N). Each
    device then runs the Hamming-weight accumulation on **its own plane
    slab** only (u^(J) is per-row arithmetic, so the row slice of the result
    equals the slice of the full-plane result bitwise), and e₀ is assembled
    by the shared ``ising.energy_from_fields`` on the ``all_gather``-ed
    u^(J) — the identical einsum the fused init runs on identical values, so
    sharded replicas start from bit-equal (u₀, s₀, e₀) for any h. Returns
    the local slices ``(u0_loc, s0_loc, e0)``.

    ``r0`` is the **global** index of this device's first replica (a replica
    group on a 2-D mesh inits its own contiguous block): key derivation is
    per-replica (``Salt.REPLICA`` folds the global index), so computing the
    block alone is bitwise the block slice of the full-R computation.
    """
    replica_keys = jax.vmap(
        lambda i: rng.stream(base, rng.Salt.REPLICA, i))(r0 + jnp.arange(r))
    spins0 = jax.vmap(lambda k: ising.random_spins(
        rng.stream(k, rng.Salt.INIT), (n,)))(replica_keys)
    spins0 = spins0.astype(jnp.float32)                  # (R, N) replicated
    u_j_loc = local_fields_from_planes(planes_loc, spins0)  # (R, N/D) exact
    h_loc = jax.lax.dynamic_slice_in_dim(fields, lo, n_loc)
    u0 = (u_j_loc + h_loc[None, :]).astype(jnp.float32)
    u_j = jax.lax.all_gather(u_j_loc, axes, axis=1, tiled=True)  # (R, N)
    e0 = ising.energy_from_fields(u_j, spins0, fields)
    s0 = jax.lax.dynamic_slice_in_dim(spins0, lo, n_loc, axis=1)
    return u0, s0, e0


def _group_layout(config: SolverConfig, mesh: Mesh, n: int):
    """The static (groups × rows) decomposition one (config, mesh, N) fixes:
    ``(grp_axes, row_axes, num_groups, r_loc, n_loc)`` with ``r_loc`` the
    per-group replica-block size and ``n_loc`` the per-row spin slice."""
    grp_axes, row_axes = _mesh_axes_split(mesh)
    num_groups = _mesh_size(mesh, grp_axes)
    num_rows = _mesh_size(mesh, row_axes)
    return grp_axes, row_axes, num_groups, config.num_replicas // num_groups, \
        n // num_rows


def _group_specs(grp_axes, row_axes):
    """PartitionSpecs of the 2-D layout — degenerate to the 1-D tier's specs
    when ``grp_axes`` is empty: replica-state arrays (R, N) shard replicas
    over the groups and spins over the rows, per-replica scalars (R,) shard
    over the groups alone, and the (chunks, R) trace shards its replica
    axis over the groups."""
    grp = tuple(grp_axes) if grp_axes else None
    rows = tuple(row_axes)
    state = P(grp, rows)
    rep = P(grp)
    trace = P(None, grp)
    return state, rep, trace


@functools.lru_cache(maxsize=32)
def sharded_anneal_fn(config: SolverConfig, mesh: Mesh, n: int, *,
                      chunk_steps: int = 256, coalesce: bool = True):
    """Build the jitted shard_map'd anneal for one (config, mesh, N).

    Returns ``fn(planes, fields, seed_arr) → (u, s, e, be, bs, nf, rows,
    trace)`` — ``rows`` is the (R,) per-replica row-broadcast count —
    with the planes sharded over the spin axis and ``fields`` (the (N,) h —
    O(N), not the O(N²) store) replicated; replica init runs *inside* the
    shard_map, plane-natively per device (:func:`_sharded_init`), so the
    driver never touches full planes or a dense J on any single host.
    Memoized on the (hashable) arguments so repeated solves of one
    configuration reuse the jitted callable instead of re-tracing per call —
    ``jax.jit`` caches on function identity, and ``local_anneal`` is a fresh
    closure per build (the analogue of ``_fused_anneal_impl``'s module-level
    jit). The per-step jaxpr pin (collectives present, no ``dot_general``)
    lives on :func:`sharded_sweep_fn` — the one-time init here legitimately
    contains O(R·N) contractions (the e₀ einsum and the popcount weighting).

    On a multi-axis mesh the leading axes are replica groups: each group's
    devices run the block of ``R / G`` replicas at global indices
    ``[g·R/G, (g+1)·R/G)``, with per-chunk uniforms drawn at the full
    (clen, R, 4) shape and ``dynamic_slice``d to the block — so every
    replica consumes exactly the bits the 1-D and fused paths would hand
    it, and the gathered (R, ·) outputs are bit-identical to theirs.
    """
    grp_axes, row_axes, num_groups, r_loc, n_loc = _group_layout(
        config, mesh, n)
    r_total = config.num_replicas
    lane = common.default_lane(n)
    g_loc = n_loc // lane
    chunk_len, num_chunks, rem_steps = _ops.anneal_chunk_plan(
        config, chunk_steps)
    tbl = _ops.solver_pwl_table(config)

    def local_anneal(planes_loc, fields, seed_arr):
        row_idx = _flat_shard_index(mesh, row_axes)
        lo = row_idx * n_loc
        g0 = row_idx * g_loc
        r0 = _flat_shard_index(mesh, grp_axes) * r_loc
        base = jax.random.fold_in(jax.random.key(0), seed_arr[0])
        u0, s0, e0 = _sharded_init(planes_loc, fields, base, r=r_loc, n=n,
                                   n_loc=n_loc, lo=lo, axes=row_axes, r0=r0)
        state = (u0, s0, e0, e0, s0, jnp.zeros((r_loc,), jnp.int32))
        rows0 = jnp.zeros((r_loc,), jnp.int32)

        def chunk(carry, c, clen):
            # Same per-chunk Salt.SWEEP stream, temps tensor, and
            # best-so-far merge as ops.fused_sweep_chunk — replicated
            # computation, identical on every device; the group consumes
            # its contiguous replica block of the full-R draw.
            steps = c * chunk_len + jnp.arange(clen)
            temps = jax.vmap(config.schedule)(steps).astype(jnp.float32)
            temps = jnp.broadcast_to(temps[:, None], (clen, r_loc))
            uniforms = rng.uniform01(
                rng.stream(base, rng.Salt.SWEEP, c), (clen, r_total, 4))
            uniforms = jax.lax.dynamic_slice_in_dim(uniforms, r0, r_loc,
                                                    axis=1)
            (u, s, e, be, bs, nf), rows = carry
            u, s, e, ce, cs, cf, rf = _sharded_sweep(
                planes_loc, u, s, e, uniforms, temps, tbl,
                mode=config.mode, uniformized=config.uniformized, n=n,
                lane=lane, axes=row_axes, lo=lo, g0=g0, coalesce=coalesce)
            better = ce < be
            state = (u, s, e, jnp.where(better, ce, be),
                     jnp.where(better[:, None], cs, bs), nf + cf)
            return (state, rows + rf), state[3]  # best-so-far at chunk end

        (state, rows), trace = jax.lax.scan(
            partial(chunk, clen=chunk_len), (state, rows0),
            jnp.arange(num_chunks))
        if rem_steps:
            (state, rows), _ = chunk((state, rows), jnp.int32(num_chunks),
                                     clen=rem_steps)
        u, s, e, be, bs, nf = state
        return u, s, e, be, bs, nf, rows, trace

    state_s, rep_s, trace_s = _group_specs(grp_axes, row_axes)
    return jax.jit(shard_map_compat(
        local_anneal, mesh=mesh,
        in_specs=(P(None, tuple(row_axes), None), P(), P()),
        out_specs=(state_s, state_s, rep_s, rep_s, state_s, rep_s, rep_s,
                   trace_s)))


@functools.lru_cache(maxsize=32)
def sharded_init_fn(config: SolverConfig, mesh: Mesh, n: int):
    """A jitted shard_map around :func:`_sharded_init` alone — the one-time
    replica init without the anneal, for drivers that advance the chain in
    host-visible chunks (the resilient supervisor, ``core.resilience``).
    Signature: ``fn(planes, fields, seed_arr) → (u0_loc, s0_loc, e0)`` with
    planes/u/s sharded over the spin axis and e₀ replicated — exactly the
    state ``sharded_anneal_fn``'s ``local_anneal`` starts from, so a chunked
    drive of :func:`sharded_sweep_fn` from this init replays the monolithic
    trajectory bit for bit (2-D meshes included: each replica group inits
    its own global-index replica block)."""
    grp_axes, row_axes, _, r_loc, n_loc = _group_layout(config, mesh, n)

    def local_init(planes_loc, fields, seed_arr):
        row_idx = _flat_shard_index(mesh, row_axes)
        r0 = _flat_shard_index(mesh, grp_axes) * r_loc
        base = jax.random.fold_in(jax.random.key(0), seed_arr[0])
        return _sharded_init(planes_loc, fields, base, r=r_loc, n=n,
                             n_loc=n_loc, lo=row_idx * n_loc, axes=row_axes,
                             r0=r0)

    state_s, rep_s, _ = _group_specs(grp_axes, row_axes)
    return jax.jit(shard_map_compat(
        local_init, mesh=mesh,
        in_specs=(P(None, tuple(row_axes), None), P(), P()),
        out_specs=(state_s, state_s, rep_s)))


def sharded_sweep_fn(config: SolverConfig, mesh: Mesh, n: int, *,
                     coalesce: bool = True):
    """A jitted shard_map around :func:`_sharded_sweep` alone — the per-step
    engine without the one-time init. This is the jaxpr-pin surface: the
    *step* must move data with collectives (psum row-tile broadcast,
    all_gather'd block sums) and must never reintroduce a quadratic
    contraction (``dot_general``) — the O(N)/step incremental-update
    contract extended across the mesh. Signature:
    ``fn(planes, u0_loc, s0_loc, e0, uniforms, temps)`` with planes/u/s
    sharded over the spin axis; the seventh output is the (R,) row-broadcast
    counter. ``coalesce=False`` restores the one-psum-per-replica fetch —
    the uncoalesced oracle the parity tests diff against.

    The uniforms/temps inputs are always the **full-R** (T, R, 4) / (T, R)
    tensors, replicated; on a 2-D mesh each replica group ``dynamic_slice``s
    its contiguous block — so the chunked driver feeds identical host-side
    tensors to every mesh shape, and the jaxpr pin can assert that the only
    collectives in the step are scoped to the rows sub-axis (no cross-group
    traffic on the hot path).
    """
    grp_axes, row_axes, _, r_loc, n_loc = _group_layout(config, mesh, n)
    lane = common.default_lane(n)
    g_loc = n_loc // lane
    tbl = _ops.solver_pwl_table(config)

    def local_sweep(planes_loc, u0, s0, e0, uniforms, temps):
        row_idx = _flat_shard_index(mesh, row_axes)
        r0 = _flat_shard_index(mesh, grp_axes) * r_loc
        uniforms = jax.lax.dynamic_slice_in_dim(uniforms, r0, r_loc, axis=1)
        temps = jax.lax.dynamic_slice_in_dim(temps, r0, r_loc, axis=1)
        return _sharded_sweep(
            planes_loc, u0, s0, e0, uniforms, temps, tbl, mode=config.mode,
            uniformized=config.uniformized, n=n, lane=lane, axes=row_axes,
            lo=row_idx * n_loc, g0=row_idx * g_loc, coalesce=coalesce)

    state_s, rep_s, _ = _group_specs(grp_axes, row_axes)
    return jax.jit(shard_map_compat(
        local_sweep, mesh=mesh,
        in_specs=(P(None, tuple(row_axes), None), state_s, state_s, rep_s,
                  P(), P()),
        out_specs=(state_s, state_s, rep_s, rep_s, state_s, rep_s, rep_s)))


def shard_planes_from_edges(edges: ising.EdgeList, mesh: Mesh,
                            num_planes: Optional[int] = None) -> BitPlanes:
    """Edge list → row-sharded plane store with **no full-plane host build**:
    each device's (B, N/D, W) slab is encoded directly from the O(nnz) edge
    arrays (``bitplane.edge_plane_words`` with ``row_range``) and placed via
    ``jax.make_array_from_callback``, so the complete (B, N, W) store — let
    alone the (N, N) f32 J — never exists on any single host or device. This
    is the ingestion path that moves the init wall: setup cost becomes
    O(nnz + plane-slab bytes) per device instead of O(N²) on one host.

    On a 2-D mesh the slabs shard over the **rows** (last) axis only and
    replicate across the replica-group axes; the slab cache below encodes
    each distinct row range exactly once per host, so the G group copies
    of one slab cost one encode, not G.
    """
    _, row_axes = _mesh_axes_split(mesh)
    n = edges.num_spins
    _check_row_shardable(n, mesh)
    if num_planes is None:
        num_planes = max(1, edges.max_abs_weight.bit_length())
    align = coupling_store.FORMATS["bitplane_sharded"].align_words
    w_min = -(-n // WORD_BITS)
    num_words = -(-w_min // align) * align
    sharding = NamedSharding(mesh, P(None, tuple(row_axes), None))
    shape = (num_planes, n, num_words)
    slabs = {}

    def slab(index):
        sl = index[1]
        lo = 0 if sl.start is None else int(sl.start)
        hi = n if sl.stop is None else int(sl.stop)
        if (lo, hi) not in slabs:   # encode each row slab exactly once
            slabs[(lo, hi)] = edge_plane_words(
                edges, num_planes, align_words=align, row_range=(lo, hi))
        return slabs[(lo, hi)]

    pos = jax.make_array_from_callback(shape, sharding,
                                       lambda idx: slab(idx)[0])
    neg = jax.make_array_from_callback(shape, sharding,
                                       lambda idx: slab(idx)[1])
    return BitPlanes(pos=pos, neg=neg, num_spins=n)


def resolve_sharded_planes(problem, config: SolverConfig, mesh: Mesh, *,
                           coupling: Optional[BitPlanes] = None,
                           num_planes: Optional[int] = None) -> BitPlanes:
    """Validate a (problem, config, mesh) triple for the sharded tier and
    produce the row-sharded plane store — the shared front door of
    ``solve_sharded`` and the resilient supervisor. Pre-packed ``coupling``
    planes skip the re-encode; edge-list problems encode per-device slabs
    straight from the O(nnz) edges; a dense J routes through
    ``CouplingStore.build``. Raises the driver's routing/alignment errors.
    On a multi-axis mesh the resolved format is ``bitplane_sharded_2d``
    (row-sharded within each replica group, replicated across groups)."""
    n = problem.num_spins
    grp_axes, _ = _mesh_axes_split(mesh)
    fmt = "bitplane_sharded_2d" if grp_axes else "bitplane_sharded"
    if config.coupling_format not in ("auto", "bitplane_sharded",
                                      "bitplane_sharded_2d"):
        raise ValueError(
            f"solve_sharded serves coupling_format='bitplane_sharded' / "
            f"'bitplane_sharded_2d' (or 'auto'), got "
            f"{config.coupling_format!r} — use solve(backend='fused') for "
            f"the single-device tiers")
    if config.coupling_format == "bitplane_sharded_2d" and not grp_axes:
        raise ValueError(
            f"coupling_format='bitplane_sharded_2d' needs a (groups..., "
            f"rows) mesh with at least 2 axes; mesh {_mesh_desc(mesh)} has "
            f"one — use 'bitplane_sharded' (or 'auto') for 1-D meshes")
    _check_row_shardable(n, mesh)
    _check_group_replicas(config, mesh)
    if coupling is not None:
        store = coupling_store.CouplingStore.from_planes(coupling, fmt)
        coupling_store.validate_planes_cover(coupling, n)
        return store.planes
    if problem.couplings is None:
        return shard_planes_from_edges(problem.edges, mesh, num_planes)
    store = coupling_store.CouplingStore.build(
        problem.couplings, fmt, num_planes=num_planes)
    return store.planes


def solve_sharded(problem, seed, config: SolverConfig, mesh: Mesh, *,
                  chunk_steps: int = 256,
                  coupling: Optional[BitPlanes] = None,
                  num_planes: Optional[int] = None,
                  coalesce: bool = True) -> SolveResult:
    """Anneal with the coupling planes row-sharded across ``mesh``.

    Trajectory-identical to ``solve(..., backend="fused")`` on the same
    seed/config (any single-device coupling tier): same replica init (now
    computed plane-natively *inside* the shard_map — each device initializes
    its own u₀ slice from its plane slab, e₀ via the shared
    ``energy_from_fields`` einsum on the gathered u^(J)), same ``Salt.SWEEP``
    chunk streams, same selection/update arithmetic via ``kernels.common`` —
    only the memory placement changes. Per-device plane bytes are
    ``store.nbytes / D``, so J capacity scales with aggregate HBM — and for
    **edge-list problems** the planes are encoded per device straight from
    the O(nnz) edges (:func:`shard_planes_from_edges`), so no host ever
    materializes the full store or any dense J at any point of the solve.

    On a multi-axis mesh the last axis row-shards the planes within each
    replica group and the leading axes replicate the planes across
    independent replica groups (the ``bitplane_sharded_2d`` tier): per-device
    J bytes are ``store.nbytes / rows_per_group`` while replica throughput
    scales with the group count, and the (R, ·) results are still
    bit-identical to the fused and 1-D paths.

    Requires an integral J (the sharded store is plane-backed; there is no
    sharded dense tier), N divisible by the row-shard count with per-shard
    spin counts divisible by the roulette lane (block-aligned sharding), and
    ``config.num_replicas`` divisible by the group count.
    ``config.coupling_format`` must be "auto", "bitplane_sharded", or (2-D
    meshes) "bitplane_sharded_2d".
    ``coupling`` takes pre-packed tile-aligned planes to skip the re-encode
    (the benchmark path); ``num_planes`` forces the precision B.
    ``coalesce`` (default on) broadcasts each step's unique rows once
    instead of once per replica — identical trajectories, and the result's
    ``rows_fetched`` records the realized per-replica broadcast counts.
    """
    n = problem.num_spins
    planes = resolve_sharded_planes(problem, config, mesh, coupling=coupling,
                                    num_planes=num_planes)
    r = config.num_replicas
    fn = sharded_anneal_fn(config, mesh, n, chunk_steps=chunk_steps,
                           coalesce=coalesce)
    seed_arr = jnp.asarray([seed], jnp.uint32)
    u, s, e, be, bs, nf, rows, trace = fn(planes, problem.fields, seed_arr)
    return SolveResult(
        best_energy=be + problem.offset,
        best_spins=bs.astype(jnp.int8),
        final_energy=e + problem.offset,
        num_flips=nf,
        trace_energy=((trace + problem.offset).astype(jnp.float32)
                      if config.trace_every else jnp.zeros((0, r), jnp.float32)),
        rows_fetched=rows,
    )
