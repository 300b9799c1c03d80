"""Jit'd public wrappers around the Pallas kernels.

``fused_anneal`` is the *production* solver backend (DESIGN.md §Backends): it
runs the annealing loop in chunks of the VMEM-resident sweep kernel, with
uniforms drawn from the dedicated ``Salt.SWEEP`` stateless threefry stream
(disjoint by construction from every stream the reference engine consumes).
``repro.core.solver.solve`` with ``backend="reference"`` remains the
paper-faithful oracle; ``backend="fused"`` routes through this module. Both
are benchmarked side by side in ``BENCH_solver_perf.json``.

On this CPU container kernels run in interpret mode (the Mosaic TPU backend is
the target); ``interpret=None`` auto-detects.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import ising, rng
from ..core.bitplane import BitPlanes, local_fields_from_planes, pack_spins
# The coupling-store subsystem (format registry, resolve/encode, the VMEM/HBM
# wall constants) is first-class in ``core.coupling``; this module re-exports
# the long-standing names so kernel-level callers keep working.
from ..core.coupling import (  # noqa: F401  (re-exported API)
    BITPLANE_VMEM_MAX_N, COUPLING_FORMATS, DENSE_COUPLING_BITS,
    DENSE_COUPLING_MAX_N, KERNEL_COUPLING_MODES, PLANE_FORMATS,
    STREAM_ALIGN_WORDS, CouplingStore)
from ..core.coupling import encode_planes as encode_for_sweep  # noqa: F401
from ..core.coupling import resolve_format as resolve_coupling_format  # noqa: F401
from ..core.pwl import pwl_table as _pwl_table
from ..core.solver import SolverConfig, SolveResult
from . import bitplane_field as _bitplane_field
from . import local_field as _local_field
from . import sweep as _sweep
from .common import replica_block

#: N at or below which the one-hot MXU row gather beats per-replica dynamic
#: slices (one small matmul vs br sequential row DMAs) — the opt-in heuristic
#: resolved by ``gather="auto"``.
ONEHOT_GATHER_MAX_N = 128


def auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def local_field_init(spins: jax.Array, couplings: jax.Array, bias: jax.Array,
                     *, interpret: Optional[bool] = None, **kw) -> jax.Array:
    """Batched u = J s + h via the MXU matmul kernel."""
    r, n = spins.shape
    kw.setdefault("block_r", replica_block(r, 8))
    # Contraction blocks must divide N and be lane multiples; else all of N.
    kw.setdefault("block_k", next((k for k in (512, 384, 256, 128)
                                   if n % k == 0), n))
    return _local_field.local_field_init(
        spins, couplings, bias, interpret=auto_interpret(interpret), **kw)


def bitplane_field_init(planes: BitPlanes, spins: jax.Array,
                        *, interpret: Optional[bool] = None, **kw) -> jax.Array:
    """Batched u^(J) from packed bit-planes via the popcount kernel.

    Spin words are packed to the planes' word count so tile-padded (HBM-
    streamed) plane stores line up — padding words are zero on both sides.
    """
    words = pack_spins(spins, planes.num_words)
    return _bitplane_field.bitplane_field_init(
        planes.pos, planes.neg, words, interpret=auto_interpret(interpret), **kw)


def _resolve_gather(gather: str, n: int) -> str:
    if gather == "auto":
        return "onehot" if n <= ONEHOT_GATHER_MAX_N else "dynamic"
    return gather


def plane_local_fields(planes: BitPlanes, spins0: jax.Array, *,
                       interpret: bool, block_r: int = 8) -> jax.Array:
    """u^(J) = J s from the packed planes via the Hamming-weight accumulation
    (Eq. 14-16) — the popcount Pallas kernel on real TPUs, its jnp oracle in
    interpret mode (tile-by-tile interpret emulation has a huge constant
    factor; same reason the dense init uses XLA's native matmul there). For
    integer J both are the exact integer result in f32, so everything built
    on this value (u₀, the plane-native e₀) is bit-identical to the dense
    matmul path."""
    if interpret:
        return local_fields_from_planes(planes, spins0)
    return bitplane_field_init(planes, spins0, interpret=False,
                               block_r=block_r)


def init_fields(problem: ising.IsingProblem, spins0: jax.Array, *,
                interpret: bool, block_r: int = 8,
                planes: Optional[BitPlanes] = None) -> jax.Array:
    """One-time u₀ = J s + h init for the fused drivers (plane-backed or
    dense; see :func:`plane_local_fields` for the packed path)."""
    if planes is not None:
        u_j = plane_local_fields(planes, spins0, interpret=interpret,
                                 block_r=block_r)
        return (u_j + problem.fields[None, :]).astype(jnp.float32)
    if interpret:
        return ising.local_fields(problem, spins0).astype(jnp.float32)
    r = spins0.shape[0]
    return local_field_init(spins0, problem.couplings, problem.fields,
                            interpret=False,
                            block_r=replica_block(r, block_r))


def fused_init_state(problem: ising.IsingProblem, base: jax.Array, r: int, *,
                     interpret: bool, block_r: int = 8,
                     planes: Optional[BitPlanes] = None):
    """Replica init for the fused drivers: the ``(u, s, e, best_e, best_s,
    num_flips)`` state tuple. Key derivation (``Salt.REPLICA`` → ``Salt.INIT``)
    is exactly the reference engine's, so both backends start every replica
    from the identical spin configuration — a single definition keeps that
    parity contract in one place.

    With ``planes`` the init is fully **dense-J-free**: u₀ comes from the
    packed store and e₀ is assembled by ``ising.energy_from_fields`` on the
    same u^(J) — the identical einsum contractions ``ising.energy`` runs on
    ``J s``, fed a bit-identical u^(J) (integer J ⇒ the Hamming-weight sum
    equals the f32 matmul exactly), so plane-fed and dense-fed replicas
    start from bitwise-equal energies for any h. Edge-list problems
    (``problem.couplings is None``) therefore never touch a dense matrix
    here.
    """
    n = problem.num_spins
    replica_keys = jax.vmap(lambda i: rng.stream(base, rng.Salt.REPLICA, i))(jnp.arange(r))
    spins0 = jax.vmap(lambda k: ising.random_spins(
        rng.stream(k, rng.Salt.INIT), (n,)))(replica_keys)
    spins0 = spins0.astype(jnp.float32)
    if planes is not None:
        u_j = plane_local_fields(planes, spins0, interpret=interpret,
                                 block_r=block_r)
        u0 = (u_j + problem.fields[None, :]).astype(jnp.float32)
        e0 = ising.energy_from_fields(u_j, spins0, problem.fields)
    else:
        u0 = init_fields(problem, spins0, interpret=interpret, block_r=block_r)
        e0 = ising.energy(problem, spins0)
    return (u0, spins0, e0, e0, spins0, jnp.zeros((r,), jnp.int32))


def solver_pwl_table(config: SolverConfig) -> Optional[jax.Array]:
    """The (S+1, 3) VMEM LUT for ``config``, or None for the exact sigmoid."""
    if not config.use_pwl:
        return None
    return _pwl_table(config.pwl_segments, config.pwl_zmax)


def fused_sweep_chunk(couplings: Union[jax.Array, BitPlanes], state,
                      chunk_key: jax.Array, num_steps: int, temps: jax.Array,
                      *, mode: str, uniformized: bool = False,
                      pwl_table: Optional[jax.Array] = None,
                      gather: str = "dynamic", block_r: int = 8,
                      coupling: Optional[str] = None, coalesce: bool = True,
                      with_rows_fetched: bool = False,
                      interpret: bool = False):
    """One fused sweep chunk + best-so-far merge — the single chunk driver
    shared by ``fused_anneal``, fused tempering, and the fused distributed
    runner, so kernel-signature changes happen in exactly one place.

    ``couplings`` is the dense (N, N) J or a packed ``BitPlanes``.
    ``coupling`` selects the kernel's J store ("dense" | "bitplane" |
    "bitplane_hbm"); None infers from the type — a ``BitPlanes`` defaults to
    the VMEM-resident "bitplane" path, so the HBM-streamed tier must be
    requested explicitly (the drivers pass their resolved format through).
    ``state`` is the 6-tuple ``(u, s, e, best_e, best_s, num_flips)`` with a
    leading replica axis; ``chunk_key`` is the chunk's ``Salt.SWEEP`` stream;
    ``temps`` is the (num_steps, R) per-replica temperature tensor.
    ``coalesce`` flows to the kernel's reuse-aware unique-row fetch (only the
    HBM-streamed tier reacts; trajectories are bit-identical either way).
    Returns the updated state tuple — the 6-tuple is the snapshot/resume
    contract, so the kernel's rows-fetched counter is only surfaced when
    ``with_rows_fetched`` asks for it, as a second ``(state, rf)`` element.
    """
    u, s, e, be, bs, nf = state
    r = e.shape[0]
    if coupling is None:
        coupling = "bitplane" if isinstance(couplings, BitPlanes) else "dense"
    uniforms = rng.uniform01(chunk_key, (num_steps, r, 4))
    u, s, e, ce, cs, cf, rf = _sweep.mcmc_sweep(
        couplings, u, s, e, uniforms, temps, pwl_table, mode=mode,
        uniformized=uniformized, gather=gather, coupling=coupling,
        block_r=block_r, coalesce=coalesce, interpret=interpret)
    better = ce < be
    state = (u, s, e, jnp.where(better, ce, be),
             jnp.where(better[:, None], cs, bs), nf + cf)
    return (state, rf) if with_rows_fetched else state


def anneal_chunk_plan(config: SolverConfig, chunk_steps: int):
    """(chunk_len, num_chunks, rem_steps) for a fused-trajectory anneal.

    Trace cadence is identical to the reference backend: with tracing on,
    chunks are exactly ``trace_every`` steps and the trace records
    best-so-far energy at every chunk end (both backends then run
    num_chunks·trace_every steps); ``chunk_steps`` is only the perf knob
    for untraced runs, where a remainder sweep keeps the total at exactly
    ``num_steps`` like the reference scan. Shared by the Pallas anneal and
    the spin-sharded anneal — identical chunking (hence identical per-chunk
    ``Salt.SWEEP`` streams) is a precondition for their exact parity.
    """
    if config.trace_every:
        chunk_len = config.trace_every
        num_chunks = max(config.num_steps // chunk_len, 1)
        rem_steps = 0
    else:
        chunk_len = max(min(chunk_steps, config.num_steps), 1)
        num_chunks = config.num_steps // chunk_len
        rem_steps = config.num_steps - num_chunks * chunk_len
    return chunk_len, num_chunks, rem_steps


def anneal_gather(store: CouplingStore, gather: str, n: int) -> str:
    """Resolve the row-fetch strategy for a resolved store: plane tiers take
    the O(N) dynamic fetch ("auto"/"dynamic" — an explicit "onehot" flows
    through so the kernel raises its dense-only error rather than being
    silently overridden), the dense tier applies the N-crossover heuristic.
    Shared by ``_fused_anneal_impl`` and the resilient chunked driver so both
    feed the kernel identically."""
    if store.planes is not None:
        return gather if gather == "onehot" else "dynamic"
    return _resolve_gather(gather, n)


def anneal_chunk_step(store: CouplingStore, state, base: jax.Array,
                      c: jax.Array, *, clen: int, chunk_len: int,
                      config: SolverConfig, gather: str, block_r: int,
                      interpret: bool, with_rows_fetched: bool = False):
    """One annealing chunk of the fused trajectory: the temps tensor for
    global steps ``[c·chunk_len, c·chunk_len + clen)``, the chunk's
    ``Salt.SWEEP`` stream, and the sweep+merge of :func:`fused_sweep_chunk`.
    This is the single chunk body under ``_fused_anneal_impl``'s scan AND the
    resilient supervisor's per-chunk jit (``core.resilience``) — one
    definition is what makes the resumed trajectory bit-identical to the
    uninterrupted scan. ``with_rows_fetched`` surfaces the kernel's
    rows-fetched counter as a second return (the state stays the bare
    6-tuple — the snapshot contract; the resilient runner carries the sum
    beside it)."""
    r = config.num_replicas
    steps = c * chunk_len + jnp.arange(clen)
    temps = jax.vmap(config.schedule)(steps).astype(jnp.float32)
    temps = jnp.broadcast_to(temps[:, None], (clen, r))
    return fused_sweep_chunk(
        store.kernel_operand, state, rng.stream(base, rng.Salt.SWEEP, c),
        clen, temps, mode=config.mode, uniformized=config.uniformized,
        pwl_table=solver_pwl_table(config), gather=gather,
        block_r=block_r, coupling=store.fmt,
        with_rows_fetched=with_rows_fetched, interpret=interpret)


@partial(jax.jit, static_argnames=("config", "chunk_steps", "block_r",
                                   "gather", "interpret"))
def _fused_anneal_impl(problem: ising.IsingProblem, seed: jax.Array,
                       config: SolverConfig, chunk_steps: int, block_r: int,
                       gather: str, interpret: bool,
                       store: CouplingStore) -> SolveResult:
    n = problem.num_spins
    r = config.num_replicas
    planes = store.planes
    base = jax.random.fold_in(jax.random.key(0), seed)
    init = fused_init_state(problem, base, r, interpret=interpret,
                            block_r=block_r, planes=planes)
    gather = anneal_gather(store, gather, n)

    chunk_len, num_chunks, rem_steps = anneal_chunk_plan(config, chunk_steps)

    def chunk(carry, c, clen):
        state, rows = carry
        state, rf = anneal_chunk_step(store, state, base, c, clen=clen,
                                      chunk_len=chunk_len, config=config,
                                      gather=gather, block_r=block_r,
                                      interpret=interpret,
                                      with_rows_fetched=True)
        return (state, rows + rf), state[3]  # best-so-far energy at chunk end

    init = (init, jnp.zeros((r,), jnp.int32))
    ((u, s, e, be, bs, nf), rows), trace = jax.lax.scan(
        partial(chunk, clen=chunk_len), init, jnp.arange(num_chunks))
    if rem_steps:
        ((u, s, e, be, bs, nf), rows), _ = chunk(
            ((u, s, e, be, bs, nf), rows), jnp.int32(num_chunks),
            clen=rem_steps)
    return SolveResult(
        best_energy=be + problem.offset,
        best_spins=bs.astype(jnp.int8),
        final_energy=e + problem.offset,
        num_flips=nf,
        trace_energy=((trace + problem.offset).astype(jnp.float32)
                      if config.trace_every else jnp.zeros((0, r), jnp.float32)),
        rows_fetched=rows,
    )


class ColoredPlan:
    """Host-side execution plan for the colored sweep: the coloring, the
    color-permuted problem, and the static window math the kernel schedule is
    built from. Built once per (problem, format) by :func:`colored_plan`;
    the permuted spin order is ``coloring.perm`` and results map back through
    ``coloring.inverse_perm``.

    Window math: the kernel slices its window at a dynamic lane offset,
    which must be a multiple of L = ``common.LANE_TILE`` (128), out of the
    state padded to ``N_pad = roundup(N, L)``. The static class window is
    ``S = min(N_pad, roundup(max_class_size + L - 1, L))`` and class c
    starts its window at ``w_c = min((offsets[c] // L)·L, N_pad - S)`` —
    both multiples of L. Coverage: ``w_c ≤ offsets[c]`` (floor) and
    ``w_c + S ≥ offsets[c] - (L-1) + (size_c + L - 1) = offsets[c] +
    size_c`` (or ``= N_pad`` when clamped), so every class fits its
    lane-aligned window.
    """

    def __init__(self, coloring, problem: ising.IsingProblem, fmt,
                 num_planes: Optional[int] = None):
        from .common import LANE_TILE, round_up

        n = problem.num_spins
        self.coloring = coloring
        perm = coloring.perm
        inv = coloring.inverse_perm
        if problem.edges is not None:
            pedges = ising.EdgeList.create(
                inv[problem.edges.rows], inv[problem.edges.cols],
                problem.edges.weights, n)
            self.problem = ising.IsingProblem.create_sparse(
                pedges, h=problem.fields[jnp.asarray(perm)],
                offset=problem.offset)
        else:
            p = jnp.asarray(perm)
            self.problem = ising.IsingProblem.create(
                problem.couplings[p][:, p], h=problem.fields[p],
                offset=problem.offset, check=False)
        self.store = CouplingStore.build(self.problem.coupling_source, fmt,
                                         num_planes=num_planes)
        self.store.require(KERNEL_COUPLING_MODES, "colored_anneal")
        import numpy as _np

        lane = LANE_TILE
        n_pad = round_up(n, lane)
        self.window = min(n_pad, round_up(coloring.max_class_size + lane - 1,
                                          lane))
        offs = coloring.offsets[:-1]
        w = _np.minimum((offs // lane) * lane, n_pad - self.window)
        self.wstarts = jnp.asarray(w, jnp.int32)
        self.offsets = jnp.asarray(offs, jnp.int32)
        self.sizes = jnp.asarray(coloring.class_sizes, jnp.int32)

    # Registered as a pytree (coloring + static window in aux — Coloring is
    # content-hashed, so jit caches key on coloring identity) so the jitted
    # anneal impl takes the plan whole.
    def tree_flatten(self):
        return ((self.problem, self.store, self.wstarts, self.offsets,
                 self.sizes), (self.coloring, self.window))

    @classmethod
    def tree_unflatten(cls, aux, children):
        plan = cls.__new__(cls)
        (plan.problem, plan.store, plan.wstarts, plan.offsets,
         plan.sizes) = children
        plan.coloring, plan.window = aux
        return plan


jax.tree_util.register_pytree_node_class(ColoredPlan)


def colored_plan(problem: ising.IsingProblem, fmt: str = "auto",
                 num_planes: Optional[int] = None) -> ColoredPlan:
    """Coloring + permutation + store for a colored solve of ``problem``.

    The greedy coloring runs on the conflict graph of
    ``problem.coupling_source`` (memoized per edge-list digest), the problem
    and its coupling store are rebuilt in color-sorted spin order (classes
    contiguous — the kernel schedules one contiguous window per step), and
    the lane-aligned window schedule is precomputed. Dense-J-free for
    edge-list problems end to end: coloring is O(N + nnz) over the COO
    edges and the permuted store runs the O(nnz) sparse encoder.
    """
    from ..graphs.coloring import greedy_coloring

    return ColoredPlan(greedy_coloring(problem.coupling_source), problem, fmt,
                       num_planes=num_planes)


def colored_sweep_chunk(couplings, state, chunk_key: jax.Array,
                        num_steps: int, temps: jax.Array, sched: jax.Array, *,
                        window: int, pwl_table: Optional[jax.Array] = None,
                        block_r: int = 8, coupling: str = "dense",
                        with_rows_fetched: bool = False,
                        interpret: bool = False):
    """One colored sweep chunk + best-so-far merge — the colored counterpart
    of :func:`fused_sweep_chunk`, with the identical 6-tuple state contract
    (snapshot/resume) and per-chunk ``Salt.SWEEP`` uniform stream. The chunk
    draws ``(num_steps, R, window)`` accept uniforms (one per window slot —
    the colored analogue of the single-flip path's 4 streams/step); ``sched``
    is the (num_steps, 3) class schedule from the plan arrays."""
    u, s, e, be, bs, nf = state
    r = e.shape[0]
    uniforms = rng.uniform01(chunk_key, (num_steps, r, window))
    u, s, e, ce, cs, cf, rf = _sweep.colored_sweep(
        couplings, u, s, e, uniforms, temps, sched, pwl_table,
        coupling=coupling, block_r=block_r, interpret=interpret)
    better = ce < be
    state = (u, s, e, jnp.where(better, ce, be),
             jnp.where(better[:, None], cs, bs), nf + cf)
    return (state, rf) if with_rows_fetched else state


def colored_class_schedule(wstarts: jax.Array, offsets: jax.Array,
                           sizes: jax.Array, steps: jax.Array) -> jax.Array:
    """(T, 3) int32 kernel schedule for absolute step indices ``steps``:
    round-robin over the χ color classes keyed on the *global* step, so a
    chunked/resumed trajectory visits the identical class sequence as one
    monolithic run (the colored leg of the resume-parity contract)."""
    cls = (steps % wstarts.shape[0]).astype(jnp.int32)
    return jnp.stack([jnp.take(wstarts, cls), jnp.take(offsets, cls),
                      jnp.take(sizes, cls)], axis=1)


def colored_chunk_step(plan: ColoredPlan, state, base: jax.Array,
                       c: jax.Array, *, clen: int, chunk_len: int,
                       config: SolverConfig, block_r: int, interpret: bool,
                       with_rows_fetched: bool = False):
    """One annealing chunk of the colored trajectory — the single chunk body
    under ``_colored_anneal_impl``'s scan AND the resilient supervisor's
    per-chunk jit, mirroring :func:`anneal_chunk_step` (same temps tensor,
    same per-chunk ``Salt.SWEEP`` stream), so chunked resume is bit-identical
    to the uninterrupted scan."""
    r = config.num_replicas
    steps = c * chunk_len + jnp.arange(clen)
    temps = jax.vmap(config.schedule)(steps).astype(jnp.float32)
    temps = jnp.broadcast_to(temps[:, None], (clen, r))
    sched = colored_class_schedule(plan.wstarts, plan.offsets, plan.sizes,
                                   steps)
    return colored_sweep_chunk(
        plan.store.kernel_operand, state,
        rng.stream(base, rng.Salt.SWEEP, c), clen, temps, sched,
        window=plan.window, pwl_table=solver_pwl_table(config),
        block_r=block_r, coupling=plan.store.fmt,
        with_rows_fetched=with_rows_fetched, interpret=interpret)


@partial(jax.jit, static_argnames=("config", "chunk_steps", "block_r",
                                   "interpret"))
def _colored_anneal_run(plan: ColoredPlan, seed: jax.Array,
                        config: SolverConfig, chunk_steps: int, block_r: int,
                        interpret: bool) -> SolveResult:
    problem = plan.problem
    r = config.num_replicas
    base = jax.random.fold_in(jax.random.key(0), seed)
    init = fused_init_state(problem, base, r, interpret=interpret,
                            block_r=block_r, planes=plan.store.planes)
    chunk_len, num_chunks, rem_steps = anneal_chunk_plan(config, chunk_steps)

    def chunk(carry, c, clen):
        state, rows = carry
        state, rf = colored_chunk_step(plan, state, base, c, clen=clen,
                                       chunk_len=chunk_len, config=config,
                                       block_r=block_r, interpret=interpret,
                                       with_rows_fetched=True)
        return (state, rows + rf), state[3]

    init = (init, jnp.zeros((r,), jnp.int32))
    ((u, s, e, be, bs, nf), rows), trace = jax.lax.scan(
        partial(chunk, clen=chunk_len), init, jnp.arange(num_chunks))
    if rem_steps:
        ((u, s, e, be, bs, nf), rows), _ = chunk(
            ((u, s, e, be, bs, nf), rows), jnp.int32(num_chunks),
            clen=rem_steps)
    return SolveResult(
        best_energy=be + problem.offset,
        best_spins=bs.astype(jnp.int8),
        final_energy=e + problem.offset,
        num_flips=nf,
        trace_energy=((trace + problem.offset).astype(jnp.float32)
                      if config.trace_every else jnp.zeros((0, r), jnp.float32)),
        rows_fetched=rows,
    )


def unpermute_spins(plan: ColoredPlan, spins: jax.Array) -> jax.Array:
    """Map (..., N) permuted-order spins back to original vertex order
    (``s_orig[..., i] = s_perm[..., inverse_perm[i]]``)."""
    return spins[..., jnp.asarray(plan.coloring.inverse_perm)]


def colored_anneal(problem: ising.IsingProblem, seed, config: SolverConfig,
                   *, chunk_steps: int = 256, block_r: int = 8,
                   coupling: Optional[str] = None,
                   num_planes: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   plan: Optional[ColoredPlan] = None) -> SolveResult:
    """Graph-colored annealing driver (``SolverConfig(flip_mode="colored")``).

    Flips one conflict-graph color class per step — every class member takes
    an independent heat-bath flip off the live local fields, exact block
    Gibbs because same-color spins share no coupling — so sparse instances
    do O(N/χ) flips per kernel step instead of 1 (ROADMAP item 3, DESIGN.md
    §Graph-colored parallel flips). The selection-mode knobs
    (``config.mode``/``uniformized``) do not enter colored semantics; PWL vs
    exact flip probability, the schedule, trace cadence, ``num_flips`` and
    ``rows_fetched`` telemetry all behave as in :func:`fused_anneal`.

    ``plan`` takes a prebuilt :func:`colored_plan` so repeated solves of one
    instance (TTS sweeps, benchmarks) skip the coloring + permutation +
    store encode; ``coupling`` overrides ``config.coupling_format`` when no
    plan is passed. Results are reported in the original vertex order — the
    color-sorted permutation is internal.
    """
    if config.flip_mode != "colored":
        raise ValueError(
            f"colored_anneal serves flip_mode='colored' configs, got "
            f"{config.flip_mode!r} — use fused_anneal / solve()")
    if plan is None:
        plan = colored_plan(
            problem, coupling if coupling is not None
            else config.coupling_format, num_planes=num_planes)
    elif coupling is not None:
        raise ValueError("pass a prebuilt plan= or a coupling= override, "
                         "not both")
    result = _colored_anneal_run(plan, jnp.asarray(seed, jnp.uint32), config,
                                 chunk_steps, block_r,
                                 auto_interpret(interpret))
    return result._replace(best_spins=unpermute_spins(plan,
                                                      result.best_spins))


def fused_anneal(problem: ising.IsingProblem, seed, config: SolverConfig,
                 *, chunk_steps: int = 256, block_r: int = 8,
                 gather: str = "dynamic",
                 coupling: Union[str, BitPlanes, None] = None,
                 num_planes: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 store: Optional[CouplingStore] = None) -> SolveResult:
    """Production annealing driver on the fused sweep kernel.

    Full ``core.solver.solve`` feature parity — both modes, uniformized RWA,
    PWL LUT vs exact flip probability, ``num_flips``, and reference-identical
    trace shape/dtype/cadence — up to RNG stream layout (the fused path draws
    its chunk uniforms from the dedicated ``Salt.SWEEP`` stream). ``gather``
    is "dynamic" (O(N)/step), "onehot" (O(N²)/step MXU contraction), or
    "auto" (onehot only for N ≤ ONEHOT_GATHER_MAX_N, i.e. 128).

    ``coupling`` overrides ``config.coupling_format`` ("auto" picks the
    packed bit-plane store when J is integral, N is past the f32 VMEM
    crossover, and packing actually shrinks J — escalating to the
    HBM-streamed store past the packed-VMEM wall); the
    ``CouplingStore.build`` packing happens here, on the host, so the jitted
    impl only ever sees ready arrays. Callers that already hold packed
    planes (benchmarks, repeated solves of one instance) pass the
    ``BitPlanes`` itself as ``coupling`` to skip the O(N²·B) re-encode —
    the store tier then follows ``config.coupling_format`` when it names a
    single-device plane format, else the VMEM-resident "bitplane" path.
    ``num_planes`` forces the precision B (default: fewest planes covering
    |J|max). The "bitplane_sharded" tier is rejected here — it is served by
    the spin-parallel ``repro.distributed.solver_sharded.solve_sharded``.

    ``store`` takes a prebuilt ``CouplingStore`` and skips the resolve→encode
    entirely (the memoization contract for repeated solves — TTS sweeps,
    tempering restarts — of one instance); it is mutually exclusive with
    ``coupling``, and its tier wins over ``config.coupling_format`` (the
    store *is* the resolved format). It must have been built from this
    problem's couplings: a dense store is identity-checked against
    ``problem.couplings`` (the init derives u₀/e₀ from the problem while
    the sweep consumes the store — feeding a different same-N matrix would
    silently corrupt trajectories); a plane store cannot be re-verified
    without re-encoding, so that half of the contract is the caller's.
    With an edge-list problem and no prebuilt store the build runs the
    O(nnz) sparse encoder — the dense (N, N) matrix is never materialized
    anywhere on this path.
    """
    if config.flip_mode != "single":
        raise ValueError(
            f"fused_anneal runs single-flip sweeps (flip_mode="
            f"{config.flip_mode!r}); colored block updates are served by "
            "colored_anneal / the 'colored' backend")
    if store is not None:
        if coupling is not None:
            raise ValueError("pass a prebuilt store= or a coupling= override, "
                             "not both")
        store.require_num_spins(problem.num_spins, "fused_anneal")
        if store.dense is not None and store.dense is not problem.couplings:
            raise ValueError(
                "prebuilt dense CouplingStore does not hold this problem's "
                "couplings array — the init would run on one J and the sweep "
                "on another; rebuild the store from problem.couplings")
    elif isinstance(coupling, BitPlanes):
        # Any plane format on the config flows into the store so require()
        # below can reject tiers this driver does not serve (a
        # "bitplane_sharded" config must raise the routing error here too,
        # never silently downgrade to the VMEM tier).
        fmt = (config.coupling_format
               if config.coupling_format in PLANE_FORMATS else "bitplane")
        store = CouplingStore.from_planes(coupling, fmt)
    else:
        store = CouplingStore.build(
            problem.coupling_source,
            coupling if coupling is not None else config.coupling_format,
            num_planes=num_planes)
    store.require(KERNEL_COUPLING_MODES, "fused_anneal")
    return _fused_anneal_impl(problem, jnp.asarray(seed, jnp.uint32), config,
                              chunk_steps, block_r, gather,
                              auto_interpret(interpret), store)
