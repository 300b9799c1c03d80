"""Distributed Snowball: replica ensembles sharded over the mesh via shard_map.

Mapping (DESIGN.md §2): replicas (independent Markov chains = the TTS
Bernoulli trials) shard over the flattened data axes (`pod` × `data`); the
coupling matrix J is replicated (or bit-plane packed — 16× smaller — for very
large N). Every ``exchange_every`` chunks, the globally best configuration is
broadcast and the *worst* replicas restart from it with fresh noise — an
elitist restart in the spirit of the paper's ensemble methodology (and unlike
parallel tempering, it needs no temperature ladder; paper §IV-A discusses why
PT is avoided).

Fault-tolerance posture: replicas are independent — losing a host removes its
replicas but never invalidates the ensemble; TTS statistics just lose trials.
Elastic rescale = re-seeding replica ids (stateless RNG streams).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import ising, rng
from ..core.bitplane import local_fields_from_planes
from ..core.coupling import KERNEL_COUPLING_MODES, CouplingStore
from ..core.solver import SolveResult, SolverConfig, _mcmc_config
from ..core import mcmc
from .shmap import shard_map_compat


@dataclasses.dataclass(frozen=True)
class DistSolverConfig:
    base: SolverConfig
    replicas_per_device: int = 1
    exchange_every: int = 0      # chunks between best-exchange; 0 = never
    restart_fraction: float = 0.25  # worst fraction restarted at exchange
    backend: str = "reference"   # "reference" | "fused" per-chunk engine


def _init_chain_from_planes(planes, fields_h, spins) -> mcmc.ChainState:
    """``mcmc.init_chain`` off the packed planes — no dense J required.

    Trajectory-exact vs the dense init for integer J: the Hamming-weight
    u^(J) equals the f32 matmul exactly (integer sums below 2²⁴), and the
    energy is assembled by ``ising.energy_from_fields`` — the *same einsum
    contractions* as ``ising.energy`` on those identical u^(J) values — so
    dense-fed and plane-fed shards produce bit-identical chains (asserted by
    ``test_distributed_fused_bitplane_matches_dense``)."""
    u_j = local_fields_from_planes(planes, spins)      # == J @ s exactly
    e = ising.energy_from_fields(u_j, spins, fields_h).astype(jnp.float32)
    return mcmc.ChainState(
        spins=spins.astype(ising.SPIN_DTYPE),
        fields=(u_j + fields_h).astype(jnp.float32),
        energy=e,
        best_energy=e,
        best_spins=spins.astype(ising.SPIN_DTYPE),
        num_flips=jnp.int32(0),
    )


def _chunk_runner(problem, mc, schedule, chunk_steps):
    """Run `chunk_steps` MCMC steps on a block of replicas (vmapped chains)."""

    def run(states, replica_keys, chunk_idx):
        def one_step(states, t):
            temperature = schedule(t)
            step_keys = jax.vmap(lambda k: rng.stream(k, t))(replica_keys)
            new_states, _ = jax.vmap(
                lambda st, k: mcmc.step(problem, st, k, temperature, mc))(states, step_keys)
            return new_states

        t0 = chunk_idx * chunk_steps
        return jax.lax.fori_loop(t0, t0 + chunk_steps,
                                 lambda t, st: one_step(st, t), states)

    return run


def _fused_chunk_runner(base_cfg: SolverConfig, chunk_steps: int, r_local: int,
                        interpret: bool, store: CouplingStore):
    """Run `chunk_steps` steps as one VMEM-resident fused sweep per shard.

    Replica chains stay in ``mcmc.ChainState`` so the elitist-exchange logic
    is backend-agnostic; the sweep kernel consumes/produces the state arrays
    directly. Per-device RNG: chunk uniforms come from the dedicated
    ``Salt.SWEEP`` stream folded with the device index, so shards draw
    disjoint streams by construction. ``store`` is the resolved
    ``CouplingStore`` (per ``base_cfg.coupling_format`` via
    ``solve_distributed``); the runner closes over its payload, replicated
    to every shard — in the HBM tier each shard streams rows from its own
    HBM-resident plane copy.
    """
    from ..kernels import ops as _ops

    tbl = _ops.solver_pwl_table(base_cfg)

    def run(states, base, device_idx, chunk_idx, dense_J=None):
        # Plane stores close over the encoded payload (replicated constant);
        # the dense store consumes the caller's per-shard J operand so the
        # matrix enters the shard exactly once either way.
        couplings = dense_J if dense_J is not None else store.kernel_operand
        steps = chunk_idx * chunk_steps + jnp.arange(chunk_steps)
        temps = jax.vmap(base_cfg.schedule)(steps).astype(jnp.float32)
        temps = jnp.broadcast_to(temps[:, None], (chunk_steps, r_local))
        state = (states.fields, states.spins.astype(jnp.float32),
                 states.energy, states.best_energy,
                 states.best_spins.astype(jnp.float32), states.num_flips)
        u, s, e, be, bs, nf = _ops.fused_sweep_chunk(
            couplings, state,
            rng.stream(base, rng.Salt.SWEEP, device_idx, chunk_idx),
            chunk_steps, temps, mode=base_cfg.mode,
            uniformized=base_cfg.uniformized, pwl_table=tbl,
            coupling=store.fmt, interpret=interpret)
        return mcmc.ChainState(
            spins=s.astype(ising.SPIN_DTYPE),
            fields=u,
            energy=e,
            best_energy=be,
            best_spins=bs.astype(ising.SPIN_DTYPE),
            num_flips=nf,
        )

    return run


class _DistSetup(NamedTuple):
    """Host-level setup shared by ``solve_distributed`` and the resilient
    chunk surfaces: chunk cadence, resolved store, per-chunk runner, and
    whether the dense J must be shipped into shard_map as an operand."""
    axes: tuple
    num_devices: int
    r_local: int
    r_total: int
    chunk: int
    num_chunks: int
    store: "CouplingStore | None"
    runner: object
    ship_dense: bool


def _dist_setup(problem: ising.IsingProblem, config: DistSolverConfig,
                mesh: Mesh) -> _DistSetup:
    axes = tuple(mesh.axis_names)
    num_devices = 1
    for a in axes:
        num_devices *= mesh.shape[a]
    r_local = config.replicas_per_device
    base_cfg = config.base
    chunk = max(base_cfg.trace_every, 1) if base_cfg.trace_every else 64
    num_chunks = max(base_cfg.num_steps // chunk, 1)
    store = None
    if config.backend == "fused":
        from ..kernels.ops import auto_interpret
        store = CouplingStore.build(
            problem.coupling_source, base_cfg.coupling_format).require(
            KERNEL_COUPLING_MODES, "solve_distributed")
        runner = _fused_chunk_runner(base_cfg, chunk, r_local,
                                     auto_interpret(None), store)
    elif config.backend == "reference":
        if problem.couplings is None:
            raise ValueError(
                "backend='reference' needs the dense J; edge-list "
                "(dense-J-free) problems are served by backend='fused'")
        runner = _chunk_runner(problem, _mcmc_config(base_cfg),
                               base_cfg.schedule, chunk)
    else:
        raise ValueError(
            f"backend must be 'reference' or 'fused', got {config.backend!r}")
    # When the fused runner closes over encoded planes, the dense J never
    # enters shard_map at all — at N=16k that is a 1 GiB replicated operand
    # that the shard would otherwise receive only to ignore (chain (re)inits
    # run off the planes too, see ``_init_chain_from_planes``).
    ship_dense = store is None or store.planes is None
    return _DistSetup(axes=axes, num_devices=num_devices, r_local=r_local,
                      r_total=r_local * num_devices, chunk=chunk,
                      num_chunks=num_chunks, store=store, runner=runner,
                      ship_dense=ship_dense)


def _dist_chain_init(J, h, store):
    """The per-shard chain (re)init closure: dense J when shipped, else the
    plane-backed init off the replicated store."""
    if J is not None:
        prob = ising.IsingProblem(couplings=J, fields=h, offset=0.0)
        return lambda sp: mcmc.init_chain(prob, sp)
    return lambda sp: _init_chain_from_planes(store.planes, h, sp)


def _dist_ids(mesh: Mesh, axes, seed_arr, r_local: int):
    """Per-device RNG derivation inside shard_map: the flattened device index
    (axis sizes are static — read off the mesh, not the
    unavailable-in-old-JAX ``lax.axis_size``), the folded base key, and the
    per-replica ``Salt.REPLICA`` keys. Recomputable from (seed, mesh) alone —
    what lets a resumed run rebuild identical streams with no carried RNG
    state."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    base = jax.random.fold_in(jax.random.key(0), seed_arr[0])
    rep_ids = idx * r_local + jnp.arange(r_local)
    keys = jax.vmap(lambda i: rng.stream(base, rng.Salt.REPLICA, i))(rep_ids)
    return idx, base, keys


def _dist_local_init(h, seed_arr, J, *, store, mesh, axes, r_local, n):
    """Per-device replica init (inside shard_map): chains, keys, ids."""
    idx, base, keys = _dist_ids(mesh, axes, seed_arr, r_local)
    chain_init = _dist_chain_init(J, h, store)
    spins0 = jax.vmap(lambda k: ising.random_spins(
        rng.stream(k, rng.Salt.INIT), (n,)))(keys)
    states = jax.vmap(chain_init)(spins0)
    return states, keys, base, idx, chain_init


def _elitist_exchange(states: mcmc.ChainState, chain_init, *, axes, n: int,
                      r_local: int, restart_fraction: float) -> mcmc.ChainState:
    """Cross-device elitist restart: broadcast the globally best configuration
    (psum-of-onehot winner-take-all) and restart the worst local replicas
    from it. Factored to module level so ``solve_distributed``'s scan and the
    resilient per-chunk surface run the identical exchange arithmetic."""
    # Global best config across ALL devices (psum-of-onehot trick).
    local_best = jnp.min(states.best_energy)
    global_best = local_best
    for a in axes:
        global_best = jax.lax.pmin(global_best, a)
    is_best = (states.best_energy == global_best)
    # Winner-take-all broadcast of the best spins.
    local_vote = jnp.where(jnp.any(is_best),
                           states.best_spins[jnp.argmax(is_best)],
                           jnp.zeros((n,), states.best_spins.dtype))
    count = jnp.any(is_best).astype(jnp.int32)
    total_vote = local_vote.astype(jnp.int32)
    total_count = count
    for a in axes:
        total_vote = jax.lax.psum(total_vote, a)
        total_count = jax.lax.psum(total_count, a)
    best_spins = jnp.sign(total_vote).astype(states.spins.dtype)
    # Ties can cancel the vote; fall back to local state then.
    usable = jnp.any(best_spins != 0) & (total_count > 0)
    # Restart the worst replicas from the broadcast best.
    order = jnp.argsort(states.energy)
    k_restart = max(int(r_local * restart_fraction), 1)
    worst = order[-k_restart:]

    def restart_one(states, j):
        spins = jnp.where(usable, best_spins, states.spins[j])
        st_j = chain_init(spins)
        improved = st_j.energy < states.best_energy[j]
        new_best_s = jnp.where(improved, st_j.spins,
                               states.best_spins[j])
        return mcmc.ChainState(
            spins=states.spins.at[j].set(st_j.spins),
            fields=states.fields.at[j].set(st_j.fields),
            energy=states.energy.at[j].set(st_j.energy),
            best_energy=states.best_energy.at[j].set(
                jnp.minimum(states.best_energy[j], st_j.energy)),
            best_spins=states.best_spins.at[j].set(new_best_s),
            num_flips=states.num_flips,
        )

    return jax.lax.fori_loop(
        0, k_restart, lambda i, st: restart_one(st, worst[i]), states)


def _dist_chunk(states: mcmc.ChainState, c, *, config: DistSolverConfig, J,
                runner, keys, base, idx, chain_init, axes, n: int,
                r_local: int) -> mcmc.ChainState:
    """One distributed chunk (inside shard_map): advance ``chunk`` steps via
    the backend runner, then the conditional elitist exchange — the single
    chunk body under ``solve_distributed``'s scan and the resilient
    supervisor's per-chunk jit."""
    if config.backend == "fused":
        states = runner(states, base, idx, c, dense_J=J)
    else:
        states = runner(states, keys, c)
    if config.exchange_every:
        states = jax.lax.cond(
            (c + 1) % config.exchange_every == 0,
            lambda s: _elitist_exchange(
                s, chain_init, axes=axes, n=n, r_local=r_local,
                restart_fraction=config.restart_fraction),
            lambda s: s, states)
    return states


def dist_operands(problem: ising.IsingProblem, seed, setup: _DistSetup):
    """The replicated shard_map operands for a (problem, seed):
    ``[h, seed_arr(, dense J)]`` — shared between the monolithic solve and
    the resilient chunk surfaces so both ship the identical inputs."""
    seed_arr = jnp.asarray([seed], jnp.uint32)
    operands = [problem.fields, seed_arr]
    if setup.ship_dense:
        operands.append(problem.couplings)
    return operands


def solve_distributed(problem: ising.IsingProblem, seed, config: DistSolverConfig,
                      mesh: Mesh) -> SolveResult:
    """shard_map annealing over every mesh axis (replica-parallel)."""
    setup = _dist_setup(problem, config, mesh)
    axes = setup.axes
    n = problem.num_spins
    r_local = setup.r_local

    def local_solve(h, seed_arr, *dense_args):
        J = dense_args[0] if dense_args else None
        states, keys, base, idx, chain_init = _dist_local_init(
            h, seed_arr, J, store=setup.store, mesh=mesh, axes=axes,
            r_local=r_local, n=n)

        def chunk_body(carry, c):
            states = _dist_chunk(carry, c, config=config, J=J,
                                 runner=setup.runner, keys=keys, base=base,
                                 idx=idx, chain_init=chain_init, axes=axes,
                                 n=n, r_local=r_local)
            return states, states.best_energy  # (r_local,) per chunk

        states, trace = jax.lax.scan(chunk_body, states,
                                     jnp.arange(setup.num_chunks))
        return (states.best_energy, states.best_spins, states.energy,
                states.num_flips, trace)

    spec_rep = P()  # replicated inputs
    out_specs = (P(axes), P(axes), P(axes), P(axes), P(None, axes))
    operands = dist_operands(problem, seed, setup)
    fn = jax.jit(shard_map_compat(
        local_solve, mesh=mesh,
        in_specs=(spec_rep,) * len(operands),
        out_specs=out_specs))
    be, bs, fe, nf, trace = fn(*operands)
    return SolveResult(best_energy=be + problem.offset, best_spins=bs,
                       final_energy=fe + problem.offset, num_flips=nf,
                       trace_energy=trace + problem.offset)


def dist_resilient_fns(problem: ising.IsingProblem, config: DistSolverConfig,
                       mesh: Mesh):
    """Chunk-granular surfaces of the replica-sharded driver for the
    resilient supervisor (``core.resilience``): ``(init_fn, chunk_fn,
    setup)``.

    ``init_fn(*operands) → state6`` and ``chunk_fn(*state6, *operands,
    c_arr) → state6`` are jitted shard_maps whose composition over
    ``c = 0 .. setup.num_chunks-1`` replays ``solve_distributed``'s scan bit
    for bit — same per-device RNG derivation (:func:`_dist_ids`), same chunk
    cadence, same elitist exchange (:func:`_dist_chunk`). ``state6`` is the
    ``ChainState`` leaf tuple ``(spins, fields, energy, best_energy,
    best_spins, num_flips)`` as *global* arrays sharded on the leading
    replica axis; ``operands`` comes from :func:`dist_operands`; ``c_arr``
    is the chunk index as a replicated (1,) int32 (dynamic, so every chunk
    reuses one compiled program)."""
    setup = _dist_setup(problem, config, mesh)
    axes = setup.axes
    n = problem.num_spins
    r_local = setup.r_local
    n_ops = 3 if setup.ship_dense else 2
    rep = P()
    state_specs = (P(axes),) * 6

    def local_init(h, seed_arr, *dense):
        J = dense[0] if dense else None
        states, _, _, _, _ = _dist_local_init(
            h, seed_arr, J, store=setup.store, mesh=mesh, axes=axes,
            r_local=r_local, n=n)
        return tuple(states)

    def local_chunk(sp, fu, en, be, bs, nf, h, seed_arr, c_arr, *dense):
        J = dense[0] if dense else None
        idx, base, keys = _dist_ids(mesh, axes, seed_arr, r_local)
        chain_init = _dist_chain_init(J, h, setup.store)
        states = mcmc.ChainState(spins=sp, fields=fu, energy=en,
                                 best_energy=be, best_spins=bs, num_flips=nf)
        states = _dist_chunk(states, c_arr[0], config=config, J=J,
                             runner=setup.runner, keys=keys, base=base,
                             idx=idx, chain_init=chain_init, axes=axes, n=n,
                             r_local=r_local)
        return tuple(states)

    init_fn = jax.jit(shard_map_compat(
        local_init, mesh=mesh,
        in_specs=(rep,) * n_ops,
        out_specs=state_specs))
    chunk_fn = jax.jit(shard_map_compat(
        local_chunk, mesh=mesh,
        in_specs=state_specs + (rep, rep, rep) + (rep,) * (n_ops - 2),
        out_specs=state_specs))
    return init_fn, chunk_fn, setup
