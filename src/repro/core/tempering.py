"""Parallel tempering (replica-exchange MCMC) — the annealing alternative the
paper discusses and deliberately avoids (§IV-A, [19], [34], [40]).

Implemented as a baseline so the paper's design choice is measurable: R
replicas at a geometric temperature ladder run the same dual-mode kernels;
every ``swap_every`` steps adjacent-temperature pairs exchange configurations
with the Metropolis swap probability

    P_swap = min(1, exp((1/T_i − 1/T_j)(E_i − E_j))).

The paper's argument — that maintaining swap acceptance needs many closely
spaced replicas as the system grows — shows up directly in the benchmark's
measured swap-acceptance column.

Two backends share the swap machinery: ``backend="reference"`` runs the
one-flip-per-XLA-op ``core.mcmc`` chains; ``backend="fused"`` runs each
between-swap phase as one VMEM-resident Pallas sweep with the ladder passed
as the kernel's per-replica ``(T, R)`` temperature tensor — swap phases land
exactly at sweep-chunk boundaries.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ising, mcmc, rng
from .pwl import make_flip_probability, make_pwl_sigmoid, pwl_table


@dataclasses.dataclass(frozen=True)
class TemperingConfig:
    num_steps: int
    t_min: float
    t_max: float
    num_replicas: int = 8        # temperature-ladder rungs
    swap_every: int = 10
    mode: str = "rsa"            # kernel for within-chain moves
    use_pwl: bool = True
    backend: str = "reference"   # "reference" | "fused"
    coupling_format: str = "auto"  # fused-backend J store; COUPLING_FORMATS
    #: Tempering moves are single-spin by construction (the swap-acceptance
    #: argument of §IV-A is about one-flip chains); the field exists so the
    #: knob is uniform across configs and "colored" is rejected loudly here
    #: instead of silently running single-flip chains.
    flip_mode: str = "single"    # "single" only

    @property
    def ladder(self) -> np.ndarray:
        return np.geomspace(self.t_max, self.t_min, self.num_replicas)


class TemperingResult(NamedTuple):
    best_energy: jax.Array       # (R,)
    best_spins: jax.Array        # (R, N)
    final_energy: jax.Array
    swap_acceptance: jax.Array   # () mean accepted swap fraction
    num_flips: jax.Array


def _swap_phase(state, energy_of: Callable, temps: jax.Array, base: jax.Array,
                round_idx: jax.Array, r: int):
    """Metropolis exchange of adjacent rungs (even pairs then odd pairs).

    ``state`` is any pytree whose leaves have a leading replica axis;
    ``energy_of(state)`` extracts the (R,) current energies. Shared by both
    backends so swap decisions consume identical RNG streams.
    """

    def try_pairs(state, parity, salt):
        e = energy_of(state)
        beta = 1.0 / temps
        # pair (i, i+1) for i ≡ parity (mod 2)
        idx = jnp.arange(r - 1)
        active = (idx % 2) == parity
        delta = (beta[idx] - beta[idx + 1]) * (e[idx] - e[idx + 1])
        key = rng.stream(base, rng.Salt.UNIFORMIZE, round_idx, salt)
        u = rng.uniform01(key, (r - 1,))
        accept = active & (u < jnp.minimum(jnp.exp(jnp.clip(delta, -80.0, 80.0)), 1.0))

        # Build a permutation that swaps accepted pairs.
        perm = jnp.arange(r)
        lo = idx
        hi = idx + 1
        perm = perm.at[lo].set(jnp.where(accept, hi, perm[lo]))
        perm = perm.at[hi].set(jnp.where(accept, lo, perm[hi]))
        swapped = jax.tree.map(lambda x: x[perm], state)
        return swapped, accept.sum(), active.sum()

    state, acc_e, n_e = try_pairs(state, 0, 0)
    state, acc_o, n_o = try_pairs(state, 1, 1)
    return state, (acc_e + acc_o, n_e + n_o)


def _solve_tempering_reference(problem: ising.IsingProblem, seed,
                               config: TemperingConfig) -> TemperingResult:
    n = problem.num_spins
    r = config.num_replicas
    temps = jnp.asarray(config.ladder, jnp.float32)
    fp = (make_flip_probability(make_pwl_sigmoid()) if config.use_pwl
          else make_flip_probability(None))
    mc = mcmc.MCMCConfig(mode=config.mode, flip_prob=fp)
    base = jax.random.fold_in(jax.random.key(0), jnp.asarray(seed, jnp.uint32))
    keys = jax.vmap(lambda i: rng.stream(base, rng.Salt.REPLICA, i))(jnp.arange(r))
    spins0 = jax.vmap(lambda k: ising.random_spins(rng.stream(k, rng.Salt.INIT), (n,)))(keys)
    states = jax.vmap(lambda s: mcmc.init_chain(problem, s))(spins0)

    def chain_steps(states, t0):
        def one(t, st):
            sk = jax.vmap(lambda k: rng.stream(k, t))(keys)
            new, _ = jax.vmap(lambda s, k, temp: mcmc.step(problem, s, k, temp, mc))(
                st, sk, temps)
            return new
        return jax.lax.fori_loop(t0, t0 + config.swap_every, one, states)

    num_rounds = max(config.num_steps // config.swap_every, 1)

    def round_body(carry, round_idx):
        states, acc, tot = carry
        states = chain_steps(states, round_idx * config.swap_every)
        states, (a, t) = _swap_phase(states, lambda st: st.energy, temps,
                                     base, round_idx, r)
        return (states, acc + a, tot + t), None

    (states, acc, tot), _ = jax.lax.scan(
        round_body, (states, jnp.int32(0), jnp.int32(0)), jnp.arange(num_rounds))
    return TemperingResult(
        best_energy=states.best_energy + problem.offset,
        best_spins=states.best_spins,
        final_energy=states.energy + problem.offset,
        swap_acceptance=acc.astype(jnp.float32) / jnp.maximum(tot, 1),
        num_flips=states.num_flips,
    )


def tempering_round_count(config: TemperingConfig) -> int:
    """Swap rounds per run — the chunk-unit count of the fused tempering
    trajectory (each round = one ``swap_every``-step sweep + swap phase)."""
    return max(config.num_steps // config.swap_every, 1)


def fused_tempering_round(state, acc, tot, base: jax.Array, round_idx,
                          config: TemperingConfig, store, *, interpret: bool):
    """One tempering round on the fused kernel: a ``swap_every``-step sweep
    chunk on the round's ``Salt.SWEEP`` stream, then the Metropolis swap
    phase. The single round body under ``_solve_tempering_fused``'s scan AND
    the resilient supervisor's per-round jit (``core.resilience``) — one
    definition keeps a resumed tempering trajectory bit-identical to the
    uninterrupted scan. ``state`` is the fused 6-tuple; ``acc``/``tot`` the
    running swap-acceptance counters."""
    from ..kernels import ops as _ops  # lazy: kernels.ops imports core.solver

    r = config.num_replicas
    temps = jnp.asarray(config.ladder, jnp.float32)
    tbl = pwl_table() if config.use_pwl else None
    temps_trs = jnp.broadcast_to(temps[None, :], (config.swap_every, r))
    state = _ops.fused_sweep_chunk(
        store.kernel_operand, state, rng.stream(base, rng.Salt.SWEEP, round_idx),
        config.swap_every, temps_trs, mode=config.mode, pwl_table=tbl,
        coupling=store.fmt, interpret=interpret)
    state, (a, t) = _swap_phase(state, lambda st: st[2], temps,
                                base, round_idx, r)
    return state, acc + a, tot + t


def _solve_tempering_fused(problem: ising.IsingProblem, seed,
                           config: TemperingConfig,
                           store) -> TemperingResult:
    """Fused backend: each between-swap phase is one VMEM-resident sweep with
    the temperature ladder as the kernel's per-replica ``(T, R)`` tensor.
    ``store`` is the resolved ``core.coupling.CouplingStore`` (dense J or
    packed planes; its format rides the pytree aux data, so it is static
    here) produced by the host-level dispatcher."""
    from ..kernels import ops as _ops  # lazy: kernels.ops imports core.solver

    r = config.num_replicas
    interpret = _ops.auto_interpret(None)
    base = jax.random.fold_in(jax.random.key(0), jnp.asarray(seed, jnp.uint32))
    init_state = _ops.fused_init_state(problem, base, r, interpret=interpret,
                                       planes=store.planes)
    num_rounds = tempering_round_count(config)

    def round_body(carry, round_idx):
        state, acc, tot = carry
        state, acc, tot = fused_tempering_round(
            state, acc, tot, base, round_idx, config, store,
            interpret=interpret)
        return (state, acc, tot), None

    init = (init_state, jnp.int32(0), jnp.int32(0))
    ((u, s, e, be, bs, nf), acc, tot), _ = jax.lax.scan(
        round_body, init, jnp.arange(num_rounds))
    return TemperingResult(
        best_energy=be + problem.offset,
        best_spins=bs.astype(ising.SPIN_DTYPE),
        final_energy=e + problem.offset,
        swap_acceptance=acc.astype(jnp.float32) / jnp.maximum(tot, 1),
        num_flips=nf,
    )


_solve_tempering_reference_jit = partial(
    jax.jit, static_argnames=("config",))(_solve_tempering_reference)
_solve_tempering_fused_jit = partial(
    jax.jit, static_argnames=("config",))(_solve_tempering_fused)


def solve_tempering(problem: ising.IsingProblem, seed,
                    config: TemperingConfig, *, store=None) -> TemperingResult:
    """Host-level dispatcher (the engines underneath are jitted): the fused
    path resolves ``config.coupling_format`` into a ``CouplingStore`` (one
    ``build`` call packs bit-planes from the concrete J — or from the edge
    list via the O(nnz) sparse encoder for dense-J-free problems) before
    entering jit.

    ``store`` takes a prebuilt ``CouplingStore`` so tempering restarts /
    repeated ladder sweeps of one instance skip the re-resolve→re-encode
    (fused backend only — the reference chains consume the dense J).
    """
    if config.flip_mode != "single":
        raise ValueError(
            f"tempering runs single-flip chains only (flip_mode="
            f"{config.flip_mode!r}); colored block updates are served by "
            "solve(..., backend='colored') on a SolverConfig")
    if config.backend == "fused":
        from .coupling import KERNEL_COUPLING_MODES, CouplingStore
        if store is None:
            store = CouplingStore.build(
                problem.coupling_source, config.coupling_format)
        else:
            store.require_num_spins(problem.num_spins, "solve_tempering")
            if (store.dense is not None
                    and store.dense is not problem.couplings):
                raise ValueError(
                    "prebuilt dense CouplingStore does not hold this "
                    "problem's couplings array — the init would run on one J "
                    "and the sweep on another; rebuild the store from "
                    "problem.couplings")
        store.require(KERNEL_COUPLING_MODES, "solve_tempering")
        return _solve_tempering_fused_jit(problem, seed, config, store)
    if store is not None:
        raise ValueError("a prebuilt CouplingStore serves the fused backend "
                         "only; backend='reference' always consumes the "
                         "dense J")
    if config.backend != "reference":
        raise ValueError(
            f"backend must be 'reference' or 'fused', got {config.backend!r}")
    if problem.couplings is None:
        raise ValueError(
            "backend='reference' tempering needs the dense J; edge-list "
            "(dense-J-free) problems are served by the fused backend")
    return _solve_tempering_reference_jit(problem, seed, config)
