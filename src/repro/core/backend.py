"""The execution-path registry: every solve driver behind one interface.

The repo grew five ways to advance an Ising trajectory — the reference
oracle scan (``core.solver``), the fused Pallas sweep over the coupling
tiers (``kernels.ops``), fused parallel tempering (``core.tempering``), the
replica-parallel distributed driver (``distributed.solver_dist``), and the
spin-sharded driver (``distributed.solver_sharded``). Each used to hand-roll
config resolution, store plumbing, and chunk cadence, and joining the
resilience / parity contracts meant editing four files. This module is the
single enumeration point instead:

* :class:`Backend` — the uniform interface. ``prepare`` resolves the
  coupling tier and builds (or passes through) the stored operands,
  ``run`` is the monolithic jitted driver, ``runner`` yields the
  chunk-granular driver the resilient supervisor and the serving layer
  consume (``init`` / ``run_chunk`` / ``finalize`` — the same chunk bodies
  the monolithic scans use, so chunked execution is bit-identical).
* :class:`Capabilities` — what each path can serve (edge-list problems,
  mesh requirement, prebuilt-store reuse, resume support, tier-fallback
  eligibility), replacing per-driver special cases in callers.
* :data:`BACKENDS` + :func:`register` — the registry.
  ``core.resilience.run_resilient``, the public ``solve`` entry point, the
  ``serve.SolverService`` front end, and the registry-completeness test
  (``tests/test_backend_registry.py``) all enumerate it, so a new
  execution path joins every contract by registering here — not by editing
  the supervisor, the dispatchers, and the test matrices separately.

Chunk-runner protocol (duck-typed; what ``runner()`` returns):
``init() -> state``, ``run_chunk(state, k) -> state``, ``unit_len(k)``,
``best_energy(state) -> float``, ``trace_row(state)``,
``finalize(state, rows) -> result``, plus attributes ``total_units``,
``collect_trace``, ``num_replicas``, ``backend``, ``fmt``. The state is a
pytree of device arrays that round-trips through a checkpoint losslessly,
and every chunk's RNG is a pure function of ``(seed, chunk index)`` — no
carried RNG state, which is what makes resume bit-identical.
"""
from __future__ import annotations

import abc
import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ising, rng
from .coupling import (KERNEL_COUPLING_MODES, CouplingStore, resolve_format)
from .solver import (SolveResult, SolverConfig, _mcmc_config,
                     reference_init_state, run_reference_chunk)
from .tempering import (TemperingConfig, TemperingResult,
                        fused_tempering_round, tempering_round_count)


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What an execution path can serve — the registry's contract surface.

    ``edge_list``     dense-J-free (``EdgeList``) problems supported.
    ``needs_mesh``    requires a device mesh (sharded / distributed).
    ``supports_store``  accepts a prebuilt ``CouplingStore`` (the
                      zero-re-encode memoization contract).
    ``supports_resume`` drivable chunk-by-chunk with bit-identical resume —
                      membership in the resume-parity matrix is asserted
                      for every backend with this bit set.
    ``tier_fallback`` participates in the coupling-tier downgrade ladder
                      (``coupling_format="auto"`` only).
    ``fixed_fmt``     the single coupling tier the path serves, or None
                      when the tier follows ``config.coupling_format``.
    ``auto``          eligible for ``backend="auto"`` config-type dispatch
                      (the reference oracle is explicit-only).
    """
    edge_list: bool
    needs_mesh: bool
    supports_store: bool
    supports_resume: bool
    tier_fallback: bool
    fixed_fmt: Optional[str] = None
    auto: bool = True
    summary: str = ""


class Backend(abc.ABC):
    """One registered execution path. Stateless; all methods take the
    problem/config explicitly so a single instance serves every request."""

    name: str
    capabilities: Capabilities

    @abc.abstractmethod
    def config_cls(self) -> type:
        """The config dataclass this path consumes (lazy import — the
        distributed config lives outside ``core``)."""

    def check_config(self, config) -> None:
        cls = self.config_cls()
        if not isinstance(config, cls):
            raise TypeError(
                f"backend {self.name!r} consumes {cls.__name__}, got "
                f"{type(config).__name__}")

    def matches_config(self, config) -> bool:
        """Whether ``backend="auto"`` may resolve to this path for
        ``config``. Default: the config-type check alone; paths that split
        one config class across execution modes (``SolverConfig.flip_mode``
        routes "single" to fused/sharded and "colored" to the colored
        backend) refine this so resolution is unambiguous."""
        return isinstance(config, self.config_cls())

    def prepare(self, problem: ising.IsingProblem, config, *, mesh=None,
                fmt: Optional[str] = None, store=None):
        """Resolve the coupling tier and build the stored operands for this
        path (a ``CouplingStore``, sharded planes, …) — the cacheable,
        host-side part of a solve. ``fmt`` is a tier override (the fallback
        ladder); a prebuilt ``store`` passes straight through when no
        override is in play. Returns None for paths with no separable
        store (reference consumes the dense J as-is; the distributed store
        is per-device by construction)."""
        return None

    @abc.abstractmethod
    def run(self, problem: ising.IsingProblem, seed, config, *, mesh=None,
            store=None):
        """The monolithic jitted driver — one launch for the whole
        trajectory (the fast path; `runner` is the resumable one)."""

    @abc.abstractmethod
    def runner(self, problem: ising.IsingProblem, seed, config, *,
               mesh=None, chunk_steps: int = 256, fmt: Optional[str] = None,
               store=None):
        """The chunk-granular driver (see the module docstring for the
        protocol) — bit-identical to ``run`` under any chunking."""


# --------------------------------------------------------------------------
# The registry.

BACKENDS: dict[str, Backend] = {}


def register(backend: Backend) -> Backend:
    """Add an execution path to the registry (latest registration wins —
    deliberate, so tests can shadow a backend). Registration is what joins
    the resilience, parity, and serving contracts."""
    BACKENDS[backend.name] = backend
    return backend


def backend_names() -> tuple:
    return tuple(sorted(BACKENDS))


def get_backend(name: str) -> Backend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}: registered backends are "
            f"{backend_names()}; 'auto' resolves one from the config type"
        ) from None


def resolve_backend(config, backend: str = "auto", mesh=None) -> str:
    """Registry-driven ``backend="auto"`` resolution: match the config type
    against each registered path's ``config_cls`` and prefer the
    mesh-matching candidate — ``TemperingConfig`` → tempering,
    ``DistSolverConfig`` → distributed, ``SolverConfig`` → sharded when a
    mesh is supplied, else fused. Explicit names are validated against the
    registry."""
    if backend != "auto":
        get_backend(backend)
        return backend
    cands = [b for name, b in sorted(BACKENDS.items())
             if b.capabilities.auto and b.matches_config(config)]
    if not cands:
        raise TypeError(f"unrecognized config type {type(config).__name__}")
    return min(cands, key=lambda b: b.capabilities.needs_mesh
               != (mesh is not None)).name


def current_fmt(problem: ising.IsingProblem, config, backend: str,
                fmt: Optional[str]) -> str:
    """The coupling tier a run attempt will use: the ladder override if one
    is active, the backend's fixed tier if it has one, else the resolved
    ``config.coupling_format``."""
    if fmt is not None:
        return fmt
    fixed = get_backend(backend).capabilities.fixed_fmt
    if fixed is not None:
        return fixed
    return resolve_format(getattr(config, "coupling_format", "auto"),
                          problem.coupling_source, problem.num_spins)


def fallback_enabled(config, backend: str) -> bool:
    """Whether the tier-downgrade ladder applies: the backend opts in via
    its capabilities AND the config left the tier on "auto"."""
    return (get_backend(backend).capabilities.tier_fallback
            and getattr(config, "coupling_format", None) == "auto")


def capability_rows() -> list:
    """(name, Capabilities) rows in name order — the DESIGN.md table and
    the registry-completeness test read the same source of truth."""
    return [(name, BACKENDS[name].capabilities) for name in backend_names()]


# --------------------------------------------------------------------------
# Per-backend chunk runners. Each runner drives the SAME chunk body the
# monolithic driver scans over, one host-visible unit at a time; the state it
# carries across units is a pytree of device arrays that round-trips through
# the checkpoint losslessly.

@partial(jax.jit, static_argnames=("config", "interpret"))
def _fused_init(problem, seed, config: SolverConfig, store: CouplingStore,
                interpret: bool):
    """The initial 6-tuple and a zero (R,) rows-fetched sum."""
    from ..kernels import ops as _ops
    base = jax.random.fold_in(jax.random.key(0), seed)
    r = config.num_replicas
    state = _ops.fused_init_state(problem, base, r, interpret=interpret,
                                  planes=store.planes)
    return state, jnp.zeros((r,), jnp.int32)


#: Replica block of the fused runner's sweep (``kernels.sweep.mcmc_sweep``).
FUSED_BLOCK_R = 8


@partial(jax.jit, static_argnames=("config", "clen", "chunk_len", "gather",
                                   "interpret"))
def _fused_chunk(state, rows_fetched, seed, c, store: CouplingStore, *,
                 config: SolverConfig, clen: int, chunk_len: int,
                 gather: str, interpret: bool):
    """One chunk; the running (R,) rows-fetched sum goes in and out, so the
    counter costs no dispatch of its own."""
    from ..kernels import ops as _ops
    base = jax.random.fold_in(jax.random.key(0), seed)
    state, rf = _ops.anneal_chunk_step(store, state, base, c, clen=clen,
                                       chunk_len=chunk_len, config=config,
                                       gather=gather,
                                       block_r=FUSED_BLOCK_R,
                                       interpret=interpret,
                                       with_rows_fetched=True)
    return state, rows_fetched + rf


class FusedRunner:
    """``solve(backend="fused")`` / ``fused_anneal``, chunk at a time."""

    backend = "fused"

    def __init__(self, problem, seed, config: SolverConfig,
                 store: CouplingStore, chunk_steps: int):
        from ..kernels import ops as _ops
        self.problem = problem
        self.config = config
        self.store = store
        self.fmt = store.fmt
        self.seed = jnp.asarray(seed, jnp.uint32)
        self.interpret = _ops.auto_interpret(None)
        self.gather = _ops.anneal_gather(store, "dynamic", problem.num_spins)
        self.chunk_len, self.num_chunks, self.rem_steps = (
            _ops.anneal_chunk_plan(config, chunk_steps))
        self.total_units = self.num_chunks + (1 if self.rem_steps else 0)
        self.collect_trace = bool(config.trace_every)
        self.num_replicas = config.num_replicas
        self._rows_fetched = None

    def unit_len(self, k: int) -> int:
        if self.rem_steps and k == self.num_chunks:
            return self.rem_steps
        return self.chunk_len

    def init(self):
        state, self._rows_fetched = _fused_init(
            self.problem, self.seed, self.config, self.store, self.interpret)
        return state

    def run_chunk(self, state, k: int):
        # As in ColoredRunner, the rows-fetched sum rides on the runner: the
        # 6-tuple snapshot contract stays fixed and the counter covers the
        # chunks this process ran since its last init (telemetry only).
        rf = self._rows_fetched
        if rf is None:      # continuing a state this runner did not init
            rf = jnp.zeros((self.num_replicas,), jnp.int32)
        state, self._rows_fetched = _fused_chunk(
            state, rf, self.seed, jnp.int32(k), self.store,
            config=self.config, clen=self.unit_len(k),
            chunk_len=self.chunk_len, gather=self.gather,
            interpret=self.interpret)
        return state

    def vmem_bytes(self) -> int:
        """The scoped-VMEM request of a full-length chunk's ``mcmc_sweep``
        (``kernels.sweep.sweep_vmem_limit``), at ``_fused_chunk``'s block."""
        from ..kernels import sweep as _sweep
        return _sweep.sweep_vmem_limit(
            self.store.kernel_operand, self.num_replicas,
            self.problem.num_spins, self.chunk_len, coupling=self.fmt,
            block_r=FUSED_BLOCK_R)

    def best_energy(self, state) -> float:
        return float(jnp.min(state[3])) + float(self.problem.offset)

    def trace_row(self, state):
        return state[3]

    def finalize(self, state, rows) -> SolveResult:
        u, s, e, be, bs, nf = state
        off = self.problem.offset
        r = self.num_replicas
        if self.collect_trace and rows:
            trace = (jnp.asarray(np.stack(rows)) + off).astype(jnp.float32)
        else:
            trace = jnp.zeros((0, r), jnp.float32)
        return SolveResult(best_energy=be + off, best_spins=bs.astype(jnp.int8),
                           final_energy=e + off, num_flips=nf,
                           trace_energy=trace,
                           rows_fetched=self._rows_fetched)


@partial(jax.jit, static_argnames=("config", "interpret"))
def _colored_init(plan, seed, config: SolverConfig, interpret: bool):
    from ..kernels import ops as _ops
    base = jax.random.fold_in(jax.random.key(0), seed)
    return _ops.fused_init_state(plan.problem, base, config.num_replicas,
                                 interpret=interpret,
                                 planes=plan.store.planes)


@partial(jax.jit, static_argnames=("config", "clen", "chunk_len",
                                   "interpret"))
def _colored_chunk(state, seed, c, plan, *, config: SolverConfig, clen: int,
                   chunk_len: int, interpret: bool):
    from ..kernels import ops as _ops
    base = jax.random.fold_in(jax.random.key(0), seed)
    return _ops.colored_chunk_step(plan, state, base, c, clen=clen,
                                   chunk_len=chunk_len, config=config,
                                   block_r=8, interpret=interpret,
                                   with_rows_fetched=True)


class ColoredRunner:
    """``solve(backend="colored")`` / ``colored_anneal``, chunk at a time.
    The carried 6-tuple lives in the plan's color-sorted spin order (the
    permutation is deterministic from the problem, so a resumed run rebuilds
    the identical layout); ``finalize`` maps best spins back to original
    vertex order."""

    backend = "colored"

    def __init__(self, problem, seed, config: SolverConfig, plan,
                 chunk_steps: int):
        from ..kernels import ops as _ops
        self.problem = problem
        self.config = config
        self.plan = plan
        self.fmt = plan.store.fmt
        self.seed = jnp.asarray(seed, jnp.uint32)
        self.interpret = _ops.auto_interpret(None)
        self.chunk_len, self.num_chunks, self.rem_steps = (
            _ops.anneal_chunk_plan(config, chunk_steps))
        self.total_units = self.num_chunks + (1 if self.rem_steps else 0)
        self.collect_trace = bool(config.trace_every)
        self.num_replicas = config.num_replicas
        self._rows_fetched = None

    def unit_len(self, k: int) -> int:
        if self.rem_steps and k == self.num_chunks:
            return self.rem_steps
        return self.chunk_len

    def init(self):
        return _colored_init(self.plan, self.seed, self.config,
                             self.interpret)

    def run_chunk(self, state, k: int):
        # Like ShardedRunner, the row-fetch counter rides on the runner:
        # the 6-tuple snapshot contract stays fixed and the counter covers
        # the chunks this process ran (telemetry only).
        state, rf = _colored_chunk(state, self.seed, jnp.int32(k), self.plan,
                                   config=self.config, clen=self.unit_len(k),
                                   chunk_len=self.chunk_len,
                                   interpret=self.interpret)
        self._rows_fetched = (rf if self._rows_fetched is None
                              else self._rows_fetched + rf)
        return state

    def best_energy(self, state) -> float:
        return float(jnp.min(state[3])) + float(self.problem.offset)

    def trace_row(self, state):
        return state[3]

    def finalize(self, state, rows) -> SolveResult:
        from ..kernels import ops as _ops
        u, s, e, be, bs, nf = state
        off = self.problem.offset
        r = self.num_replicas
        if self.collect_trace and rows:
            trace = (jnp.asarray(np.stack(rows)) + off).astype(jnp.float32)
        else:
            trace = jnp.zeros((0, r), jnp.float32)
        return SolveResult(
            best_energy=be + off,
            best_spins=_ops.unpermute_spins(self.plan, bs.astype(jnp.int8)),
            final_energy=e + off, num_flips=nf, trace_energy=trace,
            rows_fetched=self._rows_fetched)


@partial(jax.jit, static_argnames=("config",))
def _reference_init(problem, seed, config: SolverConfig):
    states, _ = reference_init_state(problem, seed, config)
    return states


@partial(jax.jit, static_argnames=("config", "clen", "chunk_len"))
def _reference_chunk(problem, states, seed, c, *, config: SolverConfig,
                     clen: int, chunk_len: int):
    # Replica keys are a pure function of the seed — recomputed per chunk so
    # the snapshot carries chain state only, never RNG state.
    base = jax.random.fold_in(jax.random.key(0), seed)
    keys = jax.vmap(lambda i: rng.stream(base, rng.Salt.REPLICA, i))(
        jnp.arange(config.num_replicas))
    return run_reference_chunk(problem, states, keys, c, clen=clen,
                               chunk_len=chunk_len, config=config,
                               mc=_mcmc_config(config))


class ReferenceRunner:
    """``solve(backend="reference")``, chunk at a time. Every step is keyed
    by its absolute index, so *any* chunking composes to the same values as
    the monolithic loop — traced runs use the trace cadence, untraced runs
    the supervisor's ``chunk_steps``."""

    backend = "reference"
    fmt = "dense"

    def __init__(self, problem, seed, config: SolverConfig, chunk_steps: int):
        from ..kernels import ops as _ops
        if problem.couplings is None:
            raise ValueError(
                "backend='reference' needs the dense J; edge-list "
                "(dense-J-free) problems are served by backend='fused'")
        self.problem = problem
        self.config = config
        self.seed = jnp.asarray(seed, jnp.uint32)
        self.chunk_len, self.num_chunks, self.rem_steps = (
            _ops.anneal_chunk_plan(config, chunk_steps))
        self.total_units = self.num_chunks + (1 if self.rem_steps else 0)
        self.collect_trace = bool(config.trace_every)
        self.num_replicas = config.num_replicas

    def unit_len(self, k: int) -> int:
        if self.rem_steps and k == self.num_chunks:
            return self.rem_steps
        return self.chunk_len

    def init(self):
        return _reference_init(self.problem, self.seed, self.config)

    def run_chunk(self, states, k: int):
        return _reference_chunk(self.problem, states, self.seed,
                                jnp.int32(k), config=self.config,
                                clen=self.unit_len(k),
                                chunk_len=self.chunk_len)

    def best_energy(self, states) -> float:
        return float(jnp.min(states.best_energy)) + float(self.problem.offset)

    def trace_row(self, states):
        return states.best_energy

    def finalize(self, states, rows) -> SolveResult:
        off = self.problem.offset
        r = self.num_replicas
        if self.collect_trace and rows:
            trace = jnp.asarray(np.stack(rows)) + off
        else:
            trace = jnp.zeros((0, r), jnp.float32)
        return SolveResult(best_energy=states.best_energy + off,
                           best_spins=states.best_spins,
                           final_energy=states.energy + off,
                           num_flips=states.num_flips,
                           trace_energy=trace)


@partial(jax.jit, static_argnames=("config", "interpret"))
def _tempering_init(problem, seed, config: TemperingConfig,
                    store: CouplingStore, interpret: bool):
    from ..kernels import ops as _ops
    base = jax.random.fold_in(jax.random.key(0), seed)
    state = _ops.fused_init_state(problem, base, config.num_replicas,
                                  interpret=interpret, planes=store.planes)
    return (state, jnp.int32(0), jnp.int32(0))


@partial(jax.jit, static_argnames=("config", "interpret"))
def _tempering_round(carry, seed, round_idx, store: CouplingStore, *,
                     config: TemperingConfig, interpret: bool):
    state, acc, tot = carry
    base = jax.random.fold_in(jax.random.key(0), seed)
    return fused_tempering_round(state, acc, tot, base, round_idx, config,
                                 store, interpret=interpret)


class TemperingRunner:
    """``solve_tempering(backend="fused")``, one swap round per unit. The
    carried state is ``(kernel 6-tuple, swap-accept, swap-total)`` so the
    acceptance statistic survives resume too."""

    backend = "tempering"

    def __init__(self, problem, seed, config: TemperingConfig,
                 store: CouplingStore):
        from ..kernels import ops as _ops
        if config.backend != "fused":
            raise ValueError(
                "the chunked tempering runner serves the fused backend only "
                "— the reference chains run one flip per XLA op and have no "
                "chunked surface to checkpoint at; set "
                "TemperingConfig(backend='fused')")
        self.problem = problem
        self.config = config
        self.store = store
        self.fmt = store.fmt
        self.seed = jnp.asarray(seed, jnp.uint32)
        self.interpret = _ops.auto_interpret(None)
        self.total_units = tempering_round_count(config)
        self.collect_trace = False
        self.num_replicas = config.num_replicas

    def unit_len(self, k: int) -> int:
        return self.config.swap_every

    def init(self):
        return _tempering_init(self.problem, self.seed, self.config,
                               self.store, self.interpret)

    def run_chunk(self, carry, k: int):
        return _tempering_round(carry, self.seed, jnp.int32(k), self.store,
                                config=self.config, interpret=self.interpret)

    def best_energy(self, carry) -> float:
        return float(jnp.min(carry[0][3])) + float(self.problem.offset)

    def trace_row(self, carry):
        return carry[0][3]

    def finalize(self, carry, rows) -> TemperingResult:
        (u, s, e, be, bs, nf), acc, tot = carry
        off = self.problem.offset
        return TemperingResult(
            best_energy=be + off,
            best_spins=bs.astype(ising.SPIN_DTYPE),
            final_energy=e + off,
            swap_acceptance=acc.astype(jnp.float32) / jnp.maximum(tot, 1),
            num_flips=nf)


@partial(jax.jit, static_argnames=("config", "clen", "chunk_len"))
def _sharded_chunk_inputs(seed, c, *, config: SolverConfig, clen: int,
                          chunk_len: int):
    # Replicated per-chunk uniforms + temps — the identical values
    # sharded_anneal_fn's local_anneal computes (replicated) on every device.
    r = config.num_replicas
    base = jax.random.fold_in(jax.random.key(0), seed)
    steps = c * chunk_len + jnp.arange(clen)
    temps = jax.vmap(config.schedule)(steps).astype(jnp.float32)
    temps = jnp.broadcast_to(temps[:, None], (clen, r))
    uniforms = rng.uniform01(rng.stream(base, rng.Salt.SWEEP, c),
                             (clen, r, 4))
    return uniforms, temps


@jax.jit
def _best_merge(be, bs, nf, ce, cs, cf):
    # ops.fused_sweep_chunk's best-so-far merge, on (possibly sharded) arrays.
    better = ce < be
    return (jnp.where(better, ce, be), jnp.where(better[:, None], cs, bs),
            nf + cf)


class ShardedRunner:
    """``solve_sharded``, chunk at a time: init via ``sharded_init_fn``, the
    per-chunk sweep via ``sharded_sweep_fn``, the best merge identical to the
    in-scan one. State leaves keep their spin-axis shardings across the
    checkpoint round-trip (restore device_puts to the template shardings).
    Serves 1-D and multi-axis (replica groups × rows) meshes alike — the
    chunk inputs are always the full-R replicated tensors; the shard_map
    slices each group's block (``solver_sharded.sharded_sweep_fn``)."""

    def __init__(self, problem, seed, config: SolverConfig, mesh,
                 chunk_steps: int, backend: str = "sharded"):
        from ..distributed import solver_sharded as _ss
        from ..kernels import ops as _ops
        self.backend = backend
        self.fmt = ("bitplane_sharded_2d" if len(mesh.axis_names) > 1
                    else "bitplane_sharded")
        self.problem = problem
        self.config = config
        self.mesh = mesh
        self.seed = jnp.asarray(seed, jnp.uint32)
        self.planes = _ss.resolve_sharded_planes(problem, config, mesh)
        n = problem.num_spins
        self._init_fn = _ss.sharded_init_fn(config, mesh, n)
        self._sweep_fn = _ss.sharded_sweep_fn(config, mesh, n)
        self.chunk_len, self.num_chunks, self.rem_steps = (
            _ops.anneal_chunk_plan(config, chunk_steps))
        self.total_units = self.num_chunks + (1 if self.rem_steps else 0)
        self.collect_trace = bool(config.trace_every)
        self.num_replicas = config.num_replicas
        self._rows_fetched = None

    def unit_len(self, k: int) -> int:
        if self.rem_steps and k == self.num_chunks:
            return self.rem_steps
        return self.chunk_len

    def init(self):
        from jax.sharding import NamedSharding, PartitionSpec
        seed_arr = jnp.asarray([self.seed], jnp.uint32)
        u0, s0, e0 = self._init_fn(self.planes, self.problem.fields, seed_arr)
        # num_flips laid out over the mesh like e0 (replica axis over the
        # group axes on a 2-D mesh, replicated on 1-D) — a default-device
        # zeros would commit the resume template's leaf to one device and
        # clash with the mesh-committed state in the merge.
        grp = tuple(self.mesh.axis_names[:-1]) or None
        nf = jax.device_put(np.zeros((self.num_replicas,), np.int32),
                            NamedSharding(self.mesh, PartitionSpec(grp)))
        return (u0, s0, e0, e0, s0, nf)

    def run_chunk(self, state, k: int):
        u, s, e, be, bs, nf = state
        uniforms, temps = _sharded_chunk_inputs(
            self.seed, jnp.int32(k), config=self.config,
            clen=self.unit_len(k), chunk_len=self.chunk_len)
        # The row-broadcast counter rides on the runner, not the state: the
        # 6-tuple snapshot contract stays fixed, and a resumed run could not
        # reconstruct the pre-crash traffic anyway — the counter covers the
        # chunks this process ran (telemetry only; trajectories unaffected).
        u, s, e, ce, cs, cf, rf = self._sweep_fn(self.planes, u, s, e,
                                                 uniforms, temps)
        self._rows_fetched = (rf if self._rows_fetched is None
                              else self._rows_fetched + rf)
        be, bs, nf = _best_merge(be, bs, nf, ce, cs, cf)
        return (u, s, e, be, bs, nf)

    def best_energy(self, state) -> float:
        return float(jnp.min(state[3])) + float(self.problem.offset)

    def trace_row(self, state):
        return state[3]

    def finalize(self, state, rows) -> SolveResult:
        u, s, e, be, bs, nf = state
        off = self.problem.offset
        r = self.num_replicas
        if self.collect_trace and rows:
            trace = (jnp.asarray(np.stack(rows)) + off).astype(jnp.float32)
        else:
            trace = jnp.zeros((0, r), jnp.float32)
        return SolveResult(best_energy=be + off, best_spins=bs.astype(jnp.int8),
                           final_energy=e + off, num_flips=nf,
                           trace_energy=trace,
                           rows_fetched=self._rows_fetched)


class DistRunner:
    """``solve_distributed``, chunk at a time via
    ``solver_dist.dist_resilient_fns`` — same per-device RNG, chunk cadence,
    and elitist exchange as the monolithic scan. Excluded from the tier
    ladder (the store choice is per-device by construction)."""

    backend = "distributed"

    def __init__(self, problem, seed, config, mesh):
        from ..distributed import solver_dist as _sd
        self.problem = problem
        self.config = config
        init_fn, chunk_fn, setup = _sd.dist_resilient_fns(problem, config,
                                                          mesh)
        self._init_fn = init_fn
        self._chunk_fn = chunk_fn
        self.operands = _sd.dist_operands(problem, seed, setup)
        self.fmt = setup.store.fmt if setup.store is not None else "dense"
        self.chunk_len = setup.chunk
        self.total_units = setup.num_chunks
        self.collect_trace = True   # the dist trace is always on
        self.num_replicas = setup.r_total

    def unit_len(self, k: int) -> int:
        return self.chunk_len

    def init(self):
        return tuple(self._init_fn(*self.operands))

    def run_chunk(self, state, k: int):
        c_arr = jnp.asarray([k], jnp.int32)
        h, seed_arr = self.operands[0], self.operands[1]
        return tuple(self._chunk_fn(*state, h, seed_arr, c_arr,
                                    *self.operands[2:]))

    def best_energy(self, state) -> float:
        return float(jnp.min(state[3])) + float(self.problem.offset)

    def trace_row(self, state):
        return state[3]

    def finalize(self, state, rows) -> SolveResult:
        sp, fu, en, be, bs, nf = state
        off = self.problem.offset
        r = self.num_replicas
        trace = ((jnp.asarray(np.stack(rows)) + off) if rows
                 else jnp.zeros((0, r), jnp.float32))
        return SolveResult(best_energy=be + off, best_spins=bs,
                           final_energy=en + off, num_flips=nf,
                           trace_energy=trace)


# --------------------------------------------------------------------------
# The registered execution paths.

class ReferenceBackend(Backend):
    name = "reference"
    capabilities = Capabilities(
        edge_list=False, needs_mesh=False, supports_store=False,
        supports_resume=True, tier_fallback=False, fixed_fmt="dense",
        auto=False,
        summary="paper-faithful one-flip-per-XLA-op oracle scan")

    def config_cls(self):
        return SolverConfig

    def run(self, problem, seed, config, *, mesh=None, store=None):
        from .solver import _run_jit
        self.check_config(config)
        _require_single_flip(config, self.name)
        if store is not None:
            raise ValueError(
                "a prebuilt CouplingStore serves the fused backend only; "
                "backend='reference' always consumes the dense J")
        if problem.couplings is None:
            raise ValueError(
                "backend='reference' needs the dense J; edge-list "
                "(dense-J-free) problems are served by backend='fused' or "
                "solve_sharded")
        return _run_jit(problem, jnp.asarray(seed, jnp.uint32), config)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        return ReferenceRunner(problem, seed, config, chunk_steps)


def _require_single_flip(config, name: str) -> None:
    """The routing guard of the single-flip paths: a colored config reaching
    them directly (bypassing ``backend="auto"``) must fail loudly, never
    silently run single-flip sweeps."""
    if getattr(config, "flip_mode", "single") != "single":
        raise ValueError(
            f"backend {name!r} runs single-flip updates (flip_mode="
            f"{config.flip_mode!r}); colored block updates are served by "
            "backend='colored'")


def _resolve_store(problem, config, *, fmt=None, store=None, caller: str):
    """The shared store-resolution contract of the fused-family paths: a
    prebuilt store passes through untouched (unless a tier override ``fmt``
    forces a rebuild — the fallback ladder must not resurrect the tier that
    just OOMed), everything else resolves ``config.coupling_format`` and
    runs the encoder once."""
    if store is None or fmt is not None:
        store = CouplingStore.build(problem.coupling_source,
                                    fmt or config.coupling_format)
    store.require(KERNEL_COUPLING_MODES, caller)
    return store


class FusedBackend(Backend):
    name = "fused"
    capabilities = Capabilities(
        edge_list=True, needs_mesh=False, supports_store=True,
        supports_resume=True, tier_fallback=True, fixed_fmt=None,
        summary="VMEM-resident Pallas sweep over the dense/bitplane/"
                "bitplane_hbm coupling tiers")

    def config_cls(self):
        return SolverConfig

    def matches_config(self, config) -> bool:
        return (isinstance(config, SolverConfig)
                and config.flip_mode == "single")

    def prepare(self, problem, config, *, mesh=None, fmt=None, store=None):
        return _resolve_store(problem, config, fmt=fmt, store=store,
                              caller=f"backend {self.name!r}")

    def run(self, problem, seed, config, *, mesh=None, store=None):
        from ..kernels import ops as _ops
        self.check_config(config)
        return _ops.fused_anneal(problem, seed, config, store=store)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        _require_single_flip(config, self.name)
        if fmt in ("bitplane_sharded", "bitplane_sharded_2d"):
            # The last rung of the tier ladder switches a fused solve onto
            # the spin-sharded driver — trajectory-identical by contract.
            if mesh is None:
                raise ValueError(f"the {fmt} tier needs a mesh")
            target = "sharded_2d" if fmt == "bitplane_sharded_2d" else "sharded"
            return get_backend(target).runner(
                problem, seed, config, mesh=mesh, chunk_steps=chunk_steps)
        store = self.prepare(problem, config, fmt=fmt, store=store)
        return FusedRunner(problem, seed, config, store, chunk_steps)


class ColoredBackend(Backend):
    name = "colored"
    capabilities = Capabilities(
        edge_list=True, needs_mesh=False, supports_store=False,
        supports_resume=True, tier_fallback=True, fixed_fmt=None,
        summary="graph-colored block updates — one conflict-graph color "
                "class per step, O(N/χ) flips on sparse instances")

    def config_cls(self):
        return SolverConfig

    def matches_config(self, config) -> bool:
        return (isinstance(config, SolverConfig)
                and config.flip_mode == "colored")

    def _check(self, config, store) -> None:
        if getattr(config, "flip_mode", None) != "colored":
            raise ValueError(
                f"backend 'colored' serves flip_mode='colored' configs, got "
                f"{getattr(config, 'flip_mode', None)!r}")
        if store is not None:
            # A prebuilt store was encoded from the ORIGINAL spin order; the
            # colored path runs in color-sorted order, so accepting it would
            # silently corrupt trajectories. The plan (coloring + permuted
            # store) is the colored path's memoization unit instead — pass it
            # to ops.colored_anneal directly.
            raise ValueError(
                "backend='colored' rebuilds its store in color-sorted spin "
                "order; a prebuilt CouplingStore (original order) cannot be "
                "reused — memoize the ops.colored_plan instead")

    def prepare(self, problem, config, *, mesh=None, fmt=None, store=None):
        from ..kernels import ops as _ops
        self._check(config, store)
        return _ops.colored_plan(problem,
                                 fmt if fmt is not None
                                 else config.coupling_format)

    def run(self, problem, seed, config, *, mesh=None, store=None):
        from ..kernels import ops as _ops
        self.check_config(config)
        self._check(config, store)
        return _ops.colored_anneal(problem, seed, config)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        if fmt in ("bitplane_sharded", "bitplane_sharded_2d"):
            raise ValueError(
                "the colored path has no spin-sharded tier — the tier "
                "ladder ends at bitplane_hbm for backend='colored'")
        plan = self.prepare(problem, config, fmt=fmt, store=store)
        return ColoredRunner(problem, seed, config, plan, chunk_steps)


class TemperingBackend(Backend):
    name = "tempering"
    capabilities = Capabilities(
        edge_list=True, needs_mesh=False, supports_store=True,
        supports_resume=True, tier_fallback=True, fixed_fmt=None,
        summary="fused parallel tempering (swap rounds over a temperature "
                "ladder)")

    def config_cls(self):
        return TemperingConfig

    def prepare(self, problem, config, *, mesh=None, fmt=None, store=None):
        return _resolve_store(problem, config, fmt=fmt, store=store,
                              caller=f"backend {self.name!r}")

    def run(self, problem, seed, config, *, mesh=None, store=None):
        from .tempering import solve_tempering
        self.check_config(config)
        return solve_tempering(problem, seed, config, store=store)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        store = self.prepare(problem, config, fmt=fmt, store=store)
        return TemperingRunner(problem, seed, config, store)


class ShardedBackend(Backend):
    name = "sharded"
    capabilities = Capabilities(
        edge_list=True, needs_mesh=True, supports_store=False,
        supports_resume=True, tier_fallback=False,
        fixed_fmt="bitplane_sharded",
        summary="spin-row-sharded planes across the mesh (capacity scales "
                "with aggregate HBM)")

    def config_cls(self):
        return SolverConfig

    def matches_config(self, config) -> bool:
        return (isinstance(config, SolverConfig)
                and config.flip_mode == "single")

    def prepare(self, problem, config, *, mesh=None, fmt=None, store=None):
        from ..distributed import solver_sharded as _ss
        if mesh is None:
            raise ValueError("backend='sharded' needs a mesh")
        return _ss.resolve_sharded_planes(problem, config, mesh)

    def run(self, problem, seed, config, *, mesh=None, store=None):
        from ..distributed import solver_sharded as _ss
        self.check_config(config)
        _require_single_flip(config, self.name)
        if mesh is None:
            raise ValueError("backend='sharded' needs a mesh")
        if store is not None:
            raise ValueError(
                "backend='sharded' builds per-device plane shards from the "
                "problem; a prebuilt CouplingStore serves the fused backend "
                "only")
        return _ss.solve_sharded(problem, seed, config, mesh)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        _require_single_flip(config, self.name)
        if mesh is None:
            raise ValueError("the bitplane_sharded tier needs a mesh")
        return ShardedRunner(problem, seed, config, mesh, chunk_steps,
                             backend=self.name)


class Sharded2DBackend(ShardedBackend):
    """The 2-D (replica groups × spin rows) instantiation of the sharded
    path: same driver, but the mesh must carry at least two axes — the last
    row-shards the planes within each group, the leading axes replicate
    planes across independent replica groups. Not auto-resolved (a plain
    ``SolverConfig`` + mesh resolves to ``"sharded"``, whose driver already
    serves multi-axis meshes natively); name it explicitly, or let the tier
    ladder escalate to it when the mesh is 2-D."""

    name = "sharded_2d"
    capabilities = Capabilities(
        edge_list=True, needs_mesh=True, supports_store=False,
        supports_resume=True, tier_fallback=False,
        fixed_fmt="bitplane_sharded_2d", auto=False,
        summary="(groups, rows) mesh: planes row-sharded within each "
                "replica group, replicated across groups — J capacity and "
                "replica throughput scale together")

    @staticmethod
    def _check_mesh(mesh) -> None:
        if mesh is None:
            raise ValueError("backend='sharded_2d' needs a (groups, rows) "
                             "mesh")
        if len(mesh.axis_names) < 2:
            raise ValueError(
                f"backend='sharded_2d' needs a mesh with >= 2 axes (leading "
                f"= replica groups, last = spin rows); got the 1-axis mesh "
                f"{tuple(mesh.axis_names)} — use backend='sharded' for 1-D "
                f"row sharding")

    def prepare(self, problem, config, *, mesh=None, fmt=None, store=None):
        self._check_mesh(mesh)
        return super().prepare(problem, config, mesh=mesh, fmt=fmt,
                               store=store)

    def run(self, problem, seed, config, *, mesh=None, store=None):
        self._check_mesh(mesh)
        return super().run(problem, seed, config, mesh=mesh, store=store)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        self._check_mesh(mesh)
        return super().runner(problem, seed, config, mesh=mesh,
                              chunk_steps=chunk_steps, fmt=fmt, store=store)


class DistributedBackend(Backend):
    name = "distributed"
    capabilities = Capabilities(
        edge_list=True, needs_mesh=True, supports_store=False,
        supports_resume=True, tier_fallback=False, fixed_fmt=None,
        summary="replica-parallel shard_map driver with elitist exchange "
                "(J replicated per device)")

    def config_cls(self):
        from ..distributed.solver_dist import DistSolverConfig
        return DistSolverConfig

    def run(self, problem, seed, config, *, mesh=None, store=None):
        from ..distributed.solver_dist import solve_distributed
        self.check_config(config)
        if mesh is None:
            raise ValueError("backend='distributed' needs a mesh")
        if store is not None:
            raise ValueError(
                "backend='distributed' builds its store per device; a "
                "prebuilt CouplingStore serves the fused backend only")
        return solve_distributed(problem, seed, config, mesh)

    def runner(self, problem, seed, config, *, mesh=None, chunk_steps=256,
               fmt=None, store=None):
        if mesh is None:
            raise ValueError("backend='distributed' needs a mesh")
        return DistRunner(problem, seed, config, mesh)


register(ReferenceBackend())
register(FusedBackend())
register(ColoredBackend())
register(TemperingBackend())
register(ShardedBackend())
register(Sharded2DBackend())
register(DistributedBackend())
