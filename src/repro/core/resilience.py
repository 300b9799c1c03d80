"""Resilient solve supervisor: crash-safe checkpoint/resume, budgets, tiers.

Long anneals die — preemption, OOM, a deadline, a ctrl-C — and the paper's
TTS methodology (§V) only works if a killed trial can either finish later or
report an honest best-so-far. This module wraps every registered execution
path (``core.backend.BACKENDS`` — reference, fused, tempering, sharded,
distributed) in one chunk-granular supervisor, :func:`run_resilient`:

* **Checkpoint/resume, bit-identical.** Every backend already advances its
  trajectory in chunks whose RNG is a pure function of ``(seed, chunk
  index)`` (the ``Salt.SWEEP`` streams / absolute-step keys) — no carried
  RNG state. The supervisor drives each backend's chunk runner
  (``core.backend.Backend.runner`` — the *same* chunk bodies the monolithic
  scans use) one host-visible chunk at a time, and atomically snapshots the
  full chain state at chunk boundaries (``checkpoint.manager``: temp dir +
  rename + sha256). A restarted run reconstructs the exact chunk cadence
  from ``(config, chunk_steps)`` and replays the remaining chunks — the
  resumed trajectory is **bit-identical** to the uninterrupted one
  (asserted across every coupling tier by ``tests/test_resilience.py`` and
  for every registered backend by ``tests/test_backend_registry.py``).

* **Corruption containment.** A snapshot that fails its checksum (torn
  write, flipped bit, truncation) raises ``SnapshotCorruptError`` at
  restore; the supervisor falls back to the next-older snapshot, and to a
  fresh start when none survives. A ``run_dir`` whose snapshots belong to a
  *different* (problem, seed, config) is refused loudly — resuming someone
  else's trajectory would silently corrupt results.

* **Budgets.** :class:`BudgetConfig` bounds the run by wall-clock deadline,
  total sweep steps, or a target energy; checks happen between chunks and
  always return the best-so-far with a structured ``stop_reason``
  ("completed" | "deadline" | "max_steps" | "target" | "interrupted").
  ``KeyboardInterrupt`` is caught at the same granularity: the state is
  snapshotted and the partial result returned instead of a traceback.

* **Tier fallback.** With ``coupling_format="auto"``, an allocation failure
  (RESOURCE_EXHAUSTED / OOM) while building the coupling store or running a
  chunk retries at the next coupling tier — dense → bitplane →
  bitplane_hbm → bitplane_sharded / bitplane_sharded_2d (the last rung only
  when a mesh is supplied and the shard alignment holds on its last axis;
  the 2-D tier when the mesh carries replica-group axes) — restoring from
  the last snapshot, so
  completed work survives the downgrade. Because the tiers are
  trajectory-identical by contract, a downgraded run still produces
  bit-identical results. Downgrades are recorded on the result and in every
  subsequent snapshot. Which paths ride the ladder is a registry capability
  (``Capabilities.tier_fallback``); the distributed driver opts out (its
  store is per-device by construction; losing a host is handled by replica
  independence, not by re-tiering).

**Spans.** Every solve opens ``jax.profiler.TraceAnnotation`` spans named
``snowball.*`` (:func:`span`) around its supervisor steps — the whole
solve, the two identity steps (``cached`` = 1 where the problem's
fingerprint was kept from an earlier solve and no byte is hashed), each
runner build (``fmt``, and for the fused runner ``vmem_bytes``, the
scoped-VMEM request of its sweep kernel), init, chunk dispatch and
finalize — tagged with a per-process solve id. Under a profiler session
they land in the same trace as the device ops, on the same clock; without
one each costs well under a microsecond.

Fault injection for tests rides on :func:`inject_faults` — a context-local
hook fired at the supervisor's seams ("store_build", "chunk_start",
"checkpoint_saved") so the harness (``tests/fault_injection.py``) can raise
synthetic OOMs or kill the process at randomized chunk boundaries.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import itertools
import re
import time
from typing import Callable, NamedTuple, Optional

import jax
import numpy as np

from . import ising
from .backend import (current_fmt as _current_fmt, fallback_enabled
                      as _fallback_enabled, get_backend, resolve_backend)
from .coupling import CouplingStore
from ..checkpoint import manager as ckpt
from ..checkpoint.manager import SnapshotCorruptError

#: Structured stop reasons — the full closed set a ``ResilientResult`` can
#: carry.
STOP_COMPLETED = "completed"
STOP_DEADLINE = "deadline"
STOP_MAX_STEPS = "max_steps"
STOP_TARGET = "target"
STOP_INTERRUPTED = "interrupted"
STOP_REASONS = (STOP_COMPLETED, STOP_DEADLINE, STOP_MAX_STEPS, STOP_TARGET,
                STOP_INTERRUPTED)


@dataclasses.dataclass(frozen=True)
class BudgetConfig:
    """Between-chunk run bounds; every bound returns best-so-far, never an
    exception. ``target_energy`` compares against the ensemble-best energy
    *including* the problem offset (the user-facing value)."""
    deadline_seconds: Optional[float] = None
    max_steps: Optional[int] = None
    target_energy: Optional[float] = None


class ResilientResult(NamedTuple):
    result: object              # SolveResult | TemperingResult (best-so-far)
    stop_reason: str            # one of STOP_REASONS
    steps_done: int             # sweep steps actually advanced (incl. resumed)
    chunks_done: int            # chunk units completed
    total_chunks: int
    resumed_from_chunk: Optional[int]   # snapshot the run resumed at, or None
    downgrades: tuple           # ((from_fmt, to_fmt, at_chunk), ...)


# --------------------------------------------------------------------------
# Spans: the supervisor's steps in the profiler's trace.

SPAN_PREFIX = "snowball."
_solve_ids = itertools.count()


def span(name: str, **ids):
    """A profiler span ``snowball.<name>`` carrying ``ids`` as its stats.
    It records only while a profiler session (``jax.profiler.trace``) is
    active, in the same trace and on the same clock as the device ops."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)


# --------------------------------------------------------------------------
# Fault injection (tests only): a context-local hook at the supervisor seams.

_fault_hook: Optional[Callable] = None


@contextlib.contextmanager
def inject_faults(hook: Callable[[str, dict], None]):
    """Install ``hook(site, info)`` for the duration of the block. Sites:
    "store_build" (before a tier's store/runner build), "chunk_start"
    (before each chunk; ``info["chunk"]``), "checkpoint_saved" (after each
    snapshot). Whatever the hook raises propagates into the supervisor —
    raising an allocation-failure error at "store_build"/"chunk_start"
    exercises the tier-fallback path without a real OOM."""
    global _fault_hook
    prev = _fault_hook
    _fault_hook = hook
    try:
        yield
    finally:
        _fault_hook = prev


def _fault(site: str, **info):
    if _fault_hook is not None:
        _fault_hook(site, info)


# --------------------------------------------------------------------------
# Allocation-failure detection and the tier ladder.

#: Whole-word markers of an allocation failure in an error message.
_ALLOC_WORDS = re.compile(
    r"\b(resource_exhausted|out of memory|failed to allocate|oom)\b")
#: A kernel the TPU compiler refuses — over its scoped-VMEM budget included —
#: is a bug in that kernel's blocking, which no other tier fixes.
_COMPILE_REFUSAL = re.compile(r"\bmosaic\b")


def is_allocation_failure(exc: BaseException) -> bool:
    """Whether ``exc`` looks like a memory-allocation failure (XLA
    RESOURCE_EXHAUSTED, allocator OOM, host ``MemoryError``) — the class of
    error the tier ladder can actually fix, as opposed to bugs it must
    propagate. Markers match whole words only ("oom" is not "room"), and a
    Mosaic compile refusal never counts, whatever memory it names."""
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc).lower()
    return (_ALLOC_WORDS.search(msg) is not None
            and _COMPILE_REFUSAL.search(msg) is None)


def next_tier(fmt: str, problem: ising.IsingProblem, mesh) -> Optional[str]:
    """The coupling tier to retry at after ``fmt`` hit an allocation
    failure, or None when the ladder ends: dense → bitplane (integral J
    only) → bitplane_hbm → bitplane_sharded / bitplane_sharded_2d (mesh
    present, shard-aligned; the 2-D tier when the mesh has replica-group
    axes — the planes row-shard over the **last** mesh axis only)."""
    if fmt == "dense":
        if problem.couplings is not None:
            J = np.asarray(jax.device_get(problem.couplings))
            if not np.array_equal(J, np.rint(J)):
                return None         # fractional J has no packed tier
        return "bitplane"
    if fmt == "bitplane":
        return "bitplane_hbm"
    if fmt == "bitplane_hbm":
        if mesh is None:
            return None
        from ..kernels import common
        num_rows = int(mesh.shape[mesh.axis_names[-1]])
        n = problem.num_spins
        if n % num_rows or (n // num_rows) % common.default_lane(n):
            return None             # unshardable problem: ladder ends
        return ("bitplane_sharded_2d" if len(mesh.axis_names) > 1
                else "bitplane_sharded")
    return None


# --------------------------------------------------------------------------
# Run identity: a resumable snapshot must belong to *this* run.

def problem_fingerprint(problem: ising.IsingProblem) -> str:
    """Content hash of the problem (couplings/edges + fields + offset) —
    written into every snapshot so a resume onto a different instance is
    refused instead of silently mixing trajectories.

    The hash (``ising.content_fingerprint``) runs once per problem object
    and is kept on it when the content cannot change under it
    (``IsingProblem.immutable``: ``jax.Array`` J and h, as
    ``IsingProblem.create`` builds, or an ``EdgeList``), so the repeat
    solves of one instance hash nothing. A problem built by hand over NumPy
    arrays, which can change in place, is hashed on every call."""
    if problem.immutable:
        return problem._fingerprint
    return ising.content_fingerprint(problem)


def fingerprint_cached(problem: ising.IsingProblem) -> bool:
    """Whether :func:`problem_fingerprint` will return a kept value and
    hash no bytes of ``problem``."""
    return problem.immutable and "_fingerprint" in vars(problem)


def run_signature(problem: ising.IsingProblem, seed, config, *, backend: str,
                  chunk_steps: int, mesh) -> str:
    """Hash of everything the chunk cadence and RNG streams depend on. The
    configs are frozen dataclasses of plain values, so their reprs are
    stable across processes. The problem enters through
    :func:`problem_fingerprint`, so an immutable problem's bytes are hashed
    only on its first solve; a NumPy-backed one is hashed on every call."""
    mesh_desc = (None if mesh is None
                 else tuple((a, int(mesh.shape[a])) for a in mesh.axis_names))
    parts = "|".join([
        f"seed={int(seed)}", f"backend={backend}",
        f"chunk_steps={int(chunk_steps)}", f"config={config!r}",
        f"mesh={mesh_desc!r}",
        f"problem={problem_fingerprint(problem)}",
    ])
    return hashlib.sha256(parts.encode()).hexdigest()


# --------------------------------------------------------------------------
# Snapshot plumbing.

def _trace_template(runner, chunks: int):
    rows = chunks if runner.collect_trace else 0
    return np.zeros((rows, runner.num_replicas), np.float32)


def _save_snapshot(mgr: ckpt.CheckpointManager, runner, state, rows,
                   chunks_done: int, steps_done: int, signature: str,
                   fingerprint: str, downgrades):
    trace = (np.stack(rows).astype(np.float32) if rows
             else _trace_template(runner, 0))
    mgr.save(chunks_done, {"state": state, "trace": trace},
             extra={"signature": signature, "fingerprint": fingerprint,
                    "chunks_done": chunks_done, "steps_done": steps_done,
                    "fmt": runner.fmt, "backend": runner.backend,
                    "downgrades": [list(d) for d in downgrades]})


def _try_resume(run_dir: str, runner, signature: str, fingerprint: str,
                emit):
    """Newest-first walk over the snapshots in ``run_dir``: identity
    mismatches are refused loudly, corrupt snapshots are skipped with an
    event, and ``(None, ...)`` means no usable snapshot — start fresh.
    Returns ``(state, rows, chunks_done, steps_done, downgrades)``."""
    for step in reversed(ckpt.snapshot_steps(run_dir)):
        try:
            manifest = ckpt.read_manifest(run_dir, step)
        except SnapshotCorruptError as e:
            emit("snapshot_corrupt", {"step": step, "error": str(e)})
            continue
        extra = manifest.get("extra", {})
        if extra.get("fingerprint") not in (None, fingerprint):
            raise ValueError(
                f"run_dir {run_dir!r} holds snapshots of a different "
                f"problem (fingerprint mismatch at step_{step}) — refusing "
                f"to resume; point --run-dir at a fresh directory")
        if extra.get("signature") not in (None, signature):
            raise ValueError(
                f"run_dir {run_dir!r} holds snapshots of a different run "
                f"configuration (signature mismatch at step_{step}) — the "
                f"chunk cadence would diverge; refusing to resume")
        template = {"state": runner.init(),
                    "trace": _trace_template(runner, step)}
        try:
            tree = ckpt.restore(run_dir, step, template)
        except SnapshotCorruptError as e:
            emit("snapshot_corrupt", {"step": step, "error": str(e)})
            continue
        rows = [np.asarray(row) for row in np.asarray(tree["trace"])]
        downgrades = [tuple(d) for d in extra.get("downgrades", [])]
        emit("resume", {"chunk": step, "fmt": extra.get("fmt")})
        return (tree["state"], rows, int(extra.get("chunks_done", step)),
                int(extra.get("steps_done", 0)), downgrades)
    return None, [], 0, 0, []


def _check_budget(budget: BudgetConfig, runner, state, steps_done: int,
                  t_start: float) -> Optional[str]:
    if budget.target_energy is not None:
        if runner.best_energy(state) <= budget.target_energy:
            return STOP_TARGET
    if budget.max_steps is not None and steps_done >= budget.max_steps:
        return STOP_MAX_STEPS
    if (budget.deadline_seconds is not None
            and time.monotonic() - t_start >= budget.deadline_seconds):
        return STOP_DEADLINE
    return None


# --------------------------------------------------------------------------
# The supervisor.

def run_resilient(problem: ising.IsingProblem, seed, config,
                  run_dir: Optional[str] = None, *, backend: str = "auto",
                  mesh=None, budget: Optional[BudgetConfig] = None,
                  chunk_steps: int = 256, checkpoint_every: int = 1,
                  keep: int = 3, resume: bool = True,
                  on_event: Optional[Callable] = None,
                  store: Optional[CouplingStore] = None) -> ResilientResult:
    """Run any registered backend chunk-by-chunk with checkpointing,
    budgets, and tier fallback — bit-identical to the monolithic driver it
    wraps.

    ``backend`` names any ``core.backend.BACKENDS`` entry; ``"auto"``
    resolves one from the config type (``TemperingConfig`` → fused
    tempering, ``DistSolverConfig`` → ``solve_distributed`` — needs
    ``mesh`` — ``SolverConfig`` → the fused anneal, or ``solve_sharded``
    when a ``mesh`` is supplied). ``backend="reference"`` selects the oracle
    scan engine explicitly. ``run_dir=None`` disables checkpointing (budgets
    and interrupts still work); with a directory, a snapshot is written
    every ``checkpoint_every`` completed chunks (``CheckpointManager``
    retention keeps the newest ``keep``) and ``resume=True`` continues from
    the newest *valid* snapshot — corrupt ones fall back to older,
    mismatched problem/config are refused with ``ValueError``.

    ``chunk_steps`` is the untraced chunk granularity (the resume/budget
    quantum); with ``trace_every`` set, chunks are the trace cadence, as in
    the monolithic drivers. It must be passed identically on resume — it is
    part of the run signature because the fused ``Salt.SWEEP`` streams are
    keyed per chunk. ``on_event(kind, info)`` observes "resume",
    "chunk", "snapshot", "snapshot_corrupt", "tier_downgrade", "stop".
    """
    sid = next(_solve_ids)
    with span("solve", solve=sid):
        t_start = time.monotonic()
        backend = resolve_backend(config, backend, mesh)
        budget = budget or BudgetConfig()
        emit = on_event or (lambda kind, info: None)
        cached = int(fingerprint_cached(problem))
        with span("fingerprint", solve=sid, what="signature", cached=cached):
            signature = run_signature(problem, seed, config, backend=backend,
                                      chunk_steps=chunk_steps, mesh=mesh)
        with span("fingerprint", solve=sid, what="fingerprint",
                  cached=cached):
            fingerprint = problem_fingerprint(problem)
        mgr = (ckpt.CheckpointManager(run_dir, keep=keep)
               if run_dir is not None else None)
        downgrades: list = []
        fmt: Optional[str] = None
        resumed_from: Optional[int] = None

        def build(fmt):
            cur = _current_fmt(problem, config, backend, fmt)
            with span("runner_build", solve=sid, fmt=cur) as sp:
                _fault("store_build", fmt=cur, backend=backend)
                runner = get_backend(backend).runner(
                    problem, seed, config, mesh=mesh,
                    chunk_steps=chunk_steps, fmt=fmt, store=store)
                if sp.is_enabled() and hasattr(runner, "vmem_bytes"):
                    sp.set_metadata(vmem_bytes=runner.vmem_bytes())
                return runner

        def downgrade_or_raise(exc, at_chunk: int):
            nonlocal fmt
            if not (_fallback_enabled(config, backend)
                    and is_allocation_failure(exc)):
                raise exc
            cur = _current_fmt(problem, config, backend, fmt)
            nxt = next_tier(cur, problem, mesh)
            if nxt is None:
                raise exc
            downgrades.append((cur, nxt, at_chunk))
            emit("tier_downgrade", {"from": cur, "to": nxt,
                                    "chunk": at_chunk, "error": str(exc)})
            fmt = nxt

        runner = None
        while runner is None:
            try:
                runner = build(fmt)
            except Exception as e:   # noqa: BLE001 — alloc-failure triage
                downgrade_or_raise(e, 0)

        while True:   # tier-retry loop around the chunk drive
            state, rows, k, steps_done = None, [], 0, 0
            try:
                if mgr is not None and resume:
                    state, rows, k, steps_done, prior = _try_resume(
                        run_dir, runner, signature, fingerprint, emit)
                    if state is not None:
                        resumed_from = k
                        # Downgrades recorded by the pre-crash attempt
                        # survive.
                        downgrades = prior + [d for d in downgrades
                                              if d not in prior]
                if state is None:
                    with span("init", solve=sid):
                        state = runner.init()
                total = runner.total_units
                stop_reason = STOP_COMPLETED
                try:
                    while k < total:
                        reason = _check_budget(budget, runner, state,
                                               steps_done, t_start)
                        if reason is not None:
                            stop_reason = reason
                            break
                        _fault("chunk_start", chunk=k, fmt=runner.fmt)
                        with span("chunk", solve=sid, chunk=k):
                            state = runner.run_chunk(state, k)
                        steps_done += runner.unit_len(k)
                        if runner.collect_trace:
                            rows.append(np.asarray(jax.device_get(
                                runner.trace_row(state))))
                        k += 1
                        emit("chunk", {"chunk": k, "total": total})
                        if mgr is not None and (k % checkpoint_every == 0
                                                or k == total):
                            _save_snapshot(mgr, runner, state, rows, k,
                                           steps_done, signature,
                                           fingerprint, downgrades)
                            emit("snapshot", {"chunk": k})
                            _fault("checkpoint_saved", chunk=k)
                except KeyboardInterrupt:
                    stop_reason = STOP_INTERRUPTED
                if (stop_reason != STOP_COMPLETED and mgr is not None
                        and k > 0):
                    # Budget/interrupt stop between snapshots: persist the
                    # frontier so a later run continues instead of replaying.
                    _save_snapshot(mgr, runner, state, rows, k, steps_done,
                                   signature, fingerprint, downgrades)
                break
            except Exception as e:   # noqa: BLE001 — alloc-failure triage
                downgrade_or_raise(e, k)
                runner = None
                while runner is None:
                    try:
                        runner = build(fmt)
                    except Exception as e2:  # noqa: BLE001
                        downgrade_or_raise(e2, k)

        with span("finalize", solve=sid):
            result = runner.finalize(state, rows)
        emit("stop", {"reason": stop_reason, "chunks_done": k,
                      "steps_done": steps_done})
        return ResilientResult(result=result, stop_reason=stop_reason,
                               steps_done=steps_done, chunks_done=k,
                               total_chunks=runner.total_units,
                               resumed_from_chunk=resumed_from,
                               downgrades=tuple(downgrades))
