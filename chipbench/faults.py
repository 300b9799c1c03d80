"""Faults planted in the program's timed path, and the control solver.

Each fault is one a solve can have; ``chipbench.check`` must read every one
of them, and the control, as not correct (``chipbench/tests/test_check.py``
on the CPU; ``python3 -m chipbench.calibrate`` on the chip at a cell's own
size):

* ``unchanged`` — every sweep chunk returns its state unchanged;
* ``half_batch`` — only the first half of the replicas is annealed;
* ``altered`` — the answer is altered where it is produced: the first
  replica's best energy is reported 2 lower than its spins give;
* ``downgrade`` — the first chunk of every solve fails to allocate on the
  cell's tier, so the program's supervisor moves the solve to the next
  coupling tier (a tier with no next one, as on ``g81``, cannot have it).

The exchange between chips does not exist in a one-chip cell.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from .reference import Reference

FAULTS = ("unchanged", "half_batch", "altered", "downgrade")


def _allocation_failure():
    """A hook for the program's fault seam: the first chunk of every solve
    on the tier the first solve ran on raises what an allocator would."""
    first = []

    def hook(site: str, info: dict) -> None:
        if site != "chunk_start" or info["chunk"] != 0:
            return
        first[:] = first or [info["fmt"]]
        if info["fmt"] == first[0]:
            raise RuntimeError("RESOURCE_EXHAUSTED: planted allocation "
                               "failure")
    return hook


@contextlib.contextmanager
def planted(kind: str):
    """Break the program's timed path with fault ``kind`` for the block."""
    if kind not in FAULTS:
        raise ValueError(f"unknown fault {kind!r}; one of {FAULTS}")
    from repro.core import backend, resilience
    cls = backend.FusedRunner
    run_chunk, finalize = cls.run_chunk, cls.finalize
    if kind == "downgrade":
        with resilience.inject_faults(_allocation_failure()):
            yield
        return
    if kind == "unchanged":
        cls.run_chunk = lambda self, state, k: state
    elif kind == "half_batch":
        def half(self, state, k):
            new = run_chunk(self, state, k)
            r = state[0].shape[0]
            keep = jnp.arange(r) < r // 2
            return tuple(jnp.where(keep.reshape((r,) + (1,) * (a.ndim - 1)),
                                   a, b) for a, b in zip(new, state))
        cls.run_chunk = half
    else:
        def altered(self, state, rows):
            res = finalize(self, state, rows)
            return res._replace(best_energy=res.best_energy.at[0].add(-2.0))
        cls.finalize = altered
    try:
        yield
    finally:
        cls.run_chunk, cls.finalize = run_chunk, finalize


class ControlSolver:
    """The control: the plain reference put in the program's place,
    computed in bfloat16 — the nearest precision below the float32 the
    configurations state."""

    def __init__(self, inst, traffic: dict):
        import jax
        self._key = jax.random.key
        self.ref = Reference(inst, traffic, dtype=jnp.bfloat16)
        self.replicas = traffic["replicas"]

    def __call__(self, seed: int):
        from .run import Out
        be, bs = self.ref.anneal(self._key(seed), self.replicas,
                                 keep_spins=True)
        return Out(be.astype(jnp.float32), bs.astype(jnp.int8),
                   jnp.zeros((self.replicas,), jnp.int32), True)


def control_factory(traffic: dict):
    return lambda problem, store, inst: ControlSolver(inst, traffic)
