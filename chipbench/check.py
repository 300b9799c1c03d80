"""The comparison that decides ``correct``.

Every solve of the window is judged by what it says, against the plain
reference (``chipbench.reference``) on the instance the benchmark made:

* ``energy_gap`` — the largest |returned best energy − H(returned best
  spins)| over every replica of a sample of the solves (drawn from the
  seed, see ``chipbench.run.SPIN_SAMPLE``), H summed exactly on the host; a
  spin that is not ±1 makes it infinite. The energies are integers carried
  in float32, so a sound program reads 0. Limit 0.
* ``quality_z`` — |two-sample z| between the mean best energy of every
  replica of every solve and that of the reference's own anneals of the
  same instance at the same length, schedule and flip law: a run whose
  sweeps do not do the chain's work shows as a shifted mean.
* ``worst_z`` — how far the worst best energy of any replica of any solve
  lies above the reference's mean, in the reference's standard deviations:
  a replica the program left at its random start reads far above the
  spread of sound anneals, however few such replicas the mean holds.
* ``failed_solves`` — solves that did not complete or changed coupling
  tier (the configurations guarantee neither happens). Limit 0.

Each limit, and the readings it was set from, is in
``chipbench/limits/<workload>.json``.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from .instances import Instance
from .reference import Reference, exact_energies


def energy_gap(inst: Instance, best_energy: np.ndarray,
               best_spins: np.ndarray) -> float:
    spins = np.asarray(best_spins)
    if not np.all(np.abs(spins) == 1):
        return math.inf
    exact = exact_energies(inst, spins)
    return float(np.max(np.abs(np.asarray(best_energy, np.float64) - exact)))


def z_score(a: np.ndarray, b: np.ndarray) -> float:
    """|mean(a) − mean(b)| over the standard error of that difference."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    diff = abs(a.mean() - b.mean())
    se = math.sqrt(a.var(ddof=1) / a.size + b.var(ddof=1) / b.size)
    if se == 0.0:
        return 0.0 if diff == 0.0 else math.inf
    return diff / se


def worst_z(program: np.ndarray, ref: np.ndarray) -> float:
    """(max(program) − mean(ref)) / sd(ref), the deviation floored at one
    energy unit so that a reference whose replicas all agree stays
    finite."""
    ref = np.asarray(ref, np.float64)
    sd = max(float(ref.std(ddof=1)), 1.0)
    return (float(np.max(program)) - float(ref.mean())) / sd


def reference_best(inst: Instance, traffic: dict, key: int) -> np.ndarray:
    ref = Reference(inst, traffic)
    be, _ = ref.anneal(jax.random.key(key), traffic["reference_replicas"])
    return np.asarray(be, np.float64)


def compare(run, inst: Instance, limits: dict, key: int) -> dict:
    """{name: {"value": v, "limit": l}} for every number compared."""
    program = np.concatenate([s.best_energy for s in run.solves])
    sampled = [s for s in run.solves if s.best_spins is not None]
    gap = energy_gap(inst, np.concatenate([s.best_energy for s in sampled]),
                     np.concatenate([s.best_spins for s in sampled]))
    ref = reference_best(inst, run.cell.traffic, key)
    values = {"energy_gap": gap, "quality_z": z_score(program, ref),
              "worst_z": worst_z(program, ref),
              "failed_solves": sum(not s.ok for s in run.solves)}
    return {k: {"value": float(v), "limit": limits[k]["limit"]}
            for k, v in values.items()}
