"""Instance families of the benchmark, made on the host from a seed.

Each family draws a fixed *base* instance (the published shape, from the
configuration's ``base_seed``) and then relabels it from the run's seed: a
random vertex permutation and a random gauge g ∈ {±1}^N, w'_{π(a)π(b)} =
g_a g_b w_ab. Every run therefore gets its own coupling data, and every run
gets the same energy landscape: spins s' with s'_{π(a)} = g_a s_a have the
energy s has on the base instance, so a target energy keeps its difficulty
from seed to seed. A fresh random instance per seed would not: at K2000 the
total weight alone moves the energy of a fixed cut by about ±1,400, which
turns a fixed target into a success share of 0 or 1.

Energies follow the program's Max-Cut convention (J = −w, h = 0):
H(s) = Σ_{i<j} w_ij s_i s_j and cut(s) = (Σ_{i<j} w_ij − H(s)) / 2.

Nothing here imports the program: the reference and the check use these
arrays directly, and the harness hands the same arrays to the program.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Instance:
    """An Ising instance in the Max-Cut convention. Exactly one of
    ``weights`` (dense symmetric (N, N) float32, zero diagonal) and
    ``edges`` ((rows, cols, w) int64 arrays, rows < cols, each edge once)
    is set."""

    num_spins: int
    weights: Optional[np.ndarray] = None
    edges: Optional[tuple] = None

    @property
    def total_weight(self) -> int:
        if self.weights is not None:
            return int(np.triu(self.weights, 1).sum(dtype=np.float64))
        return int(self.edges[2].sum())

    def neighbors(self):
        """(N, D) neighbour indices and (N, D) weights w, zero-padded (a
        padded slot points at the vertex itself with weight 0)."""
        rows, cols, w = self.edges
        n = self.num_spins
        a = np.concatenate([rows, cols])
        b = np.concatenate([cols, rows])
        ww = np.concatenate([w, w])
        order = np.argsort(a, kind="stable")
        a, b, ww = a[order], b[order], ww[order]
        deg = np.bincount(a, minlength=n)
        d = int(deg.max())
        slot = np.arange(a.size) - np.repeat(np.cumsum(deg) - deg, deg)
        nbr = np.repeat(np.arange(n)[:, None], d, axis=1)
        wt = np.zeros((n, d), np.int64)
        nbr[a, slot] = b
        wt[a, slot] = ww
        return nbr, wt


def _pcg(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.PCG64(seed))


def complete_bipolar(n: int, seed: int) -> Instance:
    """Complete graph with w_ij ∈ {−1, +1} uniform (the paper's K2000, §V-A2).
    Draws the same matrix as the program's ``graphs.complete_bipolar``."""
    rng = _pcg(seed)
    signs = rng.choice(np.array([-1.0, 1.0], np.float32), size=(n, n))
    w = np.triu(np.ones((n, n), np.float32), 1) * signs
    return Instance(n, weights=(w + w.T).astype(np.float32))


def torus(rows: int, cols: int, seed: int) -> Instance:
    """2-D periodic grid with ±1 edge weights (the Gset torus family, G81's
    shape). Draws the same edges and signs as the program's
    ``graphs.generators.torus_grid_edges``."""
    rng = _pcg(seed)
    n = rows * cols
    idx = np.arange(n, dtype=np.int64)
    r, c = idx // cols, idx % cols
    i = np.concatenate([idx, idx])
    j = np.concatenate([((r + 1) % rows) * cols + c, r * cols + (c + 1) % cols])
    w = rng.choice(np.array([-1, 1], np.int64), size=i.size)
    lo, hi = np.minimum(i, j), np.maximum(i, j)
    order = np.lexsort((hi, lo))
    return Instance(n, edges=(lo[order], hi[order], w[order]))


FAMILIES = {
    "complete_bipolar": lambda cfg: complete_bipolar(cfg["num_vertices"],
                                                     cfg["base_seed"]),
    "torus": lambda cfg: torus(cfg["rows"], cfg["cols"], cfg["base_seed"]),
}


def base_instance(cfg: dict) -> Instance:
    return FAMILIES[cfg["family"]](cfg)


def relabel(inst: Instance, rng: np.random.Generator) -> Instance:
    """The base instance under a random vertex permutation and gauge."""
    n = inst.num_spins
    perm = rng.permutation(n)                 # base vertex a -> perm[a]
    gauge = rng.choice(np.array([-1, 1], np.int64), size=n)
    if inst.weights is not None:
        w = inst.weights * np.outer(gauge, gauge).astype(np.float32)
        out = np.empty_like(w)
        out[np.ix_(perm, perm)] = w
        return Instance(n, weights=out)
    rows, cols, w = inst.edges
    a, b = perm[rows], perm[cols]
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    ww = w * gauge[rows] * gauge[cols]
    order = np.lexsort((hi, lo))
    return Instance(n, edges=(lo[order], hi[order], ww[order]))


def target_energy(cfg: dict, base: Instance) -> float:
    """The configuration's target as an energy: given directly, or as a cut
    of the base instance (H = Σw − 2·cut). Relabelling keeps energies, so
    the value holds for every run's instance."""
    target = cfg["target"]
    if "energy" in target:
        return float(target["energy"])
    return float(base.total_weight - 2 * int(target["cut"]))
