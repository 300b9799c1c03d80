"""Plain reference of the annealer the benchmark drives.

A straightforward implementation of the same Markov chains as the program
(paper Alg. 1): R independent replicas from uniform random spins, a
geometric temperature schedule T(t) = T0·(T1/T0)^(t/(L−1)), the Glauber
flip probability σ(−ΔE/T) through the piecewise-linear table (uniform
knots on [−z_max, z_max], exact σ at the knots, clamped tails), and the
best energy seen by each replica. The update rule is the rejection-free
roulette wheel (``rwa``): each step flips one spin j per replica, drawn
with probability p_j / Σ_k p_k.

It imports nothing of the program and takes nothing the program made: the
instance comes from ``chipbench.instances``, the random numbers from its
own keys. It is a statistical twin, not a bit-exact one: it draws its own
random numbers, so it is compared with the program by the distribution of
best energies (``chipbench.check``). ``dtype`` sets the arithmetic of the
fields, energies and probabilities; ``jnp.bfloat16`` gives the control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .instances import Instance


def pwl_arrays(segments: int, zmax: float):
    """Knots, σ at the knots and the slopes between them, as float32."""
    knots = np.linspace(-zmax, zmax, segments + 1).astype(np.float32)
    values = (1.0 / (1.0 + np.exp(-knots.astype(np.float64)))).astype(
        np.float32)
    slopes = (np.diff(values) / np.diff(knots)).astype(np.float32)
    return knots, values, slopes


def temperatures(t0: float, t1: float, steps: int) -> np.ndarray:
    """The geometric schedule at every step, float32."""
    frac = np.minimum(np.arange(steps, dtype=np.float64) / max(steps - 1, 1),
                      1.0)
    return (t0 * (t1 / t0) ** frac).astype(np.float32)


def _flip_probability(de, temp, knots, values, slopes, dtype):
    """σ(−ΔE/T) through the table: segment k = ⌊(z − z₀)/h⌋, clamped."""
    z = (-de / temp).astype(dtype)
    segments = slopes.shape[0]
    lo, hi = knots[0], knots[-1]
    zc = jnp.clip(z, lo, hi)
    h = (hi - lo) / segments
    k = jnp.clip(jnp.floor((zc - lo) / h).astype(jnp.int32), 0, segments - 1)
    icpt = (values[:-1] - slopes * knots[:-1]).astype(dtype)
    slope = slopes.astype(dtype)
    a = jnp.zeros_like(zc)
    b = jnp.zeros_like(zc)
    for s in range(segments):   # selects, no gather
        a = jnp.where(k == s, icpt[s], a)
        b = jnp.where(k == s, slope[s], b)
    return a + b * zc


def _init(key, replicas: int, n: int, field_fn, dtype):
    s = jnp.where(jax.random.bernoulli(key, 0.5, (replicas, n)), 1, -1
                  ).astype(dtype)
    u = field_fn(s)
    e = (-0.5 * jnp.sum(s * u, axis=1)).astype(dtype)
    return s, u, e


def _dense_field(w):
    # u = J s with J = −w; ±1 products summed exactly in float32.
    return lambda s: -jnp.matmul(s.astype(jnp.float32), w,
                                 precision=jax.lax.Precision.HIGHEST
                                 ).astype(s.dtype)


def _sparse_field(nbr, wt):
    return lambda s: -jnp.sum(wt.astype(s.dtype)[None] * s[:, nbr], axis=2)


@partial(jax.jit, static_argnames=("steps", "replicas", "lane", "dtype",
                                   "keep_spins"))
def _rwa(key, temps, knots, values, slopes, w, nbr, wt, *, steps: int,
         replicas: int, lane: int, dtype, keep_spins: bool):
    dense = w is not None
    n = w.shape[0] if dense else nbr.shape[0]
    field = _dense_field(w) if dense else _sparse_field(nbr, wt)
    s, u, e = _init(jax.random.fold_in(key, 0), replicas, n, field, dtype)
    ids = jnp.arange(replicas)
    groups = n // lane

    def step(t, carry):
        s, u, e, be, bs = carry
        de = 2 * s * u
        p = _flip_probability(de, temps[t], knots, values, slopes, dtype)
        p = p.astype(jnp.float32)
        blocks = p.reshape(replicas, groups, lane)
        cb = jnp.cumsum(blocks.sum(axis=2), axis=1)
        radius = jax.random.uniform(jax.random.fold_in(key, t + 1),
                                    (replicas,)) * cb[:, -1]
        g = jnp.minimum(jnp.sum(cb <= radius[:, None], axis=1), groups - 1)
        below = jnp.where(g > 0, cb[ids, g - 1], 0.0)
        cl = jnp.cumsum(blocks[ids, g], axis=1)
        l = jnp.minimum(jnp.sum(cl <= (radius - below)[:, None], axis=1),
                        lane - 1)
        j = g * lane + l
        s_old = s[ids, j]
        e = e + de[ids, j]
        s = s.at[ids, j].set(-s_old)
        if dense:
            u = u + (2 * s_old[:, None] * w[j].astype(dtype))
        else:
            u = u.at[ids[:, None], nbr[j]].add(
                2 * s_old[:, None] * wt[j].astype(dtype))
        better = e < be
        be = jnp.where(better, e, be)
        if keep_spins:
            bs = jnp.where(better[:, None], s, bs)
        return s, u, e, be, bs

    bs0 = s if keep_spins else jnp.zeros((replicas, 1), dtype)
    _, _, _, be, bs = jax.lax.fori_loop(0, steps, step, (s, u, e, e, bs0))
    return be, bs


def roulette_lane(n: int) -> int:
    """Width of the roulette's second level: the largest divisor of N that
    is at most 128."""
    return next(k for k in range(min(128, n), 0, -1) if n % k == 0)


class Reference:
    """The reference annealer for one instance and one traffic mix, with
    its device operands built once."""

    def __init__(self, inst: Instance, traffic: dict, *, dtype=jnp.float32):
        self.inst = inst
        self.traffic = traffic
        self.dtype = dtype
        n = inst.num_spins
        self.steps = int(traffic["anneal_steps"])
        t0 = max(traffic["t0_over_sqrt_n"] * n ** 0.5, traffic["t0_min"])
        self.temps = jnp.asarray(temperatures(t0, traffic["t1"], self.steps))
        knots, values, slopes = pwl_arrays(traffic["pwl_segments"],
                                           traffic["pwl_zmax"])
        self.table = (jnp.asarray(knots), jnp.asarray(values),
                      jnp.asarray(slopes))
        if inst.weights is not None:
            self.w = jnp.asarray(inst.weights)
            self.nbr = self.wt = None
        else:
            nbr, wt = inst.neighbors()
            self.w = None
            self.nbr, self.wt = jnp.asarray(nbr, jnp.int32), jnp.asarray(wt)

    def anneal(self, key, replicas: int, *, keep_spins: bool = False):
        """(best energy (R,), best spins (R, N) or None) of one anneal."""
        be, bs = _rwa(key, self.temps, *self.table, self.w, self.nbr,
                      self.wt, steps=self.steps, replicas=replicas,
                      lane=roulette_lane(self.inst.num_spins),
                      dtype=self.dtype, keep_spins=keep_spins)
        return be, (bs if keep_spins else None)


def exact_energies(inst: Instance, spins: np.ndarray,
                   block: int = 512) -> np.ndarray:
    """H(s) = Σ_{i<j} w_ij s_i s_j of each row of ``spins``, exactly, as
    int64, ``block`` rows at a time. Dense w: (w s)_i sums at most N terms
    of ±1, an integer far below 2^24, so the float32 product is exact."""
    s = np.asarray(spins)
    out = np.empty(s.shape[0], np.int64)
    for lo in range(0, s.shape[0], block):
        b = s[lo:lo + block]
        if inst.weights is not None:
            ws = (b.astype(np.float32) @ inst.weights).astype(np.int64)
            out[lo:lo + block] = (ws * b).sum(axis=1) // 2
        else:
            rows, cols, w = inst.edges
            out[lo:lo + block] = (b[:, rows].astype(np.int32) * b[:, cols]
                                  * w.astype(np.int32)).sum(axis=1)
    return out
