"""Ising problem definitions and Hamiltonian (paper §II-B).

The Ising Hamiltonian over spins ``s ∈ {-1,+1}^N`` is

    H(s) = -Σ_{i<j} J_ij s_i s_j - Σ_i h_i s_i
         = -1/2 sᵀ J s - hᵀ s          (J symmetric, zero diagonal)

The *local field* at spin i is ``u_i = h_i + Σ_{j≠i} J_ij s_j`` and the flip
energy change is ``ΔE_i = H(s^(i→-i)) - H(s) = 2 s_i u_i`` (paper Eq. 2).
"""
from __future__ import annotations

import dataclasses
import hashlib
from functools import cached_property, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

SPIN_DTYPE = jnp.int8


@dataclasses.dataclass(frozen=True, eq=False)
class EdgeList:
    """Canonical sparse (COO / edge-list) couplings: the dense-J-free problem
    representation.

    Real benchmark instances (Gset Max-Cut, the paper's own evaluation set)
    are O(nnz) edge lists, not O(N²) matrices — storing them as a dense J
    costs a 1 GiB host allocation at N=16384 before the first flip. An
    ``EdgeList`` holds each undirected edge exactly once in canonical form:
    ``rows[k] < cols[k]`` (int32), integer ``weights`` (int64), sorted
    lexicographically, duplicates coalesced. The equivalent dense matrix is
    ``J[i, j] = J[j, i] = w`` for every entry — :meth:`to_dense` materializes
    it (tests/small problems only; the solve path never does).

    Construction goes through :meth:`create`, which defines the ingestion
    semantics explicitly: entries are symmetric-canonicalized (``(i, j)`` and
    ``(j, i)`` name the same edge), duplicates **sum** (scipy-COO
    convention — so an edge listed in both directions doubles), exact-zero
    coalesced weights are dropped, and self-loops raise (the encoders only
    warn on a nonzero diagonal, but an edge list with self-loops is almost
    always an ingestion bug, so the sparse front door refuses).

    Host-side numpy by design: the edge arrays feed the O(nnz) bit-plane
    encoder (``core.bitplane.encode_edges``) outside jit, and ride
    ``IsingProblem``'s pytree *aux* data (content-hashed, so jitted drivers
    cache correctly across repeated solves of one instance).
    """

    rows: np.ndarray     # (nnz,) int32, rows[k] < cols[k]
    cols: np.ndarray     # (nnz,) int32
    weights: np.ndarray  # (nnz,) int64, never zero
    num_spins: int

    @classmethod
    def create(cls, rows, cols, weights, num_spins: int) -> "EdgeList":
        """Canonicalize a raw COO triple (see class docstring for the exact
        duplicate / symmetric-entry semantics)."""
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        w = np.asarray(weights)
        if rows.ndim != 1 or rows.shape != cols.shape or rows.shape != w.shape:
            raise ValueError(
                f"edge arrays must be equal-length 1-D, got rows {rows.shape} "
                f"cols {cols.shape} weights {w.shape}")
        n = int(num_spins)
        if n <= 0:
            raise ValueError(f"num_spins must be positive, got {num_spins}")
        ri = rows.astype(np.int64)
        ci = cols.astype(np.int64)
        if not (np.array_equal(ri, rows) and np.array_equal(ci, cols)):
            raise ValueError("edge endpoints must be integers")
        if rows.size and (ri.min() < 0 or ci.min() < 0
                          or ri.max() >= n or ci.max() >= n):
            raise ValueError(f"edge endpoints out of range for N={n}")
        if np.any(ri == ci):
            raise ValueError("self-loop edges (i == i) are not representable "
                             "couplings; drop the diagonal before ingestion")
        wf = w.astype(np.float64)
        bad = np.flatnonzero(~np.isfinite(wf))
        if bad.size:
            k = int(bad[0])
            raise ValueError(
                f"edge weights must be finite: edge #{k} "
                f"({int(ri[k])}, {int(ci[k])}) has weight {float(w[k])!r}"
                + (f" (+{bad.size - 1} more non-finite)" if bad.size > 1
                   else ""))
        wi = np.rint(wf).astype(np.int64)
        bad = np.flatnonzero(wi != wf)
        if bad.size:
            k = int(bad[0])
            raise ValueError(
                "edge-list ingestion requires integer weights (pre-scale "
                f"first): edge #{k} ({int(ri[k])}, {int(ci[k])}) has weight "
                f"{float(w[k])!r}")
        lo = np.minimum(ri, ci)
        hi = np.maximum(ri, ci)
        order = np.lexsort((hi, lo))
        lo, hi, wi = lo[order], hi[order], wi[order]
        if lo.size:
            first = np.ones(lo.size, bool)
            first[1:] = (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])
            starts = np.flatnonzero(first)
            wi = np.add.reduceat(wi, starts)
            lo, hi = lo[starts], hi[starts]
            keep = wi != 0
            lo, hi, wi = lo[keep], hi[keep], wi[keep]
        return cls(rows=lo.astype(np.int32), cols=hi.astype(np.int32),
                   weights=wi, num_spins=n)

    @classmethod
    def from_dense(cls, J) -> "EdgeList":
        """Upper-triangle nonzeros of a symmetric zero-diagonal matrix
        (tests / migration convenience — the point of the class is to never
        need this direction at scale)."""
        J = np.asarray(J)
        if J.ndim != 2 or J.shape[0] != J.shape[1]:
            raise ValueError(f"J must be square, got {J.shape}")
        if not np.array_equal(J, J.T):
            raise ValueError("J must be symmetric")
        if np.any(np.diag(J) != 0):
            raise ValueError("J must have zero diagonal")
        r, c = np.nonzero(np.triu(J, 1))
        return cls.create(r, c, J[r, c], J.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.rows.size)

    @property
    def max_abs_weight(self) -> int:
        return int(np.abs(self.weights).max(initial=0))

    @property
    def nbytes(self) -> int:
        return int(self.rows.nbytes + self.cols.nbytes + self.weights.nbytes)

    def negated(self) -> "EdgeList":
        """The edge list of −J (e.g. the Max-Cut w → J = −w mapping)."""
        return EdgeList(rows=self.rows, cols=self.cols,
                        weights=-self.weights, num_spins=self.num_spins)

    def to_dense(self, dtype=np.float32) -> np.ndarray:
        """Materialize the (N, N) matrix — O(N²); tests and tiny N only."""
        J = np.zeros((self.num_spins, self.num_spins), dtype)
        J[self.rows, self.cols] = self.weights
        J[self.cols, self.rows] = self.weights
        return J

    @cached_property
    def _digest(self) -> bytes:
        h = hashlib.sha256()
        h.update(str(self.num_spins).encode())
        for a in (self.rows, self.cols, self.weights):
            h.update(a.tobytes())
        return h.digest()

    # Content-based identity: EdgeList rides IsingProblem's pytree aux data,
    # which jit hashes/compares for cache lookups — numpy arrays are neither
    # hashable nor unambiguously comparable, so both are defined here.
    def __eq__(self, other) -> bool:
        return (isinstance(other, EdgeList)
                and self.num_spins == other.num_spins
                and self._digest == other._digest)

    def __hash__(self) -> int:
        return hash((self.num_spins, self._digest))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IsingProblem:
    """An Ising instance: symmetric couplings ``J`` (zero diag) and fields ``h``.

    ``J`` may be stored dense (all-to-all coupled machine, paper §III-A;
    sparse problem graphs simply have zero entries — no minor embedding is
    ever needed, the paper's first design consideration) **or** as a
    canonical :class:`EdgeList` (``couplings=None``): the dense-J-free
    representation for instances whose O(N²) matrix would not even fit on one
    host. Edge-list problems are served by the plane-backed solve paths
    (``backend="fused"`` / ``solve_sharded``); the dense-oracle helpers below
    (``energy``/``local_fields``/the reference backend) require the dense J
    and raise a routing error otherwise.
    """

    couplings: Optional[jax.Array]  # (N, N) float32, symmetric, zero diagonal
    fields: jax.Array  # (N,) float32
    offset: float = 0.0  # constant energy offset (e.g. from Max-Cut mapping)
    edges: Optional[EdgeList] = None  # dense-J-free couplings (host-side COO)

    def tree_flatten(self):
        # ``edges`` is host-side numpy and rides the aux data (content-hashed,
        # see EdgeList.__hash__) so jitted drivers cache across repeat solves.
        return (self.couplings, self.fields), (self.offset, self.edges)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(couplings=children[0], fields=children[1], offset=aux[0],
                   edges=aux[1] if len(aux) > 1 else None)

    @property
    def num_spins(self) -> int:
        if self.couplings is not None:
            return self.couplings.shape[-1]
        return self.edges.num_spins

    @property
    def immutable(self) -> bool:
        """Whether this object's content can never change: ``h`` and a dense
        ``J`` are ``jax.Array``s (what :meth:`create` and
        :meth:`create_sparse` build), or the couplings are an
        :class:`EdgeList`, whose digest is cached on first use. A problem
        built by hand over NumPy arrays can change in place."""
        return isinstance(self.fields, jax.Array) and (
            isinstance(self.couplings, jax.Array)
            or (self.couplings is None and self.edges is not None))

    @cached_property
    def _fingerprint(self) -> str:
        """:func:`content_fingerprint` of this object, computed once. Read
        only where :attr:`immutable` holds
        (``core.resilience.problem_fingerprint``)."""
        return content_fingerprint(self)

    @property
    def coupling_source(self):
        """What ``core.coupling.CouplingStore.build`` consumes: the edge list
        when the problem is dense-J-free, else the dense J."""
        return self.edges if self.couplings is None else self.couplings

    @staticmethod
    def validate(J: np.ndarray, h: np.ndarray) -> None:
        if J.ndim != 2 or J.shape[0] != J.shape[1]:
            raise ValueError(f"J must be square, got {J.shape}")
        if h.shape != (J.shape[0],):
            raise ValueError(f"h shape {h.shape} incompatible with J {J.shape}")
        # Finite checks first: a NaN anywhere would otherwise surface as the
        # misleading "J must be symmetric" (NaN != NaN under allclose).
        if not np.isfinite(J).all():
            i, j = np.argwhere(~np.isfinite(J))[0]
            raise ValueError(
                f"J must be finite: J[{i}, {j}] = {float(J[i, j])!r}")
        if not np.isfinite(h).all():
            (i,) = np.argwhere(~np.isfinite(h))[0]
            raise ValueError(f"h must be finite: h[{i}] = {float(h[i])!r}")
        if not np.allclose(J, J.T):
            raise ValueError("J must be symmetric")
        if not np.allclose(np.diag(J), 0.0):
            raise ValueError("J must have zero diagonal")

    @classmethod
    def create(cls, J, h=None, offset: float = 0.0, check: bool = True) -> "IsingProblem":
        J = np.asarray(J, dtype=np.float32)
        if h is None:
            h = np.zeros(J.shape[0], dtype=np.float32)
        h = np.asarray(h, dtype=np.float32)
        if check:
            cls.validate(J, h)
        return cls(couplings=jnp.asarray(J), fields=jnp.asarray(h), offset=float(offset))

    @classmethod
    def create_sparse(cls, edges: EdgeList, h=None,
                      offset: float = 0.0) -> "IsingProblem":
        """Dense-J-free instance from a canonical :class:`EdgeList` — the
        (N, N) f32 matrix is never materialized, here or anywhere downstream
        on the plane-backed solve path."""
        if not isinstance(edges, EdgeList):
            raise TypeError(f"create_sparse needs an EdgeList, got "
                            f"{type(edges).__name__} (EdgeList.create "
                            "canonicalizes raw COO arrays)")
        n = edges.num_spins
        if h is None:
            h = np.zeros(n, dtype=np.float32)
        h = np.asarray(h, dtype=np.float32)
        if h.shape != (n,):
            raise ValueError(f"h shape {h.shape} incompatible with N={n}")
        return cls(couplings=None, fields=jnp.asarray(h), offset=float(offset),
                   edges=edges)


def content_fingerprint(problem: IsingProblem) -> str:
    """sha256 hex digest of a problem's content: the dense J's shape and
    bytes (or the edge list's digest), then h's bytes and the offset as a
    float64. Hashes on every call; ``core.resilience.problem_fingerprint``
    is the entry point that reuses the value per immutable object."""
    h = hashlib.sha256()
    if problem.couplings is not None:
        J = np.ascontiguousarray(jax.device_get(problem.couplings))
        h.update(b"dense")
        h.update(repr(J.shape).encode())
        h.update(J.tobytes())
    else:
        h.update(b"edges")
        h.update(problem.edges._digest)
    fields = np.ascontiguousarray(jax.device_get(problem.fields))
    h.update(fields.tobytes())
    h.update(np.float64(problem.offset).tobytes())
    return h.hexdigest()


def _require_dense(problem: IsingProblem, what: str) -> jax.Array:
    if problem.couplings is None:
        raise ValueError(
            f"{what} needs the dense (N, N) couplings, but this problem is "
            "edge-list-backed (dense-J-free). Use the plane-backed paths "
            "(backend='fused', solve_sharded) or materialize explicitly via "
            "problem.edges.to_dense() for small N.")
    return problem.couplings


def energy(problem: IsingProblem, spins: jax.Array) -> jax.Array:
    """H(s); ``spins`` is (..., N) in {-1,+1}. Returns (...,)."""
    _require_dense(problem, "ising.energy")
    s = spins.astype(jnp.float32)
    Js = jnp.einsum("ij,...j->...i", problem.couplings, s)
    pair = -0.5 * jnp.einsum("...i,...i->...", s, Js)
    field = -jnp.einsum("i,...i->...", problem.fields, s)
    return pair + field


def local_fields(problem: IsingProblem, spins: jax.Array) -> jax.Array:
    """u_i = h_i + Σ_j J_ij s_j, computed from scratch (paper Eq. 11)."""
    _require_dense(problem, "ising.local_fields")
    s = spins.astype(jnp.float32)
    return jnp.einsum("ij,...j->...i", problem.couplings, s) + problem.fields


def energy_from_fields(u_j: jax.Array, spins: jax.Array,
                       fields: jax.Array) -> jax.Array:
    """H(s) from precomputed pairwise local fields ``u^J = J s``.

    ``pair = -0.5 Σ_i s_i u^J_i`` and ``field = -Σ_i h_i s_i`` — the *same
    einsum contractions* as :func:`energy`, evaluated on ``u^J`` instead of
    ``J s``. When ``u^J`` is bit-identical to the dense matmul (the
    Hamming-weight accumulation on an integer J is — exact integer sums below
    2²⁴ in f32), the result is bitwise equal to the dense-path energy for
    *any* h, which is what keeps dense-fed and plane-fed trajectories exactly
    equal. This is the single e₀ assembly every dense-J-free init routes
    through (fused init, the sharded per-device init, and the distributed
    driver's plane-fed chain re-init).
    """
    s = spins.astype(jnp.float32)
    pair = -0.5 * jnp.einsum("...i,...i->...", s, u_j.astype(jnp.float32))
    field = -jnp.einsum("i,...i->...", fields, s)
    return pair + field


def delta_energies(problem: IsingProblem, spins: jax.Array, u: Optional[jax.Array] = None) -> jax.Array:
    """ΔE_i = 2 s_i u_i for every candidate single-spin flip (paper Eq. 2)."""
    if u is None:
        u = local_fields(problem, spins)
    return 2.0 * spins.astype(jnp.float32) * u


def incremental_field_update(J: jax.Array, u: jax.Array, j: jax.Array, s_old_j: jax.Array) -> jax.Array:
    """u'_i = u_i - 2 J_ij s_j_old after flipping spin j (paper Eq. 12/17).

    Θ(N) instead of the Θ(N²) from-scratch recompute; J symmetric so the row
    J[j] equals the column J[:, j] the hardware streams (DESIGN.md §2).
    """
    row = jnp.take(J, j, axis=0)  # (N,)
    return u - 2.0 * row * s_old_j.astype(u.dtype)


def random_spins(key: jax.Array, shape) -> jax.Array:
    """Uniform random ±1 spin configuration."""
    bits = jax.random.bernoulli(key, 0.5, shape)
    return jnp.where(bits, 1, -1).astype(SPIN_DTYPE)


@partial(jax.jit, static_argnames=("n",))
def _brute_force_impl(J, h, n):
    idx = jnp.arange(2**n)
    bits = (idx[:, None] >> jnp.arange(n)[None, :]) & 1
    spins = (2 * bits - 1).astype(jnp.float32)
    Js = spins @ J
    e = -0.5 * jnp.einsum("ki,ki->k", spins, Js) - spins @ h
    k = jnp.argmin(e)
    return e[k], spins[k].astype(SPIN_DTYPE), e


def brute_force_ground_state(problem: IsingProblem):
    """Exhaustive ground-state search (tests only; N ≤ ~20)."""
    n = problem.num_spins
    if n > 24:
        raise ValueError("brute force limited to N<=24")
    e, s, all_e = _brute_force_impl(problem.couplings, problem.fields, n)
    return float(e) + problem.offset, np.asarray(s), np.asarray(all_e) + problem.offset
