"""Per-kernel allclose vs pure-jnp oracles across shape/dtype sweeps (interpret mode)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane, ising
from repro.core.schedules import geometric
from repro.core.solver import SolverConfig, solve
from repro.kernels import ops, ref
from repro.kernels.bitplane_field import bitplane_field_init as bp_kernel
from repro.kernels.local_field import local_field_init as lf_kernel
from repro.kernels.sweep import mcmc_sweep as sweep_kernel


def _sym(rng, n, dtype=np.float32, integer=False, scale=1.0):
    J = rng.normal(size=(n, n)) * scale
    if integer:
        J = np.rint(J)
    J = np.triu(J, 1)
    return (J + J.T).astype(dtype)


@pytest.mark.parametrize("r,n,br,bn,bk", [
    (8, 256, 8, 128, 128),
    pytest.param(16, 512, 8, 256, 512, marks=pytest.mark.slow),
    (4, 128, 4, 128, 64),
    pytest.param(32, 384, 16, 128, 128, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("sdtype,jdtype", [
    (jnp.int8, jnp.float32),
    (jnp.float32, jnp.float32),
    (jnp.int8, jnp.int8),
    (jnp.bfloat16, jnp.bfloat16),
])
def test_local_field_kernel_shapes_dtypes(r, n, br, bn, bk, sdtype, jdtype):
    rng = np.random.default_rng(r * n)
    s = np.where(rng.random((r, n)) < 0.5, 1, -1)
    J = _sym(rng, n, integer=(jdtype == jnp.int8), scale=3.0)
    h = rng.normal(size=n).astype(np.float32)
    s_j = jnp.asarray(s, sdtype)
    J_j = jnp.asarray(J, jdtype)
    h_j = jnp.asarray(h)
    got = lf_kernel(s_j, J_j, h_j, block_r=br, block_n=bn, block_k=bk, interpret=True)
    want = ref.local_field_init(s_j, J_j, h_j)
    tol = 2e-2 if jdtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol * n)


def test_local_field_kernel_rejects_bad_blocks():
    with pytest.raises(ValueError, match="divisible"):
        lf_kernel(jnp.ones((7, 128), jnp.int8), jnp.zeros((128, 128)),
                  jnp.zeros(128), block_r=4, interpret=True)


@pytest.mark.parametrize("n,b,r", [
    (64, 1, 4), (128, 2, 8),
    pytest.param(256, 8, 8, marks=pytest.mark.slow),
    pytest.param(96, 4, 16, marks=pytest.mark.slow),
])
def test_bitplane_kernel_matches_oracle_and_dense(n, b, r):
    rng = np.random.default_rng(n + b)
    limit = (1 << b) - 1
    J = rng.integers(-limit, limit + 1, size=(n, n))
    J = np.triu(J, 1)
    J = J + J.T
    planes = bitplane.encode_couplings(J, b)
    s = np.where(rng.random((r, n)) < 0.5, 1, -1).astype(np.int8)
    words = bitplane.pack_spins(jnp.asarray(s))
    got = bp_kernel(planes.pos, planes.neg, words, block_r=min(8, r),
                    block_n=min(128, n), interpret=True)
    want = ref.bitplane_field_init(planes.pos, planes.neg, words, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got), s.astype(np.float64) @ J.T, atol=1e-3)


def _sweep_inputs(rng, J, r, n, t):
    s0 = np.where(rng.random((r, n)) < 0.5, 1.0, -1.0).astype(np.float32)
    u0 = (s0 @ J.T).astype(np.float32)
    e0 = (-0.5 * np.einsum("ri,ri->r", s0, s0 @ J.T)).astype(np.float32)
    unif = rng.random((t, r, 4)).astype(np.float32)
    temps = np.broadcast_to(np.geomspace(3.0, 0.05, t).astype(np.float32)[:, None],
                            (t, r)).copy()
    return tuple(map(jnp.asarray, (J, u0, s0, e0, unif, temps)))


@pytest.mark.parametrize("mode", ["rsa", "rwa"])
@pytest.mark.parametrize("r,n,t,br", [
    (8, 128, 64, 8),
    pytest.param(16, 64, 128, 4, marks=pytest.mark.slow),
    pytest.param(4, 256, 32, 4, marks=pytest.mark.slow),
])
def test_sweep_kernel_matches_oracle(mode, r, n, t, br):
    rng = np.random.default_rng(r + n + t)
    args = _sweep_inputs(rng, _sym(rng, n), r, n, t)
    got = sweep_kernel(*args, mode=mode, block_r=br, interpret=True)
    want = ref.mcmc_sweep(*args, mode=mode)
    names = ("fields", "spins", "energy", "best_energy", "best_spins", "num_flips")
    for name, a, b in zip(names, got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-3, err_msg=f"{mode}:{name}")


def test_sweep_onehot_gather_matches_dynamic():
    """The opt-in MXU gather heuristic is a pure perf choice — same trajectory."""
    rng = np.random.default_rng(11)
    r, n, t = 8, 64, 32
    args = _sweep_inputs(rng, _sym(rng, n), r, n, t)
    got_dyn = sweep_kernel(*args, mode="rwa", block_r=4, interpret=True)
    got_oh = sweep_kernel(*args, mode="rwa", block_r=4, gather="onehot",
                          interpret=True)
    for name, a, b in zip(("fields", "spins", "energy", "best_energy",
                           "best_spins", "num_flips"), got_dyn, got_oh):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-4, err_msg=name)


def test_sweep_kernel_step_has_no_quadratic_contraction():
    """Acceptance gate for the O(N²)→O(N) gather fix: the default kernel's
    jaxpr must contain no dot_general at all (the one-hot × J contraction was
    the only matmul in the step loop); the opt-in MXU path must contain it."""
    rng = np.random.default_rng(0)
    r, n, t = 4, 128, 8
    args = _sweep_inputs(rng, _sym(rng, n), r, n, t)

    def trace(gather):
        return str(jax.make_jaxpr(
            lambda *a: sweep_kernel(*a, mode="rwa", block_r=4, gather=gather,
                                    interpret=True))(*args))

    assert "dot_general" not in trace("dynamic")
    assert "dot_general" in trace("onehot")


def test_sweep_bitplane_step_has_no_quadratic_contraction():
    """The bit-plane coupling path keeps the O(N)/step contract: its row
    decode is shift-and-mask bit expansion, so the default step jaxpr must
    contain no dot_general either."""
    rng = np.random.default_rng(0)
    r, n, t = 4, 128, 8
    J = _sym(rng, n, integer=True, scale=2.0)
    planes = bitplane.encode_couplings(np.clip(J, -7, 7), 3)
    _, u0, s0, e0, unif, temps = _sweep_inputs(rng, np.clip(J, -7, 7), r, n, t)
    trace = str(jax.make_jaxpr(
        lambda *a: sweep_kernel(planes, *a, mode="rwa", block_r=4,
                                coupling="bitplane", interpret=True))(
        u0, s0, e0, unif, temps))
    assert "dot_general" not in trace


def test_sweep_bitplane_hbm_step_has_no_quadratic_contraction():
    """The HBM-streamed coupling path keeps the O(N)/step contract too: rows
    arrive by DMA and decode through the same shift-and-mask expansion, so
    the step jaxpr must contain no dot_general — and must actually stream
    (the copy primitive appears; the planes never enter a blocked load)."""
    rng = np.random.default_rng(0)
    r, n, t = 4, 128, 8
    J = _sym(rng, n, integer=True, scale=2.0)
    planes = bitplane.encode_couplings(np.clip(J, -7, 7), 3)
    _, u0, s0, e0, unif, temps = _sweep_inputs(rng, np.clip(J, -7, 7), r, n, t)
    trace = str(jax.make_jaxpr(
        lambda *a: sweep_kernel(planes, *a, mode="rwa", block_r=4,
                                coupling="bitplane_hbm", interpret=True))(
        u0, s0, e0, unif, temps))
    assert "dot_general" not in trace
    assert "dma_start" in trace and "dma_wait" in trace


def test_bitplane_field_kernel_clamps_blocks():
    """Non-dividing block_r/block_n do not raise: R=12/block_r=8 takes all
    12 replicas in one block, N=96/block_n=64 runs a ragged edge block."""
    rng = np.random.default_rng(4)
    n, b, r = 96, 2, 12
    J = rng.integers(-3, 4, size=(n, n))
    J = np.triu(J, 1)
    J = J + J.T
    planes = bitplane.encode_couplings(J, b)
    s = np.where(rng.random((r, n)) < 0.5, 1, -1).astype(np.int8)
    words = bitplane.pack_spins(jnp.asarray(s))
    got = bp_kernel(planes.pos, planes.neg, words, block_r=8, block_n=64,
                    interpret=True)
    want = ref.bitplane_field_init(planes.pos, planes.neg, words, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sweep_handles_zero_temperature_degenerate():
    """T=0 at a local optimum ⇒ W=0 ⇒ fallback path must not flip or NaN."""
    n, r, t = 32, 4, 16
    J = np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)
    s0 = np.ones((r, n), np.float32)
    u0 = (s0 @ J.T).astype(np.float32)
    e0 = (-0.5 * np.einsum("ri,ri->r", s0, s0 @ J.T)).astype(np.float32)
    unif = np.random.default_rng(0).random((t, r, 4)).astype(np.float32)
    temps = np.zeros((t, r), np.float32)
    got = sweep_kernel(*map(jnp.asarray, (J, u0, s0, e0, unif, temps)),
                       mode="rwa", block_r=4, interpret=True)
    assert np.all(np.asarray(got[1]) == 1.0)
    assert np.all(np.isfinite(np.asarray(got[2])))
    assert np.all(np.asarray(got[5]) == 0)  # zero accepted flips tracked


def test_fused_anneal_solves_and_matches_reference_quality():
    """Optimized backend reaches the same ground state as the paper-faithful
    scan driver on a small exhaustible instance."""
    rng = np.random.default_rng(5)
    n = 12
    J = _sym(rng, n, integer=True, scale=2.0)
    prob = ising.IsingProblem.create(J=J)
    e_star, _, _ = ising.brute_force_ground_state(prob)
    cfg = SolverConfig(num_steps=1024, schedule=geometric(6.0, 0.02, 1024),
                       mode="rwa", num_replicas=8)
    fused = ops.fused_anneal(prob, 3, cfg, chunk_steps=256, interpret=True)
    assert float(jnp.min(fused.best_energy)) == pytest.approx(e_star, abs=1e-2)
    # Energy bookkeeping inside the kernel is exact:
    recomputed = np.asarray(ising.energy(prob, fused.best_spins))
    np.testing.assert_allclose(np.asarray(fused.best_energy), recomputed, atol=1e-2)
    # num_flips is tracked (RWA at T>0 flips nearly every step).
    assert np.all(np.asarray(fused.num_flips) > 0)
    baseline = solve(prob, 3, cfg)
    assert float(jnp.min(baseline.best_energy)) == pytest.approx(e_star, abs=1e-2)


def test_pwl_segment_select_matches_gather_exactly():
    """The lane-friendly PWL formulation (ROADMAP item): a branch-free
    compare-and-select sweep over the S segments must agree with the
    per-element two-gather evaluation *bitwise* — eagerly, under one jit
    (where the compiler could fuse differently), and across the RWA-style
    (T, 1) temperature broadcast — so switching formulations per backend can
    never split kernel/oracle parity."""
    from repro.core.pwl import pwl_table
    from repro.kernels import common

    tbl = pwl_table(64, 8.0)
    g = np.random.default_rng(7)
    # Dense z coverage: interior, exact knots, clamp tails, zero, +/-inf-ish.
    de = np.concatenate([g.normal(size=2048) * 30,
                         np.linspace(-8.5, 8.5, 257),
                         [0.0, 1e30, -1e30]]).astype(np.float32)
    de = jnp.asarray(np.broadcast_to(de, (4, de.size)))
    for t in (0.0, 0.25, 1.0, 7.0):
        a = common.flip_probability(de, t, tbl, pwl_select="gather")
        b = common.flip_probability(de, t, tbl, pwl_select="select")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    temps = jnp.asarray([0.0, 0.5, 1.0, 3.0])[:, None]
    np.testing.assert_array_equal(
        np.asarray(common.flip_probability(de, temps, tbl, pwl_select="gather")),
        np.asarray(common.flip_probability(de, temps, tbl, pwl_select="select")))
    fn = jax.jit(lambda d: (
        common.flip_probability(d, 0.7, tbl, pwl_select="gather"),
        common.flip_probability(d, 0.7, tbl, pwl_select="select")))
    a, b = fn(de)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="pwl_select"):
        common.flip_probability(de, 1.0, tbl, pwl_select="nope")
    # Default resolution is deterministic per backend (gather off-TPU), so
    # kernel and oracle always land on the same formulation.
    assert common.default_pwl_select() in ("gather", "select")


def test_sweep_trajectory_invariant_under_pwl_formulation(monkeypatch):
    """End-to-end guard: forcing the select formulation through the fused
    sweep leaves the whole trajectory bit-identical to the gather default."""
    from repro.kernels import common

    rng = np.random.default_rng(3)
    n = 48
    J = _sym(rng, n, integer=True, scale=2.0)
    prob = ising.IsingProblem.create(J=J)
    cfg = SolverConfig(num_steps=128, schedule=geometric(4.0, 0.05, 128),
                       mode="rwa", num_replicas=4, trace_every=32)
    base = ops.fused_anneal(prob, 9, cfg, interpret=True)
    monkeypatch.setattr(common, "default_pwl_select", lambda: "select")
    # Tracing re-resolves the formulation; with trace_every set the chunk
    # plan ignores chunk_steps, so bumping it forces a fresh trace (a cached
    # jit would silently reuse the gather path) without touching cadence.
    forced = ops.fused_anneal(prob, 9, cfg, interpret=True, chunk_steps=257)
    for name in ("best_energy", "best_spins", "final_energy", "num_flips",
                 "trace_energy"):
        np.testing.assert_array_equal(np.asarray(getattr(base, name)),
                                      np.asarray(getattr(forced, name)),
                                      err_msg=name)
