"""Backend-parity suite: the fused Pallas sweep is the production engine and
must agree with its jnp oracle *exactly* (shared selection math ⇒ identical
trajectories), and the fused drivers (solve / tempering / distributed) must
return finite, monotone-nonincreasing best-energy traces with reference-
identical trace shape/dtype/cadence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane, ising, rng
from repro.core.pwl import pwl_table
from repro.core.schedules import geometric
from repro.core.solver import SolverConfig, solve
from repro.core.tempering import TemperingConfig, solve_tempering
from repro.kernels import ref
from repro.kernels.sweep import mcmc_sweep as sweep_kernel


def _sym(seed, n, integer=False, scale=1.0):
    g = np.random.default_rng(seed)
    J = g.normal(size=(n, n)) * scale
    if integer:
        J = np.rint(J)
    J = np.triu(J, 1)
    return (J + J.T).astype(np.float32)


def _inputs(seed, r, n, t, temps=None):
    g = np.random.default_rng(seed)
    J = _sym(seed + 1, n)
    s0 = np.where(g.random((r, n)) < 0.5, 1.0, -1.0).astype(np.float32)
    u0 = (s0 @ J.T).astype(np.float32)
    e0 = (-0.5 * np.einsum("ri,ri->r", s0, s0 @ J.T)).astype(np.float32)
    unif = g.random((t, r, 4)).astype(np.float32)
    if temps is None:
        temps = np.broadcast_to(
            np.geomspace(2.5, 0.05, t).astype(np.float32)[:, None], (t, r)).copy()
    return tuple(map(jnp.asarray, (J, u0, s0, e0, unif, temps)))


NAMES = ("fields", "spins", "energy", "best_energy", "best_spins", "num_flips")

VARIANTS = {
    "warm": dict(),                       # T > 0, exact sigmoid
    "zero_t": dict(zero_t=True),          # greedy limit
    "degenerate": dict(degenerate=True),  # W = 0 fallback / null transition
    "uniformized": dict(uniformized=True),
    "pwl": dict(pwl=True),
}


@pytest.mark.parametrize("mode", ["rsa", "rwa"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_fused_matches_oracle_exactly(mode, variant):
    # Trajectory-exactness is size-independent, so the default tier runs a
    # small instance; the full-size sweep lives in
    # test_fused_matches_oracle_exactly_large behind -m slow.
    opts = VARIANTS[variant]
    if mode == "rsa" and variant in ("degenerate", "uniformized"):
        pytest.skip("RWA-only variant")
    r, n, t = 8, 64, 48
    if opts.get("degenerate"):
        # All-ferromagnetic at the all-up state, T=0 ⇒ every ΔE > 0 ⇒ W = 0.
        J = np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)
        s0 = np.ones((r, n), np.float32)
        u0 = (s0 @ J.T).astype(np.float32)
        e0 = (-0.5 * np.einsum("ri,ri->r", s0, s0 @ J.T)).astype(np.float32)
        unif = np.random.default_rng(0).random((t, r, 4)).astype(np.float32)
        temps = np.zeros((t, r), np.float32)
        args = tuple(map(jnp.asarray, (J, u0, s0, e0, unif, temps)))
    elif opts.get("zero_t"):
        args = _inputs(7, r, n, t, temps=np.zeros((t, r), np.float32))
    else:
        args = _inputs(7, r, n, t)
    tbl = pwl_table() if opts.get("pwl") else None
    uniformized = bool(opts.get("uniformized")) and mode == "rwa"
    got = sweep_kernel(*args, tbl, mode=mode, uniformized=uniformized,
                       block_r=4, interpret=True)
    want = ref.mcmc_sweep(*args, tbl, mode=mode, uniformized=uniformized)
    for name, a, b in zip(NAMES, got, want):
        # Shared selection math ⇒ trajectory-exact agreement, not just close.
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"{mode}/{variant}:{name}")


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["rsa", "rwa"])
def test_fused_matches_oracle_exactly_large(mode):
    """Full-size parity sweep (N=512, multi-block R) — slow tier."""
    r, n, t = 16, 512, 64
    args = _inputs(7, r, n, t)
    got = sweep_kernel(*args, mode=mode, block_r=8, interpret=True)
    want = ref.mcmc_sweep(*args, mode=mode)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"{mode}:{name}")


BITPLANE_VARIANTS = {
    "warm": dict(),
    "zero_t": dict(zero_t=True),
    "uniformized": dict(uniformized=True),
    "pwl": dict(pwl=True),
}


@pytest.mark.parametrize("mode", ["rsa", "rwa"])
@pytest.mark.parametrize("variant", sorted(BITPLANE_VARIANTS))
def test_fused_bitplane_matches_oracle_exactly(mode, variant):
    """The packed bit-plane coupling path (kernel `coupling="bitplane"`) is
    trajectory-exact against the jnp oracle fed the same planes, and the
    planes-fed oracle is trajectory-exact against the dense-J oracle — so
    the packed store changes memory layout only, never the chain."""
    opts = BITPLANE_VARIANTS[variant]
    if mode == "rsa" and variant == "uniformized":
        pytest.skip("RWA-only variant")
    r, n, t, b = 8, 96, 48, 3
    g = np.random.default_rng(13)
    J = np.clip(np.rint(g.normal(size=(n, n)) * 2.0), -7, 7)
    J = np.triu(J, 1)
    J = J + J.T
    planes = bitplane.encode_couplings(J, b)
    s0 = np.where(g.random((r, n)) < 0.5, 1.0, -1.0).astype(np.float32)
    u0 = (s0 @ J.T).astype(np.float32)
    e0 = (-0.5 * np.einsum("ri,ri->r", s0, s0 @ J.T)).astype(np.float32)
    unif = g.random((t, r, 4)).astype(np.float32)
    temps = (np.zeros((t, r), np.float32) if opts.get("zero_t") else
             np.broadcast_to(np.geomspace(2.5, 0.05, t).astype(np.float32)[:, None],
                             (t, r)).copy())
    state = tuple(map(jnp.asarray, (u0, s0, e0, unif, temps)))
    tbl = pwl_table() if opts.get("pwl") else None
    uniformized = bool(opts.get("uniformized"))
    got = sweep_kernel(planes, *state, tbl, mode=mode, uniformized=uniformized,
                       coupling="bitplane", block_r=4, interpret=True)
    want = ref.mcmc_sweep(planes, *state, tbl, mode=mode,
                          uniformized=uniformized)
    want_dense = ref.mcmc_sweep(jnp.asarray(J, jnp.float32), *state, tbl,
                                mode=mode, uniformized=uniformized)
    for name, a, b_, c in zip(NAMES, got, want, want_dense):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b_, np.float32),
                                      err_msg=f"{mode}/{variant}:{name} kernel-vs-oracle")
        np.testing.assert_array_equal(np.asarray(b_, np.float32),
                                      np.asarray(c, np.float32),
                                      err_msg=f"{mode}/{variant}:{name} planes-vs-dense")


def _three_way_matrix(n, r, t, *, b=2, block_r=4, warm_chunks=2):
    """Dense-kernel vs VMEM-bitplane vs HBM-streamed-bitplane vs both oracles,
    exercising warm-start (state threaded through ``warm_chunks`` consecutive
    sweeps), the PWL LUT, and per-replica temperature ladders. Every pair must
    agree trajectory-exactly (assert_array_equal) — the coupling store is a
    memory-layout choice, never a chain change."""
    g = np.random.default_rng(97)
    J = np.clip(np.rint(g.normal(size=(n, n)) * 1.5), -(2 ** b - 1), 2 ** b - 1)
    J = np.triu(J, 1)
    J = (J + J.T).astype(np.float32)
    planes = bitplane.encode_couplings(J, b)
    planes_hbm = ops_mod().encode_for_sweep(J, b, fmt="bitplane_hbm")
    s0 = np.where(g.random((r, n)) < 0.5, 1.0, -1.0).astype(np.float32)
    u0 = (s0 @ J.T).astype(np.float32)
    e0 = (-0.5 * np.einsum("ri,ri->r", s0, s0 @ J.T)).astype(np.float32)
    # Per-replica geometric ladders, distinct per replica (tempering's shape).
    ladder = np.geomspace(4.0, 0.1, r).astype(np.float32)
    temps = np.broadcast_to(ladder[None, :], (t, r)).copy()
    tbl = pwl_table()

    backends = {
        "dense": dict(couplings=jnp.asarray(J), coupling="dense"),
        "bitplane": dict(couplings=planes, coupling="bitplane"),
        "bitplane_hbm": dict(couplings=planes_hbm, coupling="bitplane_hbm"),
    }
    state0 = tuple(map(jnp.asarray, (u0, s0, e0)))
    outs = {}
    for name, kw in backends.items():
        state = state0
        for c in range(warm_chunks):  # chunk c>0 warm-starts from chunk c-1
            unif = jnp.asarray(
                np.random.default_rng(1000 + c).random((t, r, 4)), jnp.float32)
            got = sweep_kernel(kw["couplings"], *state, unif,
                               jnp.asarray(temps), tbl, mode="rwa",
                               coupling=kw["coupling"], block_r=block_r,
                               interpret=True)
            state = got[:3]
        outs[name] = got
    oracle_state = state0
    for c in range(warm_chunks):
        unif = jnp.asarray(
            np.random.default_rng(1000 + c).random((t, r, 4)), jnp.float32)
        want = ref.mcmc_sweep(planes, *oracle_state, unif, jnp.asarray(temps),
                              tbl, mode="rwa")
        want_dense = ref.mcmc_sweep(jnp.asarray(J), *oracle_state, unif,
                                    jnp.asarray(temps), tbl, mode="rwa")
        oracle_state = want[:3]
    for name in NAMES:
        i = NAMES.index(name)
        base = np.asarray(outs["dense"][i], np.float32)
        for other in ("bitplane", "bitplane_hbm"):
            np.testing.assert_array_equal(
                base, np.asarray(outs[other][i], np.float32),
                err_msg=f"dense-vs-{other}:{name}")
        np.testing.assert_array_equal(base, np.asarray(want[i], np.float32),
                                      err_msg=f"kernel-vs-planes-oracle:{name}")
        np.testing.assert_array_equal(base, np.asarray(want_dense[i], np.float32),
                                      err_msg=f"kernel-vs-dense-oracle:{name}")


def ops_mod():
    from repro.kernels import ops
    return ops


def test_three_way_coupling_parity_small():
    """Default tier: the full dense/VMEM-plane/HBM-plane matrix at a shrunk
    size (trajectory-exactness is size-independent; the full past-the-wall
    size runs behind -m slow)."""
    _three_way_matrix(n=640, r=8, t=16)


@pytest.mark.slow
def test_three_way_coupling_parity_past_vmem_wall():
    """Full-size matrix at N just past BITPLANE_VMEM_MAX_N — the size class
    where, on real TPUs, only the HBM-streamed store fits on-chip memory
    (interpret mode has no VMEM ceiling, so all three paths still run and
    must agree exactly)."""
    n = ops_mod().BITPLANE_VMEM_MAX_N + 192  # 8192: past the wall, lane-tiled
    _three_way_matrix(n=n, r=2, t=6, block_r=2, warm_chunks=2)


def test_sweep_bitplane_rejects_mismatches():
    r, n, t = 4, 64, 8
    g = np.random.default_rng(3)
    J = np.rint(np.triu(g.normal(size=(n, n)), 1))
    J = J + J.T
    planes = bitplane.encode_couplings(J, 4)
    s0 = jnp.ones((r, n), jnp.float32)
    u0 = jnp.asarray(s0 @ jnp.asarray(J, jnp.float32).T)
    e0 = jnp.zeros((r,), jnp.float32)
    unif = jnp.zeros((t, r, 4), jnp.float32)
    temps = jnp.ones((t, r), jnp.float32)
    with pytest.raises(ValueError, match="onehot"):
        sweep_kernel(planes, u0, s0, e0, unif, temps, coupling="bitplane",
                     gather="onehot", interpret=True)
    with pytest.raises(TypeError, match="BitPlanes"):
        sweep_kernel(jnp.asarray(J, jnp.float32), u0, s0, e0, unif, temps,
                     coupling="bitplane", interpret=True)
    with pytest.raises(ValueError, match="coupling"):
        sweep_kernel(planes, u0, s0, e0, unif, temps, coupling="packed",
                     interpret=True)
    # The HBM-streamed tier enforces the same contracts as the VMEM tier.
    with pytest.raises(TypeError, match="BitPlanes"):
        sweep_kernel(jnp.asarray(J, jnp.float32), u0, s0, e0, unif, temps,
                     coupling="bitplane_hbm", interpret=True)
    with pytest.raises(ValueError, match="onehot"):
        sweep_kernel(planes, u0, s0, e0, unif, temps, coupling="bitplane_hbm",
                     gather="onehot", interpret=True)


def test_sweep_block_r_clamps_to_divisor():
    """R=12 with block_r=8 has no legal 8-multiple block, so the kernel
    takes all 12 replicas in one block instead of raising — and the clamped
    run stays trajectory-exact vs the oracle."""
    r, n, t = 12, 64, 16
    args = _inputs(21, r, n, t)
    got = sweep_kernel(*args, mode="rwa", block_r=8, interpret=True)
    want = ref.mcmc_sweep(*args, mode="rwa")
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


def test_site_index_derivation_is_canonical():
    """Kernel/oracle site picks route through core.rng's canonical helper."""
    keys = [jax.random.fold_in(jax.random.key(0), i) for i in range(64)]
    for n in (7, 96, 4096):
        via_index = np.array([int(rng.uniform_index(k, n)) for k in keys])
        via_uniform = np.array(
            [int(rng.index_from_uniform(rng.uniform01(k), n)) for k in keys])
        np.testing.assert_array_equal(via_index, via_uniform)
        assert via_index.min() >= 0 and via_index.max() < n


def test_sweep_salt_is_disjoint():
    """The fused chunk stream must not collide with any sequential-engine salt."""
    assert rng.Salt.SWEEP not in {rng.Salt.SITE, rng.Salt.ACCEPT,
                                  rng.Salt.ROULETTE, rng.Salt.UNIFORMIZE,
                                  rng.Salt.INIT, rng.Salt.REPLICA,
                                  rng.Salt.PROBLEM}
    base = jax.random.key(1)
    a = rng.uniform01(rng.stream(base, rng.Salt.SWEEP, 0), (8,))
    b = rng.uniform01(rng.stream(base, rng.Salt.ROULETTE, 0), (8,))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode,uniformized,use_pwl", [
    ("rsa", False, False), ("rwa", False, True), ("rwa", True, False),
])
def test_solve_fused_backend_quality_and_trace(mode, uniformized, use_pwl):
    prob = ising.IsingProblem.create(J=_sym(5, 12, integer=True, scale=2.0))
    e_star, _, _ = ising.brute_force_ground_state(prob)
    cfg = SolverConfig(num_steps=1024, schedule=geometric(6.0, 0.02, 1024),
                       mode=mode, uniformized=uniformized, use_pwl=use_pwl,
                       num_replicas=8, trace_every=128)
    fused = solve(prob, 3, cfg, backend="fused")
    reference = solve(prob, 3, cfg, backend="reference")
    # Identical trace contract across backends (shape, dtype, cadence).
    assert fused.trace_energy.shape == reference.trace_energy.shape == (8, 8)
    assert fused.trace_energy.dtype == reference.trace_energy.dtype == jnp.float32
    trace = np.asarray(fused.trace_energy)
    assert np.isfinite(trace).all()
    assert (np.diff(trace, axis=0) <= 1e-6).all(), "best-energy trace must be monotone"
    assert float(jnp.min(fused.best_energy)) == pytest.approx(e_star, abs=1e-2)
    # Bookkeeping: reported energies match the spins they claim.
    recomputed = np.asarray(ising.energy(prob, fused.best_spins))
    np.testing.assert_allclose(np.asarray(fused.best_energy), recomputed, atol=1e-2)
    assert np.all(np.asarray(fused.num_flips) >= 0)


def test_solve_fused_trace_disabled_matches_reference_contract():
    prob = ising.IsingProblem.create(J=_sym(6, 10, integer=True, scale=2.0))
    cfg = SolverConfig(num_steps=128, schedule=geometric(4.0, 0.05, 128),
                       mode="rwa", num_replicas=4, trace_every=0)
    fused = solve(prob, 0, cfg, backend="fused")
    reference = solve(prob, 0, cfg, backend="reference")
    assert fused.trace_energy.shape == reference.trace_energy.shape == (0, 4)
    assert fused.trace_energy.dtype == reference.trace_energy.dtype == jnp.float32


@pytest.mark.parametrize("num_steps", [100, 360])
def test_solve_fused_runs_exactly_num_steps(num_steps):
    """Untraced fused runs must not round num_steps to a chunk multiple —
    RWA at T>0 is rejection-free, so num_flips counts executed steps."""
    prob = ising.IsingProblem.create(J=_sym(2, 10, integer=True, scale=2.0))
    cfg = SolverConfig(num_steps=num_steps,
                       schedule=geometric(6.0, 0.5, num_steps),
                       mode="rwa", num_replicas=4, trace_every=0)
    fused = solve(prob, 0, cfg, backend="fused")
    np.testing.assert_array_equal(np.asarray(fused.num_flips),
                                  np.full(4, num_steps))


def test_solve_rejects_unknown_backend():
    prob = ising.IsingProblem.create(J=_sym(6, 8))
    cfg = SolverConfig(num_steps=8, schedule=geometric(1.0, 0.1, 8))
    with pytest.raises(ValueError, match="backend"):
        solve(prob, 0, cfg, backend="mystery")


def test_tempering_fused_backend():
    prob = ising.IsingProblem.create(J=_sym(1, 12, integer=True, scale=2.0))
    e_star, _, _ = ising.brute_force_ground_state(prob)
    cfg = TemperingConfig(num_steps=1600, t_min=0.05, t_max=8.0,
                          num_replicas=8, swap_every=10, backend="fused")
    res = solve_tempering(prob, 0, cfg)
    assert float(jnp.min(res.best_energy)) == pytest.approx(e_star, abs=1e-2)
    recomputed = np.asarray(ising.energy(prob, res.best_spins))
    np.testing.assert_allclose(np.asarray(res.best_energy), recomputed, atol=1e-2)
    assert 0.0 <= float(res.swap_acceptance) <= 1.0
    assert np.all(np.asarray(res.num_flips) > 0)
    assert np.isfinite(np.asarray(res.final_energy)).all()


def test_distributed_fused_backend_single_device():
    """Fused chunked sweeps inside shard_map (single-device mesh in-process;
    the multi-device path runs in test_distributed's subprocesses)."""
    from jax.sharding import Mesh
    from repro.distributed.solver_dist import DistSolverConfig, solve_distributed

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    prob = ising.IsingProblem.create(J=_sym(9, 32, integer=True, scale=1.5))
    base = SolverConfig(num_steps=256, schedule=geometric(6.0, 0.05, 256),
                        mode="rwa", num_replicas=1, trace_every=64)
    cfg = DistSolverConfig(base=base, replicas_per_device=4,
                           exchange_every=4, backend="fused")
    r1 = solve_distributed(prob, 7, cfg, mesh)
    r2 = solve_distributed(prob, 7, cfg, mesh)
    np.testing.assert_array_equal(np.asarray(r1.best_energy),
                                  np.asarray(r2.best_energy))
    recomputed = np.asarray(ising.energy(prob, r1.best_spins))
    np.testing.assert_allclose(np.asarray(r1.best_energy), recomputed, atol=1e-2)
    trace = np.asarray(r1.trace_energy)
    assert trace.shape == (4, 4) and np.isfinite(trace).all()
    assert (np.diff(trace, axis=0) <= 1e-6).all()


def test_solve_fused_bitplane_format_matches_dense_exactly():
    """`coupling_format="bitplane"`/`"bitplane_hbm"` change the J store, not
    the chain: the fused driver returns bit-identical results for an
    integer-J problem (plane-decoded rows and the popcount u₀ init are exact
    in f32, and the streamed rows decode through the same expansion)."""
    prob = ising.IsingProblem.create(J=_sym(5, 12, integer=True, scale=2.0))
    cfg = SolverConfig(num_steps=1024, schedule=geometric(6.0, 0.02, 1024),
                       mode="rwa", num_replicas=8, trace_every=128)
    dense = solve(prob, 3, dataclasses.replace(cfg, coupling_format="dense"),
                  backend="fused")
    for fmt in ("bitplane", "bitplane_hbm"):
        packed = solve(prob, 3, dataclasses.replace(cfg, coupling_format=fmt),
                       backend="fused")
        for name in ("best_energy", "best_spins", "final_energy", "num_flips",
                     "trace_energy"):
            np.testing.assert_array_equal(np.asarray(getattr(dense, name)),
                                          np.asarray(getattr(packed, name)),
                                          err_msg=f"{fmt}:{name}")


def test_coupling_format_auto_resolution():
    """"auto" packs only past the f32 VMEM crossover and only for integral J;
    explicit "bitplane" under a jax trace (no host J to encode) raises."""
    from repro.kernels import ops

    J_int = np.asarray(_sym(8, 16, integer=True, scale=2.0))
    J_frac = J_int + np.triu(np.full((16, 16), 0.5), 1) + np.tril(np.full((16, 16), 0.5), -1)
    assert ops.resolve_coupling_format("auto", J_int, 16) == "dense"
    assert ops.resolve_coupling_format(
        "auto", J_int, ops.DENSE_COUPLING_MAX_N + 1) == "bitplane"
    assert ops.resolve_coupling_format(
        "auto", J_frac, ops.DENSE_COUPLING_MAX_N + 1) == "dense"
    # Past the packed-VMEM wall "auto" escalates to the HBM-streamed tier.
    assert ops.resolve_coupling_format(
        "auto", J_int, ops.BITPLANE_VMEM_MAX_N) == "bitplane"
    assert ops.resolve_coupling_format(
        "auto", J_int, ops.BITPLANE_VMEM_MAX_N + 1) == "bitplane_hbm"
    assert ops.resolve_coupling_format("bitplane_hbm", J_int, 64) == "bitplane_hbm"
    # Integral but huge magnitudes: 2·B ≥ 32 bits/coupler would not shrink J,
    # so "auto" must stay dense rather than pack a bigger-than-f32 store.
    assert ops.resolve_coupling_format(
        "auto", J_int * np.float32(2.0 ** 15),
        ops.DENSE_COUPLING_MAX_N + 1) == "dense"
    assert ops.resolve_coupling_format("dense", J_int, 4096) == "dense"
    with pytest.raises(ValueError, match="coupling"):
        ops.resolve_coupling_format("packed", J_int, 16)

    def traced(J):
        return ops.resolve_coupling_format("bitplane", J, 4096)

    with pytest.raises(ValueError, match="concrete"):
        jax.make_jaxpr(traced)(jnp.asarray(J_int))
    # "auto" under trace quietly stays dense (never inspects values).
    assert jax.make_jaxpr(
        lambda J: jnp.zeros(()) if ops.resolve_coupling_format(
            "auto", J, 4096) == "dense" else jnp.ones(()))(
        jnp.asarray(J_int)) is not None


def test_coupling_store_build_is_the_single_dispatch_point():
    """The CouplingStore subsystem (core.coupling): build() resolves + packs
    in one call, the registry spans all four tiers, stores are pytrees with
    static formats, and per-shard byte accounting divides the plane store."""
    from repro.core import coupling as cs

    J = _sym(8, 64, integer=True, scale=2.0)
    assert cs.COUPLING_FORMATS == ("auto", "dense", "bitplane",
                                   "bitplane_hbm", "bitplane_sharded",
                                   "bitplane_sharded_2d")
    assert cs.KERNEL_COUPLING_MODES == ("dense", "bitplane", "bitplane_hbm")
    dense = cs.CouplingStore.build(jnp.asarray(J), "dense")
    assert dense.fmt == "dense" and dense.planes is None
    assert dense.kernel_operand is dense.dense
    assert dense.nbytes == 64 * 64 * 4
    packed = cs.CouplingStore.build(J, "bitplane")
    assert packed.fmt == "bitplane" and packed.dense is None
    assert packed.kernel_operand is packed.planes
    # HBM/sharded tiers tile-pad the word axis per the registry.
    for fmt in ("bitplane_hbm", "bitplane_sharded"):
        store = cs.CouplingStore.build(J, fmt)
        assert store.planes.num_words % cs.STREAM_ALIGN_WORDS == 0
        assert store.plane_bytes_per_shard(2) * 2 == store.planes.nbytes
    # Stores are pytrees whose format is aux data (static under jit).
    leaves, treedef = jax.tree_util.tree_flatten(packed)
    again = jax.tree_util.tree_unflatten(treedef, leaves)
    assert again.fmt == "bitplane" and again.num_spins == 64
    # require() is the driver-side registry check with a routing hint.
    with pytest.raises(ValueError, match="solve_sharded"):
        cs.CouplingStore.build(J, "bitplane_sharded").require(
            cs.KERNEL_COUPLING_MODES, "fused_anneal")


def test_sharded_format_is_explicit_only_and_rejected_by_kernel_drivers():
    """"auto" never resolves to the sharded tier (it needs a mesh), an
    explicit sharded format under a trace raises the concrete-J error, and
    each single-device driver rejects the sharded store with a pointer at
    the spin-parallel driver."""
    from repro.kernels import ops

    J_int = np.asarray(_sym(8, 16, integer=True, scale=2.0))
    assert ops.resolve_coupling_format(
        "bitplane_sharded", J_int, 16) == "bitplane_sharded"
    huge = ops.BITPLANE_VMEM_MAX_N * 4
    assert ops.resolve_coupling_format("auto", J_int, huge) == "bitplane_hbm"

    def traced(J):
        return ops.resolve_coupling_format("bitplane_sharded", J, 4096)

    with pytest.raises(ValueError, match="concrete"):
        jax.make_jaxpr(traced)(jnp.asarray(J_int))

    prob = ising.IsingProblem.create(J=_sym(5, 12, integer=True, scale=2.0))
    cfg = SolverConfig(num_steps=8, schedule=geometric(1.0, 0.1, 8),
                       num_replicas=2, coupling_format="bitplane_sharded")
    with pytest.raises(ValueError, match="solve_sharded"):
        solve(prob, 0, cfg, backend="fused")
    tcfg = TemperingConfig(num_steps=8, t_min=0.1, t_max=1.0, num_replicas=2,
                           backend="fused", coupling_format="bitplane_sharded")
    with pytest.raises(ValueError, match="solve_sharded"):
        solve_tempering(prob, 0, tcfg)


def test_distributed_fused_planes_do_not_ship_dense_couplings():
    """Satellite contract: with a plane-backed store the dense J never enters
    shard_map (the runner closes over the encoded planes; chain inits run
    off the planes too) — and the plane-fed chain init is value-identical to
    the dense one."""
    from jax.sharding import Mesh
    from repro.core.coupling import CouplingStore
    from repro.core import mcmc
    from repro.distributed.solver_dist import (_init_chain_from_planes,
                                               DistSolverConfig,
                                               solve_distributed)

    prob = ising.IsingProblem.create(J=_sym(9, 32, integer=True, scale=1.5),
                                     h=np.linspace(-1, 1, 32).astype(np.float32))
    store = CouplingStore.build(prob.couplings, "bitplane")
    spins = np.where(np.random.default_rng(0).random(32) < 0.5, 1, -1)
    spins = jnp.asarray(spins, jnp.int8)
    via_planes = _init_chain_from_planes(store.planes, prob.fields, spins)
    via_dense = mcmc.init_chain(prob, spins)
    for name in mcmc.ChainState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(via_planes, name)),
                                      np.asarray(getattr(via_dense, name)),
                                      err_msg=name)
    # End-to-end: the bitplane-format distributed solve (which no longer
    # receives J as an operand) still matches its dense-format twin exactly.
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    base = SolverConfig(num_steps=128, schedule=geometric(6.0, 0.05, 128),
                        mode="rwa", num_replicas=1, trace_every=32)
    results = {}
    for fmt in ("dense", "bitplane"):
        cfg = DistSolverConfig(
            base=dataclasses.replace(base, coupling_format=fmt),
            replicas_per_device=4, exchange_every=2, backend="fused")
        results[fmt] = solve_distributed(prob, 7, cfg, mesh)
    np.testing.assert_array_equal(np.asarray(results["dense"].best_energy),
                                  np.asarray(results["bitplane"].best_energy))
    np.testing.assert_array_equal(np.asarray(results["dense"].trace_energy),
                                  np.asarray(results["bitplane"].trace_energy))


def test_fused_anneal_accepts_prepacked_planes_and_rejects_onehot():
    """Callers may pass ready BitPlanes as `coupling` (skips the O(N²·B)
    re-encode — the benchmark path), and an explicit onehot gather on the
    packed store surfaces the kernel's dense-only error instead of being
    silently overridden."""
    from repro.kernels import ops

    prob = ising.IsingProblem.create(J=_sym(5, 12, integer=True, scale=2.0))
    cfg = SolverConfig(num_steps=256, schedule=geometric(6.0, 0.05, 256),
                       mode="rwa", num_replicas=4)
    planes = ops.encode_for_sweep(prob.couplings)
    via_planes = ops.fused_anneal(prob, 3, cfg, coupling=planes)
    via_format = ops.fused_anneal(prob, 3, cfg, coupling="bitplane")
    np.testing.assert_array_equal(np.asarray(via_planes.best_energy),
                                  np.asarray(via_format.best_energy))
    with pytest.raises(ValueError, match="onehot"):
        ops.fused_anneal(prob, 3, cfg, coupling="bitplane", gather="onehot")


def test_tempering_fused_bitplane_matches_dense():
    prob = ising.IsingProblem.create(J=_sym(1, 12, integer=True, scale=2.0))
    base = dict(num_steps=1200, t_min=0.05, t_max=8.0, num_replicas=8,
                swap_every=10, backend="fused")
    dense = solve_tempering(prob, 0, TemperingConfig(**base, coupling_format="dense"))
    packed = solve_tempering(prob, 0, TemperingConfig(**base, coupling_format="bitplane"))
    np.testing.assert_array_equal(np.asarray(dense.best_energy),
                                  np.asarray(packed.best_energy))
    np.testing.assert_array_equal(np.asarray(dense.num_flips),
                                  np.asarray(packed.num_flips))


def test_distributed_fused_bitplane_matches_dense():
    from jax.sharding import Mesh
    from repro.distributed.solver_dist import DistSolverConfig, solve_distributed

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    prob = ising.IsingProblem.create(J=_sym(9, 32, integer=True, scale=1.5))
    base = SolverConfig(num_steps=256, schedule=geometric(6.0, 0.05, 256),
                        mode="rwa", num_replicas=1, trace_every=64)
    results = {}
    for fmt in ("dense", "bitplane"):
        cfg = DistSolverConfig(
            base=dataclasses.replace(base, coupling_format=fmt),
            replicas_per_device=4, exchange_every=4, backend="fused")
        results[fmt] = solve_distributed(prob, 7, cfg, mesh)
    np.testing.assert_array_equal(np.asarray(results["dense"].best_energy),
                                  np.asarray(results["bitplane"].best_energy))
    np.testing.assert_array_equal(np.asarray(results["dense"].trace_energy),
                                  np.asarray(results["bitplane"].trace_energy))
