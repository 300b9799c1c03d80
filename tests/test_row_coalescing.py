"""Reuse-aware coalesced row fetch (ROADMAP item 4).

The HBM-streamed kernel tier and the spin-sharded driver fetch each step's
*unique* selected coupling rows exactly once (``kernels.common.coalesce_rows``)
and broadcast the decoded row to every replica that picked it. The decoded
row is a function of the site alone, so coalescing can never move a
trajectory — these tests force known duplicate-selection structures
(all replicas on one row; two groups; all-distinct) across
{rsa, rwa, uniformized-rwa} and assert (a) bit-identical trajectories vs the
uncoalesced oracles and (b) the rows-fetched counter matches the forced
duplicate structure exactly.

Forcing mechanics: replicas are fully independent and deterministic given
(state, uniforms), so replicas given identical initial spins and identical
per-step uniform streams select identical sites forever — grouping replicas
this way forces duplicates in *every* mode, including the state-dependent
roulette modes where the site stream cannot be dictated directly. For rsa the
site uniform stream is the site (Eq. 22: j = floor(u·N)), so arbitrary
distinct patterns can be forced as well.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bitplane import encode_couplings
from repro.kernels import common, ref
from repro.kernels.sweep import mcmc_sweep

N = 256
R = 8
T = 64

MODES = [("rsa", False), ("rwa", False), ("rwa", True)]


def _coupling():
    g = np.random.default_rng(3)
    J = np.clip(np.rint(g.normal(size=(N, N)) * 1.5), -3, 3)
    J = np.triu(J, 1)
    J = J + J.T
    return J


def _grouped_state(J, groups, seed=0):
    """(u0, s0, e0) with replicas sharing a group sharing identical spins."""
    g = np.random.default_rng(seed)
    n_groups = max(groups) + 1
    s_g = np.where(g.random((n_groups, N)) < 0.5, 1.0, -1.0)
    s0 = s_g[np.asarray(groups)].astype(np.float32)
    u0 = (J @ s0.T).T.astype(np.float32)
    e0 = (-0.5 * np.einsum("rn,rn->r", u0, s0)).astype(np.float32)
    return jnp.asarray(u0), jnp.asarray(s0), jnp.asarray(e0)


def _grouped_uniforms(groups, seed=1):
    """(T, R, 4) uniforms identical within each replica group."""
    g = np.random.default_rng(seed)
    n_groups = max(groups) + 1
    u_g = g.random((T, n_groups, 4)).astype(np.float32)
    return jnp.asarray(u_g[:, np.asarray(groups), :])


def _run(J, u0, s0, e0, uniforms, *, mode, uniformized, coalesce=True,
         block_r=8):
    planes = encode_couplings(J, 2, align_words=128)
    temps = jnp.full((uniforms.shape[0], u0.shape[0]), 1.0, jnp.float32)
    return mcmc_sweep(planes, u0, s0, e0, uniforms, temps, mode=mode,
                      uniformized=uniformized, coupling="bitplane_hbm",
                      block_r=block_r, coalesce=coalesce, interpret=True)


def _assert_trajectory_equal(J, u0, s0, e0, uniforms, got, *, mode,
                             uniformized):
    temps = jnp.full((uniforms.shape[0], u0.shape[0]), 1.0, jnp.float32)
    want = ref.mcmc_sweep(jnp.asarray(J, jnp.float32), u0, s0, e0, uniforms,
                          temps, mode=mode, uniformized=uniformized)
    for name, a, b in zip(("u", "s", "e", "be", "bs", "nf"), want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# --------------------------------------------------- the fetch plan itself

def test_coalesce_rows_matches_python_oracle():
    g = np.random.default_rng(0)
    for _ in range(200):
        r = int(g.integers(1, 12))
        j = g.integers(0, 7, size=r).astype(np.int32)
        # The plan takes and returns (R, 1) columns (the kernel's layout).
        nu, usite, uo, fetched = jax.jit(common.coalesce_rows)(
            jnp.asarray(j)[:, None])
        assert usite.shape == uo.shape == fetched.shape == (r, 1)
        nu, usite, uo, fetched = (np.asarray(x).reshape(-1)
                                  for x in (nu, usite, uo, fetched))
        nu = int(nu[0])
        uniq = list(dict.fromkeys(j.tolist()))   # first-occurrence order
        assert nu == len(uniq)
        assert (usite[:nu] == np.array(uniq)).all()
        assert (usite[nu:] == j[0]).all()        # tail parked on a valid site
        for ri, site in enumerate(j):
            assert uo[ri] < nu and usite[uo[ri]] == site
        seen, want = set(), []
        for site in j.tolist():
            want.append(0 if site in seen else 1)
            seen.add(site)
        assert (fetched == np.array(want)).all()  # lowest-index attribution
        assert fetched.sum() == nu


# ------------------------------------------- streamed kernel, forced groups

@pytest.mark.parametrize("mode,uniformized", MODES)
def test_identical_replicas_fetch_one_row_per_step(mode, uniformized):
    """All R replicas share init + uniforms ⇒ they pick one row per step in
    every mode ⇒ the coalesced stream DMAs exactly T rows, not R·T — while
    the trajectory stays bit-identical to the uncoalesced jnp oracle."""
    J = _coupling()
    groups = [0] * R
    u0, s0, e0 = _grouped_state(J, groups)
    uniforms = _grouped_uniforms(groups)
    got = _run(J, u0, s0, e0, uniforms, mode=mode, uniformized=uniformized)
    _assert_trajectory_equal(J, u0, s0, e0, uniforms, got, mode=mode,
                             uniformized=uniformized)
    rf = np.asarray(got[6])
    assert rf.sum() == T
    assert (rf[1:] == 0).all()       # all fetches attributed to replica 0


@pytest.mark.parametrize("mode,uniformized", MODES)
def test_two_replica_groups_fetch_at_most_two_rows_per_step(mode,
                                                            uniformized):
    """Two groups of four ⇒ at most two unique rows per step. The exact
    expected traffic comes from a 2-replica run of one representative per
    group (replicas are independent, so representatives replay their group's
    trajectory exactly): both runs must count the same unique sites."""
    J = _coupling()
    groups = [0, 0, 0, 0, 1, 1, 1, 1]
    u0, s0, e0 = _grouped_state(J, groups)
    uniforms = _grouped_uniforms(groups)
    got = _run(J, u0, s0, e0, uniforms, mode=mode, uniformized=uniformized)
    _assert_trajectory_equal(J, u0, s0, e0, uniforms, got, mode=mode,
                             uniformized=uniformized)
    rf = np.asarray(got[6])
    assert T <= rf.sum() <= 2 * T
    assert (rf[[1, 2, 3, 5, 6, 7]] == 0).all()  # only group leaders fetch
    reps = jnp.asarray([0, 4])
    rep = _run(J, u0[reps], s0[reps], e0[reps],
               uniforms[:, np.asarray([0, 4]), :], mode=mode,
               uniformized=uniformized, block_r=2)
    assert rf.sum() == np.asarray(rep[6]).sum()


def test_all_distinct_rsa_sites_fetch_every_row():
    """rsa sites forced pairwise-distinct per step (the site uniform *is*
    the site) ⇒ zero reuse ⇒ the coalesced counter must equal the
    uncoalesced R·T exactly, and the trajectory still matches the oracle."""
    J = _coupling()
    u0, s0, e0 = _grouped_state(J, list(range(R)))
    g = np.random.default_rng(2)
    uniforms = g.random((T, R, 4)).astype(np.float32)
    for t in range(T):
        sites = g.choice(N, size=R, replace=False)
        uniforms[t, :, 0] = (sites + 0.5) / N
    uniforms = jnp.asarray(uniforms)
    got = _run(J, u0, s0, e0, uniforms, mode="rsa", uniformized=False)
    _assert_trajectory_equal(J, u0, s0, e0, uniforms, got, mode="rsa",
                             uniformized=False)
    rf = np.asarray(got[6])
    assert (rf == T).all()           # every replica fetched its own row
    assert rf.sum() == R * T


def test_all_one_row_forced_rsa_sites():
    """rsa with every replica forced onto the same (per-step random) site —
    the all-one-row case driven through the site stream rather than through
    replica identity, so replica *states* differ while selections collide."""
    J = _coupling()
    u0, s0, e0 = _grouped_state(J, list(range(R)))
    g = np.random.default_rng(4)
    uniforms = g.random((T, R, 4)).astype(np.float32)
    sites = g.integers(0, N, size=T)
    uniforms[:, :, 0] = ((sites + 0.5) / N)[:, None]
    uniforms = jnp.asarray(uniforms)
    got = _run(J, u0, s0, e0, uniforms, mode="rsa", uniformized=False)
    _assert_trajectory_equal(J, u0, s0, e0, uniforms, got, mode="rsa",
                             uniformized=False)
    assert np.asarray(got[6]).sum() == T


# ------------------------------------------ sharded driver, forced 2-device

def test_sharded_coalesced_matches_uncoalesced_oracle(forced_device_mesh):
    """On the forced 2-device mesh: ``sharded_sweep_fn(coalesce=True)`` is
    bit-identical to the uncoalesced psum-per-replica oracle in all three
    modes, the uncoalesced counter is exactly R·T, and forced duplicate
    groups (identical replicas / two groups) reduce the coalesced counter to
    the duplicate structure."""
    code = """
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import schedules
    from repro.core.bitplane import encode_couplings, BitPlanes
    from repro.core.solver import SolverConfig
    from repro.distributed.solver_sharded import sharded_sweep_fn

    N, R, T = 256, 8, 48
    g = np.random.default_rng(3)
    J = np.clip(np.rint(g.normal(size=(N, N)) * 1.5), -3, 3)
    J = np.triu(J, 1); J = J + J.T
    planes = encode_couplings(J, 2, align_words=128)
    mesh = Mesh(np.array(jax.devices()[:2]), ("spins",))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, "spins", None))
    planes = BitPlanes(pos=jax.device_put(planes.pos, sharding),
                       neg=jax.device_put(planes.neg, sharding),
                       num_spins=N)

    def state(groups, seed=0):
        gg = np.random.default_rng(seed)
        s_g = np.where(gg.random((max(groups) + 1, N)) < .5, 1., -1.)
        s0 = s_g[np.asarray(groups)].astype(np.float32)
        u0 = (J @ s0.T).T.astype(np.float32)
        e0 = (-0.5 * np.einsum('rn,rn->r', u0, s0)).astype(np.float32)
        return jnp.asarray(u0), jnp.asarray(s0), jnp.asarray(e0)

    def uniforms(groups, seed=1):
        gg = np.random.default_rng(seed)
        u_g = gg.random((T, max(groups) + 1, 4)).astype(np.float32)
        return jnp.asarray(u_g[:, np.asarray(groups), :])

    temps = jnp.full((T, R), 1.0, jnp.float32)
    for mode, uni in (("rsa", False), ("rwa", False), ("rwa", True)):
        cfg = SolverConfig(num_steps=T,
                           schedule=schedules.linear(3.0, 0.1, T),
                           mode=mode, uniformized=uni, num_replicas=R,
                           coupling_format="bitplane_sharded")
        fn_c = sharded_sweep_fn(cfg, mesh, N, coalesce=True)
        fn_u = sharded_sweep_fn(cfg, mesh, N, coalesce=False)
        for groups, max_unique in (([0] * R, 1),
                                   ([0, 0, 0, 0, 1, 1, 1, 1], 2),
                                   (list(range(R)), R)):
            u0, s0, e0 = state(groups)
            unif = uniforms(groups)
            got = fn_c(planes, u0, s0, e0, unif, temps)
            want = fn_u(planes, u0, s0, e0, unif, temps)
            for name, a, b in zip(("u", "s", "e", "be", "bs", "nf"),
                                  want, got):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=f"{mode} {name}")
            rf_c = np.asarray(got[6]); rf_u = np.asarray(want[6])
            assert rf_u.sum() == R * T, rf_u
            assert rf_c.sum() <= max_unique * T, (groups, rf_c)
            n_groups = max(groups) + 1
            assert rf_c.sum() >= min(n_groups, 1) * T
            leaders = sorted({groups.index(x) for x in set(groups)})
            others = [r for r in range(R) if r not in leaders]
            if others:
                assert (rf_c[np.asarray(others)] == 0).all()
    print("SHARDED COALESCE OK")
    """
    out = forced_device_mesh(code, n_devices=2)
    assert "SHARDED COALESCE OK" in out
