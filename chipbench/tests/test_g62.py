"""The g62 configuration on the CPU: the instance has G62's published
shape, resolves the VMEM bit-plane tier, the cell's sweep asks for less
scoped VMEM than a v5e core allows, and a small torus of the same family
solved through the benchmark's own solver agrees with the plain reference
under the cell's limits. Each check counts solves, not seconds."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, instances
from chipbench import run as bench
from repro.core.bitplane import BitPlanes
from repro.core.coupling import CouplingStore, resolve_format
from repro.kernels import common, sweep

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
CONFIG = json.loads((BENCH / "configs" / "g62.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "rwa_l8192.json").read_text())
LIMITS = json.loads((BENCH / "limits" / "g62.rwa.json").read_text())


def test_base_instance_has_g62s_shape_and_tier():
    inst = instances.base_instance(CONFIG)
    rows, cols, w = inst.edges
    assert inst.num_spins == 7000 and rows.size == 14000
    assert np.all(rows < cols)
    assert len(set(zip(rows.tolist(), cols.tolist()))) == 14000
    assert np.all(np.bincount(np.concatenate([rows, cols]),
                              minlength=7000) == 4)
    assert set(np.unique(w).tolist()) == {-1, 1}
    problem = bench.program_problem(inst)
    assert resolve_format("auto", problem.coupling_source,
                          inst.num_spins) == CONFIG["coupling_tier"]


def test_cell_sweep_vmem_request_is_below_the_cap(monkeypatch):
    n, r, t = 7000, TRAFFIC["replicas"], TRAFFIC["chunk_steps"]
    w = -(-n // 32)
    plane = jax.ShapeDtypeStruct((CONFIG["planes"], n, w), jnp.uint32)
    planes = BitPlanes(plane, plane, n)
    request = sweep.sweep_vmem_limit(planes, r, n, t, coupling="bitplane")
    # The planes alone are 2 × 7,000 rows × 256 padded lanes × 4 bytes.
    assert 2 * n * 256 * 4 < request < common.VMEM_LIMIT_CAP
    # ... and it is the request the kernel itself makes.
    asked = []
    params = sweep._compiler_params
    monkeypatch.setattr(sweep, "_compiler_params",
                        lambda limit: asked.append(limit) or params(limit))
    f32 = jnp.float32
    jax.eval_shape(
        lambda p, *a: sweep.mcmc_sweep(p, *a, mode="rwa", coupling="bitplane",
                                       interpret=True),
        planes, jax.ShapeDtypeStruct((r, n), f32),
        jax.ShapeDtypeStruct((r, n), f32), jax.ShapeDtypeStruct((r,), f32),
        jax.ShapeDtypeStruct((t, r, 4), f32), jax.ShapeDtypeStruct((t, r), f32))
    assert asked == [request]


def test_small_torus_agrees_with_the_reference_under_the_cells_limits():
    cfg = dict(CONFIG, rows=16, cols=24)
    traffic = dict(TRAFFIC, anneal_steps=512, reference_replicas=48)
    seed = 6262
    inst = instances.relabel(instances.base_instance(cfg),
                             np.random.default_rng(seed))
    problem = bench.program_problem(inst)
    store = CouplingStore.build(problem.coupling_source, "auto")
    assert store.fmt == "bitplane"
    solve = bench.ProgramSolver(
        problem, bench.solver_config(traffic, inst.num_spins), traffic, store)
    outs = [solve(bench.solve_seed(seed, i)) for i in range(6)]
    assert all(o.ok for o in outs)
    best = np.concatenate([np.asarray(o.best_energy) for o in outs])
    spins = np.concatenate([np.asarray(o.best_spins) for o in outs])
    assert check.energy_gap(inst, best, spins) == 0
    ref = check.reference_best(inst, traffic, bench.reference_key(seed))
    assert check.z_score(best, ref) <= LIMITS["quality_z"]["limit"]
    assert check.worst_z(best, ref) <= LIMITS["worst_z"]["limit"]
