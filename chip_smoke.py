#!/usr/bin/env python3
"""Smoke test of the production solver path on TPU, end to end.

    python3 chip_smoke.py              # one chip: phases (a)-(c)
    python3 chip_smoke.py --chips 4    # four chips: phase (d) only

Everything runs in this one process (a chip belongs to one process at a
time), through the entry points a user calls: ``run_resilient`` over the
backend registry, as ``python -m repro.launch.solve`` does.

(a) K2000 — the paper's complete ±1 graph at N=2000 (``configs/snowball.
    K2000``), dense tier, R=8, 20,480 steps in RWA and in RSA. Fails on a
    tier downgrade, or unless the carried best energy equals the energy
    recomputed from the best spins. The best cut is printed beside the
    33,000 target; the cut informs, it does not gate.
(b) One 256-step chunk of the compiled sweep kernel against the jnp oracle
    ``kernels.ref.mcmc_sweep`` on identical inputs, both modes: K2000 on
    the dense tier, and G62 (the 70×100 torus of ``chipbench/configs/
    g62.json``) on the VMEM bit-plane tier, whose chunk is also run on the
    HBM-streamed tier at the same seed. "exact" / "identical", or the
    first step at which the two differ (reported, not gated — the check in
    (a) is the gate).
(c) The other single-device tiers: a G61-sized sparse ±1 Erdős–Rényi graph
    (N=7000, |E|≈17148, VMEM bit-planes), a sparse N=16384 graph (64 MiB of
    planes streamed from HBM), and colored block-Gibbs sweeps on an 84×84
    torus (N=7056). Same gates as (a).
(d) ``--chips 4`` only: the N=16384 graph spin-sharded over a 1-D mesh of
    4 chips and a 2×2 (groups, rows) mesh, against the one-chip HBM run.
    Best energies must be identical (the tiers' parity contract); each
    shard's device is printed.

Every phase fails the run unless JAX's first device is a TPU and the fused
runners resolved ``interpret=False``. Each phase prints one line; the last
line of standard output is the JSON verdict.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
REPLICAS = 8
K2000_STEPS = 20_480
TIER_STEPS = 4_096
COLORED_STEPS = 1_024
ORACLE_STEPS = 256


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_compiled(interpret: bool, what: str) -> None:
    """The fused paths resolve ``interpret`` from the backend; on a TPU it
    must be False (the Mosaic kernel, not the interpreter)."""
    check(interpret is False, f"{what} resolved interpret={interpret}")


def edge_energy(problem, spins) -> np.ndarray:
    """H(s) + offset of an edge-list problem, in exact integer arithmetic."""
    e = problem.edges
    s = np.asarray(spins, np.int64)
    pair = -(s[:, e.rows] * s[:, e.cols] * e.weights).sum(axis=1)
    field = -(s * np.asarray(problem.fields, np.float64)).sum(axis=1)
    return pair + field + problem.offset


def solve_checked(problem, config, backend: str, *, energy_fn, mesh=None):
    """One ``run_resilient`` solve; fails on a downgrade, an early stop, or a
    best energy that differs from the one recomputed from the best spins.
    Returns (result, wall seconds including compilation)."""
    import jax
    from repro.core.backend import get_backend
    from repro.core.resilience import run_resilient

    if backend in ("fused", "colored"):
        runner = get_backend(backend).runner(problem, SEED, config)
        require_compiled(runner.interpret, f"the {backend} runner")
    t0 = time.perf_counter()
    rr = run_resilient(problem, SEED, config, backend=backend, mesh=mesh)
    jax.block_until_ready(rr.result.best_energy)
    wall = time.perf_counter() - t0
    check(not rr.downgrades, f"tier downgrade: {rr.downgrades}")
    check(rr.stop_reason == "completed", f"stopped: {rr.stop_reason}")
    best = np.asarray(rr.result.best_energy, np.float64)
    recomputed = np.asarray(energy_fn(rr.result.best_spins), np.float64)
    check(np.array_equal(best, recomputed),
          f"carried best energy {best} != recomputed {recomputed}")
    return rr.result, wall


def store_format(problem) -> str:
    from repro.core.coupling import CouplingStore
    return CouplingStore.build(problem.coupling_source, "auto").fmt


def k2000_problem():
    from repro.configs.snowball import K2000
    from repro.graphs import complete_bipolar, maxcut_to_ising

    inst = complete_bipolar(K2000.num_vertices, seed=SEED)
    return inst, maxcut_to_ising(inst)


def phase_k2000(inst, problem, steps: int = K2000_STEPS,
                target: float = 33_000.0):
    from repro.configs.snowball import default_solver
    from repro.core import ising
    from repro.graphs.maxcut import cut_from_energy

    fmt = store_format(problem)
    check(fmt == "dense", f"K{problem.num_spins} resolved {fmt}, not dense")
    for mode in ("rwa", "rsa"):
        cfg = default_solver(problem.num_spins, steps, mode=mode,
                             num_replicas=REPLICAS)
        result, wall = solve_checked(
            problem, cfg, "fused",
            energy_fn=lambda s: ising.energy(problem, s) + problem.offset)
        cuts = cut_from_energy(inst, np.asarray(result.best_energy))
        print(f"[a] {inst.name} {mode}: tier={fmt} R={REPLICAS} steps={steps} "
              f"wall_s={wall:.3f} (incl. compile) best_cut={cuts.max():.0f} "
              f"target_cut={target:.0f} energy_check=exact", flush=True)


def _sweep_inputs(problem, store, mode: str, steps: int, interpret: bool):
    """Identical kernel/oracle operands: the store's coupling operand, the
    fused init and the first chunk's uniforms and temperatures of an
    anneal."""
    import jax
    import jax.numpy as jnp
    from repro.configs.snowball import default_solver
    from repro.core import rng
    from repro.kernels import ops

    cfg = default_solver(problem.num_spins, steps, mode=mode,
                         num_replicas=REPLICAS)
    base = jax.random.fold_in(jax.random.key(0), jnp.uint32(SEED))
    u, s, e = ops.fused_init_state(problem, base, REPLICAS,
                                   interpret=interpret,
                                   planes=store.planes)[:3]
    unif = rng.uniform01(rng.stream(base, rng.Salt.SWEEP, 0),
                         (steps, REPLICAS, 4))
    temps = jnp.broadcast_to(jax.vmap(cfg.schedule)(jnp.arange(steps))
                             .astype(jnp.float32)[:, None], (steps, REPLICAS))
    return store.kernel_operand, u, s, e, unif, temps


def _first_difference(kernel, oracle, j, u, s, e, unif, temps):
    """Step both engines one step at a time from the shared start; the
    first step whose outputs differ, and the largest field difference."""
    ks = os_ = (u, s, e)
    for t in range(unif.shape[0]):
        k_out = kernel(j, *ks, unif[t:t + 1], temps[t:t + 1])
        o_out = oracle(j, *os_, unif[t:t + 1], temps[t:t + 1])
        for a, b in zip(k_out[:6], o_out[:6]):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                du = np.abs(np.asarray(k_out[0]) - np.asarray(o_out[0])).max()
                return t, float(du)
        ks, os_ = k_out[:3], o_out[:3]
    return None, 0.0


def _verdict(kernel, other, args, same_word: str) -> str:
    got, want = kernel(*args), other(*args)
    if all(np.array_equal(np.asarray(a), np.asarray(b))
           for a, b in zip(got[:6], want[:6])):
        return same_word
    t, du = _first_difference(kernel, other, *args)
    return f"differ first_step={t} max_abs_field_diff={du:g}"


def phase_oracle(name: str, problem, steps: int = ORACLE_STEPS,
                 also: tuple = ()):
    """The kernel on the tier "auto" resolves against the oracle, and the
    kernel on each tier of ``also`` against that first kernel, at the same
    seed (the tiers' trajectory-parity contract)."""
    import jax
    from repro.core.coupling import CouplingStore
    from repro.kernels import ops, ref, sweep

    interpret = ops.auto_interpret(None)
    require_compiled(interpret, "the sweep kernel")
    store = CouplingStore.build(problem.coupling_source, "auto")

    def kernel_on(st, mode):
        """The kernel on ``st``'s tier, fed ``st``'s own coupling operand in
        place of the shared one."""
        run = jax.jit(lambda *a: sweep.mcmc_sweep(
            *a, mode=mode, coupling=st.fmt, interpret=interpret))
        return lambda _, *state: run(st.kernel_operand, *state)

    for mode in ("rwa", "rsa"):
        args = _sweep_inputs(problem, store, mode, steps, interpret)
        kernel = kernel_on(store, mode)
        oracle = jax.jit(lambda *a, mode=mode: ref.mcmc_sweep(*a, mode=mode))
        print(f"[b] {name} {mode}: {store.fmt} kernel vs ref.mcmc_sweep over "
              f"{steps} steps: {_verdict(kernel, oracle, args, 'exact')}",
              flush=True)
        for fmt in also:
            other = kernel_on(CouplingStore.build(problem.coupling_source,
                                                  fmt), mode)
            print(f"[b] {name} {mode}: {fmt} kernel vs {store.fmt} kernel "
                  f"over {steps} steps: "
                  f"{_verdict(other, kernel, args, 'identical')}", flush=True)


def sparse_problem(n: int, num_edges: int, seed: int):
    from repro.graphs import maxcut_edges_to_ising
    from repro.graphs.generators import sparse_bipolar_edges

    return maxcut_edges_to_ising(sparse_bipolar_edges(n, num_edges, seed=seed))


def phase_tier(name: str, problem, want_fmt: str, steps: int = TIER_STEPS,
               mode: str = "rsa", colored: bool = False):
    from repro.configs.snowball import default_solver

    cfg = default_solver(problem.num_spins, steps, mode=mode,
                         num_replicas=REPLICAS)
    if colored:
        cfg = dataclasses.replace(cfg, flip_mode="colored")
    fmt = store_format(problem)
    check(fmt == want_fmt, f"{name} resolved {fmt}, not {want_fmt}")
    result, wall = solve_checked(problem, cfg,
                                 "colored" if colored else "fused",
                                 energy_fn=lambda s: edge_energy(problem, s))
    print(f"[c] {name}: N={problem.num_spins} |E|={problem.edges.nnz} "
          f"tier={fmt} {'colored' if colored else mode} R={REPLICAS} "
          f"steps={steps} wall_s={wall:.3f} (incl. compile) "
          f"best_energy={float(np.min(result.best_energy)):.0f} "
          f"energy_check=exact", flush=True)
    return result


def phase_sharded(problem, steps: int = TIER_STEPS):
    import jax
    from jax.sharding import Mesh
    from repro.configs.snowball import default_solver

    devices = np.array(jax.devices()[:4])
    meshes = (("sharded", Mesh(devices, ("spins",))),
              ("sharded_2d", Mesh(devices.reshape(2, 2), ("groups", "rows"))))
    differ = []
    for mode in ("rsa", "rwa"):
        cfg = default_solver(problem.num_spins, steps, mode=mode,
                             num_replicas=REPLICAS)
        one, wall = solve_checked(problem, cfg, "fused",
                                  energy_fn=lambda s: edge_energy(problem, s))
        base = np.asarray(one.best_energy)
        print(f"[d] one chip bitplane_hbm {mode}: N={problem.num_spins} "
              f"steps={steps} wall_s={wall:.3f} (incl. compile) "
              f"best_energy={float(base.min()):.0f}", flush=True)
        for backend, mesh in meshes:
            res, wall = solve_checked(
                problem, cfg, backend, mesh=mesh,
                energy_fn=lambda s: edge_energy(problem, s))
            shards = ", ".join(
                f"r{sh.index[0].start or 0}s{sh.index[1].start or 0}"
                f"@{sh.device.id}"
                for sh in sorted(res.best_spins.addressable_shards,
                                 key=lambda sh: sh.device.id))
            same = np.array_equal(np.asarray(res.best_energy), base)
            shape = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
            print(f"[d] {backend} {mode} mesh={shape}: wall_s={wall:.3f} "
                  f"(incl. compile) best_energy="
                  f"{float(np.min(res.best_energy)):.0f} "
                  f"equal_to_one_chip={same} "
                  f"shards(replica,spin@device)=[{shards}]", flush=True)
            if not same:
                differ.append(f"{backend} {mode}")
    check(not differ, f"best energies differ from the one-chip run: {differ}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase (d) on four chips")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the solver ({e}); run from the "
              "root of a checkout", file=sys.stderr)
        return 2
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    try:
        if args.chips == 4:
            phase_sharded(sparse_problem(16384, 49152, seed=16384))
        else:
            inst, k2000 = k2000_problem()
            phase_k2000(inst, k2000)
            phase_oracle(f"K{k2000.num_spins}", k2000)
            from repro.graphs import maxcut_edges_to_ising
            from repro.graphs.generators import torus_grid_edges
            phase_oracle("G62", maxcut_edges_to_ising(
                torus_grid_edges(70, 100, seed=62)), also=("bitplane_hbm",))
            phase_tier("G61-sized ER", sparse_problem(7000, 17148, seed=61),
                       "bitplane")
            phase_tier("sparse N=16384", sparse_problem(16384, 49152,
                                                        seed=16384),
                       "bitplane_hbm")
            phase_tier("torus 84x84", maxcut_edges_to_ising(
                torus_grid_edges(84, 84, seed=62)), "bitplane",
                steps=COLORED_STEPS, colored=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
