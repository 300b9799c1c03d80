"""Snowball solve launcher: instances, modes, engines, optional distribution.

    PYTHONPATH=src python -m repro.launch.solve --instance k200 --mode rwa
    PYTHONPATH=src python -m repro.launch.solve --gset path/to/G6 --mode rsa

Long solves can run under the resilient supervisor (crash-safe snapshots,
budgets, bit-identical resume — see DESIGN.md §Resilient solves):

    PYTHONPATH=src python -m repro.launch.solve --instance k200 \\
        --run-dir runs/k200 --deadline-seconds 3600
    # after a crash/preemption, the same command resumes where it stopped
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time

import numpy as np

from repro.configs.snowball import default_solver
from repro.core import tts
from repro.core.resilience import BudgetConfig, run_resilient
from repro.core.solver import solve
from repro.graphs import (complete_bipolar, erdos_renyi, maxcut_to_ising,
                          parse_gset, small_world, torus_grid)
from repro.graphs.maxcut import cut_from_energy
from repro.kernels import fused_anneal
from repro.launch.compile_cache import enable_compile_cache


def build_instance(args):
    if args.gset:
        return parse_gset(args.gset, name=args.gset)
    name = args.instance.lower()
    if name.startswith("k"):
        return complete_bipolar(int(name[1:]), seed=args.seed)
    if name.startswith("er"):
        n = int(name[2:])
        return erdos_renyi(n, n * 24, seed=args.seed)
    if name.startswith("sw"):
        return small_world(int(name[2:]), 12, seed=args.seed)
    if name.startswith("torus"):
        side = int(name[5:])
        return torus_grid(side, side, seed=args.seed)
    raise SystemExit(
        f"unknown instance {args.instance!r}: expected k<N> (complete "
        "bipolar), er<N> (Erdős–Rényi, 24·N edges), sw<N> (small-world, "
        "degree 12), or torus<side> (side×side grid) — e.g. k200, er500, "
        "sw1000, torus32 — or pass a Gset-format file via --gset instead")


def build_mesh(spec: str | None):
    """Device mesh for ``--engine sharded``: ``"4"`` → 1-D row sharding over
    4 devices; ``"2x2"`` → the 2-D (groups, rows) layout. ``None`` takes
    every visible device as a 1-D mesh."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if spec is None:
        shape = (len(devices),)
    else:
        try:
            shape = tuple(int(s) for s in spec.lower().split("x"))
        except ValueError:
            raise SystemExit(
                f"--mesh-shape {spec!r}: expected e.g. '4' or '2x2'")
    ndev = math.prod(shape)
    if ndev > len(devices):
        raise SystemExit(
            f"--mesh-shape {spec} needs {ndev} devices but only "
            f"{len(devices)} are visible (force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={ndev})")
    names = ("spins",) if len(shape) == 1 else ("groups", "rows")
    return Mesh(np.array(devices[:ndev]).reshape(shape), names)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instance", default="k200",
                    help="k<N>|er<N>|sw<N>|torus<side>")
    ap.add_argument("--gset", default=None, help="path to a Gset-format file")
    ap.add_argument("--mode", choices=("rsa", "rwa"), default="rwa")
    ap.add_argument("--steps", type=int, default=5000)
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--engine", choices=("scan", "fused", "sharded"),
                    default="scan",
                    help="sharded = spin-row-sharded planes over a device "
                    "mesh (see --mesh-shape); always supervised")
    ap.add_argument("--mesh-shape", default=None,
                    help="device mesh for --engine sharded: '4' shards spin "
                    "rows over 4 devices; '2x2' runs 2 replica groups × 2 "
                    "row shards (the bitplane_sharded_2d tier)")
    ap.add_argument("--flip-mode", choices=("single", "colored"),
                    default="single",
                    help="colored = one conflict-graph color class per step "
                    "(O(N/χ) flips/step on sparse instances; runs under the "
                    "resilient supervisor on the 'colored' backend)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tts-threshold", type=float, default=None,
                    help="cut value for TTS(0.99) estimation")
    res = ap.add_argument_group(
        "resilience", "crash-safe supervised solve (any of these flags "
        "routes the run through repro.core.resilience.run_resilient)")
    res.add_argument("--run-dir", default=None,
                     help="snapshot directory; rerunning with the same "
                     "arguments resumes bit-identically from the last "
                     "intact snapshot")
    res.add_argument("--no-resume", action="store_true",
                     help="ignore snapshots already in --run-dir")
    res.add_argument("--deadline-seconds", type=float, default=None,
                     help="wall-clock budget, checked between chunks")
    res.add_argument("--target-energy", type=float, default=None,
                     help="stop once the ensemble best reaches this energy")
    res.add_argument("--max-steps", type=int, default=None,
                     help="step budget (may stop before --steps)")
    res.add_argument("--chunk-steps", type=int, default=256,
                     help="snapshot/budget granularity for untraced runs")
    args = ap.parse_args()
    enable_compile_cache()

    inst = build_instance(args)
    problem = maxcut_to_ising(inst)
    cfg = default_solver(inst.num_vertices, args.steps, mode=args.mode,
                         num_replicas=args.replicas)
    colored = args.flip_mode == "colored"
    sharded = args.engine == "sharded"
    if colored and sharded:
        raise SystemExit("--engine sharded is single-flip only; drop "
                         "--flip-mode colored")
    if colored:
        cfg = dataclasses.replace(cfg, flip_mode="colored")
    mesh = build_mesh(args.mesh_shape) if sharded else None
    resilient = (colored
                 or sharded
                 or args.run_dir is not None
                 or args.deadline_seconds is not None
                 or args.target_energy is not None
                 or args.max_steps is not None)
    t0 = time.perf_counter()
    if resilient:
        backend = ("colored" if colored
                   else ("sharded_2d" if len(mesh.axis_names) > 1
                         else "sharded") if sharded
                   else "fused" if args.engine == "fused" else "reference")
        rr = run_resilient(
            problem, args.seed, cfg, run_dir=args.run_dir, backend=backend,
            mesh=mesh,
            budget=BudgetConfig(deadline_seconds=args.deadline_seconds,
                                max_steps=args.max_steps,
                                target_energy=args.target_energy),
            chunk_steps=args.chunk_steps, resume=not args.no_resume)
        result = rr.result
    else:
        engine = fused_anneal if args.engine == "fused" else solve
        result = engine(problem, args.seed, cfg)
    result.best_energy.block_until_ready()
    wall = time.perf_counter() - t0

    cuts = cut_from_energy(inst, np.asarray(result.best_energy))
    print(f"instance={inst.name} |V|={inst.num_vertices} |E|={inst.num_edges} "
          f"density={inst.density*100:.1f}%")
    print(f"mode={args.mode} engine={args.engine} steps={args.steps} "
          f"replicas={args.replicas} wall={wall:.2f}s")
    if resilient:
        resumed = ("" if rr.resumed_from_chunk is None
                   else f" resumed_from_chunk={rr.resumed_from_chunk}")
        downgraded = ("" if not rr.downgrades else
                      " tier_downgrades=" + ",".join(
                          f"{a}->{b}@{c}" for a, b, c in rr.downgrades))
        print(f"stop_reason={rr.stop_reason} steps_done={rr.steps_done}/"
              f"{args.steps} chunks={rr.chunks_done}/{rr.total_chunks}"
              f"{resumed}{downgraded}")
    steps_done = rr.steps_done if resilient else args.steps
    if colored:
        from repro.graphs.coloring import greedy_coloring
        col = greedy_coloring(problem.coupling_source)
        flips = float(np.sum(np.asarray(result.num_flips)))
        per_step = flips / max(steps_done, 1)
        print(f"flip_mode=colored color_classes={col.num_classes} "
              f"max_class={col.max_class_size} "
              f"mean_class={col.num_spins / col.num_classes:.1f} "
              f"flips/step={per_step:.1f} (ensemble, {args.replicas} "
              f"replicas)")
    if sharded:
        shape = ", ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
        print(f"engine=sharded backend={backend} mesh=({shape})")
    if sharded or colored:
        # Perf telemetry for the coalescing / mesh-sharding tiers:
        # µs/step (wall clock, compile included) plus the kernel's
        # unique-rows-fetched counter where the tier reports one — the
        # coalescing win is rows/step below replicas/step.
        us = wall / max(steps_done, 1) * 1e6
        line = f"us/step={us:.1f} (wall incl. compile)"
        if result.rows_fetched is not None:
            rf = float(np.sum(np.asarray(result.rows_fetched)))
            baseline = (f"vs {args.replicas}/step uncoalesced" if sharded
                        else f"of N={problem.num_spins} dense")
            line += (f" rows_fetched={rf:.0f} "
                     f"({rf / max(steps_done, 1):.2f} rows/step "
                     f"{baseline})")
        print(line)
    print(f"best cut = {cuts.max():.0f}  (per-replica: {np.sort(cuts)[::-1][:8]})")
    if args.tts_threshold:
        r = tts.estimate(-cuts, threshold=-args.tts_threshold,
                         time_per_run=wall / args.replicas * 1e3)
        print(f"TTS(0.99) @ cut≥{args.tts_threshold:.0f}: {r.tts:.2f} ms "
              f"(P_a={r.success_probability:.2f})")


if __name__ == "__main__":
    main()
