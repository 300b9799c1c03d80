"""Readings the limits of ``chipbench/limits/`` are set from.

    python3 -m chipbench.calibrate --workload k2000.rwa --seeds 3 \
        --first-seed 5000 --seconds 30 --mode control

Runs the cell's set-up, window and check in one process for each of
``--seeds`` consecutive seeds, and prints every number compared, one JSON
line per seed. ``--mode`` picks what is in the program's place: ``program``
(sound runs: the lower readings), ``control`` (the plain reference in
bfloat16) or a fault of ``chipbench.faults`` planted in the program (the
upper readings). The benchmark's own runs never run this. Needs the chips
the cell asks for.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

from . import faults
from . import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", required=True,
                    choices=("program", "control") + faults.FAULTS)
    args = ap.parse_args(argv)
    root = Path.cwd()
    cell = bench.load_cell(args.workload, root)
    sys.path.insert(0, str(root / "src"))
    import jax
    bench.enable_compile_cache(root)
    if jax.devices()[0].platform != "tpu":
        print("chipbench.calibrate: no TPU", file=sys.stderr)
        return 3
    for i in range(args.seeds):
        seed = args.first_seed + i
        solver = (faults.control_factory(cell.traffic)
                  if args.mode == "control" else None)
        with (faults.planted(args.mode) if args.mode in faults.FAULTS
              else contextlib.nullcontext()):
            run, numbers, correct, _, _ = bench.run_cell(
                cell, seed, args.seconds, False, root=root, solver=solver)
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "solves": len(run.solves),
                          "correct": correct,
                          **{k: v["value"] for k, v in numbers.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
