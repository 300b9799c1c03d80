"""``correct`` on the CPU at a small size: sound runs pass, and the
control (the reference in bfloat16 in the program's place) and each fault
planted in the program's timed path fail. The harness's look for a chip is
skipped; the rest of a run (set-up, window, check) is driven as on the
chip, with the Pallas kernels in interpret mode."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import faults
from chipbench import run as bench

ROOT = Path(__file__).resolve().parents[2]
TRAFFIC = ROOT / "chipbench" / "traffic"

# Small stand-ins for the two configurations, each held to its cell's
# limits. The control needs energies that bfloat16 cannot carry exactly:
# past 256 on K_N, and past 1,024 on a torus, whose energy moves in steps
# of 4.
DENSE = {"name": "k128", "family": "complete_bipolar", "num_vertices": 128,
         "base_seed": 0, "planes": 1, "coupling_tier": "dense",
         "target": {"energy": -900}}
TORUS = {"name": "t384", "family": "torus", "rows": 16, "cols": 24,
         "base_seed": 81, "planes": 1, "coupling_tier": "bitplane",
         "target": {"energy": -500}}
BIG_TORUS = dict(TORUS, name="t1920", rows=40, cols=48,
                 target={"energy": -2500})


def cell(config, traffic_file, steps, limits_of):
    traffic = json.loads((TRAFFIC / traffic_file).read_text())
    traffic.update(anneal_steps=steps, reference_replicas=32)
    limits = json.loads((ROOT / "chipbench" / "limits" / limits_of
                         ).read_text())
    return bench.Cell({"name": config["name"], "chips": 1}, config, traffic,
                      limits, [], [])


CELLS = {
    "dense": cell(DENSE, "rwa_l8192.json", 512, "k2000.rwa.json"),
    "torus": cell(TORUS, "rwa_l16384.json", 256, "g81.rwa.json"),
    "torus_big": cell(BIG_TORUS, "rwa_l16384.json", 1024, "g81.rwa.json"),
}


def check(name, mode, tmp_path, seed=20260):
    c = CELLS["torus_big" if name == "torus" and mode == "control"
              else name]
    solver = faults.control_factory(c.traffic) if mode == "control" else None
    if mode in faults.FAULTS:
        with faults.planted(mode):
            out = bench.run_cell(c, seed, 2.0, False, root=tmp_path)
    else:
        out = bench.run_cell(c, seed, 2.0, False, root=tmp_path,
                             solver=solver)
    run, numbers, correct, _, _ = out
    assert len(run.solves) >= 4
    return numbers, correct


@pytest.mark.parametrize("name", ["dense", "torus"])
def test_sound_runs_are_correct(name, tmp_path):
    numbers, correct = check(name, "program", tmp_path)
    assert correct, numbers
    assert numbers["energy_gap"]["value"] == 0
    assert numbers["failed_solves"]["value"] == 0


@pytest.mark.parametrize("name", ["dense", "torus"])
def test_control_is_not_correct(name, tmp_path):
    numbers, correct = check(name, "control", tmp_path)
    assert not correct, numbers
    assert numbers["energy_gap"]["value"] > 0


@pytest.mark.parametrize("name", ["dense", "torus"])
@pytest.mark.parametrize("fault,reads", [("unchanged", "quality_z"),
                                         ("half_batch", "worst_z"),
                                         ("altered", "energy_gap"),
                                         ("downgrade", "failed_solves")])
def test_planted_faults_are_not_correct(name, fault, reads, tmp_path):
    numbers, correct = check(name, fault, tmp_path)
    assert not correct, numbers
    assert numbers[reads]["value"] > numbers[reads]["limit"]


def test_faults_are_removed_after_the_block():
    from repro.core import backend, resilience
    before = backend.FusedRunner.run_chunk, backend.FusedRunner.finalize
    with faults.planted("unchanged"):
        assert backend.FusedRunner.run_chunk is not before[0]
    with faults.planted("altered"):
        pass
    with faults.planted("downgrade"):
        assert resilience._fault_hook is not None
    assert resilience._fault_hook is None
    assert (backend.FusedRunner.run_chunk,
            backend.FusedRunner.finalize) == before


def test_spin_sample_is_uniform_and_bounded():
    draws = np.zeros(40)
    for seed in range(400):
        r = bench.Reservoir(10, np.random.default_rng(seed))
        for i in range(40):
            r.offer(i)
        assert len(r.items) == 10 and len(set(r.items)) == 10
        draws[r.items] += 1
    # Each of 40 items is kept with probability 1/4: 100 of 400 times.
    assert draws.min() > 60 and draws.max() < 140


def test_window_keeps_spins_of_the_sample_only(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "SPIN_SAMPLE", 3)
    run, numbers, correct, _, _ = bench.run_cell(CELLS["dense"], 7, 2.0,
                                                 False, root=tmp_path)
    kept = [s for s in run.solves if s.best_spins is not None]
    assert len(run.solves) > 3 and len(kept) == 3
    assert correct, numbers
