"""BENCHMARK.json resolves every name to its file, and keeps to the
characters and key sets the benchmark's format allows."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [w[k] for w in SPEC["workloads"] for k in ("config", "traffic")]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(UNIT.match(u) for u in units), units


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_its_files(w):
    configs = {c["name"]: c for c in SPEC["configs"]}
    cfg = configs[w["config"]]
    assert (ROOT / cfg["file"]).is_file()
    assert cfg["file"] == f"chipbench/configs/{w['config']}.json"
    assert json.loads((ROOT / cfg["file"]).read_text())["name"] == cfg["name"]
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
    assert set(limits) == {"energy_gap", "quality_z", "worst_z",
                           "failed_solves"}
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    reported = [m for m in SPEC["end_to_end"] + SPEC["per_layer"]
                if w["name"] in m.get("workloads", [w["name"]])]
    for m in reported:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(w["name"] in m.get("workloads", [w["name"]])
               for m in SPEC["per_layer"])


def test_every_config_is_used_and_in_its_own_file():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))


def test_end_to_end_metrics():
    assert "setup_s" in [m["name"] for m in SPEC["end_to_end"]]
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(m):
    assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    moved = {e["name"]: e for e in SPEC["end_to_end"]}[m["moves"]]
    cells = m.get("workloads", [w["name"] for w in SPEC["workloads"]])
    for cell in cells:
        assert cell in moved.get("workloads", [cell]), (m["name"], cell)
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def test_layer_names_are_spelled_alike():
    by_prefix = {}
    for m in SPEC["per_layer"]:
        by_prefix.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_prefix.values()), by_prefix


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
