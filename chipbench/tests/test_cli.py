"""The measurement path fails, and prints no result, without a TPU or
without the program; it never falls back to the CPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]


def test_cpu_platform_fails_the_run(capsys, monkeypatch):
    monkeypatch.chdir(ROOT)
    # The cache setting is process-wide; keep it away from later tests.
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    rc = run.main(["--workload", "k2000.short", "--seed", "3",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "TPU" in err


def test_checkout_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "g81.rwa",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
