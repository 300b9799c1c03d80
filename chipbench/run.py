"""One run of one benchmark cell of the Snowball solver on a TPU.

    python3 -m chipbench.run --workload k2000.rwa --seed 7 --seconds 30 --trace 0

Run from the root of a checkout. The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``chipbench/configs/<config>.json``:
the instance) and a traffic mix (``chipbench/traffic/<traffic>.json``: the
solve stream). The run builds the instance from ``--seed``, builds the
coupling store, warms up the solve's programs (all of it is ``setup_s``),
then issues solves through the program's normal entry point
(``repro.core.resilience.run_resilient`` over the backend registry) in a
closed loop — one caller, the next solve issued when the last one's best
energy is ready — until ``--seconds`` have passed, and counts every solve
it issued. After the window it checks every solve against the plain
reference (``chipbench/check.py``, limits in ``chipbench/limits/``) and
prints one JSON line: the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics from a profiler trace of the window. Each metric is
computed by ``chipbench/metrics/<name>.py``.

Exits non-zero, with no result line, when JAX's devices are not TPUs or are
fewer than the cell asks for, and when the program is not in ``src/``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent


# --------------------------------------------------------------------------
# The cell, found by name.

@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    w = found[0]
    config = json.loads((HERE / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json"
                          ).read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return Cell(w, config, traffic, limits,
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def metric_reader(name: str) -> Callable:
    """``read(run)`` of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# What the metric readers see.

@dataclasses.dataclass
class Solve:
    t_call: float         # run_resilient called
    t_return: float       # run_resilient returned (the device may still run)
    t_ready: float        # best energies on the host
    best_energy: np.ndarray      # (R,)
    ok: bool              # completed, no tier downgrade
    flips: object         # (R,) program's flip counter; on the host once the
                          # window is closed
    #: (R, N) best spins, kept for the solves of the check's sample only
    best_spins: Optional[np.ndarray] = None


@dataclasses.dataclass
class Run:
    cell: Cell
    num_spins: int
    replicas: int
    anneal_steps: int     # single-flip: one spin offered an update per step
    row_bytes: int        # one coupling row of all bit-planes, in bytes
    target_energy: Optional[float]
    solves: list
    window_s: float
    setup_s: float
    store_build_s: Optional[float]
    peaks: dict
    trace: object = None  # chipbench.trace.Trace of the window, if traced

    @property
    def steps(self) -> int:
        """Anneal steps of every solve in the window."""
        return len(self.solves) * self.anneal_steps


# --------------------------------------------------------------------------
# Seeds: everything a run draws comes from --seed.

def _seq(seed: int, *key: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(seed % 2**64, spawn_key=key)


def solve_seed(seed: int, i: int) -> int:
    """The program's seed of the i-th solve (i = -1: the warm-up)."""
    return int(_seq(seed, 1, i + 1).generate_state(1, np.uint32)[0])


def reference_key(seed: int) -> int:
    return int(_seq(seed, 2).generate_state(1, np.uint32)[0])


#: Solves whose best spins the check reads back: a uniform sample, drawn
#: from the seed, of every solve of the window. The window keeps only
#: these on the device, so what it holds does not grow with the solves.
SPIN_SAMPLE = 256


class Reservoir:
    """A uniform sample of at most ``size`` of the items offered, one pass
    (Algorithm R), its draws from ``rng``."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen = size, rng, 0
        self.items: list = []

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


# --------------------------------------------------------------------------
# Spans, and JAX's own count of what it traces and compiles.

def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name)


#: JAX's events for a function traced anew and for a backend compile.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
_compiles = {e: 0 for e in COMPILE_EVENTS}


def _count_compile(event: str, _secs: float, **_kw) -> None:
    if event in _compiles:
        _compiles[event] += 1


def compile_counts() -> tuple:
    """(traces, backend compiles) this process has made so far."""
    import jax
    if not getattr(compile_counts, "listening", False):
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        compile_counts.listening = True
    return tuple(_compiles[e] for e in COMPILE_EVENTS)


# --------------------------------------------------------------------------
# The system under test.

def program_problem(inst):
    """The instance as the program's ``IsingProblem`` (J = −w, h = 0)."""
    from repro.core.ising import EdgeList
    from repro.graphs.maxcut import (MaxCutInstance, maxcut_edges_to_ising,
                                     maxcut_to_ising)
    if inst.weights is not None:
        return maxcut_to_ising(MaxCutInstance(weights=inst.weights))
    rows, cols, w = inst.edges
    return maxcut_edges_to_ising(EdgeList.create(rows, cols, w,
                                                 inst.num_spins))


def solver_config(traffic: dict, n: int):
    """The program's single-flip ``SolverConfig``, every field from the
    traffic mix."""
    from repro.core.schedules import Schedule
    from repro.core.solver import SolverConfig
    steps = int(traffic["anneal_steps"])
    t0 = max(traffic["t0_over_sqrt_n"] * n ** 0.5, traffic["t0_min"])
    return SolverConfig(
        num_steps=steps,
        schedule=Schedule(traffic["schedule"], t0, traffic["t1"], steps),
        mode=traffic["mode"], uniformized=False, use_pwl=True,
        pwl_segments=traffic["pwl_segments"], pwl_zmax=traffic["pwl_zmax"],
        num_replicas=traffic["replicas"], coupling_format="auto")


@dataclasses.dataclass
class Out:
    best_energy: object
    best_spins: object
    flips: object
    ok: bool


class ProgramSolver:
    """Solves through ``run_resilient`` with ``run_dir=None`` on the fused
    backend, passing the store built in set-up (the documented way for
    repeated solves of one instance to skip re-encoding)."""

    def __init__(self, problem, config, traffic: dict, store):
        self.problem, self.config, self.store = problem, config, store
        self.chunk_steps = traffic["chunk_steps"]

    def __call__(self, seed: int) -> Out:
        from repro.core.resilience import run_resilient
        rr = run_resilient(self.problem, seed, self.config, run_dir=None,
                           backend="fused", store=self.store,
                           chunk_steps=self.chunk_steps)
        res = rr.result
        return Out(res.best_energy, res.best_spins, res.num_flips,
                   rr.stop_reason == "completed" and not rr.downgrades)


# --------------------------------------------------------------------------
# The run.

def closed_loop(solve: Callable[[int], Out], seed: int, seconds: float):
    """Issue solves one after another until ``seconds`` have passed; the
    window ends when the last solve's best energies are on the host.
    Returns the solves, the window's length, and the sample of
    ``(solve, best spins on the device)`` the check reads back."""
    solves = []
    sample = Reservoir(SPIN_SAMPLE, np.random.default_rng(_seq(seed, 3)))
    t_start = time.perf_counter()
    with span("window"):
        while True:
            s = solve_seed(seed, len(solves))
            t_call = time.perf_counter()
            with span("run_resilient"):
                out = solve(s)
            t_return = time.perf_counter()
            with span("block_wait"):
                be = np.asarray(out.best_energy, np.float64)
            t_ready = time.perf_counter()
            solves.append(Solve(t_call, t_return, t_ready, be, out.ok,
                                out.flips))
            sample.offer((solves[-1], out.best_spins))
            del out
            if t_ready - t_start >= seconds:
                break
    return solves, solves[-1].t_ready - t_start, sample.items


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: Path, peaks: Optional[dict] = None,
             solver: Optional[Callable] = None):
    """Set-up, window, check and metrics of one run. Returns
    ``(run, check_numbers, correct, device_extra, breakdown)``. ``solver``
    (``(problem, store, inst) -> solve``) replaces the program's solve: the
    control and its faults go in there."""
    import jax
    from repro.core.coupling import CouplingStore, resolve_format
    from . import check, instances
    from . import trace as tr

    cfg, traffic = cell.config, cell.traffic
    phases = {"start": time.perf_counter() - T_PROCESS}
    t0 = time.perf_counter()
    with span("instance_build"):
        base = instances.base_instance(cfg)
        inst = instances.relabel(base, np.random.default_rng(_seq(seed, 0)))
        problem = program_problem(inst)
    n = inst.num_spins
    target = instances.target_energy(cfg, base) if "target" in cfg else None
    fmt = resolve_format("auto", problem.coupling_source, n)
    if fmt != cfg["coupling_tier"]:
        raise SystemExit(f"chipbench: {cfg['name']} resolves the {fmt} tier, "
                         f"not {cfg['coupling_tier']}")
    phases["instance"] = time.perf_counter() - t0
    with span("store_build"):
        t0 = time.perf_counter()
        store = jax.block_until_ready(
            CouplingStore.build(problem.coupling_source, "auto"))
        store_s = phases["store"] = time.perf_counter() - t0
    config = solver_config(traffic, n)
    solve = (solver or (lambda p, st, i: ProgramSolver(p, config, traffic,
                                                        st)))(problem, store,
                                                              inst)
    t0 = time.perf_counter()
    with span("warm_up"):
        warm = solve(solve_seed(seed, -1))
        np.asarray(warm.best_energy)
        del warm
    phases["warm_up"] = time.perf_counter() - t0

    trace_dir = root / ".chipbench_trace" / cell.workload["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # keep host overhead low
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiled = compile_counts()
    t_window = time.perf_counter()
    try:
        solves, window_s, sample = closed_loop(solve, seed, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    setup_s = t_window - T_PROCESS
    compiled = [b - a for a, b in zip(compiled, compile_counts())]
    stats = jax.devices()[0].memory_stats() or {}
    device_extra = {"memory_peak_bytes": int(stats.get("peak_bytes_in_use",
                                                       0))}
    for s in solves:    # the window is closed: now the host may wait
        s.flips = np.asarray(s.flips, np.int64)
    for s, spins in sample:
        s.best_spins = np.asarray(spins)
    del solve, store, problem, sample
    gc.collect()

    run = Run(cell=cell, num_spins=n, replicas=traffic["replicas"],
              anneal_steps=int(traffic["anneal_steps"]),
              row_bytes=2 * cfg["planes"] * n // 8, target_energy=target,
              solves=solves, window_s=window_s, setup_s=setup_s,
              store_build_s=store_s, peaks=peaks or {})

    with span("check"):
        t_check = time.perf_counter()
        numbers = check.compare(run, inst, cell.limits, reference_key(seed))
        check_s = time.perf_counter() - t_check
    correct = all(v["value"] <= v["limit"] for v in numbers.values())

    breakdown = None
    if trace:
        run.trace = tr.load(tr.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device_extra["busy_s"] = tr.busy_s(run.trace)
        device_extra["window_s"] = run.trace.window_s
        breakdown = {"device_ops": tr.top_ops(run.trace),
                     "idle_gaps": tr.idle_by_activity(run.trace)}
    print(f"chipbench: {len(solves)} solves in {window_s:.3f} s, set-up "
          f"{setup_s:.3f} s, check {check_s:.3f} s", file=sys.stderr)
    print("chipbench: set-up " + ", ".join(f"{k} {v:.3f} s"
                                            for k, v in phases.items()),
          file=sys.stderr)
    print("chipbench: " + slowest_solve(solves) + f"; inside the window "
          f"{compiled[0]} traces, {compiled[1]} compiles", file=sys.stderr)
    return run, numbers, correct, device_extra, breakdown


def slowest_solve(solves: list) -> str:
    """Where the window's slowest solve spent its time, beside the median:
    a stall on the host shows in the call, one on the device in the wait."""
    took = [s.t_ready - s.t_call for s in solves]
    i = int(np.argmax(took))
    s = solves[i]
    between = max((b.t_call - a.t_ready for a, b in zip(solves, solves[1:])),
                  default=0.0)
    return (f"slowest solve {i}: {took[i]:.4f} s (call "
            f"{s.t_return - s.t_call:.4f} s, wait {s.t_ready - s.t_return:.4f}"
            f" s), median {float(np.median(took)):.4f} s; longest gap "
            f"between solves {between:.4f} s")


def metrics_of(run: Run, entries: list) -> dict:
    out = {}
    for m in entries:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``.jax_cache/`` in the checkout (a fixed path: the path is part of the
    cache key). Every program is kept, however fast it compiled, so a
    second run of a cell compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path.cwd()
    cell = load_cell(args.workload, root)
    if not (root / "src" / "repro").is_dir():
        print("chipbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    import jax
    enable_compile_cache(root)
    devices = jax.devices()
    dev = devices[0]
    chips = int(cell.workload["chips"])
    if dev.platform != "tpu" or len(devices) < chips:
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 3
    from repro.kernels.ops import auto_interpret
    if auto_interpret(None):
        print("chipbench: the kernels would run in interpret mode",
              file=sys.stderr)
        return 3
    peaks_all = json.loads((HERE / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks_all:
        print(f"chipbench: no peaks for device kind {dev.device_kind!r} in "
              "chipbench/peaks.json", file=sys.stderr)
        return 3

    run, numbers, correct, extra, breakdown = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), root=root,
        peaks=peaks_all[dev.device_kind])
    metrics = metrics_of(run, cell.per_layer if args.trace
                         else cell.end_to_end)
    result = {
        "correct": correct,
        "attempted": len(run.solves),
        "failed": sum(not s.ok for s in run.solves),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), **extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    for name, v in numbers.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
