"""The program's spans and counter in the benchmark: the idle split by
program span on synthetic events and on a trace recorded here on the CPU
around real solves, and the readers of the per-layer numbers."""
import numpy as np
import pytest

from chipbench import program_spans as ps
from chipbench import run as bench
from chipbench import trace as tr


def test_the_prefix_is_the_programs():
    from repro.core import resilience
    assert ps.PROGRAM_SPAN_PREFIX == resilience.SPAN_PREFIX


def synthetic():
    """Device ops at [10, 20) and [70, 100); one solve [5, 90) holding a
    fingerprint [8, 30), an init [30, 45) and a chunk [45, 80)."""
    ops = [[("a", 10, 20, False), ("k", 70, 100, True)]]
    spans = [("chipbench.window", 0, 100)]
    program = [("solve", 5, 90, {"solve": 0}),
               ("fingerprint", 8, 30, {"solve": 0, "what": "fingerprint"}),
               ("init", 30, 45, {"solve": 0}),
               ("chunk", 45, 80, {"solve": 0, "chunk": 0})]
    return tr.Trace(ops=ops, spans=spans, window=(0, 100)), program


def test_innermost_pieces():
    _, program = synthetic()
    assert ps.innermost(program, 0, 100) == [
        (0, 5, "outside"), (5, 8, "solve"), (8, 30, "fingerprint"),
        (30, 45, "init"), (45, 80, "chunk"), (80, 90, "solve"),
        (90, 100, "outside")]
    # Of two spans opened together, the one that closes first is inner.
    assert ps.innermost([("outer", 0, 10, {}), ("inner", 0, 4, {})],
                        0, 10) == [(0, 4, "inner"), (4, 10, "outer")]


def test_a_gap_over_two_program_spans_is_split_by_exact_overlap():
    t, program = synthetic()
    # Gaps [0, 10) and [20, 70): the first covers outside and the solve's
    # own time before the fingerprint, the second the fingerprint, init and
    # chunk, which its midpoint (45) alone would not tell apart.
    got = dict(ps.idle_by_program_span(t, program))
    assert got == {"outside": pytest.approx(5e-9),
                   "solve": pytest.approx(3e-9),
                   "fingerprint": pytest.approx(12e-9),
                   "init": pytest.approx(15e-9),
                   "chunk": pytest.approx(25e-9)}
    assert sum(got.values()) == pytest.approx(t.window_s - tr.busy_s(t))
    assert ps.idle_by_program_span(t, []) == [["outside",
                                               pytest.approx(60e-9)]]


def synthetic_run(trace=None, rows_fetched=(None, None)):
    solves = [bench.Solve(0.0, 0.0, 0.0, np.zeros(8), True, np.zeros(8))
              for _ in rows_fetched]
    for s, rf in zip(solves, rows_fetched):
        s.rows_fetched = rf
    return bench.Run(cell=None, num_spins=64, replicas=8, anneal_steps=5,
                     row_bytes=16, target_energy=None, solves=solves,
                     window_s=1e-7, setup_s=1.0, store_build_s=None,
                     peaks={}, trace=trace)


READERS = (ps.fingerprint_ms_per_solve, ps.chunk_dispatch_ms_per_solve,
           ps.fingerprint_idle_share, ps.mcmc_sweep_rows_per_step)


@pytest.mark.parametrize("read", READERS, ids=lambda f: f.__name__)
def test_readers_return_none_without_their_input(read):
    t, _ = synthetic()
    assert read(synthetic_run()) is None            # untraced
    assert read(synthetic_run(trace=t)) is None     # no program spans
    t.program_spans = []
    assert read(synthetic_run(trace=t)) is None


@pytest.mark.parametrize("read,expected", [
    (ps.fingerprint_ms_per_solve, 22e-6 / 2),
    (ps.chunk_dispatch_ms_per_solve, 35e-6 / 2),
    (ps.fingerprint_idle_share, 12.0),
    (ps.mcmc_sweep_rows_per_step, (40 + 38) / (2 * 5)),
], ids=lambda v: getattr(v, "__name__", ""))
def test_readers_on_a_synthetic_run(read, expected):
    t, program = synthetic()
    t.program_spans = program
    run = synthetic_run(trace=t, rows_fetched=(np.full(8, 5),
                                               np.array([5] * 6 + [4] * 2)))
    assert read(run) == pytest.approx(expected)


def test_program_spans_of_a_recorded_cpu_trace(tmp_path):
    import jax
    from repro.core import ising, schedules
    from repro.core.resilience import run_resilient
    from repro.core.solver import SolverConfig

    g = np.random.default_rng(0)
    j = np.triu(g.choice([-1.0, 1.0], size=(32, 32)), 1)
    problem = ising.IsingProblem.create(j + j.T, np.zeros(32, np.float32))
    cfg = SolverConfig(num_steps=40, schedule=schedules.linear(3.0, 0.1, 40),
                       num_replicas=8, coupling_format="dense")

    def solve(seed):
        rr = run_resilient(problem, seed, cfg, backend="fused",
                           chunk_steps=20)
        return np.asarray(rr.result.best_energy), rr.result.rows_fetched

    solve(0)        # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for seed in (1, 2):
                with jax.profiler.TraceAnnotation("chipbench.run_resilient"):
                    _, rf = solve(seed)
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    t = tr.load(path, device_plane=lambda n: n == "/host:CPU",
                op_line=lambda n: n.startswith("tf_XLA"))
    program = ps.load(path, t.window)

    # The benchmark's own reduction does not see the program's spans.
    assert {n for n, *_ in t.spans} == {tr.WINDOW_SPAN,
                                        "chipbench.run_resilient"}
    assert {k for k, _ in tr.idle_by_activity(t)} <= {"window",
                                                      "run_resilient"}
    names = [n for n, *_ in program]
    assert names.count("solve") == 2 and names.count("chunk") == 4
    assert all(t.window[0] <= a <= b <= t.window[1] for _, a, b, _ in program)
    idle = ps.idle_by_program_span(t, program, top=None)
    assert sum(v for _, v in idle) == pytest.approx(
        sum(v for _, v in tr.idle_by_activity(t, top=None)))
    assert "fingerprint" in dict(idle)
    assert int(np.asarray(rf).sum()) == 8 * 40     # dense: one per step
