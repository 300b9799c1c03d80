"""Resilient-solve supervisor: monolithic parity, bit-identical resume,
corrupt-snapshot recovery, budgets, and tier fallback (in-process tiers;
the spin-sharded tier's kill-and-resume runs on a forced mesh in
``test_fault_injection.py``)."""
import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ising, schedules
from repro.core.coupling import CouplingStore
from repro.core.solver import SolverConfig, solve
from repro.core.tempering import TemperingConfig, solve_tempering
from repro.core.resilience import (BudgetConfig, run_resilient,
                                   inject_faults, is_allocation_failure,
                                   next_tier, SPAN_PREFIX, STOP_COMPLETED,
                                   STOP_DEADLINE, STOP_INTERRUPTED,
                                   STOP_MAX_STEPS, STOP_TARGET)
from repro.checkpoint import snapshot_steps
from repro.kernels import sweep

from fault_injection import (SimulatedCrash, corrupt_snapshot, fake_oom,
                             kill_after_chunk_hook, oom_once_hook)

N = 64
STEPS = 120
TRACE = 20          # -> 6 chunks
REPLICAS = 4
FUSED_FMTS = ("dense", "bitplane", "bitplane_hbm")
RESULT_FIELDS = ("best_energy", "best_spins", "final_energy", "num_flips",
                 "trace_energy")


def _problem():
    g = np.random.default_rng(0)
    J = np.clip(np.rint(g.normal(size=(N, N)) * 1.5), -3, 3)
    J = np.triu(J, 1)
    J = J + J.T
    h = g.normal(size=(N,)).astype(np.float32)
    return ising.IsingProblem.create(J, h, offset=1.5)


@pytest.fixture(scope="module")
def problem():
    return _problem()


def _cfg(mode="rwa", fmt="auto"):
    return SolverConfig(num_steps=STEPS,
                        schedule=schedules.linear(3.0, 0.1, STEPS),
                        mode=mode, num_replicas=REPLICAS, trace_every=TRACE,
                        coupling_format=fmt)


def _tcfg(fmt="auto"):
    return TemperingConfig(num_steps=STEPS, t_min=0.1, t_max=3.0,
                           num_replicas=REPLICAS, swap_every=TRACE,
                           backend="fused", coupling_format=fmt)


def _assert_same_solve(mono, got):
    for field in RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(mono, field)), np.asarray(getattr(got, field)),
            err_msg=field)


def _assert_same_tempering(mono, got):
    for field in ("best_energy", "best_spins", "final_energy",
                  "swap_acceptance", "num_flips"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mono, field)), np.asarray(getattr(got, field)),
            err_msg=field)


def _interrupted_then_resumed(problem, config, tmp_path, boundary, *,
                              seed=7, backend="auto"):
    """Kill a checkpointed run right after snapshot ``boundary``, resume it,
    and return the resumed ResilientResult."""
    run_dir = str(tmp_path / f"run_b{boundary}")
    with pytest.raises(SimulatedCrash):
        run_resilient(problem, seed, config, run_dir=run_dir,
                      backend=backend,
                      on_event=kill_after_chunk_hook(boundary))
    res = run_resilient(problem, seed, config, run_dir=run_dir,
                        backend=backend)
    assert res.resumed_from_chunk == boundary
    assert res.stop_reason == STOP_COMPLETED
    return res


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("fmt,mode", [("dense", "rsa"), ("dense", "rwa"),
                                      ("bitplane", "rwa"),
                                      ("bitplane_hbm", "rsa")])
def test_resilient_matches_monolithic_fused(problem, fmt, mode):
    cfg = _cfg(mode, fmt)
    mono = solve(problem, 7, cfg, backend="fused")
    res = run_resilient(problem, 7, cfg)
    assert res.stop_reason == STOP_COMPLETED
    assert res.chunks_done == res.total_chunks == STEPS // TRACE
    assert res.steps_done == STEPS
    _assert_same_solve(mono, res.result)


def test_resilient_matches_monolithic_reference(problem):
    cfg = _cfg("rwa", "auto")
    mono = solve(problem, 7, cfg, backend="reference")
    res = run_resilient(problem, 7, cfg, backend="reference")
    assert res.stop_reason == STOP_COMPLETED
    _assert_same_solve(mono, res.result)


@pytest.mark.parametrize("fmt", ["dense", "bitplane"])
def test_resilient_matches_monolithic_tempering(problem, fmt):
    tc = _tcfg(fmt)
    mono = solve_tempering(problem, 7, tc)
    res = run_resilient(problem, 7, tc)
    assert res.stop_reason == STOP_COMPLETED
    _assert_same_tempering(mono, res.result)


def test_untraced_run_covers_remainder_chunk(problem):
    # 120 steps at chunk_steps=50 -> chunks of 50, 50, and a 20-step tail.
    # Chunking is part of the RNG stream layout for untraced runs, so the
    # monolithic oracle must be driven at the same chunk_steps.
    from repro.kernels.ops import fused_anneal
    cfg = SolverConfig(num_steps=STEPS,
                       schedule=schedules.linear(3.0, 0.1, STEPS),
                       num_replicas=REPLICAS)
    mono = fused_anneal(problem, 7, cfg, chunk_steps=50)
    res = run_resilient(problem, 7, cfg, chunk_steps=50)
    assert res.total_chunks == 3 and res.steps_done == STEPS
    _assert_same_solve(mono, res.result)


@pytest.mark.parametrize("fmt", ["dense", "bitplane_hbm"])
def test_fused_rows_fetched_matches_fused_anneal(problem, fmt):
    # The fused runner carries the kernel's rows-fetched counter across the
    # chunks (50, 50 and a 20-step tail), beside an unchanged trajectory.
    from repro.kernels.ops import fused_anneal
    cfg = SolverConfig(num_steps=STEPS,
                       schedule=schedules.linear(3.0, 0.1, STEPS),
                       num_replicas=REPLICAS, coupling_format=fmt)
    mono = fused_anneal(problem, 7, cfg, chunk_steps=50)
    res = run_resilient(problem, 7, cfg, backend="fused",
                        chunk_steps=50).result
    _assert_same_solve(mono, res)
    np.testing.assert_array_equal(np.asarray(mono.rows_fetched),
                                  np.asarray(res.rows_fetched))
    assert np.asarray(res.rows_fetched).sum() > 0


# ---------------------------------------------------------------- spans

SPANS = ("solve", "fingerprint", "runner_build", "init", "chunk", "finalize")


def _profiled(log_dir, fn):
    """Run ``fn`` under a profiler session. Returns, from the one xplane
    file it wrote, the program's spans as ``(name, start, end, ids)`` with
    the prefix dropped, and the ``(start, end)`` of the XLA:CPU op events."""
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    spans, ops = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name[len(SPAN_PREFIX):], a, b,
                                  dict(e.stats)))
                elif (plane.name == "/host:CPU"
                      and line.name.startswith("tf_XLA")):
                    ops.append((a, b))
    return spans, ops


def test_spans_of_two_solves_nest_under_their_solve_id(problem, tmp_path):
    cfg = SolverConfig(num_steps=STEPS,
                       schedule=schedules.linear(3.0, 0.1, STEPS),
                       num_replicas=REPLICAS, coupling_format="dense")

    def solve_once(seed):
        rr = run_resilient(problem, seed, cfg, backend="fused",
                           chunk_steps=STEPS // 2)
        jax.block_until_ready(rr.result.best_energy)
        return rr

    solve_once(6)       # compiles outside the profiled window
    # A new object with the same content: its first solve hashes it, the
    # second finds the fingerprint kept on the object.
    problem = ising.IsingProblem(problem.couplings, problem.fields,
                                 problem.offset)
    results = []
    spans, ops = _profiled(tmp_path, lambda: results.extend(
        solve_once(seed) for seed in (7, 8)))

    assert {s[0] for s in spans} == set(SPANS)
    solves = sorted((s for s in spans if s[0] == "solve"),
                    key=lambda s: s[1])
    assert len(solves) == 2
    assert solves[0][3]["solve"] != solves[1][3]["solve"]
    for i, ((_, lo, hi, ids), rr) in enumerate(zip(solves, results)):
        kids = sorted((s for s in spans
                       if s[0] != "solve" and s[3]["solve"] == ids["solve"]),
                      key=lambda s: s[1])
        assert all(lo <= a <= b <= hi for _, a, b, _ in kids)
        assert [s[0] for s in kids] == ["fingerprint", "fingerprint",
                                        "runner_build", "init", "chunk",
                                        "chunk", "finalize"]
        assert [s[3]["what"] for s in kids[:2]] == ["signature",
                                                    "fingerprint"]
        assert [s[3]["cached"] for s in kids[:2]] == [min(i, 1)] * 2
        assert kids[2][3]["fmt"] == "dense"
        assert rr.total_chunks == 2
        assert [s[3]["chunk"] for s in kids if s[0] == "chunk"] == \
            list(range(rr.total_chunks))
    # One file, one clock: the XLA ops the solves dispatched start inside
    # their solve spans.
    assert ops
    assert any(lo <= a < hi for a, _ in ops for _, lo, hi, _ in solves)


@pytest.mark.parametrize("fmt", FUSED_FMTS)
def test_runner_build_span_carries_the_sweeps_vmem_request(problem, tmp_path,
                                                           fmt):
    cfg = _cfg("rwa", fmt)
    spans, _ = _profiled(tmp_path, lambda: jax.block_until_ready(
        run_resilient(problem, 7, cfg, backend="fused").result.best_energy))
    build, = (s for s in spans if s[0] == "runner_build")
    store = CouplingStore.build(problem.couplings, fmt)
    want = sweep.sweep_vmem_limit(store.kernel_operand, REPLICAS, N, TRACE,
                                  coupling=fmt)
    assert build[3]["fmt"] == fmt
    assert build[3]["vmem_bytes"] == want >= 32 * 2**20


# ---------------------------------------------------------------- resume

def test_resume_parity_every_boundary(problem, tmp_path):
    """Interrupt at EVERY chunk boundary (bitplane x rwa): the resumed
    trajectory must be bit-identical to the uninterrupted one."""
    cfg = _cfg("rwa", "bitplane")
    mono = solve(problem, 7, cfg, backend="fused")
    for boundary in range(1, STEPS // TRACE):
        res = _interrupted_then_resumed(problem, cfg, tmp_path, boundary)
        _assert_same_solve(mono, res.result)


@pytest.mark.parametrize("fmt,mode", [("dense", "rsa"),
                                      ("bitplane_hbm", "rwa")])
def test_resume_parity_one_boundary(problem, tmp_path, fmt, mode):
    cfg = _cfg(mode, fmt)
    mono = solve(problem, 7, cfg, backend="fused")
    res = _interrupted_then_resumed(problem, cfg, tmp_path, 2)
    _assert_same_solve(mono, res.result)


def test_resume_parity_reference(problem, tmp_path):
    cfg = _cfg("rwa", "auto")
    mono = solve(problem, 7, cfg, backend="reference")
    res = _interrupted_then_resumed(problem, cfg, tmp_path, 3,
                                    backend="reference")
    _assert_same_solve(mono, res.result)


def test_resume_parity_tempering(problem, tmp_path):
    tc = _tcfg("bitplane")
    mono = solve_tempering(problem, 7, tc)
    res = _interrupted_then_resumed(problem, tc, tmp_path, 2)
    _assert_same_tempering(mono, res.result)


@pytest.mark.slow
@pytest.mark.parametrize("fmt", FUSED_FMTS)
@pytest.mark.parametrize("mode", ["rsa", "rwa"])
def test_resume_parity_full_matrix(problem, tmp_path, fmt, mode):
    cfg = _cfg(mode, fmt)
    mono = solve(problem, 7, cfg, backend="fused")
    for boundary in range(1, STEPS // TRACE):
        res = _interrupted_then_resumed(problem, cfg, tmp_path, boundary)
        _assert_same_solve(mono, res.result)


@pytest.mark.slow
@pytest.mark.parametrize("fmt", ["dense", "bitplane", "bitplane_hbm"])
def test_resume_parity_tempering_full(problem, tmp_path, fmt):
    tc = _tcfg(fmt)
    mono = solve_tempering(problem, 7, tc)
    for boundary in range(1, STEPS // TRACE):
        res = _interrupted_then_resumed(problem, tc, tmp_path, boundary)
        _assert_same_tempering(mono, res.result)


# ------------------------------------------------------------ corruption

def test_corrupt_newest_snapshot_falls_back(problem, tmp_path):
    cfg = _cfg("rwa", "bitplane")
    run_dir = str(tmp_path / "run")
    with pytest.raises(SimulatedCrash):
        run_resilient(problem, 7, cfg, run_dir=run_dir, keep=10,
                      on_event=kill_after_chunk_hook(4))
    assert snapshot_steps(run_dir) == [1, 2, 3, 4]
    corrupt_snapshot(run_dir, 4, how="flip")
    events = []
    res = run_resilient(problem, 7, cfg, run_dir=run_dir, keep=10,
                        on_event=lambda k, i: events.append(k))
    assert res.resumed_from_chunk == 3
    assert "snapshot_corrupt" in events
    _assert_same_solve(solve(problem, 7, cfg, backend="fused"), res.result)


@pytest.mark.parametrize("how", ["truncate", "manifest", "legacy_empty"])
def test_all_snapshots_corrupt_restarts_fresh(problem, tmp_path, how):
    cfg = _cfg("rwa", "bitplane")
    run_dir = str(tmp_path / f"run_{how}")
    with pytest.raises(SimulatedCrash):
        run_resilient(problem, 7, cfg, run_dir=run_dir,
                      on_event=kill_after_chunk_hook(3))
    for step in snapshot_steps(run_dir):
        corrupt_snapshot(run_dir, step, how=how)
    res = run_resilient(problem, 7, cfg, run_dir=run_dir)
    assert res.resumed_from_chunk is None
    assert res.stop_reason == STOP_COMPLETED
    _assert_same_solve(solve(problem, 7, cfg, backend="fused"), res.result)


def test_legacy_snapshot_truncated_npz_falls_back(problem, tmp_path):
    """A pre-checksum snapshot (no ``arrays_sha256`` in the manifest) whose
    arrays.npz was torn to zero bytes: ``np.load`` raises ``EOFError`` with
    no checksum gate in front of it, and the newest-first walk must convert
    that into fallback to the next-older snapshot, not crash."""
    cfg = _cfg("rwa", "bitplane")
    run_dir = str(tmp_path / "run")
    with pytest.raises(SimulatedCrash):
        run_resilient(problem, 7, cfg, run_dir=run_dir, keep=10,
                      on_event=kill_after_chunk_hook(4))
    corrupt_snapshot(run_dir, 4, how="legacy_empty")
    events = []
    res = run_resilient(problem, 7, cfg, run_dir=run_dir, keep=10,
                        on_event=lambda k, i: events.append(k))
    assert res.resumed_from_chunk == 3
    assert "snapshot_corrupt" in events
    _assert_same_solve(solve(problem, 7, cfg, backend="fused"), res.result)


def test_mismatched_run_dir_is_refused(problem, tmp_path):
    cfg = _cfg("rwa", "bitplane")
    run_dir = str(tmp_path / "run")
    with pytest.raises(SimulatedCrash):
        run_resilient(problem, 7, cfg, run_dir=run_dir,
                      on_event=kill_after_chunk_hook(2))
    other_cfg = _cfg("rsa", "bitplane")
    with pytest.raises(ValueError, match="signature mismatch"):
        run_resilient(problem, 7, other_cfg, run_dir=run_dir)
    with pytest.raises(ValueError, match="mismatch"):
        run_resilient(problem, 8, cfg, run_dir=run_dir)
    with pytest.raises(ValueError, match="mismatch"):
        run_resilient(_problem_with_offset(2.5), 7, cfg, run_dir=run_dir)


def _problem_with_offset(offset):
    p = _problem()
    return ising.IsingProblem.create(np.asarray(p.couplings),
                                     np.asarray(p.fields), offset=offset)


# --------------------------------------------------------------- budgets

def test_budget_max_steps(problem):
    cfg = _cfg("rwa", "bitplane")
    res = run_resilient(problem, 7, cfg, budget=BudgetConfig(max_steps=40))
    assert res.stop_reason == STOP_MAX_STEPS
    assert res.steps_done == 40 and res.chunks_done == 2
    # The partial result is the best-so-far after exactly those chunks.
    assert np.isfinite(np.asarray(res.result.best_energy)).all()
    assert np.asarray(res.result.trace_energy).shape == (2, REPLICAS)


def test_budget_deadline(problem):
    cfg = _cfg("rwa", "bitplane")
    res = run_resilient(problem, 7, cfg,
                        budget=BudgetConfig(deadline_seconds=0.0))
    assert res.stop_reason == STOP_DEADLINE
    assert res.chunks_done == 0


def test_budget_target_energy(problem):
    cfg = _cfg("rwa", "bitplane")
    # A target above the initial energy is hit immediately...
    res = run_resilient(problem, 7, cfg,
                        budget=BudgetConfig(target_energy=1e9))
    assert res.stop_reason == STOP_TARGET and res.chunks_done == 0
    # ...an unreachable one never fires.
    res = run_resilient(problem, 7, cfg,
                        budget=BudgetConfig(target_energy=-1e9))
    assert res.stop_reason == STOP_COMPLETED


def test_budget_stop_then_resume_to_parity(problem, tmp_path):
    cfg = _cfg("rwa", "bitplane")
    run_dir = str(tmp_path / "run")
    res = run_resilient(problem, 7, cfg, run_dir=run_dir,
                        budget=BudgetConfig(max_steps=60))
    assert res.stop_reason == STOP_MAX_STEPS and res.chunks_done == 3
    res = run_resilient(problem, 7, cfg, run_dir=run_dir)
    assert res.resumed_from_chunk == 3
    assert res.stop_reason == STOP_COMPLETED
    _assert_same_solve(solve(problem, 7, cfg, backend="fused"), res.result)


def test_keyboard_interrupt_returns_best_so_far(problem, tmp_path):
    cfg = _cfg("rwa", "bitplane")
    run_dir = str(tmp_path / "run")

    def interrupt(kind, info):
        if kind == "chunk" and info["chunk"] == 2:
            raise KeyboardInterrupt()

    res = run_resilient(problem, 7, cfg, run_dir=run_dir, on_event=interrupt)
    assert res.stop_reason == STOP_INTERRUPTED
    assert res.chunks_done == 2
    assert np.asarray(res.result.trace_energy).shape == (2, REPLICAS)
    # The interrupt frontier was snapshotted; a follow-up run finishes.
    res = run_resilient(problem, 7, cfg, run_dir=run_dir)
    assert res.resumed_from_chunk == 2
    _assert_same_solve(solve(problem, 7, cfg, backend="fused"), res.result)


# ---------------------------------------------------------- tier fallback

def test_is_allocation_failure_classification():
    assert is_allocation_failure(fake_oom())
    assert is_allocation_failure(MemoryError("x"))
    assert is_allocation_failure(RuntimeError("Failed to allocate 8 bytes"))
    assert is_allocation_failure(RuntimeError("host OOM while staging J"))
    assert not is_allocation_failure(ValueError("J must be symmetric"))
    # Markers match whole words only.
    assert not is_allocation_failure(RuntimeError("no room left in the zoom"))
    assert not is_allocation_failure(RuntimeError("bloomfilter misconfigured"))
    assert not is_allocation_failure(_MOSAIC_VMEM_REFUSAL)


#: The shape of the TPU compiler's refusal of a kernel whose blocks overflow
#: its scoped VMEM: it names memory, but no coupling tier can fix it.
_MOSAIC_VMEM_REFUSAL = RuntimeError(
    "INTERNAL: Mosaic failed to compile TPU kernel: Out of memory while "
    "trying to allocate 40.00M of scoped vmem; scoped allocation exceeds "
    "the vmem_limit_bytes of the kernel")


def test_mosaic_compile_error_propagates_without_downgrade(problem):
    """A compile refusal inside an "auto" run is raised as it is — never
    retried one tier down (which would hide the broken kernel behind a
    slower, silently chosen store)."""
    cfg = _cfg("rwa", "auto")
    events = []

    def refuse(site, info):
        if site == "chunk_start":
            raise _MOSAIC_VMEM_REFUSAL

    with inject_faults(refuse):
        with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
            run_resilient(problem, 7, cfg,
                          on_event=lambda k, i: events.append(k))
    assert "tier_downgrade" not in events


def test_next_tier_ladder(problem):
    assert next_tier("dense", problem, None) == "bitplane"
    assert next_tier("bitplane", problem, None) == "bitplane_hbm"
    assert next_tier("bitplane_hbm", problem, None) is None  # no mesh
    assert next_tier("bitplane_sharded", problem, None) is None
    frac = ising.IsingProblem.create(
        np.array([[0.0, 0.5], [0.5, 0.0]], np.float32))
    assert next_tier("dense", frac, None) is None  # fractional J stays dense


def test_downgrade_chain_on_build_oom(problem):
    cfg = _cfg("rwa", "auto")
    mono = solve(problem, 7, cfg, backend="fused")
    with inject_faults(oom_once_hook("store_build",
                                     fmts=("dense", "bitplane"))):
        res = run_resilient(problem, 7, cfg)
    assert [d[:2] for d in res.downgrades] == [
        ("dense", "bitplane"), ("bitplane", "bitplane_hbm")]
    _assert_same_solve(mono, res.result)   # tiers are trajectory-identical


def test_downgrade_midrun_restores_from_snapshot(problem, tmp_path):
    cfg = _cfg("rwa", "auto")
    mono = solve(problem, 7, cfg, backend="fused")
    run_dir = str(tmp_path / "run")
    events = []
    with inject_faults(oom_once_hook("chunk_start", at_chunk=3)):
        res = run_resilient(problem, 7, cfg, run_dir=run_dir,
                            on_event=lambda k, i: events.append((k, i)))
    assert res.downgrades == (("dense", "bitplane", 3),)
    assert ("tier_downgrade" in [k for k, _ in events])
    # Work before the OOM survived: the post-downgrade attempt resumed.
    assert any(k == "resume" and i["chunk"] == 3 for k, i in events)
    _assert_same_solve(mono, res.result)
    # The recorded downgrade survives in the final snapshot.
    res2 = run_resilient(problem, 7, cfg, run_dir=run_dir)
    assert res2.downgrades == (("dense", "bitplane", 3),)


def test_explicit_format_propagates_oom(problem):
    cfg = _cfg("rwa", "dense")   # not "auto": the ladder is disabled
    with inject_faults(oom_once_hook("store_build", fmts=("dense",))):
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            run_resilient(problem, 7, cfg)


def test_non_alloc_error_propagates(problem):
    cfg = _cfg("rwa", "auto")

    def bad(site, info):
        if site == "chunk_start":
            raise ValueError("some real bug")

    with inject_faults(bad):
        with pytest.raises(ValueError, match="some real bug"):
            run_resilient(problem, 7, cfg)
