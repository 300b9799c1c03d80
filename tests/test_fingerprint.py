"""Run identity: ``problem_fingerprint`` and ``run_signature`` keep their
values, hash an immutable problem's bytes once per object, and still hash a
NumPy-backed problem on every call."""
import hashlib

import numpy as np
import pytest

import jax

from repro.core import ising, schedules
from repro.core.resilience import (STOP_COMPLETED, fingerprint_cached,
                                   problem_fingerprint, run_resilient,
                                   run_signature)
from repro.core.solver import SolverConfig, solve
from repro.checkpoint import manager as ckpt

from fault_injection import SimulatedCrash, kill_after_chunk_hook

N = 32
STEPS = 40
CHUNK = 20
REPLICAS = 4

# A fixed 4-spin instance and the fingerprints its two forms have always
# had (snapshots on disk carry these strings).
J4 = np.array([[0, 1, -2, 0], [1, 0, 3, -1], [-2, 3, 0, 1], [0, -1, 1, 0]],
              np.float32)
H4 = np.array([0.5, -1.0, 0.0, 2.0], np.float32)
GOLDEN = dict(
    dense="ab733e24b5b19037570b227fb0897f417b39785b40bf8b83bc080dc48cdb1b13",
    edges="dba20ac50e4737eaed86f74211164fb74c7d6b1cb1f817104cd6a6649574c02a",
)


def _four(kind):
    if kind == "dense":
        return ising.IsingProblem.create(J4, H4, offset=1.5)
    return ising.IsingProblem.create_sparse(ising.EdgeList.from_dense(J4),
                                            H4, offset=-0.5)


def _couplings(seed=0, flip=False):
    g = np.random.default_rng(seed)
    J = np.triu(g.choice([-1.0, 1.0], size=(N, N)), 1).astype(np.float32)
    if flip:
        J[0, 1] = -J[0, 1]
    return J + J.T, g.normal(size=(N,)).astype(np.float32)


def _problem(kind="dense", **kw):
    J, h = _couplings(**kw)
    if kind == "dense":
        return ising.IsingProblem.create(J, h, offset=1.5)
    return ising.IsingProblem.create_sparse(ising.EdgeList.from_dense(J), h,
                                            offset=1.5)


def _cfg(fmt="dense"):
    return SolverConfig(num_steps=STEPS,
                        schedule=schedules.linear(3.0, 0.1, STEPS),
                        num_replicas=REPLICAS, trace_every=CHUNK,
                        coupling_format=fmt)


def _formula(problem) -> str:
    """The identity formula written out, independent of the program."""
    h = hashlib.sha256()
    if problem.couplings is not None:
        J = np.ascontiguousarray(jax.device_get(problem.couplings))
        h.update(b"dense")
        h.update(repr(J.shape).encode())
        h.update(J.tobytes())
    else:
        e = problem.edges
        d = hashlib.sha256()
        d.update(str(e.num_spins).encode())
        for a in (e.rows, e.cols, e.weights):
            d.update(a.tobytes())
        h.update(b"edges")
        h.update(d.digest())
    h.update(np.ascontiguousarray(jax.device_get(problem.fields)).tobytes())
    h.update(np.float64(problem.offset).tobytes())
    return h.hexdigest()


def _signature_formula(problem, seed, config, backend, chunk_steps) -> str:
    parts = "|".join([f"seed={seed}", f"backend={backend}",
                      f"chunk_steps={chunk_steps}", f"config={config!r}",
                      "mesh=None", f"problem={_formula(problem)}"])
    return hashlib.sha256(parts.encode()).hexdigest()


@pytest.fixture
def hashes(monkeypatch):
    """Counts the calls of the hashing helper behind both identities."""
    calls = []
    inner = ising.content_fingerprint

    def counting(problem):
        calls.append(problem)
        return inner(problem)

    monkeypatch.setattr(ising, "content_fingerprint", counting)
    return calls


# ------------------------------------------------------------ same values

@pytest.mark.parametrize("kind", ["dense", "edges"])
def test_fingerprint_and_signature_equal_the_formula(kind):
    p = _four(kind)
    assert problem_fingerprint(p) == GOLDEN[kind] == _formula(p)
    assert problem_fingerprint(p) == GOLDEN[kind]       # the kept value
    cfg = _cfg()
    for seed in (7, 8):
        assert (run_signature(p, seed, cfg, backend="fused", chunk_steps=CHUNK,
                              mesh=None)
                == _signature_formula(p, seed, cfg, "fused", CHUNK))


@pytest.mark.parametrize("kind", ["dense", "edges"])
def test_a_remade_object_starts_uncached_with_the_same_value(kind, hashes):
    p = _four(kind)
    fp = problem_fingerprint(p)
    leaves, tree = jax.tree_util.tree_flatten(p)
    q = jax.tree_util.tree_unflatten(tree, leaves)
    assert fingerprint_cached(p) and not fingerprint_cached(q)
    assert problem_fingerprint(q) == fp
    assert len(hashes) == 2 and fingerprint_cached(q)


# ------------------------------------------------------------ hashed once

@pytest.mark.parametrize("kind,fmt", [("dense", "dense"),
                                      ("edges", "bitplane")])
def test_repeat_solves_of_one_problem_hash_it_once(kind, fmt, hashes):
    p, cfg = _problem(kind), _cfg(fmt)
    for seed in (7, 8):
        rr = run_resilient(p, seed, cfg, backend="fused", chunk_steps=CHUNK)
        assert rr.stop_reason == STOP_COMPLETED
        mono = solve(p, seed, cfg, backend="fused")
        for field in ("best_energy", "best_spins", "final_energy",
                      "num_flips", "trace_energy"):
            np.testing.assert_array_equal(
                np.asarray(getattr(mono, field)),
                np.asarray(getattr(rr.result, field)), err_msg=field)
    assert hashes == [p]
    assert problem_fingerprint(p) == _formula(p)


@pytest.mark.parametrize("kind,where", [("dense", "J"), ("dense", "h"),
                                        ("edges", "h")])
def test_a_numpy_problem_mutated_in_place_is_hashed_again(kind, where,
                                                          hashes):
    J, h = _couplings()
    if kind == "dense":
        p = ising.IsingProblem(couplings=J, fields=h, offset=0.0)
    else:
        p = ising.IsingProblem(couplings=None, fields=h, offset=0.0,
                               edges=ising.EdgeList.from_dense(J))
    assert not p.immutable
    before = problem_fingerprint(p)
    assert problem_fingerprint(p) == before == _formula(p)
    if where == "J":
        J[0, 1] = J[1, 0] = 5.0
    else:
        h[3] += 1.0
    after = problem_fingerprint(p)
    assert after != before and after == _formula(p)
    assert not fingerprint_cached(p) and len(hashes) == 3


# ------------------------------------------------------------ resume

def test_snapshots_keep_the_formula_and_resume_onto_a_new_object(tmp_path):
    p, cfg = _problem(), _cfg("dense")
    run_dir = str(tmp_path / "run")
    with pytest.raises(SimulatedCrash):
        run_resilient(p, 7, cfg, run_dir=run_dir, chunk_steps=CHUNK,
                      on_event=kill_after_chunk_hook(1))
    extra = ckpt.read_manifest(run_dir, 1)["extra"]
    assert extra["fingerprint"] == _formula(p)
    assert extra["signature"] == _signature_formula(p, 7, cfg, "fused",
                                                    CHUNK)
    twin = _problem()           # same content, a new object: nothing kept
    assert not fingerprint_cached(twin)
    res = run_resilient(twin, 7, cfg, run_dir=run_dir, chunk_steps=CHUNK)
    assert res.resumed_from_chunk == 1 and res.stop_reason == STOP_COMPLETED
    np.testing.assert_array_equal(
        np.asarray(solve(p, 7, cfg, backend="fused").best_energy),
        np.asarray(res.result.best_energy))


def test_another_problem_object_with_other_couplings_is_refused(tmp_path):
    p, other = _problem(), _problem(flip=True)
    cfg = _cfg("dense")
    run_dir = str(tmp_path / "run")
    with pytest.raises(SimulatedCrash):
        run_resilient(p, 7, cfg, run_dir=run_dir, chunk_steps=CHUNK,
                      on_event=kill_after_chunk_hook(1))
    assert problem_fingerprint(other) != problem_fingerprint(p)
    assert fingerprint_cached(p) and fingerprint_cached(other)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        run_resilient(other, 7, cfg, run_dir=run_dir, chunk_steps=CHUNK)
