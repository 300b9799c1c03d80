"""Time to solution at 99% (paper Eq. 32). t_a is the elapsed window over
the completed solves; p is the replica success share pooled over every
replica of every solve, (hits + 1/2) / (trials + 1); a solve of R replicas
succeeds with P = 1 − (1 − p)^R."""
from chipbench import stats


def read(run):
    if run.target_energy is None:
        return None
    be = [e for s in run.solves for e in s.best_energy]
    p = stats.smoothed_success(sum(e <= run.target_energy for e in be), len(be))
    return stats.tts(run.window_s / len(run.solves), p, run.replicas)
