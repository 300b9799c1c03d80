"""The replica success share p of tts99_s, in percent: (hits + 1/2) /
(trials + 1) over every replica of every solve. It tells a change of
quality from a change of speed."""
from chipbench import stats


def read(run):
    if run.target_energy is None:
        return None
    be = [e for s in run.solves for e in s.best_energy]
    hits = sum(e <= run.target_energy for e in be)
    return 100.0 * stats.smoothed_success(hits, len(be))
