"""95th percentile, over every solve of the window, of the time from the
run_resilient call to its best energies being on the host."""
from chipbench import stats


def read(run):
    if len(run.solves) < 2:
        return None
    return stats.percentile([s.t_ready - s.t_call for s in run.solves], 95)
