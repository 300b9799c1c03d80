"""Mean host milliseconds inside each run_resilient call of the window. The
call returns before the device finishes, so this is the supervisor's and
the runners' own host work: signature and fingerprint, store or plan, and
dispatching every chunk."""


def read(run):
    return 1e3 * sum(s.t_return - s.t_call for s in run.solves) / len(run.solves)
