"""Device microseconds per anneal step in the `mcmc_sweep` kernel, summed over
every event of the traced window."""
from chipbench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.op_seconds(run.trace, lambda n, k: k and n == "mcmc_sweep")
    return 1e6 * t / run.steps if t > 0 else None
