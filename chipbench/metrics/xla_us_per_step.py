"""Device microseconds per anneal step of every traced op that is not a
Pallas kernel: the chunk driver's threefry uniforms, temperature schedule,
best-so-far merge, and the copies around them."""
from chipbench import trace as tr


def read(run):
    if run.trace is None:
        return None
    return 1e6 * tr.op_seconds(run.trace, lambda n, k: not k) / run.steps
