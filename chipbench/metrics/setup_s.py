"""Set-up: process start to the first solve of the window — imports, the
instance, the coupling store, and the warm-up solve that compiles (or loads
from the persistent cache) every program the window runs."""


def read(run):
    return run.setup_s
