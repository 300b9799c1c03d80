"""HBM roofline arithmetic for the sweep kernels: bytes from the shapes
and the program's counters, time from the trace, peak from peaks.json."""
from __future__ import annotations

from . import trace as tr


def flips(run) -> int:
    """Spins the program flipped over every solve of the window."""
    return int(sum(int(s.flips.sum()) for s in run.solves))


def roofline_share(run, kernel: str, nbytes: float):
    """100 · (nbytes / peak HBM bytes/s) / the kernel's device seconds, or
    None where the trace holds no event of the kernel."""
    t = tr.op_seconds(run.trace, lambda n, k: k and n == kernel)
    if t <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t
