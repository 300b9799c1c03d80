"""The program's own spans and counters in a run of a cell.

``repro.core.resilience.run_resilient`` opens ``jax.profiler.TraceAnnotation``
spans named ``snowball.<step>`` around each solve (``solve``) and its
steps: the two identity hashes (``fingerprint``, ``what=signature`` or
``what=fingerprint``), each ``runner_build``, ``init``, every ``chunk``
dispatch and ``finalize``. Each carries the solve's id. They land in the
same xplane as the device ops and the benchmark's spans, on the same clock.
:func:`load` reads them, clipped to a :class:`chipbench.trace.Trace`'s
window, and :func:`idle_by_program_span` splits the device's idle time by
the innermost one open, by exact overlap.

The readers at the end take a ``chipbench.run.Run``. They find the spans at
``run.trace.program_spans`` and the fused runner's rows-fetched counter at
each solve's ``rows_fetched``. Where either is absent (an untraced run, or a
program without the spans or the counter), they return None.
"""
from __future__ import annotations

import heapq
from typing import Optional

from chipbench import trace as tr

PROGRAM_SPAN_PREFIX = "snowball."
#: Idle time while no program span is open.
OUTSIDE = "outside"


def load(path: str, window: tuple) -> list:
    """The program spans of an xplane file as ``(name, start, end, ids)``,
    the prefix dropped, clipped to ``window``, in order of start."""
    from jax.profiler import ProfileData

    lo, hi = window
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if (e.name.startswith(PROGRAM_SPAN_PREFIX)
                        and b > lo and a < hi):
                    spans.append((e.name[len(PROGRAM_SPAN_PREFIX):],
                                  max(a, lo), min(b, hi), dict(e.stats)))
    return sorted(spans, key=lambda s: s[1])


def innermost(spans, lo: float, hi: float) -> list:
    """``[lo, hi)`` cut into ``(start, end, name)`` pieces, each named by the
    innermost span open throughout it (the one opened last; of two opened
    together, the one that closes first), or :data:`OUTSIDE`."""
    points = sorted({lo, hi} | {t for _, a, b, *_ in spans for t in (a, b)
                                if lo < t < hi})
    order = sorted(spans, key=lambda s: s[1])
    heap, i, pieces = [], 0, []
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][1] <= a:
            name, s, e, *_ = order[i]
            heapq.heappush(heap, (-s, e, i, name))
            i += 1
        while heap and heap[0][1] <= a:     # closed: never open again
            heapq.heappop(heap)
        pieces.append((a, b, heap[0][3] if heap else OUTSIDE))
    return pieces


def idle_by_program_span(trace: tr.Trace, spans, top: int = 10) -> list:
    """[[span, seconds], ...]: the device's idle time split by exact overlap
    with the innermost program span open, largest first."""
    acc: dict = {}
    gaps = tr.idle_gaps(trace)
    pieces = innermost(spans, *trace.window)
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            acc[name] = acc.get(name, 0.0) + (min(b, pb) - max(a, pa)) * 1e-9
            k += 1
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            ][:top]


# --------------------------------------------------------------------------
# Readers of the per-layer numbers.

def _spans(run) -> Optional[list]:
    spans = getattr(run.trace, "program_spans", None)
    return spans or None


def span_ms_per_solve(run, name: str) -> Optional[float]:
    """Host milliseconds per solve of the window inside ``snowball.<name>``
    spans."""
    spans = _spans(run)
    if spans is None or not any(s[0] == name for s in spans):
        return None
    total = sum(b - a for n, a, b, _ in spans if n == name)
    return total * 1e-6 / len(run.solves)


def idle_share_under(run, name: str) -> Optional[float]:
    """Percent of the window in which the device is idle and the innermost
    open program span is ``snowball.<name>``."""
    spans = _spans(run)
    if spans is None:
        return None
    idle = dict(idle_by_program_span(run.trace, spans, top=None))
    return 100.0 * idle.get(name, 0.0) / run.trace.window_s


def fingerprint_ms_per_solve(run) -> Optional[float]:
    return span_ms_per_solve(run, "fingerprint")


def chunk_dispatch_ms_per_solve(run) -> Optional[float]:
    return span_ms_per_solve(run, "chunk")


def fingerprint_idle_share(run) -> Optional[float]:
    return idle_share_under(run, "fingerprint")


def mcmc_sweep_rows_per_step(run) -> Optional[float]:
    """Coupling rows the sweep kernel fetched per anneal step, summed over
    the replicas: the program's counter over every solve of the window."""
    counts = [getattr(s, "rows_fetched", None) for s in run.solves]
    if not counts or any(c is None for c in counts):
        return None
    return float(sum(int(c.sum()) for c in counts)) / run.steps
