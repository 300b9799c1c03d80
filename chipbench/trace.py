"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps attributed to what the host was doing.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device operations are the events of the device planes' op line;
host spans are the ``TraceAnnotation`` events the benchmark records, whose
names start with :data:`SPAN_PREFIX`. One span named ``<prefix>window``
bounds the measured window; everything is clipped to it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Optional

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
#: Plane and line of the device operations in a TPU trace.
TPU_PLANE_PREFIX = "/device:TPU:"
TPU_OP_LINE = "XLA Ops"
#: How a Pallas (Mosaic) kernel shows in its op's HLO text.
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def op_name(raw: str) -> str:
    """The HLO instruction name of a trace op without its "%" and ".N"
    suffix: "%mcmc_sweep.1 = (...) custom-call(...)" -> "mcmc_sweep"."""
    head = raw.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


@dataclasses.dataclass
class Trace:
    """Events in nanoseconds on the profiler's clock. ``ops`` holds one list
    of ``(name, start, end, is_kernel)`` per device, ``is_kernel`` true for
    a Pallas kernel; ``spans`` the benchmark's host spans ``(name, start,
    end)``; ``window`` the measured window."""

    ops: list
    spans: list
    window: tuple

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, *, device_plane: Callable[[str], bool] = None,
         op_line: Callable[[str], bool] = None) -> Trace:
    """Read an xplane file. By default the device planes are the TPU planes
    and their op line is "XLA Ops"; a test on the CPU passes predicates for
    the host threads that run XLA:CPU programs instead."""
    from jax.profiler import ProfileData

    device_plane = device_plane or (lambda n: n.startswith(TPU_PLANE_PREFIX))
    op_line = op_line or (lambda n: n == TPU_OP_LINE)
    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        if device_plane(plane.name):
            evs = []
            for line in plane.lines:
                if op_line(line.name):
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns,
                                KERNEL_MARK in e.name) for e in line.events)
            if evs:
                ops.append(sorted(evs, key=lambda e: e[1]))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    clipped = [[(n, max(a, lo), min(b, hi), k) for n, a, b, k in dev
                if b > lo and a < hi] for dev in ops]
    return Trace(ops=clipped, spans=spans, window=(lo, hi))


def busy_intervals(events) -> list:
    """The union of the events' intervals, as sorted disjoint (start, end)."""
    merged = []
    for _, a, b, *_ in sorted(events, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran on the device, averaged over the
    devices."""
    if not trace.ops:
        return 0.0
    per = [sum(b - a for a, b in busy_intervals(dev)) for dev in trace.ops]
    return sum(per) / len(per) * 1e-9


def idle_share(trace: Trace) -> float:
    """1 − busy / window."""
    return 1.0 - busy_s(trace) / trace.window_s


def op_seconds(trace: Trace, pred: Callable[[str, bool], bool]) -> float:
    """Device seconds of the operations for which ``pred(name, is_kernel)``
    holds, averaged over the devices."""
    if not trace.ops:
        return 0.0
    per = [sum(b - a for n, a, b, k in dev if pred(n, k))
           for dev in trace.ops]
    return sum(per) / len(per) * 1e-9


def idle_gaps(trace: Trace, device: int = 0) -> list:
    """(start, end) of every stretch of the window with no device op."""
    gaps, cur = [], trace.window[0]
    ops = trace.ops[device] if trace.ops else []
    for a, b in busy_intervals(ops):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if trace.window[1] > cur:
        gaps.append((cur, trace.window[1]))
    return gaps


def host_activity(spans, t: float) -> str:
    """Name of the innermost host span open at time ``t`` (its prefix
    dropped), or "untraced"."""
    best: Optional[tuple] = None
    for name, a, b in spans:
        if a <= t < b and (best is None or a >= best[1]):
            best = (name, a)
    return best[0][len(SPAN_PREFIX):] if best else "untraced"


def idle_by_activity(trace: Trace, top: int = 10) -> list:
    """[[activity, seconds], ...]: the device's idle time split by what the
    host was doing at the middle of each gap, largest first."""
    acc: dict = {}
    for a, b in idle_gaps(trace):
        key = host_activity(trace.spans, (a + b) / 2)
        acc[key] = acc.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            ][:top]


def top_ops(trace: Trace, top: int = 10) -> list:
    """[[op name, seconds], ...]: the device ops that took most time."""
    acc: dict = {}
    for n, a, b, _ in (trace.ops[0] if trace.ops else []):
        acc[n] = acc.get(n, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            ][:top]
