"""Reduce a JAX profiler trace to the benchmark's device numbers.

From the ``.xplane.pb`` that ``jax.profiler`` writes:

- the window: the host annotation ``bench.window`` the harness opens
  around the measured loop;
- device busy time: per chip, the union of the intervals in which an
  operation ran (the ``XLA Ops`` line of each ``/device:TPU:n``
  plane), clipped to the window, averaged over the chips used;
- device time by operation name, for the metric readers to pick their
  kernels from by a stable name pattern of their own;
- idle gaps on chip 0, each named by the innermost host span that
  covers its midpoint (the harness's own annotations, and the
  program's spans mapped onto the trace's clock), or ``unattributed``.

A trace with no device plane, or with no operation in the window, is
an error: a traced run that saw no device work measured nothing.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
#: host spans looked at before a gap's midpoint when naming it
SCAN = 1024


class TraceError(RuntimeError):
    pass


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"the profiler wrote no trace under {log_dir}")
    return paths[-1]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    t = lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


class Trace:
    """Plain lists read out of one xplane file: per device plane its
    op events, and host events, as (name, start_ns, end_ns)."""

    def __init__(self, devices: Dict[str, list], host: list, lines: dict):
        self.devices = devices
        self.host = host
        #: {plane: {line: events}}, for a reader who must find names
        self.lines = lines

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        if path.endswith(".gz"):
            import gzip

            with gzip.open(path, "rb") as f:
                pd = ProfileData.from_serialized_xspace(f.read())
        else:
            pd = ProfileData.from_file(path)
        devices: Dict[str, list] = {}
        host: list = []
        lines: dict = {}
        for plane in pd.planes:
            lines[plane.name] = {
                line.name: sum(1 for _ in line.events) for line in plane.lines
            }
            if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
                evs = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs.extend(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                        )
                devices[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    )
        return cls(devices, host, lines)

    def window(self) -> Tuple[float, float]:
        marks = [(a, b) for n, a, b in self.host if n == WINDOW]
        if len(marks) != 1:
            raise TraceError(f"{len(marks)} '{WINDOW}' annotations in the trace")
        return marks[0]


def reduce(
    trace: Trace,
    n_chips: int,
    spans: Optional[List[Tuple[str, float, float]]] = None,
    top: int = 10,
) -> dict:
    """Device numbers for the window. ``spans`` are extra host spans
    already on the trace's clock."""
    lo, hi = trace.window()
    window_s = (hi - lo) / 1e9
    planes = sorted(trace.devices, key=lambda n: int(n[12:]))[:n_chips]
    if not planes or not any(trace.devices[p] for p in planes):
        raise TraceError(
            f"no device operations in the trace (planes and lines: {trace.lines})")
    busy_s = []
    for p in planes:
        iv = union(clip([(a, b) for _, a, b in trace.devices[p]], lo, hi))
        busy_s.append(sum(b - a for a, b in iv) / 1e9)
    by_name: Dict[str, float] = {}
    for p in planes:
        for name, a, b in trace.devices[p]:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    chip0 = union(clip([(a, b) for _, a, b in trace.devices[planes[0]]], lo, hi))
    host = sorted(
        [(a, b, n) for n, a, b in trace.host if n != WINDOW and b > a]
        + [(a, b, n) for n, a, b in spans or []]
    )
    starts = [h[0] for h in host]
    named = []
    for a, b in gaps(chip0, lo, hi):
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid)
        cover = [(hb - ha, n) for ha, hb, n in host[max(0, i - SCAN):i]
                 if hb >= mid]
        named.append([min(cover)[1] if cover else "unattributed", (b - a) / 1e9])
    named.sort(key=lambda x: -x[1])
    ops = sorted(by_name.items(), key=lambda x: -x[1])
    busy = sum(busy_s) / len(busy_s)
    return {
        "window_s": window_s,
        "busy_s": busy,
        "idle_pct": 100.0 * (1.0 - busy / window_s) if window_s > 0 else None,
        "op_s": by_name,
        "device_ops": [[n, s] for n, s in ops[:top]],
        "idle_gaps": named[:top],
        "n_gaps": len(named),
    }
