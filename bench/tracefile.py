"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read with ``jax.profiler.ProfileData``: device planes are named
``/device:<KIND>:<n>``, and the operations a device ran lie on their
``XLA Ops`` line (whole programs on ``XLA Modules``).  The traced window is
the host span the harness opens around the traced part of a run
(:data:`WINDOW_SPAN`); device events and host spans share the profiler's
clock.  Busy time is the union of the operation intervals inside the
window, averaged over the chips used.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_COLLECTIVE_PERMUTE = re.compile(r"collective-permute", re.IGNORECASE)


@dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float  # union of op intervals, mean over chips
    module_s: dict = field(default_factory=dict)  # program name -> s, chip 0
    op_s: dict = field(default_factory=dict)  # op name -> s, chip 0
    collective_permute_s: float = 0.0  # chip 0
    idle_gaps: list = field(default_factory=list)  # [(host span, s)]

    def idle_percent(self) -> float | None:
        """Share of the window in which no operation ran on the device:
        1 - busy / window, busy being the union of operation intervals."""
        if self.window_s <= 0.0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_s(self, pattern: str) -> float:
        """Device seconds of the programs whose name contains ``pattern``."""
        return sum(s for name, s in self.module_s.items() if pattern in name)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _device_index(name: str) -> int | None:
    m = re.fullmatch(r"/device:([A-Za-z_]+):(\d+)", name)
    if m is None or m.group(1).upper() == "CPU":
        return None
    return int(m.group(2))


def summarize(planes, chips: int) -> TraceSummary | None:
    """Reduce profile planes (objects with ``name`` and ``lines``; each line
    has ``name`` and ``events`` with ``name``, ``start_ns``,
    ``duration_ns``) to a :class:`TraceSummary`.  Returns None where the
    trace holds no window span or no device operation."""
    host_spans = []
    devices = {}
    for plane in planes:
        idx = _device_index(plane.name)
        if idx is not None:
            if idx < chips:
                devices[idx] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    host_spans.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    window_s = (hi - lo) * 1e-9

    busy = []
    first = None
    module_s: dict = {}
    op_s: dict = {}
    cp_s = 0.0
    for idx in sorted(devices):
        lines = {ln.name: ln for ln in devices[idx].lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:
            continue
        iv = []
        for ev in ops.events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            iv.append((s, e))
            if first is None or idx == first:
                first = idx
                d = (min(e, hi) - max(s, lo)) * 1e-9
                name = ev.name.split(" = ", 1)[0]
                op_s[name] = op_s.get(name, 0.0) + d
                if _COLLECTIVE_PERMUTE.search(ev.name):
                    cp_s += d
        merged = _clip(_union(iv), lo, hi)
        busy.append(merged)
        if idx == first and MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                d = (min(e, hi) - max(s, lo)) * 1e-9
                module_s[ev.name] = module_s.get(ev.name, 0.0) + d
    if not busy or not any(busy):
        return None
    busy_s = sum(sum(e - s for s, e in m) for m in busy) * 1e-9 / len(busy)

    # Idle gaps of the first chip, each named by the innermost benchmark
    # span that covers its middle (what the host was doing meanwhile).
    edges = [lo] + [x for iv in busy[0] for x in iv] + [hi]
    longest = heapq.nlargest(
        10, ((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]))
    )
    inner = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in host_spans
             if n != WINDOW_SPAN]
    gaps = []
    for length, a, b in longest:
        if length <= 0:
            continue
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in inner if s <= mid <= e]
        gaps.append((min(cover)[1] if cover else "host", length * 1e-9))
    return TraceSummary(window_s=window_s, chips=len(busy), busy_s=busy_s,
                        module_s=module_s, op_s=op_s,
                        collective_permute_s=cp_s, idle_gaps=gaps)


def summarize_file(path: str, chips: int) -> TraceSummary | None:
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(path).planes, chips)
