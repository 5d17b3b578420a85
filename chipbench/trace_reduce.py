"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A traced run wraps its window in a host annotation named ``WINDOW``; the
reduction keeps what falls inside it:

  devices   per TPU device plane, its ``XLA Ops`` events (one per HLO op
            or kernel launch) and ``XLA Modules`` events (one per program
            execution, named after the jitted function);
  host      every host-thread event (dispatches, the harness's own
            annotations), to say what the host was doing in a gap.

Busy time is the union of a device's op intervals inside the window; idle
is the rest. Times are in seconds.
"""
from __future__ import annotations

import bisect
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "chipbench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


class Events:
    """One line's events inside the window: names, starts and ends (ns),
    clipped to the window."""

    def __init__(self, names: List[str], starts, ends):
        self.names = names
        self.starts = np.asarray(starts, np.float64)
        self.ends = np.asarray(ends, np.float64)

    def total_s(self, pattern: str) -> float:
        """Summed duration of the events whose name matches ``pattern``."""
        rx = re.compile(pattern)
        keep = [i for i, n in enumerate(self.names) if rx.search(n)]
        if not keep:
            return 0.0
        return float(np.sum(self.ends[keep] - self.starts[keep])) * 1e-9

    def count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(1 for n in self.names if rx.search(n))

    def by_name(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n, s, e in zip(self.names, self.starts, self.ends):
            out[n] = out.get(n, 0.0) + (e - s) * 1e-9
        return out


class Trace:
    def __init__(self, window: Tuple[float, float], ops: Dict[int, Events],
                 modules: Dict[int, Events],
                 host: List[Tuple[float, float, str]]):
        self.window = window
        self.ops = ops
        self.modules = modules
        self.host = sorted(host)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy_intervals(self, dev: int) -> List[Tuple[float, float]]:
        ev = self.ops[dev]
        order = np.argsort(ev.starts)
        merged: List[List[float]] = []
        for s, e in zip(ev.starts[order], ev.ends[order]):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_s(self, dev: int) -> float:
        return sum(e - s for s, e in self.busy_intervals(dev)) * 1e-9

    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s(d) for d in self.devices]))

    def idle_gaps(self, dev: int, top: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps in ``dev``'s busy time, each labelled with the
        most specific host event that covers most of it."""
        w0, w1 = self.window
        gaps, t = [], w0
        for s, e in self.busy_intervals(dev):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        starts = [h[0] for h in self.host]
        out = []
        for g0, g1 in gaps[:top]:
            best, best_len = "host: no event", None
            hi = bisect.bisect_right(starts, g1)
            for s, e, name in self.host[:hi]:
                if e <= g0 or name == WINDOW:
                    continue
                cover = min(e, g1) - max(s, g0)
                if cover >= 0.5 * (g1 - g0) and \
                        (best_len is None or e - s < best_len):
                    best, best_len = name, e - s
            out.append((best, (g1 - g0) * 1e-9))
        return out


def find_xplane(trace_dir: str) -> str:
    for root, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb") or f.endswith(".xplane.pb.gz"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def load(path: str) -> Trace:
    """Read a trace (a directory, an ``.xplane.pb`` or a gzipped one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)

    host_raw: List[Tuple[float, float, str]] = []
    dev_lines: Dict[int, Dict[str, list]] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = dev_lines.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns)
                                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host_raw.append((e.start_ns, e.start_ns + e.duration_ns,
                                     f"{line.name}: {e.name}"
                                     if e.name != WINDOW else WINDOW))
    wins = [(s, e) for s, e, n in host_raw if n == WINDOW]
    if wins:
        window = max(wins, key=lambda w: w[1] - w[0])
    else:
        spans = [(s, e) for lines in dev_lines.values()
                 for evs in lines.values() for _n, s, e in evs]
        if not spans:
            raise ValueError(f"{path}: no device events and no {WINDOW}")
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    w0, w1 = window

    def clip(evs):
        names, starts, ends = [], [], []
        for n, s, e in evs:
            if e > w0 and s < w1:
                names.append(n)
                starts.append(max(s, w0))
                ends.append(min(e, w1))
        return Events(names, starts, ends)

    ops = {d: clip(lines.get("XLA Ops", [])) for d, lines in dev_lines.items()}
    modules = {d: clip(lines.get("XLA Modules", []))
               for d, lines in dev_lines.items()}
    host = [(max(s, w0), min(e, w1), n) for s, e, n in host_raw
            if e > w0 and s < w1]
    return Trace(window, ops, modules, host)


def breakdown(trace: Trace, top: int = 10) -> Optional[Dict]:
    """The device programs that took most time (summed over devices) and
    device 0's longest idle gaps, labelled by what the host was doing."""
    if not trace.devices:
        return None
    totals: Dict[str, float] = {}
    for d in trace.devices:
        for name, s in trace.modules[d].by_name().items():
            totals[name] = totals.get(name, 0.0) + s
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in
                          trace.idle_gaps(trace.devices[0], top)]}


def summary(path: str, top: int = 15) -> str:
    """Every plane and line of a trace with its event count and its most
    frequent event names: what to look at before writing a reader."""
    from collections import Counter

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            names = Counter(e.name for e in line.events)
            out.append(f"  line {line.name!r}: {sum(names.values())} events")
            for n, c in names.most_common(top):
                out.append(f"    {c:7d}  {n[:160]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summary(sys.argv[1]))
