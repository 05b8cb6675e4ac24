"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device planes (``/device:TPU:<n>``) carry one line of module
executions (``XLA Modules``: one event per run of a jitted program, named
after the jitted function) and one line of the operations inside them
(``XLA Ops``: fusions, copies and the Pallas kernels under their kernel
names). The host plane carries the benchmark's own spans
(``jax.profiler.TraceAnnotation``): ``bench.slice`` around the traced part
of the window, and inside it ``bench.step``, ``bench.submit`` and
``bench.wait``. Host and device events are on one clock in the trace.

Busy time is the union of the operation intervals inside the slice; idle
is the rest of it, and each idle gap is labelled with the innermost
benchmark span open at its middle.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

SPANS = ("bench.step", "bench.submit", "bench.wait")
SLICE = "bench.slice"
# control flow whose event spans the operations of its body
NESTING = re.compile(r"^%?(while|conditional|call)[.\s]")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over the devices traced
    devices: int
    modules: list  # (name, start_ns, end_ns)
    ops: list  # (name, start_ns, end_ns)
    spans: list  # (name, start_ns, end_ns): benchmark spans in the slice
    gaps: list  # (start_ns, end_ns): idle intervals of device 0

    def module_time(self, pattern: str) -> tuple[int, float]:
        """Runs and device seconds of the modules whose name matches."""
        rx = re.compile(pattern)
        hits = [(s, e) for n, s, e in self.modules if rx.search(n)]
        return len(hits), sum(e - s for s, e in hits) / 1e9

    def op_time(self, op_pattern: str, module_pattern: str) -> float:
        """Device seconds of the operations whose name matches
        ``op_pattern`` and that run inside a module matching
        ``module_pattern``."""
        ro, rm = re.compile(op_pattern), re.compile(module_pattern)
        spans = sorted((s, e) for n, s, e in self.modules if rm.search(n))
        if not spans:
            return 0.0
        starts = np.asarray([s for s, _ in spans])
        ends = np.asarray([e for _, e in spans])
        total = 0
        for n, s, e in self.ops:
            if not ro.search(n):
                continue
            i = np.searchsorted(starts, s, side="right") - 1
            if i >= 0 and e <= ends[i]:
                total += e - s
        return total / 1e9

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations with the most device time, under their HLO
        names, control flow that encloses other operations left out."""
        acc: dict[str, float] = {}
        for name, s, e in self.ops:
            if NESTING.match(name):
                continue
            name = name.split(" = ")[0]
            acc[name] = acc.get(name, 0.0) + (e - s) / 1e9
        return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle seconds by the benchmark span open at each gap's middle,
        then the longest single gaps, ``n`` entries at most."""
        by_label: dict[str, float] = {}
        labelled = []
        for s, e in self.gaps:
            label = self.label_at((s + e) / 2)
            by_label[label] = by_label.get(label, 0.0) + (e - s) / 1e9
            labelled.append([f"longest:{label}", (e - s) / 1e9])
        out = sorted(([k, v] for k, v in by_label.items()), key=lambda kv: -kv[1])
        labelled.sort(key=lambda kv: -kv[1])
        return (out + labelled)[:n]

    def label_at(self, t: float) -> str:
        best, width = "none", None
        for name, s, e in self.spans:
            if s <= t <= e and (width is None or e - s < width):
                best, width = name, e - s
        return best


def _union(intervals: list, lo: float, hi: float) -> tuple[float, list]:
    """Total covered length of ``intervals`` clipped to [lo, hi], and the
    uncovered gaps."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s or e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
        busy += e - max(s, cur)
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_profile(profile) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_spans, window = [], None
    device_planes = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == SLICE:
                        window = (s, e)
                    elif name in SPANS:
                        host_spans.append((name, s, e))
    if window is None:
        raise ValueError(f"trace holds no {SLICE!r} span")
    if not device_planes:
        raise ValueError("trace holds no TPU device plane")
    lo, hi = window
    busy_total, modules, ops, gaps0 = 0.0, [], [], []
    for k, plane in enumerate(sorted(device_planes, key=lambda p: p.name)):
        lines = {line.name: line for line in plane.lines}
        dev_ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        busy, gaps = _union([(s, e) for _, s, e in dev_ops], lo, hi)
        busy_total += busy
        if k == 0:
            gaps0 = gaps
            ops = [o for o in dev_ops if lo <= o[1] <= hi]
            if "XLA Modules" in lines:
                modules = [m for m in _events(lines["XLA Modules"])
                           if lo <= m[1] <= hi]
    spans = [sp for sp in host_spans if sp[2] >= lo and sp[1] <= hi]
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / len(device_planes) / 1e9,
        devices=len(device_planes),
        modules=modules, ops=ops, spans=spans, gaps=gaps0,
    )


def reduce_dir(log_dir) -> Reduction:
    """Reduce the one ``.xplane.pb`` under a profiler log directory."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_profile(ProfileData.from_file(str(files[-1])))
