"""The device trace of a window, reduced to what the metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it. The
device's plane (``/device:TPU:0``) has a line of XLA operations; busy
time is the union of their intervals, and a kernel's time is the sum of
the durations of its operations, found by the kernel's name in the
operation's name or its statistics. The host's plane holds the
benchmark's own spans (``bench.release``, ``bench.step``,
``bench.on_cycle``); each idle gap on the device is charged to the span
the host was in at the gap's middle.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: kernel -> substrings of the name its operations carry in the trace
KERNELS = {
    "paged_decode": ("paged_decode",),
    "flash_prefill": ("flash",),
}
#: the line of the device plane that holds one event per operation
OPS_LINE = "XLA Ops"
#: the benchmark's host spans
HOST_SPANS = ("bench.release", "bench.step", "bench.on_cycle")


@dataclass
class Trace:
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]
    kernel_s: Dict[str, float]
    idle_by_host: Dict[str, float]
    steps: list = field(default_factory=list)
    prefill_groups: List[Tuple[int, ...]] = field(default_factory=list)

    def kernel_seconds(self, kernel: str) -> float:
        return self.kernel_s.get(kernel, 0.0)

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _stats_text(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, (str, bytes)):
            parts.append(v if isinstance(v, str) else v.decode("utf-8",
                                                               "replace"))
    return " ".join(parts)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_events(ops, spans, lo: int, hi: int) -> Trace:
    """``ops``: (name, text, start_ns, end_ns) of the device's operations;
    ``spans``: (name, start_ns, end_ns) of the host's bench spans; both
    clipped to [lo, hi]."""
    clipped = [(n, t, max(a, lo), min(b, hi)) for n, t, a, b in ops
               if b > lo and a < hi]
    busy = union([(a, b) for _, _, a, b in clipped])
    op_s: Dict[str, float] = {}
    kern: Dict[str, float] = {}
    for n, t, a, b in clipped:
        op_s[n] = op_s.get(n, 0.0) + (b - a) * 1e-9
        for k, pats in KERNELS.items():
            if any(p in n or p in t for p in pats):
                kern[k] = kern.get(k, 0.0) + (b - a) * 1e-9
    gaps, prev = [], lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if prev < hi:
        gaps.append((prev, hi))
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid) - 1
        label = "host: frontend loop"
        while i >= 0 and spans[i][1] <= mid:
            if spans[i][2] >= mid:
                label = "host: " + spans[i][0]
                break
            i -= 1
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    return Trace(window_s=(hi - lo) * 1e-9,
                 busy_s=sum(b - a for a, b in busy) * 1e-9,
                 op_seconds=op_s, kernel_s=kern, idle_by_host=idle)


def _device_plane(pd):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:0")]
    if not planes:
        planes = [p for p in pd.planes if p.name.startswith("/device:")]
    return planes[0] if planes else None


def read_file(path: str):
    """(ops, spans) from one xplane: each operation as (its HLO name, the
    whole event text, start, end); no ops where there is no device plane
    with an operations line (a CPU run)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev = _device_plane(pd)
    line = None
    if dev is not None:
        line = {ln.name: ln for ln in dev.lines}.get(OPS_LINE)
    ops = [] if line is None else [
        (ev.name.split(" = ", 1)[0], ev.name + " " + _stats_text(ev),
         ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name in HOST_SPANS:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return ops, spans


def reduce(trace_dir: str, window) -> Optional[Trace]:
    """The window's trace, or None where none was written. The traced
    window runs from the first bench span in the trace to the last one's
    end; the engine steps the host recorded inside it are attached."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files or window.trace_span is None:
        return None
    ops, spans = read_file(files[-1])
    if not ops or not spans:
        return None
    lo = min(s[1] for s in spans)
    hi = max(s[2] for s in spans)
    t = reduce_events(ops, spans, lo, hi)
    a, b = window.trace_span
    t.steps = [s for s in window.steps if s.start >= a and s.end <= b]
    t.prefill_groups = [s.prefill_lengths for s in t.steps if s.prefill]
    return t
