"""The device's idle time in the traced window, split by the engine phase
the host was in.

The program opens a host span per engine phase inside
``BulletServer.step`` (``engine.step``, ``engine.schedule``, ...), in the
profiler's own trace. This reads the same ``.xplane.pb`` that
``harness.trace.reduce`` read, over the same window (the first bench
span's start to the last one's end). The device is idle wherever no
``XLA Ops`` event runs; each idle nanosecond is charged to the innermost
engine span open at that instant, or to ``OUTSIDE`` where the host was
not inside ``engine.step`` (release, the frontend loop, the benchmark's
hooks). So the parts sum to ``device_idle_share``.

The span names are a copy, not an import of the program's tuple: a
renamed span reads as a null metric, not as a silently moved one.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from harness import trace

#: the program's engine spans
ENGINE_SPANS = ("engine.step", "engine.admit", "engine.schedule",
                "engine.prefill", "engine.migrate", "engine.tables",
                "engine.decode", "engine.readback", "engine.emit",
                "engine.refit")
#: where the host was outside every engine span
OUTSIDE = "outside"
#: each idle share's spans; ``step_other`` is ``engine.step`` under no
#: child that another share reads (``engine.refit`` solves only where
#: ``record_cycle_actual`` is fed measured cycle times; the benchmark
#: feeds none)
GROUPS = {
    "schedule": ("engine.schedule",),
    "prefill_host": ("engine.admit", "engine.prefill", "engine.migrate"),
    "decode_host": ("engine.tables", "engine.decode", "engine.emit"),
    "readback": ("engine.readback",),
    "step_other": ("engine.step", "engine.refit"),
    "outside_step": (OUTSIDE,),
}


@dataclass
class Phases:
    window_s: float
    #: idle seconds by the innermost engine span open (or ``OUTSIDE``)
    idle_s: Dict[str, float]
    #: durations of the engine spans wholly inside the window, by name
    span_s: Dict[str, List[float]] = field(default_factory=dict)

    def idle_share(self, group: str) -> float:
        """% of the window the device idled under ``group``'s spans."""
        idle = sum(self.idle_s.get(n, 0.0) for n in GROUPS[group])
        return 100.0 * idle / self.window_s


def idle_gaps(ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    """[lo, hi] less the union of the operations' intervals."""
    busy = trace.union([(max(a, lo), min(b, hi)) for _, _, a, b in ops
                        if b > lo and a < hi])
    gaps, prev = [], lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if prev < hi:
        gaps.append((prev, hi))
    return gaps


def innermost(spans, lo: int, hi: int) -> List[Tuple[int, int, str]]:
    """[lo, hi] cut into (start, end, name) pieces, each under the
    innermost span open there: the one opened last (``OUTSIDE`` where
    none is)."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    cuts = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                              if lo < t < hi})
    pieces, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][1] <= a:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s[2] > a]
        name = open_[-1][0] if open_ else OUTSIDE
        if pieces and pieces[-1][2] == name and pieces[-1][1] == a:
            pieces[-1] = (pieces[-1][0], b, name)
        else:
            pieces.append((a, b, name))
    return pieces


def reduce_events(ops, spans, lo: int, hi: int) -> Optional[Phases]:
    """``ops``: (name, text, start_ns, end_ns) of the device's operations;
    ``spans``: (name, start_ns, end_ns) of the host's engine spans; the
    window is [lo, hi]. None where no engine span falls in the window."""
    spans = [s for s in spans if s[0] in ENGINE_SPANS
             and s[2] > lo and s[1] < hi]
    if not spans or hi <= lo:
        return None
    idle: Dict[str, float] = {}
    pieces = innermost(spans, lo, hi)
    j = 0
    for a, b in idle_gaps(ops, lo, hi):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            over = min(b, pb) - max(a, pa)
            if over > 0:
                idle[name] = idle.get(name, 0.0) + over * 1e-9
            k += 1
    span_s: Dict[str, List[float]] = {}
    for name, a, b in spans:
        if a >= lo and b <= hi:
            span_s.setdefault(name, []).append((b - a) * 1e-9)
    return Phases((hi - lo) * 1e-9, idle, span_s)


def engine_spans(path: str) -> List[Tuple[str, int, int]]:
    """(name, start, end) of every engine span on the xplane's host
    planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name in ENGINE_SPANS:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def read(trace_dir: str) -> Optional[Phases]:
    """The phases of the newest trace under ``trace_dir``, over the
    window ``harness.trace.reduce`` used; None where there is no trace,
    no bench span or no engine span."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    ops, bench = trace.read_file(files[-1])
    if not ops or not bench:
        return None
    lo = min(s[1] for s in bench)
    hi = max(s[2] for s in bench)
    return reduce_events(ops, engine_spans(files[-1]), lo, hi)


def of(run, reader: str) -> Optional[Phases]:
    """The run's phases, read once and kept on the run. ``reader`` is the
    calling metric's file: the trace lies in ``.bench_trace`` at the root
    of the checkout that holds it. None for a run without a trace."""
    if not hasattr(run, "phases"):
        run.phases = None
        if run.trace is not None:
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(reader))))
            run.phases = read(os.path.join(root, ".bench_trace"))
    return run.phases


def idle_share(run, reader: str, group: str) -> Optional[float]:
    p = of(run, reader)
    return None if p is None else p.idle_share(group)
