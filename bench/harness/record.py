"""What one run hands the metric readers, and the arithmetic they share.

Times are host seconds (``time.perf_counter``). A request is *due* at
``window.t0 + due``; its time to first token runs from then to when the
host received its first token. A request due in the window that never
finished counts as above any finished value: its latency is taken as the
wait until the run stopped waiting for it, or the largest finished value
where that is larger.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional


@dataclass
class Run:
    cell: object
    conf: dict
    spec: dict
    traffic: object
    window: object
    setup_s: float
    peak: dict
    trace: Optional[object] = None

    # -- requests -------------------------------------------------------
    def requests(self):
        return self.traffic.all_requests()

    def due(self, r) -> float:
        return self.window.t0 + r.due

    def counted(self) -> List:
        """Open loop: the requests due inside the window."""
        return [r for r in self.requests() if r.counted]

    def finished(self, r) -> bool:
        return len(self.window.token_times.get(r.rid, ())) >= r.output_len

    def stop_time(self) -> float:
        steps = self.window.steps
        return steps[-1].end if steps else self.window.end

    def ttft_s(self, r) -> float:
        ts = self.window.token_times.get(r.rid)
        if not ts or not self.finished(r):
            return self.stop_time() - self.due(r)
        return ts[0] - self.due(r)

    def tpot_s(self, r) -> float:
        ts = self.window.token_times.get(r.rid)
        if not ts or not self.finished(r):
            return self.stop_time() - self.due(r)
        return (ts[-1] - ts[0]) / max(len(ts) - 1, 1)

    def latencies(self, of) -> List[float]:
        """``of`` (``ttft_s`` or ``tpot_s``) of every request due in the
        window, an unfinished one ranked above every finished one."""
        reqs = self.counted()
        done = [of(r) for r in reqs if self.finished(r)]
        top = max(done, default=0.0)
        return done + [max(of(r), top) for r in reqs
                       if not self.finished(r)]

    def meets_limits(self, r) -> bool:
        lim = self.spec["limits"]
        if not self.finished(r):
            return False
        return (self.ttft_s(r) * 1e3 <= lim["norm_ttft_ms_per_token"]
                * len(r.prompt)
                and self.tpot_s(r) * 1e3 <= lim["tpot_ms"])

    # -- the window -----------------------------------------------------
    @property
    def seconds(self) -> float:
        return self.window.end - self.window.start

    def in_window(self, t: float) -> bool:
        return self.window.start <= t <= self.window.end

    def window_steps(self):
        """Engine steps that began and ended inside the window."""
        return [s for s in self.window.steps
                if s.start >= self.window.start and s.end <= self.window.end]

    def tokens_in_window(self) -> Dict[int, List[int]]:
        """Per request, the indices of its output tokens received inside
        the window."""
        out = {}
        for rid, ts in self.window.token_times.items():
            idx = [i for i, t in enumerate(ts) if self.in_window(t)]
            if idx:
                out[rid] = idx
        return out


def percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule)."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def prefill_flops_in_window(run: Run) -> float:
    """FLOPs of every prompt whose first token came in the window (its
    matmuls, causal attention and output head)."""
    from harness import costs
    total = 0.0
    for r in run.requests():
        ts = run.window.token_times.get(r.rid)
        if ts and run.in_window(ts[0]):
            total += costs.prefill_flops(run.conf, len(r.prompt))
    return total


def cycle_ms(run: Run, kind: str) -> Optional[float]:
    """Host time per engine step of one kind inside the window: the
    summed step times over their count. ``decode``: steps that ran one
    decode iteration and no prefill group; ``prefill``: steps that ran a
    prefill layer group."""
    steps = run.window_steps()
    if kind == "decode":
        pick = [s for s in steps if s.decode and not s.prefill]
    elif kind == "prefill":
        pick = [s for s in steps if s.prefill]
    else:
        raise ValueError(kind)
    if not pick:
        return None
    return 1e3 * sum(s.end - s.start for s in pick) / len(pick)
