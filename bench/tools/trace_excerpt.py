"""Cut a small excerpt from the newest trace under .bench_trace for the
harness's self-test: the device operations and bench host spans of a
short slice, as ``harness.trace.read_file`` returns them, and each
kernel's summed time in the slice, added up here event by event.

    python3 bench/tools/trace_excerpt.py <out.json> [slice_ms]
"""
import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from harness import trace  # noqa: E402

f = sorted(glob.glob(os.path.join(os.path.dirname(BENCH), ".bench_trace", "**",
                                  "*.xplane.pb"), recursive=True))[-1]
ops, spans = trace.read_file(f)
slice_ns = float(sys.argv[2] if len(sys.argv) > 2 else 30) * 1e6
steps = sorted(s for s in spans if s[0] == "bench.step")
mid = steps[len(steps) // 2][1]
lo, hi = mid, mid + slice_ns
keep_ops = [o for o in ops if o[3] > lo and o[2] < hi]
keep_spans = [s for s in spans if s[2] > lo and s[1] < hi]
kern = {}
for k, pats in trace.KERNELS.items():
    total = 0.0
    for name, text, a, b in keep_ops:
        if any(p in name or p in text for p in pats):
            total += (min(b, hi) - max(a, lo)) * 1e-9
    kern[k] = total
with open(sys.argv[1], "w") as out:
    json.dump({"source": os.path.basename(f), "window_ns": [lo, hi],
               "ops": [[n, t[:200], a, b] for n, t, a, b in keep_ops],
               "spans": keep_spans, "kernel_seconds": kern}, out)
print(len(keep_ops), "ops", len(keep_spans), "spans", kern)
