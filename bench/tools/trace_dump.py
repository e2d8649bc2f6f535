"""Print the layout of the newest trace under .bench_trace: planes, lines,
event counts and the heaviest event names with a few of their stats, and
write it all as JSON to the path given (default trace_layout.json).

    python3 bench/tools/trace_dump.py [out.json]
"""
import glob, json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from jax.profiler import ProfileData
f = sorted(glob.glob(os.path.join(ROOT, ".bench_trace", "**", "*.xplane.pb"), recursive=True))[-1]
pd = ProfileData.from_file(f)
out = {"file": f, "bytes": os.path.getsize(f), "planes": {}}
for plane in pd.planes:
    lines = {}
    for line in plane.lines:
        agg = {}
        ex = {}
        for ev in line.events:
            a = agg.setdefault(ev.name, [0, 0])
            a[0] += 1; a[1] += ev.duration_ns
            if ev.name not in ex:
                ex[ev.name] = [(k, str(v)[:160]) for k, v in ev.stats][:8]
        top = sorted(agg.items(), key=lambda kv: -kv[1][1])[:25]
        lines[line.name] = {"events": sum(v[0] for v in agg.values()),
                            "top": [[n, c, d, ex[n]] for n, (c, d) in top]}
    out["planes"][plane.name] = lines
dst = sys.argv[1] if len(sys.argv) > 1 else "trace_layout.json"
json.dump(out, open(dst, "w"), indent=1)
for p, lines in out["planes"].items():
    print(p, {k: v["events"] for k, v in lines.items()})
