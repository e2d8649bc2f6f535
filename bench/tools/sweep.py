"""Find an open-loop cell's knee: serve its mix at several rates, in one
process that sets up once, and print per rate the share of requests due
in the window that met both limits and whether the queue grew.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

The knee is the highest rate at which at least 90% of the requests meet
both limits with no growing backlog.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from harness import cell as cells, device, serve  # noqa: E402
from harness.record import Run, percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = bench_run.ROOT
    cell = cells.load(root, args.workload)
    peaks = device.load_peaks(root)
    devices = device.require_accelerator(cell.chips, peaks)
    bench_run.compile_cache(root)
    counter = device.CompileCounter()
    conf, pool = cell.conf, cell.conf["pool"]
    params, server = bench_run.set_up(cell, args.seed, counter)
    cfg = server.cfg
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        spec = dict(cell.spec, rate_req_s=rate)
        traffic = cell.generator.generate(
            spec, seed=args.seed, seconds=args.seconds, slots=pool["slots"],
            slot_tokens=pool["slot_tokens"], vocab=conf["vocab_size"])
        w = serve.run_window(server, traffic, seconds=args.seconds,
                             drain_s=spec["drain_s"], counter=counter)
        run = Run(cell, conf, spec, traffic, w, 0.0,
                  peaks["kinds"][devices[0].device_kind])
        reqs = run.counted()
        met = sum(run.meets_limits(r) for r in reqs)
        steps = run.window_steps()
        half = len(steps) // 2
        q1 = sum(s.queued for s in steps[:half]) / max(half, 1)
        q2 = sum(s.queued for s in steps[half:]) / max(len(steps) - half, 1)
        row = {"rate": rate, "due": len(reqs), "met": met,
               "attainment": met / max(len(reqs), 1),
               "finished": sum(run.finished(r) for r in reqs),
               **{f"{k}_p{q}_ms": 1e3 * percentile(run.latencies(f), q)
                  for k, f in (("ttft", run.ttft_s), ("tpot", run.tpot_s))
                  for q in (50, 90)},
               "output_tok_s": sum(len(i) for i in run.tokens_in_window().values()) / run.seconds,
               "queued_first_half": q1, "queued_second_half": q2,
               "drain_s": run.stop_time() - w.end,
               "window_compiles": w.compiles_in_window}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del server
        gc.collect()
        server = serve.build_server(cfg, params, conf, cell.spec)
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
