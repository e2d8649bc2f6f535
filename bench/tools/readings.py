"""The readings a cell's correctness limit is set from, in one process.

    python3 bench/tools/readings.py --workload <cell> --seconds <s> \\
        --seeds 201,202,... --control-seeds 201,202,203

For each seed: weights from the seed, a window of the cell's own traffic
at its own load, and the run's check (``run.check``): the widest gap of
the sampled served tokens under the float32 reference, and the device's
memory peak so far. For the control seeds also the control's reading:
the reference with float8 weights put in the program's place, read at
the same prompts and tokens. Set-up (the
warm-up) is paid once; one JSON line per seed.
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
from harness import cell as cells, device, model, serve  # noqa: E402
from harness.record import Run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    root = bench_run.ROOT
    cell = cells.load(root, args.workload)
    peaks = device.load_peaks(root)
    devices = device.require_accelerator(cell.chips, peaks)
    bench_run.compile_cache(root)
    counter = device.CompileCounter()
    conf, spec, pool = cell.conf, cell.spec, cell.conf["pool"]
    rows = []
    for i, seed in enumerate(seeds):
        if i == 0:
            params, server = bench_run.set_up(cell, seed, counter)
        else:
            params = model.program_weights(conf, seed)
            server = serve.build_server(model.model_config(conf), params,
                                        conf, spec)
        traffic = cell.generator.generate(
            spec, seed=seed, seconds=args.seconds, slots=pool["slots"],
            slot_tokens=pool["slot_tokens"], vocab=conf["vocab_size"])
        w = serve.run_window(server, traffic, seconds=args.seconds,
                             drain_s=spec["drain_s"], counter=counter)
        mem = device.memory_peak_bytes(devices)
        del server, params
        gc.collect()
        run = Run(cell, conf, spec, traffic, w, 0.0,
                  peaks["kinds"][devices[0].device_kind])
        attempted, failed, checks, correct = bench_run.check(
            run, seed, control=seed in control)
        row = {"seed": seed, "correct": correct, "attempted": attempted,
               "failed": failed, "window_compiles": w.compiles_in_window,
               "memory_peak_bytes": mem,
               **{k: v["value"] for k, v in checks.items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
