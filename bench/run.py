"""One run of one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It makes the weights on the device from the seed, warms every shape the
cell's traffic can reach, then serves the traffic for ``--seconds``
through ``OnlineFrontend`` -> ``BulletServer.step`` on a wall clock.
After the window it reads the device's memory peak, frees the server,
and checks a sample of the finished requests against the float32
reference (``harness/reference.py``). The last line on standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``checks`` compared for ``correct`` beside their limits.

Without a TPU whose kind is in ``bench/peaks.json``, or with fewer chips
than the cell asks for, it exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell as cells  # noqa: E402
from harness import device  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def compile_cache(root: str) -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, keeping every program however quickly it compiled."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def sample(run, seed: int, n: int):
    """The finished requests the reference checks: the one that served
    the most tokens, and the rest drawn from the seed."""
    import numpy as np
    w = run.window
    if run.traffic.loop == "open":
        pool = [r for r in run.counted() if run.finished(r)]
    else:
        pool = [r for r in run.requests() if run.finished(r)
                and run.in_window(w.token_times[r.rid][-1])]
    if not pool:
        return []
    longest = max(pool, key=lambda r: (r.output_len, len(r.prompt), r.rid))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [longest] + pick


def set_up(cell, seed: int, counter):
    """Weights from the seed, the server, and every shape the cell's
    traffic can reach warmed through the server's own path."""
    import jax
    import numpy as np
    from harness import model, serve
    from repro.models.attention import prefill_length

    conf, spec, pool = cell.conf, cell.spec, cell.conf["pool"]
    cfg = model.model_config(conf)
    params = model.program_weights(conf, seed)
    jax.block_until_ready(params)
    model.check_layout(cfg, params)
    server = serve.build_server(cfg, params, conf, spec)
    span = cell.generator.reachable_prompt_lengths(spec, pool["slot_tokens"])
    plan = serve.warm_plan(spec, conf, span, prefill_length)
    n0, c0 = counter.lowerings, counter.compiles
    steps = serve.warm(server, plan, conf["vocab_size"],
                       np.random.default_rng([seed, 2]), pool["page_size"])
    log(f"bench: warmed {len(plan['prefill'])} prefill shapes and "
        f"{len(plan['decode_buckets'])} decode buckets in {steps} steps: "
        f"{counter.lowerings - n0} programs lowered, "
        f"{counter.compiles - c0} compiled "
        f"({counter.compile_s:.1f} s compiling in all), "
        f"{time.perf_counter() - T_START:.1f} s since start")
    return params, server


def check(run, seed: int, *, control: bool = False):
    """Whether what the window served is correct: every request due in it
    came, and the widest gap of the sampled requests' served tokens under
    the float32 reference is within the cell's limit. Returns (attempted,
    failed, checks, correct); with ``control`` the checks also carry the
    fp8 control's widest gap on the same prompts and tokens."""
    from harness import model, reference
    window, limits = run.window, run.cell.limits
    if run.traffic.loop == "open":
        attempted = len(run.counted())
        failed = sum(not run.finished(r) for r in run.counted())
    else:
        attempted = sum(1 for r in run.requests() if run.finished(r)
                        and run.in_window(window.token_times[r.rid][-1]))
        failed = 0
    picked = sample(run, seed, limits["sample_requests"])
    checks = {"unfinished": {"value": failed, "limit": 0},
              "sampled": {"value": len(picked), "limit": 1}}
    t0 = time.perf_counter()
    correct = failed == 0 and bool(picked)
    if picked:
        w = model.published_weights(run.conf, seed)
        seqs = [(r.prompt, window.outputs[r.rid]) for r in picked]
        slot = run.conf["pool"]["slot_tokens"]
        gap = max(reference.widest_gaps(run.conf, w, seqs, slot))
        checks["widest_gap"] = {"value": gap, "limit": limits["widest_gap"]}
        correct = correct and gap <= limits["widest_gap"]
        if control:
            checks["control_widest_gap"] = {"value": max(
                reference.widest_gaps(run.conf, w, seqs, slot, fp8=True)),
                "limit": limits["widest_gap"]}
        del w
    log(f"bench: reference over {len(picked)} requests "
        f"({sum(len(window.outputs[r.rid]) for r in picked)} served "
        f"tokens) in {time.perf_counter() - t0:.1f} s")
    return attempted, failed, checks, correct


def main(argv=None, root: str = ROOT) -> int:
    args = parse(argv)
    cell = cells.load(root, args.workload)
    peaks = device.load_peaks(root)
    try:
        devices = device.require_accelerator(cell.chips, peaks)
    except device.NoAccelerator as e:
        log(f"bench: {e}")
        return 2

    from harness import serve, trace
    from harness.record import Run

    compile_cache(root)
    counter = device.CompileCounter()
    peak = peaks["kinds"][devices[0].device_kind]
    conf, spec = cell.conf, cell.spec
    pool = conf["pool"]
    params, server = set_up(cell, args.seed, counter)
    traffic = cell.generator.generate(
        spec, seed=args.seed, seconds=args.seconds, slots=pool["slots"],
        slot_tokens=pool["slot_tokens"], vocab=conf["vocab_size"])
    log(f"bench: {cell.name} seed {args.seed}: "
        f"{cell.generator.summary(traffic)}")

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = serve.run_window(
        server, traffic, seconds=args.seconds, drain_s=spec["drain_s"],
        counter=counter, trace_dir=trace_dir,
        trace_seconds=min(spec["trace_seconds"], args.seconds))
    setup_s = window.start - T_START
    mem = device.memory_peak_bytes(devices)
    del server
    gc.collect()

    run = Run(cell, conf, spec, traffic, window, setup_s, peak)
    if args.trace:
        run.trace = trace.reduce(trace_dir, window)
    group = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for name, (entry, reader) in group.items():
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}

    del params
    gc.collect()
    attempted, failed, checks, correct = check(run, args.seed)

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = mem
    if args.trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if args.trace and run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    log(f"bench: setup {setup_s:.1f} s, window {run.seconds:.1f} s, "
        f"{len(window.steps)} steps, {window.compiles_in_window} programs "
        "lowered in the window")
    for name, c in checks.items():
        op = "<=" if name != "sampled" else ">="
        log(f"check {name} {c['value']!r} {op} {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
