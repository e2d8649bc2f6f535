"""Benchmark driver — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [names...]``

Prints ``name,...`` CSV rows; derived headline numbers carry a
``-summary``/``-headline`` suffix.
"""

import sys
import time


BENCHES = [
    ("table1_wave_quantization", "benchmarks.wave_quantization"),
    ("fig4_chunked_prefill", "benchmarks.chunked_prefill_cost"),
    ("fig7_partition_scaling", "benchmarks.partition_scaling"),
    ("fig11_end_to_end", "benchmarks.end_to_end"),
    ("fig12_timeline", "benchmarks.timeline"),
    ("fig13_sensitivity", "benchmarks.sensitivity"),
    ("fig14_ablation", "benchmarks.ablation"),
    ("fig15_estimator_accuracy", "benchmarks.estimator_accuracy"),
    ("replay_vs_sim", "benchmarks.replay_vs_sim"),
    ("table3_overheads", "benchmarks.overheads"),
    ("kernels", "benchmarks.kernel_bench"),
    ("paged_decode", "benchmarks.paged_decode_attention"),
    ("fused_vs_serial", "benchmarks.fused_vs_serial"),
    ("obs_overhead", "benchmarks.obs_overhead"),
    ("prefix_reuse", "benchmarks.prefix_reuse"),
    ("chaos_replay", "benchmarks.chaos_replay"),
    ("fairness_replay", "benchmarks.fairness_replay"),
    ("capacity_plan", "benchmarks.capacity_plan"),
    ("roofline", "benchmarks.roofline_table"),
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    wanted = set(sys.argv[1:])
    failures = []
    for name, module in BENCHES:
        if wanted and not any(w in name for w in wanted):
            continue
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        try:
            mod = __import__(module, fromlist=["run"])
            mod.run(lambda line: print(line, flush=True))
            print(f"=== {name} done in {time.time()-t0:.1f}s ===", flush=True)
        except Exception as e:      # noqa: BLE001 - report all benches
            failures.append((name, repr(e)))
            import traceback
            traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
