"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
lowers and compiles on the production meshes (16×16 single-pod, 2×16×16
multi-pod), and extract the memory/cost/roofline numbers.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # full matrix
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape decode_32k [--multi-pod]
Results append to launch_results/dryrun.json (idempotent per combo).
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze_hlo
from repro.launch.specs import build_dryrun, sharded_resident_gb

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "launch_results", "dryrun.json")


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args, in_sh, out_sh, policy = build_dryrun(arch, shape_name, mesh)
    shape_kind = INPUT_SHAPES[shape_name].kind
    donate = (1,) if shape_kind in ("prefill", "decode") else (0,)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    resident_gb = sharded_resident_gb(args, in_sh, mesh)
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    rep = analyze_hlo(hlo)
    cfg = get_config(arch)
    n_chips = mesh.size
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 2**30,
            "output_gb": mem.output_size_in_bytes / 2**30,
            "temp_gb": mem.temp_size_in_bytes / 2**30,
            "alias_gb": mem.alias_size_in_bytes / 2**30,
            "per_device_gb": (mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes
                              + mem.output_size_in_bytes
                              - mem.alias_size_in_bytes) / 2**30,
            "tpu_resident_gb": resident_gb,
        },
        "cost_analysis": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "roofline": rep.to_json(),
        "policy": {
            "shard_heads": policy.shard_heads,
            "shard_kv_heads": policy.shard_kv_heads,
            "seq_parallel_decode": policy.seq_parallel_decode,
            "shard_experts": policy.shard_experts,
            "shard_vocab": policy.shard_vocab,
            "shard_batch": policy.shard_batch,
            "fsdp": policy.fsdp,
        },
        "model_flops_note": "6*N_active*D tokens (see benchmarks/roofline_table.py)",
    }
    if verbose:
        m = result["memory"]
        t = rep.terms()
        print(f"[OK] {arch:28s} {shape_name:12s} {result['mesh']:8s} "
              f"compile={result['compile_s']:6.1f}s "
              f"mem/dev={m['per_device_gb']:6.2f}GB "
              f"resident={m['tpu_resident_gb']:5.2f}GB "
              f"compute={t['compute_s']*1e3:8.2f}ms "
              f"memory={t['memory_s']*1e3:8.2f}ms "
              f"coll={t['collective_s']*1e3:8.2f}ms "
              f"dom={rep.dominant()}")
        print(f"     memory_analysis: {mem}")
    return result


def load_results() -> list:
    path = os.path.abspath(RESULTS)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return []


def save_results(results: list):
    path = os.path.abspath(RESULTS)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    results = load_results()
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}
    failures = []
    for mp in meshes:
        mesh_name = "2x16x16" if mp else "16x16"
        for arch in archs:
            for shape in shapes:
                key = (arch, shape, mesh_name)
                if key in done and not args.force:
                    print(f"[skip] {key} (cached)")
                    continue
                try:
                    r = run_one(arch, shape, multi_pod=mp)
                    results = [x for x in results
                               if (x["arch"], x["shape"], x["mesh"]) != key]
                    results.append(r)
                    save_results(results)
                except Exception as e:     # noqa: BLE001 - report and continue
                    failures.append((key, repr(e)))
                    print(f"[FAIL] {key}: {e}")
                    traceback.print_exc()
    print(f"\n{len(results)} results, {len(failures)} failures")
    for k, e in failures:
        print("  FAIL:", k, e[:200])
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
