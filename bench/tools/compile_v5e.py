"""Compile a configuration's serving steps for one v5e chip, with no chip.

    JAX_PLATFORMS=cpu python bench/tools/compile_v5e.py qwen3-1.7b [--slots N]

For a described ``v5e:2x2`` topology (one of its chips), this lowers and
compiles, at the configuration's pool and the cells' largest shapes, the
decode iteration (largest page bucket) and one prefill layer group with
its page scatter (largest prefill batch and padded prompt), and prints
each program's memory analysis as JSON. The program's own device checks
see the CPU here, so the attention wrappers are told they are on a TPU
(Pallas kernels compiled, not interpreted). Nothing runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--prefill-batch", type=int, default=4)
    ap.add_argument("--prefill-len", type=int, default=2048)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import model
    from repro.core import engine as E
    from repro.kernels import ops
    from repro.models import attention
    from repro.models import transformer as T

    attention._on_tpu = ops._on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        conf = json.load(f)
    pool = dict(conf["pool"])
    if args.slots:
        pool["slots"] = args.slots
    cfg = model.model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = place(jax.eval_shape(lambda: model.program_weights(conf, 0)))
    n_pages = pool["slots"] * pool["slot_tokens"] // pool["page_size"]
    cache = place(jax.eval_shape(lambda: T.init_paged_cache(
        cfg, n_pages, pool["page_size"], jnp.bfloat16)))
    slots, ps = pool["slots"], pool["page_size"]
    n_b = pool["slot_tokens"] // ps
    b, n = args.prefill_batch, args.prefill_len
    block = jax.tree.map(lambda a: s(a.shape[1:], a.dtype), params["blocks"])
    leaf = cache["blocks"][0]["k"]
    kv = s((b, n, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    steps = {
        "decode": lambda: E._decode_iteration.lower(
            params, cache, s((slots, 1)), s((slots,)), s((slots,), jnp.bool_),
            s((slots, n_b)), cfg=cfg),
        "prefill_group": lambda: E._prefill_group_paged.lower(
            block, s((b, n, cfg.d_model), jnp.bfloat16), s((1, n)), cfg=cfg),
        "scatter": lambda: E._scatter_group_pages.lower(
            leaf, kv, s((b, n // ps)), s(())),
    }
    out = {"config": conf["name"], "slots": slots, "decode_page_bucket": n_b,
           "prefill": [b, n], "device": "v5e (described, one chip)"}
    for name, lower in steps.items():
        t0 = time.perf_counter()
        c = lower().compile()
        m = c.memory_analysis()
        out[name] = {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
        out[name]["compile_s"] = round(time.perf_counter() - t0, 1)
    d = out["decode"]
    out["decode_peak_bytes"] = (d["argument_size_in_bytes"]
                                + d["temp_size_in_bytes"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
