"""Distributed-numerics validation: the sharded paths (tensor parallel,
sequence-parallel decode shard_map, token-parallel MoE, 2D expert weights)
must produce the SAME numbers as the single-device reference.

Runs in a subprocess with 8 virtual CPU devices (the XLA device-count flag
must be set before jax initializes, so it cannot run in the main test
process).
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.environ["REPRO_SRC"])
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import (init_params, init_cache, forward, prefill,
                          decode_step, param_specs, cache_specs, make_policy)
from repro.models import transformer as T

import os as _os
if _os.environ.get("REPRO_TEST_MULTIPOD") == "1":
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
else:
    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

def named(tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))

def run_arch(arch, *, heads=8, kv=4, moe_2d=False, seq_par_expected=None):
    cfg = get_config(arch).reduced(n_heads=heads, n_kv_heads=kv,
                                   d_model=128, head_dim=32)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S, S0 = 4, 16, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)

    # single-device reference
    ref_logits, _ = forward(params, toks, cfg)
    cache0 = init_cache(cfg, B, S + 4, jnp.float32)
    ref_pre, ref_cache = prefill(params, toks[:, :S0],
                                 jnp.array([S0] * B), cache0, cfg)
    ref_dec, _ = decode_step(params, ref_cache, toks[:, S0:S0 + 1],
                             jnp.array([S0] * B), cfg)

    # sharded
    policy = make_policy(cfg, mesh, global_batch=B, moe_2d_weights=moe_2d)
    if seq_par_expected is not None:
        assert policy.seq_parallel_decode == seq_par_expected, (
            arch, policy.seq_parallel_decode)
    pspecs = named(param_specs(cfg, policy))
    params_sh = jax.device_put(params, pspecs)
    with mesh:
        sh_logits, _ = jax.jit(
            lambda p, t: forward(p, t, cfg, policy))(params_sh, toks)
        cache_sh = jax.device_put(init_cache(cfg, B, S + 4, jnp.float32),
                                  named(cache_specs(cfg, policy)))
        sh_pre, sh_cache = jax.jit(
            lambda p, t, l, c: prefill(p, t, l, c, cfg, policy))(
            params_sh, toks[:, :S0], jnp.array([S0] * B), cache_sh)
        sh_dec, _ = jax.jit(
            lambda p, c, t, pos: decode_step(p, c, t, pos, cfg, policy))(
            params_sh, sh_cache, toks[:, S0:S0 + 1], jnp.array([S0] * B))

    scale = max(float(jnp.abs(ref_logits).max()), 1.0)
    for name, a, b in (("forward", ref_logits, sh_logits),
                       ("prefill", ref_pre, sh_pre),
                       ("decode", ref_dec, sh_dec)):
        err = float(jnp.abs(a - b).max())
        assert err < 5e-3 * scale, (arch, name, err, scale)
    print(f"OK {arch} (seq_par={policy.seq_parallel_decode}, "
          f"moe_2d={moe_2d})")

multipod = _os.environ.get("REPRO_TEST_MULTIPOD") == "1"
# tensor-parallel heads + kv shardable
run_arch("qwen3-1.7b", heads=8, kv=4 if not multipod else 2,
         seq_par_expected=False)
if multipod:
    run_arch("mixtral-8x22b", heads=8, kv=2)
    print("ALL_SHARDED_NUMERICS_OK")
    raise SystemExit(0)
# kv (1, 3) NOT shardable by model=4 -> sequence-parallel decode shard_map
run_arch("granite-3-2b", heads=8, kv=1, seq_par_expected=True)
run_arch("codeqwen1.5-7b", heads=6, kv=3, seq_par_expected=True)
# MoE: token-parallel shard_map dispatch
run_arch("mixtral-8x22b", heads=8, kv=4)
# MoE: 2D expert-weight sharding
run_arch("mixtral-8x22b", heads=8, kv=4, moe_2d=True)
# SSM (no attention) under data sharding
run_arch("mamba2-2.7b", heads=0, kv=0)
print("ALL_SHARDED_NUMERICS_OK")
"""


def _run(multipod: bool):
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    if multipod:
        env["REPRO_TEST_MULTIPOD"] = "1"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1100)
    assert "ALL_SHARDED_NUMERICS_OK" in out.stdout, (
        out.stdout[-2000:], out.stderr[-3000:])


@pytest.mark.timeout(1200)
def test_sharded_equals_single_device():
    _run(multipod=False)


@pytest.mark.timeout(1200)
def test_multipod_mesh_numerics():
    """(pod, data, model) mesh: the pod axis joins the batch sharding."""
    _run(multipod=True)
