"""v5e compiles of the attention kernels at Qwen3-1.7B widths.

Interpret mode (tests/test_kernels.py) cannot show what the TPU compiler
refuses: blocks that break its (8, 128) tiling rule, or more fast memory
than a kernel may use. These tests compile each ``kernels/ops.py``
attention wrapper with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology — no chip attached — at the published head widths
(16 query heads, 8 KV heads, head_dim 128, bf16) and the serving smoke
run's largest shapes. The topology is described inside a fixture, never
at import, so every test worker collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

CFG = get_config("qwen3-1.7b")
#: (prefill batch, padded prompt length), decode slots, page size, KV
#: tokens per slot — chip_smoke.py's largest shapes
BP, SP = 4, 1024
B, PS, MAX_LEN = 8, 16, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 - any failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shapes(one_chip):
    h, kh, d = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    n_pages = B * MAX_LEN // PS + 1

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    prefill = (s((BP, SP, h, d)), s((BP, SP, kh, d)), s((BP, SP, kh, d)))
    dense = (s((B, 1, h, d)), s((B, MAX_LEN, kh, d)), s((B, MAX_LEN, kh, d)),
             s((B, MAX_LEN), jnp.int32), s((B,), jnp.int32))
    paged = (s((B, 1, h, d)), s((n_pages, PS, kh, d)),
             s((n_pages, PS, kh, d)), s((B, MAX_LEN // PS), jnp.int32),
             s((B,), jnp.int32))
    return {"prefill": prefill, "dense": dense, "paged": paged}


CASES = {
    "flash_attention_op": (
        lambda q, k, v: ops.flash_attention_op(q, k, v, interpret=False),
        ("prefill",)),
    "decode_attention_op": (
        lambda *a: ops.decode_attention_op(*a, interpret=False), ("dense",)),
    "paged_decode_attention_op": (
        lambda *a: ops.paged_decode_attention_op(*a, interpret=False),
        ("paged",)),
    "bullet_attention_op": (
        lambda *a: ops.bullet_attention_op(*a, interpret=False),
        ("prefill", "dense")),
    "bullet_attention_paged_op": (
        lambda *a: ops.bullet_attention_paged_op(*a, interpret=False),
        ("prefill", "paged")),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_attention_kernel_compiles_for_v5e(name, shapes):
    fn, groups = CASES[name]
    args = [a for g in groups for a in shapes[g]]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
