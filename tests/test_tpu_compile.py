"""v5e compiles of the attention kernels at Qwen3-1.7B widths.

Interpret mode (tests/test_kernels.py) cannot show what the TPU compiler
refuses: blocks that break its (8, 128) tiling rule, or more fast memory
than a kernel may use. These tests compile each ``kernels/ops.py``
attention wrapper with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology — no chip attached — at the published head widths
(16 query heads, 8 KV heads, head_dim 128, bf16) and the serving smoke
run's largest shapes. The topology is described inside a fixture, never
at import, so every test worker collects the same tests. The paged decode
iteration is compiled whole, at published widths and 4 layers deep, to
guard that it updates the page pool in place.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import attention

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


@pytest.fixture
def on_tpu(monkeypatch):
    """The program's device checks see the CPU here: tell them they are on
    a TPU, so attention compiles the Pallas kernels for the chip."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_pool_heads_major_matches_v5e_layout(dtype, one_chip, on_tpu):
    """``pool_heads_major`` says what the chip's default layout of a page
    pool is: K heads major to the page's tokens, or the pool's own order."""
    for kh in (1, 2, 4, 8, 12, 16, 20, 32, 40):
        pool = jax.ShapeDtypeStruct((3, 65, PS, kh, 128), dtype,
                                    sharding=one_chip)
        fmt = jax.jit(lambda a: a).lower(pool).compile().input_formats
        order = fmt[0][0].layout.major_to_minor[-3:]
        assert order in ((2, 3, 4), (3, 2, 4)), (kh, order)
        assert attention.pool_heads_major(pool) == (order == (3, 2, 4)), kh


@pytest.mark.parametrize("name", ["qwen1.5-4b", "qwen3-1.7b"])
def test_paged_decode_iteration_updates_pool_in_place(name, one_chip,
                                                      on_tpu):
    """The serving decode iteration at published widths (4 layers deep, 8
    slots × 2048 tokens of 16-token pages) writes the donated page pool in
    place: its temporaries stay under one layer stack's pool, and no copy
    has the pool's size. Qwen1.5-4B's 20 KV heads are stored head-major."""
    from repro.core import engine as E
    from repro.models import init_params
    from repro.models import transformer as T

    cfg = dataclasses.replace(get_config(name), n_layers=4)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = place(jax.eval_shape(lambda: init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    cache = place(T.init_paged_cache(cfg, B * MAX_LEN // PS, PS,
                                     abstract=True))
    compiled = E._decode_iteration.lower(
        params, cache, s((B, 1)), s((B,)), s((B,), jnp.bool_),
        s((B, MAX_LEN // PS)), cfg=cfg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool = cache["blocks"][0]["k"]
    elems = math.prod(pool.shape)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < elems * pool.dtype.itemsize, temp
    copies = [m.group(0) for m in re.finditer(
        r"%copy[\w.-]* = \w+\[([\d,]*)\]", compiled.as_text())
        if math.prod(int(n) for n in m.group(1).split(",") if n) == elems]
    assert not copies, copies
