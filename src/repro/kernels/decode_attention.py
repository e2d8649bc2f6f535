"""Single-token GQA decode attention Pallas TPU kernel (memory-bound).

One new query token attends over the KV cache. Grid: (batch, seq_tiles)
with the sequence dimension sequential; the online-softmax accumulators
for all H query heads live in VMEM scratch. The cache streams HBM→VMEM
tile by tile — this is the DMA-dominated kernel the Bullet fused schedule
interleaves under prefill MXU work (see bullet_attention.py).

Every tile carries all K KV heads: a ``(1, bs, K, D)`` block ends in the
cache's own ``(K, D)`` dims, which is what the TPU's (8, 128) block rule
accepts for any K. Inside, the tile is flattened token-major to
``(bs·K, D)`` and one ``(H, D) × (bs·K, D)ᵀ`` product scores every head
against every column; a static head-match mask keeps each query head on
its own KV head's columns (GQA), so no in-kernel gather or per-head slice
is needed. The helpers here are shared with the paged and fused kernels.

Ring-buffer caches are supported through ``kv_positions`` (absolute position
per slot, −1 = empty): masking is positional, not index-based.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def gqa_tile_masks(h: int, kh: int, n: int):
    """Static masks for a KV tile of ``n`` tokens flattened token-major
    (column ``c`` = token ``c // kh``, KV head ``c % kh``).

    Returns ``same_head`` (H, n·K) int32 — 1 where query head ``r``
    (KV head ``r // (H/K)``) owns column ``c`` — and ``col_tok`` (1, n·K)
    int32, the column's token offset within the tile."""
    c = np.arange(n * kh)
    same = (np.arange(h)[:, None] // (h // kh)) == (c[None, :] % kh)
    return (jnp.asarray(same.astype(np.int32)),
            jnp.asarray((c // kh)[None, :].astype(np.int32)))


def flat_tile(ref) -> jax.Array:
    """A ``(1, n, K, D)`` KV block as ``(n·K, D)`` float32 rows."""
    _, n, kh, d = ref.shape
    return ref[0].astype(jnp.float32).reshape(n * kh, d)


def init_softmax(m_ref, l_ref, acc_ref) -> None:
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def attend_tile(q, k, v, valid, m_ref, l_ref, acc_ref) -> None:
    """One online-softmax step: q (H, D) pre-scaled, k/v (n·K, D), valid
    (H, n·K) bool; accumulators m/l (H, 1) and acc (H, D) in scratch."""
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = jnp.where(valid, logits, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def finish_softmax(l_ref, acc_ref, dtype):
    return (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, kvpos_ref, same_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, n_s: int, scale: float):
    b_ = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        init_softmax(m_ref, l_ref, acc_ref)

    kvpos = kvpos_ref[0]                                   # (1, bs·K)
    valid = ((same_ref[...] == 1) & (kvpos >= 0)
             & (kvpos <= pos_ref[b_]))                     # (H, bs·K)
    attend_tile(q_ref[0].astype(jnp.float32) * scale, flat_tile(k_ref),
                flat_tile(v_ref), valid, m_ref, l_ref, acc_ref)

    @pl.when(si == n_s - 1)
    def _finalize():
        o_ref[0] = finish_softmax(l_ref, acc_ref, o_ref.dtype)


def decode_attention(q, k_cache, v_cache, kv_positions, pos, *,
                     block_s: int = 128, interpret: bool = False):
    """q: (B, K, G, D); caches: (B, S, K, D); kv_positions: (B, S);
    pos: (B,) int32. Returns (B, K, G, D)."""
    b, kh, g, d = q.shape
    h = kh * g
    s = k_cache.shape[1]
    bs = min(block_s, s)
    n_s = -(-s // bs)
    pad = n_s * bs - s
    if pad:
        # tail block: pad the cache and mark the padded slots empty
        # (kv_position −1 masks them) so any cache length works
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, padw)
        v_cache = jnp.pad(v_cache, padw)
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-1)
    # one position per flattened (token, kv head) column
    kvpos_cols = jnp.repeat(kv_positions.astype(jnp.int32), kh,
                            axis=1)[:, None, :]            # (B, 1, S·K)
    same, _ = gqa_tile_masks(h, kh, bs)

    kernel = functools.partial(_decode_kernel, n_s=n_s, scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_s),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda b_, si, pos: (b_, 0, 0)),
                pl.BlockSpec((1, bs, kh, d),
                             lambda b_, si, pos: (b_, si, 0, 0)),
                pl.BlockSpec((1, bs, kh, d),
                             lambda b_, si, pos: (b_, si, 0, 0)),
                pl.BlockSpec((1, 1, bs * kh),
                             lambda b_, si, pos: (b_, 0, si)),
                pl.BlockSpec((h, bs * kh), lambda b_, si, pos: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda b_, si, pos: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), q.reshape(b, h, d), k_cache, v_cache,
      kvpos_cols, same)
    return out.reshape(b, kh, g, d)
