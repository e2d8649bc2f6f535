"""Block-paged single-token GQA decode attention Pallas TPU kernel.

The KV cache lives in a shared page pool ``(n_pages + 1, page_size, K, D)``
(the last page is a write-off "trash" page); each batch slot owns an
ordered list of pages recorded in a device block table
``(B, pages_per_seq)``. The kernel streams HBM->VMEM **one live page per
grid step** — the block table is a scalar-prefetch operand, so the page
index feeds the DMA descriptor directly (``PrefetchScalarGridSpec``) and
only pages the table names are ever fetched. Decode HBM traffic therefore
scales with live context (``sum_i ceil(ctx_i/ps)·ps``), not with the dense
``B × max_len`` capacity the slot-cache kernel streams.

Each step fetches the whole page, all K KV heads (``(1, ps, K, D)``, which
ends in the pool's own ``(K, D)`` dims as the TPU block rule requires),
and scores all H query heads against it under the static head-match mask
of ``decode_attention.gqa_tile_masks``. The slot positions ride in as a
second scalar-prefetch operand.

``pages_per_seq`` is the *bucketed* max live page count across the batch:
callers round it up (powers of two) so the grid — and hence the compiled
executable — changes only O(log max_pages) times over a request's life.

Masking is positional: page ``i`` covers absolute positions
``[i·ps, (i+1)·ps)`` and a slot attends positions ``<= pos``; slots with
``pos < 0`` (inactive) attend nothing and produce zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (attend_tile, finish_softmax,
                                            flat_tile, gqa_tile_masks,
                                            init_softmax)


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, same_ref,
                         tok_ref, o_ref, m_ref, l_ref, acc_ref, *, ps: int,
                         n_b: int, scale: float):
    del bt_ref                       # consumed by the index maps
    b_ = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        init_softmax(m_ref, l_ref, acc_ref)

    kvpos = i * ps + tok_ref[...]                          # (1, ps·K)
    valid = (same_ref[...] == 1) & (kvpos <= pos_ref[b_])  # (H, ps·K)
    attend_tile(q_ref[0].astype(jnp.float32) * scale, flat_tile(k_ref),
                flat_tile(v_ref), valid, m_ref, l_ref, acc_ref)

    @pl.when(i == n_b - 1)
    def _finalize():
        o_ref[0] = finish_softmax(l_ref, acc_ref, o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           interpret: bool = False):
    """q: (B, K, G, D); pages: (P, ps, K, D); block_tables: (B, n_b) int32
    physical page per (slot, block) — entries past a slot's live context
    must point at a valid (e.g. trash) page; pos: (B,) int32 absolute
    position of the current token (−1 = inactive slot). Returns
    (B, K, G, D)."""
    b, kh, g, d = q.shape
    h = kh * g
    ps = k_pages.shape[1]
    n_b = block_tables.shape[1]
    same, tok = gqa_tile_masks(h, kh, ps)

    kernel = functools.partial(_paged_decode_kernel, ps=ps, n_b=n_b,
                               scale=d ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_b),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, i, bt, pos: (b_, 0, 0)),
            pl.BlockSpec((1, ps, kh, d),
                         lambda b_, i, bt, pos: (bt[b_, i], 0, 0, 0)),
            pl.BlockSpec((1, ps, kh, d),
                         lambda b_, i, bt, pos: (bt[b_, i], 0, 0, 0)),
            pl.BlockSpec((h, ps * kh), lambda b_, i, bt, pos: (0, 0)),
            pl.BlockSpec((1, ps * kh), lambda b_, i, bt, pos: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, i, bt, pos: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(b, h, d), k_pages, v_pages, same, tok)
    return out.reshape(b, kh, g, d)
