"""Bullet fused prefill+decode attention — the paper's spatial-temporal
co-execution adapted to TPU (DESIGN.md §2).

On GPU, Bullet runs prefill and decode kernels concurrently on disjoint SM
partitions. A TPU core has no SM-mask analogue: grid steps of one kernel run
sequentially, but the hardware overlaps the *DMA* of upcoming tiles with the
*MXU* work of the current tile. This kernel therefore fuses the two phases
into a single ``pallas_call`` whose 1-D grid is a static interleave of

  - prefill tiles  (compute-bound: bq×bk MXU flash-attention steps), and
  - decode tiles   (memory-bound: KV-cache streaming for one-token queries),

so decode's HBM traffic hides under prefill's MXU waves — the same
complementary-resource co-location, at tile rather than SM granularity. The
``decode_share`` knob (ratio of decode tiles per slot) is the ``m_i/M``
resource fraction of the paper's Eq. 2, and is what the Bullet scheduler
(repro.core.scheduler) tunes per layer-group.

Phase bookkeeping is done with static schedule arrays consumed by the
index_maps; the inactive phase's block indices *hold their last value* so
pallas neither refetches their inputs nor evicts the active phase's
accumulator state.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (attend_tile, finish_softmax,
                                            flat_tile, gqa_tile_masks,
                                            init_softmax)


def build_schedule(n_prefill: int, n_decode: int, decode_share: float
                   ) -> np.ndarray:
    """Bresenham-merge the two tile streams.

    Returns phase array (total,) of 0 (prefill) / 1 (decode). decode_share
    is the target fraction of grid slots handed to decode while both streams
    have tiles left; leftovers are appended.
    """
    total = n_prefill + n_decode
    phase = np.zeros(total, np.int32)
    p = d = 0
    err = 0.0
    for g in range(total):
        take_decode = (d < n_decode) and (err + decode_share >= 1.0 or p >= n_prefill)
        if take_decode:
            phase[g] = 1
            d += 1
            err = err + decode_share - 1.0
        else:
            phase[g] = 0
            p += 1
            err = err + decode_share
    return phase


def _mk_index_arrays(phase: np.ndarray, dims_p: Tuple[int, ...],
                     dims_d: Tuple[int, ...]):
    """Per-grid-step multi-indices for each phase, hold-last when inactive."""
    def unravel(count, dims):
        return np.array(np.unravel_index(np.arange(count), dims))
    total = len(phase)
    p_idx = np.zeros((len(dims_p), total), np.int32)
    d_idx = np.zeros((len(dims_d), total), np.int32)
    up = unravel(int((phase == 0).sum()), dims_p)
    ud = unravel(int((phase == 1).sum()), dims_d)
    pi = di = 0
    for g in range(total):
        if phase[g] == 0:
            p_idx[:, g] = up[:, pi]
            pi += 1
        else:
            d_idx[:, g] = ud[:, di]
            di += 1
        if g and phase[g] == 1:
            p_idx[:, g] = p_idx[:, g - 1]          # hold-last
        if g and phase[g] == 0:
            d_idx[:, g] = d_idx[:, g - 1]
    return p_idx, d_idx


def _prefill_tile(qp_ref, kp_ref, vp_ref, m_ref, l_ref, acc_ref, qi, ki, *,
                  bq, bk, causal, window, scale):
    """One (q tile, kv tile) flash-attention step of the prefill stream."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    attend_tile(qp_ref[0].astype(jnp.float32) * scale,
                kp_ref[0].astype(jnp.float32), vp_ref[0].astype(jnp.float32),
                mask, m_ref, l_ref, acc_ref)


def _fused_kernel(phase_ref, pqi_ref, pki_ref, dsi_ref, qp_ref, kp_ref,
                  vp_ref, op_ref, od_ref, pm, plse, pacc, dm, dlse, dacc,
                  decode_valid, qd_ref, kd_ref, vd_ref, *, bq, bk, n_kv_p,
                  n_s_d, causal, window, scale):
    """The body both fused kernels share: a prefill flash tile or a decode
    tile, by the step's phase. ``decode_valid`` gives the decode tile's
    (H, n·K) mask; it is only evaluated on decode steps."""
    g = pl.program_id(0)
    ph = phase_ref[g]
    ki = pki_ref[g]
    si = dsi_ref[g]

    # ---------------- prefill tile (compute-bound) ----------------
    @pl.when((ph == 0) & (ki == 0))
    def _init_p():
        init_softmax(pm, plse, pacc)

    @pl.when(ph == 0)
    def _prefill():
        _prefill_tile(qp_ref, kp_ref, vp_ref, pm, plse, pacc, pqi_ref[g], ki,
                      bq=bq, bk=bk, causal=causal, window=window,
                      scale=scale)

    @pl.when((ph == 0) & (ki == n_kv_p - 1))
    def _fin_p():
        op_ref[0] = finish_softmax(plse, pacc, op_ref.dtype)

    # ---------------- decode tile (memory-bound) -------------------
    @pl.when((ph == 1) & (si == 0))
    def _init_d():
        init_softmax(dm, dlse, dacc)

    @pl.when(ph == 1)
    def _decode():
        attend_tile(qd_ref[0].astype(jnp.float32) * scale, flat_tile(kd_ref),
                    flat_tile(vd_ref), decode_valid(g, si), dm, dlse, dacc)

    @pl.when((ph == 1) & (si == n_s_d - 1))
    def _fin_d():
        od_ref[0] = finish_softmax(dlse, dacc, od_ref.dtype)


def _bullet_kernel(phase_ref, pbh_ref, pqi_ref, pki_ref, db_ref, dsi_ref,
                   pos_ref, qp_ref, kp_ref, vp_ref, qd_ref, kd_ref, vd_ref,
                   kvpos_ref, same_ref, op_ref, od_ref, *scratch, **kw):
    """Fused schedule over prefill tiles and dense-cache decode tiles
    (masking by the table-driven ``kv_positions``)."""
    del pbh_ref                      # consumed by the index maps

    def decode_valid(g, si):
        kvpos = kvpos_ref[0]                                 # (1, bs·K)
        return ((same_ref[...] == 1) & (kvpos >= 0)
                & (kvpos <= pos_ref[db_ref[g]]))

    _fused_kernel(phase_ref, pqi_ref, pki_ref, dsi_ref, qp_ref, kp_ref,
                  vp_ref, op_ref, od_ref, *scratch, decode_valid, qd_ref,
                  kd_ref, vd_ref, **kw)


def _bullet_paged_kernel(phase_ref, pbh_ref, pqi_ref, pki_ref, db_ref,
                         dsi_ref, pos_ref, bt_ref, qp_ref, kp_ref, vp_ref,
                         qd_ref, kpg_ref, vpg_ref, same_ref, tok_ref,
                         op_ref, od_ref, *scratch, ps, **kw):
    """Fused schedule over prefill tiles and *paged* decode tiles.

    Identical to ``_bullet_kernel`` on the prefill side; the decode side
    streams one physical KV page per tile (``bt_ref`` is consumed by the
    index maps — page ``bt[slot, col]`` covers absolute positions
    ``[col·ps, (col+1)·ps)``), so masking is positional like
    ``paged_decode_attention`` instead of table-driven ``kv_positions``.
    """
    del pbh_ref, bt_ref              # consumed by the index maps

    def decode_valid(g, si):
        kvpos = si * ps + tok_ref[...]                       # (1, ps·K)
        return (same_ref[...] == 1) & (kvpos <= pos_ref[db_ref[g]])

    _fused_kernel(phase_ref, pqi_ref, pki_ref, dsi_ref, qp_ref, kp_ref,
                  vp_ref, op_ref, od_ref, *scratch, decode_valid, qd_ref,
                  kpg_ref, vpg_ref, **kw)


def _fused_call(kernel, prefetch, qp, kp, vp, qd, decode_specs,
                decode_inputs, *, n_d_tiles, dims_d, decode_share, bq, bk,
                group, interpret):
    """Build the interleaved schedule and launch one fused ``pallas_call``.

    ``prefetch`` are the scalar-prefetch operands that follow the schedule
    arrays (pos, and the block tables for the paged kernel);
    ``decode_specs(ix)`` builds the decode-side input BlockSpecs from
    ``ix(f)``, which turns ``f(db, dsi, *prefetch)`` into an index map."""
    bhp, sp, d = qp.shape
    bd, h, _ = qd.shape
    n_q, n_kv = sp // bq, sp // bk
    dims_p = (bhp, n_q, n_kv)
    phase = build_schedule(int(np.prod(dims_p)), n_d_tiles, decode_share)
    p_idx, d_idx = _mk_index_arrays(phase, dims_p, dims_d)
    # phase, pbh, pqi, pki, db, dsi. On the chip the pipeline evaluates
    # the index maps one step past the grid to prefetch; without trailing
    # entries that read runs off the arrays and the block-table lookup
    # turns it into an out-of-bounds page DMA, which halts the core.
    # Hold-last padding (to a multiple of 128) keeps every read in bounds.
    n = len(phase)
    pad = -(-(n + 1) // 128) * 128 - n
    sched = [np.concatenate([a, np.repeat(a[-1:], pad)])
             for a in (phase, *p_idx, *d_idx)]

    def ix(f):
        return lambda g, ph, pbh, pqi, pki, db, dsi, *pre: f(
            g, pbh, pqi, pki, db, dsi, *pre)

    q_spec = pl.BlockSpec((1, bq, d), ix(
        lambda g, pbh, pqi, pki, db, dsi, *pre: (pbh[g], pqi[g], 0)))
    kv_spec = pl.BlockSpec((1, bk, d), ix(
        lambda g, pbh, pqi, pki, db, dsi, *pre: (pbh[g] // group, pki[g], 0)))
    qd_spec = pl.BlockSpec((1, h, d), ix(
        lambda g, pbh, pqi, pki, db, dsi, *pre: (db[g], 0, 0)))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched) + len(prefetch),
            grid=(n,),
            in_specs=[q_spec, kv_spec, kv_spec, qd_spec,
                      *decode_specs(ix)],
            out_specs=[q_spec, qd_spec],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bhp, sp, d), qp.dtype),
            jax.ShapeDtypeStruct((bd, h, d), qd.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*[jnp.asarray(a) for a in sched], *prefetch, qp, kp, vp, qd,
      *decode_inputs)


def bullet_attention_paged(qp, kp, vp, qd, k_pages, v_pages, block_tables,
                           pos, *, decode_share: float = 0.5,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           group: int = 1, interpret: bool = False):
    """Fused prefill+decode attention with decode KV in a block-paged pool.

    Prefill: qp (BHp, Sp, D), kp/vp (BHp/group, Sp, D).
    Decode:  qd (Bd, K, G, D), pages (P+1, ps, K, D) shared physical pool,
             block_tables (Bd, n_b) int32 physical page per (slot, block) —
             every entry must name a valid page (trash page past a slot's
             live context), pos (Bd,) absolute position of the new token.
    Returns (out_p (BHp, Sp, D), out_d (Bd, K, G, D)).

    The decode tile stream walks ``(slot, block)``, one whole page (all KV
    heads) per tile; each tile's page index comes from the
    scalar-prefetched block table, so — like ``paged_decode_attention`` —
    only pages the tables name are ever DMA'd, while the Bresenham
    schedule still hides that HBM traffic under the prefill tiles' MXU
    work.
    """
    sp, d = qp.shape[1:]
    bd, kh, gg, _ = qd.shape
    h = kh * gg
    ps = k_pages.shape[1]
    n_b = block_tables.shape[1]
    bq, bk = min(block_q, sp), min(block_k, sp)
    assert sp % bq == 0 and sp % bk == 0
    same, tok = gqa_tile_masks(h, kh, ps)

    def decode_specs(ix):
        page = pl.BlockSpec((1, ps, kh, d), ix(
            lambda g, pbh, pqi, pki, db, dsi, pos, bt:
            (bt[db[g], dsi[g]], 0, 0, 0)))
        const = ix(lambda *_: (0, 0))
        return [page, page, pl.BlockSpec((h, ps * kh), const),
                pl.BlockSpec((1, ps * kh), const)]

    kernel = functools.partial(
        _bullet_paged_kernel, ps=ps, bq=bq, bk=bk, n_kv_p=sp // bk,
        n_s_d=n_b, causal=causal, window=window, scale=d ** -0.5)
    out_p, out_d = _fused_call(
        kernel, [pos.astype(jnp.int32), block_tables.astype(jnp.int32)],
        qp, kp, vp, qd.reshape(bd, h, d), decode_specs,
        [k_pages, v_pages, same, tok], n_d_tiles=bd * n_b, dims_d=(bd, n_b),
        decode_share=decode_share, bq=bq, bk=bk, group=group,
        interpret=interpret)
    return out_p, out_d.reshape(bd, kh, gg, d)


def bullet_attention(qp, kp, vp, qd, kd, vd, kv_positions, pos, *,
                     decode_share: float = 0.5,
                     causal: bool = True, window: int = 0,
                     block_q: int = 128, block_k: int = 128,
                     block_s: int = 128, group: int = 1,
                     interpret: bool = False):
    """Fused prefill+decode attention.

    Prefill: qp (BHp, Sp, D), kp/vp (BHp/group, Sp, D).
    Decode:  qd (Bd, K, G, D), kd/vd (Bd, Sk, K, D), kv_positions (Bd, Sk),
             pos (Bd,).
    Returns (out_p (BHp, Sp, D), out_d (Bd, K, G, D)).
    """
    sp, d = qp.shape[1:]
    bd, kh, gg, _ = qd.shape
    h = kh * gg
    sk = kd.shape[1]
    bq, bk = min(block_q, sp), min(block_k, sp)
    bs = min(block_s, sk)
    assert sp % bq == 0 and sp % bk == 0 and sk % bs == 0
    n_s = sk // bs
    # one position per flattened (token, kv head) column
    kvpos_cols = jnp.repeat(kv_positions.astype(jnp.int32), kh,
                            axis=1)[:, None, :]            # (Bd, 1, Sk·K)
    same, _ = gqa_tile_masks(h, kh, bs)

    def decode_specs(ix):
        tile = pl.BlockSpec((1, bs, kh, d), ix(
            lambda g, pbh, pqi, pki, db, dsi, pos: (db[g], dsi[g], 0, 0)))
        return [tile, tile,
                pl.BlockSpec((1, 1, bs * kh), ix(
                    lambda g, pbh, pqi, pki, db, dsi, pos:
                    (db[g], 0, dsi[g]))),
                pl.BlockSpec((h, bs * kh), ix(lambda *_: (0, 0)))]

    kernel = functools.partial(
        _bullet_kernel, bq=bq, bk=bk, n_kv_p=sp // bk, n_s_d=n_s,
        causal=causal, window=window, scale=d ** -0.5)
    out_p, out_d = _fused_call(
        kernel, [pos.astype(jnp.int32)], qp, kp, vp, qd.reshape(bd, h, d),
        decode_specs, [kd, vd, kvpos_cols, same], n_d_tiles=bd * n_s,
        dims_d=(bd, n_s), decode_share=decode_share, bq=bq, bk=bk,
        group=group, interpret=interpret)
    return out_p, out_d.reshape(bd, kh, gg, d)
