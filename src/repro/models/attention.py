"""Attention ops (XLA backend).

- ``flash_ref_attention``: blockwise online-softmax causal/windowed attention
  (never materializes the S×S score matrix) — used for training & prefill.
- ``decode_attention``: single-token GQA attention over a KV cache.
- ``seq_parallel_decode_attention``: flash-decoding-style shard_map over the
  cache *sequence* dim for architectures whose KV heads do not divide the
  model axis (DESIGN.md §4).

The Pallas TPU kernels in ``repro.kernels`` implement the same contracts and
are validated against these (and their ref.py oracles) in interpret mode.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


#: the Pallas kernels' sequence block: on a TPU every prefill length is a
#: multiple of it (the engine pads prompt batches with ``prefill_length``)
KERNEL_BLOCK = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas_kernels() -> bool:
    """Route attention through the Pallas TPU kernels when running on TPU
    (or when forced via REPRO_FORCE_PALLAS=1, which uses interpret mode —
    CPU tests exercise this path in tests/test_kernels.py)."""
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return _on_tpu()


def prefill_length(n: int) -> int:
    """The padded length of a prefill batch whose longest row has ``n``
    tokens: on a TPU a multiple of ``KERNEL_BLOCK``, so prefill and the
    fused cycle run the Pallas kernels; ``n`` elsewhere, so the CPU path
    keeps its numerics reference. Padding sits after every row's last
    real token, where causal attention never reads it."""
    if _on_tpu():
        return -(-n // KERNEL_BLOCK) * KERNEL_BLOCK
    return n


def _prefill_kernel_ok(s: int) -> bool:
    """Whether a prefill of ``s`` tokens takes the Pallas kernels. On a
    TPU a length they cannot tile is an error, never a quiet XLA detour;
    off the TPU (forced interpret mode) such lengths keep the XLA path."""
    if not use_pallas_kernels():
        return False
    if s % KERNEL_BLOCK and _on_tpu():
        raise ValueError(
            f"prefill length {s} is not a multiple of {KERNEL_BLOCK}: pad "
            "the batch with prefill_length() before attention on a TPU")
    return s % KERNEL_BLOCK == 0


def attention_prefill(q, k, v, *, causal=True, window=0, block_size=None,
                      kernels: bool = True):
    """Backend-dispatching prefill attention (model layout).

    ``kernels=False`` keeps the XLA ops on every backend (the numerics
    reference a TPU run compares its kernels with).

    §Perf knobs: REPRO_ATTN_BLOCK (kv block), REPRO_ATTN_BF16_PROBS
    (half-precision probabilities), REPRO_ATTN_CAUSAL_SKIP (q-chunked scan
    with a dynamic kv bound — skips fully-masked upper-triangle blocks;
    forward-only, used by the serving prefill path).
    """
    if kernels and _prefill_kernel_ok(q.shape[1]):
        from repro.kernels import flash_attention_op
        return flash_attention_op(q, k, v, causal=causal, window=window)
    if block_size is None:
        block_size = int(os.environ.get("REPRO_ATTN_BLOCK", "1024"))
    if (causal and os.environ.get("REPRO_ATTN_CAUSAL_SKIP") == "1"
            and q.shape[1] == k.shape[1] and q.shape[1] % block_size == 0):
        return flash_ref_attention_causal_skip(
            q, k, v, window=window, block_size=block_size)
    return flash_ref_attention(q, k, v, causal=causal, window=window,
                               block_size=block_size)


def attention_decode(q, k_cache, v_cache, kv_positions, pos):
    """Backend-dispatching decode attention (model layout, unsharded)."""
    if use_pallas_kernels() and k_cache.shape[1] % 128 == 0:
        from repro.kernels import decode_attention_op
        return decode_attention_op(q, k_cache, v_cache, kv_positions, pos)
    return decode_attention(q, k_cache, v_cache, kv_positions, pos)


def attention_decode_paged(q, k_pages, v_pages, block_tables, pos):
    """Backend-dispatching decode attention over a block-paged cache.

    q: (B, 1, H, D); pages: (P, ps, K, D) shared physical page pool;
    block_tables: (B, n_b) int32 physical page per (slot, block) — every
    entry must be a valid page index (unused entries point at a trash
    page); pos: (B,) absolute position of the current token. Streams only
    the pages the tables name, so HBM traffic scales with live context.
    """
    if use_pallas_kernels():
        from repro.kernels import paged_decode_attention_op
        return paged_decode_attention_op(q, k_pages, v_pages, block_tables,
                                         pos)
    return paged_decode_ref(q, k_pages, v_pages, block_tables, pos)


def attention_fused_paged(qp, kp, vp, qd, k_pages, v_pages, block_tables,
                          pos, *, decode_share: float = 0.5,
                          causal: bool = True, window: int = 0):
    """Backend-dispatching fused prefill+decode attention (model layout).

    One call computes a prefill batch's attention (qp/kp/vp, (Bp,Sp,·,D))
    AND a decode iteration's paged attention (qd (Bd,1,H,D) over the page
    pool) — on TPU through the bullet co-execution schedule whose grid
    interleaves the two tile streams by ``decode_share``, off-TPU through
    the exact same XLA ops the serial engine uses (``attention_prefill`` +
    ``attention_decode_paged``), so fused and serial engines are
    token-identical on every backend.
    """
    if _prefill_kernel_ok(qp.shape[1]):
        from repro.kernels import bullet_attention_paged_op
        return bullet_attention_paged_op(
            qp, kp, vp, qd, k_pages, v_pages, block_tables, pos,
            decode_share=decode_share, causal=causal, window=window)
    out_p = attention_prefill(qp, kp, vp, causal=causal, window=window)
    out_d = attention_decode_paged(qd, k_pages, v_pages, block_tables, pos)
    return out_p, out_d


def gather_pages(pages, block_tables):
    """Materialize each slot's paged KV as a contiguous per-slot cache:
    pages (P, ps, K, D) + tables (B, n_b) -> (B, n_b·ps, K, D). Positions
    are contiguous from 0 by construction of the paged layout."""
    b, n_b = block_tables.shape
    ps = pages.shape[1]
    return pages[block_tables].reshape(b, n_b * ps, *pages.shape[2:])


def paged_decode_ref(q, k_pages, v_pages, block_tables, pos):
    """XLA fallback + numerics reference for the paged kernel: gather each
    slot's pages into a contiguous per-slot cache and run the dense path."""
    b, n_b = block_tables.shape
    ps = k_pages.shape[1]
    kc = gather_pages(k_pages, block_tables)
    vc = gather_pages(v_pages, block_tables)
    kvpos = jnp.broadcast_to(jnp.arange(n_b * ps)[None], (b, n_b * ps))
    return decode_attention(q, kc, vc, kvpos, pos)


def write_paged_kv(k_pages, v_pages, k_new, v_new, block_tables, pos):
    """Write one new token's K/V into the page pool.

    k_new/v_new: (B, 1, K, D); the token at absolute position ``pos[b]``
    lands in page ``block_tables[b, pos[b] // ps]`` at offset
    ``pos[b] % ps``. The block index is clamped to the table width so
    slots with stale ``pos`` (inactive) write into whatever page their
    table names there — engines point unused table entries at a trash
    page, making those writes harmless.
    """
    ps = k_pages.shape[1]
    n_b = block_tables.shape[1]
    bi = jnp.clip(pos // ps, 0, n_b - 1)
    phys = jnp.take_along_axis(block_tables, bi[:, None], axis=1)[:, 0]
    off = jnp.clip(pos % ps, 0, ps - 1)
    k_pages = k_pages.at[phys, off].set(k_new[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[phys, off].set(v_new[:, 0].astype(v_pages.dtype))
    return k_pages, v_pages


def prefix_suffix_attention(q, k_sfx, v_sfx, k_pre, v_pre, prefix_len,
                            q_positions):
    """Suffix prefill attending over a reused (gathered) KV prefix.

    The shared-prefix prefill path (docs/KV_SHARING.md): a cache-hit
    request recomputes only its unshared suffix, whose queries must attend
    both the freshly projected suffix KV and the prefix KV already sitting
    in shared pages.

    q: (B, S, H, D) suffix queries at absolute positions ``q_positions``
    (B, S); k_sfx/v_sfx: (B, S, K, D) the suffix's own KV; k_pre/v_pre:
    (B, Lp, K, D) prefix KV gathered from the page pool, slot ``t`` valid
    iff ``t < prefix_len[b]`` (slot index == absolute position, since
    shared pages are prompt-aligned from 0). Padded suffix columns are
    masked by causality: their positions exceed every valid query's.
    Single-block evaluation (serving suffixes are short); mirrors
    ``flash_ref_attention``'s op sequence so an empty prefix is
    numerically identical to the plain prefill path.
    """
    b, sq, h, d = q.shape
    lp = k_pre.shape[1]
    scale = d ** -0.5
    q = (q * scale).astype(q.dtype)
    kc = jnp.concatenate([k_pre.astype(k_sfx.dtype), k_sfx], axis=1)
    vc = jnp.concatenate([v_pre.astype(v_sfx.dtype), v_sfx], axis=1)
    pre_pos = jnp.broadcast_to(jnp.arange(lp)[None], (b, lp))
    pre_pos = jnp.where(pre_pos < prefix_len[:, None], pre_pos,
                        jnp.iinfo(jnp.int32).max)
    kv_pos = jnp.concatenate([pre_pos, q_positions], axis=1)  # (B, Lp+S)
    logits = _gqa_logits(q, kc)                         # (B,K,G,Sq,Lp+S)
    mask = kv_pos[:, None, :] <= q_positions[:, :, None]      # (B,Sq,Sk)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc
                     ).astype(jnp.float32)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def _gqa_logits(q, k):
    """q: (B,Sq,H,D), k: (B,Sk,K,D) -> (B, K, H/K, Sq, Sk) fp32 logits."""
    b, sq, h, d = q.shape
    kheads = k.shape[2]
    g = h // kheads
    qg = q.reshape(b, sq, kheads, g, d)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: (B,K,G,Sq,Sk) fp32, v: (B,Sk,K,D) -> (B,Sq,H,D)."""
    b, kheads, g, sq, sk = p.shape
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(b, sq, kheads * g, -1)


def flash_ref_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: int = 0,
                        q_offset=0,
                        block_size: int = 1024) -> jax.Array:
    """Blockwise attention with online softmax.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H % K == 0.
    ``q_offset``: absolute position of q[0] relative to k[0] (chunked prefill).
    ``window`` > 0 enables sliding-window masking (|i-j| < window).
    Scans over KV blocks so peak memory is O(Sq × block_size) per head.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kheads = k.shape[2]
    g = h // kheads
    scale = d ** -0.5
    q = (q * scale).astype(q.dtype)

    bs = min(block_size, sk)
    n_blocks = -(-sk // bs)
    pad = n_blocks * bs - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, n_blocks, bs, kheads, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, bs, kheads, d).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(sq) + q_offset                       # (Sq,)

    def body(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, blk_idx = blk
        k_pos = blk_idx * bs + jnp.arange(bs)               # (bs,)
        logits = _gqa_logits(q, k_blk)                      # (B,K,G,Sq,bs)
        mask = jnp.broadcast_to(k_pos[None, :] < sk, (sq, bs))
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kheads, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kheads, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kheads, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (kb, vb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def flash_ref_attention_causal_skip(q, k, v, *, window: int = 0,
                                    block_size: int = 1024):
    """Causal blockwise attention that SKIPS fully-masked kv blocks.

    One scan over the *statically flattened lower triangle* of
    (q_block, kv_block) pairs — nq(nq+1)/2 steps instead of nq² — so
    upper-triangle blocks are never fetched or computed, halving attention
    FLOPs and HBM traffic, with a static trip count (exact roofline
    accounting). Online-softmax carries reset at each row start; outputs
    are gathered at the (static) row-end steps. Forward-only path used by
    serving prefill; training keeps flash_ref_attention.
    """
    import numpy as np
    b, s, h, d = q.shape
    kheads = k.shape[2]
    g = h // kheads
    bs = block_size
    nq = s // bs
    scale = d ** -0.5
    probs_dtype = (jnp.bfloat16 if os.environ.get("REPRO_ATTN_BF16_PROBS")
                   == "1" else jnp.float32)

    kb = k.reshape(b, nq, bs, kheads, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nq, bs, kheads, d).transpose(1, 0, 2, 3, 4)
    qb = (q * scale).reshape(b, nq, bs, h, d).transpose(1, 0, 2, 3, 4)

    qi_l, ki_l = [], []
    for qi in range(nq):
        lo = max(0, (qi * bs - window) // bs) if window > 0 else 0
        for ki in range(lo, qi + 1):
            qi_l.append(qi)
            ki_l.append(ki)
    QI = jnp.asarray(qi_l, jnp.int32)
    KI = jnp.asarray(ki_l, jnp.int32)
    row_start = jnp.asarray(
        [1 if (i == 0 or qi_l[i] != qi_l[i - 1]) else 0
         for i in range(len(qi_l))], bool)
    ends = np.asarray([i for i in range(len(qi_l))
                       if i + 1 == len(qi_l) or qi_l[i + 1] != qi_l[i]])

    def step(carry, inp):
        m, l, acc = carry
        qi, ki, reset = inp
        m = jnp.where(reset, NEG_INF, m)
        l = jnp.where(reset, 0.0, l)
        acc = jnp.where(reset, 0.0, acc)
        q_i = jax.lax.dynamic_index_in_dim(qb, qi, 0, keepdims=False)
        k_blk = jax.lax.dynamic_index_in_dim(kb, ki, 0, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vb, ki, 0, keepdims=False)
        q_pos = qi * bs + jnp.arange(bs)
        k_pos = ki * bs + jnp.arange(bs)
        logits = _gqa_logits(q_i, k_blk)                   # (B,K,G,bs,bs)
        mask = k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None]).astype(probs_dtype)
        l_new = l * alpha + p.sum(axis=-1).astype(jnp.float32)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)
        y = (acc_new / jnp.maximum(l_new, 1e-30)[..., None]
             ).transpose(0, 3, 1, 2, 4).reshape(b, bs, h, d).astype(q.dtype)
        return (m_new, l_new, acc_new), y

    m0 = jnp.full((b, kheads, g, bs), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kheads, g, bs), jnp.float32)
    acc0 = jnp.zeros((b, kheads, g, bs, d), jnp.float32)
    _, ys = jax.lax.scan(step, (m0, l0, acc0), (QI, KI, row_start))
    out = ys[ends]                                         # (nq, B, bs, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_positions: jax.Array, pos: jax.Array) -> jax.Array:
    """Single-token attention over a cache.

    q: (B, 1, H, D); caches: (B, S, K, D); kv_positions: (B, S) absolute
    position of each cache slot (−1 = empty; ring buffers permute them);
    pos: (B,) current absolute position. Returns (B, 1, H, D).
    """
    d = q.shape[-1]
    logits = _gqa_logits(q * d ** -0.5, k_cache)            # (B,K,G,1,S)
    valid = (kv_positions >= 0) & (kv_positions <= pos[:, None])
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return _gqa_out(p, v_cache)


def seq_parallel_decode_attention(q, k_cache, v_cache, kv_positions, pos, *,
                                  mesh, axis: str, batch_axes=None):
    """Flash-decoding over a sequence-sharded cache.

    Caches are sharded (B_batch_axes, S/axis, K, D); q replicated over
    ``axis`` but sharded over ``batch_axes``. Each shard computes a partial
    softmax (m, l, o) over its cache slice and the results are merged with
    exp-weighted psums over ``axis`` only.
    """
    d = q.shape[-1]
    bax = batch_axes

    def local(q, kc, vc, kv_pos, pos):
        logits = _gqa_logits(q * d ** -0.5, kc)             # (B,K,G,1,S_loc)
        valid = (kv_pos >= 0) & (kv_pos <= pos[:, None])
        logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
        m = logits.max(axis=-1)                             # (B,K,G,1)
        p = jnp.exp(logits - m[..., None])
        p = jnp.where(valid[:, None, None, None, :], p, 0.0)
        l = p.sum(axis=-1)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc
                       ).astype(jnp.float32)
        m_g = jax.lax.pmax(m, axis)
        scale = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * scale, axis)
        o_g = jax.lax.psum(o * scale[..., None], axis)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        b, kh, g, sq, dd = out.shape
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, kh * g, dd
                                                    ).astype(q.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bax), P(bax, axis), P(bax, axis), P(bax, axis), P(bax)),
        out_specs=P(bax),
        check_vma=False)
    return fn(q, k_cache, v_cache, kv_positions, pos)


def write_cache_slot(cache: jax.Array, new: jax.Array, slot: jax.Array):
    """Write ``new`` (B, 1, K, D) into ``cache`` (B, S, K, D) at per-batch
    ``slot`` (B,) indices (vmapped dynamic_update_slice)."""
    def upd(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))
    return jax.vmap(upd)(cache, new, slot)


def write_cache_slot_seq_sharded(cache, new, slot, *, mesh, axis: str,
                                 batch_axes=None):
    """Sequence-sharded variant of ``write_cache_slot``.

    cache: (B, S, K, D) sharded (batch_axes, axis); the shard owning
    ``slot`` performs the write, others keep their slice unchanged.
    """
    bax = batch_axes
    def local(c, n, s):
        s_loc = c.shape[1]
        idx = jax.lax.axis_index(axis)
        local_slot = s - idx * s_loc
        owns = (local_slot >= 0) & (local_slot < s_loc)
        clamped = jnp.clip(local_slot, 0, s_loc - 1)
        def upd(ci, ni, sl, ow):
            written = jax.lax.dynamic_update_slice(ci, ni, (sl, 0, 0))
            return jnp.where(ow, written, ci)
        return jax.vmap(upd)(c, n, clamped, owns)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bax, axis), P(bax), P(bax)),
        out_specs=P(bax, axis),
        check_vma=False)
    return fn(cache, new, slot)
