"""Jit'd public wrappers around the Pallas kernels.

These adapt model-layout tensors ((B, S, H, D) etc.) to kernel layouts, pick
TPU-aligned block sizes, and fall back to interpret mode off-TPU (the CPU
tests) so the same call sites work everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bullet_attention as _bullet
from repro.kernels import decode_attention as _decode
from repro.kernels import flash_attention as _flash
from repro.kernels import paged_decode_attention as _paged
from repro.kernels import rglru_scan as _rglru
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (prefer target itself)."""
    if n % target == 0:
        return target
    b = min(n, target)
    while n % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=0, interpret=None):
    """Model layout: q (B,S,H,D), k/v (B,S,K,D). Returns (B,S,H,D)."""
    if interpret is None:
        interpret = not _on_tpu()
    b, s, h, d = q.shape
    kh = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, s, d)
    bq = _pick_block(s, 128)
    bk = _pick_block(s, 128)
    o = _flash.flash_attention(qf, kf, vf, causal=causal, window=window,
                               block_q=bq, block_k=bk, group=h // kh,
                               interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_op(q, k_cache, v_cache, kv_positions, pos, *,
                        interpret=None):
    """Model layout: q (B,1,H,D), caches (B,S,K,D). Returns (B,1,H,D)."""
    if interpret is None:
        interpret = not _on_tpu()
    b, _, h, d = q.shape
    kh = k_cache.shape[2]
    g = h // kh
    qr = q[:, 0].reshape(b, kh, g, d)
    bs = _pick_block(k_cache.shape[1], 128)
    o = _decode.decode_attention(qr, k_cache, v_cache, kv_positions, pos,
                                 block_s=bs, interpret=interpret)
    return o.reshape(b, 1, h, d)


@functools.partial(jax.jit, static_argnames=("kv_major", "interpret"))
def paged_decode_attention_op(q, k_pages, v_pages, block_tables, pos, *,
                              kv_major=False, interpret=None):
    """Model layout: q (B,1,H,D), pages (P,ps,K,D) — (P,K,ps,D) with
    ``kv_major`` — block_tables (B,n_b) int32 physical pages, pos (B,).
    Returns (B,1,H,D)."""
    if interpret is None:
        interpret = not _on_tpu()
    b, _, h, d = q.shape
    kh = k_pages.shape[1 if kv_major else 2]
    g = h // kh
    qr = q[:, 0].reshape(b, kh, g, d)
    o = _paged.paged_decode_attention(qr, k_pages, v_pages, block_tables,
                                      pos, kv_major=kv_major,
                                      interpret=interpret)
    return o.reshape(b, 1, h, d)


@functools.partial(jax.jit, static_argnames=(
    "decode_share", "causal", "window", "interpret"))
def bullet_attention_op(qp, kp, vp, qd, kd, vd, kv_positions, pos, *,
                        decode_share=0.5, causal=True, window=0,
                        interpret=None):
    """Fused hybrid-batch attention (model layouts).

    Prefill: qp (Bp,Sp,H,D), kp/vp (Bp,Sp,K,D).
    Decode:  qd (Bd,1,H,D), kd/vd (Bd,Sk,K,D).
    Returns (out_p (Bp,Sp,H,D), out_d (Bd,1,H,D)).
    """
    if interpret is None:
        interpret = not _on_tpu()
    bp, sp, h, d = qp.shape
    kh = kp.shape[2]
    g = h // kh
    bd = qd.shape[0]
    qpf = qp.transpose(0, 2, 1, 3).reshape(bp * h, sp, d)
    kpf = kp.transpose(0, 2, 1, 3).reshape(bp * kh, sp, d)
    vpf = vp.transpose(0, 2, 1, 3).reshape(bp * kh, sp, d)
    qdr = qd[:, 0].reshape(bd, kh, g, d)
    op, od = _bullet.bullet_attention(
        qpf, kpf, vpf, qdr, kd, vd, kv_positions, pos,
        decode_share=decode_share, causal=causal, window=window,
        block_q=_pick_block(sp, 128), block_k=_pick_block(sp, 128),
        block_s=_pick_block(kd.shape[1], 128), group=g,
        interpret=interpret)
    out_p = op.reshape(bp, h, sp, d).transpose(0, 2, 1, 3)
    return out_p, od.reshape(bd, 1, h, d)


@functools.partial(jax.jit, static_argnames=(
    "decode_share", "causal", "window", "interpret"))
def bullet_attention_paged_op(qp, kp, vp, qd, k_pages, v_pages, block_tables,
                              pos, *, decode_share=0.5, causal=True,
                              window=0, interpret=None):
    """Fused hybrid-batch attention with paged decode KV (model layouts).

    Prefill: qp (Bp,Sp,H,D), kp/vp (Bp,Sp,K,D).
    Decode:  qd (Bd,1,H,D), pages (P+1,ps,K,D), block_tables (Bd,n_b) int32
             physical pages (trash page past live context), pos (Bd,).
    Returns (out_p (Bp,Sp,H,D), out_d (Bd,1,H,D)).
    """
    if interpret is None:
        interpret = not _on_tpu()
    bp, sp, h, d = qp.shape
    kh = kp.shape[2]
    g = h // kh
    bd = qd.shape[0]
    qpf = qp.transpose(0, 2, 1, 3).reshape(bp * h, sp, d)
    kpf = kp.transpose(0, 2, 1, 3).reshape(bp * kh, sp, d)
    vpf = vp.transpose(0, 2, 1, 3).reshape(bp * kh, sp, d)
    qdr = qd[:, 0].reshape(bd, kh, g, d)
    op, od = _bullet.bullet_attention_paged(
        qpf, kpf, vpf, qdr, k_pages, v_pages, block_tables, pos,
        decode_share=decode_share, causal=causal, window=window,
        block_q=_pick_block(sp, 128), block_k=_pick_block(sp, 128),
        group=g, interpret=interpret)
    out_p = op.reshape(bp, h, sp, d).transpose(0, 2, 1, 3)
    return out_p, od.reshape(bd, 1, h, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru_scan_op(a, b, h0=None, *, interpret=None):
    """a, b: (B,S,W). Returns (y (B,S,W), h_T (B,W))."""
    if interpret is None:
        interpret = not _on_tpu()
    bsz, s, w = a.shape
    y = _rglru.rglru_scan(a, b, h0,
                          block_b=_pick_block(bsz, 8),
                          block_w=_pick_block(w, 128),
                          block_s=_pick_block(s, 256),
                          interpret=interpret)
    return y, y[:, -1].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_op(x, dt, A, B_, C, D, *, chunk=256, interpret=None):
    """Model layout (matches repro.models.ssm.ssd_chunked):

    x (B,S,H,P), dt (B,S,H) softplus'd, A (H,) negative, B_/C (B,S,N),
    D (H,). Returns (y (B,S,H,P), final_state (B,H,P,N) fp32).
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, s, h, p = x.shape
    n = B_.shape[-1]
    q = min(chunk, s)
    pad = (q - s % q) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // q
    da = (dt * A[None, None, :]).reshape(b, nc, q, h)
    cum = jnp.cumsum(da, axis=2)
    xw = (x * dt[..., None]).reshape(b, nc, q, h, p)
    Bc = B_.reshape(b, nc, q, n)
    Cc = C.reshape(b, nc, q, n)
    y = _ssd.ssd_scan(xw, cum, Bc, Cc, interpret=interpret)
    y = y.reshape(b, sp, h, p)[:, :s]
    y = y + x[:, :s] * D[None, None, :, None]
    # final state recovered analytically (same recurrence over chunk sums)
    d2e = jnp.exp(cum[:, :, -1:, :] - cum)
    cs = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc, d2e.astype(Bc.dtype), xw)
    cd = jnp.exp(cum[:, :, -1, :])
    def body(st, inp):
        s_c, d_c = inp
        return st * d_c[..., None, None] + s_c, None
    state, _ = jax.lax.scan(
        body, jnp.zeros((b, h, p, n), jnp.float32),
        (cs.astype(jnp.float32).transpose(1, 0, 2, 3, 4),
         cd.transpose(1, 0, 2)))
    return y.astype(x.dtype), state
