"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (bullet_attention_op, decode_attention_op,
                           flash_attention_op, paged_decode_attention_op,
                           rglru_scan_op, ssd_scan_op)
from repro.kernels import ref as R
from repro.kernels.bullet_attention import build_schedule
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kh,d", [
    (1, 32, 4, 4, 32), (2, 64, 8, 2, 32), (2, 48, 4, 1, 64), (1, 128, 2, 2, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kh, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, s, h, d), dtype)
    k = rand(ks[1], (b, s, kh, d), dtype)
    v = rand(ks[2], (b, s, kh, d), dtype)
    out = flash_attention_op(q, k, v, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d).astype(jnp.float32)
    kx = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(b * kh, s, d), h // kh, 0)
    vx = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(b * kh, s, d), h // kh, 0)
    ref = R.flash_attention_ref(qf.astype(jnp.float32), kx.astype(jnp.float32),
                                vx.astype(jnp.float32))
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_window():
    b, s, h, d = 1, 64, 2, 32
    ks = jax.random.split(KEY, 3)
    q, k, v = (rand(ks[i], (b, s, h, d)) for i in range(3))
    out = flash_attention_op(q, k, v, window=17, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    ref = R.flash_attention_ref(qf, kf, vf, window=17)
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,kh,g,s,d", [
    (2, 2, 4, 64, 32), (1, 4, 1, 128, 64), (3, 1, 8, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, kh, g, s, d, dtype):
    h = kh * g
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, 1, h, d), dtype)
    kc = rand(ks[1], (b, s, kh, d), dtype)
    vc = rand(ks[2], (b, s, kh, d), dtype)
    kvpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    pos = jnp.asarray(np.random.default_rng(0).integers(1, s, b))
    out = decode_attention_op(q, kc, vc, kvpos, pos, interpret=True)
    ref = R.decode_attention_ref(
        q[:, 0].reshape(b, kh, g, d).astype(jnp.float32),
        kc.astype(jnp.float32), vc.astype(jnp.float32), kvpos, pos)
    np.testing.assert_allclose(np.asarray(out[:, 0].reshape(b, kh, g, d),
                                          np.float32),
                               np.asarray(ref), atol=_tol(dtype),
                               rtol=_tol(dtype))


def test_decode_attention_ring_positions():
    """Ring-buffer semantics: scrambled kv_positions + holes."""
    b, kh, g, s, d = 2, 2, 2, 64, 32
    h = kh * g
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, 1, h, d))
    kc = rand(ks[1], (b, s, kh, d))
    vc = rand(ks[2], (b, s, kh, d))
    base = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    kvpos = jnp.where(base % 5 == 0, -1, (base * 13) % 80)
    pos = jnp.array([40, 70])
    out = decode_attention_op(q, kc, vc, kvpos, pos, interpret=True)
    ref = R.decode_attention_ref(q[:, 0].reshape(b, kh, g, d), kc, vc,
                                 kvpos, pos)
    np.testing.assert_allclose(np.asarray(out[:, 0].reshape(b, kh, g, d)),
                               np.asarray(ref), atol=2e-5)


def test_decode_attention_tail_block():
    """Cache lengths that are not a multiple of the kv block: the kernel
    pads the tail block and masks the padded slots instead of crashing."""
    from repro.kernels.decode_attention import decode_attention
    b, kh, g, s, d = 2, 2, 2, 72, 32
    h = kh * g
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, 1, h, d))
    kc = rand(ks[1], (b, s, kh, d))
    vc = rand(ks[2], (b, s, kh, d))
    kvpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    pos = jnp.array([50, 71])
    out = decode_attention(q[:, 0].reshape(b, kh, g, d), kc, vc, kvpos, pos,
                           block_s=32, interpret=True)   # 72 = 2*32 + 8 tail
    ref = R.decode_attention_ref(q[:, 0].reshape(b, kh, g, d), kc, vc,
                                 kvpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention (block-table gather over the shared page pool)
# ---------------------------------------------------------------------------

def _mk_paged(key, b, kh, d, n_pages, ps, n_b, seed_tables=0):
    ks = jax.random.split(key, 3)
    kp = rand(ks[0], (n_pages + 1, ps, kh, d))
    vp = rand(ks[1], (n_pages + 1, ps, kh, d))
    rng = np.random.default_rng(seed_tables)
    # each slot owns a disjoint shuffled set of physical pages
    perm = rng.permutation(n_pages)[:b * n_b].reshape(b, n_b)
    return kp, vp, jnp.asarray(perm, jnp.int32)


@pytest.mark.parametrize("b,kh,g,n_b,ps,d", [
    (2, 2, 4, 4, 16, 32), (1, 4, 1, 2, 32, 64), (3, 1, 8, 3, 16, 16),
])
def test_paged_decode_matches_dense(b, kh, g, n_b, ps, d):
    """Acceptance: paged decode == dense decode numerics (fp32, ≤1e-5)
    when the dense cache holds the gathered page contents."""
    h = kh * g
    n_pages = b * n_b + 2
    q = rand(jax.random.fold_in(KEY, 1), (b, 1, h, d))
    kp, vp, bt = _mk_paged(jax.random.fold_in(KEY, 2), b, kh, d,
                           n_pages, ps, n_b)
    pos = jnp.asarray(
        np.random.default_rng(1).integers(1, n_b * ps, b), jnp.int32)
    out = paged_decode_attention_op(q, kp, vp, bt, pos, interpret=True)
    # dense reference: gather each slot's pages into a contiguous cache
    kc = kp[bt].reshape(b, n_b * ps, kh, d)
    vc = vp[bt].reshape(b, n_b * ps, kh, d)
    kvpos = jnp.broadcast_to(jnp.arange(n_b * ps)[None], (b, n_b * ps))
    ref = decode_attention_op(q, kc, vc, kvpos, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)
    ref2 = R.paged_decode_attention_ref(q[:, 0].reshape(b, kh, g, d),
                                        kp, vp, bt, pos)
    np.testing.assert_allclose(
        np.asarray(out[:, 0].reshape(b, kh, g, d)), np.asarray(ref2),
        atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,kh,g,n_b,ps,d", [
    (2, 3, 2, 3, 16, 32), (3, 5, 1, 2, 8, 128),
])
def test_paged_decode_heads_major_pool(b, kh, g, n_b, ps, d):
    """The kernel reading the pool head-major, ``(P, K, ps, D)`` (how a TPU
    stores K heads that would pad its tiles), matches the same pool read
    in its own order."""
    h = kh * g
    q = rand(jax.random.fold_in(KEY, 7), (b, 1, h, d))
    kp, vp, bt = _mk_paged(jax.random.fold_in(KEY, 8), b, kh, d,
                           b * n_b + 1, ps, n_b)
    pos = jnp.asarray(
        np.random.default_rng(2).integers(1, n_b * ps, b), jnp.int32)
    base = paged_decode_attention_op(q, kp, vp, bt, pos, interpret=True)
    out = paged_decode_attention_op(q, jnp.swapaxes(kp, 1, 2),
                                    jnp.swapaxes(vp, 1, 2), bt, pos,
                                    kv_major=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-5,
                               rtol=1e-5)


def test_paged_decode_trash_page_isolation():
    """Entries past a slot's live context point at the trash page; its
    contents must never leak into the output (positional masking)."""
    b, kh, g, ps, n_b = 2, 2, 2, 16, 3
    h, d = kh * g, 32
    n_pages = b * n_b
    q = rand(jax.random.fold_in(KEY, 3), (b, 1, h, d))
    kp, vp, bt = _mk_paged(jax.random.fold_in(KEY, 4), b, kh, d,
                           n_pages, ps, n_b)
    pos = jnp.array([ps - 1, 2 * ps - 5])   # live: 1 page / 2 pages
    base = paged_decode_attention_op(q, kp, vp, bt, pos, interpret=True)
    # rewrite the dead table entries to the (poisoned) trash page
    kp = kp.at[n_pages].set(1e4)
    vp = vp.at[n_pages].set(-1e4)
    bt_np = np.asarray(bt).copy()
    bt_np[0, 1:] = n_pages
    bt_np[1, 2:] = n_pages
    out = paged_decode_attention_op(q, kp, vp, jnp.asarray(bt_np), pos,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-6)


def test_paged_decode_xla_fallback_matches_kernel():
    """models.attention.paged_decode_ref (the engine's off-TPU path) and
    the Pallas kernel implement the same contract."""
    from repro.models.attention import paged_decode_ref
    b, kh, g, ps, n_b = 2, 2, 2, 16, 2
    h, d = kh * g, 32
    q = rand(jax.random.fold_in(KEY, 5), (b, 1, h, d))
    kp, vp, bt = _mk_paged(jax.random.fold_in(KEY, 6), b, kh, d,
                           b * n_b, ps, n_b)
    pos = jnp.array([7, 30])
    out_k = paged_decode_attention_op(q, kp, vp, bt, pos, interpret=True)
    out_x = paged_decode_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# bullet fused attention (the paper's co-execution kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("share", [0.0, 0.25, 0.5, 0.75, 1.0])
def test_bullet_attention_shares(share):
    Bp, Sp, H, K, D = 2, 32, 4, 2, 32
    Bd, Sk = 2, 64
    ks = jax.random.split(KEY, 8)
    qp = rand(ks[0], (Bp, Sp, H, D))
    kp = rand(ks[1], (Bp, Sp, K, D))
    vp = rand(ks[2], (Bp, Sp, K, D))
    qd = rand(ks[3], (Bd, 1, H, D))
    kd = rand(ks[4], (Bd, Sk, K, D))
    vd = rand(ks[5], (Bd, Sk, K, D))
    kvpos = jnp.broadcast_to(jnp.arange(Sk)[None], (Bd, Sk))
    pos = jnp.array([40, 63])
    op, od = bullet_attention_op(qp, kp, vp, qd, kd, vd, kvpos, pos,
                                 decode_share=share, interpret=True)
    ref_p = flash_attention_op(qp, kp, vp, interpret=True)
    ref_d = decode_attention_op(qd, kd, vd, kvpos, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(op), np.asarray(ref_p), atol=2e-5)
    np.testing.assert_allclose(np.asarray(od), np.asarray(ref_d), atol=2e-5)


def test_bullet_schedule_properties():
    for n_p, n_d, share in [(10, 10, 0.5), (7, 3, 0.25), (0, 5, 0.5),
                            (5, 0, 0.9), (100, 10, 0.1)]:
        ph = build_schedule(n_p, n_d, share)
        assert len(ph) == n_p + n_d
        assert int((ph == 0).sum()) == n_p
        assert int((ph == 1).sum()) == n_d


# ---------------------------------------------------------------------------
# recurrent scans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,w", [(2, 32, 16), (4, 17, 8), (1, 64, 128)])
def test_rglru_scan_sweep(b, s, w):
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(rand(ks[0], (b, s, w)))
    bb = rand(ks[1], (b, s, w))
    y, hT = rglru_scan_op(a, bb, interpret=True)
    yr, hr = R.rglru_scan_ref(a, bb)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr), atol=1e-5)


def test_rglru_scan_with_initial_state():
    b, s, w = 2, 16, 8
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(rand(ks[0], (b, s, w)))
    bb = rand(ks[1], (b, s, w))
    h0 = rand(ks[2], (b, w))
    y, _ = rglru_scan_op(a, bb, h0, interpret=True)
    yr, _ = R.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 48, 3, 8, 4, 16), (1, 64, 2, 16, 8, 32), (2, 32, 4, 4, 16, 8),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 6)
    x = rand(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, s, h)))
    A = -jnp.exp(rand(ks[2], (h,)))
    B_ = rand(ks[3], (b, s, n))
    C = rand(ks[4], (b, s, n))
    D = rand(ks[5], (h,))
    y, st = ssd_scan_op(x, dt, A, B_, C, D, chunk=chunk, interpret=True)
    yr, sr = ssd_chunked(x, dt, A, B_, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=2e-4)
