"""Paged decode step with the page pool as a scan carry.

``transformer.decode_step`` with block tables carries each layer stack's
pool through the layer scan viewed flat, ``(R·(P+1), ps, K, D)``, and
offsets the tables by ``rep·(P+1)`` per layer. These tests hold it to the
per-layer path it replaced — each layer's pool sliced out of the stack,
written, attended and stacked back — bit for bit, check that every layer's
pool changes only at its own slots' pages and its own trash page, and that
a deeper paged engine samples the dense engine's tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import CacheConfig, ServerConfig
from repro.core.engine import BulletServer
from repro.models import attention, init_params
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving.request import Request, SLO

#: 3 pattern repeats, so layers 1 and 2 read pages at a nonzero offset
CFG = get_config("qwen3-1.7b").reduced(n_layers=3)
PS, N_B, N_PAGES, STEPS = 4, 3, 8, 5
#: slot 0 owns pages 5, 2, 7; slot 1 is inactive: all trash
TABLES = np.array([[5, 2, 7], [N_PAGES] * N_B], np.int32)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


def _random_pool(seed):
    """A pool whose every page holds distinct values, so any write shows."""
    shape = (CFG.n_pattern_repeats, N_PAGES + 1, PS, CFG.n_kv_heads,
             CFG.head_dim)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"blocks": ({n: jax.random.normal(k, shape).astype(jnp.bfloat16)
                        for n, k in zip(("k", "v"), ks)},)}


def _per_layer_decode(params, cache, tokens, pos, block_tables, cfg):
    """The paged decode step as a scan over each layer's own pool: slices
    the stacked pool per layer and stacks the written pools back."""
    x = T.embed_tokens(params, tokens, cfg, None)

    def body(x, slices):
        p_slices, c_slices = slices
        entries = []
        for j, blk in enumerate(cfg.pattern):
            x, e = T._apply_block_decode(x, p_slices[j], blk, cfg, None,
                                         c_slices[j], pos, None,
                                         block_tables=block_tables)
            entries.append(e)
        return x, tuple(entries)

    x, blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    x = L.rms_norm(x, params["final_norm"], cfg.rmsnorm_eps)
    return T.lm_logits(params, x, cfg, None)[:, 0], {"blocks": blocks}


def _run(step, params, cache, tokens0, pos0):
    """``STEPS`` greedy iterations; slot 1 keeps its stale token and pos."""
    tokens, pos = tokens0, pos0
    logits_seen = []
    for _ in range(STEPS):
        logits, cache = step(params, cache, tokens, pos,
                             jnp.asarray(TABLES))
        logits_seen.append(np.asarray(logits))
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        tokens = jnp.stack([nxt[0], tokens[1, 0]])[:, None]
        pos = pos.at[0].add(1)
    return logits_seen, cache


@pytest.mark.parametrize("heads_major", [False, True])
def test_paged_decode_step_matches_per_layer_path(params, monkeypatch,
                                                  heads_major):
    """Logits and every pool bit for bit against the per-layer path, for
    the pool in its own order and viewed head-major (as a TPU stores 20
    bf16 KV heads)."""
    monkeypatch.setattr(attention, "pool_heads_major",
                        lambda pages: heads_major)
    tokens = jnp.array([[11], [3]], jnp.int32)
    pos = jnp.array([5, 2], jnp.int32)
    new = jax.jit(lambda p, c, t, q, bt: T.decode_step(
        p, c, t, q, CFG, block_tables=bt))
    old = jax.jit(lambda p, c, t, q, bt: _per_layer_decode(
        p, c, t, q, bt, CFG))
    lg_new, c_new = _run(new, params, _random_pool(1), tokens, pos)
    lg_old, c_old = _run(old, params, _random_pool(1), tokens, pos)
    for a, b in zip(lg_new, lg_old):
        np.testing.assert_array_equal(a, b)
    for e_new, e_old in zip(c_new["blocks"], c_old["blocks"]):
        for n in ("k", "v"):
            assert e_new[n].shape == e_old[n].shape
            np.testing.assert_array_equal(
                np.asarray(e_new[n].astype(jnp.float32)),
                np.asarray(e_old[n].astype(jnp.float32)))


def test_paged_decode_writes_only_own_pages(params):
    """Each layer's pool changes only where its slots' tokens go: slot 0's
    (page, offset) per step, and the inactive slot's writes on that
    layer's own trash page — never another layer's pages."""
    tokens = jnp.array([[11], [3]], jnp.int32)
    pos = jnp.array([5, 2], jnp.int32)
    step = jax.jit(lambda p, c, t, q, bt: T.decode_step(
        p, c, t, q, CFG, block_tables=bt))
    before = _random_pool(2)
    _, after = _run(step, params, before, tokens, pos)
    expect = np.zeros((N_PAGES + 1, PS), bool)
    for t in range(STEPS):
        p0 = 5 + t
        expect[TABLES[0, p0 // PS], p0 % PS] = True
    expect[N_PAGES, 2 % PS] = True           # slot 1: stale pos 2, trash
    for n in ("k", "v"):
        b = np.asarray(before["blocks"][0][n].astype(jnp.float32))
        a = np.asarray(after["blocks"][0][n].astype(jnp.float32))
        changed = (a != b).any(axis=(-2, -1))     # (R, P+1, ps)
        for r in range(CFG.n_pattern_repeats):
            np.testing.assert_array_equal(changed[r], expect, err_msg=n)


def test_deep_paged_engine_matches_dense_engine(params):
    """A 3-repeat paged engine with 2 slots samples the dense engine's
    tokens; requests of unequal length leave a slot inactive while the
    other decodes."""
    outs = []
    for paged in (False, True):
        server = BulletServer(CFG, params, config=ServerConfig(
            slo=SLO(3.0, 150.0), max_slots=2, max_len=48,
            cache=CacheConfig(paged=paged, page_size=4)))
        assert server.paged == paged
        for rid, out_len in enumerate((7, 2, 4)):
            plen = 5 + 3 * rid
            prompt = np.random.default_rng(rid).integers(
                0, CFG.vocab_size, plen)
            server.submit(Request(rid=rid, arrival=0.0, prompt_len=plen,
                                  output_len=out_len), prompt)
        outs.append(server.run())
        if paged:
            server.pool.check_invariants()
            assert server.pool.free_blocks == server.pool.n_blocks
    assert outs[1] == outs[0]
    assert all(len(v) for v in outs[0].values())
