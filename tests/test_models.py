"""Model-zoo unit tests: attention variants, MoE routing, recurrences,
sharding spec consistency."""


import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.configs import get_config
from repro.models import attention as A
from repro.models import init_params, param_specs, init_cache, cache_specs
from repro.models.moe import moe_ffn, route_topk, _capacity
from repro.models.sharding import make_policy
from repro.launch.mesh import make_host_mesh

KEY = jax.random.PRNGKey(0)


# -- attention ----------------------------------------------------------------

def naive_attention(q, k, v, causal=True, window=0):
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    logits = jnp.einsum("bqkgd,bskd->bkgqs",
                        (q * d ** -0.5).reshape(b, s, kh, g, d), k)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    m = jnp.ones((s, s), bool)
    if causal:
        m &= j <= i
    if window:
        m &= j > i - window
    logits = jnp.where(m, logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(b, s, h, d)


@settings(max_examples=20, deadline=None)
@given(s=st.integers(4, 48), window=st.integers(0, 20),
       block=st.sampled_from([4, 8, 16]))
def test_flash_ref_matches_naive(s, window, block):
    q = jax.random.normal(KEY, (2, s, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, s, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, 2, 16))
    out = A.flash_ref_attention(q, k, v, causal=True, window=window,
                                block_size=block)
    ref = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_seq_parallel_matches_plain_decode():
    mesh = make_host_mesh(1, 1)
    B, S, H, K, D = 2, 32, 8, 2, 16
    q = jax.random.normal(KEY, (B, 1, H, D))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, D))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, D))
    kvpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    pos = jnp.array([10, 31])
    plain = A.decode_attention(q, kc, vc, kvpos, pos)
    sp = A.seq_parallel_decode_attention(q, kc, vc, kvpos, pos,
                                         mesh=mesh, axis="model")
    np.testing.assert_allclose(np.asarray(plain), np.asarray(sp), atol=1e-5)


def test_ring_cache_write_equivalence():
    mesh = make_host_mesh(1, 1)
    B, S, K, D = 2, 16, 2, 8
    cache = jax.random.normal(KEY, (B, S, K, D))
    new = jax.random.normal(jax.random.PRNGKey(1), (B, 1, K, D))
    slot = jnp.array([3, 15])
    c1 = A.write_cache_slot(cache, new, slot)
    c2 = A.write_cache_slot_seq_sharded(cache, new, slot, mesh=mesh,
                                        axis="model")
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2))
    for b, s_ in enumerate([3, 15]):
        np.testing.assert_allclose(np.asarray(c1[b, s_]),
                                   np.asarray(new[b, 0]))


# -- MoE ----------------------------------------------------------------------

def test_route_topk_normalized():
    logits = jax.random.normal(KEY, (32, 8))
    w, idx, probs = route_topk(logits, 2)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    assert idx.shape == (32, 2)
    assert bool((idx[:, 0] != idx[:, 1]).all())


def test_capacity_alignment():
    for t, e, k, f in [(64, 4, 1, 1.25), (1000, 16, 2, 1.0)]:
        c = _capacity(t, e, k, f)
        assert c % 8 == 0 and c >= 8


def test_moe_no_drop_at_high_capacity():
    d, e, f = 32, 4, 64
    ks = jax.random.split(KEY, 4)
    params = {
        "router": jax.random.normal(ks[0], (d, e)) * 0.1,
        "w_in": jax.random.normal(ks[1], (e, d, 2 * f)) * 0.1,
        "w_out": jax.random.normal(ks[2], (e, f, d)) * 0.1,
    }
    x = jax.random.normal(ks[3], (2, 16, d))
    y, metrics = moe_ffn(x, params, n_experts=e, k=2, capacity_factor=8.0)
    assert y.shape == x.shape
    assert float(metrics.dropped_fraction) == 0.0
    assert float(metrics.load_balance_loss) >= 0.9   # >= 1 at balance


def test_moe_dropping_under_tight_capacity():
    d, e, f = 16, 4, 32
    ks = jax.random.split(KEY, 4)
    # biased router: positive inputs × positive col-0 weights -> expert 0
    router = jnp.zeros((d, e)).at[:, 0].set(1.0)
    params = {
        "router": router,
        "w_in": jax.random.normal(ks[1], (e, d, 2 * f)) * 0.1,
        "w_out": jax.random.normal(ks[2], (e, f, d)) * 0.1,
    }
    x = jnp.abs(jax.random.normal(ks[3], (4, 32, d))) + 0.5
    _, metrics = moe_ffn(x, params, n_experts=e, k=1, capacity_factor=0.25)
    assert float(metrics.dropped_fraction) > 0.3
    assert float(metrics.load_balance_loss) > 2.0    # strongly unbalanced


# -- sharding specs ------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x22b",
                                  "mamba2-2.7b", "recurrentgemma-2b",
                                  "seamless-m4t-large-v2"])
def test_param_specs_match_param_tree(arch):
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh(1, 1)
    policy = make_policy(cfg, mesh)
    params = jax.eval_shape(lambda k: init_params(cfg, k), KEY)
    specs = param_specs(cfg, policy)
    assert jax.tree_util.tree_structure(
        jax.tree.map(lambda _: 0, params)) == jax.tree_util.tree_structure(
        jax.tree.map(lambda _: 0, specs,
                     is_leaf=lambda x: isinstance(
                         x, jax.sharding.PartitionSpec)))
    # every spec rank matches its param rank
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    spec_map = {tuple(str(k) for k in path): s for path, s in
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))}
    for path, leaf in flat_p:
        s = spec_map[tuple(str(k) for k in path)]
        assert len(s) <= leaf.ndim, (path, s, leaf.shape)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x22b",
                                  "mamba2-2.7b"])
def test_cache_specs_match_cache_tree(arch):
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh(1, 1)
    policy = make_policy(cfg, mesh)
    cache = init_cache(cfg, 2, 32, abstract=True)
    specs = cache_specs(cfg, policy)
    sl = jax.tree_util.tree_structure(
        jax.tree.map(lambda _: 0, cache))
    sr = jax.tree_util.tree_structure(
        jax.tree.map(lambda _: 0, specs,
                     is_leaf=lambda x: isinstance(
                         x, jax.sharding.PartitionSpec)))
    assert sl == sr


def test_vocab_padding_masked():
    cfg = get_config("mamba2-2.7b").reduced()
    assert cfg.vocab_padded % 256 == 0
    assert cfg.vocab_padded >= cfg.vocab_size
    params = init_params(cfg, KEY, jnp.float32)
    from repro.models import forward
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    logits, _ = forward(params, toks, cfg)
    pad = np.asarray(logits[..., cfg.vocab_size:])
    if pad.size:
        assert (pad <= -1e29).all()


# -- prefill lengths on the chip: padded to the kernels' block, never a
# -- quiet XLA detour (the TPU branch is steered through A._on_tpu)

@pytest.mark.parametrize("n,on_tpu,want", [(1, False, 1), (300, False, 300),
                                           (1, True, 128), (128, True, 128),
                                           (300, True, 384)])
def test_prefill_length_pads_only_on_tpu(monkeypatch, n, on_tpu, want):
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    assert A.prefill_length(n) == want


def test_unpadded_prefill_raises_on_tpu(monkeypatch):
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    q = jnp.zeros((1, 100, 4, 32))
    k = jnp.zeros((1, 100, 2, 32))
    with pytest.raises(ValueError, match="multiple of 128"):
        A.attention_prefill(q, k, k)
    # the reference path keeps the XLA ops even on a TPU
    assert A.attention_prefill(q, k, k, kernels=False).shape == q.shape
