"""Composable transformer: init, partition specs, train/prefill/decode.

One code path covers all assigned families:

- the model is ``n_pattern_repeats`` repeats of ``cfg.pattern`` (a tuple of
  BlockSpec), lowered as a single ``lax.scan`` over stacked per-pattern
  parameters (keeps HLO small: one layer body compiled once);
- per-block caches (KV / ring-window KV / RG-LRU state / SSD state) are
  likewise stacked and scanned;
- enc-dec (seamless) adds an encoder stack + per-decoder-layer cross-KV;
- VLM/audio prepend stub frontend embeddings through a projector.

Param init and partition specs are derived from a single table
(``_param_defs``), so sharding always matches the parameter tree.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN, MLP, RGLRU, SSD, SWA, BlockSpec, ModelConfig
from repro.models import attention as attn_ops
from repro.models import layers as L
from repro.models.moe import moe_ffn
from repro.models.rglru import RGLRUState, rglru_block
from repro.models.sharding import ShardingPolicy
from repro.models.ssm import SSDState, ssd_block

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter definitions (shape + init + partition spec from one table)
# ---------------------------------------------------------------------------

class PDef(NamedTuple):
    shape: Tuple[int, ...]
    init: str                               # "dense" | "embed" | "zeros" | "ones" | "lru"
    spec: Callable[[ShardingPolicy], P]     # partition spec builder


def _mp(policy, cond=True):
    return policy.model_axis if (policy and cond) else None


def _attn_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, PDef]:
    d, h, k, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pre = "c" if cross else ""
    defs = {
        pre + "wq": PDef((d, h * dh), "dense",
                         lambda p: P(None, _mp(p, p.shard_heads)) if p.shard_heads
                         else P(_mp(p), None)),
        pre + "wk": PDef((d, k * dh), "dense",
                         lambda p: P(None, _mp(p, p.shard_kv_heads)) if p.shard_kv_heads
                         else P(_mp(p), None)),
        pre + "wv": PDef((d, k * dh), "dense",
                         lambda p: P(None, _mp(p, p.shard_kv_heads)) if p.shard_kv_heads
                         else P(_mp(p), None)),
        pre + "wo": PDef((h * dh, d), "dense",
                         lambda p: P(_mp(p, p.shard_heads), None) if p.shard_heads
                         else P(None, _mp(p))),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = PDef((h * dh,), "zeros",
                          lambda p: P(_mp(p, p.shard_heads)))
        defs["bk"] = PDef((k * dh,), "zeros",
                          lambda p: P(_mp(p, p.shard_kv_heads)))
        defs["bv"] = PDef((k * dh,), "zeros",
                          lambda p: P(_mp(p, p.shard_kv_heads)))
    if cfg.qk_norm and not cross:
        defs["q_norm"] = PDef((dh,), "zeros", lambda p: P(None))
        defs["k_norm"] = PDef((dh,), "zeros", lambda p: P(None))
    return defs


def _mlp_defs(cfg: ModelConfig) -> Dict[str, PDef]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wi": PDef((d, 2 * f), "dense", lambda p: P(None, _mp(p))),
        "wo_mlp": PDef((f, d), "dense", lambda p: P(_mp(p), None)),
    }


def _moe_defs(cfg: ModelConfig) -> Dict[str, PDef]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts

    def _f_axes(p):
        """d_ff axes for 2D sharding: data (+model when experts cannot
        span the model axis, so no compute is replicated)."""
        axes = tuple(p.data_axes)
        if not p.shard_experts and p.model_axis:
            axes = (p.model_axis,) + axes
        return axes or None

    def w_in_spec(p):
        if getattr(p, "moe_2d_weights", False):
            return P(_mp(p, p.shard_experts), None, _f_axes(p))
        return (P(_mp(p, p.shard_experts), None, None)
                if p.shard_experts else P(None, None, _mp(p)))

    def w_out_spec(p):
        if getattr(p, "moe_2d_weights", False):
            return P(_mp(p, p.shard_experts), _f_axes(p), None)
        return (P(_mp(p, p.shard_experts), None, None)
                if p.shard_experts else P(None, _mp(p), None))

    defs = {
        "router": PDef((d, e), "dense", lambda p: P(None, None)),
        "w_in": PDef((e, d, 2 * f), "dense", w_in_spec),
        "w_out": PDef((e, f, d), "dense", w_out_spec),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        defs["shared_wi"] = PDef((d, 2 * fs), "dense", lambda p: P(None, _mp(p)))
        defs["shared_wo"] = PDef((fs, d), "dense", lambda p: P(_mp(p), None))
    return defs


def _rglru_defs(cfg: ModelConfig) -> Dict[str, PDef]:
    d, w = cfg.d_model, cfg.lru_width
    kw = cfg.rglru_conv_width
    return {
        "w_in": PDef((d, 2 * w), "dense", lambda p: P(None, _mp(p))),
        "conv": PDef((kw, w), "dense", lambda p: P(None, _mp(p))),
        "w_a": PDef((w, w), "dense", lambda p: P(None, _mp(p))),
        "w_x": PDef((w, w), "dense", lambda p: P(None, _mp(p))),
        "b_a": PDef((w,), "zeros", lambda p: P(_mp(p))),
        "b_x": PDef((w,), "zeros", lambda p: P(_mp(p))),
        "lambda": PDef((w,), "lru", lambda p: P(_mp(p))),
        "w_out": PDef((w, d), "dense", lambda p: P(_mp(p), None)),
    }


def _ssd_defs(cfg: ModelConfig) -> Dict[str, PDef]:
    d, di, n, h = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
    kw = cfg.rglru_conv_width
    hs = lambda p: P(_mp(p, h % max(p.model_size, 1) == 0))
    return {
        "in_proj": PDef((d, 2 * di + 2 * n + h), "dense",
                        lambda p: P(None, _mp(p))),
        "conv": PDef((kw, di + 2 * n), "dense", lambda p: P(None, _mp(p))),
        "A_log": PDef((h,), "lru", hs),
        "D": PDef((h,), "ones", hs),
        "dt_bias": PDef((h,), "zeros", hs),
        "norm": PDef((di,), "zeros", lambda p: P(_mp(p))),
        "out_proj": PDef((di, d), "dense", lambda p: P(_mp(p), None)),
    }


def _block_defs(cfg: ModelConfig, blk: BlockSpec, *, decoder: bool) -> Dict[str, PDef]:
    d = cfg.d_model
    defs: Dict[str, PDef] = {"ln1": PDef((d,), "zeros", lambda p: P(None))}
    if blk.mixer in (ATTN, SWA):
        defs.update(_attn_defs(cfg))
    elif blk.mixer == RGLRU:
        defs.update(_rglru_defs(cfg))
    elif blk.mixer == SSD:
        defs.update(_ssd_defs(cfg))
    if decoder and cfg.cross_attention:
        defs["ln_cross"] = PDef((d,), "zeros", lambda p: P(None))
        defs.update(_attn_defs(cfg, cross=True))
    if blk.ff != "none":
        defs["ln2"] = PDef((d,), "zeros", lambda p: P(None))
        if blk.ff == MLP:
            defs.update(_mlp_defs(cfg))
        else:
            defs.update(_moe_defs(cfg))
    return defs


def _top_defs(cfg: ModelConfig) -> Dict[str, PDef]:
    d, v = cfg.d_model, cfg.vocab_padded
    defs = {
        "embed": PDef((v, d), "embed",
                      lambda p: P(_mp(p, p.shard_vocab), None)
                      if p.shard_vocab else P(None, _mp(p))),
        "final_norm": PDef((d,), "zeros", lambda p: P(None)),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = PDef((d, v), "dense",
                               lambda p: P(None, _mp(p, p.shard_vocab))
                               if p.shard_vocab else P(_mp(p), None))
    if cfg.frontend_embed_len:
        defs["frontend_proj"] = PDef((cfg.frontend_embed_dim, d), "dense",
                                     lambda p: P(None, None))
    return defs


# ---------------------------------------------------------------------------
# Init / specs
# ---------------------------------------------------------------------------

def _init_one(key, pdef: PDef, dtype):
    if pdef.init == "dense":
        return L.dense_init(key, pdef.shape, dtype)
    if pdef.init == "embed":
        return L.embed_init(key, pdef.shape, dtype)
    if pdef.init == "zeros":
        return jnp.zeros(pdef.shape, dtype)
    if pdef.init == "ones":
        return jnp.ones(pdef.shape, dtype)
    if pdef.init == "lru":   # Griffin Lambda / mamba A_log init
        u = jax.random.uniform(key, pdef.shape, jnp.float32, 0.1, 0.9)
        return jnp.log(u / (1 - u)).astype(jnp.float32).astype(dtype)
    raise ValueError(pdef.init)


def _init_block_stack(key, defs: Dict[str, PDef], repeats: int, dtype):
    out = {}
    for i, (name, pdef) in enumerate(sorted(defs.items())):
        k = jax.random.fold_in(key, i)
        ks = jax.random.split(k, repeats)
        out[name] = jnp.stack([_init_one(ks[r], pdef, dtype)
                               for r in range(repeats)])
    return out


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    r = cfg.n_pattern_repeats
    params: Params = {}
    for i, (name, pdef) in enumerate(sorted(_top_defs(cfg).items())):
        params[name] = _init_one(jax.random.fold_in(key, 1000 + i), pdef, dtype)
    params["blocks"] = tuple(
        _init_block_stack(jax.random.fold_in(key, j),
                          _block_defs(cfg, blk, decoder=True), r, dtype)
        for j, blk in enumerate(cfg.pattern))
    if cfg.pattern_tail:
        params["tail_blocks"] = tuple(
            {name: _init_one(jax.random.fold_in(key, 5000 + 100 * j + i),
                             pdef, dtype)
             for i, (name, pdef) in enumerate(sorted(
                 _block_defs(cfg, blk, decoder=True).items()))}
            for j, blk in enumerate(cfg.pattern_tail))
    if cfg.n_encoder_layers:
        enc_defs = _block_defs(cfg, BlockSpec(mixer=ATTN, ff=MLP), decoder=False)
        params["encoder"] = _init_block_stack(
            jax.random.fold_in(key, 777), enc_defs, cfg.n_encoder_layers, dtype)
        params["encoder_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return params


def _maybe_fsdp(spec: P, shape, policy: ShardingPolicy) -> P:
    if not policy.fsdp or not policy.data_axes:
        return spec
    # already data-sharded (e.g. 2D MoE weights) -> nothing to add
    for part in spec:
        axes = part if isinstance(part, tuple) else (part,)
        if any(a in policy.data_axes for a in axes if a):
            return spec
    dsz = policy.data_size
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (pt, dim) in enumerate(zip(parts, shape)):
        if pt is None and dim % dsz == 0 and dim >= dsz:
            parts[i] = policy.data_axes if len(policy.data_axes) > 1 \
                else policy.data_axes[0]
            return P(*parts)
    return spec


def param_specs(cfg: ModelConfig, policy: ShardingPolicy) -> Params:
    """Partition-spec tree matching ``init_params`` output."""
    specs: Params = {}
    for name, pdef in sorted(_top_defs(cfg).items()):
        specs[name] = _maybe_fsdp(pdef.spec(policy), pdef.shape, policy)

    def stack_spec(pdef: PDef) -> P:
        base = _maybe_fsdp(pdef.spec(policy), pdef.shape, policy)
        return P(*((None,) + tuple(base)))

    specs["blocks"] = tuple(
        {name: stack_spec(pdef)
         for name, pdef in sorted(_block_defs(cfg, blk, decoder=True).items())}
        for blk in cfg.pattern)
    if cfg.pattern_tail:
        specs["tail_blocks"] = tuple(
            {name: _maybe_fsdp(pdef.spec(policy), pdef.shape, policy)
             for name, pdef in sorted(
                 _block_defs(cfg, blk, decoder=True).items())}
            for blk in cfg.pattern_tail)
    if cfg.n_encoder_layers:
        enc_defs = _block_defs(cfg, BlockSpec(mixer=ATTN, ff=MLP), decoder=False)
        specs["encoder"] = {name: stack_spec(pdef)
                            for name, pdef in sorted(enc_defs.items())}
        specs["encoder_norm"] = P(None)
    return specs


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def _cache_len(cfg: ModelConfig, blk: BlockSpec, max_len: int,
               long_context: bool) -> int:
    if blk.mixer == ATTN and long_context:
        return min(cfg.long_context_window, max_len)
    if blk.mixer == SWA:
        return min(cfg.sliding_window, max_len)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, long_context: bool = False,
               abstract: bool = False):
    """Stacked decode cache. ``long_context`` switches full-attention blocks
    to their ring-window variant (the long_500k carve-out, DESIGN.md §4)."""
    r = cfg.n_pattern_repeats
    k, dh = cfg.n_kv_heads, cfg.head_dim

    def mk(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def entry(blk, lead):
        if blk.mixer in (ATTN, SWA):
            s = _cache_len(cfg, blk, max_len, long_context)
            return {"k": mk(lead + (batch, s, k, dh), dtype),
                    "v": mk(lead + (batch, s, k, dh), dtype)}
        if blk.mixer == RGLRU:
            w, kw = cfg.lru_width, cfg.rglru_conv_width
            return {"conv": mk(lead + (batch, kw - 1, w), dtype),
                    "hidden": mk(lead + (batch, w), jnp.float32)}
        if blk.mixer == SSD:
            di, n = cfg.ssm_d_inner, cfg.ssm_state
            h, p_ = cfg.ssm_n_heads, cfg.ssm_head_dim
            kw = cfg.rglru_conv_width
            return {"conv": mk(lead + (batch, kw - 1, di + 2 * n), dtype),
                    "ssm": mk(lead + (batch, h, p_, n), jnp.float32)}
        raise ValueError(blk.mixer)

    cache = {"blocks": tuple(entry(blk, (r,)) for blk in cfg.pattern)}
    if cfg.pattern_tail:
        cache["tail"] = tuple(entry(blk, ()) for blk in cfg.pattern_tail)
    if cfg.cross_attention:
        se = cfg.encoder_seq_len
        cache["cross"] = {"k": mk((r, batch, se, k, dh), dtype),
                          "v": mk((r, batch, se, k, dh), dtype)}
    return cache


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """Block-paged caches cover homogeneous full-attention stacks: every
    position is a GQA KV entry addressed by absolute position. Ring caches
    (SWA / long-context carve-out), recurrent states, and cross-attention
    keep the dense per-slot layout."""
    return (all(blk.mixer == ATTN for blk in cfg.pattern)
            and not cfg.pattern_tail and not cfg.cross_attention)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16, *, abstract: bool = False):
    """Block-paged decode cache: per pattern position a shared physical
    page pool ``(R, n_pages + 1, page_size, K, D)`` — one page pool per
    layer, all indexed by the same logical block ids (the engine's
    ``PagedKVPool`` allocates token ranges once; every layer stores its KV
    for that range in its own pool at the same page index). The extra last
    page (index ``n_pages``) is the trash page: unused block-table entries
    point at it, so masked gathers and inactive-slot writes stay in
    bounds."""
    assert supports_paged_cache(cfg), cfg.pattern
    r = cfg.n_pattern_repeats
    k, dh = cfg.n_kv_heads, cfg.head_dim
    shape = (r, n_pages + 1, page_size, k, dh)

    def mk():
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    return {"blocks": tuple({"k": mk(), "v": mk()} for _ in cfg.pattern)}


def cache_specs(cfg: ModelConfig, policy: ShardingPolicy) -> Dict[str, Any]:
    b = policy.data_axes if policy.shard_batch else None
    m = policy.model_axis
    blocks = []
    for blk in cfg.pattern:
        if blk.mixer in (ATTN, SWA):
            if policy.shard_kv_heads:
                s = P(None, b, None, m, None)
            elif policy.seq_parallel_decode:
                s = P(None, b, m, None, None)
            else:
                s = P(None, b, None, None, None)
            blocks.append({"k": s, "v": s})
        elif blk.mixer == RGLRU:
            blocks.append({"conv": P(None, b, None, m),
                           "hidden": P(None, b, m)})
        elif blk.mixer == SSD:
            hm = m if (cfg.ssm_n_heads % max(policy.model_size, 1) == 0) else None
            blocks.append({"conv": P(None, b, None, m),
                           "ssm": P(None, b, hm, None, None)})
    specs = {"blocks": tuple(blocks)}
    if cfg.pattern_tail:
        def strip(spec_dict):
            return {k_: P(*tuple(v)[1:]) for k_, v in spec_dict.items()}
        tail = []
        bi = 0
        for blk in cfg.pattern_tail:
            # rebuild the per-kind spec without the leading stack dim
            if blk.mixer in (ATTN, SWA):
                if policy.shard_kv_heads:
                    sp = P(b, None, m, None)
                elif policy.seq_parallel_decode:
                    sp = P(b, m, None, None)
                else:
                    sp = P(b, None, None, None)
                tail.append({"k": sp, "v": sp})
            elif blk.mixer == RGLRU:
                tail.append({"conv": P(b, None, m), "hidden": P(b, m)})
            elif blk.mixer == SSD:
                hm = m if (cfg.ssm_n_heads % max(policy.model_size, 1) == 0) else None
                tail.append({"conv": P(b, None, m),
                             "ssm": P(b, hm, None, None)})
        specs["tail"] = tuple(tail)
    if cfg.cross_attention:
        cs = P(None, b, None, m if policy.shard_kv_heads else None, None)
        specs["cross"] = {"k": cs, "v": cs}
    return specs


# ---------------------------------------------------------------------------
# Forward building blocks
# ---------------------------------------------------------------------------

def _cst(x, policy: Optional[ShardingPolicy], *spec):
    """Apply a sharding constraint if running under a >1-device policy."""
    if policy is None or policy.mesh is None or policy.mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(policy.mesh, P(*spec)))


def _project_qkv(x, p, cfg, positions, policy):
    b, s, _ = x.shape
    h, k, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    kk = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, dh)
    kk = kk.reshape(b, s, k, dh)
    v = v.reshape(b, s, k, dh)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.rmsnorm_eps)
        kk = L.rms_norm(kk, p["k_norm"], cfg.rmsnorm_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    kk = L.apply_rope(kk, positions, cfg.rope_theta)
    if policy and policy.shard_heads:
        bax = policy.data_axes if policy.shard_batch else None
        q = _cst(q, policy, bax, None, policy.model_axis, None)
    return q, kk, v


def _ff(x, p, blk, cfg, policy):
    """Feed-forward sub-block; returns (y, aux_loss)."""
    if blk.ff == "none":
        return jnp.zeros_like(x), jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["ln2"], cfg.rmsnorm_eps)
    if blk.ff == MLP:
        y = L.gated_mlp(h, p["wi"], p["wo_mlp"])
        return y, jnp.zeros((), jnp.float32)
    if (policy is not None and policy.mesh.size > 1
            and getattr(policy, "moe_2d_weights", False)):
        # 2D-sharded expert weights: GSPMD einsum path; the F-contraction
        # psums small (E_loc, C, D) activations, weights never move.
        m = policy.model_axis if policy.shard_experts else None
        y, metrics = moe_ffn(h, p, n_experts=cfg.n_experts,
                             k=cfg.n_experts_per_token,
                             capacity_factor=cfg.moe_capacity_factor,
                             constrain=lambda t: _cst(t, policy, m, None, None))
    elif (policy is not None and policy.mesh.size > 1
            and policy.moe_token_shard_map):
        from repro.models.moe import moe_ffn_sharded
        p_moe = {k_: v for k_, v in p.items()
                 if k_ in ("router", "w_in", "w_out",
                           "shared_wi", "shared_wo")}
        y, metrics = moe_ffn_sharded(h, p_moe, n_experts=cfg.n_experts,
                                     k=cfg.n_experts_per_token,
                                     capacity_factor=cfg.moe_capacity_factor,
                                     policy=policy)
    else:
        y, metrics = moe_ffn(h, p, n_experts=cfg.n_experts,
                             k=cfg.n_experts_per_token,
                             capacity_factor=cfg.moe_capacity_factor)
    return y, metrics.load_balance_loss


def _cross_attend(x, p, cfg, cross_k, cross_v, policy):
    h = L.rms_norm(x, p["ln_cross"], cfg.rmsnorm_eps)
    b, s, _ = h.shape
    q = (h @ p["cwq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    o = attn_ops.flash_ref_attention(q, cross_k, cross_v, causal=False)
    return o.reshape(b, s, -1) @ p["cwo"]


def _apply_block_full(x, p, blk, cfg, policy, positions, cross_kv, *,
                      window_override: Optional[int] = None,
                      kernels: bool = True):
    """Training/prefill block application over a full sequence.

    Returns (x, cache_entry, aux_loss). cache_entry holds the state a decode
    step would need (k/v or recurrent states). ``kernels=False`` keeps
    attention on the XLA ops (see ``attention_prefill``).
    """
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["ln1"], cfg.rmsnorm_eps)
    if blk.mixer in (ATTN, SWA):
        q, k, v = _project_qkv(h, p, cfg, positions, policy)
        window = cfg.sliding_window if blk.mixer == SWA else 0
        if window_override is not None and blk.mixer == ATTN:
            window = window_override
        o = attn_ops.attention_prefill(q, k, v, causal=True, window=window,
                                       kernels=kernels)
        y = o.reshape(*o.shape[:2], -1) @ p["wo"]
        entry = {"k": k, "v": v}
    elif blk.mixer == RGLRU:
        y, st = rglru_block(h, p, cfg)
        entry = {"conv": st.conv, "hidden": st.hidden}
    elif blk.mixer == SSD:
        y, st = ssd_block(h, p, cfg, policy=policy)
        entry = {"conv": st.conv, "ssm": st.ssm}
    else:
        raise ValueError(blk.mixer)
    x = x + y
    if cross_kv is not None:
        x = x + _cross_attend(x, p, cfg, *cross_kv, policy)
    y, aux = _ff(x, p, blk, cfg, policy)
    x = x + y
    if (policy is not None and policy.model_axis and
            __import__("os").environ.get("REPRO_SEQ_SHARD_RESIDUAL") == "1"
            and x.shape[1] % policy.model_size == 0):
        # Megatron-style sequence parallelism: keep the residual stream
        # sequence-sharded between blocks; GSPMD turns the post-matmul
        # all-reduces into reduce-scatter + pre-matmul all-gather and all
        # elementwise/norm traffic shards over the model axis (§Perf-1).
        bax = policy.data_axes if policy.shard_batch else None
        x = _cst(x, policy, bax, policy.model_axis, None)
    return x, entry, aux


# -- cache write helpers ----------------------------------------------------

def _window_gather(full_k, full_v, lengths, wsize):
    """Collapse prefill K/V (B,S,K,D) into ring-window caches (B,W,K,D).

    Slot s holds position p*(s) = len-1 - ((len-1-s) mod W) (the latest
    position congruent to s); invalid slots (p* < 0) are zeroed.
    """
    b, s_full = full_k.shape[:2]
    slots = jnp.arange(wsize)[None, :]                    # (1, W)
    last = lengths[:, None] - 1                           # (B, 1)
    pstar = last - jnp.mod(last - slots, wsize)           # (B, W)
    valid = pstar >= 0
    idx = jnp.clip(pstar, 0, s_full - 1)
    gk = jnp.take_along_axis(full_k, idx[:, :, None, None], axis=1)
    gv = jnp.take_along_axis(full_v, idx[:, :, None, None], axis=1)
    gk = jnp.where(valid[:, :, None, None], gk, 0)
    gv = jnp.where(valid[:, :, None, None], gv, 0)
    return gk, gv


def _prefill_cache_entry(entry, blk, cfg, lengths, cache_tpl, long_context):
    """Convert a full-sequence cache entry into the decode cache layout of
    ``cache_tpl`` (pad full KV to max_len or gather into ring window)."""
    if blk.mixer in (ATTN, SWA):
        tgt = cache_tpl["k"].shape[1]                     # (B, S_cache, K, D)
        k, v = entry["k"], entry["v"]
        s = k.shape[1]
        if blk.mixer == SWA or (long_context and tgt < s):
            k, v = _window_gather(k, v, lengths, tgt)
        elif s < tgt:
            padw = ((0, 0), (0, tgt - s), (0, 0), (0, 0))
            k, v = jnp.pad(k, padw), jnp.pad(v, padw)
        else:
            k, v = k[:, :tgt], v[:, :tgt]
        return {"k": k.astype(cache_tpl["k"].dtype),
                "v": v.astype(cache_tpl["v"].dtype)}
    return {key: entry[key].astype(cache_tpl[key].dtype)
            for key in cache_tpl}


def _kv_positions(pos, s_cache, window_like: bool):
    """(B, S_cache) absolute positions per slot given current pos (B,)."""
    slots = jnp.arange(s_cache)[None, :]
    if not window_like:
        return jnp.broadcast_to(slots, (pos.shape[0], s_cache))
    p = pos[:, None] - jnp.mod(pos[:, None] - slots, s_cache)
    return jnp.where(p >= 0, p, -1)


def _apply_block_decode(x, p, blk, cfg, policy, cache_entry, pos, cross_kv, *,
                        long_context: bool = False, block_tables=None):
    """Single-token block application. x: (B,1,D). Returns (x, new_entry).

    With ``block_tables`` (B, n_b) the cache entry is a block-paged pool
    (P+1, ps, K, D): the new token's K/V is scattered into its slot's
    current page and attention gathers only the pages the table names.
    """
    h = L.rms_norm(x, p["ln1"], cfg.rmsnorm_eps)
    if block_tables is not None and blk.mixer == ATTN:
        q, k_new, v_new = _project_qkv(h, p, cfg, pos[:, None], policy)
        kp, vp = attn_ops.write_paged_kv(
            cache_entry["k"], cache_entry["v"], k_new, v_new,
            block_tables, pos)
        o = attn_ops.attention_decode_paged(q, kp, vp, block_tables, pos)
        y = o.reshape(*o.shape[:2], -1) @ p["wo"]
        x = x + y
        y, _ = _ff(x, p, blk, cfg, policy)
        return x + y, {"k": kp, "v": vp}
    if blk.mixer in (ATTN, SWA):
        q, k_new, v_new = _project_qkv(h, p, cfg, pos[:, None], policy)
        kc, vc = cache_entry["k"], cache_entry["v"]
        s_cache = kc.shape[1]
        # A cache is a ring iff positions can exceed its length: SWA windows
        # always; full-attention only in the long_500k window carve-out.
        ring = blk.mixer == SWA or (blk.mixer == ATTN and long_context)
        slot = jnp.mod(pos, s_cache) if ring else jnp.minimum(pos, s_cache - 1)
        kvpos = _kv_positions(pos, s_cache, ring)
        if policy is not None and policy.seq_parallel_decode and \
                policy.mesh.size > 1:
            bax = policy.data_axes if policy.shard_batch else None
            kc = attn_ops.write_cache_slot_seq_sharded(
                kc, k_new.astype(kc.dtype), slot,
                mesh=policy.mesh, axis=policy.model_axis, batch_axes=bax)
            vc = attn_ops.write_cache_slot_seq_sharded(
                vc, v_new.astype(vc.dtype), slot,
                mesh=policy.mesh, axis=policy.model_axis, batch_axes=bax)
            o = attn_ops.seq_parallel_decode_attention(
                q, kc, vc, kvpos, pos,
                mesh=policy.mesh, axis=policy.model_axis, batch_axes=bax)
        else:
            kc = attn_ops.write_cache_slot(kc, k_new.astype(kc.dtype), slot)
            vc = attn_ops.write_cache_slot(vc, v_new.astype(vc.dtype), slot)
            o = attn_ops.attention_decode(q, kc, vc, kvpos, pos)
        y = o.reshape(*o.shape[:2], -1) @ p["wo"]
        entry = {"k": kc, "v": vc}
    elif blk.mixer == RGLRU:
        st = RGLRUState(cache_entry["conv"], cache_entry["hidden"])
        y, st = rglru_block(h, p, cfg, state=st, decode=True)
        entry = {"conv": st.conv, "hidden": st.hidden}
    elif blk.mixer == SSD:
        st = SSDState(cache_entry["conv"], cache_entry["ssm"])
        y, st = ssd_block(h, p, cfg, state=st, decode=True, policy=policy)
        entry = {"conv": st.conv, "ssm": st.ssm}
    else:
        raise ValueError(blk.mixer)
    x = x + y
    if cross_kv is not None:
        x = x + _cross_attend(x, p, cfg, *cross_kv, policy)
    y, _ = _ff(x, p, blk, cfg, policy)
    return x + y, entry


def scatter_prefill_pages(pages, kv, page_map, rep=None):
    """Scatter a prefill batch's full-sequence K or V (B, Sp, K, D) into a
    block-paged pool: prompt block ``(b, c)`` lands in physical page
    ``page_map[b, c]`` (trash page past each request's length, so padded
    rows are write-offs). ``pages`` is one layer's pool (P+1, ps, K, D),
    or the repeat-stacked pool (R, P+1, ps, K, D) with ``rep`` naming the
    slice to scatter into (no full-slice copy — the page indices extend
    with the leading repeat index)."""
    ps = pages.shape[-3]            # page size, stacked or not
    pad = page_map.shape[1] * ps - kv.shape[1]
    if pad:
        kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kvb = kv.reshape(-1, ps, kv.shape[2], kv.shape[3]).astype(pages.dtype)
    if rep is None:
        return pages.at[page_map.reshape(-1)].set(kvb)
    return pages.at[rep, page_map.reshape(-1)].set(kvb)


def scatter_suffix_pages(pages, kv, page_map, offsets, rep=None):
    """Scatter a *suffix* prefill's K or V (B, Ss, K, D) into a block-paged
    pool at a per-row page offset (shared-prefix path, docs/KV_SHARING.md).

    Row ``b``'s suffix starts mid-page: its first token lands in page
    ``page_map[b, 0]`` at slot ``offsets[b]`` (the tail of a copy-on-write
    page, whose copied prefix below the offset must survive). Read-modify-
    write: gather the mapped pages, splice the suffix in at the offset
    (vmapped dynamic_update_slice over the flattened token dim), scatter
    the whole pages back. Rows pad with the trash page; a row's real pages
    are disjoint from every other row's, so duplicate trash writes are the
    only index collisions and their content is garbage by contract."""
    ps = pages.shape[-3]
    b, n_b = page_map.shape
    src = pages[page_map] if rep is None else pages[rep][page_map]
    flat = src.reshape(b, n_b * ps, *src.shape[3:])

    def splice(f, knew, o):
        return jax.lax.dynamic_update_slice(f, knew, (o, 0, 0))

    flat = jax.vmap(splice)(flat, kv.astype(pages.dtype), offsets)
    src = flat.reshape(b, n_b, ps, *src.shape[3:])
    kvb = src.reshape(-1, ps, *src.shape[3:])
    if rep is None:
        return pages.at[page_map.reshape(-1)].set(kvb)
    return pages.at[rep, page_map.reshape(-1)].set(kvb)


def _apply_block_prefix(x, p, blk, cfg, policy, positions, k_pre, v_pre,
                        prefix_lens):
    """Prefill block application for a suffix continuing reused prefix KV
    (docs/KV_SHARING.md). ``x`` (B, Ss, D) holds only the unshared suffix
    at absolute ``positions`` (B, Ss); ``k_pre/v_pre`` (B, Lp, K, D) is
    the prefix KV gathered from shared pages, valid below ``prefix_lens``.
    Returns (x, {"k","v"}) with the *suffix's own* KV for page scatter."""
    assert blk.mixer == ATTN, blk.mixer
    h = L.rms_norm(x, p["ln1"], cfg.rmsnorm_eps)
    q, k, v = _project_qkv(h, p, cfg, positions, policy)
    o = attn_ops.prefix_suffix_attention(q, k, v, k_pre, v_pre,
                                         prefix_lens, positions)
    y = o.reshape(*o.shape[:2], -1) @ p["wo"]
    x = x + y
    y, _ = _ff(x, p, blk, cfg, policy)
    return x + y, {"k": k, "v": v}


def _apply_block_fused(x_p, x_d, p, blk, cfg, policy, positions_p, pos_d,
                       cache_entry, block_tables, page_map, decode_share):
    """Spatially-fused block application: one prefill layer of the current
    layer group AND one decode layer of the same (repeat, pattern) position
    share a single attention launch (paper §3.5 co-execution).

    x_p: (Bp, Sp, D) prefill activations; x_d: (Bd, 1, D) decode
    activations; cache_entry: this layer's paged pool {(P+1, ps, K, D)}.
    The decode token's K/V is written to its slot's page and the prefill
    group's K/V is scattered into its requests' pages (disjoint page sets:
    mid-prefill slots sit on the trash page in ``block_tables``). Returns
    (x_p, x_d, new_cache_entry).
    """
    assert blk.mixer == ATTN, blk.mixer
    hp = L.rms_norm(x_p, p["ln1"], cfg.rmsnorm_eps)
    qp, kp_new, vp_new = _project_qkv(hp, p, cfg, positions_p, policy)
    hd = L.rms_norm(x_d, p["ln1"], cfg.rmsnorm_eps)
    qd, kd_new, vd_new = _project_qkv(hd, p, cfg, pos_d[:, None], policy)
    kpg, vpg = attn_ops.write_paged_kv(
        cache_entry["k"], cache_entry["v"], kd_new, vd_new,
        block_tables, pos_d)
    kpg = scatter_prefill_pages(kpg, kp_new, page_map)
    vpg = scatter_prefill_pages(vpg, vp_new, page_map)
    op, od = attn_ops.attention_fused_paged(
        qp, kp_new, vp_new, qd, kpg, vpg, block_tables, pos_d,
        decode_share=decode_share, causal=True, window=0)
    x_p = x_p + op.reshape(*op.shape[:2], -1) @ p["wo"]
    yp, _ = _ff(x_p, p, blk, cfg, policy)
    x_p = x_p + yp
    x_d = x_d + od.reshape(*od.shape[:2], -1) @ p["wo"]
    yd, _ = _ff(x_d, p, blk, cfg, policy)
    x_d = x_d + yd
    return x_p, x_d, {"k": kpg, "v": vpg}


def fused_group_decode(params, cache, x_p, positions, page_map, tokens, pos,
                       cfg: ModelConfig, policy=None, *, rep: int,
                       decode_share: float, block_tables):
    """One fused engine cycle: pattern-repeat group ``rep`` of an in-flight
    prefill AND a full continuous-batching decode iteration, in a single
    computation (the serial engine dispatches these back-to-back).

    The decode pass walks every layer; at repeat ``rep`` each layer fuses
    with the matching prefill layer via :func:`_apply_block_fused` (the
    bullet co-execution schedule on TPU), scattering the group's prompt KV
    into pooled pages as it goes. Requires the block-paged cache layout
    (``supports_paged_cache``). Returns (x_p, decode_logits (B, V),
    new_cache) — layer math is op-for-op the serial path's, so token
    streams are identical.
    """
    assert supports_paged_cache(cfg), cfg.pattern
    x_d = embed_tokens(params, tokens, cfg, policy)
    blocks = [dict(entry) for entry in cache["blocks"]]

    def _is_leaf(a):
        return hasattr(a, "shape")

    for r in range(cfg.n_pattern_repeats):
        for j, blk in enumerate(cfg.pattern):
            p_rj = jax.tree.map(lambda a, _r=r: a[_r], params["blocks"][j],
                                is_leaf=_is_leaf)
            entry_rj = {key: leaf[r] for key, leaf in blocks[j].items()}
            if r == rep:
                x_p, x_d, new_entry = _apply_block_fused(
                    x_p, x_d, p_rj, blk, cfg, policy, positions, pos,
                    entry_rj, block_tables, page_map, decode_share)
            else:
                x_d, new_entry = _apply_block_decode(
                    x_d, p_rj, blk, cfg, policy, entry_rj, pos, None,
                    block_tables=block_tables)
            blocks[j] = {key: blocks[j][key].at[r].set(new_entry[key])
                         for key in blocks[j]}
    x_d = L.rms_norm(x_d, params["final_norm"], cfg.rmsnorm_eps)
    logits = lm_logits(params, x_d, cfg, policy)[:, 0]
    return x_p, logits, {"blocks": tuple(blocks)}


# ---------------------------------------------------------------------------
# Embeddings / head
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg, policy,
                 frontend: Optional[jax.Array] = None):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype) if cfg.tie_embeddings else x
    if frontend is not None:
        fe = frontend.astype(x.dtype) @ params["frontend_proj"]
        x = jnp.concatenate([fe, x], axis=1)
    bax = (policy.data_axes if policy and policy.shard_batch else None)
    return _cst(x, policy, bax, None, None)


def lm_logits(params, x, cfg, policy):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        # mask the padded vocab tail out of the softmax
        idx = jnp.arange(cfg.vocab_padded)
        logits = jnp.where(idx < cfg.vocab_size, logits, -1e30)
    bax = (policy.data_axes if policy and policy.shard_batch else None)
    m = policy.model_axis if (policy and policy.shard_vocab) else None
    return _cst(logits, policy, bax, None, m)


# ---------------------------------------------------------------------------
# Encoder (enc-dec models)
# ---------------------------------------------------------------------------

def encode(params, frontend, cfg, policy):
    """Bidirectional encoder over stub frontend embeddings (B,Se,De)."""
    x = frontend.astype(params["encoder"]["wq"].dtype) @ params["frontend_proj"]
    positions = jnp.arange(x.shape[1])[None, :]

    def body(x, p):
        h = L.rms_norm(x, p["ln1"], cfg.rmsnorm_eps)
        q, k, v = _project_qkv(h, p, cfg, positions, policy)
        o = attn_ops.flash_ref_attention(q, k, v, causal=False)
        x = x + o.reshape(*o.shape[:2], -1) @ p["wo"]
        h = L.rms_norm(x, p["ln2"], cfg.rmsnorm_eps)
        x = x + L.gated_mlp(h, p["wi"], p["wo_mlp"])
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["encoder_norm"], cfg.rmsnorm_eps)


def _cross_kv_from_encoder(p_blk, enc_out, cfg):
    b, se, _ = enc_out.shape
    k = (enc_out @ p_blk["cwk"]).reshape(b, se, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p_blk["cwv"]).reshape(b, se, cfg.n_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# Top-level: train forward / prefill / decode
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ModelConfig, policy=None, *,
            frontend: Optional[jax.Array] = None,
            remat: bool = False, kernels: bool = True):
    """Teacher-forcing forward. Returns (logits (B,S,V), aux_loss).

    ``kernels=False`` runs attention on the XLA ops even on a TPU: the
    plain reference the served path's Pallas kernels are checked against.
    """
    enc_out = None
    if cfg.n_encoder_layers:
        assert frontend is not None
        enc_out = encode(params, frontend, cfg, policy)
        x = embed_tokens(params, tokens, cfg, policy)
    else:
        x = embed_tokens(params, tokens, cfg, policy, frontend=frontend)
    positions = jnp.arange(x.shape[1])[None, :]

    def one_block(x, p_j, j):
        blk = cfg.pattern[j]
        cross = None
        if cfg.cross_attention:
            cross = _cross_kv_from_encoder(p_j, enc_out, cfg)
        x, _, a = _apply_block_full(x, p_j, blk, cfg, policy,
                                    positions, cross, kernels=kernels)
        return x, a

    if remat:
        # per-block remat: one block's intermediates live during backward
        # (pattern periods reach 13 blocks — recurrentgemma — so wrapping
        # the whole scan body would hold all of them at once)
        one_block = jax.checkpoint(one_block, static_argnums=(2,))

    def body(carry, p_slices):
        x, aux = carry
        for j in range(len(cfg.pattern)):
            x, a = one_block(x, p_slices[j], j)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    for j, blk in enumerate(cfg.pattern_tail):
        cross = None
        if cfg.cross_attention:
            cross = _cross_kv_from_encoder(params["tail_blocks"][j],
                                           enc_out, cfg)
        x, _, a = _apply_block_full(x, params["tail_blocks"][j], blk, cfg,
                                    policy, positions, cross,
                                    kernels=kernels)
        aux = aux + a
    x = L.rms_norm(x, params["final_norm"], cfg.rmsnorm_eps)
    return lm_logits(params, x, cfg, policy), aux


def prefill(params, tokens, lengths, cache, cfg: ModelConfig, policy=None, *,
            frontend: Optional[jax.Array] = None,
            long_context: bool = False):
    """Process the prompt, fill ``cache``; returns (last_logits (B,V), cache).

    ``lengths`` (B,) are prompt lengths (tokens beyond are padding). For
    VLM/audio decoder-only models the frontend embeddings are prepended and
    lengths must count them.
    """
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = encode(params, frontend, cfg, policy)
        x = embed_tokens(params, tokens, cfg, policy)
    else:
        x = embed_tokens(params, tokens, cfg, policy, frontend=frontend)
    positions = jnp.arange(x.shape[1])[None, :]
    window_override = (min(cfg.long_context_window, x.shape[1])
                       if long_context else None)

    def body(x, slices):
        p_slices, c_slices = slices
        new_entries = []
        cross_entries = []
        for j, blk in enumerate(cfg.pattern):
            cross = None
            if cfg.cross_attention:
                ck, cv = _cross_kv_from_encoder(p_slices[j], enc_out, cfg)
                cross = (ck, cv)
                cross_entries.append({"k": ck, "v": cv})
            x, entry, _ = _apply_block_full(
                x, p_slices[j], blk, cfg, policy, positions, cross,
                window_override=window_override)
            entry = _prefill_cache_entry(entry, blk, cfg, lengths,
                                         c_slices[j], long_context)
            new_entries.append(entry)
        ys = tuple(new_entries)
        if cfg.cross_attention:
            # all pattern positions share the stacked cross cache layout
            ys = (ys, cross_entries[0])
        return x, ys

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    if cfg.cross_attention:
        new_blocks, cross = new_cache
        out_cache = {"blocks": new_blocks,
                     "cross": {k: v.astype(cache["cross"][k].dtype)
                               for k, v in cross.items()}}
    else:
        out_cache = {"blocks": new_cache}
    if cfg.pattern_tail:
        tail_entries = []
        for j, blk in enumerate(cfg.pattern_tail):
            p_j = params["tail_blocks"][j]
            cross = None
            if cfg.cross_attention:
                ck, cv = _cross_kv_from_encoder(p_j, enc_out, cfg)
                cross = (ck, cv)
            x, entry, _ = _apply_block_full(
                x, p_j, blk, cfg, policy, positions, cross,
                window_override=window_override)
            tail_entries.append(_prefill_cache_entry(
                entry, blk, cfg, lengths, cache["tail"][j], long_context))
        out_cache["tail"] = tuple(tail_entries)
    x = L.rms_norm(x, params["final_norm"], cfg.rmsnorm_eps)
    # gather last valid token per batch entry
    idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(params, last[:, None], cfg, policy)[:, 0]
    return logits, out_cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig, policy=None, *,
                long_context: bool = False, block_tables=None):
    """One decode iteration.

    tokens: (B, 1) int32; pos: (B,) absolute position of the new token.
    ``block_tables`` (B, n_b) switches attention blocks to the block-paged
    cache layout of :func:`init_paged_cache` (shared across layers — every
    layer's pool is indexed by the same table). Returns
    (logits (B, V), new_cache).
    """
    x = embed_tokens(params, tokens, cfg, policy)

    def body(x, slices):
        if cfg.cross_attention:
            p_slices, c_slices, cross_c = slices
        else:
            p_slices, c_slices = slices
            cross_c = None
        new_entries = []
        for j, blk in enumerate(cfg.pattern):
            cross = None
            if cross_c is not None:
                cross = (cross_c["k"], cross_c["v"])
            x, entry = _apply_block_decode(x, p_slices[j], blk, cfg, policy,
                                           c_slices[j], pos, cross,
                                           long_context=long_context,
                                           block_tables=block_tables)
            new_entries.append(entry)
        ys = tuple(new_entries)
        if cfg.cross_attention:
            ys = (ys, cross_c)
        return x, ys

    if cfg.cross_attention:
        xs = (params["blocks"], cache["blocks"], cache["cross"])
    else:
        xs = (params["blocks"], cache["blocks"])
    x, new_cache = jax.lax.scan(body, x, xs)
    if cfg.cross_attention:
        new_blocks, cross = new_cache
        out_cache = {"blocks": new_blocks, "cross": cross}
    else:
        out_cache = {"blocks": new_cache}
    if cfg.pattern_tail:
        tail_entries = []
        for j, blk in enumerate(cfg.pattern_tail):
            cross = None
            if cfg.cross_attention:
                cross = (cache["cross"]["k"][-1], cache["cross"]["v"][-1])
            x, entry = _apply_block_decode(
                x, params["tail_blocks"][j], blk, cfg, policy,
                cache["tail"][j], pos, cross, long_context=long_context)
            tail_entries.append(entry)
        out_cache["tail"] = tuple(tail_entries)
    x = L.rms_norm(x, params["final_norm"], cfg.rmsnorm_eps)
    logits = lm_logits(params, x, cfg, policy)[:, 0]
    return logits, out_cache


def _apply_block_chunk(x, p, blk, cfg, policy, ctx_start: int, cache_entry):
    """Chunked-prefill block: process a chunk of ``Sq`` prompt tokens with
    ``ctx_start`` tokens already in the cache (the paper's §2.3 workflow —
    attention re-reads the cached context). ctx_start is static per call
    (chunked engines process one request's chunk per iteration)."""
    sq = x.shape[1]
    positions = ctx_start + jnp.arange(sq)[None, :]
    h = L.rms_norm(x, p["ln1"], cfg.rmsnorm_eps)
    if blk.mixer in (ATTN, SWA):
        q, k_new, v_new = _project_qkv(h, p, cfg, positions, policy)
        kc = jax.lax.dynamic_update_slice(
            cache_entry["k"], k_new.astype(cache_entry["k"].dtype),
            (0, ctx_start, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache_entry["v"], v_new.astype(cache_entry["v"].dtype),
            (0, ctx_start, 0, 0))
        window = cfg.sliding_window if blk.mixer == SWA else 0
        o = attn_ops.flash_ref_attention(q, kc, vc, causal=True,
                                         window=window, q_offset=ctx_start)
        y = o.reshape(*o.shape[:2], -1) @ p["wo"]
        entry = {"k": kc, "v": vc}
    elif blk.mixer == RGLRU:
        st = RGLRUState(cache_entry["conv"], cache_entry["hidden"])
        y, st = rglru_block(h, p, cfg, state=st)
        entry = {"conv": st.conv, "hidden": st.hidden}
    elif blk.mixer == SSD:
        st = SSDState(cache_entry["conv"], cache_entry["ssm"])
        y, st = ssd_block(h, p, cfg, state=st, policy=policy)
        entry = {"conv": st.conv, "ssm": st.ssm}
    else:
        raise ValueError(blk.mixer)
    x = x + y
    y, _ = _ff(x, p, blk, cfg, policy)
    return x + y, entry


def prefill_chunk(params, tokens, ctx_start: int, cache,
                  cfg: ModelConfig, policy=None):
    """One chunked-prefill iteration (SARATHI/SGLang-style baseline at real
    execution fidelity): runs ``tokens`` (B, chunk) through all layers with
    ``ctx_start`` cached tokens of left context; the KV cache must be sized
    for the full prompt (no ring). Returns (last_logits (B,V), cache).
    Not supported for enc-dec configs (chunking the decoder prompt of a
    translation model is not a meaningful baseline)."""
    assert not cfg.cross_attention, "chunked prefill: decoder-only models"
    x = embed_tokens(params, tokens, cfg, policy)

    def body(x, slices):
        p_slices, c_slices = slices
        entries = []
        for j, blk in enumerate(cfg.pattern):
            x, e = _apply_block_chunk(x, p_slices[j], blk, cfg, policy,
                                      ctx_start, c_slices[j])
            entries.append(e)
        return x, tuple(entries)

    x, new_blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    out_cache = {"blocks": new_blocks}
    if cfg.pattern_tail:
        tail = []
        for j, blk in enumerate(cfg.pattern_tail):
            x, e = _apply_block_chunk(x, params["tail_blocks"][j], blk, cfg,
                                      policy, ctx_start, cache["tail"][j])
            tail.append(e)
        out_cache["tail"] = tuple(tail)
    x = L.rms_norm(x, params["final_norm"], cfg.rmsnorm_eps)
    logits = lm_logits(params, x[:, -1:], cfg, policy)[:, 0]
    return logits, out_cache
