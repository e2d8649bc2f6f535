"""Routed mixture-of-experts (GShard-style capacity dispatch).

Baseline dispatch uses sort-free cumsum ranking + scatter into an
(E, C, D) expert buffer — O(tokens×E) memory, no (tokens×E×C) one-hots.
Experts are sharded over the "model" axis when divisible (expert parallel);
XLA inserts the token redistribution collectives from the sharding
constraints. The beyond-paper perf pass adds an explicit shard_map
all_to_all dispatch (``moe_ep.py``) — see EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import gated_mlp


class MoEMetrics(NamedTuple):
    load_balance_loss: jax.Array     # scalar aux loss (Switch-style)
    dropped_fraction: jax.Array      # fraction of tokens over capacity


def _capacity(n_tokens: int, n_experts: int, k: int, factor: float) -> int:
    cap = int(n_tokens * k * factor / n_experts)
    return max(8, -(-cap // 8) * 8)   # round up to 8 for TPU lane alignment


def route_topk(router_logits: jax.Array, k: int):
    """router_logits: (T, E) -> (weights (T,k), experts (T,k) int32)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx, probs


def moe_ffn_sharded(x: jax.Array, params: dict, *, n_experts: int, k: int,
                    capacity_factor: float, policy):
    """Token-parallel MoE: shard_map over the data axes so each shard
    dispatches only its local tokens into a local (E, C_local, D) buffer
    (the naive global dispatch replicates a (E, C_global, D) buffer on
    every device — hundreds of GB at prefill_32k scale). The model axis
    stays automatic, so expert/d_ff tensor parallelism inside continues to
    be handled by GSPMD. Returns (y, MoEMetrics)."""
    from jax.sharding import PartitionSpec as P
    mesh = policy.mesh
    bax = policy.data_axes if policy.shard_batch else None
    if bax is None or mesh is None or policy.data_size == 1:
        return moe_ffn(x, params, n_experts=n_experts, k=k,
                       capacity_factor=capacity_factor)

    def local(x_loc, params_loc):
        y, m = moe_ffn(x_loc, params_loc, n_experts=n_experts, k=k,
                       capacity_factor=capacity_factor)
        # average the aux metrics across data shards
        lb = jax.lax.pmean(m.load_balance_loss, bax)
        dr = jax.lax.pmean(m.dropped_fraction, bax)
        return y, MoEMetrics(lb, dr)

    pspecs = jax.tree.map(lambda _: P(), params)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bax, None, None), pspecs),
        out_specs=(P(bax, None, None), MoEMetrics(P(), P())),
        axis_names=set(bax if isinstance(bax, tuple) else (bax,)),
        check_vma=False)
    return fn(x, params)


def moe_ffn(x: jax.Array, params: dict, *, n_experts: int, k: int,
            capacity_factor: float, constrain=None):
    """x: (B, S, D). params: router (D,E), w_in (E,D,2F), w_out (E,F,D),
    optional shared_wi/shared_wo. Returns (y, MoEMetrics)."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    logits = xf @ params["router"]                       # (T, E)
    weights, experts, probs = route_topk(logits, k)

    cap = _capacity(t, n_experts, k, capacity_factor)

    # Switch-transformer load-balance loss
    me = probs.mean(0)                                   # (E,)
    onehot_top1 = jax.nn.one_hot(experts[:, 0], n_experts, dtype=jnp.float32)
    ce = onehot_top1.mean(0)
    lb_loss = n_experts * jnp.sum(me * ce)

    ybuf = jnp.zeros((t, d), jnp.float32)
    dropped = jnp.zeros((), jnp.float32)
    for kk in range(k):                                  # small static k (1 or 2)
        e_idx = experts[:, kk]                           # (T,)
        onehot = jax.nn.one_hot(e_idx, n_experts, dtype=jnp.int32)  # (T,E)
        rank = jnp.cumsum(onehot, axis=0) - 1            # position within expert
        pos = jnp.take_along_axis(rank, e_idx[:, None], axis=1)[:, 0]
        keep = pos < cap
        dropped = dropped + (1.0 - keep.mean()) / k
        dest = jnp.where(keep, e_idx * cap + pos, t * 0 + n_experts * cap)
        # scatter tokens -> (E*C+1, D); last row is the drop bin
        buf = jnp.zeros((n_experts * cap + 1, d), x.dtype)
        buf = buf.at[dest].set(xf, mode="drop")
        ebuf = buf[:-1].reshape(n_experts, cap, d)       # (E, C, D)
        if constrain is not None:
            ebuf = constrain(ebuf)
        h = jnp.einsum("ecd,edf->ecf", ebuf, params["w_in"])
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
        eout = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
        if constrain is not None:
            eout = constrain(eout)
        flat = jnp.concatenate(
            [eout.reshape(n_experts * cap, d),
             jnp.zeros((1, d), eout.dtype)], axis=0)
        gathered = flat[dest]                            # (T, D)
        ybuf = ybuf + gathered.astype(jnp.float32) * weights[:, kk:kk + 1]

    y = ybuf.astype(x.dtype)
    if "shared_wi" in params:
        y = y + gated_mlp(xf, params["shared_wi"], params["shared_wo"])
    return y.reshape(b, s, d), MoEMetrics(lb_loss, dropped)
