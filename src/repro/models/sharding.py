"""Sharding policy: maps a ModelConfig onto a mesh.

Rules (documented in DESIGN.md §4):

- activations: batch over data axes ("pod","data"); hidden replicated unless
  tensor-parallel op output (then over "model").
- attention: heads over "model" iff divisible; otherwise attention weights
  replicated on "model" (Megatron divisibility fallback).
- GQA KV heads: shard over "model" iff divisible; else decode KV cache is
  sharded over the *sequence* dim on "model" and attention uses the
  sequence-parallel (flash-decoding style) shard_map path.
- MLP: d_ff over "model" (all assigned configs divide evenly).
- MoE: experts over "model" iff divisible, else per-expert d_ff over "model".
- vocab: over "model" iff divisible, else replicated.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ShardingPolicy:
    mesh: Mesh
    data_axes: Tuple[str, ...]          # e.g. ("pod", "data") or ("data",)
    model_axis: Optional[str]           # "model" or None
    shard_heads: bool
    shard_kv_heads: bool
    shard_experts: bool
    shard_vocab: bool
    seq_parallel_decode: bool           # KV-cache sequence sharded on model axis
    shard_batch: bool                   # batch divisible by prod(data axes)
    fsdp: bool = False                  # additionally shard params over "data"
    #: token-parallel shard_map MoE dispatch (serving); training uses the
    #: GSPMD einsum path — microbatched dispatch buffers are small, and the
    #: shard_map backward's bf16 grad all-reduce trips an XLA:CPU
    #: AllReducePromotion CHECK (compiler bug, documented in DESIGN.md §4)
    moe_token_shard_map: bool = True
    #: 2D expert-weight sharding (experts over model, d_ff over data):
    #: weights stay fully resident — no per-layer FSDP gathers; the
    #: contraction psums small (E_loc, C, D) activations instead. The
    #: serving-decode default for MoE archs (§Perf-3): gathering GB-scale
    #: expert weights for a one-token step dominates the collective term.
    moe_2d_weights: bool = False

    # -- helpers ------------------------------------------------------
    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis] if self.model_axis else 1

    @property
    def data_size(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    def batch_spec(self) -> P:
        return P(self.data_axes if self.shard_batch else None)

    def mp(self) -> Optional[str]:
        return self.model_axis

    def spec(self, *axes) -> P:
        return P(*axes)

    def shard(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def make_policy(cfg: ModelConfig, mesh: Mesh, *,
                global_batch: int = 0, fsdp: bool = False,
                moe_token_shard_map: bool = True,
                moe_2d_weights: bool = False) -> ShardingPolicy:
    axis_names = mesh.axis_names
    model_axis = "model" if "model" in axis_names else None
    data_axes = tuple(a for a in ("pod", "data") if a in axis_names)
    m = mesh.shape[model_axis] if model_axis else 1
    dsz = 1
    for a in data_axes:
        dsz *= mesh.shape[a]

    shard_heads = bool(cfg.n_heads) and cfg.n_heads % m == 0
    shard_kv = bool(cfg.n_kv_heads) and cfg.n_kv_heads % m == 0
    # sequence-parallel decode when KV heads cannot span the model axis
    seq_par = bool(cfg.n_kv_heads) and not shard_kv and m > 1
    shard_experts = cfg.n_experts > 0 and cfg.n_experts % m == 0
    shard_vocab = cfg.vocab_padded % m == 0
    shard_batch = global_batch == 0 or (global_batch % dsz == 0 and global_batch >= dsz)

    return ShardingPolicy(
        mesh=mesh,
        data_axes=data_axes,
        model_axis=model_axis,
        shard_heads=shard_heads,
        shard_kv_heads=shard_kv,
        shard_experts=shard_experts,
        shard_vocab=shard_vocab,
        seq_parallel_decode=seq_par,
        shard_batch=shard_batch,
        fsdp=fsdp,
        moe_token_shard_map=moe_token_shard_map,
        moe_2d_weights=moe_2d_weights,
    )


# ---------------------------------------------------------------------------
# Per-sub-mesh placements (chip-granular partitions, launch/submesh.py)
# ---------------------------------------------------------------------------

def submesh_param_sharding(mesh: Mesh) -> NamedSharding:
    """Parameter placement for one side of a chip-granular split: fully
    replicated over the sub-mesh's devices. Each carved side runs its
    phase with its own resident copy (the pre-configured execution state
    of §3.4.2 — no cross-side traffic except the explicit KV handoff);
    model-parallel sharding *within* a sub-mesh would come from
    ``make_policy`` on that mesh and is deliberately not the default: the
    equivalence contract (chip == single-mesh token streams) holds
    trivially under replication."""
    return NamedSharding(mesh, P())


def submesh_cache_sharding(mesh: Mesh) -> NamedSharding:
    """KV-page-pool placement on a sub-mesh: replicated, like the params.
    ``jax.device_put`` from the prefill sub-mesh's pool sharding onto the
    decode sub-mesh's is the cross-mesh page re-shard the handoff path
    (kvcache/paged.py ``transfer_pages``) charges to the interconnect.
    (Same placement as the params today; kept separate so sharding the
    pool within a sub-mesh stays a one-function change.)"""
    return NamedSharding(mesh, P())


def with_fsdp(spec: P, policy: ShardingPolicy) -> P:
    """Try to additionally shard the first unsharded dim over data axes."""
    if not policy.fsdp or not policy.data_axes:
        return spec
    parts = list(spec)
    for i, p in enumerate(parts):
        if p is None:
            parts[i] = policy.data_axes
            return P(*parts)
    return spec
