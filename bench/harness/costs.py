"""Operations and bytes the algorithm needs, from shapes alone.

Each function counts what one call has to do for its real tokens —
padding, dead pages and idle slots are not counted — so a kernel's
roofline share charges its waste against it. Bytes are HBM bytes read
and written once; FLOPs count a multiply-add as two.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def paged_decode(contexts: Iterable[int], heads: int, kv_heads: int,
                 head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One paged decode attention call (one layer) over the slots that
    ran, slot ``i`` attending to ``contexts[i]`` cached tokens: scores and
    the weighted sum of values (4 FLOPs per head, token and dimension);
    every live key and value read once, the query read and the output
    written."""
    ctx = list(contexts)
    flops = 4.0 * heads * head_dim * sum(ctx)
    kv = 2.0 * kv_heads * head_dim * itemsize * sum(ctx)
    qo = 2.0 * heads * head_dim * itemsize * len(ctx)
    return flops, kv + qo


def flash_prefill(lengths: Iterable[int], heads: int, kv_heads: int,
                  head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One causal prefill attention call (one layer) over prompts of
    ``lengths``: row ``t`` attends to ``t + 1`` keys; q, k, v read once
    and the output written once."""
    ls = list(lengths)
    pairs = sum(n * (n + 1) / 2 for n in ls)
    flops = 4.0 * heads * head_dim * pairs
    io = (2.0 * heads + 2.0 * kv_heads) * head_dim * itemsize * sum(ls)
    return flops, io


def matmul_params(conf: dict) -> int:
    """Weights one token multiplies through per layer."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def decode_token_flops(conf: dict, context: int) -> float:
    """A decoded token at ``context`` cached tokens (itself included):
    every layer's matmuls, its attention, and the output head."""
    n = conf["num_hidden_layers"]
    attn = 4.0 * conf["num_attention_heads"] * conf["head_dim"] * context
    return (2.0 * n * matmul_params(conf) + n * attn
            + 2.0 * conf["hidden_size"] * conf["vocab_size"])


def prefill_flops(conf: dict, length: int) -> float:
    """A prompt of ``length`` tokens: every layer's matmuls for each
    token, causal attention, and the output head for the last token."""
    n = conf["num_hidden_layers"]
    attn = flash_prefill([length], conf["num_attention_heads"],
                         conf["num_key_value_heads"], conf["head_dim"])[0]
    return (2.0 * n * matmul_params(conf) * length + n * attn
            + 2.0 * conf["hidden_size"] * conf["vocab_size"])


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
