"""The plain float32 reference of the served models, and its fp8 control.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one
layer after another (``lax.scan`` over the stacked layers, each layer's
bfloat16 weights widened as it runs), over one whole sequence: no
kernels, no cache, no batching. It imports nothing of the program and
reads only the published-layout weights of ``harness.model``, which the
benchmark makes itself from the seed.

The model (Qwen2/Qwen3 ``*ForCausalLM``): token embedding; per layer
``x += Wo·attn(RoPE(norm_q(x Wq + bq)), RoPE(norm_k(x Wk + bk)), x Wv +
bv)`` on ``RMSNorm(x)``, causal, grouped query heads, softmax scale
1/sqrt(head_dim), RoPE with the rotate-half pairing; then ``x +=
W_down(silu(h W_gate) * h W_up)`` on ``RMSNorm(x)``; a final RMSNorm and
the output head (the embedding's transpose when tied). ``norm_q``,
``norm_k`` are per-head RMSNorms (Qwen3 only); the biases are Qwen2's.

The served number compared is the *widest gap*: over every served token,
how far the reference's logit of that token lies below the reference's
best logit at the same position. The control puts the reference in the
program's place with every weight matrix in float8 (e4m3, scaled per
output channel), the precision below the served bfloat16, and reads the
same gap for the token it would put first.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, D): rotate-half RoPE at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None, None] * inv[None, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, s_ = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], -1)


def _quant_fp8(w):
    """float8 e4m3 per output channel (last axis), scaled to its range,
    back in float32."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def forward_logits(conf: dict, w: Dict[str, jax.Array], tokens: jax.Array,
                   *, fp8: bool = False) -> jax.Array:
    """Logits (S, vocab) of one sequence ``tokens`` (S,), float32."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    h, k, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                conf["head_dim"])
    g = h // k
    s = tokens.shape[0]
    cast = _quant_fp8 if fp8 else (lambda a: a.astype(F32))
    x = w["embed"][tokens].astype(F32)
    mask = jnp.tril(jnp.ones((s, s), bool))
    layer_keys = [n for n in w if w[n].ndim >= 2 and n not in
                  ("embed", "lm_head")]

    def layer(x, p):
        m = {n: (cast(p[n]) if n in MATRICES else p[n].astype(F32))
             for n in p}
        a = _rms(x, m["ln1"], eps)
        q, kk, v = a @ m["wq"], a @ m["wk"], a @ m["wv"]
        if "bq" in m:
            q, kk, v = q + m["bq"], kk + m["bk"], v + m["bv"]
        q, kk, v = q.reshape(s, h, dh), kk.reshape(s, k, dh), v.reshape(s, k, dh)
        if "q_norm" in m:
            q, kk = _rms(q, m["q_norm"], eps), _rms(kk, m["k_norm"], eps)
        q, kk = _rope(q, theta), _rope(kk, theta)
        q = q.reshape(s, k, g, dh)
        sc = jnp.einsum("qkgd,tkd->kgqt", q, kk) / jnp.sqrt(F32(dh))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        o = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(s, h * dh) @ m["wo"]
        a = _rms(x, m["ln2"], eps)
        x = x + (jax.nn.silu(a @ m["w_gate"]) * (a @ m["w_up"])) @ m["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in layer_keys})
    x = _rms(x, w["final_norm"].astype(F32), eps)
    head = w["embed"].T if "lm_head" not in w else w["lm_head"]
    return x @ cast(head)


@functools.partial(jax.jit, static_argnames=("conf_key", "fp8"))
def _gaps(w, tokens, served, *, conf_key, fp8):
    """(gap of each served token, gap of the reference's own pick) per
    position; -1 where no token was served."""
    conf = dict(conf_key)
    with jax.default_matmul_precision("highest"):
        ref = forward_logits(conf, w, tokens)
        best = jnp.max(ref, -1)
        picks = served
        if fp8:
            picks = jnp.argmax(forward_logits(conf, w, tokens, fp8=True), -1)
            picks = jnp.where(served >= 0, picks, -1)
    at = jnp.take_along_axis(ref, jnp.maximum(picks, 0)[:, None], -1)[:, 0]
    return jnp.where(picks >= 0, best - at, -1.0)


def conf_key(conf: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, conf[k]) for k in keys)


def served_positions(prompt: np.ndarray, outputs: Sequence[int],
                     length: int) -> Tuple[np.ndarray, np.ndarray]:
    """The sequence the reference reads (prompt, then every served token
    but the last), padded to ``length``, and at each position the served
    token it produced (-1 where none)."""
    p, n = len(prompt), len(outputs)
    seq = np.zeros(length, np.int32)
    seq[:p] = prompt
    seq[p:p + n - 1] = outputs[:-1]
    served = np.full(length, -1, np.int32)
    served[p - 1:p - 1 + n] = outputs
    return seq, served


def widest_gaps(conf: dict, w, sample: List[Tuple[np.ndarray, List[int]]],
                length: int, *, fp8: bool = False) -> List[float]:
    """Per sampled request, the widest gap of its served tokens (or, with
    ``fp8``, of the tokens the fp8 control puts first)."""
    out = []
    for prompt, outputs in sample:
        seq, served = served_positions(prompt, outputs, length)
        g = np.asarray(_gaps(w, jnp.asarray(seq), jnp.asarray(served),
                             conf_key=conf_key(conf), fp8=fp8))
        out.append(float(g.max()))
    return out
