"""Model configuration and weights, made by the benchmark from the seed.

The configuration file (``bench/configs/<name>.json``) holds the
published keys; this module turns them into the program's
``ModelConfig``. Weights are made on the device in one jitted call, in
bfloat16, in the published layout (``make_weights``); ``to_program``
converts them into the program's parameter tree inside the same call.
The float32 reference reads the published layout and nothing else.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file. A tied
    embedding is served as an untied copy (see the file's ``assumed``)."""
    from repro.configs.base import ATTN, MLP, BlockSpec, ModelConfig
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        pattern=(BlockSpec(mixer=ATTN, ff=MLP),),
        qkv_bias=conf["attention_bias"], qk_norm=conf["qk_norm"],
        rope_theta=conf["rope_theta"], rmsnorm_eps=conf["rms_norm_eps"],
        tie_embeddings=False, citation=conf["source"])


def padded_vocab(conf: dict) -> int:
    """The program pads its vocabulary to a multiple of 256."""
    return -(-conf["vocab_size"] // 256) * 256


def weight_shapes(conf: dict) -> Dict[str, tuple]:
    """Published layout: per-layer weights stacked on a leading layer
    axis, matrices as (in, out)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, k, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                conf["head_dim"])
    n, v = conf["num_hidden_layers"], conf["vocab_size"]
    s = {"embed": (v, d), "final_norm": (d,),
         "ln1": (n, d), "ln2": (n, d),
         "wq": (n, d, h * dh), "wk": (n, d, k * dh), "wv": (n, d, k * dh),
         "wo": (n, h * dh, d),
         "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}
    if conf["attention_bias"]:
        s.update(bq=(n, h * dh), bk=(n, k * dh), bv=(n, k * dh))
    if conf["qk_norm"]:
        s.update(q_norm=(n, dh), k_norm=(n, dh))
    if not conf["tie_word_embeddings"]:
        s["lm_head"] = (d, v)
    return s


#: spread of the drawn RMSNorm scales around 1, and of the q/k/v biases
#: (the projections they are added to have entries of about unit size)
NORM_STD = 0.2
BIAS_STD = 0.5


def _key(seed: int) -> jax.Array:
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, name: str, shape: tuple, dtype):
    """Dense matrices: normal, std 1/sqrt(fan_in); embedding: std 0.02;
    RMSNorm scales 1 + N(0, NORM_STD); q/k/v biases N(0, BIAS_STD). Scales
    and biases are drawn, not left at the identity, so that a program that
    ignores them serves other tokens than the reference."""
    if name in ("embed",):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name.startswith("ln") or name.endswith("norm"):
        return (1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name in ("bq", "bk", "bv"):
        return (BIAS_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, dtype)
            * jnp.asarray(fan_in ** -0.5, dtype))


def _make(conf: dict, key: jax.Array, dtype) -> Dict[str, jax.Array]:
    return {name: _leaf(jax.random.fold_in(key, i), name, shape, dtype)
            for i, (name, shape) in enumerate(sorted(weight_shapes(conf).items()))}


def to_program(conf: dict, w: Dict[str, jax.Array]):
    """The program's parameter tree: RMSNorm scales as ``scale - 1`` (it
    multiplies by ``1 + scale``), gate and up fused as ``wi``, the
    vocabulary padded, and the output head explicit."""
    vp = padded_vocab(conf)
    pad = vp - conf["vocab_size"]
    embed = jnp.pad(w["embed"], ((0, pad), (0, 0)))
    head = w["embed"].T if conf["tie_word_embeddings"] else w["lm_head"]
    blk = {"ln1": w["ln1"] - 1, "ln2": w["ln2"] - 1,
           "wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
           "wi": jnp.concatenate([w["w_gate"], w["w_up"]], axis=-1),
           "wo_mlp": w["w_down"]}
    for b in ("bq", "bk", "bv"):
        if b in w:
            blk[b] = w[b]
    if "q_norm" in w:
        blk["q_norm"] = w["q_norm"] - 1
        blk["k_norm"] = w["k_norm"] - 1
    return {"embed": embed, "final_norm": w["final_norm"] - 1,
            "lm_head": jnp.pad(head, ((0, 0), (0, pad))),
            "blocks": (blk,)}


@functools.partial(jax.jit, static_argnames=("conf_key",))
def _program_weights(key, *, conf_key):
    conf = dict(conf_key)
    return to_program(conf, _make(conf, key, jnp.bfloat16))


@functools.partial(jax.jit, static_argnames=("conf_key",))
def _published_weights(key, *, conf_key):
    return _make(dict(conf_key), key, jnp.bfloat16)


def _frozen(conf: dict) -> tuple:
    keys = ("name", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "attention_bias", "qk_norm", "tie_word_embeddings")
    return tuple((k, conf[k]) for k in keys)


def program_weights(conf: dict, seed: int):
    """The served weights, bfloat16, in the program's tree: one call."""
    return _program_weights(_key(seed), conf_key=_frozen(conf))


def published_weights(conf: dict, seed: int):
    """The same weights in the published layout (for the reference)."""
    return _published_weights(_key(seed), conf_key=_frozen(conf))


def check_layout(cfg, params) -> None:
    """The tree handed to the program has exactly the structure and shapes
    its own initializer would make."""
    from repro.models import init_params
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                              jnp.bfloat16))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"want {want}\ngot {got}")
