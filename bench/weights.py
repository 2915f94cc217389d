"""Weights made from ``--seed``, the same for the system under test and
for the plain reference, which regenerates them layer by layer.

Each weight is a normal draw scaled by ``fan_in ** -0.5`` (the embedding
by 1), keyed by its name and layer, in the dtype the model is served in;
norm scales are ones. The padded vocabulary rows the program keeps
beyond ``vocab_size`` are zero, so their logits are 0 and never lead.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    vocab: int
    padded_vocab: int
    theta: float
    eps: float
    gated: bool                 # SwiGLU (3 matrices) or GELU (2)


def dims(cfg: dict) -> Dims:
    """The sizes of a configuration file's model."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    act = cfg["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"unknown hidden_act {act!r}")
    v = cfg["vocab_size"]
    return Dims(layers=cfg["num_hidden_layers"], d=d, heads=h,
                kv_heads=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], vocab=v,
                padded_vocab=-(-v // 256) * 256,
                theta=float(cfg["rope_theta"]),
                eps=float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))),
                gated=act == "silu")


# (group, name, shape as a function of Dims, fan_in); the leaf's key is
# derived from its position in this list, never from the program
LAYER_LEAVES = (
    ("attn", "wq", lambda m: (m.d, m.heads, m.hd), lambda m: m.d),
    ("attn", "wk", lambda m: (m.d, m.kv_heads, m.hd), lambda m: m.d),
    ("attn", "wv", lambda m: (m.d, m.kv_heads, m.hd), lambda m: m.d),
    ("attn", "wo", lambda m: (m.heads, m.hd, m.d), lambda m: m.heads * m.hd),
    ("mlp", "wi_gate", lambda m: (m.d, m.ff), lambda m: m.d),
    ("mlp", "wi_up", lambda m: (m.d, m.ff), lambda m: m.d),
    ("mlp", "wo", lambda m: (m.ff, m.d), lambda m: m.ff),
)
_EMBED, _HEAD = 100, 101


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (64-bit and larger too)."""
    word = np.random.SeedSequence(int(seed) % 2**128).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) >> 1)


def _normal(key, shape, fan_in, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return x.astype(dtype)


def one_layer(m: Dims, key, i, dtype):
    """Layer ``i``'s matrices ``{(group, name): array}`` in ``dtype``."""
    out = {}
    for j, (group, name, shape, fan_in) in enumerate(LAYER_LEAVES):
        if m.gated or name != "wi_gate":
            k = jax.random.fold_in(jax.random.fold_in(key, j), i)
            out[(group, name)] = _normal(k, shape(m), fan_in(m), dtype)
    return out


def embedding(m: Dims, key, dtype):
    """``[padded_vocab, d]``, rows past the vocabulary zero."""
    e = _normal(jax.random.fold_in(key, _EMBED), (m.padded_vocab, m.d), 1.0,
                dtype)
    live = jnp.arange(m.padded_vocab)[:, None] < m.vocab
    return jnp.where(live, e, jnp.zeros((), dtype))


def head(m: Dims, key, dtype):
    """``[d, padded_vocab]``, columns past the vocabulary zero."""
    w = _normal(jax.random.fold_in(key, _HEAD), (m.d, m.padded_vocab), m.d,
                dtype)
    live = jnp.arange(m.padded_vocab)[None, :] < m.vocab
    return jnp.where(live, w, jnp.zeros((), dtype))


def program_params(m: Dims, key, dtype=jnp.bfloat16) -> dict:
    """The whole tree in the program's layout (stacked layers), built
    layer after layer so only one layer's float32 draw is live. Call it
    under ``jax.jit``."""
    def layer(i):
        return one_layer(m, key, i, dtype)

    stacked = jax.lax.map(layer, jnp.arange(m.layers))
    blocks: dict = {"attn": {}, "mlp": {}}
    for (group, name), v in stacked.items():
        blocks[group][name] = v
    ones = jnp.ones((m.layers, m.d), dtype)
    blocks["ln_attn"], blocks["ln_mlp"] = ones, ones
    return {"embed": embedding(m, key, dtype), "blocks": blocks,
            "final_norm": jnp.ones((m.d,), dtype),
            "lm_head": head(m, key, dtype)}


def make_program_params(m: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """One jitted call on the device makes every weight from ``seed``."""
    return jax.jit(lambda k: program_params(m, k, dtype))(base_key(seed))
