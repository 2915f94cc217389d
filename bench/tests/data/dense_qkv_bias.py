"""A test fixture architecture: the dense GQA transformer with biases on
the query, key and value projections (the program's ``qkv_bias``, as in
``qwen2``), which ``arch/dense.py`` does not make. The harness has no
code for it: this file and its configuration file are all it takes.

Each bias is a normal draw of scale 0.5, keyed by its place in
``BIAS_LEAVES`` and its layer; it is added before the rotation.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reference as R
import weights as W
from arch import dense

Dims, dims, TINY = dense.Dims, dense.dims, dense.TINY
ref_embed, ref_logits, step_flops = (dense.ref_embed, dense.ref_logits,
                                     dense.step_flops)

# (name, shape as a function of Dims); keys apart from the dense leaves'
BIAS_LEAVES = (("bq", lambda m: (m.heads, m.hd)),
               ("bk", lambda m: (m.kv_heads, m.hd)),
               ("bv", lambda m: (m.kv_heads, m.hd)))
_BIAS = 200


def program_config(name: str, m: Dims):
    return dataclasses.replace(dense.program_config(name, m), qkv_bias=True)


def biases(m: Dims, key, i, dtype):
    return {("attn", name): W.normal(
        jax.random.fold_in(jax.random.fold_in(key, _BIAS + j), i),
        shape(m), 4.0, dtype) for j, (name, shape) in enumerate(BIAS_LEAVES)}


def program_params(m: Dims, key, dtype=jnp.bfloat16) -> dict:
    tree = dense.program_params(m, key, dtype)
    stacked = jax.lax.map(lambda i: biases(m, key, i, dtype),
                          jnp.arange(m.layers))
    for (group, name), v in stacked.items():
        tree["blocks"][group][name] = v
    return tree


def attend(m: Dims, h, w, pos, rnd=R.same):
    h = rnd(h, (1,))
    q = R.mm("sd,dnk->snk", h, w["attn", "wq"]) + w["attn", "bq"]
    k = R.mm("sd,dnk->snk", h, w["attn", "wk"]) + w["attn", "bk"]
    v = R.mm("sd,dnk->snk", h, w["attn", "wv"]) + w["attn", "bv"]
    q, k = R.rotary(q, pos, m.theta), R.rotary(k, pos, m.theta)
    q, k, v = rnd(q, (2,)), rnd(k, (2,)), rnd(v, (2,))
    return dense.gqa(m, q, k, v, w, pos, rnd)


def ref_layer(m: Dims, x, key, i, control):
    w = dense.ref_weights(m, key, i, control)
    w.update({k: v.astype(jnp.float32)
              for k, v in biases(m, key, i, jnp.bfloat16).items()})
    return dense.layer(m, x, w, R.fp8_round if control else R.same,
                       attn=attend)


def ref_blocks(m: Dims):
    return [(ref_layer, range(m.layers))]


def step_bytes(m: Dims, step) -> int:
    """The dense step's bytes and the biases read once."""
    return dense.step_bytes(m, step) + \
        m.layers * (m.heads + 2 * m.kv_heads) * m.hd * dense.BF16
