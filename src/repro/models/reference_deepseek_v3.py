"""A plain reference of the DeepSeek-V3 decoder (``moonlight-16b-a3b``):
the full forward pass of one sequence in float32, with no cache, no
batching across requests, no absorption and no kernels, written from the
published equations and sharing no code with ``models/``. It reads the
program's weight tree (``transformer.init``'s layout) and holds the
experts the configuration holds, so it computes the same expert share.

Per layer ``x += attn(norm(x)); x += ffn(norm(x))``, where attention is

    q = x Wq = [q_nope, q_pe]            c, k_pe = split(x Wkv_a)
    c = RMSNorm(c)                       q_pe, k_pe = rope(q_pe), rope(k_pe)
    k = [c Wk_b, k_pe] (k_pe shared by every head),  v = c Wv_b
    out = softmax(q.k / sqrt(nope + rope), causal) v  Wo

and the FFN is a SwiGLU in the leading dense layers, else the routed
experts (sigmoid scores; top-k by score + bias; weights the unbiased
scores normalised over the k, times ``routed_scale``) plus the shared
experts. Rotary positions act on half-rotated pairs, as everywhere in the
program (the checkpoint's interleaved pairs are a fixed permutation of
the rope columns).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, ..., d]: pairs (i, i + d/2) rotate by position * theta^(-2i/d)."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    inv = theta ** (-np.arange(d // 2, dtype=np.float32) * 2.0 / d)
    ang = pos.reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _swiglu(w, x):
    return (jax.nn.silu(x @ w["wi_gate"]) * (x @ w["wi_up"])) @ w["wo"]


def attention(cfg, w, x):
    S = x.shape[0]
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("sd,dhk->shk", x, w["wq"])
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)
    kv = x @ w["wkv_a"]
    c = _rms(kv[:, :R], w["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv[:, R:], cfg.rope_theta)
    k_nope = jnp.einsum("sr,rhn->shn", c, w["wk_b"])
    v = jnp.einsum("sr,rhv->shv", c, w["wv_b"])
    s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
         + jnp.einsum("qhp,kp->hqk", q_pe, k_pe))
    s = s / np.sqrt(nope + cfg.qk_rope_head_dim)
    causal = np.tril(np.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khv->qhv", p, v)
    return jnp.einsum("shv,hvd->sd", o, w["wo"])


def routed(cfg, w, x):
    """The held experts' part of the routed FFN: x [S, D] -> [S, D]."""
    scores = jax.nn.sigmoid(x @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"], cfg.top_k)
    wt = jnp.take_along_axis(scores, idx, -1)
    wt = wt / wt.sum(-1, keepdims=True) * cfg.routed_scale
    lo, hi = cfg.expert_range
    out = jnp.zeros_like(x)
    for e in range(lo, hi):
        gate = jnp.where(idx == e, wt, 0.0).sum(-1)      # 0 where not chosen
        we = {k: w[k][e - lo] for k in ("wi_gate", "wi_up", "wo")}
        out = out + gate[:, None] * _swiglu(we, x)
    return out


def forward(params, cfg, tokens) -> jnp.ndarray:
    """tokens [S] -> logits [S, vocab_size], in float32 at the highest
    matmul precision."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        layers = [("dense_blocks", i) for i in range(cfg.n_dense_layers)]
        layers += [("blocks", i)
                   for i in range(cfg.n_layers - cfg.n_dense_layers)]
        for stack, i in layers:
            w = jax.tree_util.tree_map(lambda a: a[i], f32[stack])
            x = x + attention(cfg, w["attn"],
                              _rms(x, w["ln_attn"], cfg.norm_eps))
            h = _rms(x, w["ln_mlp"], cfg.norm_eps)
            if stack == "dense_blocks":
                x = x + _swiglu(w["mlp"], h)
            else:
                x = x + routed(cfg, w["moe"], h) + _swiglu(w["shared_mlp"], h)
        x = _rms(x, f32["final_norm"], cfg.norm_eps)
        return (x @ f32["lm_head"])[:, :cfg.vocab_size]
