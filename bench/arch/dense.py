"""The dense GQA transformer: RMSNorm, rotary positions on half-rotated
pairs, grouped-query causal attention, and a SwiGLU or tanh-GELU MLP
(``internlm2``, ``starcoder2``). The contract it keeps is in
``arch/__init__.py``.

Weights: each matrix is a normal draw scaled by ``fan_in ** -0.5`` (the
embedding by 1), keyed by its place in ``LAYER_LEAVES`` and its layer;
norm scales are ones; the padded vocabulary rows the program keeps
beyond ``vocab_size`` are zero, so their logits are 0 and never lead.

The reference is the model's full forward pass in float32 with no
cache, no batching across requests and no kernels, written from the
published description and importing nothing of the program. Its
control (``control=True``) computes the same pass in float8 (e4m3):
both operands of every matrix product with a weight are rounded to
float8, weights with one scale per output channel and activations with
one scale per token, and so are the queries, keys and values (a float8
KV cache), with one scale per head vector.

Counts: the operations and bytes a decode step needs, from the shapes
and the tokens the step really processed, whatever implements it. A
step processes one token for each seated slot; the token at position
``p`` attends over ``p + 1`` positions.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W

BF16 = 2

# a size the CPU dry run holds (``--tiny-cpu``)
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 128,
        "vocab_size": 512, "head_dim": 16}


# ----------------------------------------------------------------- sizes --

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


def program_config(name: str, m: Dims):
    """The program's ``ModelConfig`` for these sizes, with weights in the
    compute dtype as ``launch/serve`` serves them."""
    from repro.config import Family, ModelConfig
    from repro.launch.serve import serving_config

    return serving_config(ModelConfig(
        arch_id=name, family=Family.DENSE, n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, d_ff=m.ff,
        vocab_size=m.vocab, head_dim=m.hd, rope_theta=m.theta,
        mlp_kind="swiglu" if m.gated else "gelu", remat="none"))


# --------------------------------------------------------------- weights --

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


def one_layer(m: Dims, key, i, dtype):
    """Layer ``i``'s matrices ``{(group, name): array}`` in ``dtype``."""
    out = {}
    for j, (group, name, shape, fan_in) in enumerate(LAYER_LEAVES):
        if m.gated or name != "wi_gate":
            k = jax.random.fold_in(jax.random.fold_in(key, j), i)
            out[(group, name)] = W.normal(k, shape(m), fan_in(m), dtype)
    return out


def embedding(m: Dims, key, dtype):
    """``[padded_vocab, d]``, rows past the vocabulary zero."""
    e = W.normal(jax.random.fold_in(key, _EMBED), (m.padded_vocab, m.d), 1.0,
                 dtype)
    live = jnp.arange(m.padded_vocab)[:, None] < m.vocab
    return jnp.where(live, e, jnp.zeros((), dtype))


def head(m: Dims, key, dtype):
    """``[d, padded_vocab]``, columns past the vocabulary zero."""
    w = W.normal(jax.random.fold_in(key, _HEAD), (m.d, m.padded_vocab), m.d,
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


# ------------------------------------------------------------- reference --

def attend(m: Dims, h, w, pos, rnd=R.same):
    """One sequence: h [S, d] -> [S, d]."""
    h = rnd(h, (1,))
    q = R.rotary(R.mm("sd,dnk->snk", h, w["attn", "wq"]), pos, m.theta)
    k = R.rotary(R.mm("sd,dnk->snk", h, w["attn", "wk"]), pos, m.theta)
    v = R.mm("sd,dnk->snk", h, w["attn", "wv"])
    q, k, v = rnd(q, (2,)), rnd(k, (2,)), rnd(v, (2,))
    return gqa(m, q, k, v, w, pos, rnd)


def gqa(m: Dims, q, k, v, w, pos, rnd=R.same):
    """Grouped-query causal attention and the output projection: q
    [S, heads, hd], k and v [S, kv_heads, hd] -> [S, d]."""
    g = m.heads // m.kv_heads
    q = q.reshape(q.shape[0], m.kv_heads, g, m.hd)
    s = R.mm("qkgh,tkh->kgqt", q, k) / np.sqrt(m.hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = R.mm("kgqt,tkh->qkgh", p, v).reshape(q.shape[0], m.heads, m.hd)
    return R.mm("snk,nkd->sd", rnd(o, (1, 2)), w["attn", "wo"])


def mlp(m: Dims, h, w, rnd=R.same):
    h = rnd(h, (1,))
    u = R.mm("sd,df->sf", h, w["mlp", "wi_up"])
    if m.gated:
        a = jax.nn.silu(R.mm("sd,df->sf", h, w["mlp", "wi_gate"])) * u
    else:
        a = jax.nn.gelu(u, approximate=True)
    return R.mm("sf,fd->sd", rnd(a, (1,)), w["mlp", "wo"])


def layer(m: Dims, x, w, rnd=R.same, attn=attend):
    """x [B, S, d] float32; norm scales are ones. ``attn`` is the
    attention sublayer (``attend``)."""
    pos = jnp.arange(x.shape[1])

    def one(xs):
        xs = xs + attn(m, R.rmsnorm(xs, m.eps), w, pos, rnd)
        return xs + mlp(m, R.rmsnorm(xs, m.eps), w, rnd)
    return jax.lax.map(one, x)


# per weight, its input axes: float8 keeps one scale per output channel
IN_AXES = {("attn", "wq"): (0,), ("attn", "wk"): (0,), ("attn", "wv"): (0,),
           ("attn", "wo"): (0, 1), ("mlp", "wi_gate"): (0,),
           ("mlp", "wi_up"): (0,), ("mlp", "wo"): (0,)}


def ref_weights(m: Dims, key, i, control):
    """Layer ``i``'s weights as served (bf16), widened to float32; with
    ``control``, rounded to float8."""
    w = one_layer(m, key, i, jnp.bfloat16)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if control:
        w = {k: R.fp8_round(v, IN_AXES[k]) for k, v in w.items()}
    return w


def ref_layer(m: Dims, x, key, i, control):
    return layer(m, x, ref_weights(m, key, i, control),
                 R.fp8_round if control else R.same)


def ref_blocks(m: Dims):
    return [(ref_layer, range(m.layers))]


def ref_embed(m: Dims, key, tokens, control):
    e = embedding(m, key, jnp.bfloat16).astype(jnp.float32)
    if control:
        e = R.fp8_round(e, (1,))
    return e[tokens]


def ref_logits(m: Dims, key, h, control):
    w = head(m, key, jnp.bfloat16).astype(jnp.float32)
    h = R.rmsnorm(h, m.eps)
    if control:
        w, h = R.fp8_round(w, (0,)), R.fp8_round(h, (1,))
    return R.mm("td,dv->tv", h, w)[:, :m.vocab]


# ---------------------------------------------------------------- counts --

def layer_matmul_params(m: Dims) -> int:
    attn = 2 * m.d * m.heads * m.hd + 2 * m.d * m.kv_heads * m.hd
    return attn + (3 if m.gated else 2) * m.d * m.ff


def token_flops(m: Dims, context: int) -> int:
    """One token attending over ``context`` positions: two operations per
    weight of every matrix it passes, the output head over the published
    vocabulary, and ``q.k`` plus ``p.v`` over the context in each layer."""
    per_layer = 2 * layer_matmul_params(m) + 4 * m.heads * m.hd * context
    return m.layers * per_layer + 2 * m.d * m.vocab


def kv_bytes_per_position(m: Dims, itemsize: int = BF16) -> int:
    return m.layers * 2 * m.kv_heads * m.hd * itemsize


def weight_bytes(m: Dims, itemsize: int = BF16) -> int:
    """Every weight read once per step: the layers' matrices and norms,
    the final norm and the output head (published vocabulary)."""
    layers = m.layers * (layer_matmul_params(m) + 2 * m.d)
    return (layers + m.d + m.d * m.vocab) * itemsize


def step_flops(m: Dims, step) -> int:
    """``step.contexts``: per seated slot, the positions its token
    attends over."""
    return sum(token_flops(m, int(c)) for c in step.contexts)


def step_bytes(m: Dims, step, itemsize: int = BF16) -> int:
    """Weights once, each seated token's embedding row, the live KV it
    reads (its ``context - 1`` earlier positions) and the KV it writes."""
    kv = kv_bytes_per_position(m, itemsize)
    n = len(step.contexts)
    read = sum(int(c) - 1 for c in step.contexts) * kv
    return (weight_bytes(m, itemsize) + n * m.d * itemsize + read + n * kv)
