"""DeepSeek-V3 layers (``deepseek_v3``: Moonlight-16B-A3B): latent
attention (MLA) without query compression, leading dense SwiGLU layers,
then MoE layers whose routed experts are scored by a sigmoid, chosen by
score plus a correction bias (``noaux_tc``, one group) and weighted by
the unbiased scores normalised over the chosen and scaled, beside shared
experts. The contract it keeps is in ``arch/__init__.py``.

This chip holds experts ``[lo, hi)`` of each MoE layer (one chip of an
expert-parallel deployment): the router scores all of them, and the
layer adds only the held experts' part, in the program and in the
reference alike.

Weights: as ``dense.py`` draws them (normal, ``fan_in ** -0.5``, keyed by
the leaf's place in its list and the layer; a routed expert's by its
global id too, so a share is the same experts whatever else is held),
but rounded to bf16 by integer ops (``_round``), so that the served
tree and the reference's redraw agree bit for bit on the TPU too; norm
scales ones; the correction bias a normal draw of scale 0.1 (a trained
bias is of that order), so it moves the choice.

The reference is the full forward pass in float32 with no cache, no
batching across requests, no absorption and no kernels; the control
(``control=True``) rounds to float8 (e4m3) both operands of every
matrix product with a weight, the latent cache entries and the queries,
as ``dense.py`` does.

Counts: what a decode step needs, whatever implements it. A token at
position ``p`` attends over ``p + 1`` positions in absorbed form
(scores over the 512-wide latent and the 64-wide rotary key, the
latent-weighted sum over 512); each (row, held expert) assignment costs
the expert's three matrices, and each held expert hit in a layer is read
once. The hits come from the step's ``counters`` (the program's
``MOE_ROUTE`` events): ``experts_hit`` and ``assignments``, one number a
MoE layer.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W
from arch import dense

BF16 = 2

# a size the CPU dry run holds (``--tiny-cpu``): a dense layer and two
# expert layers, half of 4 experts held. Every token takes all 4, so
# no choice is made: at these widths bf16 rounding of the hidden state
# flips choices between near-equal scores, and one flip moves a logit by
# more than the check's limit (choices are tested in float32 in tier 1,
# and at the published widths by the check on the chip)
TINY = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_router_experts": 4,
        "n_routed_experts": 2, "experts_held": [0, 2],
        "num_experts_per_tok": 4, "vocab_size": 512}


# ----------------------------------------------------------------- sizes --

@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    rank: int               # kv_lora_rank: the latent a token caches
    nope: int               # query/key width without rotary, a head
    rope: int               # rotary width (one key head shared)
    vd: int                 # value width, a head
    dense_ff: int
    ff: int                 # one routed expert's width
    shared_ff: int          # the shared experts as one SwiGLU
    experts: int            # routed experts the router scores
    lo: int                 # this chip's experts: [lo, hi)
    hi: int
    top_k: int
    scale: float            # routed_scaling_factor
    vocab: int
    padded_vocab: int
    theta: float
    eps: float

    @property
    def held(self) -> int:
        return self.hi - self.lo

    @property
    def entry(self) -> int:
        """Cached numbers a token and layer: the latent and rotary key."""
        return self.rank + self.rope


def dims(cfg: dict) -> Dims:
    """The sizes of a configuration file's model; refuses settings this
    module does not compute."""
    want = {"model_type": "deepseek_v3", "hidden_act": "silu",
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "q_lora_rank": None, "moe_layer_freq": 1,
            "attention_bias": False, "tie_word_embeddings": False}
    for k, v in want.items():
        if cfg.get(k) != v:
            raise ValueError(f"deepseek_v3: {k} = {cfg.get(k)!r}, not {v!r}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("experts_held does not span n_routed_experts")
    v = cfg["vocab_size"]
    return Dims(layers=cfg["num_hidden_layers"],
                dense_layers=cfg["first_k_dense_replace"],
                d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
                dense_ff=cfg["intermediate_size"],
                ff=cfg["moe_intermediate_size"],
                shared_ff=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"],
                experts=cfg["n_router_experts"], lo=lo, hi=hi,
                top_k=cfg["num_experts_per_tok"],
                scale=float(cfg["routed_scaling_factor"]), vocab=v,
                padded_vocab=-(-v // 256) * 256,
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def program_config(name: str, m: Dims):
    """The program's ``ModelConfig`` for these sizes, with weights in the
    compute dtype as ``launch/serve`` serves them."""
    from repro.config import Family, ModelConfig
    from repro.launch.serve import serving_config

    return serving_config(ModelConfig(
        arch_id=name, family=Family.MOE, n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.heads, d_ff=m.ff, vocab_size=m.vocab,
        rope_theta=m.theta, norm_eps=m.eps, kv_lora_rank=m.rank,
        qk_nope_head_dim=m.nope, qk_rope_head_dim=m.rope, v_head_dim=m.vd,
        n_experts=m.experts, top_k=m.top_k, router="sigmoid_bias",
        routed_scale=m.scale, shared_d_ff=m.shared_ff,
        n_dense_layers=m.dense_layers, dense_d_ff=m.dense_ff,
        experts_held=(m.lo, m.hi), remat="none"))


# --------------------------------------------------------------- weights --

# (group, name, shape as a function of Dims, fan_in); a leaf's key is its
# place in this list and the layer, never anything of the program
ATTN_LEAVES = (
    ("attn", "wq", lambda m: (m.d, m.heads, m.nope + m.rope), lambda m: m.d),
    ("attn", "wkv_a", lambda m: (m.d, m.entry), lambda m: m.d),
    ("attn", "wk_b", lambda m: (m.rank, m.heads, m.nope), lambda m: m.rank),
    ("attn", "wv_b", lambda m: (m.rank, m.heads, m.vd), lambda m: m.rank),
    ("attn", "wo", lambda m: (m.heads, m.vd, m.d), lambda m: m.heads * m.vd),
)


def _swiglu_leaves(group, width):
    return ((group, "wi_gate", lambda m: (m.d, width(m)), lambda m: m.d),
            (group, "wi_up", lambda m: (m.d, width(m)), lambda m: m.d),
            (group, "wo", lambda m: (width(m), m.d), width))


DENSE_LEAVES = ATTN_LEAVES + _swiglu_leaves("mlp", lambda m: m.dense_ff)
MOE_LEAVES = ATTN_LEAVES + _swiglu_leaves("shared_mlp",
                                          lambda m: m.shared_ff) + (
    ("moe", "router", lambda m: (m.d, m.experts), lambda m: m.d),
    ("moe", "router_bias", lambda m: (m.experts,), lambda m: 100.0))
EXPERT_LEAVES = _swiglu_leaves("moe", lambda m: m.ff)
_EXPERTS = 50               # key offset of the routed experts' leaves


def _round(x, dtype):
    """A float32 draw in ``dtype``; to bf16 by integer round-to-nearest-
    even. A float convert lets the TPU's compiler compute the draw that
    feeds it in bf16, differently inside the served tree's jit and the
    reference's: on a TPU v5e the two differed by a bf16 step in nearly
    every weight, so the reference was not the served model. Integer ops
    leave the draw float32 in both."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return x.astype(dtype)
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) >> 16
    return jax.lax.bitcast_convert_type(u.astype(jnp.uint16), jnp.bfloat16)


def _draw(leaves, m: Dims, key, i, dtype, offset=0):
    out = {}
    for j, (group, name, shape, fan_in) in enumerate(leaves):
        k = jax.random.fold_in(jax.random.fold_in(key, offset + j), i)
        out[(group, name)] = _round(
            W.normal(k, shape(m), fan_in(m), jnp.float32), dtype)
    return out


def one_layer(m: Dims, key, i, dtype, moe: bool):
    """Layer ``i``'s weights ``{(group, name): array}``; an expert layer's
    routed experts are this chip's, stacked first."""
    if not moe:
        return _draw(DENSE_LEAVES, m, key, i, dtype)
    out = _draw(MOE_LEAVES, m, key, i, dtype)
    ids = jnp.arange(m.lo, m.hi)

    def expert(e):
        return _draw(EXPERT_LEAVES, m, jax.random.fold_in(key, e), i, dtype,
                     offset=_EXPERTS)
    out.update(jax.lax.map(expert, ids))
    return out


def _tree(flat: dict, m: Dims, n: int, dtype) -> dict:
    tree: dict = {}
    for (group, name), v in flat.items():
        tree.setdefault(group, {})[name] = v
    tree["attn"]["kv_norm"] = jnp.ones((n, m.rank), dtype)
    ones = jnp.ones((n, m.d), dtype)
    tree["ln_attn"], tree["ln_mlp"] = ones, ones
    return tree


def program_params(m: Dims, key, dtype=jnp.bfloat16) -> dict:
    """The whole tree in the program's layout: the leading dense layers
    and the expert layers, each stacked, built one layer at a time so
    only one layer's float32 draw is live. Call it under ``jax.jit``."""
    dense_ids = jnp.arange(m.dense_layers)
    moe_ids = jnp.arange(m.dense_layers, m.layers)
    flat_d = jax.lax.map(lambda i: one_layer(m, key, i, dtype, False),
                         dense_ids)
    flat_m = jax.lax.map(lambda i: one_layer(m, key, i, dtype, True),
                         moe_ids)
    return {"embed": dense.embedding(m, key, dtype),
            "dense_blocks": _tree(flat_d, m, m.dense_layers, dtype),
            "blocks": _tree(flat_m, m, m.layers - m.dense_layers, dtype),
            "final_norm": jnp.ones((m.d,), dtype),
            "lm_head": dense.head(m, key, dtype)}


# ------------------------------------------------------------- reference --

def attend(m: Dims, h, w, pos, rnd=R.same):
    """One sequence, h [S, d] -> [S, d]: every position's keys and values
    expanded from its latent."""
    h = rnd(h, (1,))
    q = R.mm("sd,dnk->snk", h, w["attn", "wq"])
    q_nope, q_pe = q[..., :m.nope], R.rotary(q[..., m.nope:], pos, m.theta)
    kv = R.mm("sd,dr->sr", h, w["attn", "wkv_a"])
    c = R.rmsnorm(kv[:, :m.rank], m.eps)
    k_pe = R.rotary(kv[:, None, m.rank:], pos, m.theta)[:, 0]
    # the cache entry (the float8 control caches it in float8)
    entry = rnd(jnp.concatenate([c, k_pe], -1), (1,))
    c, k_pe = entry[:, :m.rank], entry[:, m.rank:]
    q_nope, q_pe = rnd(q_nope, (2,)), rnd(q_pe, (2,))
    k = R.mm("tr,rnk->tnk", c, w["attn", "wk_b"])
    v = R.mm("tr,rnk->tnk", c, w["attn", "wv_b"])
    s = (R.mm("snk,tnk->nst", q_nope, k) + R.mm("snk,tk->nst", q_pe, k_pe))
    s = s / np.sqrt(m.nope + m.rope)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    o = R.mm("nst,tnk->snk", jax.nn.softmax(s, axis=-1), v)
    return R.mm("snk,nkd->sd", rnd(o, (1, 2)), w["attn", "wo"])


def swiglu(h, w, group, rnd=R.same):
    h = rnd(h, (1,))
    a = (jax.nn.silu(R.mm("sd,df->sf", h, w[group, "wi_gate"]))
         * R.mm("sd,df->sf", h, w[group, "wi_up"]))
    return R.mm("sf,fd->sd", rnd(a, (1,)), w[group, "wo"])


def routed(m: Dims, h, w, rnd=R.same):
    """The held experts' part of the routed FFN, h [S, d] -> [S, d]:
    every held expert over every position, weighted by its gate (zero
    where the position did not choose it)."""
    scores = jax.nn.sigmoid(R.mm("sd,de->se", rnd(h, (1,)),
                                 w["moe", "router"]))
    _, idx = jax.lax.top_k(scores + w["moe", "router_bias"], m.top_k)
    wt = jnp.take_along_axis(scores, idx, -1)
    wt = wt / wt.sum(-1, keepdims=True) * m.scale
    gate = jnp.where(idx[..., None] == jnp.arange(m.lo, m.hi), wt[..., None],
                     0.0).sum(1)                            # [S, held]

    def expert(acc, e):
        we = {k: v[e] for k, v in w.items() if k[0] == "moe"
              and k[1] in ("wi_gate", "wi_up", "wo")}
        return acc + gate[:, e, None] * swiglu(h, we, "moe", rnd), None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m.held))
    return out


def in_axes(group: str, name: str) -> tuple:
    """A weight's input axes (float8 keeps one scale per output channel);
    a routed expert's weights have the expert axis first."""
    ax = (0, 1) if (group, name) == ("attn", "wo") else (0,)
    if group == "moe" and name != "router":
        return tuple(a + 1 for a in ax)
    return ax


def ref_weights(m: Dims, key, i, control, moe):
    """Layer ``i``'s weights as served (bf16), widened to float32; with
    ``control``, every matrix rounded to float8."""
    w = one_layer(m, key, i, jnp.bfloat16, moe)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if control:
        w = {k: v if k[1] == "router_bias" else R.fp8_round(v, in_axes(*k))
             for k, v in w.items()}
    return w


def _layer(m: Dims, x, key, i, control, moe):
    w = ref_weights(m, key, i, control, moe)
    rnd = R.fp8_round if control else R.same
    pos = jnp.arange(x.shape[1])

    def one(xs):
        xs = xs + attend(m, R.rmsnorm(xs, m.eps), w, pos, rnd)
        h = R.rmsnorm(xs, m.eps)
        if not moe:
            return xs + swiglu(h, w, "mlp", rnd)
        return xs + routed(m, h, w, rnd) + swiglu(h, w, "shared_mlp", rnd)
    return jax.lax.map(one, x)


def ref_dense_layer(m: Dims, x, key, i, control):
    return _layer(m, x, key, i, control, False)


def ref_moe_layer(m: Dims, x, key, i, control):
    return _layer(m, x, key, i, control, True)


def ref_blocks(m: Dims):
    return [(ref_dense_layer, range(m.dense_layers)),
            (ref_moe_layer, range(m.dense_layers, m.layers))]


ref_embed, ref_logits = dense.ref_embed, dense.ref_logits


# ---------------------------------------------------------------- counts --

def attn_params(m: Dims) -> int:
    return (m.d * m.heads * (m.nope + m.rope) + m.d * m.entry
            + m.rank * m.heads * (m.nope + m.vd) + m.heads * m.vd * m.d)


def expert_params(m: Dims) -> int:
    return 3 * m.d * m.ff


def weight_bytes(m: Dims, itemsize: int = BF16) -> int:
    """Every weight but the routed experts', read once a step: attention
    and norms of every layer, the dense layers' MLP, each expert layer's
    router, bias and shared experts, the final norm and the output head
    (published vocabulary)."""
    moe_layers = m.layers - m.dense_layers
    n = (m.layers * (attn_params(m) + m.rank + 2 * m.d)
         + m.dense_layers * 3 * m.d * m.dense_ff
         + moe_layers * (m.d * m.experts + m.experts + 3 * m.d * m.shared_ff)
         + m.d + m.d * m.vocab)
    return n * itemsize


def token_flops(m: Dims, context: int) -> int:
    """One token over ``context`` positions, its routed experts aside: two
    operations per weight it passes in absorbed form (the query through
    ``wk_b``, the latent sum through ``wv_b``), the scores over the latent
    and the rotary key and the latent-weighted sum in every layer, the
    dense MLP, each expert layer's router and shared experts, the head."""
    moe_layers = m.layers - m.dense_layers
    proj = (m.d * m.heads * (m.nope + m.rope) + m.d * m.entry
            + m.heads * m.nope * m.rank + m.heads * m.rank * m.vd
            + m.heads * m.vd * m.d)
    attn = 2 * proj + 2 * m.heads * context * (m.entry + m.rank)
    return (m.layers * attn + m.dense_layers * 2 * 3 * m.d * m.dense_ff
            + moe_layers * 2 * (m.d * m.experts + 3 * m.d * m.shared_ff)
            + 2 * m.d * m.vocab)


def step_flops(m: Dims, step) -> int:
    """``step.contexts``: per seated slot, the positions its token attends
    over; ``step.counters["assignments"]``: per expert layer, the (row,
    held expert) pairs the rows chose."""
    routed_ops = 2 * expert_params(m) * sum(step.counters["assignments"])
    return sum(token_flops(m, int(c)) for c in step.contexts) + routed_ops


def step_bytes(m: Dims, step, itemsize: int = BF16) -> int:
    """Weights once, each hit held expert once a layer
    (``step.counters["experts_hit"]``), each seated token's embedding row,
    the latent entries it reads (its ``context - 1`` earlier positions)
    and the one it writes, in every layer."""
    entry = m.layers * m.entry * itemsize
    n = len(step.contexts)
    read = sum(int(c) - 1 for c in step.contexts) * entry
    experts = sum(step.counters["experts_hit"]) * expert_params(m) * itemsize
    return (weight_bytes(m, itemsize) + experts + n * m.d * itemsize + read
            + n * entry)
