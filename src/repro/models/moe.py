"""Mixture-of-Experts FFN: a router over ``n_experts`` routed experts, of
which this configuration holds ``cfg.expert_range`` (all, or one chip's
share of an expert-parallel layer), shared experts beside them, and an
optional dense residual MLP (arctic).

Routing (:func:`route`) scores every routed expert in float32: softmax
then top-k, or DeepSeek-V3's ``noaux_tc`` (sigmoid scores, top-k chosen
by score plus a per-expert correction bias, weighted by the unbiased
scores); the k weights are normalised to sum to 1 and scaled by
``routed_scale``.

Two dispatches:

* dropless (serving: every cached forward, so the continuous engine's
  decode step and prompt chunks): each held expert runs over every
  token, weighted by its gate, which is zero where the token did not
  choose it. Exact, and a row's output never depends on its batchmates.
* GShard capacity dispatch (training, no cache): tokens grouped across
  the batch, einsum dispatch/combine, tokens over an expert's capacity
  dropped. Experts sit on the "model" mesh axis, so the contractions
  induce the expert all-to-all in the compiled HLO.

moonlight-16b-a3b: 64 experts, top-6 by sigmoid with a bias, 2 shared.
arctic-480b: 128 experts, top-2, softmax, plus a dense residual MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding import constrain

GROUP_SIZE = 256  # tokens per dispatch group (GShard 'G'); perf knob


def init_moe(pb, cfg):
    D, E, F = cfg.d_model, cfg.n_held_experts, cfg.d_ff
    m = pb.sub("moe")
    m.param("router", (D, cfg.n_experts), ("embed", None))
    if cfg.router == "sigmoid_bias":
        m.param("router_bias", (cfg.n_experts,), (None,), init="zeros")
    m.param("wi_gate", (E, D, F), ("experts", "embed", "expert_mlp"))
    m.param("wi_up", (E, D, F), ("experts", "embed", "expert_mlp"))
    m.param("wo", (E, F, D), ("experts", "expert_mlp", "embed"))
    if cfg.shared_d_ff:
        L.init_mlp(pb, cfg, d_ff=cfg.shared_d_ff, prefix="shared_mlp")
    if cfg.dense_residual:
        L.init_mlp(pb, cfg, prefix="dense_mlp")


def route(mp, cfg, x):
    """x [..., D] -> (weights [..., K] float32, expert ids [..., K]) over
    all ``n_experts`` routed experts."""
    f32 = jnp.float32
    # float32 in earnest: at default precision the TPU multiplies float32
    # in one bfloat16 pass, and near-equal scores then swap experts
    logits = jnp.einsum("...d,de->...e", x.astype(f32),
                        mp["router"].astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + mp["router_bias"].astype(f32),
                               cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    return w * cfg.routed_scale, idx


def _capacity(cfg, g: int) -> int:
    c = int(g * cfg.top_k * cfg.capacity_factor / cfg.n_held_experts)
    return max(4, -(-c // 4) * 4)  # round up to multiple of 4, >=4


def _capacity_dispatch(mp, cfg, rules, x):
    dt = x.dtype
    B, S, D = x.shape
    E, K = cfg.n_held_experts, cfg.top_k
    lo = cfg.expert_range[0]
    T = B * S
    g = min(GROUP_SIZE, T)      # group across ALL tokens
    n = T // g
    xg = x.reshape(n, g, D)
    xg = constrain(xg, rules, "batch", None, "embed")
    gate_vals, gate_idx = route(mp, cfg, xg)               # [n,g,K]

    C = _capacity(cfg, g)
    combine = jnp.zeros((n, g, E, C), jnp.float32)
    counts = jnp.zeros((n, 1, E), jnp.int32)
    for j in range(K):                                     # GShard k-loop
        # an expert outside the held range one-hots to zeros: dropped
        oh = jax.nn.one_hot(gate_idx[..., j] - lo, E, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=1) - 1 + counts                    # [n,g,E]
        counts = counts + oh.sum(axis=1, keepdims=True)
        keep = (pos < C) & (oh > 0)
        pos_c = jax.nn.one_hot(jnp.where(keep, pos, -1), C,
                               dtype=jnp.float32)                     # [n,g,E,C]
        combine = combine + gate_vals[..., j, None, None] * \
            (oh[..., None].astype(jnp.float32) * pos_c)
    dispatch = (combine > 0).astype(dt)                               # [n,g,E,C]

    # dispatch -> [n,E,C,D]; experts on "model", groups on batch axes
    ein = jnp.einsum("ngec,ngd->necd", dispatch, xg)
    ein = constrain(ein, rules, "batch", "experts", None, "embed")
    h_g = jnp.einsum("necd,edf->necf", ein, mp["wi_gate"].astype(dt))
    h_u = jnp.einsum("necd,edf->necf", ein, mp["wi_up"].astype(dt))
    h = jax.nn.silu(h_g) * h_u
    h = constrain(h, rules, "batch", "experts", None, "expert_mlp")
    eo = jnp.einsum("necf,efd->necd", h, mp["wo"].astype(dt))
    eo = constrain(eo, rules, "batch", "experts", None, "embed")
    y = jnp.einsum("ngec,necd->ngd", combine.astype(dt), eo)
    y = constrain(y, rules, "batch", None, "embed")
    return y.reshape(B, S, D)


def _dropless(mp, cfg, rules, x, rows=None):
    """Every held expert over every token, weighted by the gate. ``rows``
    [B] marks the rows whose choices are counted -> (y, hits [held] int32:
    how many of those rows' tokens chose each held expert)."""
    dt = x.dtype
    lo, hi = cfg.expert_range
    w, idx = route(mp, cfg, x)                             # [B,S,K]
    chosen = idx[..., None] == jnp.arange(lo, hi)          # [B,S,K,E]
    gate = jnp.where(chosen, w[..., None], 0.0).sum(-2)    # [B,S,E] f32
    g = jnp.einsum("bsd,edf->bsef", x, mp["wi_gate"].astype(dt))
    u = jnp.einsum("bsd,edf->bsef", x, mp["wi_up"].astype(dt))
    h = (jax.nn.silu(g) * u * gate[..., None]).astype(dt)
    h = constrain(h, rules, "batch", "seq", "experts", "expert_mlp")
    y = jnp.einsum("bsef,efd->bsd", h, mp["wo"].astype(dt))
    y = constrain(y, rules, "batch", "seq", "embed")
    hits = None
    if rows is not None:
        hits = (chosen.any(-2) & rows[:, None, None]).sum((0, 1),
                                                         dtype=jnp.int32)
    return y, hits


def moe_mlp(p, cfg, rules, x, *, dropless: bool, rows=None):
    """x: [B,S,D] -> (MoE output + shared experts + dense residual, hits).
    ``hits`` (dropless with ``rows`` only, else None): per held expert,
    the tokens of ``rows`` that chose it."""
    hits = None
    if dropless:
        y, hits = _dropless(p["moe"], cfg, rules, x, rows)
    else:
        y = _capacity_dispatch(p["moe"], cfg, rules, x)
    if cfg.shared_d_ff:
        y = y + L.mlp(p["shared_mlp"], rules, x)
    if cfg.dense_residual:
        y = y + L.mlp(p["dense_mlp"], rules, x)
    return y, hits


def load_balance_loss(logits_f32, gate_idx, n_experts: int) -> jnp.ndarray:
    """Standard Switch/GShard auxiliary loss (mean fraction * mean prob)."""
    probs = jax.nn.softmax(logits_f32, axis=-1)
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))
    oh = jax.nn.one_hot(gate_idx[..., 0], n_experts)
    ce = oh.mean(axis=tuple(range(oh.ndim - 1)))
    return n_experts * jnp.sum(me * ce)
