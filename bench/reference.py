"""The plain reference: the model's full forward pass in float32 with no
cache, no batching across requests and no kernels, written from the
published description (RMSNorm, rotary positions on half-rotated pairs,
grouped-query causal attention, SwiGLU or tanh-GELU MLP) and importing
nothing of the program. It regenerates the weights from the seed
(``weights.py``) one layer at a time, so the whole model never sits on
the device in float32.

``served_gaps`` reads, for each token the server sent back, how far the
reference's logit for it lies below the reference's best at that
position. ``control=True`` runs the same pass a second time computed in
float8 (e4m3), the precision one step below the configuration's
bfloat16: both operands of every matrix product with a weight are
rounded to float8, weights with one scale per output channel and
activations with one scale per token, and so are the queries, keys and
values (a float8 KV cache), with one scale per head vector. It reads
the gap of the token that pass puts first instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HI,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotary(x, pos, theta):
    """x [S, n, hd]; the first and second halves of hd form the pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def fp8_round(w, in_axes):
    """Round to float8 e4m3 with one scale per slice along the axes not
    in ``in_axes`` (per output channel of a weight, per token of an
    activation)."""
    s = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _same(x, axes):
    return x


def attend(m: W.Dims, h, w, pos, rnd=_same):
    """One sequence: h [S, d] -> [S, d]."""
    h = rnd(h, (1,))
    q = rotary(_mm("sd,dnk->snk", h, w["attn", "wq"]), pos, m.theta)
    k = rotary(_mm("sd,dnk->snk", h, w["attn", "wk"]), pos, m.theta)
    v = _mm("sd,dnk->snk", h, w["attn", "wv"])
    q, k, v = rnd(q, (2,)), rnd(k, (2,)), rnd(v, (2,))
    g = m.heads // m.kv_heads
    q = q.reshape(q.shape[0], m.kv_heads, g, m.hd)
    s = _mm("qkgh,tkh->kgqt", q, k) / np.sqrt(m.hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("kgqt,tkh->qkgh", p, v).reshape(q.shape[0], m.heads, m.hd)
    return _mm("snk,nkd->sd", rnd(o, (1, 2)), w["attn", "wo"])


def mlp(m: W.Dims, h, w, rnd=_same):
    h = rnd(h, (1,))
    u = _mm("sd,df->sf", h, w["mlp", "wi_up"])
    if m.gated:
        a = jax.nn.silu(_mm("sd,df->sf", h, w["mlp", "wi_gate"])) * u
    else:
        a = jax.nn.gelu(u, approximate=True)
    return _mm("sf,fd->sd", rnd(a, (1,)), w["mlp", "wo"])


def layer(m: W.Dims, x, w, rnd=_same):
    """x [B, S, d] float32; norm scales are ones."""
    pos = jnp.arange(x.shape[1])

    def one(xs):
        xs = xs + attend(m, rmsnorm(xs, m.eps), w, pos, rnd)
        return xs + mlp(m, rmsnorm(xs, m.eps), w, rnd)
    return jax.lax.map(one, x)


_IN_AXES = {("attn", "wq"): (0,), ("attn", "wk"): (0,), ("attn", "wv"): (0,),
            ("attn", "wo"): (0, 1), ("mlp", "wi_gate"): (0,),
            ("mlp", "wi_up"): (0,), ("mlp", "wo"): (0,)}


class Reference:
    """Jitted pieces of the reference for one configuration, compiled for
    a fixed ``[rows, positions]`` so every run reuses them."""

    def __init__(self, cfg: dict, seed: int, rows: int, positions: int):
        self.m = m = W.dims(cfg)
        self.key = W.base_key(seed)
        self.shape = (rows, positions)

        def weights_of(key, i, control):
            w = W.one_layer(m, key, i, jnp.bfloat16)
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
            if control:
                w = {k: fp8_round(v, _IN_AXES[k]) for k, v in w.items()}
            return w

        def run_layer(x, key, i, control):
            return layer(m, x, weights_of(key, i, control),
                         fp8_round if control else _same)

        def embed(key, tokens, control):
            e = W.embedding(m, key, jnp.bfloat16).astype(jnp.float32)
            if control:
                e = fp8_round(e, (1,))
            return e[tokens]

        def logits(key, h, control):
            w = W.head(m, key, jnp.bfloat16).astype(jnp.float32)
            h = rmsnorm(h, m.eps)
            if control:
                w, h = fp8_round(w, (0,)), fp8_round(h, (1,))
            return _mm("td,dv->tv", h, w)[:, :m.vocab]

        self._layer = jax.jit(run_layer, static_argnums=3)
        self._embed = jax.jit(embed, static_argnums=2)
        self._logits = jax.jit(logits, static_argnums=2)

    def hidden(self, tokens: np.ndarray, control: bool = False):
        """Final hidden states [rows, positions, d] of padded tokens."""
        x = self._embed(self.key, jnp.asarray(tokens), control)
        for i in range(self.m.layers):
            x = self._layer(x, self.key, jnp.int32(i), control)
        return x

    def head_logits(self, h, control: bool = False):
        return self._logits(self.key, h, control)


def pack(seqs: list[np.ndarray], rows: int, positions: int) -> np.ndarray:
    out = np.zeros((rows, positions), np.int32)
    for r, s in enumerate(seqs):
        out[r, :len(s)] = s
    return out


def served_gaps(ref: Reference, prompts: list[np.ndarray],
                served: list[list[int]], *, control: bool = False,
                chunk: int = 512) -> np.ndarray:
    """Per served token, ``max(ref logits) - ref logit of the token`` at
    the position that produced it (``inf`` for an id outside the
    vocabulary). With ``control``, the token is the one the float8 pass
    puts first at that position, not the served one."""
    rows, positions = ref.shape
    seqs, where = [], []
    for r, (p, g) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, np.asarray(g[:-1], np.int32)])
        if len(seq) > positions or r >= rows:
            raise ValueError("sample does not fit the reference's shape")
        seqs.append(seq)
        where += [(r, len(p) - 1 + j, t) for j, t in enumerate(g)]
    tokens = pack(seqs, rows, positions)
    idx = np.asarray([(r, j) for r, j, _ in where])
    want = np.asarray([t for _, _, t in where])
    h = ref.hidden(tokens)[idx[:, 0], idx[:, 1]]
    hc = ref.hidden(tokens, control=True)[idx[:, 0], idx[:, 1]] \
        if control else None
    gaps = []
    for a in range(0, len(want), chunk):
        sl = slice(a, a + chunk)
        lg = np.asarray(ref.head_logits(_pad_rows(h[sl], chunk)))
        lg = lg[:len(want[sl])]
        if control:
            lc = np.asarray(ref.head_logits(_pad_rows(hc[sl], chunk),
                                            control=True))[:len(want[sl])]
            pick = lc.argmax(axis=1)
        else:
            pick = want[sl]
        best = lg.max(axis=1)
        ok = (pick >= 0) & (pick < lg.shape[1])
        got = np.where(ok, lg[np.arange(len(pick)),
                              np.clip(pick, 0, lg.shape[1] - 1)], -np.inf)
        gaps.append(best - got)
    return np.concatenate(gaps)


def _pad_rows(h, n):
    pad = n - h.shape[0]
    return jnp.pad(h, ((0, pad), (0, 0))) if pad > 0 else h
