"""The plain reference's shared part: the numerics every architecture's
reference is written with, the pass over its blocks, and the comparison
with the served tokens. It imports nothing of the program.

An architecture module (``arch/<arch>.py``) gives the pieces: its
embedding, its blocks of layers and its head, each in float32, and
each with a ``control`` switch that computes it one precision below the
configuration's instead. They regenerate the weights from the seed one
layer at a time, so the whole model never sits on the device in
float32.

``served_gaps`` reads, for each token the server sent back, how far the
reference's logit for it lies below the reference's best at that
position. ``control=True`` runs the same pass a second time with the
control on, and reads the gap of the token that pass puts first
instead.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def mm(spec, *xs):
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


def same(x, axes):
    """The rounding of the float32 pass: none."""
    return x


class Reference:
    """Jitted pieces of one architecture's reference for one
    configuration, compiled for a fixed ``[rows, positions]`` so every
    run reuses them."""

    def __init__(self, arch, cfg: dict, seed: int, rows: int,
                 positions: int):
        self.m = m = arch.dims(cfg)
        self.key = W.base_key(seed)
        self.shape = (rows, positions)
        self._embed = jax.jit(partial(arch.ref_embed, m), static_argnums=2)
        self._blocks = [(jax.jit(partial(fn, m), static_argnums=3), ids)
                        for fn, ids in arch.ref_blocks(m)]
        self._logits = jax.jit(partial(arch.ref_logits, m), static_argnums=2)

    def hidden(self, tokens: np.ndarray, control: bool = False):
        """Final hidden states [rows, positions, d] of padded tokens."""
        x = self._embed(self.key, jnp.asarray(tokens), control)
        for layer, ids in self._blocks:
            for i in ids:
                x = layer(x, self.key, jnp.int32(i), control)
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
    vocabulary). With ``control``, the token is the one the control's
    pass puts first at that position, not the served one."""
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
