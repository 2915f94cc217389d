"""Pallas TPU flash attention (causal, GQA-native) — forward + backward.

Layout: q [B, H, Sq, hd]; k, v [B, KV, Skv, hd]; GQA handled in the
BlockSpec index maps (kv head = q head // group), so KV is never expanded.

Tiling: (block_q x hd) query tiles stream over (block_k x hd) KV tiles with
online softmax; accumulators live in VMEM scratch across the innermost
(arbitrary-semantics) KV grid dimension. block sizes default to 128 —
MXU-aligned (128x128) and small enough that the working set
(q + k + v + acc + p ~ 5 * 128 * hd * 4B ~ 320KB at hd=128) fits VMEM.

Backward: dq kernel (grid over q tiles, KV innermost) and dkv kernel (grid
over kv tiles, revisited across group heads and q tiles) using saved
logsumexp and delta = rowsum(do * o).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decode_attention import resolve_interpret

DEFAULT_BLOCK = 128
NEG_INF = -1e30


def _causal_mask(i, j, bq, bk):
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos >= kpos


# ----------------------------------------------------------------- fwd -----

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i, *,
                causal: bool, scale: float, block_q: int, block_k: int,
                nk: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal \
        else (j < nk)  # always-true traced pred for the non-causal path

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(i, j, block_q, block_k), s, NEG_INF)
        m_new = jnp.maximum(m_i[...], s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_i[...] - m_new)
        l_i[...] = l_i[...] * corr + p.sum(axis=1)
        acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_i[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_i[...], 1e-30)
        o_ref[0, 0] = (acc[...] / denom[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_i[...] + jnp.log(denom)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK,
                        block_k: int = DEFAULT_BLOCK,
                        interpret: bool | None = None):
    """q [B,H,Sq,hd]; k,v [B,KV,Skv,hd] -> (o [B,H,Sq,hd], lse [B,H,Sq])."""
    B, H, Sq, hd = q.shape
    _, KV, Skv, _ = k.shape
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    nq, nk = Sq // bq, Skv // bk
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=bq, block_k=bk,
        nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pl_scratch((bq, hd)),
            pl_scratch((bq,)),
            pl_scratch((bq,)),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return o, lse


def pl_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


# ----------------------------------------------------------------- bwd -----

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc, *, causal, scale, block_q, block_k, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal else (j < nk)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(i, j, block_q, block_k), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal, scale,
                block_q, block_k, nq, G):
    # grid: (B, KV, nk, G, nq); kv tile revisited across (g, i)
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = ((i + 1) * block_q - 1 >= j * block_k) if causal else (i < nq)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(i, j, block_q, block_k), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                      # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, hd]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g == G - 1) & (i == nq - 1))
    def _fin():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, scale=None,
                        block_q: int = DEFAULT_BLOCK,
                        block_k: int = DEFAULT_BLOCK,
                        interpret: bool | None = None):
    B, H, Sq, hd = q.shape
    _, KV, Skv, _ = k.shape
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    nq, nk = Sq // bq, Skv // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                # [B,H,Sq]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[pl_scratch((bq, hd))],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nq=nq, G=G),
        grid=(B, KV, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, kv, j, g, i, G=G: (b, kv * G + g, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j, g, i: (b, kv, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j, g, i: (b, kv, j, 0)),
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, kv, j, g, i, G=G: (b, kv * G + g, i, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, kv, j, g, i, G=G: (b, kv * G + g, i)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, kv, j, g, i, G=G: (b, kv * G + g, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j, g, i: (b, kv, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j, g, i: (b, kv, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Skv, hd), k.dtype),
            jax.ShapeDtypeStruct((B, KV, Skv, hd), v.dtype),
        ],
        scratch_shapes=[pl_scratch((bk, hd)), pl_scratch((bk, hd))],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
