"""Shared model layers: RMSNorm, RoPE, GQA attention (blockwise-flash XLA
reference + Pallas hook), SwiGLU MLP. All functions are pure; params come
from ParamBuilder subtrees.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.sharding import constrain


# ----------------------------------------------------------------- norms ----

def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------- rope -----

def rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ------------------------------------------------- blockwise flash (XLA) ----

def flash_attention_xla(q, k, v, *, causal: bool, q_offset=0,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        scale: float | None = None):
    """Memory-efficient attention in pure lax — the reference the Pallas
    kernel must match. q: [B,Sq,H,hd]; k,v: [B,Skv,KV,hd] (grouped).

    Online-softmax over KV chunks, outer lax.map over Q chunks, so the
    materialized working set is O(q_chunk * kv_chunk) per head.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    q = q.reshape(B, Sq, KV, G, hd)
    nq = max(1, Sq // q_chunk) if Sq % (q_chunk) == 0 else 1
    q_chunk = Sq // nq
    nk = max(1, Skv // kv_chunk) if Skv % kv_chunk == 0 else 1
    kv_chunk = Skv // nk

    qpos = q_offset + jnp.arange(Sq, dtype=jnp.int32)
    kpos = jnp.arange(Skv, dtype=jnp.int32)

    def one_q_chunk(qi):
        qs = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(qpos, qi * q_chunk, q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            kp = jax.lax.dynamic_slice_in_dim(kpos, ki * kv_chunk, kv_chunk)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qs, ks,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = qp[:, None] >= kp[None, :]
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vs.dtype), vs,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk, dtype=jnp.int32))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)  # [B,KV,G,qc,hd]

    if nq == 1:
        out = one_q_chunk(jnp.asarray(0))
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    outs = jax.lax.map(one_q_chunk, jnp.arange(nq, dtype=jnp.int32))
    # [nq,B,KV,G,qc,hd] -> [B,Sq,H,hd]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, KV, G, hd)
    return out.reshape(B, Sq, H, hd)


def decode_attention_two_part(q, k_cache, v_cache, k_new, v_new, cache_len,
                              *, scale=None):
    """Decode without writing the cache first: softmax over
    [old cache (masked < cache_len); new token]. q [B,1,H,hd];
    caches [B,S,KV,hd]; k_new/v_new [B,1,KV,hd]."""
    B, _, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    qr = q.reshape(B, KV, G, hd)
    s_old = jnp.einsum("bkgh,bskh->bkgs", qr, k_cache,
                       preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S, dtype=jnp.int32)
    mask = pos[None, :] < cache_len[:, None]
    s_old = jnp.where(mask[:, None, None, :], s_old, -jnp.inf)
    s_new = jnp.einsum("bkgh,bkh->bkg", qr, k_new[:, 0],
                       preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(s_old.max(axis=-1), s_new)              # [B,KV,G]
    p_old = jnp.exp(s_old - m[..., None])
    p_new = jnp.exp(s_new - m)
    denom = p_old.sum(axis=-1) + p_new
    o = jnp.einsum("bkgs,bskh->bkgh", p_old.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o + p_new[..., None] * v_new[:, 0, :, None, :].astype(jnp.float32)
    o = o / denom[..., None]
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# static symmetric int8 KV quantization scale; production carries
# per-block scales (+<1% bytes) — see DESIGN.md
QSCALE = 16.0


def decode_attention_xla(q, k_cache, v_cache, cache_len, *, scale=None):
    """Single-token decode: q [B,1,H,hd]; caches [B,S,KV,hd]; cache_len [B]."""
    B, _, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    qr = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S, dtype=jnp.int32)
    mask = pos[None, :] < cache_len[:, None]            # [B,S]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def _row_pages(pages, layer, block_tables):
    """Each row's pages of layer ``layer`` of the stacked arena
    [L,NB,BS,...], gathered through its block table [B,MB] into
    [B, MB*BS, ...], with no slice of the layer made."""
    g = pages[layer, block_tables]
    B, MB, BS = g.shape[:3]
    return g.reshape(B, MB * BS, *g.shape[3:])


def paged_attention_xla(q, k_pages, v_pages, layer, block_tables, lens, *,
                        scale=None):
    """Single-token decode against layer ``layer`` of the genesys.pagedkv
    block arena. q [B,1,H,hd]; k_pages/v_pages [L,NB,BS,KV,hd];
    block_tables [B,MB] int32 (pad with the pool's null block; padded
    positions are masked by ``lens``); lens [B].

    Gathers each sequence's pages into a [B, MB*BS, KV, hd] view and runs
    the masked decode softmax — the logical computation the Pallas
    split-KV kernel performs in place through the block table.
    """
    kd = _row_pages(k_pages, layer, block_tables)
    vd = _row_pages(v_pages, layer, block_tables)
    if k_pages.dtype == jnp.int8:
        kd = kd.astype(q.dtype) / QSCALE
        vd = vd.astype(q.dtype) / QSCALE
    return decode_attention_xla(q, kd, vd, lens, scale=scale)


def paged_chunk_attention_xla(q, k_pages, v_pages, layer, block_tables,
                              positions, *, scale=None):
    """Causal attention of S queries per row against the genesys.pagedkv
    block arena: the query at ``positions[b, i]`` attends every position
    of its row up to and including its own. The masked float32 softmax of
    :func:`decode_attention_xla`, over the same gathered pages as
    :func:`paged_attention_xla`. q [B,S,H,hd]; k_pages/v_pages
    [L,NB,BS,KV,hd], read at ``layer``; block_tables [B,MB]; positions
    [B,S]."""
    B, S, H, hd = q.shape
    kd = _row_pages(k_pages, layer, block_tables)
    vd = _row_pages(v_pages, layer, block_tables)
    KV = kd.shape[2]
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    if k_pages.dtype == jnp.int8:
        kd = kd.astype(q.dtype) / QSCALE
        vd = vd.astype(q.dtype) / QSCALE
    qr = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qr, kd,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(kd.shape[1], dtype=jnp.int32)
    mask = pos[None, None, :] <= positions[:, :, None]      # [B,S,MB*BS]
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(vd.dtype), vd,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, hd).astype(q.dtype)


def _table_blocks(block_tables, positions, block_size):
    """The arena block that holds each position [B,S] of a row, through
    the row's table [B,MB] (a position past the table reads its last
    entry; only padding lies there)."""
    col = jnp.minimum(positions // block_size, block_tables.shape[1] - 1)
    return jnp.take_along_axis(block_tables, col, axis=1)


def _drop_padding(blk, n_valid, past):
    """Aim the padding tokens (i >= n_valid) of each row at block
    ``past``, outside the arena, so a ``mode="drop"`` scatter drops their
    entries."""
    if n_valid is None:
        return blk
    i = jnp.arange(blk.shape[1], dtype=jnp.int32)
    return jnp.where(i[None] < n_valid[:, None], blk, past)


# ------------------------------------------------------------ attention -----

def init_attention(pb, cfg, *, rope_scaled: bool = True, prefix: str = "attn"):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    a = pb.sub(prefix)
    a.param("wq", (D, H, hd), ("embed", "heads", "head_dim"))
    a.param("wk", (D, KV, hd), ("embed", "kv_heads", "kv_head_dim"))
    a.param("wv", (D, KV, hd), ("embed", "kv_heads", "kv_head_dim"))
    a.param("wo", (H, hd, D), ("heads", "head_dim", "embed"))
    if cfg.qkv_bias:
        a.param("bq", (H, hd), ("heads", "head_dim"), init="zeros")
        a.param("bk", (KV, hd), ("kv_heads", "kv_head_dim"), init="zeros")
        a.param("bv", (KV, hd), ("kv_heads", "kv_head_dim"), init="zeros")


def attention(p, cfg, rules, x, *, positions, causal=True, kv_x=None,
              cache=None, cache_len=None, use_rope=True,
              carried_cache=None, paged_cache=None, n_valid=None):
    """GQA attention. cache: dict(k,v) [B,S,KV,hd] for decode; kv_x for
    cross-attention (enc-dec); carried_cache: (kc, vc, layer_idx) stacked
    [L,B,S,KV,hd] buffers updated in place; paged_cache:
    (k_pages, v_pages, block_tables, layer_idx) stacked [L,NB,BS,KV,hd]
    genesys.pagedkv arenas addressed per row through block_tables [B,MB]
    with a per-row cache_len; for S > 1, n_valid [B] counts each row's
    real tokens (the rest are padding). Returns (out, new_cache)."""
    dt = x.dtype
    kv_src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if rules.kv_rep > 1:
        # Megatron-style KV replication to the TP degree: consecutive blocks
        # stay aligned with the (KV_eff, G_eff) grouping used by flash attn.
        k = jnp.repeat(k, rules.kv_rep, axis=2)
        v = jnp.repeat(v, rules.kv_rep, axis=2)
    q = constrain(q, rules, "batch", "seq", "heads", "head_dim")
    k = constrain(k, rules, "batch", "seq", "kv_heads", "kv_head_dim")
    v = constrain(v, rules, "batch", "seq", "kv_heads", "kv_head_dim")

    if use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        if cache is None:
            k = rope(k, positions, cfg.rope_theta)
        else:
            k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if paged_cache is not None and kv_x is None:
        # the genesys.pagedkv block arenas [L,NB,BS,KV,hd], updated in
        # place: the S new tokens' K/V are scattered straight into
        # arena[li, block, offset] through each row's table, then every
        # row's pages are gathered as arena[li, block_tables]; no layer
        # is sliced out or written back. Write before read, so the query
        # sees its own token through the table. Inactive batch rows carry
        # all-null block tables and cache_len 0, so their writes land in
        # the pool's null block and their outputs are garbage nobody
        # reads (slot-masked continuous batching, serving/engine.py); a
        # prompt chunk's padding (i >= n_valid) is aimed past the arena
        # and dropped. S == 1 (the decode step) attends with per-row
        # lens = cache_len + 1; S > 1 (a prompt chunk) attends each query
        # causally over its row's pages.
        kp, vp, bt, li = paged_cache
        NB, BS = kp.shape[1:3]
        k_w, v_w = k, v
        if kp.dtype == jnp.int8:
            k_w = jnp.clip(jnp.round(k * QSCALE), -127, 127).astype(jnp.int8)
            v_w = jnp.clip(jnp.round(v * QSCALE), -127, 127).astype(jnp.int8)
        blk = _drop_padding(_table_blocks(bt, positions, BS), n_valid, NB)
        off = positions % BS
        kp = kernel_ops.update_kv_buffer(kp, li, blk, off, k_w)
        vp = kernel_ops.update_kv_buffer(vp, li, blk, off, v_w)
        if x.shape[1] == 1:
            out = paged_attention_xla(q, kp, vp, li, bt, cache_len + 1)
        else:
            out = paged_chunk_attention_xla(q, kp, vp, li, bt, positions)
        new_cache = (kp, vp)
    elif carried_cache is not None and kv_x is None:
        # decode against a CARRIED stacked cache [L,B,S,KV,hd] (§Perf
        # "in-place carried KV cache"): READ the old layer slice, attend
        # the new token separately (two-part softmax), then WRITE only the
        # new token. The write operand is data-tied to the read so XLA
        # orders read-before-write and can alias the buffer in place.
        kc, vc, li = carried_cache
        zero = jnp.zeros((), jnp.int32)
        quant = kc.dtype == jnp.int8
        k_l = jax.lax.dynamic_slice(
            kc, (li, zero, zero, zero, zero), (1,) + kc.shape[1:])[0]
        v_l = jax.lax.dynamic_slice(
            vc, (li, zero, zero, zero, zero), (1,) + vc.shape[1:])[0]
        if quant:
            k_l = k_l.astype(dt) / QSCALE
            v_l = v_l.astype(dt) / QSCALE
        out = decode_attention_two_part(q, k_l, v_l, k, v, cache_len)
        # order the cache write after ALL reads (out depends on k_l and
        # v_l in full) so copy-insertion can alias the buffer in place
        tie = out[0, 0, 0, 0] * 0
        if quant:
            k_w = jnp.clip(jnp.round(k * QSCALE + tie), -127, 127
                           ).astype(jnp.int8)
            v_w = jnp.clip(jnp.round(v * QSCALE + tie), -127, 127
                           ).astype(jnp.int8)
        else:
            k_w = (k + tie).astype(kc.dtype)
            v_w = (v + tie).astype(vc.dtype)
        # per-row scatter at each row's own cache_len (rows at different
        # depths — continuous batching — write to different positions;
        # uniform rows degenerate to the old single-slice update, and
        # rows past capacity drop instead of clamp-overwriting)
        rows = jnp.arange(kc.shape[1])
        kc = kc.at[li, rows, cache_len].set(k_w[:, 0], mode="drop")
        vc = vc.at[li, rows, cache_len].set(v_w[:, 0], mode="drop")
        new_cache = (kc, vc)
    elif cache is not None and kv_x is None:
        # decode: append to cache at cache_len (per-layer slice variant)
        B = x.shape[0]
        idx = cache_len  # [B] int32, same for all batch in our serving loop
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), idx[0], axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), idx[0], axis=1)
        new_cache = {"k": k_cache, "v": v_cache}
        out = decode_attention_xla(q, k_cache, v_cache, cache_len + 1)
    elif cache is not None:  # cross-attention with precomputed cache
        out = flash_attention_xla(q, cache["k"], cache["v"], causal=False)
        new_cache = cache
    else:
        out = flash_attention_xla(q, k, v, causal=causal)
    out = constrain(out, rules, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return constrain(y, rules, "batch", "seq", "embed"), new_cache


# --------------------------------------------------- latent attention -----

def init_mla(pb, cfg, prefix: str = "attn"):
    """DeepSeek-V3 latent attention without query compression
    (``q_lora_rank`` null): ``kv_b_proj`` is kept as its key half ``wk_b``
    and its value half ``wv_b``, so decode can absorb them."""
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope_d, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    a = pb.sub(prefix)
    a.param("wq", (D, H, nope + rope_d), ("embed", "heads", "head_dim"))
    a.param("wkv_a", (D, R + rope_d), ("embed", "kv_lora"))
    a.param("kv_norm", (R,), ("kv_lora",), init="ones")
    a.param("wk_b", (R, H, nope), ("kv_lora", "heads", "head_dim"))
    a.param("wv_b", (R, H, vd), ("kv_lora", "heads", "head_dim"))
    a.param("wo", (H, vd, D), ("heads", "head_dim", "embed"))


def mla_entries(p, cfg, x, positions):
    """Each token's cache entries, shared by every head: ``latent``
    [..., kv_lora_rank], the RMS-normed latent, and ``rope``
    [..., qk_rope_head_dim], the rotated key. Two arrays, not one of
    their 576-wide concatenation: a minor dimension that is no multiple
    of 128 gives the TPU's arena another layout than the step computes
    in, and every step then converts the whole arena twice."""
    dt = x.dtype
    R = cfg.kv_lora_rank
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c = rmsnorm(kv[..., :R], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, R:], positions, cfg.rope_theta)[..., 0, :]
    return {"latent": c, "rope": k_pe}


def mla_absorbed(p, cfg, q_nope, q_pe, kv, mask):
    """Scores over the latent itself: the queries absorb ``wk_b`` and the
    latent-weighted sum leaves through ``wv_b`` (decode). q_nope
    [B,S,H,nope], q_pe [B,S,H,rope]; kv {latent [B,T,R], rope
    [B,T,rope]}; mask [B,S,T] -> [B,S,H,v]."""
    dt = q_nope.dtype
    f32 = jnp.float32
    lat = kv["latent"]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["wk_b"].astype(dt),
                       preferred_element_type=f32).astype(dt)
    s = (jnp.einsum("bshr,btr->bhst", q_lat, lat,
                    preferred_element_type=f32)
         + jnp.einsum("bshk,btk->bhst", q_pe, kv["rope"],
                      preferred_element_type=f32))
    pr = _masked_softmax(s, mask, cfg)
    o = jnp.einsum("bhst,btr->bhsr", pr.astype(dt), lat,
                   preferred_element_type=f32).astype(dt)
    return jnp.einsum("bhsr,rhv->bshv", o, p["wv_b"].astype(dt),
                      preferred_element_type=f32).astype(dt)


def mla_expanded(p, cfg, q_nope, q_pe, kv, mask):
    """The published form: every position's keys and values expanded from
    its latent through ``wk_b``/``wv_b`` (training, prompt chunks). Same
    shapes as :func:`mla_absorbed`."""
    dt = q_nope.dtype
    f32 = jnp.float32
    k = jnp.einsum("btr,rhn->bthn", kv["latent"], p["wk_b"].astype(dt))
    v = jnp.einsum("btr,rhv->bthv", kv["latent"], p["wv_b"].astype(dt))
    s = (jnp.einsum("bshn,bthn->bhst", q_nope, k, preferred_element_type=f32)
         + jnp.einsum("bshk,btk->bhst", q_pe, kv["rope"],
                      preferred_element_type=f32))
    pr = _masked_softmax(s, mask, cfg)
    return jnp.einsum("bhst,bthv->bshv", pr.astype(dt), v,
                      preferred_element_type=f32).astype(dt)


def _masked_softmax(s, mask, cfg):
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = jnp.where(mask[:, None], s * scale, -jnp.inf)
    return jax.nn.softmax(s, axis=-1)


def mla_attention(p, cfg, rules, x, *, positions, n_valid=None,
                  entries=None, block_tables=None, li=None):
    """Latent attention -> ``(out, entries')``. Without ``entries``,
    causal over the S tokens themselves (``entries'`` None). With them,
    the S new entries are first written in place at their slots of layer
    ``li``, then each query attends over its row's entries of that layer
    up to its own position: ``entries`` is :func:`mla_entries`' pair as
    paged arenas [L,NB,BS,n] addressed through ``block_tables`` [B,MB]
    (gathered at the layer, never sliced out), or, without tables, a
    carried cache [L,B,Smax,n]. Padding tokens
    (i >= n_valid) and positions past the cache are aimed out of range,
    so dropped; inactive rows write into the null block, as in the paged
    GQA branch of :func:`attention`. S == 1 (decode) runs the absorbed
    form, S > 1 the expanded one."""
    dt = x.dtype
    B, S = x.shape[:2]
    nope = cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q = constrain(q, rules, "batch", "seq", "heads", "head_dim")
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], positions,
                                       cfg.rope_theta)
    new = mla_entries(p, cfg, x, positions)                # {name: [B,S,n]}
    if entries is None:
        kv, kpos = new, positions[:, None, :]
    else:
        NB, BS = entries["latent"].shape[1:3]
        if block_tables is not None:
            blk = _table_blocks(block_tables, positions, BS)
            off = positions % BS
        else:
            # the carried cache: row b's entries are "block" b
            blk = jnp.broadcast_to(jnp.arange(B)[:, None], positions.shape)
            off = positions
        blk = _drop_padding(blk, n_valid, NB)
        # written in place before the read, so the view holds the new
        # entries (a padding token's lies past every real query anyway)
        entries = {name: kernel_ops.update_kv_buffer(a, li, blk, off,
                                                     new[name])
                   for name, a in entries.items()}
        kv = {name: (a[li] if block_tables is None else
                     _row_pages(a, li, block_tables)).astype(dt)
              for name, a in entries.items()}
        kpos = jnp.arange(kv["latent"].shape[1], dtype=jnp.int32)[None, None]
    mask = kpos <= positions[:, :, None]                   # [B,S,T]
    form = mla_absorbed if S == 1 and entries is not None else mla_expanded
    o = form(p, cfg, q_nope, q_pe, kv, mask)
    y = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))
    return constrain(y, rules, "batch", "seq", "embed"), entries


# ------------------------------------------------------------------ mlp -----

def init_mlp(pb, cfg, d_ff=None, prefix: str = "mlp"):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    m = pb.sub(prefix)
    if cfg.mlp_kind == "swiglu":
        m.param("wi_gate", (D, F), ("embed", "mlp"))
    m.param("wi_up", (D, F), ("embed", "mlp"))
    m.param("wo", (F, D), ("mlp", "embed"))


def mlp(p, rules, x):
    dt = x.dtype
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(dt))
    if "wi_gate" in p:   # swiglu
        g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(dt))
        h = jax.nn.silu(g) * u
    else:                # gelu 2-matrix (starcoder2 / seamless)
        h = jax.nn.gelu(u)
    h = constrain(h, rules, "batch", "seq", "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))
    return constrain(y, rules, "batch", "seq", "embed")
