"""Dense decoder-only transformer LM (qwen2 / internlm2 / deepseek /
starcoder2 / llava backbone), with scan-over-layers and remat.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, Family
from repro.models.module import ParamBuilder, stack_layers
from repro.models import layers as L
from repro.models import moe as MOE
from repro.sharding import constrain


def init(rng, cfg: ModelConfig):
    pb = ParamBuilder(rng, jnp.dtype(cfg.params_dtype))
    pb.param("embed", (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
             scale=1.0)
    def one(lpb: ParamBuilder, i: int):
        L.init_attention(lpb, cfg)
        if cfg.family == Family.MOE:
            MOE.init_moe(lpb, cfg)
        else:
            L.init_mlp(lpb, cfg)
        lpb.param("ln_attn", (cfg.d_model,), ("embed",), init="ones")
        lpb.param("ln_mlp", (cfg.d_model,), ("embed",), init="ones")
    blocks, blocks_axes = stack_layers(rng, jnp.dtype(cfg.params_dtype),
                                       cfg.n_layers, one)
    pb.params["blocks"] = blocks
    pb.axes["blocks"] = blocks_axes
    pb.param("final_norm", (cfg.d_model,), ("embed",), init="ones")
    if not cfg.tie_embeddings:
        pb.param("lm_head", (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))
    return pb.params, pb.axes


def _block(cfg, rules, p, x, *, positions, cache=None, cache_len=None,
           carried_cache=None, paged_cache=None, n_valid=None):
    h, new_cache = L.attention(
        p["attn"], cfg, rules, L.rmsnorm(x, p["ln_attn"]),
        positions=positions, cache=cache, cache_len=cache_len,
        carried_cache=carried_cache, paged_cache=paged_cache,
        n_valid=n_valid)
    x = x + h
    if cfg.family == Family.MOE:
        x = x + MOE.moe_mlp(p, cfg, rules, L.rmsnorm(x, p["ln_mlp"]))
    else:
        x = x + L.mlp(p["mlp"], rules, L.rmsnorm(x, p["ln_mlp"]))
    return x, new_cache


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, cfg: ModelConfig, rules, tokens, *, embeds=None,
            positions=None, cache=None, cache_len=None, paged_cache=None,
            n_valid=None):
    """tokens: [B,S] int32. embeds: [B,P,D] precomputed prefix (VLM stub).
    cache: stacked {k,v: [L,B,S,KV,hd]} for decode. paged_cache:
    (k_pages, v_pages, block_tables) with arenas [L,NB,BS,KV,hd] shared by
    all sequences and per-row block tables [B,MB] + cache_len [B]
    (genesys.pagedkv continuous batching); with S > 1, n_valid [B] counts
    each row's real tokens. Returns (logits, cache').
    """
    dt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(dt)[tokens]
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(dt), x], axis=1)
    B, S, _ = x.shape
    if positions is None:
        if cache_len is not None:
            positions = cache_len[:, None] + jnp.arange(S, dtype=jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                         (B, S))
    x = constrain(x, rules, "batch", "seq", "embed")

    if paged_cache is not None:
        # paged decode: every layer reads/writes its slice of the shared
        # block arenas through the SAME per-sequence block table (a block
        # id is valid at every layer — one table per sequence, not per
        # layer), carried through the scan like the dense stacked cache
        kp0, vp0, bt = paged_cache

        def body(carry, z):
            h, kp, vp = carry
            h, (kp, vp) = _block(cfg, rules, z["p"], h, positions=positions,
                                 paged_cache=(kp, vp, bt, z["i"]),
                                 cache_len=cache_len, n_valid=n_valid)
            return (h, kp, vp), None
        xs = {"p": params["blocks"],
              "i": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
        (x, kp, vp), _ = jax.lax.scan(body, (x, kp0, vp0), xs)
        new_cache = {"k": kp, "v": vp}
    elif cache is not None:
        # carried stacked cache: in-place single-token updates (§Perf)
        def body(carry, z):
            h, kc, vc = carry
            h, (kc, vc) = _block(cfg, rules, z["p"], h, positions=positions,
                                 carried_cache=(kc, vc, z["i"]),
                                 cache_len=cache_len)
            return (h, kc, vc), None
        xs = {"p": params["blocks"],
              "i": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
        (x, kc, vc), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]), xs)
        new_cache = {"k": kc, "v": vc}
    else:
        def body(h, layer):
            h, _ = _block(cfg, rules, layer, h, positions=positions)
            return h, None
        body = _remat(body, cfg)
        x, _ = jax.lax.scan(body, x, params["blocks"])
        new_cache = None

    x = L.rmsnorm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))
    logits = constrain(logits, rules, "batch", "seq", "vocab")
    return logits.astype(jnp.float32), new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               kv_rep: int = 1):
    dtype = dtype or jnp.dtype(cfg.kv_cache_dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads * kv_rep, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_arena(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=None, kv_rep: int = 1):
    """Block arenas for paged decode: {k,v: [L, NB, BS, KV, hd]}. One
    arena serves every concurrent sequence; block 0 is the pool's null
    block (padding target for inactive rows / short tables)."""
    dtype = dtype or jnp.dtype(cfg.kv_cache_dtype)
    shape = (cfg.n_layers, n_blocks, block_size,
             cfg.n_kv_heads * kv_rep, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_axes(cfg: ModelConfig):
    ax = ("stack", "batch", "seq", "kv_heads", "kv_head_dim")
    return {"k": ax, "v": ax}
