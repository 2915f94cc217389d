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


DENSE_STACK = 0x0DE5E     # the leading dense layers' key, apart from blocks


def init(rng, cfg: ModelConfig):
    pb = ParamBuilder(rng, jnp.dtype(cfg.params_dtype))
    pb.param("embed", (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
             scale=1.0)

    def attn_and_norms(lpb: ParamBuilder):
        if cfg.mla:
            L.init_mla(lpb, cfg)
        else:
            L.init_attention(lpb, cfg)
        lpb.param("ln_attn", (cfg.d_model,), ("embed",), init="ones")
        lpb.param("ln_mlp", (cfg.d_model,), ("embed",), init="ones")

    def one(lpb: ParamBuilder, i: int):
        attn_and_norms(lpb)
        if cfg.family == Family.MOE:
            MOE.init_moe(lpb, cfg)
        else:
            L.init_mlp(lpb, cfg)

    def dense(lpb: ParamBuilder, i: int):
        attn_and_norms(lpb)
        L.init_mlp(lpb, cfg, d_ff=cfg.dense_d_ff)

    dt = jnp.dtype(cfg.params_dtype)
    if cfg.n_dense_layers:
        pb.params["dense_blocks"], pb.axes["dense_blocks"] = stack_layers(
            jax.random.fold_in(rng, DENSE_STACK), dt, cfg.n_dense_layers,
            dense)
    blocks, blocks_axes = stack_layers(
        rng, dt, cfg.n_layers - cfg.n_dense_layers, one)
    pb.params["blocks"] = blocks
    pb.axes["blocks"] = blocks_axes
    pb.param("final_norm", (cfg.d_model,), ("embed",), init="ones")
    if not cfg.tie_embeddings:
        pb.param("lm_head", (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))
    return pb.params, pb.axes


def _stacks(params, cfg):
    """Each scanned stack of layers and the index of its first layer: the
    leading dense layers (MoE family), then the uniform ``blocks``."""
    out = [(params["dense_blocks"], 0)] if cfg.n_dense_layers else []
    return out + [(params["blocks"], cfg.n_dense_layers)]


def _block(cfg, rules, p, x, *, positions, cache_len=None, entries=None,
           block_tables=None, li=None, n_valid=None, moe_rows=None):
    """One layer. ``entries`` is the family's cache (:func:`init_cache`'s
    or :func:`init_paged_arena`'s dict), paged when ``block_tables`` is
    given; ``li`` its layer. -> (x, entries', hits or None)."""
    h = L.rmsnorm(x, p["ln_attn"], cfg.norm_eps)
    if cfg.mla:
        h, entries = L.mla_attention(
            p["attn"], cfg, rules, h, positions=positions, n_valid=n_valid,
            entries=entries, block_tables=block_tables, li=li)
    else:
        carried = paged = None
        if entries is not None and block_tables is not None:
            paged = (entries["k"], entries["v"], block_tables, li)
        elif entries is not None:
            carried = (entries["k"], entries["v"], li)
        h, kv = L.attention(
            p["attn"], cfg, rules, h, positions=positions,
            cache_len=cache_len, carried_cache=carried, paged_cache=paged,
            n_valid=n_valid)
        entries = None if kv is None else {"k": kv[0], "v": kv[1]}
    x = x + h
    hits = None
    h = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
    if "moe" in p:
        y, hits = MOE.moe_mlp(p, cfg, rules, h, dropless=entries is not None,
                              rows=moe_rows)
    else:
        y = L.mlp(p["mlp"], rules, h)
    return x + y, entries, hits


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, cfg: ModelConfig, rules, tokens, *, embeds=None,
            positions=None, cache=None, cache_len=None, paged_cache=None,
            n_valid=None, moe_rows=None):
    """tokens: [B,S] int32. embeds: [B,P,D] precomputed prefix (VLM stub).
    cache: the carried stacked cache (:func:`init_cache`) for decode.
    paged_cache: (arenas, block_tables): the family's arenas
    (:func:`init_paged_arena`) shared by all sequences and per-row block
    tables [B,MB] + cache_len [B] (genesys.pagedkv continuous batching);
    with S > 1, n_valid [B] counts each row's real tokens. Returns
    (logits, cache'); with ``moe_rows`` [B] bool (a cached MoE forward)
    also the expert layers' hits [MoE layers, held experts] int32 over
    those rows' tokens.
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

    new_cache = hits = None
    if paged_cache is not None or cache is not None:
        # every layer reads/writes its slice of the stacked cache, carried
        # through the scan; paged, through the SAME per-sequence block
        # table (a block id is valid at every layer). Every stack indexes
        # the one cache by its layers' own indices.
        entries, bt = paged_cache if paged_cache is not None else (cache,
                                                                   None)
        for stack, first in _stacks(params, cfg):
            def body(carry, z):
                h, ent = carry
                h, ent, hit = _block(cfg, rules, z["p"], h,
                                     positions=positions, cache_len=cache_len,
                                     entries=ent, block_tables=bt, li=z["i"],
                                     n_valid=n_valid, moe_rows=moe_rows)
                return (h, ent), hit
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            xs = {"p": stack, "i": first + jnp.arange(n, dtype=jnp.int32)}
            (x, entries), hit = jax.lax.scan(body, (x, entries), xs)
            hits = hit if hit is not None else hits
        new_cache = entries
    else:
        def body(h, layer):
            h, _, _ = _block(cfg, rules, layer, h, positions=positions)
            return h, None
        body = _remat(body, cfg)
        for stack, _ in _stacks(params, cfg):
            x, _ = jax.lax.scan(body, x, stack)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))
    logits = constrain(logits, rules, "batch", "seq", "vocab")
    if moe_rows is not None:
        return logits.astype(jnp.float32), new_cache, hits
    return logits.astype(jnp.float32), new_cache


def _entry_shapes(cfg: ModelConfig, kv_rep: int = 1) -> dict:
    """The family's cache entries, each per token and layer: one K and
    one V of [KV, hd], or MLA's latent [kv_lora_rank] and rotated key
    [qk_rope_head_dim] (:func:`layers.mla_entries`)."""
    if cfg.mla:
        return {"latent": (cfg.kv_lora_rank,),
                "rope": (cfg.qk_rope_head_dim,)}
    one = (cfg.n_kv_heads * kv_rep, cfg.hd)
    return {"k": one, "v": one}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               kv_rep: int = 1):
    """The carried decode cache: {entry: [L, B, S, ...]}."""
    dtype = dtype or jnp.dtype(cfg.kv_cache_dtype)
    return {name: jnp.zeros((cfg.n_layers, batch, max_len) + shape, dtype)
            for name, shape in _entry_shapes(cfg, kv_rep).items()}


def init_paged_arena(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=None, kv_rep: int = 1):
    """Block arenas for paged decode: {entry: [L, NB, BS, ...]}, the
    family's entries ({k,v: [L, NB, BS, KV, hd]}, or MLA's {latent:
    [L, NB, BS, kv_lora_rank], rope: [L, NB, BS, qk_rope_head_dim]}).
    One arena serves every concurrent sequence; block 0 is the pool's
    null block (padding target for inactive rows / short tables)."""
    dtype = dtype or jnp.dtype(cfg.kv_cache_dtype)
    return {name: jnp.zeros((cfg.n_layers, n_blocks, block_size) + shape,
                            dtype)
            for name, shape in _entry_shapes(cfg, kv_rep).items()}


def cache_axes(cfg: ModelConfig):
    if cfg.mla:
        ax = ("stack", "batch", "seq", "kv_lora")
        return {"latent": ax, "rope": ax}
    ax = ("stack", "batch", "seq", "kv_heads", "kv_head_dim")
    return {"k": ax, "v": ax}
