"""train_step / prefill_step / serve_step builders — the functions the
launcher jits, shards and dry-runs for every (arch x shape) cell.

Batch dict convention:
  tokens  [B, S_text] int32        (always)
  labels  [B, S_text] int32        (train; -100 = masked)
  embeds  [B, P, D]   compute_dtype (vlm patch / audio frame stub, optional)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, Family, TrainConfig
from repro.models.registry import get_api
from repro.optim import AdamW
from repro.serving.pagedkv import NULL_BLOCK

IGNORE = -100


def cross_entropy(logits, labels, z_loss: float = 1e-4):
    """logits [B,S,V] f32; labels [B,S] with IGNORE masking."""
    mask = (labels != IGNORE)
    labels_safe = jnp.where(mask, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    zl = z_loss * jnp.square(lse) * mask
    denom = jnp.maximum(mask.sum(), 1)
    return (nll + zl).sum() / denom, nll.sum() / denom


def _call_forward(params, cfg, rules, batch, **kw):
    api = get_api(cfg)
    if cfg.family == Family.ENCDEC:
        return api.forward(params, cfg, rules, batch["tokens"],
                           frames=batch.get("embeds"), **kw)
    return api.forward(params, cfg, rules, batch["tokens"],
                       embeds=batch.get("embeds"), **kw)


def loss_fn(params, cfg: ModelConfig, rules, batch, tc: TrainConfig):
    logits, _ = _call_forward(params, cfg, rules, batch)
    labels = batch["labels"]
    if cfg.family == Family.VLM and batch.get("embeds") is not None:
        # loss only on text positions: logits cover [patch; text]
        logits = logits[:, batch["embeds"].shape[1]:]
    loss, nll = cross_entropy(logits, labels, tc.z_loss)
    return loss, {"nll": nll}


def make_train_step(cfg: ModelConfig, rules, tc: TrainConfig):
    opt = AdamW(lr=tc.lr, beta1=tc.beta1, beta2=tc.beta2,
                weight_decay=tc.weight_decay, grad_clip=tc.grad_clip)

    def train_step(params, opt_state, batch):
        if tc.microbatches > 1:
            def micro(g_acc, mb):
                (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, cfg, rules, mb, tc)
                return jax.tree_util.tree_map(jnp.add, g_acc, g), (l, aux)
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(tc.microbatches,
                                    x.shape[0] // tc.microbatches,
                                    *x.shape[1:]), batch)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, auxes) = jax.lax.scan(micro, g0, mbs)
            grads = jax.tree_util.tree_map(
                lambda g: g / tc.microbatches, grads)
            loss = losses.mean()
            aux = jax.tree_util.tree_map(jnp.mean, auxes)
        else:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, cfg, rules, batch, tc)
        new_params, new_state, gnorm = opt.update(grads, opt_state, params)
        metrics = {"loss": loss, "grad_norm": gnorm, **aux}
        return new_params, new_state, metrics

    return train_step, opt


def make_prefill_step(cfg: ModelConfig, rules):
    def prefill_step(params, batch):
        logits, _ = _call_forward(params, cfg, rules, batch)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return logits, next_tok
    return prefill_step


def make_serve_step(cfg: ModelConfig, rules):
    """One decode step: (params, cache, token [B,1], cache_len [B]) ->
    (next_token [B], new_cache)."""
    api = get_api(cfg)

    def serve_step(params, cache, token, cache_len, enc_out=None):
        kw = dict(cache=cache, cache_len=cache_len)
        if cfg.family == Family.ENCDEC:
            logits, new_cache = api.forward(params, cfg, rules, token,
                                            enc_out=enc_out, **kw)
        else:
            logits, new_cache = api.forward(params, cfg, rules, token, **kw)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    return serve_step


def _paged_api(cfg: ModelConfig):
    if cfg.family not in (Family.DENSE, Family.MOE, Family.VLM):
        raise ValueError(
            f"the paged KV arena supports transformer-family archs, not "
            f"{cfg.family} (SSM/hybrid state is not block-addressable)")
    return get_api(cfg)


def make_paged_serve_step(cfg: ModelConfig, rules):
    """One paged decode step over the genesys.pagedkv arena:
    (params, arenas, block_tables [B,MB], token [B,1], cache_len [B]) ->
    (next_token [B], new_arenas), where ``arenas`` holds the family's
    entries (``transformer.init_paged_arena``: {k,v: [L,NB,BS,KV,hd]} for
    GQA, {latent: [L,NB,BS,R], rope: [L,NB,BS,r]} for MLA). An MoE step
    also returns ``hits`` [MoE layers, held experts] int32: how many
    seated rows (a row whose block table is not all null) chose each held
    expert.

    The batch shape is the engine's FIXED slot count — admitting or
    retiring a request changes only block_tables/cache_len row contents,
    never an array shape, so membership churn cannot trigger a re-jit.
    """
    api = _paged_api(cfg)
    moe = cfg.family == Family.MOE

    def paged_serve_step(params, arenas, block_tables, token, cache_len):
        kw = {"moe_rows": block_tables[:, 0] != NULL_BLOCK} if moe else {}
        logits, new_arenas, *hits = api.forward(
            params, cfg, rules, token, paged_cache=(arenas, block_tables),
            cache_len=cache_len, **kw)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (next_tok, new_arenas, *hits)

    return paged_serve_step


def make_paged_prefill_chunk(cfg: ModelConfig, rules):
    """Write one prompt chunk's entries into the genesys.pagedkv arena:
    (params, arenas (the family's, as the decode step's), block_row
    [1,MB], tokens [1,C], start [1], n_valid [1]) -> new_arenas.

    The C tokens sit at positions start + arange(C) of the row; the first
    n_valid are the prompt's, the rest padding whose writes are dropped.
    Each attends causally over the row's pages, so the entries it leaves
    equal what feeding the tokens one decode step at a time writes. It
    returns no logits: under jit the unused final norm and output head
    are dead code. One fixed shape, so the engine compiles it once.
    """
    api = _paged_api(cfg)

    def paged_prefill_chunk(params, arenas, block_row, tokens, start,
                            n_valid):
        _, new_arenas = api.forward(
            params, cfg, rules, tokens, paged_cache=(arenas, block_row),
            cache_len=start, n_valid=n_valid)
        return new_arenas

    return paged_prefill_chunk
