"""jit'd public wrappers around the Pallas kernels.

flash_attention carries a custom_vjp wired to the Pallas backward kernels,
so models can switch between the XLA reference path and the kernel path
with cfg.use_pallas. moe_gmm_apply does the sort/pad/tile bookkeeping for
the grouped matmul.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import mamba2_scan as _ms
from repro.kernels import rwkv6_scan as _rs
from repro.kernels import moe_gmm as _gm


# ------------------------------------------------- flash attention op ------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=None):
    o, _ = _fa.flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
    return o


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _fa.flash_attention_fwd(q, k, v, causal=causal,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _fa.flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def decode_attention(q, k, v, lens, *, block_k=512, interpret=None):
    """``interpret=None`` auto-selects from the JAX backend (compiled on
    TPU, interpreter elsewhere) — see decode_attention.default_interpret.
    Pass an explicit bool to override."""
    return _da.decode_attention(q, k, v, lens, block_k=block_k,
                                interpret=interpret)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lens, *,
                           n_splits=4, interpret=None):
    """Split-KV flash-decode through block tables (genesys.pagedkv).

    q [B,H,hd]; k_pages/v_pages [NB,BS,KV,hd]; block_tables [B,MB] int32;
    lens [B] -> [B,H,hd]. Long contexts parallelize over ``n_splits``
    partial reductions merged by one cross-split log-sum-exp.
    """
    return _da.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                      lens, n_splits=n_splits,
                                      interpret=interpret)


def update_kv_buffer(pages, layer, blocks, offsets, new):
    """Paged KV-cache append (lite_llama's ``update_kv_buffer`` surface):
    write new entries in place into one layer of a stacked block arena.

    pages [L,NB,BS,...]; layer a scalar; blocks/offsets [B,S] int32, the
    block and the offset within it of each new entry; new [B,S,...]. Only
    those entries change, with no slice of the layer made. Multiple
    entries may only alias a slot inside the pool's null block (inactive
    batch rows), where any write order is acceptable; an entry aimed out
    of range (a block past the arena) is dropped.
    """
    return pages.at[layer, blocks, offsets].set(new.astype(pages.dtype),
                                                 mode="drop")


def mamba2_ssd(x, dt, A, Bm, Cm, *, chunk=64, interpret=None):
    return _ms.mamba2_ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def rwkv6_wkv(r, k, v, w, u, *, chunk=64, interpret=None):
    return _rs.rwkv6_wkv(r, k, v, w, u, chunk=chunk, interpret=interpret)


# ---------------------------------------------------- grouped matmul -------

def moe_gmm_apply(x, w, expert_of_token, *, n_experts: int, tile_m=128,
                  interpret=None):
    """Ragged expert matmul with host-free sort/pad bookkeeping.

    x [T, D]; w [E, D, F]; expert_of_token [T] int32 -> [T, F] aligned with
    the INPUT token order (unsorted on return).
    """
    T, D = x.shape
    E, _, F = w.shape
    order = jnp.argsort(expert_of_token)
    xs = x[order]
    sorted_eids = expert_of_token[order]
    group_sizes = jnp.bincount(expert_of_token, length=n_experts)

    # pad every group to a tile_m multiple by scattering rows into slots
    padded_group = ((group_sizes + tile_m - 1) // tile_m) * tile_m
    starts = jnp.cumsum(padded_group) - padded_group
    Tp = int(((T + tile_m - 1) // tile_m + n_experts) * tile_m)
    rank_in_group = jnp.arange(T) - (
        jnp.cumsum(group_sizes) - group_sizes)[sorted_eids]
    slot = starts[sorted_eids] + rank_in_group
    xp = jnp.zeros((Tp, D), x.dtype).at[slot].set(xs)
    # expert id of each tile: tile t belongs to expert e iff
    # starts[e] <= t*tile_m < starts[e] + padded_group[e]
    tile_idx = jnp.arange(Tp // tile_m) * tile_m
    tile_eids = jnp.searchsorted(jnp.cumsum(padded_group), tile_idx,
                                 side="right").astype(jnp.int32)
    tile_eids = jnp.clip(tile_eids, 0, E - 1)

    out_p = _gm.gmm(xp, w, tile_eids, tile_m=tile_m, interpret=interpret)
    out_sorted = out_p[slot]
    inv = jnp.argsort(order)
    return out_sorted[inv]
