"""Pallas chunked wkv6 (RWKV-6 "Finch") scan.

Grid (B, H, n_chunks), chunk innermost (arbitrary) with the [hd, hd]
recurrent state in VMEM scratch. Per chunk: cumulative log-decay, a
strictly-lower-triangular (C x C) intra-chunk attention-like product, the
bonus diagonal, and the cross-chunk state term — everything tiles in VMEM
(C=64, hd=64: ~128KB working set).

Oracle: repro.models.rwkv6.wkv6_chunked (validated against the pure
recurrence in tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sfin_ref, state, *,
            chunk: int, nc: int):
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0, :, 0].astype(jnp.float32)            # [C, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)
    w = w_ref[0, :, 0].astype(jnp.float32)
    u = u_ref[0, 0].astype(jnp.float32)               # [hd]

    lw = jnp.log(jnp.clip(w, 1e-6, 1.0))
    lw_cs = jnp.cumsum(lw, axis=0)                    # [C, hd] inclusive
    lw_prev = lw_cs - lw                              # exclusive cumsum
    ri = r * jnp.exp(lw_prev)                         # r_t * W_{t-1}
    ki = k * jnp.exp(-lw_cs)                          # k_s / W_s
    att = jax.lax.dot_general(ri, ki, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [C,C]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(ii > jj, att, 0.0)                # strictly lower
    bonus = jnp.sum(r * u[None, :] * k, axis=1)       # [C]
    o = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o += bonus[:, None] * v
    o += jax.lax.dot_general(ri, state[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    w_tot = jnp.exp(lw_cs[-1])                        # [hd]
    k_scaled = k * jnp.exp(lw_cs[-1][None, :] - lw_cs)
    upd = jax.lax.dot_general(k_scaled, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state[...] = state[...] * w_tot[:, None] + upd
    o_ref[0, :, 0] = o.astype(o_ref.dtype)

    @pl.when(z == nc - 1)
    def _fin():
        sfin_ref[0, 0] = state[...].astype(sfin_ref.dtype)


def rwkv6_wkv(r, k, v, w, u, *, chunk: int = 64,
              interpret: bool | None = None):
    """r,k,v,w [B,L,H,hd] (w in (0,1)); u [H,hd]
    -> (o [B,L,H,hd], state [B,H,hd,hd])."""
    B, L, H, hd = r.shape
    c = min(chunk, L)
    nc = L // c
    assert nc * c == L
    o, sfin = pl.pallas_call(
        functools.partial(_kernel, chunk=c, nc=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, c, 1, hd), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, c, 1, hd), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, c, 1, hd), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, c, 1, hd), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, h, z: (0, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, 1, hd), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, z: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u[None])
    return o, sfin
