"""Pallas chunked SSD (Mamba2) scan.

Grid (B, H, n_chunks); the chunk dimension is innermost with arbitrary
semantics — the [N, P] recurrent state lives in VMEM scratch across chunks.
Per-chunk work is all (C x C)/(C x N)/(C x P) matmuls with C=64..128,
N=P=64: the full working set (~6 tiles * 64KB) stays inside VMEM, and the
intra-chunk decay matrix is never materialized in HBM (the XLA reference
materializes it per chunk — this kernel is why the hybrid archs' memory
term drops).

Oracle: repro.models.mamba2.ssd_chunked (also validated against the pure
recurrence in tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import resolve_interpret


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, state, *,
            chunk: int, nc: int):
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, :, 0].astype(jnp.float32)            # [C, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # [C]
    A = a_ref[0]                                      # scalar (per head)
    Bm = b_ref[0].astype(jnp.float32)                 # [C, N]
    Cm = c_ref[0].astype(jnp.float32)                 # [C, N]

    dA = dt * A                                       # [C], negative
    dA_cs = jnp.cumsum(dA)                            # [C]
    # intra-chunk decay L_ij = exp(cs_i - cs_j) for j <= i
    diff = dA_cs[:, None] - dA_cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lm = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    xdt = x * dt[:, None]                             # [C, P]
    y = jax.lax.dot_general(scores * Lm, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # cross-chunk: y += exp(cs) * C @ state_prev
    y += jnp.exp(dA_cs)[:, None] * jax.lax.dot_general(
        Cm, state[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # state update
    decay = jnp.exp(dA_cs[-1] - dA_cs)                # [C]
    upd = jax.lax.dot_general(Bm, xdt * decay[:, None],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [N, P]
    state[...] = jnp.exp(dA_cs[-1]) * state[...] + upd
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(z == nc - 1)
    def _fin():
        sfin_ref[0, 0] = state[...].astype(sfin_ref.dtype)


def mamba2_ssd(x, dt, A, Bm, Cm, *, chunk: int = 64,
               interpret: bool | None = None):
    """x [B,L,H,P]; dt [B,L,H]; A [H]; Bm,Cm [B,L,N]
    -> (y [B,L,H,P], state [B,H,N,P])."""
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    c = min(chunk, L)
    nc = L // c
    assert nc * c == L, (L, c)
    y, sfin = pl.pallas_call(
        functools.partial(_kernel, chunk=c, nc=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, c, 1, P), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, c, 1), lambda b, h, z: (b, z, h)),
            pl.BlockSpec((1,), lambda b, h, z: (h,)),
            pl.BlockSpec((1, c, N), lambda b, h, z: (b, z, 0)),
            pl.BlockSpec((1, c, N), lambda b, h, z: (b, z, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, 1, P), lambda b, h, z: (b, z, h, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, z: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, A.astype(jnp.float32), Bm, Cm)
    return y, sfin
