"""Pallas grouped (ragged) matmul for MoE expert FFNs.

Tokens arrive sorted by expert with every group padded to a multiple of the
token tile (ops.py does the sort/pad), so each [tm, D] token tile belongs to
exactly one expert. The expert id per tile rides in scalar-prefetch memory
(SMEM) and drives the weight BlockSpec index map — each grid step streams
one (tm x tk) token tile against the owning expert's (tk x tn) weight tile,
accumulating over the K grid dimension in VMEM scratch.

This is the sort-based alternative to the GShard one-hot dispatch einsum in
repro.models.moe (which burns ~2x capacity x d_model FLOPs on dispatch);
used by the §Perf MoE hillclimb. Oracle: ref.gmm_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import resolve_interpret


def _kernel(eids_ref, x_ref, w_ref, o_ref, acc, *, nk: int):
    kdim = pl.program_id(2)

    @pl.when(kdim == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kdim == nk - 1)
    def _fin():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def gmm(x, w, tile_expert, *, tile_m: int = 128, tile_k: int = 128,
        tile_n: int = 128, interpret: bool | None = None):
    """x [T, D] (sorted/padded by expert); w [E, D, F];
    tile_expert [T // tile_m] int32 -> out [T, F]."""
    T, D = x.shape
    E, _, F = w.shape
    tm = min(tile_m, T)
    tk = min(tile_k, D)
    tn = min(tile_n, F)
    assert T % tm == 0 and D % tk == 0 and F % tn == 0
    nm, nk, nn = T // tm, D // tk, F // tn
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda m, n, k, eids: (m, k)),
            pl.BlockSpec((1, tk, tn), lambda m, n, k, eids: (eids[m], k, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda m, n, k, eids: (m, n)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        interpret=resolve_interpret(interpret),
    )(tile_expert, x, w)
