"""Model-family registry: family -> (init, forward, init_cache, cache_axes).

VLM (llava-next) reuses the dense transformer with a precomputed patch-embed
prefix (modality frontend stubbed per assignment); audio enc-dec (seamless)
takes precomputed frame embeddings.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax

from repro.config import ModelConfig, Family
from repro.models import transformer, mamba2, rwkv6, encdec


@dataclass(frozen=True)
class ModelApi:
    init: Callable
    forward: Callable
    init_cache: Callable
    cache_axes: Callable


_BY_FAMILY = {
    Family.DENSE: ModelApi(transformer.init, transformer.forward,
                           transformer.init_cache, transformer.cache_axes),
    Family.MOE: ModelApi(transformer.init, transformer.forward,
                         transformer.init_cache, transformer.cache_axes),
    Family.VLM: ModelApi(transformer.init, transformer.forward,
                         transformer.init_cache, transformer.cache_axes),
    Family.HYBRID: ModelApi(mamba2.init, mamba2.forward,
                            mamba2.init_cache, mamba2.cache_axes),
    Family.SSM: ModelApi(rwkv6.init, rwkv6.forward,
                         rwkv6.init_cache, rwkv6.cache_axes),
    Family.ENCDEC: ModelApi(encdec.init, encdec.forward,
                            encdec.init_cache, encdec.cache_axes),
}


def get_api(cfg: ModelConfig) -> ModelApi:
    return _BY_FAMILY[cfg.family]


def init_params(cfg: ModelConfig, rng, *, mesh=None, rules=None):
    """``cfg``'s params in ``cfg.params_dtype``, made by ONE jitted init.

    Under jit XLA writes each stacked leaf of ``stack_layers`` in place,
    so the per-layer arrays never sit on the device beside the stacked
    copy, and a bf16 config never holds an f32 copy. With ``mesh`` and
    ``rules`` every leaf is placed by its logical axes (data-parallel
    replicas, tensor-parallel shards); without them it lands on the
    default device."""
    api = get_api(cfg)
    box = {}

    def init(r):
        params, box["axes"] = api.init(r, cfg)
        return params

    out_shardings = None
    if mesh is not None:
        from repro.sharding import tree_shardings
        shapes = jax.eval_shape(init, rng)
        out_shardings = tree_shardings(mesh, rules, box["axes"], shapes)
    return jax.jit(init, out_shardings=out_shardings)(rng)
