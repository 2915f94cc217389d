"""Operations and bytes that a decode step of a dense GQA transformer
needs, from the configuration's shapes and the tokens the step really
processed: what the algorithm requires, whatever implements it.

A step processes one token for each active slot. The token at position
``p`` attends over ``p + 1`` positions. Inactive slots do no useful work
and count nothing.
"""
from __future__ import annotations

from weights import Dims

BF16 = 2


def layer_matmul_params(m: Dims) -> int:
    attn = 2 * m.d * m.heads * m.hd + 2 * m.d * m.kv_heads * m.hd
    return attn + (3 if m.gated else 2) * m.d * m.ff


def token_flops(m: Dims, context: int) -> int:
    """One token attending over ``context`` positions: two operations per
    weight of every matrix it passes, the output head over the published
    vocabulary, and ``q.k`` plus ``p.v`` over the context in each layer."""
    per_layer = 2 * layer_matmul_params(m) + 4 * m.heads * m.hd * context
    return m.layers * per_layer + 2 * m.d * m.vocab


def kv_bytes_per_position(m: Dims, itemsize: int = BF16) -> int:
    return m.layers * 2 * m.kv_heads * m.hd * itemsize


def weight_bytes(m: Dims, itemsize: int = BF16) -> int:
    """Every weight read once per step: the layers' matrices and norms,
    the final norm and the output head (published vocabulary)."""
    layers = m.layers * (layer_matmul_params(m) + 2 * m.d)
    return (layers + m.d + m.d * m.vocab) * itemsize


def step_flops(m: Dims, contexts) -> int:
    """``contexts``: per active slot, the positions its token attends."""
    return sum(token_flops(m, int(c)) for c in contexts)


def step_bytes(m: Dims, contexts, itemsize: int = BF16) -> int:
    """Weights once, each active token's embedding row, the live KV it
    reads (its ``context - 1`` earlier positions) and the KV it writes."""
    kv = kv_bytes_per_position(m, itemsize)
    n = len(contexts)
    read = sum(int(c) - 1 for c in contexts) * kv
    return (weight_bytes(m, itemsize) + n * m.d * itemsize + read + n * kv)
