"""Weights made from ``--seed``, the same for the system under test and
for the plain reference, which regenerates them layer by layer.

What is shared by every architecture: the key a seed gives, and the
seeded normal draw each module makes its weights from. The tree and the
layout are the architecture module's (``arch/<arch>.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (64-bit and larger too)."""
    word = np.random.SeedSequence(int(seed) % 2**128).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) >> 1)


def normal(key, shape, fan_in, dtype):
    """A normal draw scaled by ``fan_in ** -0.5``, in ``dtype``."""
    x = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return x.astype(dtype)


def make_program_params(arch, m, seed: int, dtype=jnp.bfloat16) -> dict:
    """One jitted call on the device makes every weight from ``seed``."""
    return jax.jit(lambda k: arch.program_params(m, k, dtype))(base_key(seed))
