"""The dense architecture's reference regenerates exactly the weights the
program serves."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import weights as W
from arch import dense

BENCH = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("config", ["internlm2-20b", "starcoder2-7b"])
def test_layer_by_layer_equals_the_stacked_tree(config):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, vocab_size=300, num_hidden_layers=3,
               head_dim=16)
    m = dense.dims(cfg)
    seed = 2**31 + 11
    tree = W.make_program_params(dense, m, seed)
    key = W.base_key(seed)
    for i in range(m.layers):
        one = dense.one_layer(m, key, i, jnp.bfloat16)
        for (group, name), v in one.items():
            assert np.array_equal(np.asarray(tree["blocks"][group][name][i]),
                                  np.asarray(v)), (group, name, i)
    assert np.array_equal(np.asarray(tree["embed"]),
                          np.asarray(dense.embedding(m, key, jnp.bfloat16)))
    assert np.array_equal(np.asarray(tree["lm_head"]),
                          np.asarray(dense.head(m, key, jnp.bfloat16)))
    assert ("mlp", "wi_gate") in one if m.gated else \
        ("mlp", "wi_gate") not in one
    # padded vocabulary rows and columns are zero
    assert not np.asarray(tree["embed"])[m.vocab:].any()
    assert not np.asarray(tree["lm_head"])[:, m.vocab:].any()


def test_seeds_of_any_size_give_distinct_keys():
    keys = {tuple(np.asarray(W.base_key(s)).tolist())
            for s in (0, 1, 2**31 - 1, 2**31, 2**32 + 1, 2**63 + 5)}
    assert len(keys) == 6
