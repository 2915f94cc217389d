"""Per-arch reduced-config smoke tests + model math equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import Family, TrainConfig
from repro.configs import all_arch_ids, get_config
from repro.models.registry import get_api
from repro.models.module import count_params
from repro.sharding import rules_for
from repro.train.steps import make_serve_step, make_train_step
from proptest import for_all


@pytest.mark.parametrize("arch", all_arch_ids())
def test_arch_smoke_train_and_serve(arch, mesh11):
    """One fwd/train step + one decode step on the reduced config: output
    shapes correct, loss finite, no NaNs."""
    cfg = get_config(arch).reduced()
    rules = rules_for(cfg, mesh11)
    api = get_api(cfg)
    params, axes = api.init(jax.random.PRNGKey(0), cfg)
    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(n, (str, type(None))) for n in x)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(axes, is_leaf=is_ax)
    B, S = 2, 32
    batch = {"tokens": jnp.full((B, S), 3, jnp.int32),
             "labels": jnp.full((B, S), 2, jnp.int32)}
    if cfg.n_patch_tokens:
        batch["embeds"] = jnp.ones((B, cfg.n_patch_tokens, cfg.d_model),
                                   jnp.bfloat16)
    if cfg.n_frame_tokens:
        batch["embeds"] = jnp.ones((B, 16, cfg.d_model), jnp.bfloat16)
    ts, opt = make_train_step(cfg, rules, TrainConfig())
    with mesh11:
        opt_state = opt.init(params)
        p2, s2, metrics = jax.jit(ts)(params, opt_state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert np.isfinite(float(metrics["grad_norm"]))
        for leaf in jax.tree_util.tree_leaves(p2):
            assert not bool(jnp.any(jnp.isnan(leaf))), arch

        serve = make_serve_step(cfg, rules)
        cache = api.init_cache(cfg, B, 64)
        kw = {}
        if cfg.family == Family.ENCDEC:
            kw["enc_out"] = jnp.ones((B, 16, cfg.d_model), jnp.bfloat16)
        tok, cache2 = jax.jit(serve)(params, cache,
                                     jnp.ones((B, 1), jnp.int32),
                                     jnp.zeros((B,), jnp.int32), **kw)
        assert tok.shape == (B,)
        assert tok.dtype == jnp.int32


@pytest.mark.parametrize("arch", all_arch_ids())
def test_param_count_formula(arch):
    """Analytic param count == actual initialized count (reduced cfg)."""
    cfg = get_config(arch).reduced()
    api = get_api(cfg)
    shapes = jax.eval_shape(lambda r: api.init(r, cfg)[0],
                            jax.random.PRNGKey(0))
    actual = count_params(shapes)
    assert cfg.param_count() == actual, (cfg.param_count(), actual)


@pytest.mark.parametrize("arch", ["qwen2-72b", "arctic-480b", "rwkv6-3b",
                                  "zamba2-2.7b", "moonlight-16b-a3b"])
def test_full_config_param_counts_sane(arch):
    """Full-size configs land near their nameplate parameter counts."""
    cfg = get_config(arch)
    n = cfg.param_count()
    nameplate = {"qwen2-72b": 72e9, "arctic-480b": 480e9,
                 "rwkv6-3b": 3e9, "zamba2-2.7b": 2.7e9,
                 "moonlight-16b-a3b": 16e9}[arch]
    assert 0.7 * nameplate < n < 1.45 * nameplate, (arch, n)


def test_moonlight_nameplate_total_and_active():
    """Moonlight-16B-A3B: about 16.0B parameters, about 2.9B a token (6
    of 64 routed experts, the shared ones, the dense layer, attention,
    embedding and head)."""
    cfg = get_config("moonlight-16b-a3b")
    assert round(cfg.param_count() / 1e9, 1) == 16.0
    assert round(cfg.active_param_count() / 1e9, 1) == 2.9


# ------------------------------------------------ decode == prefill ---------

def test_dense_decode_matches_prefill(mesh11):
    """Greedy decode via KV cache must match argmax of the full forward."""
    from repro.models import transformer
    cfg = get_config("internlm2-20b").reduced()
    rules = rules_for(cfg, mesh11)
    params, _ = transformer.init(jax.random.PRNGKey(1), cfg)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 100)
    with mesh11:
        logits_full, _ = transformer.forward(params, cfg, rules, toks)
        cache = transformer.init_cache(cfg, B, 32)
        # feed tokens one by one through the decode path
        outs = []
        for t in range(S):
            logits_t, cache = transformer.forward(
                params, cfg, rules, toks[:, t:t+1], cache=cache,
                cache_len=jnp.full((B,), t, jnp.int32))
            outs.append(logits_t[:, 0])
        dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(jax.nn.softmax(logits_full)),
                               np.asarray(jax.nn.softmax(dec)),
                               atol=3e-2)
    # greedy tokens identical
    assert (jnp.argmax(logits_full, -1) == jnp.argmax(dec, -1)).all()


# ------------------------------------------- recurrence equivalences --------

@for_all(n_cases=8)
def test_property_ssd_chunked_equals_recurrence(rng):
    from repro.models.mamba2 import ssd_chunked, ssd_decode_step
    b, h, p, n = 2, 2, 8, 4
    l = int(rng.choice([8, 16, 32]))
    chunk = int(rng.choice([4, 8]))
    k = jax.random.PRNGKey(int(rng.integers(0, 2**31)))
    ks = jax.random.split(k, 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, l, n))
    Cm = jax.random.normal(ks[4], (b, l, n))
    y_c, s_c = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    s = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(l):
        y, s = ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], s)
        ys.append(y)
    np.testing.assert_allclose(y_c, jnp.stack(ys, 1), atol=2e-4)
    np.testing.assert_allclose(s_c, s, atol=2e-4)


@for_all(n_cases=8)
def test_property_wkv6_chunked_equals_recurrence(rng):
    from repro.models.rwkv6 import wkv6_chunked, wkv6_step
    b, h, c = 2, 2, 8
    l = int(rng.choice([8, 16, 32]))
    chunk = int(rng.choice([4, 8]))
    k = jax.random.PRNGKey(int(rng.integers(0, 2**31)))
    ks = jax.random.split(k, 5)
    r = jax.random.normal(ks[0], (b, l, h, c))
    kk = jax.random.normal(ks[1], (b, l, h, c))
    v = jax.random.normal(ks[2], (b, l, h, c))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, l, h, c))) * 0.55 + 0.4
    u = jax.random.normal(ks[4], (h, c)) * 0.1
    o_c, s_c = wkv6_chunked(r, kk, v, w, u, chunk=chunk)
    s = jnp.zeros((b, h, c, c))
    os_ = []
    for t in range(l):
        o, s = wkv6_step(r[:, t], kk[:, t], v[:, t], w[:, t], u, s)
        os_.append(o)
    np.testing.assert_allclose(o_c, jnp.stack(os_, 1), atol=5e-4)
    np.testing.assert_allclose(s_c, s, atol=5e-4)


def test_blockwise_attention_matches_naive():
    from repro.models.layers import flash_attention_xla
    from repro.kernels.ref import attention_ref
    k = jax.random.PRNGKey(5)
    ks = jax.random.split(k, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    kk = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    out = flash_attention_xla(q, kk, v, causal=True, q_chunk=16, kv_chunk=16)
    # ref expects [B,H,S,hd]
    ref = attention_ref(q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(out.transpose(0, 2, 1, 3), ref, atol=2e-5)


def test_int8_kv_cache_decode_close_to_bf16(mesh11):
    """int8 KV cache (serving option) stays close to the bf16 path and
    picks identical greedy tokens on a small model."""
    import dataclasses
    from repro.models import transformer
    cfg = get_config("internlm2-20b").reduced()
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    rules = rules_for(cfg, mesh11)
    params, _ = transformer.init(jax.random.PRNGKey(1), cfg)
    B, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 100)
    with mesh11:
        outs = {}
        for name, c in (("bf16", cfg), ("int8", cfg8)):
            cache = transformer.init_cache(c, B, 16)
            logits_seq = []
            cc = cache
            for t in range(S):
                lg, cc = transformer.forward(
                    params, c, rules, toks[:, t:t+1], cache=cc,
                    cache_len=jnp.full((B,), t, jnp.int32))
                logits_seq.append(lg[:, 0])
            outs[name] = jnp.stack(logits_seq, 1)
    p16 = jax.nn.softmax(outs["bf16"])
    p8 = jax.nn.softmax(outs["int8"])
    assert float(jnp.max(jnp.abs(p16 - p8))) < 0.12
    assert (jnp.argmax(outs["bf16"], -1) == jnp.argmax(outs["int8"], -1)
            ).mean() > 0.8
