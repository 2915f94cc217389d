"""Latent attention, dropless routing over an expert share and a leading
dense layer (``moonlight-16b-a3b``, DeepSeek-V3 layers) through the paged
serving path, against the plain float32 reference
(``models/reference_deepseek_v3``) on seeded random weights.

Tolerances: the program runs in float32 here, so it differs from the
reference (float32 at the highest matmul precision) by float32 rounding
alone, about 1e-6 of the largest logit. ``TOL`` is far above that and
far below what the same program computing in bfloat16 is off by, which
``test_bf16_program_fails_the_tolerance`` shows.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe as MOE
from repro.models import reference_deepseek_v3 as REF
from repro.models import transformer
from repro.models.module import count_params
from repro.sharding import rules_for

TOL = 1e-3      # of the largest logit: float32 reads ~1e-6 here, bf16 ~1e-2
BS = 4          # positions a block


def _cfg(dtype="float32", **kw):
    cfg = get_config("moonlight-16b-a3b").reduced()
    return dataclasses.replace(cfg, params_dtype=dtype, compute_dtype=dtype,
                               kv_cache_dtype=dtype, **kw)


def _params(cfg, seed=3):
    """Weights from ``init``, with a nonzero correction bias and norm
    scales, so that every weight moves the answer."""
    params, _ = transformer.init(jax.random.PRNGKey(seed), cfg)
    k = jax.random.PRNGKey(seed + 100)

    def jitter(path, a):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            return jax.random.normal(k, a.shape, a.dtype) * 0.3
        if "norm" in name or "ln_" in name:
            return a + jax.random.normal(
                jax.random.fold_in(k, hash(name) % 997), a.shape, a.dtype) * 0.2
        return a
    return jax.tree_util.tree_map_with_path(jitter, params)


def _paged_logits(cfg, rules, params, tokens, chunk, prompt=None):
    """The first ``prompt`` tokens (half by default) written in
    ``chunk``-token paged chunks, then every later token decoded one step
    at a time through the latent arena: the logits at each position
    [S, V] (chunks' and steps' alike)."""
    S = len(tokens)
    mb = -(-S // BS) + 1
    arenas = transformer.init_paged_arena(cfg, mb + 2, BS)
    row = jnp.arange(2, mb + 2, dtype=jnp.int32)[None]   # blocks 2.. of row 0
    prompt = S // 2 if prompt is None else prompt
    out = []
    for a in range(0, prompt, chunk):
        n = min(chunk, prompt - a)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = tokens[a:a + n]
        lg, arenas = transformer.forward(
            params, cfg, rules, jnp.asarray(toks), paged_cache=(arenas, row),
            cache_len=jnp.asarray([a], jnp.int32),
            n_valid=jnp.asarray([n], jnp.int32))
        out.append(lg[0, :n])
    for t in range(prompt, S):
        lg, arenas = transformer.forward(
            params, cfg, rules, jnp.asarray([[tokens[t]]], jnp.int32),
            paged_cache=(arenas, row), cache_len=jnp.asarray([t], jnp.int32))
        out.append(lg[0, :1])
    return np.asarray(jnp.concatenate(out))[:, :cfg.vocab_size]


def _err(a, b):
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


TOKENS = np.random.default_rng(7).integers(1, 512, 22).astype(np.int32)


@pytest.mark.parametrize("chunk", [4, 8])
def test_chunks_then_decode_match_the_reference(mesh11, chunk):
    cfg = _cfg()
    params = _params(cfg)
    with mesh11:
        got = _paged_logits(cfg, rules_for(cfg, mesh11), params, TOKENS,
                            chunk)
    want = np.asarray(REF.forward(params, cfg, TOKENS))
    assert _err(got, want) < TOL


def test_bf16_program_fails_the_tolerance(mesh11):
    """The control of every tolerance here: the program in bfloat16 on the
    same (bf16-representable) weights misses the reference by more."""
    cfg, cfg16 = _cfg(), _cfg("bfloat16")
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), _params(cfg))
    with mesh11:
        got = _paged_logits(cfg16, rules_for(cfg16, mesh11),
                            jax.tree_util.tree_map(
                                lambda a: a.astype(jnp.bfloat16), params),
                            TOKENS, 8)
    want = np.asarray(REF.forward(params, cfg, TOKENS))
    assert _err(got, want) > 3 * TOL


def test_absorbed_decode_matches_the_expanded_chunk(mesh11):
    """S == 1 scores over the latent with absorbed projections; S > 1
    expands keys and values. One chunk of the whole sequence and one
    decode step a token leave the same logits."""
    cfg = _cfg()
    params = _params(cfg)
    with mesh11:
        rules = rules_for(cfg, mesh11)
        chunked = _paged_logits(cfg, rules, params, TOKENS[:10], 10, 10)
        decoded = _paged_logits(cfg, rules, params, TOKENS[:10], 1, 0)
    assert _err(decoded, chunked) < TOL


def test_a_rows_logits_do_not_depend_on_its_batchmates(mesh11):
    """Dropless routing: a decode row reads the same logits alone (the
    other rows inactive: null tables) and beside two busy rows."""
    from repro.train.steps import make_paged_serve_step
    cfg = _cfg()
    params = _params(cfg)
    arenas = transformer.init_paged_arena(cfg, 16, BS)
    rng = np.random.default_rng(1)
    arenas = {k: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
              for k, a in arenas.items()}
    bt = np.asarray([[1, 2, 0], [3, 4, 5], [6, 7, 0]], np.int32)
    tok = np.asarray([[5], [9], [11]], np.int32)
    cl = np.asarray([6, 9, 3], np.int32)
    with mesh11:
        rules = rules_for(cfg, mesh11)

        def logits(bt, tok, cl):
            lg, _, hits = transformer.forward(
                params, cfg, rules, jnp.asarray(tok),
                paged_cache=(arenas, jnp.asarray(bt)),
                cache_len=jnp.asarray(cl), moe_rows=jnp.asarray(bt[:, 0] > 0))
            return np.asarray(lg[:, 0]), np.asarray(hits)
        alone, hits1 = logits(bt * [[1], [0], [0]], tok * [[1], [0], [0]],
                              cl * [1, 0, 0])
        beside, hits3 = logits(bt, tok, cl)
        step = jax.jit(make_paged_serve_step(cfg, rules))
        nxt, _, hits_step = step(params, arenas, jnp.asarray(bt),
                                 jnp.asarray(tok), jnp.asarray(cl))
    np.testing.assert_allclose(alone[0], beside[0], rtol=0, atol=1e-5)
    # each seated row chose top_k experts in each expert layer (all held)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert hits1.shape == (n_moe, cfg.n_experts)
    assert (hits1.sum(1) == cfg.top_k).all()
    assert (hits3.sum(1) == 3 * cfg.top_k).all()
    np.testing.assert_array_equal(np.asarray(hits_step), hits3)
    assert np.asarray(nxt).tolist() == beside.argmax(-1).tolist()


def _moe_share(cfg, params, lo, hi):
    """One chip's configuration and weights: the routed experts [lo, hi)."""
    c = dataclasses.replace(cfg, experts_held=(lo, hi))
    p = dict(params, moe={k: (v[lo:hi] if k.startswith("w") else v)
                          for k, v in params["moe"].items()})
    return c, p


def test_the_expert_shares_add_up_to_the_uncut_layer(mesh11):
    """Four chips' partial outputs of one MoE layer, the shared experts
    counted once, add up to the whole layer; and each share agrees with
    the reference given the same share."""
    cfg = _cfg(n_experts=8, top_k=3)
    params = jax.tree_util.tree_map(lambda a: a[0],
                                    _params(cfg)["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    with mesh11:
        rules = rules_for(cfg, mesh11)
        whole, _ = MOE.moe_mlp(params, cfg, rules, x, dropless=True)
        shared = MOE.L.mlp(params["shared_mlp"], rules, x)
        parts = []
        for s in range(4):
            c, p = _moe_share(cfg, params, 2 * s, 2 * s + 2)
            y, _ = MOE.moe_mlp(p, c, rules, x, dropless=True)
            parts.append(y - shared)
            with jax.default_matmul_precision("highest"):
                ref = jax.vmap(lambda r: REF.routed(c, p["moe"], r))(x)
            scale = float(np.abs(np.asarray(whole)).max())
            assert np.abs(np.asarray(y - shared - ref)).max() < 1e-5 * scale
            assert np.abs(np.asarray(ref)).max() > 0.01 * scale  # chosen
    assert _err(np.asarray(sum(parts) + shared), np.asarray(whole)) < 1e-5


def test_the_bias_moves_the_choice_not_the_weights():
    cfg = _cfg(n_experts=8, top_k=2)
    rng = np.random.default_rng(2)
    mp = {"router": jnp.asarray(rng.normal(size=(cfg.d_model, 8)) * 0.1,
                                jnp.float32),
          "router_bias": jnp.zeros(8)}
    x = jnp.asarray(rng.normal(size=(6, cfg.d_model)), jnp.float32)
    w0, idx0 = MOE.route(mp, cfg, x)
    # a large bias on an expert no token chose: every token now takes it
    e = int(np.setdiff1d(np.arange(8), np.asarray(idx0))[0]) \
        if len(np.setdiff1d(np.arange(8), np.asarray(idx0))) else 0
    w1, idx1 = MOE.route(dict(mp, router_bias=jnp.zeros(8).at[e].set(10.0)),
                         cfg, x)
    assert (np.asarray(idx1) == e).any(-1).all()
    assert not np.array_equal(np.asarray(idx0), np.asarray(idx1))
    # the weights are the unbiased scores of the chosen, normalised
    scores = np.asarray(jax.nn.sigmoid(x @ mp["router"]))
    for w, idx in ((w0, idx0), (w1, idx1)):
        s = np.take_along_axis(scores, np.asarray(idx), -1)
        np.testing.assert_allclose(
            np.asarray(w), s / s.sum(-1, keepdims=True) * cfg.routed_scale,
            rtol=1e-6)


def test_param_count_matches_init_for_a_chip_share():
    """The published widths, one chip's 16 of 64 experts, depth cut to 3
    (shapes only: nothing is allocated)."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), n_layers=3,
                              experts_held=(0, 16))
    shapes = jax.eval_shape(lambda r: transformer.init(r, cfg)[0],
                            jax.random.PRNGKey(0))
    assert cfg.param_count() == count_params(shapes)
    assert shapes["blocks"]["moe"]["wi_gate"].shape == (2, 16, 2048, 1408)
    assert shapes["blocks"]["moe"]["router"].shape == (2, 2048, 64)
    assert shapes["dense_blocks"]["mlp"]["wi_gate"].shape == (1, 2048, 11264)


def test_dense_arenas_keep_their_shapes():
    cfg = get_config("internlm2-20b")
    a = jax.eval_shape(lambda: transformer.init_paged_arena(cfg, 10, 16))
    assert {k: v.shape for k, v in a.items()} == {
        "k": (48, 10, 16, 8, 128), "v": (48, 10, 16, 8, 128)}
    m = jax.eval_shape(lambda: transformer.init_paged_arena(
        get_config("moonlight-16b-a3b"), 10, 16))
    assert {k: v.shape for k, v in m.items()} == {
        "latent": (27, 10, 16, 512), "rope": (27, 10, 16, 64)}


def test_engine_records_one_route_event_per_step_and_layer(mesh11):
    """The engine fetches the step's hits with its tokens and records one
    MOE_ROUTE per expert layer and step; a dense engine records none."""
    from repro.core.genesys.trace import (EV_MOE_ROUTE, EV_STEP,
                                          MOE_ROUTE_SHIFT, Tracer)
    from repro.serving.engine import make_engine
    for name in ("moonlight-16b-a3b", "internlm2-20b"):
        cfg = dataclasses.replace(get_config(name).reduced(),
                                  params_dtype="float32")
        params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
        tracer = Tracer(1 << 12)
        with mesh11:
            eng = make_engine(cfg, rules_for(cfg, mesh11), params,
                              n_slots=3, n_blocks=16, block_size=16,
                              max_blocks_per_seq=4)
            eng.trace = tracer.channel("engine")
            eng.admit(np.arange(1, 20, dtype=np.int32), 3, span=1)
            eng.admit(np.arange(1, 4, dtype=np.int32), 2, span=2)
            eng.drain()
        evs = tracer.events.snapshot()
        routes = evs[evs["ev"] == EV_MOE_ROUTE]
        if name != "moonlight-16b-a3b":
            assert len(routes) == 0
            continue
        steps = evs[evs["ev"] == EV_STEP]
        n_moe = cfg.n_layers - cfg.n_dense_layers
        assert len(routes) == n_moe * len(np.unique(steps["sysno"]))
        assert set(routes["seq"].tolist()) == set(
            range(cfg.n_dense_layers, cfg.n_layers))
        for k in np.unique(steps["sysno"]):
            rows = int((steps["sysno"] == k).sum())
            aux = routes[routes["sysno"] == k]["aux"]
            hit, assigned = aux & ((1 << MOE_ROUTE_SHIFT) - 1), \
                aux >> MOE_ROUTE_SHIFT
            assert (assigned == rows * cfg.top_k).all()
            assert ((hit >= cfg.top_k) & (hit <= cfg.n_experts)).all()


def test_a_latent_block_round_trips_through_spill_bytes():
    """Spill and revival move a block's entries whatever the family
    declares: MLA's latent [L, BS, R] and rotated key [L, BS, rope]."""
    from repro.serving.engine import ContinuousBatchEngine
    from repro.serving.pagedkv import PagedKVPool
    cfg = _cfg()
    rng = np.random.default_rng(0)
    arenas = {k: rng.normal(size=a.shape).astype(np.float32) for k, a in
              transformer.init_paged_arena(cfg, 4, BS).items()}
    eng = ContinuousBatchEngine(
        None, {}, {k: jnp.asarray(a) for k, a in arenas.items()},
        PagedKVPool(4, BS), prefill_chunk=None, n_slots=1,
        max_blocks_per_seq=2)
    C = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert eng.block_bytes() == cfg.n_layers * BS * C * 4
    payload = eng._extract_block(2)
    eng._install_block(3, payload)
    for k, a in arenas.items():
        np.testing.assert_array_equal(np.asarray(eng.arenas[k][:, 3]),
                                      a[:, 2])
