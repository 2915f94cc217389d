"""genesys.pagedkv: paged KV pool semantics, the genesys memory binding
(mmap/touch/DONTNEED residency, PWRITE64 spill + PREAD64_FIXED revival),
and continuous-batching engine equivalence against a dense teacher-forced
reference.

Equivalence tests run in float32: the paged path computes softmax in one
pass while the dense carried-cache path uses the two-part kernel — they
are mathematically equal, but in bf16 last-ulp differences flip argmax on
the near-tied logits of a random tiny model.
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.engine import CHUNK
from repro.serving.pagedkv import (NULL_BLOCK, PagedKVPool, PoolExhausted,
                                   chain_hashes)

BS = 4


def _pool(n_blocks=8):
    return PagedKVPool(n_blocks, BS)


# ------------------------------------------------------- pool semantics -----

def test_alloc_free_refcount_and_null_block():
    p = _pool(6)                       # null + 5 usable
    a = p.alloc(3)
    assert len(a) == 3 and NULL_BLOCK not in a
    assert p.stats.blocks_in_use == 3
    b = p.alloc(2)
    assert not set(a) & set(b)
    with pytest.raises(PoolExhausted):
        p.alloc(1)
    assert p.stats.blocks_in_use == 5  # failed alloc takes nothing
    p.retire(a)
    assert p.free_blocks() == 3
    assert p.stats.frees == 3 and p.stats.blocks_in_use == 2
    # null-block entries in a table row are skipped on retirement
    p.retire([NULL_BLOCK, NULL_BLOCK])
    assert p.stats.blocks_in_use == 2


def test_alloc_is_all_or_nothing():
    p = _pool(4)
    p.alloc(2)
    with pytest.raises(PoolExhausted):
        p.alloc(3)
    assert len(p.alloc(1)) == 1        # the partial claim was rolled back


def test_chain_hashes_depend_on_depth():
    """The same token window at different prefix depths must not alias."""
    toks = list(range(3 * BS))
    h = chain_hashes(toks, BS)
    assert len(h) == 3 and len(set(h)) == 3
    # identical second block content, different first block -> different h[1]
    other = [99] * BS + toks[BS:2 * BS]
    assert chain_hashes(other, BS)[1] != h[1]
    # partial trailing block contributes no hash
    assert len(chain_hashes(toks[:2 * BS + 1], BS)) == 2


def test_prefix_seal_share_and_lru_eviction():
    p = _pool(8)
    prompt = list(range(2 * BS))
    blocks = p.alloc(2)
    p.retire(blocks, prompt_tokens=prompt)
    assert p.stats.sealed == 2
    assert p.free_blocks() == 7        # cached blocks stay reclaimable
    # two sharers hold the prefix concurrently: refcount, not copies
    ids1, f1 = p.acquire_prefix(prompt)
    ids2, f2 = p.acquire_prefix(prompt)
    assert ids1 == blocks and ids2 == blocks and f1 == [] and f2 == []
    assert p.stats.prefix_hits == 4 and p.stats.hit_rate() == 1.0
    p.retire(ids1)
    p.retire(ids2, prompt_tokens=prompt)   # re-seal is a no-op, re-parks
    assert p.stats.blocks_in_use == 0
    # an oversized alloc reclaims the cached blocks LRU-first
    got = p.alloc(7)
    assert p.stats.evictions == 2
    assert set(blocks) <= set(got)
    # the sealed mapping died with the eviction (no spill file bound)
    ids3, _ = p.acquire_prefix(prompt)
    assert ids3 == []


def test_acquire_prefix_stops_at_first_miss():
    p = _pool(8)
    blocks = p.alloc(3)
    prompt = list(range(3 * BS))
    p.retire(blocks, prompt_tokens=prompt)
    # a prompt sharing only the first two blocks reuses exactly those
    other = prompt[:2 * BS] + [777] * BS
    ids, _ = p.acquire_prefix(other)
    assert ids == blocks[:2]
    p.retire(ids)


# ------------------------------------------------- genesys memory binding ---

@pytest.fixture()
def gsys():
    from repro.core.genesys import Genesys, GenesysConfig
    g = Genesys(GenesysConfig(n_workers=2))
    yield g
    g.shutdown()


def test_bound_pool_tracks_rss(gsys):
    p = _pool(6)
    p.bind_genesys(gsys, block_bytes=8192)
    assert p.rss_bytes() == 0
    a = p.alloc(3)                     # touch -> resident
    assert p.rss_bytes() >= 3 * 8192
    p.retire(a)                        # MADV_DONTNEED -> dropped
    assert p.rss_bytes() == 0
    assert "pagedkv" in gsys.tenants()


def test_spill_and_fixed_read_roundtrip(gsys):
    """Evicting a sealed block PWRITE64s its payload; the next prefix hit
    revives the exact bytes via PREAD64_FIXED into the registered staging
    buffer (no heap resolve on the read path)."""
    spill = tempfile.mktemp(suffix=".kvspill")
    p = _pool(4)                       # null + 3 usable
    p.bind_genesys(gsys, block_bytes=256, spill_path=spill)
    payload = bytes(np.random.default_rng(0).integers(
        0, 256, size=256, dtype=np.uint8))
    p.extractor = lambda bid: payload
    try:
        prompt = list(range(BS))
        p.retire(p.alloc(1), prompt_tokens=prompt)     # sealed, cached
        working = p.alloc(3)                           # forces the eviction
        assert p.stats.evictions == 1 and p.stats.spill_writes == 1
        p.retire(working)                              # room for the revival
        ids, fetches = p.acquire_prefix(prompt)
        assert p.stats.fixed_reads == 1
        assert len(ids) == 1 and len(fetches) == 1
        bid, got = fetches[0]
        assert bid == ids[0] and got == payload
    finally:
        if os.path.exists(spill):
            os.unlink(spill)


# ------------------------------------------- engine vs dense reference ------

def _f32(cfg):
    return dataclasses.replace(cfg, params_dtype="float32",
                               compute_dtype="float32",
                               kv_cache_dtype="float32")


def _model(mesh11):
    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.sharding import rules_for
    cfg = _f32(get_config("internlm2-20b").reduced())
    rules = rules_for(cfg, mesh11)
    api = get_api(cfg)
    params, _ = api.init(jax.random.PRNGKey(1), cfg)
    return cfg, rules, api, params


def _dense_reference(cfg, rules, api, params, prompt, budget, *,
                     serve=None, max_len=64):
    """Teacher-forced prefill + greedy decode on the carried dense cache
    (``serve``: a ready ``make_serve_step``, for instance a jitted one)."""
    from repro.train.steps import make_serve_step
    serve = serve or make_serve_step(cfg, rules)
    cache = api.init_cache(cfg, 1, max_len)
    toks = [int(t) for t in prompt]
    gen = []
    for i in range(len(prompt) + budget - 1):
        nxt, cache = serve(params, cache,
                           jnp.asarray([[toks[i]]], jnp.int32),
                           jnp.full((1,), i, jnp.int32))
        if i >= len(prompt) - 1:
            gen.append(int(nxt[0]))
            toks.append(gen[-1])
    return gen


def test_engine_matches_dense_reference_with_churn(mesh11):
    """Staggered admissions/retirements mid-decode: every request's
    continuation equals its solo dense decode — slot churn, block-table
    indirection and null-block masking never leak across rows."""
    from repro.serving.engine import make_engine
    cfg, rules, api, params = _model(mesh11)
    rng = np.random.default_rng(5)
    n_req = 6
    reqs = [(rng.integers(1, cfg.vocab_size, size=rng.integers(1, 10))
             .astype(np.int32), int(rng.integers(2, 6)))
            for _ in range(n_req)]
    eng = make_engine(cfg, rules, params, n_slots=3, n_blocks=32,
                      block_size=BS, jit=True)
    done = {}
    with mesh11:
        want = {i: _dense_reference(cfg, rules, api, params, p, b)
                for i, (p, b) in enumerate(reqs)}
        pending = list(enumerate(reqs))
        while pending or eng.n_active:
            while pending and eng.admit(pending[0][1][0], pending[0][1][1],
                                        meta=pending[0][0]):
                pending.pop(0)          # arrivals land mid-decode
            for meta, gen in eng.step():
                done[meta] = gen
    assert done == want
    assert eng.stats.admitted == n_req and eng.stats.retired == n_req
    assert eng.stats.occupancy() > 1.0  # the point of continuous batching
    assert eng.pool.stats.blocks_in_use == 0


def test_engine_prefix_reuse_and_spill_revival_exact(mesh11, gsys):
    """Shared-prefix admission skips sealed-block prefill and — after the
    prefix is evicted to the spill file — revives it through
    PREAD64_FIXED + arena install, with token-identical output."""
    from repro.serving.engine import make_engine
    cfg, rules, api, params = _model(mesh11)
    spill = tempfile.mktemp(suffix=".kvspill")
    eng = make_engine(cfg, rules, params, n_slots=2, n_blocks=12,
                      block_size=BS, max_blocks_per_seq=10, gsys=gsys,
                      spill_path=spill)
    rng = np.random.default_rng(9)
    prefix = rng.integers(1, cfg.vocab_size, size=2 * BS).tolist()
    p1 = np.asarray(prefix + [17], np.int32)
    p2 = np.asarray(prefix + [23], np.int32)
    try:
        with mesh11:
            want1 = _dense_reference(cfg, rules, api, params, p1, 3)
            want2 = _dense_reference(cfg, rules, api, params, p2, 3)
            assert eng.admit(p1, 3)
            (_, gen1), = eng.drain()
            saved0 = eng.stats.prefill_steps_saved
            assert eng.admit(p2, 3)    # hits the sealed prefix in-arena
            (_, gen2), = eng.drain()
            assert eng.stats.prefill_steps_saved - saved0 == 2 * BS
            assert eng.pool.stats.prefix_hits == 2
            # evict the sealed prefix to spill (10 wanted, 9 free)...
            assert eng.admit(np.asarray([5], np.int32), 10 * BS)
            eng.drain()
            assert eng.pool.stats.spill_writes >= 1
            # ...and revive it: PREAD64_FIXED + _install_block
            assert eng.admit(p2, 3)
            (_, gen3), = eng.drain()
            assert eng.pool.stats.fixed_reads >= 1
        assert gen1 == want1
        assert gen2 == want2 and gen3 == want2
    finally:
        if os.path.exists(spill):
            os.unlink(spill)


def test_engine_admission_backpressure(mesh11):
    """admit() returns False — claiming nothing — on slot or block
    exhaustion, and the request succeeds after retirements."""
    from repro.serving.engine import make_engine
    cfg, rules, api, params = _model(mesh11)
    eng = make_engine(cfg, rules, params, n_slots=2, n_blocks=9,
                      block_size=BS, max_blocks_per_seq=4, jit=False)
    with mesh11:
        assert eng.admit(np.asarray([3], np.int32), 2 * BS)   # 2 blocks
        assert eng.admit(np.asarray([4], np.int32), 2 * BS)
        in_use = eng.pool.stats.blocks_in_use
        assert not eng.admit(np.asarray([5], np.int32), 2)    # slots full
        assert eng.pool.stats.blocks_in_use == in_use
        eng.drain()
        assert eng.admit(np.asarray([5], np.int32), 2)
        # block-table width is a hard cap, not a soft failure
        with pytest.raises(ValueError):
            eng.admit(np.asarray([6], np.int32), 5 * BS)
        eng.drain()
    assert eng.pool.stats.blocks_in_use == 0


# ------------------------------------------------------ chunked prefill -----

C = CHUNK
TABLE = 136                  # block-table width: 544 positions
REUSED = C + 2 * BS          # whole blocks of a retired prompt, reused
# (prompt length, positions reused from the pool): lengths at and across
# the chunk edges, one that fills its whole block table with its budget
# of 3 (the last chunk's padding runs past the table), and one whose
# chunks start after a reused prefix
CHUNK_CASES = [(1, 0), (2, 0), (C - 1, 0), (C, 0), (C + 1, 0), (C + 2, 0),
               (2 * C + 1, 0), (2 * C + 9, 0), (TABLE * BS - 2, 0),
               (REUSED + C + 2, REUSED)]


@pytest.fixture(scope="module")
def chunk_rig():
    from repro.launch.mesh import make_host_mesh
    from repro.train.steps import make_serve_step
    mesh = make_host_mesh()
    cfg, rules, api, params = _model(mesh)
    return mesh, cfg, rules, api, params, jax.jit(make_serve_step(cfg,
                                                                  rules))


def _chunks(p, r):
    return -(-(p - 1 - r) // C)


@pytest.mark.parametrize("plen,reused", CHUNK_CASES,
                         ids=[f"P{p}-r{r}" for p, r in CHUNK_CASES])
def test_chunked_engine_matches_dense_reference(chunk_rig, plen, reused):
    """A prompt written in CHUNK-token dispatches, beside staggered
    arrivals (a 1-token prompt already decoding, a short prompt queued
    behind the long one's chunks): every request's tokens equal its solo
    dense decode, the chunk counters are exact, and no prompt token goes
    through the decode step but the last."""
    from repro.serving.engine import EngineStats, make_engine
    mesh, cfg, rules, api, params, serve = chunk_rig
    rng = np.random.default_rng([plen, reused])

    def prompt(n):
        return rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)

    eng = make_engine(cfg, rules, params, n_slots=3, n_blocks=200,
                      block_size=BS, max_blocks_per_seq=TABLE)
    long = prompt(plen)
    with mesh:
        if reused:
            # a retired prompt seals its whole blocks in the pool
            assert eng.admit(np.concatenate([long[:reused], prompt(1)]), 2)
            eng.drain()
            eng.stats = EngineStats()
        # (admitted at step, prompt, budget)
        reqs = [(0, prompt(1), 6), (1, long, 3), (2, prompt(7), 2)]
        want = {i: _dense_reference(cfg, rules, api, params, p, b,
                                    serve=serve, max_len=576)
                for i, (_, p, b) in enumerate(reqs)}
        done, n_step = {}, 0
        while len(done) < len(reqs):
            for i, (at, p, b) in enumerate(reqs):
                if at == n_step:
                    assert eng.admit(p, b, meta=i)
            for meta, gen in eng.step():
                done[meta] = gen
            n_step += 1
    assert done == want
    st = eng.stats
    assert st.prefill_steps_saved == reused
    assert st.prefill_chunks == _chunks(plen, reused) + _chunks(7, 0)
    assert st.prefill_chunk_tokens == (plen - 1 - reused) + 6
    assert st.prefill_steps == 0
    assert eng.pool.stats.blocks_in_use == 0


def test_chunked_prefill_compiles_nothing_after_warm_up(chunk_rig):
    """make_engine compiles the chunk program at its one shape, so once a
    1-token prompt has warmed the decode step (as a server warms it), a
    prompt of several chunks compiles nothing more."""
    from repro.serving.engine import make_engine
    mesh, cfg, rules, api, params, _ = chunk_rig
    compiles = []

    def listen(event, duration, **kw):
        if "backend_compile" in event:
            compiles.append(event)

    eng = make_engine(cfg, rules, params, n_slots=2, n_blocks=200,
                      block_size=BS, max_blocks_per_seq=TABLE)
    jax.clear_caches()      # as in a fresh process: earlier tests warmed
    with mesh:
        eng.admit(np.zeros(1, np.int32), 2)
        eng.drain()
        jax.block_until_ready(eng.arenas)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            assert eng.admit(np.arange(1, 2 * C + 3, dtype=np.int32), 2)
            eng.drain()
            jax.block_until_ready(eng.arenas)
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
    assert eng.stats.prefill_chunks == 3
    assert compiles == []


# ---------------------------------------------- arenas updated in place -----

FAMILIES = ["internlm2-20b", "moonlight-16b-a3b"]     # {k, v}; {latent, rope}


def _family(name, mesh):
    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.sharding import rules_for
    cfg = _f32(get_config(name).reduced())
    params, _ = get_api(cfg).init(jax.random.PRNGKey(2), cfg)
    return cfg, rules_for(cfg, mesh), params


def _changed(before, after):
    """{entry: [L, NB, BS] bool}: where an arena's entries differ."""
    return {k: np.any(np.asarray(after[k]) != a, axis=tuple(
        range(3, a.ndim))) for k, a in before.items()}


@pytest.mark.parametrize("name", FAMILIES)
def test_step_and_chunk_write_only_their_new_entries(mesh11, name):
    """The donated decode step writes each seated row's new token at its
    table slot and nothing else but the null block (the inactive rows'
    target); a chunk writes its n_valid prompt positions and drops its
    padding. On random arenas, so any other write would show."""
    from repro.models import transformer
    from repro.serving.engine import compile_programs
    from repro.train.steps import (make_paged_prefill_chunk,
                                   make_paged_serve_step)
    cfg, rules, params = _family(name, mesh11)
    NB, MB = 16, 6
    rng = np.random.default_rng(4)
    before = {k: rng.normal(size=a.shape).astype(np.float32) for k, a in
              transformer.init_paged_arena(cfg, NB, BS).items()}
    with mesh11:
        step, chunk = compile_programs(
            make_paged_serve_step(cfg, rules),
            make_paged_prefill_chunk(cfg, rules), params,
            {k: jnp.asarray(a) for k, a in before.items()}, n_slots=3,
            max_blocks=MB)
        # rows 0 and 2 seated at positions 9 and 5; row 1 free (all null)
        bt = np.zeros((3, MB), np.int32)
        bt[0, :3], bt[2, :2] = [3, 4, 5], [7, 8]
        out = step(params, {k: jnp.asarray(a) for k, a in before.items()},
                   jnp.asarray(bt), jnp.asarray([[11], [0], [13]], np.int32),
                   jnp.asarray([9, 0, 5], np.int32))
        got = _changed(before, out[1])
        want = np.zeros((NB, BS), bool)
        want[5, 9 % BS] = want[8, 5 % BS] = True
        for k, ch in got.items():
            # the null block: only the free row's slot, offset 0
            assert (ch[:, NULL_BLOCK + 1:] == want[None, 1:]).all(), k
            assert not ch[:, NULL_BLOCK, 1:].any(), k
        # a chunk of 7 prompt tokens at positions 4..10 of a 6-block row
        row = np.asarray([[2, 6, 9, 10, 11, 12]], np.int32)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :7] = np.arange(1, 8)
        after = chunk(params, {k: jnp.asarray(a) for k, a in before.items()},
                      jnp.asarray(row), jnp.asarray(toks),
                      jnp.asarray([4], np.int32), jnp.asarray([7], np.int32))
    want = np.zeros((NB, BS), bool)
    for p in range(4, 11):
        want[row[0, p // BS], p % BS] = True
    for k, ch in _changed(before, after).items():
        assert (ch == want[None]).all(), k


@pytest.mark.parametrize("name", FAMILIES)
def test_donated_engine_serves_the_undonated_engines_tokens(mesh11, gsys,
                                                          name):
    """An engine whose compiled programs update the arenas in place serves
    the same tokens as one running the same functions unjitted, over
    staggered admissions, prompts written in chunks (one of two chunks)
    and retirements that free slots for the next request. Its telemetry
    counts both programs' aliased arenas."""
    from repro.serving.engine import make_engine
    cfg, rules, params = _family(name, mesh11)
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(1, cfg.vocab_size, size=n).astype(np.int32), b)
            for n, b in [(1, 4), (6, 3), (CHUNK + 9, 2), (3, 5), (2, 2)]]
    served = []
    for jit in (True, False):
        eng = make_engine(cfg, rules, params, n_slots=2, n_blocks=160,
                          block_size=BS, max_blocks_per_seq=72, jit=jit,
                          gsys=gsys if jit else None)
        arena_bytes = sum(a.size * a.dtype.itemsize
                          for a in eng.arenas.values())
        assert eng.stats.arena_alias_bytes == (2 * arena_bytes if jit else 0)
        done, pending = {}, list(enumerate(reqs))
        with mesh11:
            while pending or eng.n_active:
                while pending and eng.admit(pending[0][1][0],
                                            pending[0][1][1],
                                            meta=pending[0][0]):
                    pending.pop(0)
                for meta, gen in eng.step():
                    done[meta] = gen
        assert eng.stats.prefill_chunks == 5
        assert eng.pool.stats.blocks_in_use == 0
        served.append(done)
    assert served[0] == served[1]
    engine = gsys.telemetry()["serving"]["engine"]
    assert engine["arena_alias_bytes"] == 2 * arena_bytes
    assert sorted(served[0]) == list(range(len(reqs)))
