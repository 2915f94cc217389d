"""genesys.admit: SLO-driven admission control, reap-credit backpressure,
hierarchical WFQ groups, fuse-aware QoS charging, spill compaction, and
deterministic fault injection through the executor's dispatch funnel."""
import os
import threading
import time
from contextlib import contextmanager

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.genesys import (
    AdmissionController, AdmitShed, FaultPlan, Genesys, GenesysConfig, Sys,
    WeightedFair,
)
from repro.core.genesys.executor import EAGAIN, EINTR, EIO

from test_system import (_chain, _fake_paged_step, _fake_prefill_chunk,
                         _serve_requests)


@contextmanager
def fresh(cfg=None):
    g = Genesys(cfg or GenesysConfig(n_workers=2))
    try:
        yield g
    finally:
        g.shutdown()


# ------------------------------------------------------------ FaultPlan ----

def test_fault_plan_schedule_is_seed_deterministic():
    def run(seed):
        p = FaultPlan(seed).inject(sysno=7, errnos=(EIO, EAGAIN), rate=0.5)
        rets = [p.check("t", 7) for _ in range(200)]
        return rets, p.digest()
    r1, d1 = run(1)
    r2, d2 = run(1)
    r3, d3 = run(2)
    assert r1 == r2 and d1 == d2
    assert r1 != r3 and d1 != d3            # the seed is the schedule
    assert any(r1) and not all(r1)          # rate 0.5 actually thins


def test_fault_plan_rate_is_statistical_and_replayable():
    p = FaultPlan(seed=1).inject(sysno=7, errnos=(EIO,), rate=0.25)
    hits = sum(1 for _ in range(4000) if p.check("t", 7))
    assert 800 < hits < 1200                # ~1000 expected
    p2 = FaultPlan(seed=1).inject(sysno=7, errnos=(EIO,), rate=0.25)
    for _ in range(4000):
        p2.check("t", 7)
    assert p2.digest() == p.digest()


def test_fault_plan_count_skip_and_filters():
    p = FaultPlan(seed=3).inject(tenant="a", sysno=9, errnos=(EAGAIN,),
                                 rate=1.0, count=2, skip=3)
    rets = [p.check("a", 9) for _ in range(10)]
    assert rets[:3] == [0, 0, 0]            # skip arms after 3 clean calls
    assert rets[3:5] == [EAGAIN, EAGAIN]    # then exactly `count` fire
    assert rets[5:] == [0] * 5
    assert p.check("b", 9) == 0             # tenant filter
    assert p.check("a", 8) == 0             # sysno filter
    assert p.injected == 2 and len(p.events()) == 2


def test_fault_plan_parse_grammar():
    p = FaultPlan.parse("42;*:17:EIO:0.05;flood:45:EAGAIN:1.0;x:9:13:0.5")
    assert p.seed == 42 and len(p._rules) == 3
    r = p._rules[1]
    assert r.tenant == "flood" and r.sysno == 45
    assert r.errnos == (EAGAIN,) and r.rate_ppm == 1_000_000
    assert p._rules[0].tenant is None       # '*' wildcard
    assert p._rules[2].errnos == (13,)      # numeric errno passes through
    with pytest.raises(ValueError):
        FaultPlan.parse("")
    with pytest.raises(ValueError):
        FaultPlan.parse("1;bad:rule")
    with pytest.raises(ValueError):
        FaultPlan(0).inject(errnos=(), rate=1.0)
    with pytest.raises(ValueError):
        FaultPlan(0).inject(errnos=(5,), rate=1.5)


# ------------------------------------------- executor retry-with-backoff ----

def test_injected_transient_retried_to_success(gsys):
    gsys.use_fault_plan(FaultPlan(seed=7).inject(
        sysno=int(Sys.ECHO), errnos=(EAGAIN,), rate=1.0, count=2))
    t = gsys.tenant("r0")
    assert t.call(Sys.ECHO, 5) == 5         # 2 EAGAINs retried through
    ex = gsys.executor.counters.snapshot()
    assert ex["injected_faults"] == 2 and ex["retries"] == 2
    assert ex["retries_exhausted"] == 0


def test_injected_transient_retry_is_bounded(gsys):
    gsys.use_fault_plan(FaultPlan(seed=7).inject(
        sysno=int(Sys.ECHO), errnos=(EINTR,), rate=1.0))
    assert gsys.tenant("r1").call(Sys.ECHO, 6) == -EINTR
    ex = gsys.executor.counters.snapshot()
    assert ex["retries"] == 3               # RetryPolicy.max_retries
    assert ex["retries_exhausted"] == 1
    assert ex["injected_faults"] == 4       # initial attempt + 3 retries


def test_injected_eio_is_not_retried(gsys):
    gsys.use_fault_plan(FaultPlan(seed=7).inject(
        sysno=int(Sys.ECHO), errnos=(EIO,), rate=1.0, count=1))
    assert gsys.tenant("r2").call(Sys.ECHO, 8) == -EIO
    ex = gsys.executor.counters.snapshot()
    assert ex["injected_faults"] == 1 and ex["retries"] == 0


# ------------------------------------------------- reap-credit ledger -------

def test_reap_credit_backpressure_isolates_slow_reaper():
    cfg = GenesysConfig(n_workers=2, sched_pollers=1, sched_inline=True,
                        tenant_cq_depth=8)
    with fresh(cfg) as g:
        slow = g.tenant("slow")
        fast = g.tenant("fast")
        comps = slow.submit([(Sys.ECHO, i) for i in range(30)],
                            want_cqe=True)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and slow.ring.reap_credit() > 0:
            time.sleep(0.005)
        # the ring stalled at ~cq_depth unreaped CQEs instead of growing
        # a backlog; the poller skips it rather than wedging
        assert slow.ring.reap_credit() <= 0
        time.sleep(0.05)
        assert slow.ring.counters.snapshot()["credit_stalls"] > 0
        # the other tenant still flows through the same PollerGroup
        assert fast.call(Sys.ECHO, 9, timeout=10) == 9
        # reaping drains credit back and the stalled SQEs complete
        total = 0
        deadline = time.monotonic() + 20
        while total < 30 and time.monotonic() < deadline:
            total += len(slow.reap(max_n=64, timeout=0.2))
        assert total == 30                  # no CQE lost or double-reaped
        assert [c.result(timeout=5) for c in comps] == list(range(30))


# ------------------------------------------------- hierarchical groups ------

class _T:
    def __init__(self, name, group=None, weight=1.0):
        self.name, self.group, self.weight = name, group, weight


def test_wfq_group_is_one_scheduling_node():
    wf = WeightedFair()
    a = _T("c1", "cust", 2.0)
    b = _T("c2", "cust", 1.0)
    lone = _T("solo")
    wf.quantum(a, 8)
    wf.quantum(b, 8)
    assert set(wf._members["cust"]) == {"c1", "c2"}
    # the node's weight is its heaviest member's, NOT the sum: opening
    # more connections buys no extra share
    assert wf._weights["cust"] == 2.0
    assert wf.quantum(lone, 8) == 4         # 8 * (1.0 / 2.0)
    entries = [(0, 0, 0, int(Sys.ECHO))] * 4
    wf.on_reap(a, entries)
    wf.on_reap(b, entries)
    v = wf.order_key(a)
    assert v > 0 and v == wf.order_key(b)   # one shared vtime
    wf.on_close(a)                          # sibling keeps the node alive
    assert wf._weights["cust"] == 1.0 and wf.order_key(b) == v
    wf.on_close(b)
    assert "cust" not in wf._members and "cust" not in wf._weights


def test_fused_batch_charges_one_kernel_crossing(tmp_path):
    cfg = GenesysConfig(n_workers=2, sched_pollers=1, sched_inline=True)
    with fresh(cfg) as g:
        wf = WeightedFair()
        g.use_policies(wf)
        path = tmp_path / "data.bin"
        path.write_bytes(bytes(range(256)) * 4)
        ph = g.heap.register(np.frombuffer(
            str(path).encode(), dtype=np.uint8).copy())
        fd = g.ring_call(Sys.OPEN, ph, os.O_RDONLY, 0)
        g.heap.release(ph)
        fused = g.tenant("fused", fuse=True)
        plain = g.tenant("plain")

        def reads(t, rounds=3):
            for _ in range(rounds):
                bhs = [g.heap.new_buffer(128) for _ in range(4)]
                comps = t.submit([(Sys.PREAD64, fd, bh, 128, i * 128)
                                  for i, bh in enumerate(bhs)])
                assert [c.result(timeout=10) for c in comps] == [128] * 4
                for bh in bhs:
                    g.heap.release(bh)

        reads(fused)
        reads(plain)
        key = int(Sys.PREAD64)
        fc = wf.charged["fused"][key]
        pc = wf.charged["plain"][key]
        # identical read traffic, but the fused tenant's adjacent preads
        # merged into single kernel crossings — QoS charges crossings
        assert 0 < fc < pc


# --------------------------------------------------- AdmissionController ----

def _controller(registry, **kw):
    kw.setdefault("span", 4)
    kw.setdefault("min_interval_s", 0.0)
    return AdmissionController(registry, **kw)


def test_controller_shed_curve_monotone_in_rank():
    with fresh() as g:
        c = _controller(g.metrics)
        c.declare("gold", slo_us=100.0, priority_class=0)
        for r in (1, 2, 3):
            c.declare(f"bulk{r}", priority_class=r)
        # protected group blows its SLO: windowed p99 >> slo_us
        for _ in range(6):
            for _ in range(50):
                g.metrics.observe("genesys_request_wall_us", 10_000.0,
                                  tenant="gold")
            c.refresh(force=True)
        assert c.level > 0.5
        fr = c.shed_fracs()
        assert fr["gold"] == 0.0            # protected: never shed
        assert 0.0 < fr["bulk1"] <= fr["bulk2"] <= fr["bulk3"]
        lvl = c.level
        # recovery: windows full of fast requests roll the bad ones out
        for _ in range(8):
            for _ in range(50):
                g.metrics.observe("genesys_request_wall_us", 10.0,
                                  tenant="gold")
            c.refresh(force=True)
        assert c.level < lvl                # AIMD decays when burn stops
        snap = c.counters.snapshot()
        assert snap["refreshes"] >= 14 and snap["shed_level"] == c.level


def test_thinning_is_an_exact_deterministic_duty_cycle():
    def pattern():
        with fresh() as g:
            c = _controller(g.metrics)
            c.declare("b", priority_class=1)
            c._shed_frac["b"] = 0.25
            return [c._thin("b") for _ in range(100)]
    p1 = pattern()
    assert p1 == pattern()                  # no PRNG anywhere
    assert p1.count("degrade") == 75 and p1.count("shed") == 25


def test_on_submit_sheds_and_degrades_by_rank():
    with fresh() as g:
        c = AdmissionController(g.metrics, step=0.0, min_interval_s=1e9,
                                degrade_delay_s=0.0)
        c.declare("bulk", priority_class=2)   # frac = level * 2/2 = 1.0
        c.declare("half", priority_class=1)   # frac = level * 1/2 = 0.5
        c._level = 1.0
        c.refresh(force=True)
        c.install(g)
        tb = g.tenant("conn0", group="bulk")
        th = g.tenant("conn1", group="half")
        other = g.tenant("other")
        assert tb.group == "bulk"             # tenant() plumbs the group
        with pytest.raises(AdmitShed):
            tb.call(Sys.ECHO, 1)              # frac 1.0: everything sheds
        with pytest.raises(AdmitShed):
            th.call(Sys.ECHO, 2)              # duty cycle: 1st sheds...
        assert th.call(Sys.ECHO, 3) == 3      # ...2nd degrades through
        assert other.call(Sys.ECHO, 4) == 4   # undeclared: no opinion
        snap = c.counters.snapshot()
        assert snap["shed"] == 2 and snap["degraded"] == 1
        assert snap["per_group"]["bulk"]["shed"] == 1
        assert snap["per_group"]["half"] == {"admitted": 0, "degraded": 1,
                                             "shed": 1}


# -------------------------------------------------- serving integration -----

def _forced_controller(registry, fracs):
    """A controller pinned at level 1.0 with step=0 (no AIMD movement) so
    serving tests see exact, deterministic shed fractions per group."""
    c = AdmissionController(registry, step=0.0, min_interval_s=1e9)
    for name, rank in fracs.items():
        c.declare(name, slo_us=(1e12 if rank <= 0 else None),
                  priority_class=rank)
    c._level = 1.0
    c.refresh(force=True)
    return c


def test_serve_model_answers_shed_with_shed_token(gsys):
    from repro.serving.server import SHED_TOKEN, GenesysUdpServer
    c = _forced_controller(gsys.metrics, {"bulk": 1})
    c.map_default(lambda cid: "bulk")
    cache = {"k": jnp.zeros((1, 1), jnp.float32)}
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                           batch_window_s=0.2, use_ring=True, admission=c)
    reqs = [[2, 201, 7, 3],                 # [budget, tag, client, prompt]
            [3, 202, 8, 5, 9]]
    replies = _serve_requests(
        gsys, srv,
        lambda rp: srv.serve_model(
            lambda p, ch, cur, cl: (cur.reshape(-1) * 2 + 1, ch),
            {}, cache, n_batches=1, reply_port=rp, max_tokens=8,
            per_request_tokens=True),
        reqs, n_replies=2)
    assert sorted(replies) == [[201, SHED_TOKEN], [202, SHED_TOKEN]]
    assert srv.stats.shed_requests == 2 and srv.stats.tokens_out == 0
    srv.close()


def test_serve_model_degrade_halves_budget(gsys):
    from repro.serving.server import SHED_TOKEN, GenesysUdpServer
    c = _forced_controller(gsys.metrics, {"half": 1, "upper": 2})
    c.map_default(lambda cid: "half")       # frac = 1.0 * 1/2 = 0.5
    cache = {"k": jnp.zeros((1, 1), jnp.float32)}
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                           batch_window_s=0.2, use_ring=True, admission=c)
    # same group, same prompt tail: the 0.5 duty cycle sheds one request
    # and degrades the other (4 -> 2 tokens), whichever arrives first
    reqs = [[4, 301, 7, 5], [4, 302, 7, 5]]
    replies = _serve_requests(
        gsys, srv,
        lambda rp: srv.serve_model(
            lambda p, ch, cur, cl: (cur.reshape(-1) * 2 + 1, ch),
            {}, cache, n_batches=1, reply_port=rp, max_tokens=8,
            per_request_tokens=True),
        reqs, n_replies=2)
    got = {r[0]: r[1:] for r in replies}
    assert sorted(got) == [301, 302]
    bodies = sorted(got.values(), key=len)
    assert bodies[0] == [SHED_TOKEN]
    assert bodies[1] == _chain(5, 2)        # degraded: budget 4 >> 1 = 2
    assert srv.stats.shed_requests == 1
    assert srv.stats.degraded_requests == 1
    srv.close()


def test_serve_continuous_protected_admitted_bulk_shed(gsys):
    from repro.serving.engine import ContinuousBatchEngine
    from repro.serving.pagedkv import PagedKVPool
    from repro.serving.server import SHED_TOKEN, GenesysUdpServer
    c = _forced_controller(gsys.metrics, {"gold": 0, "bulk": 1})
    c.map_default(lambda cid: "gold" if int(cid) % 2 == 0 else "bulk")
    NB, BS = 8, 4
    arenas = {"k": jnp.zeros((1, NB, BS, 1, 1)),
              "v": jnp.zeros((1, NB, BS, 1, 1))}
    eng = ContinuousBatchEngine(_fake_paged_step, {}, arenas,
                                PagedKVPool(NB, BS),
                                prefill_chunk=_fake_prefill_chunk, n_slots=2,
                                max_blocks_per_seq=4)
    eng.admission = c
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                           batch_window_s=0.02, use_ring=True, admission=c)
    gsys.table._sockets[srv.fd].settimeout(0.05)
    reqs = [[2, 900, 0, 3],                 # client 0 -> gold: protected
            [2, 901, 1, 4]]                 # client 1 -> bulk: shed
    replies = _serve_requests(
        gsys, srv,
        lambda rp: srv.serve_model_continuous(eng, reply_port=rp,
                                              n_requests=2,
                                              max_idle_polls=5),
        reqs, n_replies=2)
    got = {r[0]: r[1:] for r in replies}
    assert got[901] == [SHED_TOKEN]         # refused, answered immediately
    assert got[900] == _chain(3, 2)         # protected: served in full
    assert srv.stats.shed_requests == 1 and eng.stats.admitted == 1
    snap = c.counters.snapshot()
    assert snap["per_group"]["gold"]["admitted"] == 1
    assert snap["per_group"]["bulk"]["shed"] == 1
    srv.close()


def test_parse_request_with_client_word():
    from repro.serving.server import parse_request
    req = np.asarray([4, 77, 9, 5, 6], np.int32)
    toks, budget, tag = parse_request(req, True, 8)
    assert budget == 4 and tag == 77 and toks.tolist() == [9, 5, 6]
    toks, budget, tag, client = parse_request(req, True, 8,
                                              with_client=True)
    assert client == 9 and toks.tolist() == [5, 6]
    toks, budget, tag, client = parse_request(req, False, 8,
                                              with_client=True)
    assert budget == 8 and tag is None and client is None


# ------------------------------------------------------ spill compaction ----

def test_spill_compaction_reclaims_dead_extents(tmp_path):
    from repro.serving.pagedkv import PagedKVPool
    with fresh() as g:
        pool = PagedKVPool(8, 4)
        pool.extractor = lambda bid: bytes([bid]) * 64
        spill = tmp_path / "spill.bin"
        pool.bind_genesys(g, block_bytes=64, spill_path=str(spill),
                          spill_slots=2, spill_compact_ratio=0.5)
        toks = list(range(8))               # 2 full blocks
        ids = pool.alloc(2)
        pool.retire(ids, prompt_tokens=toks)
        pool.alloc(7)                       # evict both cached -> spill
        assert pool.stats.spill_writes == 2
        assert pool.stats.spill_live_bytes == 128
        # kill the extents on disk: revivals short-read, the entry dies
        # AND its slot leaks (the dead-extent source compaction reclaims)
        os.truncate(spill, 0)
        got, fetches = pool.acquire_prefix(toks)
        assert got == [] and fetches == []
        assert pool.stats.spill_live_bytes == 64   # h1 died, h2 still mapped
        # free the arena, reseal fresh blocks, evict again: the free list
        # is empty so _spill auto-compacts, dropping the unreadable extent
        # and reclaiming both slots before writing
        pool.retire([b for b in range(1, 8) if pool._ref[b]],
                    prompt_tokens=list(range(100, 108)))
        pool.alloc(7)                       # evicts the 2 fresh seals
        assert pool.stats.spill_compactions >= 1
        assert pool.stats.spill_writes == 4
        assert pool.stats.spill_live_bytes == 128
        assert pool._spill_live == 2
        # and the freshly spilled extents revive with correct payloads
        pool.retire([b for b in range(1, 8) if pool._ref[b]])
        got2, fetches2 = pool.acquire_prefix(list(range(100, 108)))
        assert len(got2) == 2 and len(fetches2) == 2
        assert all(len(p) == 64 for _, p in fetches2)
        assert pool.stats.spill_live_bytes == 0


def test_spill_relocation_preserves_payload(tmp_path):
    from repro.serving.pagedkv import PagedKVPool
    with fresh() as g:
        pool = PagedKVPool(8, 4)
        pool.extractor = lambda bid: bytes([0x40 + bid]) * 64
        pool.bind_genesys(g, block_bytes=64,
                          spill_path=str(tmp_path / "s.bin"), spill_slots=6)
        toks = list(range(12))              # 3 full blocks
        ids = pool.alloc(3)
        tags = {bytes([0x40 + b]) for b in ids}
        pool.retire(ids, prompt_tokens=toks)
        pool.alloc(7)                       # spill all 3 (slots 0,1,2)
        pool.retire([b for b in range(1, 8) if pool._ref[b]])
        # revive block 0 only: its slot frees, extents 1,2 stay at 1,2
        got, _ = pool.acquire_prefix(toks[:4])
        assert len(got) == 1
        pool.retire(got)
        moved = pool.compact_spill()        # extents slide down to 0,1
        assert pool.stats.spill_compactions == 1
        assert sorted(s for k, s in pool._by_hash.values()
                      if k == "spill") == [0, 1]
        got2, fetches = pool.acquire_prefix(toks)
        assert len(got2) == 3 and len(fetches) == 2
        assert {p[:1] for _, p in fetches} <= tags   # bytes survived the move
        del moved


# ----------------------------------------------------- the slow storm -------

@pytest.mark.slow
def test_eintr_storm_invariants_and_reproducibility():
    """Seeded EINTR storm through 3 tenants on a 2-poller group: every
    Completion resolves (echo value, or -EINTR after bounded retries),
    every CQE is reaped exactly once, submitted >= reaped per tenant, and
    two identical runs inject the bit-identical fault schedule.

    Each tenant's calls run sequentially on its own thread: at most one
    in-flight check per (tenant, sysno) key, so the per-key call indices
    — and with them the whole injection schedule — are reproducible even
    though tenants, pollers, and workers all interleave freely."""
    N = 40

    def run():
        with fresh(GenesysConfig(n_workers=2, sched_pollers=2)) as g:
            plan = g.use_fault_plan(FaultPlan(seed=5).inject(
                sysno=int(Sys.ECHO), errnos=(EINTR,), rate=0.3))
            tenants = [g.tenant(f"t{i}") for i in range(3)]
            results = {t.name: [] for t in tenants}

            def caller(t):
                for k in range(N):
                    c = t.submit([(Sys.ECHO, k)], want_cqe=True)[0]
                    results[t.name].append((k, c.result(timeout=30)))

            ths = [threading.Thread(target=caller, args=(t,))
                   for t in tenants]
            for th in ths:
                th.start()
            for th in ths:
                th.join(120)
            assert all(not th.is_alive() for th in ths)
            for t in tenants:
                assert len(results[t.name]) == N
                for k, r in results[t.name]:
                    assert r == k or r == -EINTR, (t.name, k, r)
            reaped = 0
            for t in tenants:
                while True:
                    got = t.reap(max_n=64, timeout=0.5)
                    if not got:
                        break
                    reaped += len(got)
            assert reaped == 3 * N          # nothing lost, nothing doubled
            for t in tenants:
                assert t.stats.submitted >= t.stats.reaped
            ex = g.executor.counters.snapshot()
            assert ex["injected_faults"] == plan.injected > 0
            assert ex["retries"] <= ex["injected_faults"]
            assert ex["retries_exhausted"] <= ex["injected_faults"] // 4
            return plan.digest(), plan.injected, dict(results)

    d1, i1, r1 = run()
    d2, i2, r2 = run()
    assert d1 == d2 and i1 == i2 and r1 == r2   # bit-reproducible
