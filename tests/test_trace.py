"""genesys.trace: Counters consistency, the staged EventRing (order,
wraparound, torn-read freedom under concurrency), histogram accuracy
against an oracle, end-to-end lifecycle tracing through the ring and
tenant paths, the Chrome-trace exporter, and the serving STATS op."""
import json
import socket
import threading
import time

import numpy as np
import pytest

from repro.core.genesys import Genesys, GenesysConfig, Sys
from repro.core.genesys.trace import (EV_ADMIT, EV_COMPLETE, EV_DISPATCH,
                                      EV_FIRST_TOKEN, EV_FUSE_MERGE,
                                      EV_LOOP, EV_NAMES, EV_REAP,
                                      EV_REQ_BEGIN, EV_REQ_END, EV_SQ_POP,
                                      EV_SUBMIT, LOOP_PHASES, Counters,
                                      EventRing, Tracer, bucket_of,
                                      format_summary, latency_histograms,
                                      summary_dict)


# ------------------------------------------------------------------ Counters --

def test_counters_add_bump_snapshot():
    import dataclasses

    @dataclasses.dataclass
    class S:
        a: int = 0
        b: float = 0.0
        hist: dict = dataclasses.field(default_factory=dict)

    c = Counters(S())
    c.add(a=2)
    c.add(a=1, b=0.5)
    c.bump(4, hist="hist")
    c.bump(4, 2, hist="hist")
    snap = c.snapshot()
    assert snap == {"a": 3, "b": 0.5, "hist": {4: 3}}
    # snapshot is a copy: mutating it cannot touch live stats
    snap["hist"][4] = 99
    assert c.snapshot()["hist"] == {4: 3}


def test_counters_dict_stats_and_update():
    c = Counters({})
    c.bump("ECHO")
    c.bump("ECHO", 3)
    c.update(lambda d: d.__setitem__("PREAD64", 7))
    assert c.snapshot() == {"ECHO": 4, "PREAD64": 7}


def test_counters_concurrent_paired_fields_never_tear():
    import dataclasses

    @dataclasses.dataclass
    class S:
        x: int = 0
        y: int = 0

    c = Counters(S())
    stop = threading.Event()

    def adder():
        while not stop.is_set():
            c.add(x=1, y=1)          # always moved together

    ths = [threading.Thread(target=adder, daemon=True) for _ in range(3)]
    for t in ths:
        t.start()
    try:
        for _ in range(300):
            s = c.snapshot()
            assert s["x"] == s["y"]  # one lock round => never half-applied
    finally:
        stop.set()
        for t in ths:
            t.join(5)


# ----------------------------------------------------------------- EventRing --

def test_event_ring_order_and_mixed_columns():
    r = EventRing(64)
    r.append(EV_SUBMIT, 0, 5, 7, aux=3)
    r.append_block(EV_SQ_POP, 1, [10, 11], [100, 101], aux=9)
    r.append_block(EV_DISPATCH, 0, np.array([20, 21]),
                   np.array([200, 201]), own=True)
    r.append_block(EV_REAP, 2, -1, [300])
    s = r.snapshot()
    assert s["ev"].tolist() == [EV_SUBMIT, EV_SQ_POP, EV_SQ_POP,
                                EV_DISPATCH, EV_DISPATCH, EV_REAP]
    assert s["sysno"].tolist() == [5, 10, 11, 20, 21, -1]
    assert s["seq"].tolist() == [7, 100, 101, 200, 201, 300]
    assert s["tenant"].tolist() == [0, 1, 1, 0, 0, 2]
    assert s["aux"].tolist() == [3, 9, 9, 0, 0, 0]
    assert r.total == 6 and r.dropped == 0


def test_event_ring_per_event_timestamps():
    r = EventRing(64)
    r.append_block(EV_LOOP, 0, [0, 1, 2], [7, 7, 7], aux=[5, 6, 7],
                   ts=[100, 105, 111], own=True)
    r.append_block(EV_SUBMIT, 0, 1, [8, 9], ts=np.int64(200))
    s = r.snapshot()
    assert s["ts"].tolist() == [100, 105, 111, 200, 200]
    assert s["sysno"].tolist() == [0, 1, 2, 1, 1]
    assert s["aux"].tolist() == [5, 6, 7, 0, 0]


def test_event_ring_wraparound_keeps_newest():
    r = EventRing(64)
    for i in range(500):                       # 1500 events into 64 slots
        r.append_block(EV_SUBMIT, 0, i, [3 * i, 3 * i + 1, 3 * i + 2])
    assert r.total == 1500 and r.dropped == 1500 - 64
    s = r.snapshot()
    assert len(s) == 64
    assert s["seq"].tolist() == list(range(1500 - 64, 1500))


def test_event_ring_interleaved_snapshot_and_giant_block():
    r = EventRing(64)
    r.append_block(EV_SUBMIT, 0, 1, list(range(40)))
    assert len(r.snapshot()) == 40             # flush, then keep appending
    r.append_block(EV_SUBMIT, 0, 2, list(range(40, 240)))   # 200 > capacity
    s = r.snapshot()
    assert len(s) == 64
    assert s["seq"].tolist() == list(range(176, 240))
    assert r.dropped == 240 - 64


def test_event_ring_concurrent_appenders_no_torn_entries():
    r = EventRing(256)
    stop = threading.Event()

    BASE = 10_000_000

    def writer(tid):
        i = 0
        while not stop.is_set():
            r.append_block(EV_SUBMIT, tid, tid,
                           [tid * BASE + i, tid * BASE + i + 1])
            i += 2

    ths = [threading.Thread(target=writer, args=(t,), daemon=True)
           for t in range(3)]
    for t in ths:
        t.start()
    try:
        for _ in range(100):
            s = r.snapshot()
            if not len(s):
                continue
            assert (s["ev"] == EV_SUBMIT).all()
            # sysno pins the writer; seq must lie in that writer's band —
            # a torn row would mix columns from two writers
            assert (s["seq"] // BASE == s["sysno"]).all()
    finally:
        stop.set()
        for t in ths:
            t.join(5)
    assert r.total >= len(r.snapshot())


# ---------------------------------------------------------------- histograms --

def test_bucket_of_edges():
    assert bucket_of(0.0) == 0 and bucket_of(1.0) == 0
    assert bucket_of(1.5) == 1 and bucket_of(2.0) == 1
    assert bucket_of(2.1) == 2 and bucket_of(1000.0) == 10


def test_latency_histograms_match_synthetic_oracle():
    # 100 calls at ~3µs + 1 straggler at ~1000µs, synthesized exactly
    r = EventRing(1024)
    t0 = 1_000_000
    for i in range(100):
        r.append(EV_SUBMIT, 0, int(Sys.ECHO), i, ts=t0 + i * 10_000)
        r.append(EV_COMPLETE, 0, int(Sys.ECHO), i,
                 ts=t0 + i * 10_000 + 3_000)
    r.append(EV_SUBMIT, 0, int(Sys.ECHO), 100, ts=t0 + 2_000_000)
    r.append(EV_COMPLETE, 0, int(Sys.ECHO), 100,
             ts=t0 + 2_000_000 + 1_000_000)
    h = latency_histograms(r.snapshot(), ["ring"])
    st = h["ring"]["ECHO"]["total"]
    assert st["count"] == 101
    assert st["p50_us"] == 4.0                 # 3µs -> bucket 2 -> edge 4
    assert st["p99_us"] == 4.0                 # 99th of 101 is still 3µs
    assert st["max_us"] == pytest.approx(1000.0)
    assert st["buckets"][2] == 100 and st["buckets"][10] == 1


# -------------------------------------------------------- wiring + lifecycle --

def test_trace_off_by_default(gsys):
    assert gsys.tracer is None
    snap = gsys.telemetry()
    assert snap["trace"] == {"enabled": False}
    assert gsys.call(Sys.ECHO, 42) == 42       # nothing records anything
    assert gsys.telemetry()["histograms"] == {}


def test_ring_lifecycle_events_and_histograms():
    g = Genesys(GenesysConfig(n_workers=2, trace=True))
    try:
        g.ring_submit([(Sys.ECHO, i) for i in range(32)], want_cqe=True)
        got = 0
        while got < 32:
            got += len(g.ring_reap(max_n=32, timeout=5.0))
        g.drain()
        snap = g.telemetry()
        assert snap["trace"]["enabled"] and snap["trace"]["events"] > 0
        evs = g.tracer.events.snapshot()
        kinds = set(evs["ev"].tolist())
        assert {EV_SUBMIT, EV_SQ_POP, EV_DISPATCH, EV_COMPLETE,
                EV_REAP} <= kinds
        assert all(k in EV_NAMES for k in kinds)
        st = snap["histograms"]["ring"]["ECHO"]
        for stage in ("queue", "service", "total", "reap"):
            assert st[stage]["count"] >= 32, stage
        assert st["total"]["p99_us"] >= st["total"]["p50_us"] > 0
    finally:
        g.shutdown()


def test_tenant_trace_opt_in_is_lazy():
    g = Genesys(GenesysConfig(n_workers=2))       # global tracing OFF
    try:
        assert g.tracer is None
        t = g.tenant("latency", trace=True)       # first opt-in creates it
        assert g.tracer is not None
        assert t.ring.trace is g.tracer.channel("latency")
        assert t.call(Sys.ECHO, 9) == 9
        hist = g.telemetry()["histograms"]
        assert hist["latency"]["ECHO"]["total"]["count"] >= 1
        # rings built after the opt-in share the tracer too (lazy shared
        # ring), each under its own channel
        assert g.ring_call(Sys.ECHO, 3) == 3
        assert "ring" in g.tracer.channel_names()
    finally:
        g.shutdown()


def test_summary_helpers():
    g = Genesys(GenesysConfig(n_workers=2, trace=True))
    try:
        g.ring_submit([(Sys.ECHO, i) for i in range(8)], want_cqe=True)
        got = 0
        while got < 8:
            got += len(g.ring_reap(max_n=8, timeout=5.0))
        g.drain()
        snap = g.telemetry()
        s = summary_dict(snap)
        assert s["submitted"] >= s["completed"] >= s["reaped"] >= 8
        assert s["trace"]["enabled"] and s["p99_us"].get("ring", 0) > 0
        json.dumps(s)                          # JSON-safe by construction
        line = format_summary(snap, None, 1.0)
        assert line.startswith("telemetry:") and "p99_us[" in line
    finally:
        g.shutdown()


# ------------------------------------------------ concurrency (satellite #3) --

def test_concurrent_submitters_with_pollers_telemetry_consistent():
    """N tenant submitters + the PollerGroup reaper at full tilt while a
    reader snapshots: every snapshot satisfies submitted >= completed >=
    reaped and the event ring never shows a torn record."""
    g = Genesys(GenesysConfig(n_workers=2, sched_pollers=2, trace=True))
    stop = threading.Event()
    errors: list[BaseException] = []

    def submitter(name):
        t = g.tenant(name)
        i = 0
        try:
            while not stop.is_set():
                futs = t.submit([(Sys.ECHO, i + k) for k in range(8)],
                                want_cqe=True)
                for f in futs:
                    f.result(timeout=5)
                got = 0
                while got < 8:
                    got += len(t.reap(max_n=8, timeout=5))
                i += 8
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ths = [threading.Thread(target=submitter, args=(f"t{k}",), daemon=True)
           for k in range(3)]
    try:
        for t in ths:
            t.start()
        deadline = time.monotonic() + 3.0
        snaps = 0
        while time.monotonic() < deadline and not errors:
            snap = g.telemetry()
            tot = snap["totals"]
            assert tot["submitted"] >= tot["completed"] >= tot["reaped"], tot
            evs = g.tracer.events.snapshot()
            if len(evs):
                assert evs["ev"].min() >= 1 and evs["ev"].max() <= 10
                assert (evs["ts"] > 0).all()   # a torn row would zero ts
            snaps += 1
        assert not errors, errors
        assert snaps >= 10
        stop.set()
        for t in ths:
            t.join(10)
        g.drain()
        final = g.telemetry()["totals"]
        assert final["submitted"] >= final["completed"] >= final["reaped"]
    finally:
        stop.set()
        for t in ths:
            t.join(10)
        g.shutdown()


# ------------------------------------------------------------------- export --

def test_chrome_trace_export_structure(tmp_path):
    g = Genesys(GenesysConfig(n_workers=2, trace=True, ring_fuse=True,
                              ring_batch_max=64))
    out = str(tmp_path / "trace.json")
    try:
        import os
        import tempfile
        fd_t, path = tempfile.mkstemp()
        os.write(fd_t, bytes(range(256)) * 16)
        os.close(fd_t)
        fd = g.call(Sys.OPEN, g.heap.register_bytes(path.encode()),
                    os.O_RDONLY, 0)
        assert fd >= 0
        bufs = [g.heap.new_buffer(64) for _ in range(16)]
        calls = [(Sys.PREAD64, fd, bh, 64, 64 * i)
                 for i, bh in enumerate(bufs)]
        g.ring_submit(calls, want_cqe=True)
        got = 0
        while got < len(calls):
            got += len(g.ring_reap(max_n=64, timeout=5.0))
        g.call(Sys.CLOSE, fd)
        trace = g.export_chrome_trace(out)
        with open(out) as f:
            reloaded = json.load(f)
        assert reloaded["traceEvents"] == trace["traceEvents"]
        evs = trace["traceEvents"]
        pids = {e["pid"] for e in evs if e["ph"] in ("X", "i")}
        assert len(pids) >= 4                   # ring/poller/worker/tenant
        fuse = [e for e in evs if e["ph"] == "X"
                and e["name"].startswith("fuse:")]
        assert fuse and max(len(e["args"]["members"]) for e in fuse) >= 2
        mergers = g.tracer.events.snapshot()
        assert (mergers["ev"] == EV_FUSE_MERGE).sum() >= 2
        os.unlink(path)
    finally:
        g.shutdown()


def test_chrome_trace_export_noop_when_off(gsys, tmp_path):
    out = str(tmp_path / "t.json")
    assert gsys.export_chrome_trace(out) is None
    import os
    assert not os.path.exists(out)


# ------------------------------------------------------------- serving STATS --

def test_server_stats_op_returns_telemetry_json():
    from repro.serving.server import STATS_MAGIC, GenesysUdpServer
    g = Genesys(GenesysConfig(n_workers=2, trace=True))
    srv = GenesysUdpServer(g, port=0, max_batch=4, payload=256)
    try:
        port = g.table._sockets[srv.fd].getsockname()[1]
        client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        client.bind(("127.0.0.1", 0))
        cport = client.getsockname()[1]
        client.settimeout(5)
        th = threading.Thread(
            target=lambda: srv.serve_echo(n_batches=1, reply_port=cport),
            daemon=True)
        th.start()
        client.sendto(STATS_MAGIC + cport.to_bytes(4, "little"),
                      ("127.0.0.1", port))
        client.sendto(b"after-stats", ("127.0.0.1", port))
        got = [client.recvfrom(60000)[0] for _ in range(2)]
        th.join(5)
        snap = json.loads(next(d for d in got if d != b"after-stats"))
        assert b"after-stats" in got
        assert snap["trace"]["enabled"] is True
        assert snap["totals"]["submitted"] >= snap["totals"]["completed"]
        assert srv.stats.stats_requests == 1
        assert srv.stats.requests == 1          # STATS is not a request
        client.close()
    finally:
        srv.close()
        g.shutdown()


# ------------------------------------------------ serving-loop phases ----

class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts constructions
    and stamps ``serve.iter`` entries and exits on perf_counter_ns."""
    made = 0
    iters: list = []

    def __init__(self, name, **kw):
        type(self).made += 1
        self.name = name

    def __enter__(self):
        if self.name == "serve.iter":
            self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self.name == "serve.iter":
            type(self).iters.append((self.t0, time.perf_counter_ns()))


def _serve_tiny(trace: bool, monkeypatch, tmp_path=None):
    """Three requests through serve_model_continuous with a stub step
    (2 ms a step) -> (replies by tag, events or None, chrome trace)."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ContinuousBatchEngine
    from repro.serving.pagedkv import PagedKVPool
    from repro.serving.server import GenesysUdpServer
    monkeypatch.setattr(_CountingAnnotation, "made", 0)
    monkeypatch.setattr(_CountingAnnotation, "iters", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)

    def step(p, a, bt, cur, cl):
        time.sleep(0.002)
        return cur[:, 0] * 2 + 1, a

    g = Genesys(GenesysConfig(n_workers=2, trace=trace))
    try:
        NB, BS = 8, 4
        arenas = {"k": jnp.zeros((1, NB, BS, 1, 1)),
                  "v": jnp.zeros((1, NB, BS, 1, 1))}
        eng = ContinuousBatchEngine(step, {}, arenas, PagedKVPool(NB, BS),
                                    n_slots=2, max_blocks_per_seq=4)
        eng.pool.bind_genesys(g, block_bytes=64)   # MADVISE on retire
        srv = GenesysUdpServer(g, port=0, max_batch=4, payload=256,
                               batch_window_s=0.02, use_ring=True)
        g.table._sockets[srv.fd].settimeout(0.05)  # cheap idle polls
        port = g.table._sockets[srv.fd].getsockname()[1]
        client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        client.bind(("127.0.0.1", 0))
        client.settimeout(10)
        th = threading.Thread(
            target=lambda: srv.serve_model_continuous(
                eng, reply_port=client.getsockname()[1], n_requests=3,
                max_idle_polls=50),
            daemon=True)
        th.start()
        time.sleep(0.05)
        # [budget, tag, prompt...]; three requests for two slots, so one
        # waits in the queue for a retirement
        for req in ([3, 900, 5], [2, 901, 7, 8], [4, 902, 1, 2, 3]):
            client.sendto(np.asarray(req, np.int32).tobytes(),
                          ("127.0.0.1", port))
        replies = {}
        for _ in range(3):
            words = np.frombuffer(client.recvfrom(4096)[0], np.int32)
            replies[int(words[0])] = words[1:].tolist()
        th.join(20)
        assert not th.is_alive()
        client.close()
        srv.close()
        evs = g.tracer.events.snapshot() if trace else None
        chrome = (g.export_chrome_trace(str(tmp_path / "trace.json"))
                  if trace and tmp_path is not None else None)
    finally:
        g.shutdown()
    assert sorted(replies) == [900, 901, 902]
    return replies, evs, chrome


def test_loop_tracing_off_records_nothing_and_annotates_nothing(monkeypatch):
    replies, evs, _ = _serve_tiny(False, monkeypatch)
    assert evs is None                        # no tracer, so no events
    assert _CountingAnnotation.made == 0
    assert [len(replies[t]) for t in (900, 901, 902)] == [3, 2, 4]


def test_loop_tracing_on_annotates_every_phase(monkeypatch):
    _, evs, _ = _serve_tiny(True, monkeypatch)
    loop = evs[evs["ev"] == EV_LOOP]
    n_iters = len(np.unique(loop["seq"]))
    # one serve.iter and one serve.<phase> annotation per recorded phase
    assert _CountingAnnotation.made == n_iters + len(loop)
    assert len(_CountingAnnotation.iters) == n_iters


def test_request_milestones_in_order(monkeypatch):
    _, evs, _ = _serve_tiny(True, monkeypatch)

    def by_span(ev):
        e = evs[evs["ev"] == ev]
        return dict(zip(e["seq"].tolist(), e["ts"].tolist()))

    begin, admit, first, end = (by_span(ev) for ev in (
        EV_REQ_BEGIN, EV_ADMIT, EV_FIRST_TOKEN, EV_REQ_END))
    assert len(end) == 3
    for span in end:
        assert begin[span] <= admit[span] <= first[span] <= end[span]
    adm = evs[evs["ev"] == EV_ADMIT]
    assert sorted(adm["aux"].tolist()) == [900, 901, 902]   # wire tags


def test_loop_phases_tile_each_iteration(monkeypatch):
    _, evs, _ = _serve_tiny(True, monkeypatch)
    loop = evs[evs["ev"] == EV_LOOP]
    covered = 0
    stepped = 0
    for it in np.unique(loop["seq"]):
        ph = loop[loop["seq"] == it]
        ph = ph[np.argsort(ph["ts"], kind="stable")]
        codes = ph["sysno"].tolist()
        assert codes == sorted(codes) and len(set(codes)) == len(codes)
        assert codes[0] == LOOP_PHASES.index("poll")
        # no overlap and no hole: each phase starts where the last ended
        assert (ph["ts"][1:] == ph["ts"][:-1] + ph["aux"][:-1]).all()
        covered += int(ph["aux"].sum())
        stepped += LOOP_PHASES.index("dispatch") in codes
    assert stepped >= 4                        # budgets 3, 2, 4 on 2 slots
    # the serve.iter annotations bracket each iteration on their own
    # clock reading: the phases cover nearly all of that wall time
    wall = sum(b - a for a, b in _CountingAnnotation.iters)
    assert covered >= 0.95 * wall


def test_chrome_trace_has_the_loop_track(monkeypatch, tmp_path):
    _, evs, chrome = _serve_tiny(True, monkeypatch, tmp_path)
    evts = chrome["traceEvents"]
    assert {"ph": "M", "pid": 6, "tid": 0, "name": "process_name",
            "args": {"name": "loop"}} in evts
    spans = [e for e in evts if e["ph"] == "X" and e["pid"] == 6]
    assert len(spans) == int((evs["ev"] == EV_LOOP).sum())
    assert {e["name"] for e in spans} <= set(LOOP_PHASES)
    assert {"poll", "dispatch", "sync", "reply"} <= {e["name"] for e in spans}
