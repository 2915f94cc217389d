"""genesys.metrics: windowed registry math, Prometheus exposition, the
collector bridge, request-scoped tracing, and the serving control ops."""
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core.genesys import (
    Genesys, GenesysConfig, MetricsHttpServer, MetricsRegistry, Sys,
)
from repro.core.genesys.metrics import N_BUCKETS
from repro.core.genesys.trace import EV_SUBMIT


# --------------------------------------------------------- registry math ----

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry(n_windows=4)
    c = reg.counter("reqs_total", "requests", tenant="a")
    g = reg.gauge("depth")
    h = reg.histogram("lat_us", tenant="a")
    c.inc()
    c.inc(4)
    g.set(7)
    g.inc(-2)
    for us in (3.0, 3.0, 100.0):
        h.observe(us)
    assert c.value == 5
    assert g.value == 5
    assert reg.quantile("lat_us", 0.5, tenant="a") == 4.0    # bucket_of(3)=2
    assert reg.quantile("lat_us", 0.99, tenant="a") == 128.0


def test_series_identity_and_growth():
    reg = MetricsRegistry(n_windows=4)
    # same (name, labels) -> same slot; label order irrelevant
    a = reg.counter("x_total", t="1", s="2")
    b = reg.counter("x_total", s="2", t="1")
    assert a.idx == b.idx
    # force the scalar arrays to double several times
    handles = [reg.counter("many_total", i=str(i)) for i in range(300)]
    for hd in handles:
        hd.inc(hd.idx)
    reg.tick(now=1.0)
    for hd in handles:
        assert hd.value == hd.idx


def test_rate_across_windows():
    reg = MetricsRegistry(n_windows=8)
    c = reg.counter("n_total")
    reg.tick(now=10.0)
    c.inc(50)
    reg.tick(now=12.0)
    assert reg.rate("n_total") == pytest.approx(25.0)
    c.inc(30)
    reg.tick(now=13.0)
    assert reg.rate("n_total") == pytest.approx(30.0)
    assert reg.rate("n_total", span=2) == pytest.approx(80 / 3)
    # span clamped to available history
    assert reg.rate("n_total", span=99) == pytest.approx(80 / 3)
    assert reg.rate("nope_total") == 0.0


def test_windowed_quantile_and_wrap():
    reg = MetricsRegistry(n_windows=4)
    h = reg.histogram("lat_us")
    # fill more ticks than windows: old history must fall away cleanly
    for i in range(7):
        h.observe(2.0 ** (i + 1))          # one observation per window
        reg.tick(now=float(i))
    # span=1 right after a tick = observations since the latest snapshot
    # (there are none); span=2 covers the last full window interval
    assert reg.quantile("lat_us", 0.99, span=1) == 0.0
    assert reg.quantile("lat_us", 0.99, span=2) == 2.0 ** 7
    assert reg.quantile("lat_us", 0.99, span=None) == 2.0 ** 7  # all-time


def test_observe_block_matches_scalar_observes():
    reg = MetricsRegistry(n_windows=4)
    h1 = reg.histogram("a_us")
    h2 = reg.histogram("b_us")
    samples = [0.5, 1.0, 3.0, 9.0, 1000.0, 2.0 ** 50]
    for s in samples:
        h1.observe(s)
    h2.observe_block(np.asarray(samples))
    with reg._lock:
        assert (reg._hb[h1.idx] == reg._hb[h2.idx]).all()
        assert reg._hb[h1.idx, N_BUCKETS - 1] == 1    # clamp, no overflow
        assert reg._hsum[h1.idx] == pytest.approx(reg._hsum[h2.idx])


def test_slo_burn_rate_gauge():
    reg = MetricsRegistry(n_windows=8)
    h = reg.histogram("wall_us", tenant="t0")
    reg.set_slo("wall_us", 100.0, target=0.9, window=4)
    reg.tick(now=0.0)                     # baseline snapshot
    for _ in range(90):
        h.observe(10.0)
    for _ in range(10):
        h.observe(10_000.0)               # 10% violations = exactly budget
    reg.tick(now=1.0)
    burns = reg.burn_rates()
    assert burns == {'wall_us{tenant="t0"}': pytest.approx(1.0)}
    # the derived gauge is visible in the exposition after the tick
    assert 'genesys_slo_burn_rate{slo="wall_us",tenant="t0"}' \
        in reg.prometheus_text()
    # burn decays once the violations age out of the burn window
    for i in range(6):
        h.observe(10.0)
        reg.tick(now=2.0 + i)
    assert reg.burn_rates()['wall_us{tenant="t0"}'] == 0.0


def test_prometheus_text_format_and_escaping():
    reg = MetricsRegistry(n_windows=4)
    reg.set("g", 1.5, path='we"ird\\la\nbel')
    reg.inc("c_total", 3)
    h = reg.histogram("h_us")
    h.observe(3.0)
    txt = reg.prometheus_text()
    assert "# TYPE c_total counter" in txt
    assert "# TYPE g gauge" in txt
    assert "# TYPE h_us histogram" in txt
    assert 'g{path="we\\"ird\\\\la\\nbel"} 1.5' in txt
    lines = dict(l.rsplit(" ", 1) for l in txt.splitlines()
                 if not l.startswith("#"))
    assert lines['h_us_bucket{le="2"}'] == "0"
    assert lines['h_us_bucket{le="4"}'] == "1"      # cumulative
    assert lines['h_us_bucket{le="+Inf"}'] == "1"
    assert lines["h_us_count"] == "1"
    assert float(lines["h_us_sum"]) == pytest.approx(3.0)


def test_concurrent_observers_lose_nothing():
    reg = MetricsRegistry(n_windows=4)
    c = reg.counter("n_total")
    h = reg.histogram("l_us")
    N, T = 2000, 4

    def work():
        for _ in range(N):
            c.inc()
            h.observe(5.0)

    ths = [threading.Thread(target=work) for _ in range(T)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert c.value == N * T
    assert reg.quantile("l_us", 0.5) == 8.0
    with reg._lock:
        assert reg._hb[h.idx].sum() == N * T


# ----------------------------------------------------- genesys collector ----

def test_install_genesys_collector_mirrors_telemetry(gsys):
    reg = gsys.metrics                       # lazy; installs the collector
    assert gsys.metrics is reg               # one registry per instance
    for _ in range(5):
        gsys.ring_call(Sys.ECHO, 1)
    reg.tick()
    txt = reg.prometheus_text()
    assert "genesys_submitted_total" in txt
    assert 'genesys_syscalls_total{sysno="ECHO"} 5' in txt
    completed = [l for l in txt.splitlines()
                 if l.startswith("genesys_completed_total")][0]
    assert int(completed.rsplit(" ", 1)[1]) >= 5


def test_attach_stats_joins_telemetry_snapshot(gsys):
    """Satellite: engine/pool stats fold onto trace.Counters and surface
    in the single coherent Genesys.telemetry() snapshot."""
    import jax.numpy as jnp

    from repro.serving.engine import ContinuousBatchEngine, EngineStats
    from repro.serving.pagedkv import PagedKVPool
    NB, BS = 8, 4
    arenas = {"k": jnp.zeros((1, NB, BS, 1, 1)),
              "v": jnp.zeros((1, NB, BS, 1, 1))}
    pool = PagedKVPool(NB, BS)
    eng = ContinuousBatchEngine(lambda p, a, bt, cur, cl: (cur[:, 0], a),
                                {}, arenas, pool,
                                prefill_chunk=lambda p, a, *_: a, n_slots=2,
                                max_blocks_per_seq=4)
    gsys.attach_stats("engine", eng.counters)
    gsys.attach_stats("pagedkv", pool.counters)
    assert eng.admit([1, 2, 3], 2)
    while eng.n_active:
        eng.step()
    srv_section = gsys.telemetry()["serving"]
    assert srv_section["engine"]["admitted"] == 1
    assert srv_section["engine"]["retired"] == 1
    assert srv_section["pagedkv"]["allocs"] >= 1
    assert srv_section["pagedkv"]["blocks_in_use"] == 0
    # benchmark reset idiom keeps attached references live
    eng.stats = EngineStats()
    assert gsys.telemetry()["serving"]["engine"]["admitted"] == 0
    reg = gsys.metrics
    reg.tick()
    assert "genesys_engine_admitted_total" in reg.prometheus_text()


# ----------------------------------------------------------- HTTP server ----

def test_metrics_http_server_routes():
    reg = MetricsRegistry(n_windows=4)
    reg.inc("hits_total")
    srv = MetricsHttpServer(reg, telemetry_fn=lambda: {"deep": {"k": 1}})
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5).read()
        assert b"hits_total 1" in body
        tel = json.loads(urllib.request.urlopen(
            f"{base}/telemetry", timeout=5).read())
        assert tel == {"deep": {"k": 1}}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        srv.close()
    assert reg._wn >= 1                      # scrapes tick the registry


# ----------------------------------------- serving control ops (UDP+TCP) ----

def _control_op(gsys, srv, magic):
    """Send a control datagram mid-echo-serve; return the reply bytes."""
    port = gsys.table._sockets[srv.fd].getsockname()[1]
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.bind(("127.0.0.1", 0))
    client.settimeout(10)
    th = threading.Thread(
        target=lambda: srv.serve_echo(
            n_batches=99, reply_port=client.getsockname()[1], n_requests=1),
        daemon=True)
    th.start()
    time.sleep(0.05)
    rp = client.getsockname()[1].to_bytes(4, "little")
    client.sendto(magic + rp, ("127.0.0.1", port))
    data, _ = client.recvfrom(65507)
    client.sendto(np.asarray([1], np.int32).tobytes(), ("127.0.0.1", port))
    client.recvfrom(65507)                   # the echo, ends the serve
    th.join(10)
    assert not th.is_alive()
    client.close()
    return data


def test_stats_op_truncation_flag_and_tcp_full_payload(gsys, monkeypatch):
    """Satellite: the UDP STATS fallback says ``"truncated": true``; the
    TCP /telemetry exposition carries the full payload regardless."""
    from repro.serving import server as server_mod
    from repro.serving.server import STATS_MAGIC, GenesysUdpServer
    srv = GenesysUdpServer(gsys, port=0, max_batch=2, payload=256,
                           batch_window_s=0.02, use_ring=True)
    monkeypatch.setattr(server_mod, "_STATS_MAX_DGRAM", 64)
    reply = json.loads(_control_op(gsys, srv, STATS_MAGIC))
    assert reply["truncated"] is True
    assert "histograms" not in reply         # the summary fallback
    http = MetricsHttpServer(gsys.metrics, telemetry_fn=gsys.telemetry)
    try:
        full = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{http.port}/telemetry", timeout=5).read())
    finally:
        http.close()
    assert "histograms" in full and "totals" in full   # nothing elided
    assert "truncated" not in full
    srv.close()


def test_metrics_udp_op_returns_prometheus_text(gsys):
    from repro.serving.server import METRICS_MAGIC, GenesysUdpServer
    srv = GenesysUdpServer(gsys, port=0, max_batch=2, payload=256,
                           batch_window_s=0.02, use_ring=True)
    text = _control_op(gsys, srv, METRICS_MAGIC).decode()
    assert "# TYPE genesys_submitted_total counter" in text
    assert "genesys_server_requests_total" in text     # attach_stats fold
    assert srv.stats.stats_requests == 1
    srv.close()


# --------------------------------------- reporter thread / format_summary ----

def test_start_stats_reporter_emits_and_stops(gsys):
    """Satellite: the --stats-interval reporter starts, emits summary
    lines, and shuts down cleanly on its stop event."""
    from repro.launch.serve import start_stats_reporter
    lines = []
    th, stop = start_stats_reporter(gsys, 0.05, out=lines.append)
    gsys.ring_call(Sys.ECHO, 3)
    for _ in range(100):
        if lines:
            break
        time.sleep(0.05)
    stop.set()
    th.join(5)
    assert not th.is_alive()
    assert lines
    assert all(isinstance(l, str) and "submitted=" in l for l in lines)


def test_format_summary_rate_math():
    from repro.core.genesys.trace import format_summary
    prev = {"totals": {"submitted": 100, "completed": 100, "reaped": 90}}
    snap = {"totals": {"submitted": 400, "completed": 350, "reaped": 300}}
    line = format_summary(snap, prev, 2.0)
    assert "rate=125/s" in line              # (350-100)/2
    line2 = format_summary(snap)             # no dt: absolute counts only
    assert "submitted=400" in line2 and "rate=" not in line2


# ----------------------------------------------- request-scoped tracing ----

def test_request_spans_nest_steps_and_syscalls(tmp_path):
    """End to end: continuous serving with tracing on produces a Chrome
    trace whose pid-5 request spans nest the request's decode steps and
    at least one span-attributed syscall."""
    import jax.numpy as jnp

    from repro.serving.engine import ContinuousBatchEngine
    from repro.serving.pagedkv import PagedKVPool
    from repro.serving.server import GenesysUdpServer
    g = Genesys(GenesysConfig(n_workers=2, trace=True))
    try:
        NB, BS = 8, 4
        arenas = {"k": jnp.zeros((1, NB, BS, 1, 1)),
                  "v": jnp.zeros((1, NB, BS, 1, 1))}
        eng = ContinuousBatchEngine(
            lambda p, a, bt, cur, cl: (cur[:, 0] * 2 + 1, a),
            {}, arenas, PagedKVPool(NB, BS),
            prefill_chunk=lambda p, a, *_: a, n_slots=2,
            max_blocks_per_seq=4)
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
                eng, reply_port=client.getsockname()[1], n_requests=2,
                max_idle_polls=50),
            daemon=True)
        th.start()
        time.sleep(0.05)
        for req in ([3, 900, 5], [2, 901, 7, 8]):   # [budget, tag, prompt..]
            client.sendto(np.asarray(req, np.int32).tobytes(),
                          ("127.0.0.1", port))
        for _ in range(2):
            client.recvfrom(4096)
        th.join(20)
        assert not th.is_alive()
        client.close()
        srv.close()
        trace = g.export_chrome_trace(str(tmp_path / "trace.json"))
    finally:
        g.shutdown()
    assert trace["metadata"]["dropped_spans"] == 0
    evs = [e for e in trace["traceEvents"] if e.get("pid") == 5]
    reqs = [e for e in evs if e.get("name") == "request"]
    steps = [e for e in evs if str(e.get("name", "")).startswith("step:")]
    syss = [e for e in evs if str(e.get("name", "")).startswith("sys:")]
    assert len(reqs) == 2 and steps and syss

    def nested(inner, outer):
        return (inner["tid"] == outer["tid"]
                and inner["ts"] >= outer["ts"]
                and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])

    for r in reqs:
        assert r["args"]["tokens"] > 0
        assert any(nested(s, r) for s in steps)
    # at least one request nests a span-attributed syscall (the retire
    # MADVISE completes synchronously before REQ_END)
    assert any(nested(s, r) for r in reqs for s in syss)


def test_export_chrome_trace_counts_dropped_spans(tmp_path):
    """Satellite: spans beyond max_spans are counted, never silently cut."""
    g = Genesys(GenesysConfig(trace=True))
    p = str(tmp_path / "t.json")
    try:
        for _ in range(40):
            g.ring_call(Sys.ECHO, 1)
        g.drain()
        full = g.tracer.export_chrome_trace(p, max_spans=10 ** 6)
        cut = g.tracer.export_chrome_trace(p, max_spans=20)
    finally:
        g.shutdown()
    assert full["metadata"]["dropped_spans"] == 0
    n_x = len([e for e in full["traceEvents"] if e["ph"] in ("X", "i")])
    assert cut["metadata"]["dropped_spans"] > 0
    kept = len([e for e in cut["traceEvents"] if e["ph"] in ("X", "i")])
    assert kept + cut["metadata"]["dropped_spans"] == n_x


def test_span_context_tags_submit_aux(gsys):
    """Syscalls submitted under Tracer.span carry the span id in their
    SUBMIT aux; outside the context aux stays 0."""
    t = gsys.tenant("spans", trace=True)
    tracer = gsys.tracer
    with tracer.span(4242):
        t.call(Sys.ECHO, 1)
    t.call(Sys.ECHO, 2)
    gsys.drain()
    evs = tracer.events.snapshot()
    subs = evs[evs["ev"] == EV_SUBMIT]
    assert 4242 in subs["aux"]
    assert 0 in subs["aux"]
    assert tracer.current_span() == 0       # context restored
