"""The serving-loop readers on made-up event arrays, the clock pairing
on a profile recorded on the CPU, and the idle-gap labels the loop's
annotations give."""
import glob
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import loop
import tracefile
from repro.core.genesys.trace import EVENT_DTYPE

MS = 1_000_000          # ns
W0, W1 = 1000 * MS, 2000 * MS
POLL, ADMIT, DISPATCH, SYNC, ADVANCE, RETIRE, REPLY = range(7)


def events(rows):
    """``(ev, sysno, seq, ts, aux)`` rows -> a program event array."""
    out = np.zeros(len(rows), EVENT_DTYPE)
    for i, (ev, sysno, seq, ts, aux) in enumerate(rows):
        out[i] = (ts, ev, 0, sysno, seq, aux)
    return out


def iteration(it, t0, phase_ms):
    """One loop iteration's LOOP events, its phases back to back."""
    rows, t = [], t0
    for code, ms in phase_ms:
        rows.append((loop.EV_LOOP, code, it, t, ms * MS))
        t += ms * MS
    return rows


def loop_events():
    stepped = [(POLL, 1), (ADMIT, 0.5), (DISPATCH, 1), (SYNC, 27),
               (ADVANCE, 0.5), (RETIRE, 2), (REPLY, 1)]
    return events(
        iteration(0, W0 - 40 * MS, stepped)               # before: out
        + iteration(1, W0, stepped)                       # host 6 ms
        + iteration(2, W0 + 40 * MS, [(POLL, 200), (ADMIT, 0),
                                      (REPLY, 0)])        # idle: no step
        + iteration(3, W0 + 300 * MS, [(POLL, 3), (ADMIT, 1),
                                       (DISPATCH, 1), (SYNC, 25),
                                       (ADVANCE, 1), (RETIRE, 0),
                                       (REPLY, 0)])       # host 6 ms...
        + iteration(4, W1, stepped))                      # at the close


def fake_run(evs, requests=()):
    return SimpleNamespace(events=evs, w0_ns=W0, w1_ns=W1,
                           requests=list(requests))


def test_loop_host_ms_per_step_over_stepping_iterations_in_the_window():
    run = fake_run(loop_events())
    # iterations 1 and 3: 33 - 27 and 31 - 25 ms; the idle one and those
    # outside [w0, w1) do not count
    assert harness.load_reader("loop_host_ms_per_step")(run) == \
        pytest.approx(6.0)
    ms = loop.phase_ms_per_step(run.events, W0, W1)
    assert ms["sync"] == pytest.approx(26.0)
    assert ms["retire"] == pytest.approx(1.0)


def admissions():
    # tags 0..9 sent 10 ms apart from the window's opening; tag k is
    # admitted 10*k ms after its send, except tag 9, never admitted; tag
    # 10 is sent after the close; the last event is at W0 + 600 ms
    rows = [(loop.EV_ADMIT, -2, 100 + k, W0 + (10 * k + 10 * k) * MS, k)
            for k in range(9)]
    rows.append((13, 5, 1, W0 + 600 * MS, 0))        # some later event
    sent = [W0 + 10 * k * MS for k in range(10)] + [W1 + MS]
    return events(rows), sent


def test_queue_wait_p90_counts_the_unadmitted_until_the_last_event():
    evs, sent = admissions()
    waits = loop.queue_waits_ms(evs, W0, W1, sent)
    assert waits == pytest.approx([10.0 * k for k in range(9)] + [510.0])
    reqs = [{"sent_s": (s - W0) / 1e9} for s in sent]
    got = harness.load_reader("queue_wait_p90_ms")(fake_run(evs, reqs))
    # nearest rank: the 9th of 10 sorted waits
    assert got == pytest.approx(80.0, abs=1e-3)


def test_prefill_p50_leaves_out_first_tokens_after_the_close():
    rows = []
    for k in range(5):             # admitted at W0 + k*100 ms, first token
        span = 200 + k             # (k + 1) * 50 ms later
        rows.append((loop.EV_ADMIT, -2, span, W0 + k * 100 * MS, k))
        rows.append((loop.EV_FIRST_TOKEN, -2, span,
                     W0 + (k * 100 + (k + 1) * 50) * MS, 7))
    rows.append((loop.EV_ADMIT, -2, 300, W1 - MS, 5))          # too late
    rows.append((loop.EV_FIRST_TOKEN, -2, 300, W1 + 9 * MS, 9))
    rows.append((loop.EV_ADMIT, -2, 301, W0 - MS, 6))          # before
    evs = events(sorted(rows, key=lambda r: r[3]))
    times, left = loop.prefill_ms(evs, W0, W1)
    assert sorted(times) == pytest.approx([50, 100, 150, 200, 250])
    assert left == 1
    assert harness.load_reader("prefill_p50_ms")(fake_run(evs)) == \
        pytest.approx(150.0)


@pytest.mark.parametrize("metric", ["loop_host_ms_per_step",
                                    "queue_wait_p90_ms", "prefill_p50_ms"])
def test_readers_read_nothing_without_the_loop_events(metric):
    # a program that records only calls and steps, as before these
    # events existed, and a run with no program trace at all
    old = events([(1, 3, 1, W0 + MS, 0), (5, 3, 1, W0 + 2 * MS, 0),
                  (13, 0, 7, W0 + 3 * MS, 25 * MS)])
    reqs = [{"sent_s": 0.001}]
    read = harness.load_reader(metric)
    assert read(fake_run(old, reqs)) is None
    assert read(fake_run(None, reqs)) is None


def test_clock_pairing_on_a_cpu_profile(tmp_path):
    import jax

    from repro.core.genesys.trace import (LOOP_ADVANCE, LOOP_SYNC, Tracer,
                                          LoopRecorder)
    tracer = Tracer(1024)
    rec = LoopRecorder(tracer.channel("loop"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(20):
            rec.begin()
            rec.phase(LOOP_SYNC)
            sum(range(2000))
            rec.phase(LOOP_ADVANCE)
            rec.end()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    iters = loop.serve_iters(path[-1])
    assert sorted(iters) == list(range(20))
    off = loop.clock_offsets(tracer.events.snapshot(), iters)
    assert len(off) == 20
    assert np.median(np.abs(off - np.median(off))) < 1e5     # 0.1 ms
    # the profile names each phase where the host ran it
    tr = tracefile.load_xplane(path[-1])
    names = {n for n, _, _ in tr["host"]}
    assert {"serve.iter", "serve.poll", "serve.sync",
            "serve.advance"} <= names


def test_a_gap_inside_serve_sync_is_named_by_it():
    # device busy [0,5) and [9,12) ms; the host was in serve.sync from 4
    # to 11 ms inside serve.iter, so the gap [5,9) is the sync's
    tr = {"ops": [["fusion.1", 0, 5 * MS], ["fusion.2", 9 * MS, 3 * MS]],
          "modules": [],
          "host": [["serve.iter", 0, 13 * MS], ["serve.dispatch", 3 * MS,
                                                 1 * MS],
                   ["serve.sync", 4 * MS, 7 * MS],
                   ["serve.advance", 11 * MS, 2 * MS]]}
    r = tracefile.reduce(tr, "x")
    assert r["idle_gaps"] == [["serve.sync", pytest.approx(4e-3)]]


RECORDED = Path(__file__).parent / "data" / "trace_sample_serve.json"


def test_recorded_gaps_inside_serve_sync_are_named_from_it():
    # three loop iterations of internlm2-20b.chat recorded on the chip:
    # every idle gap whose middle lies in a serve.sync span is named by
    # that span or by an annotation nested inside it
    tr = json.loads(RECORDED.read_text())
    busy = tracefile.union(np.asarray([[s, s + d] for _, s, d in tr["ops"]]))
    syncs = [(s, s + d) for n, s, d in tr["host"] if n == "serve.sync"]
    assert len(syncs) >= 3
    named = 0
    for (_, e0), (s1, _) in zip(busy[:-1], busy[1:]):
        mid = 0.5 * (e0 + s1)
        inside = [(a, b) for a, b in syncs if a <= mid <= b]
        if not inside:
            continue
        a, b = inside[0]
        label = tracefile.host_label(tr["host"], e0, s1)
        assert any(n == label and a <= s and s + d <= b
                   for n, s, d in tr["host"]), label
        named += 1
    assert named > 0
    # and every gap over a millisecond lies in a loop phase that names it
    phases = [(s, s + d) for n, s, d in tr["host"]
              if n.startswith("serve.") and n != "serve.iter"]
    for (_, e0), (s1, _) in zip(busy[:-1], busy[1:]):
        if s1 - e0 < 1e6:
            continue
        label = tracefile.host_label(tr["host"], e0, s1)
        mid = 0.5 * (e0 + s1)
        a, b = next((a, b) for a, b in phases if a <= mid <= b)
        assert any(n == label and a <= s and s + d <= b
                   for n, s, d in tr["host"]), label
