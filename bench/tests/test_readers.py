"""Metric readers over a run's counters and its reduced device trace,
on made-up readings."""
from types import SimpleNamespace

import harness


def fake_run(**kw):
    run = dict(engine0={"admitted": 1}, engine1={"admitted": 41},
               totals0={"submitted": 100}, totals1={"submitted": 1300})
    run.update(kw)
    return SimpleNamespace(**run)


def test_syscalls_per_request_counts_admissions_in_the_window():
    # 1200 calls over the 40 requests admitted in the window, whatever
    # number of them was answered by its close
    assert harness.load_reader("syscalls_per_request")(fake_run()) == 30.0


def test_syscalls_per_request_reads_nothing_without_admissions():
    run = fake_run(engine1={"admitted": 1})
    assert harness.load_reader("syscalls_per_request")(run) is None


def test_prefill_device_share_in_percent():
    # 3 chunks of 0.5 s in 30 s of device-busy time; without a chunk, or
    # without a trace, nothing is read
    trace = {"busy_s": 30.0, "chunk_device_s": [0.5, 0.5, 0.5]}
    read = harness.load_reader("prefill_device_share")
    assert read(fake_run(trace=trace)) == 5.0
    assert read(fake_run(trace=dict(trace, chunk_device_s=[]))) is None
    assert read(fake_run(trace=None)) is None
