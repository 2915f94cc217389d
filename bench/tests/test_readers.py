"""Metric readers over a run's counters, on made-up readings."""
from types import SimpleNamespace

import harness


def fake_run(**kw):
    run = dict(engine0={"admitted": 1, "step_slots": 10, "prefill_steps": 4},
               engine1={"admitted": 41, "step_slots": 1010,
                        "prefill_steps": 704},
               totals0={"submitted": 100}, totals1={"submitted": 1300})
    run.update(kw)
    return SimpleNamespace(**run)


def test_syscalls_per_request_counts_admissions_in_the_window():
    # 1200 calls over the 40 requests admitted in the window, whatever
    # number of them was answered by its close
    assert harness.load_reader("syscalls_per_request")(fake_run()) == 30.0


def test_syscalls_per_request_reads_nothing_without_admissions():
    run = fake_run(engine1={"admitted": 1, "step_slots": 10,
                            "prefill_steps": 4})
    assert harness.load_reader("syscalls_per_request")(run) is None


def test_prefill_slot_share_in_percent():
    assert harness.load_reader("prefill_slot_share")(fake_run()) == 70.0
