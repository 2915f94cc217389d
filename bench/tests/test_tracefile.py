"""The reduction from a device trace to busy time, step times, top
operations and labelled idle gaps: exact on a hand-made trace, and
checked against a plain sweep on a slice recorded on the chip."""
import json
from pathlib import Path

import numpy as np
import pytest

import tracefile

MS = 1e6    # ns


def hand_trace():
    # ops (ns): [0,2) [1,3) overlap -> busy [0,3); [5,6); [10,14)
    # busy 3 + 1 + 4 = 8 ms; gaps [3,5) 2 ms, [6,10) 4 ms
    return {
        "ops": [["fusion.1", 0, 2 * MS], ["copy.2", 1 * MS, 2 * MS],
                ["fusion.1", 5 * MS, 1 * MS], ["dot.3", 10 * MS, 4 * MS]],
        "modules": [["jit_paged_serve_step(12)", 0, 6 * MS],
                    ["jit_other(3)", 10 * MS, 4 * MS],
                    ["jit_paged_serve_step(12)", 10 * MS, 4 * MS]],
        "host": [["PjitFunction(paged_serve_step)", 2.5 * MS, 2 * MS],
                 ["outer", 2 * MS, 10 * MS]],
    }


def test_busy_steps_and_ops_by_hand():
    r = tracefile.reduce(hand_trace(), "paged_serve_step")
    assert r["busy_s"] == pytest.approx(8e-3)
    assert r["step_device_s"] == pytest.approx([6e-3, 4e-3])
    assert r["device_ops"][0] == ["dot.3", pytest.approx(4e-3)]
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(3e-3)


def test_chunk_module_time_beside_the_steps():
    tr = hand_trace()
    tr["modules"].append(["jit_paged_prefill_chunk(7)", 6 * MS, 2 * MS])
    r = tracefile.reduce(tr, "paged_serve_step",
                         chunk_module="paged_prefill_chunk")
    assert r["chunk_device_s"] == pytest.approx([2e-3])
    assert r["step_device_s"] == pytest.approx([6e-3, 4e-3])
    assert tracefile.reduce(tr, "paged_serve_step")["chunk_device_s"] == []


def test_idle_gaps_longest_first_with_the_innermost_host_label():
    r = tracefile.reduce(hand_trace(), "paged_serve_step")
    # [6,10): only "outer" covers its middle; [3,5): the Pjit call does
    assert r["idle_gaps"] == [["outer", pytest.approx(4e-3)],
                              ["PjitFunction(paged_serve_step)",
                               pytest.approx(2e-3)]]


def test_empty_trace_reads_nothing_busy():
    r = tracefile.reduce({"ops": [], "modules": [], "host": []}, "x")
    assert r["busy_s"] == 0 and r["step_device_s"] == [] \
        and r["idle_gaps"] == []


def _sweep_busy(ops):
    """Busy time by a sweep over sorted interval ends: an independent
    count of the union."""
    ends = sorted([(s, 1) for _, s, _ in ops] + [(s + d, -1)
                                                  for _, s, d in ops])
    depth, last, busy = 0, None, 0.0
    for t, k in ends:
        if depth > 0:
            busy += t - last
        depth += k
        last = t
    return busy / 1e9


RECORDED = Path(__file__).parent / "data" / "trace_sample.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_slice_matches_an_independent_sweep():
    tr = json.loads(RECORDED.read_text())
    r = tracefile.reduce(tr, "paged_serve_step")
    assert r["busy_s"] == pytest.approx(_sweep_busy(tr["ops"]), rel=1e-9)
    steps = [d for n, _, d in tr["modules"] if "paged_serve_step" in n]
    assert len(r["step_device_s"]) == len(steps) > 0
    assert sum(r["step_device_s"]) == pytest.approx(sum(steps) / 1e9)
    span = (max(s + d for _, s, d in tr["ops"])
            - min(s for _, s, _ in tr["ops"])) / 1e9
    assert 0 < r["busy_s"] <= span
    assert len(r["idle_gaps"]) <= 10 and len(r["device_ops"]) <= 10
