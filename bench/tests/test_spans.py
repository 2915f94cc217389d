"""The contexts a step's seated slots attend over, from the program's
STEP and ADMIT events, and the step counts the architecture makes of
them."""
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import spans
from arch import dense
from repro.core.genesys.trace import EVENT_DTYPE

MS = 1_000_000
W0, W1 = 1000 * MS, 2000 * MS
A, B, WARM = 101, 102, 7          # spans


def events(rows):
    """``(ev, sysno, seq, ts, aux)`` rows -> a program event array."""
    out = np.zeros(len(rows), EVENT_DTYPE)
    for i, (ev, sysno, seq, ts, aux) in enumerate(rows):
        out[i] = (ts, ev, 0, sysno, seq, aux)
    return out


def step_events():
    # request A (tag 0, a 300-token prompt, chunked) is admitted first;
    # B (tag 1, one token, no chunk) joins at step 1; an untagged warm-up
    # request's step comes before; steps 1 and 2 lie in the window
    return events([
        (spans.EV_ADMIT, -2, WARM, W0 - 900 * MS, -1),
        (spans.EV_STEP, 0, WARM, W0 - 800 * MS, 0),
        (spans.EV_ADMIT, -2, A, W0 - 100 * MS, 0),
        (spans.EV_STEP, 1, A, W0 - 10 * MS, 0),
        (spans.EV_ADMIT, -2, B, W0 - 5 * MS, 1),
        (spans.EV_STEP, 2, A, W0 + 20 * MS, 0),
        (spans.EV_STEP, 2, B, W0 + 20 * MS, 0),
        (spans.EV_STEP, 3, A, W0 + 50 * MS, 0),
        (spans.EV_STEP, 3, B, W0 + 50 * MS, 0),
        (spans.EV_STEP, 4, A, W1 + MS, 0),
    ])


def test_a_chunked_request_attends_over_its_prompt():
    # A's n-th step attends over 300 - 1 + n positions, B's over n
    got = spans.step_contexts(step_events(), W0, W1, [300, 1])
    assert got == [[301, 1], [302, 2]]


def test_without_prompt_lengths_a_request_counts_from_one():
    assert spans.step_contexts(step_events(), W0, W1, []) == [[2, 1], [3, 2]]


def test_decode_mfu_counts_each_step_over_its_contexts():
    m = dense.dims(dict(dense.TINY, rope_theta=1e6, rms_norm_eps=1e-6,
                        hidden_act="silu"))
    run = SimpleNamespace(
        events=step_events(), w0_ns=W0, w1_ns=W1, dims=m,
        cell=SimpleNamespace(arch=dense),
        requests=[{"prompt_len": 300}, {"prompt_len": 1}],
        trace={"step_device_s": [1e-3, 3e-3]},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    flops = (dense.token_flops(m, 301) + dense.token_flops(m, 1)
             + dense.token_flops(m, 302) + dense.token_flops(m, 2)) / 2
    assert harness.load_reader("decode_mfu")(run) == \
        pytest.approx(100 * flops / (2e-3 * 1e12))
    nbytes = (dense.step_bytes(m, spans.Step([301, 1]))
              + dense.step_bytes(m, spans.Step([302, 2]))) / 2
    assert harness.load_reader("step_hbm_share")(run) == \
        pytest.approx(100 * nbytes / (2e-3 * 1e11))
