"""Small helpers the metric readers share."""
from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    of the sample at or below it."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def latencies_ms(run) -> list[float]:
    """From due time to the whole reply, for every request due in the
    window. A request with no reply counts as waiting until the client
    gave up on it."""
    give_up = run.seconds + float(run.cell.mix["reply_cap_s"])
    return [1e3 * ((r["recv_s"] if r["recv_s"] is not None else give_up)
                   - r["due_s"]) for r in run.requests]


def engine_delta(run, field: str) -> int:
    return run.engine1[field] - run.engine0[field]


def step_work(run):
    """Mean per-step operations and bytes of the engine steps that began
    in the window (from the program's step spans), and the mean device
    time of one step (from the device trace), or None."""
    import counts
    import spans

    if run.events is None or run.trace is None:
        return None
    dev = run.trace["step_device_s"]
    ctx = spans.step_contexts(run.events, run.w0_ns, run.w1_ns)
    if not dev or not ctx:
        return None
    flops = sum(counts.step_flops(run.dims, c) for c in ctx) / len(ctx)
    nbytes = sum(counts.step_bytes(run.dims, c) for c in ctx) / len(ctx)
    return flops, nbytes, sum(dev) / len(dev)
