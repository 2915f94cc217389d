"""Engine steps with the experts they read: the program's ``MOE_ROUTE``
events (``genesys.trace`` code 17, one per expert layer and step: the
step's index in ``sysno``, the layer in ``seq``, and in ``aux`` the held
experts the seated rows hit plus the (row, held expert) assignments
shifted left by 16) joined by step index to the contexts of
``spans.step_contexts``, as the ``spans.Step`` an expert architecture's
``step_flops``/``step_bytes`` count. A program that records no such
event (a dense model, or a program older than the event) gives nothing.

``readings.step_work`` builds its steps without counters, so the expert
architecture's readers (``moe_decode_mfu``, ``moe_step_hbm_share``) count
through ``routed_work``.
"""
from __future__ import annotations

import numpy as np

import spans

EV_MOE_ROUTE = 17
SHIFT = 16


def routed_steps(evs: np.ndarray, w0: int, w1: int,
                 prompt_len: list[int]) -> list[spans.Step]:
    """Each engine step that started inside ``[w0, w1)`` and recorded its
    routes: its slots' contexts, and ``counters`` ``experts_hit`` and
    ``assignments``, one number an expert layer, in layer order."""
    ctx = spans.step_contexts(evs, w0, w1, prompt_len)
    st = evs[(evs["ev"] == spans.EV_STEP) & (evs["ts"] >= w0)
             & (evs["ts"] < w1)]
    keys = sorted({int(k) for k in st["sysno"]})   # step_contexts' order
    routes: dict = {}
    for r in evs[evs["ev"] == EV_MOE_ROUTE]:
        routes.setdefault(int(r["sysno"]), {})[int(r["seq"])] = int(r["aux"])
    out = []
    for k, c in zip(keys, ctx):
        if k not in routes:
            continue
        aux = [routes[k][layer] for layer in sorted(routes[k])]
        out.append(spans.Step(c, {
            "experts_hit": [a & ((1 << SHIFT) - 1) for a in aux],
            "assignments": [a >> SHIFT for a in aux]}))
    return out



def routed_work(run):
    """Mean operations and bytes of the routed steps that began in the
    window (the cell's architecture counts them), and the mean device
    time of one step, or None where the program recorded no routes."""
    if run.events is None or run.trace is None:
        return None
    dev = run.trace["step_device_s"]
    steps = routed_steps(run.events, run.w0_ns, run.w1_ns,
                         [r["prompt_len"] for r in run.requests])
    if not dev or not steps:
        return None
    arch = run.cell.arch
    flops = sum(arch.step_flops(run.dims, s) for s in steps) / len(steps)
    nbytes = sum(arch.step_bytes(run.dims, s) for s in steps) / len(steps)
    return flops, nbytes, sum(dev) / len(dev)
