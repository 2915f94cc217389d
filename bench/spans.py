"""Readings from the program's own GENESYS trace events (``genesys.trace``
event ring, ``perf_counter_ns`` timestamps): the context each engine
step's slots attended over, and the time from submission to completion
of each call.

Event codes are the program's (``EV_SUBMIT`` 1, ``EV_COMPLETE`` 5,
``EV_IRQ`` 7, ``EV_STEP`` 13); a ``STEP`` event is written
once per active request per engine step, with the step's index in
``sysno``, the request's span id in ``seq`` and the step's start in
``ts``.
"""
from __future__ import annotations

import numpy as np

EV_SUBMIT, EV_COMPLETE, EV_IRQ, EV_STEP = 1, 5, 7, 13
_KEY = np.int64(1) << np.int64(44)


def merge(snapshots: list[np.ndarray]) -> np.ndarray:
    """Union of event-ring snapshots taken at different times (the ring
    wraps, so one snapshot may not hold a whole window)."""
    evs = np.concatenate([s for s in snapshots if len(s)]) if snapshots \
        else np.zeros(0)
    if not len(evs):
        return evs
    _, idx = np.unique(evs, return_index=True)
    out = evs[np.sort(idx)]
    return out[np.argsort(out["ts"], kind="stable")]


def step_contexts(evs: np.ndarray, w0: int, w1: int) -> list[list[int]]:
    """For each engine step that started inside ``[w0, w1)``, the number
    of positions each active slot's token attended over. A request's
    n-th step (from 1) attends over n positions; every request of the
    window began inside it, so its earlier steps are all in ``evs``."""
    st = evs[evs["ev"] == EV_STEP]
    if not len(st):
        return []
    order = np.lexsort((st["sysno"], st["seq"]))
    st = st[order]
    new_span = np.concatenate([[True], st["seq"][1:] != st["seq"][:-1]])
    start_idx = np.maximum.accumulate(np.where(new_span,
                                               np.arange(len(st)), 0))
    context = np.arange(len(st)) - start_idx + 1
    inside = (st["ts"] >= w0) & (st["ts"] < w1)
    steps: dict = {}
    for k, c in zip(st["sysno"][inside], context[inside]):
        steps.setdefault(int(k), []).append(int(c))
    return [steps[k] for k in sorted(steps)]


def call_latencies_us(evs: np.ndarray, w0: int, w1: int) -> np.ndarray:
    """Submission-to-completion time of every call submitted inside
    ``[w0, w1)``: from its ring ``SUBMIT``, or from its doorbell ``IRQ``,
    to the ``COMPLETE`` that gives it a return value (the ``total`` and
    ``irq_total`` stages of the program's own histograms)."""
    out = []
    for ea, eb in ((EV_SUBMIT, EV_COMPLETE), (EV_IRQ, EV_COMPLETE)):
        a = evs[(evs["ev"] == ea) & (evs["ts"] >= w0) & (evs["ts"] < w1)]
        b = evs[evs["ev"] == eb]
        if not len(a) or not len(b):
            continue
        ka = a["tenant"].astype(np.int64) * _KEY + a["seq"]
        kb = b["tenant"].astype(np.int64) * _KEY + b["seq"]
        _, ia, ib = np.intersect1d(ka, kb, return_indices=True)
        out.append((b["ts"][ib] - a["ts"][ia]) / 1e3)
    return np.concatenate(out) if out else np.zeros(0)
