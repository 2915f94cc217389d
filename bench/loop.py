"""The serving loop's phases and each request's milestones, from the
program's own trace events (``genesys.trace``), and the pairing of those
events with a device trace.

Event codes are the program's: ``LOOP`` 14 (``sysno`` the phase's code,
an index into ``PHASES``; ``seq`` the loop iteration; ``ts`` the phase's
start; ``aux`` its duration in ns), ``ADMIT`` 15 (``seq`` the request's
span; ``aux`` its wire tag, which is its index in the schedule) and
``FIRST_TOKEN`` 16 (``seq`` the span; ``ts`` when the host had the
token). A program that records none of them yields ``None`` from every
reading here.
"""
from __future__ import annotations

import numpy as np

EV_LOOP, EV_ADMIT, EV_FIRST_TOKEN = 14, 15, 16
PHASES = ("poll", "admit", "dispatch", "sync", "advance", "retire", "reply")
DISPATCH, SYNC = PHASES.index("dispatch"), PHASES.index("sync")


def iterations(evs: np.ndarray, w0: int, w1: int):
    """The loop iterations that began in ``[w0, w1)``: the ns each spent
    in each phase, and which phases each ran; ``None`` without ``LOOP``
    events."""
    lp = evs[(evs["ev"] == EV_LOOP) & (evs["sysno"] >= 0)
             & (evs["sysno"] < len(PHASES))]
    if not len(lp):
        return None
    _, inv = np.unique(lp["seq"], return_inverse=True)
    n = int(inv.max()) + 1
    start = np.full(n, np.iinfo(np.int64).max, np.int64)
    np.minimum.at(start, inv, lp["ts"])
    per = np.zeros((n, len(PHASES)))
    np.add.at(per, (inv, lp["sysno"]), lp["aux"].astype(np.float64))
    ran = np.zeros((n, len(PHASES)), bool)
    ran[inv, lp["sysno"]] = True
    inside = (start >= w0) & (start < w1)
    return per[inside], ran[inside]


def phase_ms_per_step(evs: np.ndarray, w0: int, w1: int) -> dict | None:
    """Mean ms per phase over the window's iterations that dispatched an
    engine step."""
    it = iterations(evs, w0, w1)
    if it is None:
        return None
    per, ran = it
    stepped = per[ran[:, DISPATCH]]
    if not len(stepped):
        return None
    return {p: float(stepped[:, k].mean()) / 1e6
            for k, p in enumerate(PHASES)}


def host_ms_per_step(evs: np.ndarray, w0: int, w1: int) -> float | None:
    """Mean, over the window's iterations that dispatched a step, of the
    iteration's wall time less its ``sync`` wait: host time in which the
    synchronous loop gives the device nothing to do."""
    ms = phase_ms_per_step(evs, w0, w1)
    if ms is None:
        return None
    return sum(ms.values()) - ms["sync"]


def queue_waits_ms(evs: np.ndarray, w0: int, w1: int,
                   sent_ns: list) -> list[float] | None:
    """From the client's send to the engine's ``ADMIT``, joined by tag,
    for every request sent in ``[w0, w1)`` (``sent_ns[tag]``, ``None``
    where never sent). A request not admitted by the last event recorded
    counts until then."""
    adm = evs[evs["ev"] == EV_ADMIT]
    if not len(adm):
        return None
    first: dict = {}
    for tag, ts in zip(adm["aux"].tolist(), adm["ts"].tolist()):
        first[tag] = min(ts, first.get(tag, ts))
    last = int(evs["ts"].max())
    return [(first.get(tag, last) - s) / 1e6 for tag, s in enumerate(sent_ns)
            if s is not None and w0 <= s < w1]


def prefill_ms(evs: np.ndarray, w0: int, w1: int):
    """``ADMIT`` to ``FIRST_TOKEN``, joined by span, for every request
    admitted in ``[w0, w1)`` whose first token came before ``w1`` ->
    ``(times in ms, how many admitted requests were left out)``; ``None``
    without ``ADMIT`` events."""
    adm = evs[evs["ev"] == EV_ADMIT]
    if not len(adm):
        return None
    ft = evs[evs["ev"] == EV_FIRST_TOKEN]
    first = dict(zip(ft["seq"].tolist(), ft["ts"].tolist()))
    out, left = [], 0
    for span, ts in zip(adm["seq"].tolist(), adm["ts"].tolist()):
        if not w0 <= ts < w1:
            continue
        t1 = first.get(span)
        if t1 is None or t1 >= w1:
            left += 1
        else:
            out.append((t1 - ts) / 1e6)
    return out, left


def serve_iters(xplane_path: str) -> dict:
    """Start (the profile's clock, ns) of every ``serve.iter`` annotation
    in a device trace, by the iteration index it carries."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "serve.iter":
                    it = dict(e.stats).get("it")
                    if it is not None:
                        out[int(it)] = float(e.start_ns)
    return out


def clock_offsets(evs: np.ndarray, iters: dict) -> np.ndarray:
    """For every iteration both traces hold: its ``serve.iter`` start
    less its first ``LOOP`` timestamp (ns). The median is the offset
    from ``perf_counter_ns`` to the profile's clock."""
    lp = evs[evs["ev"] == EV_LOOP]
    start: dict = {}
    for it, ts in zip(lp["seq"].tolist(), lp["ts"].tolist()):
        start[it] = min(ts, start.get(it, ts))
    both = sorted(set(start) & set(iters))
    return np.asarray([iters[i] - start[i] for i in both], np.float64)
