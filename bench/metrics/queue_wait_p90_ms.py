"""The 90th percentile, over the requests the client sent in the window,
of the time from the send to the engine's ``ADMIT`` of the request,
joined by its wire tag (both clocks are CLOCK_MONOTONIC). A request not
admitted by the last recorded event counts until then."""
from loop import queue_waits_ms
from readings import percentile


def read(run):
    if run.events is None:
        return None
    sent = [None if r["sent_s"] is None
            else run.w0_ns + int(r["sent_s"] * 1e9) for r in run.requests]
    waits = queue_waits_ms(run.events, run.w0_ns, run.w1_ns, sent)
    return percentile(waits, 0.90) if waits else None
