"""Median, over the requests admitted in the window whose first token
came before it closed, of the time from the engine's ``ADMIT`` to its
``FIRST_TOKEN``: the prompt fed one token a step. Requests whose first
token came later are left out (``loop.prefill_ms`` counts them)."""
from loop import prefill_ms
from readings import percentile


def read(run):
    if run.events is None:
        return None
    got = prefill_ms(run.events, run.w0_ns, run.w1_ns)
    return percentile(got[0], 0.50) if got and got[0] else None
