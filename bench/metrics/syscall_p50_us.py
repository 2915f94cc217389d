"""Median time from submission to completion of every GENESYS call
submitted in the traced window, from the program's trace events."""
import numpy as np

from spans import call_latencies_us


def read(run):
    if run.events is None:
        return None
    lat = call_latencies_us(run.events, run.w0_ns, run.w1_ns)
    return float(np.median(lat)) if len(lat) else None
