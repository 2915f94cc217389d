"""Bytes an expert model's decode step needs (every weight outside the
routed experts once, each held expert the step's rows hit once a layer,
the latent entries read over each slot's context and the new ones
written; the cell's architecture module counts them from the step's
``MOE_ROUTE`` events) over the step's device time times the chip's HBM
bandwidth, in percent. A program that records no routes gives
nothing."""
from moe_spans import routed_work


def read(run):
    w = routed_work(run)
    if w is None:
        return None
    _, nbytes, step_s = w
    return 100.0 * nbytes / (step_s * run.peaks["hbm_bytes_per_s"])
