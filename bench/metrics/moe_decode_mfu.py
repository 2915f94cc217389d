"""Operations of an expert model's decode steps over their device time
times the chip's bf16 peak, in percent: each seated slot's token in
absorbed attention over its own context, the dense and shared layers,
the router, the head, and each (row, held expert) assignment the step
recorded in its ``MOE_ROUTE`` events (the cell's architecture module
counts them). A program that records no routes gives nothing."""
from moe_spans import routed_work


def read(run):
    w = routed_work(run)
    if w is None:
        return None
    flops, _, step_s = w
    return 100.0 * flops / (step_s * run.peaks["bf16_flops_per_s"])
