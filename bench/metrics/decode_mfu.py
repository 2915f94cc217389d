"""Model operations of the tokens the engine's steps processed, over the
device time of those steps times the chip's bf16 peak, in percent. The
operations count only the active slots' tokens, each attending over its
own live context (``counts.py``)."""
from readings import step_work


def read(run):
    w = step_work(run)
    if w is None:
        return None
    flops, _, step_s = w
    return 100.0 * flops / (step_s * run.peaks["bf16_flops_per_s"])
