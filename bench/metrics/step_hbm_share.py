"""Bytes the algorithm needs per engine step (weights once, the live KV
read, the new KV written; ``counts.py``) over the step's device time
times the chip's HBM bandwidth, in percent. Copies of the whole arena
and dense gathers are not needed bytes: this share exposes them."""
from readings import step_work


def read(run):
    w = step_work(run)
    if w is None:
        return None
    _, nbytes, step_s = w
    return 100.0 * nbytes / (step_s * run.peaks["hbm_bytes_per_s"])
