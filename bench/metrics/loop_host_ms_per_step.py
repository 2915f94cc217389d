"""Host time per engine step in which the synchronous serving loop gives
the device nothing to do: mean, over the loop iterations that began in
the window and dispatched a step, of the iteration's wall time less its
``sync`` wait (the program's ``LOOP`` phase events)."""
from loop import host_ms_per_step


def read(run):
    if run.events is None:
        return None
    return host_ms_per_step(run.events, run.w0_ns, run.w1_ns)
