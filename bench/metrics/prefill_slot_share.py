"""Share of the window's slot-steps that fed prompt tokens:
EngineStats.prefill_steps over step_slots, in percent."""
from readings import engine_delta


def read(run):
    slots = engine_delta(run, "step_slots")
    if slots <= 0:
        return None
    return 100.0 * engine_delta(run, "prefill_steps") / slots
