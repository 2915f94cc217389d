"""GENESYS calls submitted in the window (telemetry totals) over the
requests the engine admitted in the same window (``EngineStats``), so
both count work that began in the window and the ratio moves only when
the calls a request costs change, not when latency does."""
from readings import engine_delta


def read(run):
    n = engine_delta(run, "admitted")
    if n <= 0 or not run.totals1:
        return None
    return (run.totals1["submitted"] - run.totals0["submitted"]) / n
