"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-operation intervals / window), in percent."""


def read(run):
    if run.trace is None or not run.window_s or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.window_s)
