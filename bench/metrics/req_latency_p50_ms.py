"""Median, over every request due in the window, of the time from when
it was due on the schedule to when the client had its whole reply."""
from readings import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 0.50)
