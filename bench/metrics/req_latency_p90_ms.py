"""The 90th percentile of the same distribution as req_latency_p50_ms;
a request with no reply counts as missing it."""
from readings import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 0.90)
