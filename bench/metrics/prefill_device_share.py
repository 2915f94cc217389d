"""Device time of the prefill chunk program (the ``jit_paged_prefill_chunk``
module executions in the device trace) over the traced window's
device-busy time, in percent: the share of the chip's work that writes
prompts into the cache rather than decoding."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0 \
            or not run.trace["chunk_device_s"]:
        return None
    return 100.0 * sum(run.trace["chunk_device_s"]) / run.trace["busy_s"]
