"""Process start to the first due request: loading, making the weights,
warming the step, compilation where the cache misses."""


def read(run):
    return run.setup_s
