"""Device time of the collective operations (collective-permute, all-reduce,
...) per training step, per chip, in milliseconds."""


def read(trace, inputs, peaks, config):
    if trace.collective_s <= 0:
        return None
    return 1e3 * trace.collective_s / inputs["steps"]
