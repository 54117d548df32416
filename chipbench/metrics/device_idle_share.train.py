"""Share of the traced training window in which no operation ran on the
device (1 - union of op intervals / window), averaged over the chips."""


def read(trace, inputs, peaks, config):
    return 100.0 * trace.idle_share
