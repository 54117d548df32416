"""Device self time of the train step's ops under the ``unembed`` (final
norm and unembedding, forward and backward) and ``loss`` (cross-entropy)
scopes, in ms per step and chip in the traced window."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    return reading.per_step_ms(r"train_step", ["unembed", "loss"]) if reading else None
