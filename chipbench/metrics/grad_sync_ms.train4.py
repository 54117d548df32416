"""Device self time of the train step's ops under the ``grad_sync`` scope
(the gradients' allreduce and the loss's mean over the data axis, loop and
copies included), in ms per step and chip in the traced window."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    return reading.per_step_ms(r"train_step", ["grad_sync"]) if reading else None
