"""Host time the training loop takes per step, in ms: the program's
``train.batch`` (the synthetic rows), ``train.place`` (their copy to the
chips) and ``train.step`` (the step's dispatch) spans of the traced window,
over its ``train.step`` spans."""

from chipbench import trace_program

SPANS = ("train.batch", "train.place", "train.step")


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    steps = reading.spans_named("train.step") if reading else []
    if not steps:
        return None
    return 1e3 * sum(s.seconds for n in SPANS for s in reading.spans_named(n)) / len(steps)
