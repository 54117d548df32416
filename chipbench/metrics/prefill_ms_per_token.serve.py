"""Device time of the prompt, in ms per prompt token: the device time of the
program executions launched inside the program's ``serve.prefill`` spans of
the traced window, over the batch x prompt tokens those spans carry."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    if reading is None:
        return None
    tokens = sum(s.args.get("batch", 0) * s.args.get("prompt", 0)
                 for s in reading.spans_named("serve.prefill"))
    runs = reading.launched_in("serve.prefill")
    if not tokens or not runs:
        return None
    return 1e3 * sum(x.end - x.start for x in runs) / tokens
