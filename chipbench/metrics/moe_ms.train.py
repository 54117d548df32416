"""Device self time of the train step's ops under the expert layer's scope
``moe`` (nested under ``mlp``: the router, dispatch, the held experts'
grouped matmuls, the shared experts, combine, and the loop over each
token's choices with its gradient sums; forward, backward and
recomputation), in ms per step and chip in the traced window; nothing where
the program has no such scope."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    return reading.per_step_ms_under(r"train_step", ["moe"]) if reading else None
