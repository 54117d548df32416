"""Device self time of the train step's ops under the ``attention`` scope
(latent attention: projections, the latent's norm and expansion, rope, the
chunked attention; forward, backward and recomputation), in ms per step and
chip in the traced window."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    return reading.per_step_ms_under(r"train_step", ["attention"]) if reading else None
