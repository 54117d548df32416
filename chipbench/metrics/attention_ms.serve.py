"""Device self time of the decode step's ops under the ``attention`` scope
(projections, rope, cache write, attention, output projection), in ms per
execution of the decode program (``serve_step``) in the traced window."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    return reading.per_step_ms(r"serve_step", ["attention"]) if reading else None
