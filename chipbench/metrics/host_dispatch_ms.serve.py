"""Host time the serving loop takes to dispatch one decode step, in ms: the
self time of the program's ``serve.step`` spans (the prompt token's copy to
the device, ``serve.h2d``, counted in it) less the time their launches
waited for room in the device's queue, per step of the traced window."""

from chipbench import trace_program


def read(trace, inputs, peaks, config):
    reading = trace_program.of(trace)
    steps = reading.spans_named("serve.step") if reading else []
    if not steps:
        return None
    return 1e3 * sum(reading.self_s(s, keep=("serve.h2d",)) - s.queue_wait
                     for s in steps) / len(steps)
