"""Model operations of the window's training steps over what the chips'
bf16 peak could do in the traced window: steps x (6N + attention) operations
per step / (chips x peak x window).  Recomputation is not counted."""


def read(trace, inputs, peaks, config):
    done = inputs["steps"] * inputs["flops_per_step"]
    return 100.0 * done / (trace.chips * peaks["bf16_flops_per_s"] * trace.window_s)
