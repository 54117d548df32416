"""Model operations of the window's serving waves over the chip's bf16 peak
in the traced window: every prompt and output position through the layers,
the unembedding where a token is produced, causal attention."""


def read(trace, inputs, peaks, config):
    return 100.0 * inputs["flops"] / (trace.chips * peaks["bf16_flops_per_s"]
                                      * trace.window_s)
