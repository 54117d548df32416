"""The decode step's (``serve_step``) share of its HBM roofline: the least bytes of every
decode step of the window (bf16 weights, the valid cache rows read, one row
written) over the device time of the decode program (``serve_step``) times
the chip's HBM bandwidth.  The rest of ``max_len`` read is not counted as
needed."""

PROGRAM = r"serve_step"


def read(trace, inputs, peaks, config):
    found = trace.module(PROGRAM)
    if found is None or found[1] <= 0:
        return None
    return 100.0 * inputs["bytes"] / (found[1] * peaks["hbm_bytes_per_s"])
