"""Layer: Kernels. MiB of recurrent state and kept convolution inputs a
live row holds over all delta layers, whatever its length: the program's
counter (``layers_<i>/linear_attn/state`` = [live rows, bytes they hold in
the layer]) summed over the delta layers, a live row of a decode step.
Moves ``serve_tokens_per_s``."""
from perfbench import flops_delta


def read(facts):
    c = flops_delta.counted(facts)
    if c is None or not c["rows"]:
        return None
    return c["state_bytes"] / (c["rows"] / c["layers"]) / 2 ** 20
