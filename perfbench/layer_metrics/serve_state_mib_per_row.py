"""Layer: Kernels. MiB of recurrent state a live row holds over all
layers, whatever its length: the program's counter
(``layers_<i>/self_attn/state`` = [live rows, KiB they hold in the
layer]) summed over layers, a live row of a decode step. Moves
``serve_tokens_per_s``."""
from perfbench import flops_retention


def read(facts):
    c = flops_retention.counted(facts)
    if c is None or not c["rows"]:
        return None
    return c["state_bytes"] / (c["rows"] / c["layers"]) / 2 ** 20
