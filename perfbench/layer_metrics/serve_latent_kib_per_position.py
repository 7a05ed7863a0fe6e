"""Layer: Kernels. KiB of cache a live position holds over all layers:
the program's counter (``layers_<i>/self_attn/latent`` = [live positions,
bytes of cache they hold in the layer]) summed over layers, a live
position of a decode step. A cache of expanded heads would hold 64 x 320 x
2 B a layer: 200 KiB over 5. Moves ``serve_tokens_per_s``."""
from perfbench import flops_latent


def read(facts):
    c = flops_latent.counted(facts)
    if c is None or not c["positions"]:
        return None
    return c["bytes"] / (c["positions"] / c["layers"]) / 1024.0
