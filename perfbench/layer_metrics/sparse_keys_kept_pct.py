"""Layer: Kernels. Of the keys resident in the live rows, the share the
learned index kept (at most ``sa_config.topk`` a row), over the window's
decode steps and all layers: 100 while no row is past ``topk``, ``topk``
over the mean row length far past it. From the program's counters
(``layers_<i>/self_attn/keys``). Moves ``serve_tokens_per_s``."""
from perfbench import flops_sparse


def read(facts):
    c = flops_sparse.counted(facts)
    if c is None or not c["resident"]:
        return None
    return 100.0 * c["kept"] / c["resident"]
