"""Layer: Residual path. The hyper-connections' share of their roofline
in decode steps: the least time the chip could take to read the live
rows' float32 streams once and write them once a sub-layer (``2 x rows x
hc_mult x hidden x 4 B``, the rows from the program's counter
``layers_<i>/hc/rows``) and to read each sub-layer's ``Phi`` once, or for
the products' operations, the larger of the two
(``perfbench/flops_xing4.py``), over the device time a step under
``hc.*``. In a decode step of 32 rows the work is a chain of small
operations, bound by neither: the share reads low, and that is what it
is for. Moves ``serve_tokens_per_s``."""
from perfbench import flops, flops_xing4


def read(facts):
    ms = flops_xing4.hc_decode_ms_per_step(facts)
    c = flops_xing4.hc_counted(facts)
    if not ms or c is None or not c["rows"]:
        return None
    sizes = facts["sizes"]
    least, _bound = flops.roofline_seconds(
        flops_xing4.hc_flops(sizes, c["rows"] / c["steps"]),
        flops_xing4.hc_bytes(sizes, c["rows"] / c["steps"],
                             c["sub_layers"] / c["steps"]),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
