"""Layer: Kernels. The absorbed attend's share of its roofline in decode
steps: the least time the chip could take to read once the cache the
program's counter says the live positions held, or to take 2 x heads x
((rank + rope) + rank) operations a live position
(``perfbench/flops_latent.py``), the larger of the two, over the device
time a step under ``attn.latent.attend`` (the kernel by its name). Moves
``serve_tokens_per_s``."""
from perfbench import flops, flops_latent


def read(facts):
    ms = flops_latent.decode_ms_per_step(facts, (flops_latent.ATTEND,))
    c = flops_latent.counted(facts)
    if not ms or c is None or not c["bytes"]:
        return None
    least, _bound = flops.roofline_seconds(
        flops_latent.decode_attend_flops(facts["sizes"],
                                         c["positions"] / c["steps"]),
        c["bytes"] / c["steps"], flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
