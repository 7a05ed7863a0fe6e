"""Layer: Kernels. The expanded attend's share of its roofline in
prefills: the least time the chip could take for the causal half of 2 x
heads x ((nope + rope) + v) operations a pair of positions of every
prompt the traced stretch admitted (the ``serve.admit`` spans'
``prompt_tokens``, squared and summed; ``perfbench/flops_latent.py``),
over the device time of the prefill programs' ``attn.latent.attend`` in
that stretch (the kernel by its name). A prefill that straddles the
stretch's edge is counted whole and timed in part, or the other way
round: read it over several runs. Moves ``serve_tokens_per_s``."""
from perfbench import flops, flops_latent


def read(facts):
    seconds = flops_latent.prefill_attend_seconds(facts)
    sq = (facts.get("admit_spans_traced") or {}).get("prompt_tokens_sq")
    if not seconds or not sq:
        return None
    least, _bound = flops.roofline_seconds(
        flops_latent.prefill_attend_flops(facts["sizes"], sq), 0.0,
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / seconds
