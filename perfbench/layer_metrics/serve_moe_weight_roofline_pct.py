"""Layer: Expert layer. The expert branch's share of its roofline in
decode steps: the least time the chip could take for the weight bytes a
step must read (the held experts its token-picks hit, from the program's
counter, whole; the shared experts; the router: ``perfbench/flops_moe.py``)
and for its operations, the larger of the two, over the ``moe.*`` scopes'
device time a step. Moves ``serve_tokens_per_s``."""
from perfbench import flops, flops_moe, model_spans


def read(facts):
    ms = model_spans.scopes_ms_per_step(facts, model_spans.MOE_SCOPES)
    c = model_spans.counted(facts)
    if not ms or c is None:
        return None
    sizes = facts["sizes"]
    moved = sum(flops_moe.moe_layer_bytes(sizes, hit / c["steps"])
                for hit in c["hit"])
    work = sum(flops_moe.moe_layer_flops(
        sizes, facts["stats_delta"]["num_slots"], picks.sum() / c["steps"])
        for picks in c["picks"])
    least, _bound = flops.roofline_seconds(
        work, moved, flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
