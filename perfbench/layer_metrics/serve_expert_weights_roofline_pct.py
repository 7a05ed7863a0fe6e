"""Layer: Expert layer. The expert branches' share of their roofline in
decode steps, for a model whose leading layers are dense: the least time
the chip could take for the weight bytes a step must read (every routed
expert its token-picks hit, from the program's counter
``layers_<i>/mlp/hit``, whole; the shared expert; the router; over the
layers that count picks: ``perfbench/flops_xing4.py``) and for its
operations, the larger of the two, over the ``moe.*`` scopes' device time
a step (what ``serve_held_experts_ms_per_step`` reads). Moves
``serve_tokens_per_s``."""
from perfbench import flops, flops_xing4, model_spans


def read(facts):
    ms = model_spans.scopes_ms_per_step(facts, model_spans.MOE_SCOPES)
    c = flops_xing4.expert_layers_counted(facts)
    if not ms or c is None:
        return None
    sizes = facts["sizes"]
    least, _bound = flops.roofline_seconds(
        flops_xing4.expert_flops(sizes, c["picks"] / c["steps"],
                                 c["tokens"] / c["steps"]),
        flops_xing4.expert_bytes(sizes, c["hit"] / c["steps"], c["layers"]),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
