"""Layer: Residual path. Device ms of the hyper-connections a thousand
prompt tokens in prefills: the prefill programs' operations under
``hc.coef``, ``hc.sinkhorn`` and ``hc.mix`` in the traced stretch
(``perfbench/flops_xing4.py``), over the prompt tokens of the admissions
that stretch held (the ``serve.admit`` spans' ``prompt_tokens``,
``admit_spans_traced``). A pass carries ``hc_mult`` float32 copies of its
positions' hidden: this is where the mixes' bytes show. A prefill that
straddles the stretch's edge is counted whole and timed in part, or the
other way round: read it over several runs. Moves
``serve_tokens_per_s``."""
from perfbench import flops_xing4


def read(facts):
    seconds = flops_xing4.hc_seconds(facts, flops_xing4.PREFILL)
    tokens = (facts.get("admit_spans_traced") or {}).get("prompt_tokens")
    if not seconds or not tokens:
        return None
    return 1e6 * seconds / tokens
