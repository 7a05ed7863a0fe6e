"""Layer: Kernels. Device ms of the gated delta-rule layers a thousand
prompt tokens in prefills: the prefill programs' operations under
``attn.delta.*`` (projections, the convolution over a pass, within a
chunk the triangular inverse and the masked products, across chunks the
read and the update of the state, the gated norm and the output
projection) in the traced stretch, over the prompt tokens of the
admissions that stretch held (the ``serve.admit`` spans'
``prompt_tokens``). Moves ``serve_tokens_per_s``."""
from perfbench import flops_delta


def read(facts):
    return flops_delta.prefill_ms_per_ktoken(facts)
