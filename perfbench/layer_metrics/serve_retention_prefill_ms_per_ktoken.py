"""Layer: Kernels. Device ms of the power retention a thousand prompt
tokens in prefills: the prefill programs' operations under
``attn.retention.*`` (within a chunk the masked power scores, across
chunks the read and the update of the state) in the traced stretch, over
the prompt tokens of the admissions that stretch held (the
``serve.admit`` spans' ``prompt_tokens``). Moves ``serve_tokens_per_s``."""
from perfbench import flops_retention


def read(facts):
    return flops_retention.prefill_ms_per_ktoken(facts)
