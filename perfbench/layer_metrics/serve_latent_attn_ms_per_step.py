"""Layer: Kernels. Device ms a decode step of the latent attention: the
decode program's operations under ``attn.latent.q`` (the queries' down-
and up-projection and the absorption of ``W_uk``), ``attn.latent.kv`` (the
latent's down-projection, norm, rotation and the page write),
``attn.latent.attend`` (the walk of the live pages; the kernel by its name)
and ``attn.latent.out`` (``W_uv`` and ``W_o``), from the trace
(``perfbench/flops_latent.py``). Moves ``serve_tokens_per_s``."""
from perfbench import flops_latent


def read(facts):
    return flops_latent.decode_ms_per_step(facts)
