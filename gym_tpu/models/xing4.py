"""Xing4.0's language model (``model_type: xing4_0``), built from its
``config.json`` keys, as the serving engine runs it: the DeepSeek-V3 block
of ``kimi_k2.py`` (multi-head latent attention over pages that hold the
latent; leading dense SwiGLU layers, then sigmoid-routed experts with a
selection bias beside one shared expert) on a residual of ``hc_mult``
streams a token, mixed around every attention and every feed-forward part
by a manifold-constrained hyper-connection (``ops/hyper_connection.py``).

A token's streams ``X`` in R^{n x C} start as ``n`` copies of its
embedding. Every sub-layer ``F`` (a layer's attention, then its SwiGLU or
its experts) has its own float32 ``phi``, ``alpha``, ``bias``::

    H_pre, H_post, H_res = coefficients(X)        ops/hyper_connection.py
    h    = sum_i H_pre[i] X_i
    y    = F(rms_w(h))            F's own weighted RMS norm, as kimi_k2.py
    X'_j = sum_i H_res[j, i] X_i + H_post[j] y

and after the last layer ``x = sum_i X_i``, the final RMS norm, the untied
head. ``F`` is ``kimi_k2.py``'s, imported: ``LatentAttention`` (absorbed
in a decode step, expanded in a prefill), ``SwiGLU``, ``moe.py:HeldExperts``
(``routed_scaling_factor * routed + shared``).

The streams live inside a program: between blocks the carry is ``[n, b,
t, C]`` float32 (the first block replicates the embedding on entry, the
last sums on exit), so the programs' arguments, the pools and the logits
are those of every paged model and the engine needs nothing. A prefill
takes its bucket ``prefill_rows`` positions a pass through all layers and
skips the passes that hold only padding (``cohere2_moe.py:
prefill_in_passes``, which carries whatever a block returns): a pass of
4,096 positions holds ``[4, 4096, 3584]`` float32, 235 MB, whatever the
bucket. The ``cache`` collection is ``layers_<i>/latent`` [kv_pages,
page_size, lanes] in ``kv_dtype``, a page ``kimi_k2.py``'s.

Counters a decode step returns: ``layers_<i>/hc/rows`` = [rows mixed
(live rows, summed over the layer's sub-layers), sub-layers], beside
``self_attn/latent``, ``self_attn/pages`` and, in an expert layer,
``mlp/picks``, ``/hit``, ``/tokens``.

Weights in ``weights_dtype``; the streams, the hyper-connections'
parameters and coefficients, the norms, the rotation, the router and the
softmax's statistics in float32. ``num_nextn_predict_layers`` is not
run: the engine drafts by lookup, not by a head (ROADMAP.md B1).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import hyper_connection as hc
from .cohere2_moe import _DTYPES, prefill_in_passes, prefill_pass
from .decoder_parts import RMSNorm, embed_tokens, untied_head
from .kimi_k2 import _MOE_CHUNK_ROWS, KimiK2Config, LatentAttention, SwiGLU
from .moe import HeldExperts

FAMILY = "xing4_0"


@dataclasses.dataclass
class Xing4Config(KimiK2Config):
    """``kimi_k2.py``'s keys at this model's published values, then the
    hyper-connection's."""

    model_type: str = FAMILY
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    first_k_dense_replace: int = 2
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    held_experts: tuple = (0, 64)
    block_size: int = 12288
    hc_mult: int = 4                    # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6                # guards the Sinkhorn divisions
    mhc_h_res_clamp_min: float = -30.0  # on A_res, before exp
    mhc_h_res_clamp_max: float = 30.0

    prefill_pass = prefill_pass

    def build(self) -> nn.Module:
        return Xing4(self)

    def program_tag(self) -> str:
        return (f",{FAMILY}:L={self.num_hidden_layers},hc={self.hc_mult}"
                f",w={self.weights_dtype},kv={self.kv_dtype}")

    def prepare_params(self, params):
        """``KimiK2Config.prepare_params``, and every layer's ``hc``
        (``phi``, ``alpha``, ``bias`` a sub-layer) float32 from the tree
        as given."""
        served = super().prepare_params(params)
        for name, layer in params.items():
            if hasattr(layer, "get") and "hc" in layer:
                served[name] = {**served[name], "hc": jax.tree.map(
                    lambda x: jnp.asarray(x, jnp.float32), layer["hc"])}
        return served


class HyperConnections(nn.Module):
    """One layer's hyper-connections, called once a sub-layer (``sub``:
    ``attn``, then ``mlp``): the sub-layer's coefficients of the streams
    ``X`` [n, b, t, C]. ``rows``: the call's live rows, counted."""

    config: Xing4Config

    @nn.compact
    def __call__(self, X, sub: str, rows):
        cfg = self.config
        n, k = cfg.hc_mult, cfg.hc_mult * (cfg.hc_mult + 2)
        phi = self.param(f"phi_{sub}", nn.initializers.normal(0.02),
                         (n * cfg.hidden_size, k), jnp.float32)
        alpha = self.param(f"alpha_{sub}", nn.initializers.ones, (3,),
                           jnp.float32)
        bias = self.param(f"bias_{sub}", nn.initializers.zeros, (k,),
                          jnp.float32)
        self.sow("counters", "rows",
                 jnp.stack([rows, jnp.ones((), rows.dtype)]).astype(
                     jnp.int32),
                 reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros((2,), jnp.int32))
        return hc.coefficients(
            X, phi, alpha, bias, eps=cfg.rms_norm_eps,
            iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))


class Block(nn.Module):
    """``__call__(x, block_table, cache_pos)``, as ``prefill_in_passes``
    calls a block; the layer's pool is its ``cache`` entry ``latent``."""

    config: Xing4Config
    dense: bool             # a leading dense layer: SwiGLU, no experts
    prefill: bool = False   # the expanded attend of a call with last_pos
    first: bool = False     # takes [b, t, C] and replicates it
    last: bool = False      # returns the streams' sum [b, t, C]

    @nn.compact
    def __call__(self, x, block_table, cache_pos):
        cfg = self.config
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        X = (jnp.broadcast_to(x[None], (cfg.hc_mult,) + x.shape)
             if self.first else x)
        _n, b, t, C = X.shape
        pool = self.variable(
            "cache", "latent", lambda: jnp.zeros(
                (cfg.kv_pages, cfg.page_size, cfg.pool_lanes), kv_dt))
        live = block_table[:, 0] != 0
        rows = live.sum() * t
        mixer = HyperConnections(cfg, name="hc")

        h_pre, h_post, h_res = mixer(X, "attn", rows)
        a = RMSNorm(cfg.rms_norm_eps, dt,
                    name="input_layernorm")(hc.read(X, h_pre))
        y, pool.value = LatentAttention(cfg, name="self_attn")(
            a, pool.value, block_table, cache_pos, self.prefill)
        X = hc.write(X, y, h_res, h_post)

        h_pre, h_post, h_res = mixer(X, "mlp", rows)
        a = RMSNorm(cfg.rms_norm_eps, dt,
                    name="post_attention_layernorm")(hc.read(X, h_pre))
        if self.dense:
            with jax.named_scope("mlp"):
                y = SwiGLU(cfg, name="mlp")(a)
        else:
            routed, shared = HeldExperts(
                hidden=C, width=cfg.moe_intermediate_size,
                n_experts=cfg.n_routed_experts,
                topk=cfg.num_experts_per_tok, held=cfg.held_experts,
                n_shared=cfg.n_shared_experts,
                norm_topk=cfg.norm_topk_prob, chunk_rows=_MOE_CHUNK_ROWS,
                param_dtype=dt, score_fn="sigmoid", select_bias=True,
                name="mlp")(a.reshape(b * t, C), jnp.repeat(live, t))
            y = (cfg.routed_scaling_factor * routed
                 + shared).reshape(b, t, C)
        X = hc.write(X, y, h_res, h_post)
        return X.sum(0) if self.last else X


class Xing4(nn.Module):
    """``__call__(tokens [b, t], train=False, block_table=, cache_pos=,
    last_pos=None)`` -> float32 logits [b, t, V], or [b, V] at position
    ``last_pos`` of every row when that is given (a prefill: the positions
    past it are padding)."""

    config: Xing4Config

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_table=None,
                 cache_pos=None, last_pos=None):
        cfg = self.config
        if train:
            raise ValueError("this decoder is served, not trained: the "
                             "trainer runs the GPT-2 block only "
                             "(ROADMAP.md B1)")
        if not (cfg.decode and cfg.page_size > 0):
            raise ValueError("this decoder runs through the paged cache "
                             "only: decode=True and page_size > 0")
        if block_table is None or cache_pos is None:
            raise ValueError("paged decode needs block_table and "
                             "cache_pos")
        for name in ("weights_dtype", "kv_dtype"):
            if getattr(cfg, name) not in _DTYPES:
                raise ValueError(f"{name} must be one of "
                                 f"{sorted(_DTYPES)}, got "
                                 f"{getattr(cfg, name)!r}")
        dt = _DTYPES[cfg.weights_dtype]
        L, C, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        kinds = [dict(dense=i < cfg.first_k_dense_replace, first=i == 0,
                      last=i == L - 1) for i in range(L)]
        if last_pos is None:
            x = embed_tokens(self, tokens, V, C, dt)
            for i, kind in enumerate(kinds):
                x = Block(cfg, name=f"layers_{i}", **kind)(
                    x, block_table, cache_pos)
            return untied_head(self, x, None, V, cfg.rms_norm_eps, dt)

        if self.is_initializing():
            raise ValueError("initialise with one token a row: a prefill "
                             "reads the parameters that a decode step "
                             "declares")
        x_last = prefill_in_passes(
            self, [Block(cfg, prefill=True, **kind) for kind in kinds],
            tokens, block_table, cache_pos, last_pos,
            cfg.prefill_pass(tokens.shape[1]))
        return untied_head(self, x_last[:, None], None, V,
                           cfg.rms_norm_eps, dt)[:, 0]
