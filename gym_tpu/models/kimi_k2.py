"""Kimi-K2's language model (``model_type: kimi_k2``; the DeepSeek-V3
block, key for key), built from its ``config.json`` keys, as the serving
engine runs it: multi-head latent attention over pages that hold the
latent (``ops/latent_attention.py``), one leading dense SwiGLU layer, then
sigmoid-routed experts with a selection bias beside one shared expert.

Per layer, ``a = rms(x)`` the layer's normed input (float32 stream ``x``)::

    c_q  = rms(a W_dq)                               q_lora_rank (1,536)
    [q_nope ; q_rope] = c_q W_uq      a head: qk_nope + qk_rope (128 + 64)
    [c_kv ; k_r] = a W_dkv            kv_lora_rank + qk_rope (512 + 64)
    c_kv = rms(c_kv)   q_rope, k_rope = rot(q_rope), rot(k_r)
                       k_rope is ONE for all heads
    THE CACHE OF A POSITION IS [c_kv ; k_rope]: 576 numbers a layer
    [k_nope ; v] = c_kv W_ukv         a head: qk_nope + v_head (128 + 128)
    s[t, u] = scale (q_nope . k_nope + q_rope . k_rope)      u <= t
    y = softmax(s) v       out = concat_heads(y) W_o
    x = x + out
    layer < first_k_dense_replace:   x = x + swiglu(rms(x))   18,432 wide
    else: g = sigmoid(b W_r) (float32, all n_routed_experts)  b = rms(x)
          chosen = top_k(g + bias)    w = g[chosen] / sum g[chosen]
          x = x + routed_scaling_factor * sum_e w_e expert_e(b)
                + shared(b)           ``models/moe.py:HeldExperts``

``rot`` turns interleaved pairs ``(2i, 2i + 1)`` (``cohere2_moe.py``'s
rotation) by yarn's frequencies and
``scale = (qk_nope + qk_rope) ** -0.5 * mscale ** 2``
(``ops/latent_attention.py``). After the last layer ``rms``, then the
untied head over the rows of the vocabulary this chip holds.

Served only, paged only. **A page holds latents, not heads**: the
``cache`` collection is ``latent_<i>`` [kv_pages, page_size, lanes] a
layer in ``kv_dtype`` (``lanes``: kv_lora_rank + qk_rope_head_dim rounded
up to whole lane tiles, 640 for 576, the spare lanes zeros:
``ops/latent_attention.py:pool_lanes`` says why), indexed by page on its first
axis like every paged model's pools, so the engine's one pool manager
(page plans, copy-on-write, scrub, parking, the prefix cache) runs it
unchanged. The two forms of the attend:

* a call without ``last_pos`` (a decode step; a speculative verify) is
  ABSORBED: ``q_abs = q_nope W_uk^T`` a head, all heads score the page
  rows as they lie, ``W_uv`` after the softmax; nothing a head wide is
  stored or built;
* a call with ``last_pos`` is a PREFILL, EXPANDED: the bucket runs
  ``prefill_rows`` positions at a time through ALL layers (a scan over
  passes whose carry is the pools, so what a long bucket holds at once
  does not grow with it; a pass that is all padding is skipped). A pass
  writes its latents into the row's pages, reads the row's latents back
  (its own and those of earlier passes or of a served prefix), expands
  keys and values a head up to its own last position and runs causal
  attention at the head's sizes.

``prepare_params`` splits the published ``q_b_proj`` and ``kv_b_proj``
into the parts each form multiplies (a slice of a weight inside a decode
step is a copy of it every step) and keeps the selection bias float32.
Weights in ``weights_dtype``; the residual stream, the norms, the
rotation, the router and the softmax's statistics in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .cohere2_moe import _DTYPES, pool_slots, rotate_interleaved
from .decoder_parts import RMSNorm, embed_tokens, rms, untied_head
from .moe import HeldExperts

FAMILY = "kimi_k2"
BIAS = "e_score_correction_bias"
_MOE_CHUNK_ROWS = 8192  # sorted token-picks a block of the grouped products


@dataclasses.dataclass
class KimiK2Config:
    """``config.json``'s keys under their own names (``rope_scaling``'s
    flattened), then what the chip holds and how it is served."""

    model_type: str = FAMILY            # first: a program key's family
    vocab_size: int = 163840            # rows of embedding and head held
    hidden_size: int = 7168
    intermediate_size: int = 18432      # the leading dense layers' width
    moe_intermediate_size: int = 2048   # width of one expert
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_routed_experts: int = 384         # the router's outputs
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0           # rope_scaling.factor (yarn)
    rope_original_max: int = 4096   # .original_max_position_embeddings
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the routed experts [lo, hi) this chip holds of every expert layer
    held_experts: Tuple[int, int] = (0, 384)
    # positions a row may reach (the block table's length times a page)
    block_size: int = 36864
    # positions a pass of a prefill through all layers
    prefill_rows: int = 4096
    decode: bool = False
    page_size: int = 0
    kv_pages: int = 0
    weights_dtype: str = "bf16"
    kv_dtype: str = "bf16"

    def __post_init__(self):
        self.held_experts = tuple(int(e) for e in self.held_experts)
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotation turns pairs: qk_rope_head_dim "
                             "must be even")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers")

    # -- what the serving engine asks a model's config --------------------

    def build(self) -> nn.Module:
        return KimiK2(self)

    def program_key(self) -> tuple:
        return dataclasses.astuple(self)

    def decode_config(self) -> "KimiK2Config":
        return dataclasses.replace(self, decode=True)

    def program_tag(self) -> str:
        return (f",{FAMILY}:L={self.num_hidden_layers}"
                f",w={self.weights_dtype},kv={self.kv_dtype}")

    @property
    def latent_width(self) -> int:
        """Numbers a layer keeps a cached position."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_lanes(self) -> int:
        """Lanes a pool row takes: ``latent_width`` in whole lane tiles."""
        from ..ops.latent_attention import pool_lanes
        return pool_lanes(self.kv_lora_rank, self.qk_rope_head_dim)

    def attend_paths(self) -> Tuple[str, ...]:
        from ..ops.latent_attention import latent_attend_path
        dt, kv = _DTYPES[self.weights_dtype], _DTYPES[self.kv_dtype]
        return (latent_attend_path(
            self.page_size, dt, kv, self.kv_lora_rank,
            self.qk_rope_head_dim),) * self.num_hidden_layers

    def prepare_params(self, params):
        """Weights as served: every leaf in ``weights_dtype`` but the
        selection bias (float32, added to float32 scores), and each
        layer's published ``q_b_proj`` [q_lora_rank, heads * (nope +
        rope)] and ``kv_b_proj`` [kv_lora_rank, heads * (nope + v)] split
        into the parts a step multiplies: ``q_b_nope``, ``q_b_rope``,
        ``kv_b_k``, ``kv_b_v``, the heads side by side. A tree that is
        split already passes through."""
        dt = _DTYPES[self.weights_dtype]
        H = self.num_attention_heads
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)

        def cast(path, x):
            name = str(getattr(path[-1], "key", path[-1]))
            return jnp.asarray(x, jnp.float32 if name == BIAS else dt)

        def split(layer):
            attn = layer.get("self_attn") if hasattr(layer, "get") else None
            if not attn or "q_b_proj" not in attn:
                return layer
            attn = dict(attn)
            qb = attn.pop("q_b_proj").reshape(-1, H, nope + rope)
            kvb = attn.pop("kv_b_proj").reshape(-1, H, nope + dv)
            attn["q_b_nope"] = qb[:, :, :nope].reshape(-1, H * nope)
            attn["q_b_rope"] = qb[:, :, nope:].reshape(-1, H * rope)
            attn["kv_b_k"] = kvb[:, :, :nope].reshape(-1, H * nope)
            attn["kv_b_v"] = kvb[:, :, nope:].reshape(-1, H * dv)
            return {**layer, "self_attn": attn}

        return {name: split(layer) for name, layer in
                jax.tree_util.tree_map_with_path(cast, params).items()}


class LatentAttention(nn.Module):
    """One layer's attention as a function of arrays: the layer's pool
    comes in and goes out beside the output, and ``KimiK2`` keeps it."""

    config: KimiK2Config

    @nn.compact
    def __call__(self, h, pool, block_table, cache_pos, prefill: bool):
        """``h`` [b, t, C] (the layer's normed input), the first of each
        row's ``t`` positions at ``cache_pos`` [b]. Returns ``(out [b, t,
        C] float32, pool)`` with the new positions' latents written."""
        from ..ops import latent_attention as la
        from ..ops.paged_attention import report_path
        cfg = self.config
        b, t, C = h.shape
        H, rq, r = (cfg.num_attention_heads, cfg.q_lora_rank,
                    cfg.kv_lora_rank)
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        page = cfg.page_size
        S = block_table.shape[1] * page
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        eps = cfg.rms_norm_eps
        init, ones = nn.initializers.normal(0.02), nn.initializers.ones
        w_dq = self.param("q_a_proj", init, (C, rq), dt)
        g_q = self.param("q_a_layernorm", ones, (rq,), dt)
        w_qn = self.param("q_b_nope", init, (rq, H * nope), dt)
        w_qr = self.param("q_b_rope", init, (rq, H * rope), dt)
        w_dkv = self.param("kv_a_proj_with_mqa", init, (C, r + rope), dt)
        g_kv = self.param("kv_a_layernorm", ones, (r,), dt)
        w_uk = self.param("kv_b_k", init, (r, H * nope), dt)
        w_uv = self.param("kv_b_v", init, (r, H * dv), dt)
        w_o = self.param("o_proj", init, (H * dv, C), dt)
        inv = la.yarn_inv_freq(rope, cfg.rope_theta, cfg.rope_factor,
                               cfg.rope_original_max, cfg.rope_beta_fast,
                               cfg.rope_beta_slow)
        gain = (la.yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                / la.yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
        scale = la.softmax_scale(nope + rope, cfg.rope_factor,
                                 cfg.rope_mscale_all_dim)
        path = cfg.attend_paths()[0]
        report_path(path, (b, t, H, r + rope), str(jnp.dtype(dt)))
        hb = h.astype(dt)
        wpos, phys, off = pool_slots(block_table, cache_pos, t, page)

        def dot(x, w):
            return jnp.dot(x, w, preferred_element_type=jnp.float32)

        with jax.named_scope("attn.latent.q"):
            c_q = rms(dot(hb, w_dq), g_q, eps).astype(dt)
            q_nope = dot(c_q, w_qn).astype(dt)                # [b,t,H*nope]
            q_rope = gain * rotate_interleaved(
                dot(c_q, w_qr).reshape(b, t, H, rope), wpos[:, :, None],
                cfg.rope_theta, inv)
            if not prefill:
                q_abs = jnp.einsum(
                    "bthd,rhd->bthr", q_nope.reshape(b, t, H, nope),
                    w_uk.reshape(r, H, nope),
                    preferred_element_type=jnp.float32)
                q = jnp.concatenate([q_abs, q_rope], -1).astype(kv_dt)
        with jax.named_scope("attn.latent.kv"):
            ckv = dot(hb, w_dkv)                              # [b,t,r+rope]
            k_rope = gain * rotate_interleaved(
                ckv[:, :, None, r:], wpos[:, :, None], cfg.rope_theta,
                inv)[:, :, 0]
            new = jnp.concatenate(
                [rms(ckv[..., :r], g_kv, eps), k_rope,
                 jnp.zeros((b, t, pool.shape[2] - r - rope), jnp.float32)],
                -1).astype(kv_dt)
            pool = pool.at[phys, off].set(new)

        if not prefill:
            live = block_table[:, 0] != 0
            resident = jnp.where(live, cache_pos + t, 0)
            # the pages the live rows hold, none skipped
            self.sow("counters", "pages",
                     jnp.stack([((resident + page - 1) // page).sum(),
                                jnp.zeros((), resident.dtype)]).astype(
                                    jnp.int32),
                     reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((2,), jnp.int32))
            # [live positions, bytes of cache they hold in this layer]
            # (a layer's pool, which bounds it, is under 2**31 bytes)
            row_bytes = pool.shape[2] * jnp.dtype(kv_dt).itemsize
            self.sow("counters", "latent",
                     jnp.stack([resident.sum(),
                                resident.sum() * row_bytes]).astype(
                                    jnp.int32),
                     reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((2,), jnp.int32))
            with jax.named_scope("attn.latent.attend"):
                z = la.decode_attend(q, pool, block_table, cache_pos, r,
                                     scale, path)             # [b,t,H,r]
            with jax.named_scope("attn.latent.out"):
                y = jnp.einsum("bthr,rhd->bthd", z.astype(dt),
                               w_uv.reshape(r, H, dv),
                               preferred_element_type=jnp.float32)
                out = dot(y.reshape(b, t, H * dv).astype(dt), w_o)
            return jnp.where((wpos < S)[:, :, None], out, jnp.nan), pool

        q_rope = jnp.moveaxis(q_rope, 2, 1).astype(kv_dt)     # [b,H,t,rope]
        ys = []
        for row in range(b):
            rows = pool[block_table[row]].reshape(S, pool.shape[2])
            with jax.named_scope("attn.latent.expand"):
                k_nope, v = la.expand(
                    rows[:, :r].astype(dt), w_uk, w_uv,
                    jnp.minimum(cache_pos[row] + t, S))
            with jax.named_scope("attn.latent.attend"):
                ys.append(la.prefill_attend(
                    q_nope[row].astype(kv_dt), q_rope[row],
                    k_nope.astype(kv_dt), rows[:, r:r + rope],
                    v.astype(kv_dt),
                    cache_pos[row], H, scale, path))
        with jax.named_scope("attn.latent.out"):
            out = dot(jnp.stack(ys).astype(dt), w_o)
        return jnp.where((wpos < S)[:, :, None], out, jnp.nan), pool


class SwiGLU(nn.Module):
    config: KimiK2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        C, F = cfg.hidden_size, cfg.intermediate_size
        init = nn.initializers.normal(0.02)
        wg = self.param("gate_proj", init, (C, F), dt)
        wu = self.param("up_proj", init, (C, F), dt)
        wd = self.param("down_proj", init, (F, C), dt)
        xb = x.astype(dt)
        up = (jax.nn.silu(jnp.dot(xb, wg, preferred_element_type=jnp.float32))
              * jnp.dot(xb, wu, preferred_element_type=jnp.float32))
        return jnp.dot(up.astype(dt), wd, preferred_element_type=jnp.float32)


class Block(nn.Module):
    config: KimiK2Config
    dense: bool         # a leading dense layer: SwiGLU in the experts' place

    @nn.compact
    def __call__(self, x, pool, block_table, cache_pos, prefill: bool):
        """``LatentAttention``'s arguments with the residual stream ``x``
        [b, t, C] in ``h``'s place: ``(x, pool)``."""
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        b, t, C = x.shape
        a = RMSNorm(cfg.rms_norm_eps, dt, name="input_layernorm")(x)
        y, pool = LatentAttention(cfg, name="self_attn")(
            a, pool, block_table, cache_pos, prefill)
        x = x + y
        h = RMSNorm(cfg.rms_norm_eps, dt,
                    name="post_attention_layernorm")(x)
        if self.dense:
            with jax.named_scope("mlp"):
                return x + SwiGLU(cfg, name="mlp")(h), pool
        live = jnp.repeat(block_table[:, 0] != 0, t)
        routed, shared = HeldExperts(
            hidden=C, width=cfg.moe_intermediate_size,
            n_experts=cfg.n_routed_experts, topk=cfg.num_experts_per_tok,
            held=cfg.held_experts, n_shared=cfg.n_shared_experts,
            norm_topk=cfg.norm_topk_prob, chunk_rows=_MOE_CHUNK_ROWS,
            param_dtype=dt, score_fn="sigmoid", select_bias=True,
            name="mlp")(h.reshape(b * t, C), live)
        return x + (cfg.routed_scaling_factor * routed
                    + shared).reshape(b, t, C), pool


class KimiK2(nn.Module):
    """``__call__(tokens [b, t], train=False, block_table=, cache_pos=,
    last_pos=None)`` -> float32 logits [b, t, V], or [b, V] at position
    ``last_pos`` of every row when that is given (a prefill: the positions
    past it are padding)."""

    config: KimiK2Config

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
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        L, C, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        b, t = tokens.shape
        shape = (cfg.kv_pages, cfg.page_size, cfg.pool_lanes)
        pools = [self.variable("cache", f"latent_{i}",
                               lambda: jnp.zeros(shape, kv_dt))
                 for i in range(L)]
        dense = [i < cfg.first_k_dense_replace for i in range(L)]
        if last_pos is None:
            x = embed_tokens(self, tokens, V, C, dt)
            for i, pool in enumerate(pools):
                x, pool.value = Block(cfg, dense[i], name=f"layers_{i}")(
                    x, pool.value, block_table, cache_pos, False)
            return untied_head(self, x, None, V, cfg.rms_norm_eps, dt)

        if self.is_initializing():
            raise ValueError("initialise with one token a row: a prefill "
                             "reads the parameters that a decode step "
                             "declares")
        step = math.gcd(t, cfg.prefill_rows)
        p = self.variables["params"]
        n_valid = jnp.broadcast_to(last_pos + 1, (b,))
        blocks = [Block(cfg, d) for d in dense]

        def one_pass(carry, lo):
            def run(carry):
                values, x_last = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, lo, step, axis=1)
                x = p["embed_tokens"][tok].astype(jnp.float32)
                out = []
                for i, value in enumerate(values):
                    x, value = blocks[i].apply(
                        {"params": p[f"layers_{i}"]}, x, value, block_table,
                        cache_pos + lo, True)
                    out.append(value)
                here = jnp.clip(last_pos - lo, 0, step - 1)
                row = jax.lax.dynamic_index_in_dim(x, here, axis=1,
                                                   keepdims=False)
                return tuple(out), jnp.where(last_pos - lo == here, row,
                                             x_last)

            # a pass past every row's prompt is a bucket's padding
            return jax.lax.cond(lo < n_valid.max(), run, lambda c: c,
                                carry), None

        (values, x_last), _ = jax.lax.scan(
            one_pass, (tuple(pool.value for pool in pools),
                       jnp.zeros((b, C), jnp.float32)),
            jnp.arange(0, t, step))
        for pool, value in zip(pools, values):
            pool.value = value
        return untied_head(self, x_last[:, None], None, V,
                           cfg.rms_norm_eps, dt)[:, 0]
