"""Qwen3-Next (``model_type: qwen3_next``), built from its ``config.json``
keys, as the serving engine runs it: three gated delta-rule layers
(``ops/gated_delta.py``) to one gated softmax-attention layer over pages
(``ops/paged_attention.py``), and in every layer softmax-routed experts
beside one shared expert behind a sigmoid gate (``models/moe.py``).

With ``rms0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (a zero-centred
weight) and layer ``i`` FULL where ``(i + 1) % full_attention_interval ==
0``, else DELTA, on the float32 residual stream ``x``::

    x = x + mixer_i(rms0(x; w_in));   x = x + moe(rms0(x; w_post))
    logits = rms0(x_last; w_f) W_head                          (untied)

    full layer (``num_attention_heads`` query heads over
    ``num_key_value_heads`` key-value heads of ``head_dim``, no bias):
      [q | gate] = a Wq            a head: head_dim of query, then of gate
      k = a Wk, v = a Wv;  q = rms0(q; w_q), k = rms0(k; w_k) a head
      rotary, rotate-half, on the first ``partial_rotary_factor`` of a
      head's lanes only, the others pass
      y = softmax(q k^T / sqrt(head_dim), causal) v
      out = (y * sigmoid(gate)) Wo

    delta layer (``linear_num_key_heads`` key heads, ``linear_num_value_
    heads`` value heads):
      [q | k | v | z] = a W_qkvz     [b | a'] = a W_ba
      (q, k, v) = silu(causal depthwise conv, ``linear_conv_kernel_dim``
                  wide, no bias, over the channels [q | k | v])
      q, k: each head over its l2 norm; q *= dk^-1/2; value head h reads
      key head h // (value heads / key heads)
      beta = sigmoid(b);  alpha = exp(-exp(A_log) softplus(a' + dt_bias))
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t
      out = concat_h(o_h / sqrt(mean(o_h^2) + eps) * w_n * silu(z_h)) W_out

    moe:  p = softmax(u W_r) over ``num_experts``, float32; the
          ``num_experts_per_tok`` largest, renormalised
          routed = the picks on HELD experts (``held_experts``)
          out = routed + sigmoid(u w_sg) * shared(u)

Served only. **A row holds two kinds of cache** (``models/serving.py``:
``row_state``): the full layers keep pages of keys and values,
``kv_<i>`` = ``{"k", "v"}`` [kv_pages, page_size, kv_heads * head_dim] in
``kv_dtype``, that grow with the row; the delta layers keep ONE state
block a row whatever its length, ``delta_<i>`` = ``{"S": [state_blocks,
value heads, dk, dv]`` in ``state_dtype`` (float32 as served), ``"conv":
[state_blocks, kernel - 1, channels]`` in ``kv_dtype`` (the convolution's
last inputs). The block table's last column names the row's state block.
A row at cursor 0 has no past: its block reads as zeros whatever the last
row left in it. A call of one token a row is a decode step; a call of
more tokens with ``last_pos`` is a prefill (``last_pos + 1`` of them are
the prompt: the padding leaves state and convolution inputs as they are,
and lies in the pages past the cursor, masked until overwritten), run
``prefill_rows`` positions at a time through all layers with the pools
and the rows' states carried from pass to pass. More tokens without
``last_pos`` would be a speculative verify, which is refused: rejected
drafts could not be taken out of the state again without a copy of it.

``prepare_params`` lays the published ``in_proj_qkvz`` and ``in_proj_ba``
(grouped a key head: ``[q_g | k_g | v_2g v_2g+1 | z_2g z_2g+1]``, ``[b_2g
b_2g+1 | a_2g a_2g+1]``) out flat, ``[q | k | v | z]`` and ``[b | a]``
(``qkvz_proj``, ``ba_proj``): a step then slices activations, not a
weight. Weights in ``weights_dtype`` (``A_log`` and ``dt_bias`` float32);
the residual stream, the norms, the gates, the router and the state's
arithmetic in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .cohere2_moe import _DTYPES, by_query_block, pool_slots
from .decoder_parts import embed_tokens, rms, rotate_half
from .moe import HeldExperts

FAMILY = "qwen3_next"
_MOE_CHUNK_ROWS = 8192  # sorted token-picks a block of the grouped products
_F32_LEAVES = ("A_log", "dt_bias")


@dataclasses.dataclass
class Qwen3NextConfig:
    """``config.json``'s keys under their own names, then what the chip
    holds and how it is served."""

    model_type: str = FAMILY            # first: a program key's family
    vocab_size: int = 151936            # rows of embedding and head held
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512              # the router's outputs
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    # the routed experts [lo, hi) this chip holds of every layer
    held_experts: Tuple[int, int] = (0, 512)
    # positions a row may reach (the block table's pages times a page)
    block_size: int = 50688
    # positions a chunk of the delta rule's prefill
    delta_chunk: int = 64
    # positions a pass of a prefill through all layers
    prefill_rows: int = 4096
    attn_query_block: int = 2048      # queries a paged attend of a prefill
    decode: bool = False
    page_size: int = 0
    kv_pages: int = 0
    state_blocks: int = 0               # the engine sets it, the null one too
    weights_dtype: str = "bf16"
    kv_dtype: str = "bf16"
    state_dtype: str = "f32"

    # a row holds ONE state block beside its run of pages: the engine
    # plans, parks, frees and scrubs both as one (models/serving.py)
    row_state = True

    def __post_init__(self):
        self.held_experts = tuple(int(e) for e in self.held_experts)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be whole groups of "
                             "key-value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("value heads must be whole groups of key "
                             "heads")
        if self.rotary_dim % 2:
            raise ValueError("rotary turns halves: the rotated lanes must "
                             "be even")
        if self.shared_expert_intermediate_size != self.moe_intermediate_size:
            raise ValueError("the shared expert runs as one expert of the "
                             "routed experts' width")

    # -- what the serving engine asks a model's config --------------------

    def build(self) -> nn.Module:
        return Qwen3Next(self)

    def program_key(self) -> tuple:
        return dataclasses.astuple(self)

    def decode_config(self) -> "Qwen3NextConfig":
        return dataclasses.replace(self, decode=True)

    def program_tag(self) -> str:
        return (f",{FAMILY}:L={self.num_hidden_layers}"
                f",w={self.weights_dtype},kv={self.kv_dtype}")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def row_state_names(self) -> Tuple[str, ...]:
        """The entries of the ``cache`` collection that are indexed by
        STATE BLOCK, not by page."""
        return tuple(f"delta_{i}" for i in range(self.num_hidden_layers)
                     if not self.is_full(i))

    def attend_path(self, full: bool) -> str:
        """A layer's path, from the dispatch point the layers themselves
        ask: the paged attend's implementation in a full layer, the delta
        rule's id in the others."""
        from ..ops.paged_attention import paged_attend_path
        return paged_attend_path(
            self.num_key_value_heads * self.head_dim, self.page_size,
            _DTYPES[self.weights_dtype], _DTYPES[self.kv_dtype],
            head_dim=self.head_dim, gated_delta=not full)

    def attend_paths(self) -> Tuple[str, ...]:
        return tuple(self.attend_path(self.is_full(i))
                     for i in range(self.num_hidden_layers))

    def state_bytes_per_row(self) -> int:
        """Bytes of state and convolution inputs one row holds over all
        delta layers."""
        layer = (self.linear_num_value_heads * self.linear_key_head_dim
                 * self.linear_value_head_dim
                 * jnp.dtype(_DTYPES[self.state_dtype]).itemsize
                 + (self.linear_conv_kernel_dim - 1) * self.conv_channels
                 * jnp.dtype(_DTYPES[self.kv_dtype]).itemsize)
        return layer * len(self.row_state_names())

    def prepare_params(self, params):
        """Weights as served: every leaf in ``weights_dtype`` but
        ``A_log`` and ``dt_bias`` (float32, the decay's exponents), and
        each delta layer's published ``in_proj_qkvz`` / ``in_proj_ba``
        laid out flat (``qkvz_proj`` / ``ba_proj``; the module docstring
        says why). A tree that is flat already passes through."""
        dt = _DTYPES[self.weights_dtype]
        Hk, Hv = self.linear_num_key_heads, self.linear_num_value_heads
        dk, dv, r = (self.linear_key_head_dim, self.linear_value_head_dim,
                     self.linear_num_value_heads // self.linear_num_key_heads)

        def cast(path, x):
            name = str(getattr(path[-1], "key", path[-1]))
            return jnp.asarray(x, jnp.float32 if name in _F32_LEAVES
                               else dt)

        def flat(layer):
            mix = layer.get("linear_attn") if hasattr(layer, "get") else None
            if not mix or "in_proj_qkvz" not in mix:
                return layer
            mix = dict(mix)
            w = mix.pop("in_proj_qkvz")
            C = w.shape[0]
            w = w.reshape(C, Hk, 2 * dk + 2 * r * dv)
            parts = (w[:, :, :dk], w[:, :, dk:2 * dk],
                     w[:, :, 2 * dk:2 * dk + r * dv],
                     w[:, :, 2 * dk + r * dv:])
            mix["qkvz_proj"] = jnp.concatenate(
                [p.reshape(C, -1) for p in parts], axis=1)
            ba = mix.pop("in_proj_ba").reshape(C, Hk, 2 * r)
            mix["ba_proj"] = jnp.concatenate(
                [ba[:, :, :r].reshape(C, Hv), ba[:, :, r:].reshape(C, Hv)],
                axis=1)
            return {**layer, "linear_attn": mix}

        return {name: flat(layer) for name, layer in
                jax.tree_util.tree_map_with_path(cast, params).items()}


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


class ZeroCentredRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + weight)``: the weight is kept as
    its distance from one."""

    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        return rms(x, 1.0 + w.astype(jnp.float32), self.eps)


class GatedAttention(nn.Module):
    """One full layer's attention through the engine's page pool: writes
    the new positions' keys and values, attends (the Pallas page walk
    where ``paged_attend_path`` says so, else a gather of the row's pages),
    and gates each head's output by the sigmoid of its own gate."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, h, pools, block_table, cache_pos):
        """``h`` [b, t, C] (the layer's normed input), the first of each
        row's ``t`` positions at ``cache_pos`` [b]; ``pools`` the layer's
        ``(k, v)`` page pools. Returns ``(out [b, t, C] float32, pools)``."""
        from ..ops.paged_attention import (GATHER, paged_attention_gqa,
                                           report_path)
        cfg = self.config
        b, t, C = h.shape
        H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        G, rd = H // KV, cfg.rotary_dim
        page = cfg.page_size
        S = block_table.shape[1] * page
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        eps, theta = cfg.rms_norm_eps, cfg.rope_theta
        init, zeros = nn.initializers.normal(0.02), nn.initializers.zeros
        wq = self.param("q_proj", init, (C, H * 2 * hd), dt)
        wk = self.param("k_proj", init, (C, KV * hd), dt)
        wv = self.param("v_proj", init, (C, KV * hd), dt)
        wo = self.param("o_proj", init, (H * hd, C), dt)
        gq = 1.0 + self.param("q_norm", zeros, (hd,), dt).astype(jnp.float32)
        gk = 1.0 + self.param("k_norm", zeros, (hd,), dt).astype(jnp.float32)
        hb = h.astype(dt)
        wpos, phys, off = pool_slots(block_table, cache_pos, t, page)
        k_pool, v_pool = pools
        with jax.named_scope("attn.gated.kv"):
            k = rotate_half(rms(_dot(hb, wk).reshape(b, t, KV, hd), gk, eps),
                            wpos[:, :, None], theta, rd)
            k_pool = k_pool.at[phys, off].set(
                k.reshape(b, t, KV * hd).astype(kv_dt))
            v_pool = v_pool.at[phys, off].set(_dot(hb, wv).astype(kv_dt))

        live = block_table[:, 0] != 0
        read = jnp.where(live, (cache_pos + t - 1) // page + 1, 0)
        # the pages the live rows hold, none skipped
        self.sow("counters", "pages",
                 jnp.stack([read.sum(), jnp.zeros((), read.dtype)]).astype(
                     jnp.int32),
                 reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros((2,), jnp.int32))
        path = cfg.attend_path(full=True)
        report_path(path, (b, KV, t, G, hd), str(jnp.dtype(dt)))

        def attend(hb_c, pos_c):
            """The queries of ``hb_c`` [b, tc, C], the first at position
            ``pos_c`` [b] of its row, against the pool (every position of
            this call is in it already); gated and projected."""
            tc = hb_c.shape[1]
            qpos = pos_c[:, None] + jnp.arange(tc)[None, :]
            with jax.named_scope("attn.gated.q"):
                qg = _dot(hb_c, wq).reshape(b, tc, KV, G, 2, hd)
                q = rotate_half(rms(qg[..., 0, :], gq, eps),
                                qpos[:, :, None, None], theta, rd)
                # grouped by key-value head, as the kernel takes them
                q = jnp.moveaxis(q, 1, 2).astype(dt)      # [b,KV,tc,G,hd]
            with jax.named_scope("attn.gated.attend"):
                if path != GATHER:
                    y = paged_attention_gqa(q, k_pool, v_pool, block_table,
                                            pos_c)
                else:
                    k_all = k_pool[block_table].reshape(b, S, KV, hd)
                    v_all = v_pool[block_table].reshape(b, S, KV, hd)
                    att = jnp.einsum(
                        "bktgd,bskd->bktgs", q, k_all.astype(dt),
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
                    seen = jnp.arange(S)[None, None, :] <= qpos[:, :, None]
                    att = jnp.where(seen[:, None, :, None, :], att,
                                    -jnp.inf)
                    att = jax.nn.softmax(att, axis=-1).astype(dt)
                    y = jnp.einsum("bktgs,bskd->bktgd", att,
                                   v_all.astype(dt),
                                   preferred_element_type=jnp.float32)
            with jax.named_scope("attn.gated.out"):
                y = (jnp.moveaxis(y, 2, 1).astype(jnp.float32)
                     * jax.nn.sigmoid(qg[..., 1, :]))     # [b,tc,KV,G,hd]
                return _dot(y.reshape(b, tc, H * hd).astype(dt), wo)

        out = by_query_block(attend, hb, cache_pos, cfg.attn_query_block)
        return (jnp.where((wpos < S)[:, :, None], out, jnp.nan),
                (k_pool, v_pool))


class GatedDeltaNet(nn.Module):
    """One delta layer's mixer as a function of arrays: the layer's state
    comes in and goes out beside the output, and ``Qwen3Next`` keeps it."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, h, state, sb, fresh, n_valid=None):
        """``h`` [b, t, C] (the layer's normed input). Without ``n_valid``
        a decode step: ``t`` is 1 and ``state`` the layer's POOLS ``(S,
        conv)``, read and updated in one pass for the rows' blocks ``sb``
        [b]; ``fresh`` [b]: the row has no past. With ``n_valid`` [b] a
        prefill pass: ``state`` is the rows' own ``(S [b, Hv, dk, dv],
        conv [b, K - 1, ch])`` and the first ``n_valid`` positions are
        the prompt's. Returns ``(out [b, t, C] float32, state)``."""
        from ..ops import gated_delta as gd
        from ..ops.paged_attention import report_path
        cfg = self.config
        b, t, C = h.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        K, ch, r = cfg.linear_conv_kernel_dim, cfg.conv_channels, Hv // Hk
        kd, vd = Hk * dk, Hv * dv
        dt = _DTYPES[cfg.weights_dtype]
        init = nn.initializers.normal(0.02)
        w_in = self.param("qkvz_proj", init, (C, ch + vd), dt)
        w_ba = self.param("ba_proj", init, (C, 2 * Hv), dt)
        w_conv = self.param("conv1d", init, (ch, K), dt)
        a_log = self.param("A_log", nn.initializers.zeros, (Hv,),
                           jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,),
                             jnp.float32)
        w_n = self.param("norm", nn.initializers.ones, (dv,), dt)
        w_out = self.param("out_proj", init, (vd, C), dt)
        S, conv = state
        hb = h.astype(dt)
        report_path(cfg.attend_path(full=False), (b, t, Hv, dk, dv),
                    str(jnp.dtype(dt)))
        with jax.named_scope("attn.delta.proj"):
            mixed = _dot(hb, w_in)                          # [b,t,ch+vd]
            x_in = mixed[..., :ch].astype(conv.dtype)
            z = mixed[..., ch:].reshape(b, t, Hv, dv)
            ba = _dot(hb, w_ba)
            beta = jax.nn.sigmoid(ba[..., :Hv])             # [b,t,Hv]
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)
        with jax.named_scope("attn.delta.conv"):
            if n_valid is None:
                y, conv = gd.conv_step(conv, sb, x_in[:, 0], w_conv, fresh)
                y = y[:, None]
            else:
                y, conv = gd.conv_run(conv, x_in, w_conv, n_valid)
            # value head h reads key head h // r
            q = jnp.repeat(gd.l2norm(y[..., :kd].reshape(b, t, Hk, dk))
                           * dk ** -0.5, r, axis=2)
            k = jnp.repeat(gd.l2norm(y[..., kd:2 * kd].reshape(b, t, Hk,
                                                               dk)),
                           r, axis=2)
            v = y[..., 2 * kd:].reshape(b, t, Hv, dv)
        if n_valid is None:
            n_live = (sb != 0).sum(dtype=jnp.int32)
            row_bytes = cfg.state_bytes_per_row() // len(
                cfg.row_state_names())
            # [live rows, bytes of state and convolution inputs they hold
            # in this layer]
            self.sow("counters", "state",
                     jnp.stack([n_live, n_live * row_bytes]),
                     reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((2,), jnp.int32))
            with jax.named_scope("attn.delta.state"):
                o, S = gd.decode_step(S, sb, q[:, 0], k[:, 0], v[:, 0],
                                      g[:, 0], beta[:, 0], fresh)
            o = o[:, None]                                  # [b,1,Hv,dv]
        else:
            valid = jnp.arange(t)[None, :] < n_valid[:, None]
            with jax.named_scope("attn.delta.chunks"):
                to = lambda x: jnp.moveaxis(x, 1, 2)     # noqa: E731
                o, S = gd.prefill(S, to(q), to(k), to(v), to(g), to(beta),
                                  valid, math.gcd(t, cfg.delta_chunk),
                                  mm_dtype=dt)
                o = jnp.moveaxis(o, 1, 2)                   # [b,t,Hv,dv]
        with jax.named_scope("attn.delta.out"):
            y = (rms(o, w_n, cfg.rms_norm_eps) * jax.nn.silu(z))
            out = _dot(y.reshape(b, t, vd).astype(dt), w_out)
        return out, (S, conv)


class Block(nn.Module):
    config: Qwen3NextConfig
    full: bool          # a full-attention layer; else a delta layer

    @nn.compact
    def __call__(self, x, cache, block_table, sb, cache_pos, n_valid=None):
        """The residual stream ``x`` [b, t, C] through one layer;
        ``cache`` is the layer's page pools (a full layer) or what
        ``GatedDeltaNet`` takes as ``state``. Returns ``(x, cache)``."""
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        b, t, C = x.shape
        a = ZeroCentredRMSNorm(cfg.rms_norm_eps, dt,
                               name="input_layernorm")(x)
        if self.full:
            y, cache = GatedAttention(cfg, name="self_attn")(
                a, cache, block_table, cache_pos)
        else:
            y, cache = GatedDeltaNet(cfg, name="linear_attn")(
                a, cache, sb, cache_pos == 0, n_valid)
        x = x + y
        h = ZeroCentredRMSNorm(cfg.rms_norm_eps, dt,
                               name="post_attention_layernorm")(x)
        h = h.reshape(b * t, C)
        routed, shared = HeldExperts(
            hidden=C, width=cfg.moe_intermediate_size,
            n_experts=cfg.num_experts, topk=cfg.num_experts_per_tok,
            held=cfg.held_experts, n_shared=1,
            norm_topk=cfg.norm_topk_prob, chunk_rows=_MOE_CHUNK_ROWS,
            param_dtype=dt, score_fn="softmax",
            name="mlp")(h, jnp.repeat(sb != 0, t))
        w_sg = self.param("shared_expert_gate",
                          nn.initializers.normal(0.02), (C, 1), dt)
        with jax.named_scope("moe.shared"):
            shared = jax.nn.sigmoid(_dot(h.astype(dt), w_sg)) * shared
        return x + (routed + shared).reshape(b, t, C), cache


def pass_rows(t: int, rows: int, chunk: int) -> int:
    """Positions a pass of a prefill of ``t``: the largest divisor of
    ``t`` not above ``rows`` that is whole chunks of the delta rule (a
    bucket capped at the row's extent need not be a power of two)."""
    c = math.gcd(t, chunk)
    n = t // c
    return c * max(d for d in range(1, max(1, min(n, rows // c)) + 1)
                   if n % d == 0)


class Qwen3Next(nn.Module):
    """``__call__(tokens [b, t], train=False, block_table=, cache_pos=,
    last_pos=None)`` -> float32 logits [b, 1, V] of a decode step (``t``
    is 1), or [b, V] at position ``last_pos`` of every row when that is
    given (a prefill: the positions past it are padding). ``block_table``
    [b, pages a row + 1]: each row's pages, then its state block."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_table=None,
                 cache_pos=None, last_pos=None):
        from ..ops import gated_delta as gd
        cfg = self.config
        if train:
            raise ValueError("this decoder is served, not trained: the "
                             "trainer runs the GPT-2 block only, and the "
                             "chunked delta rule has no backward pass here "
                             "(ROADMAP.md B1)")
        if not (cfg.decode and cfg.page_size > 0 and cfg.state_blocks > 1):
            raise ValueError("this decoder runs through the engine's pool "
                             "of pages and state blocks only: decode=True, "
                             "page_size > 0 and state_blocks > 1")
        if block_table is None or cache_pos is None:
            raise ValueError("decode needs block_table and cache_pos")
        for name in ("weights_dtype", "kv_dtype", "state_dtype"):
            if getattr(cfg, name) not in _DTYPES:
                raise ValueError(f"{name} must be one of "
                                 f"{sorted(_DTYPES)}, got "
                                 f"{getattr(cfg, name)!r}")
        mb = cfg.block_size // cfg.page_size
        if block_table.shape[1] != mb + 1:
            raise ValueError(
                f"a row's table is its {mb} pages and its state block: "
                f"{mb + 1} columns, got {block_table.shape[1]}")
        b, t = tokens.shape
        if t > 1 and last_pos is None:
            raise ValueError(
                "several tokens a row without last_pos is a speculative "
                "verify (spec_tokens > 0): refused, a recurrent state "
                "cannot be rewound past rejected drafts without a copy "
                "of it")
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        st_dt = _DTYPES[cfg.state_dtype]
        L, C, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        P, page, B = cfg.kv_pages, cfg.page_size, cfg.state_blocks
        width = cfg.num_key_value_heads * cfg.head_dim
        Hv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        K, ch = cfg.linear_conv_kernel_dim, cfg.conv_channels
        full = [cfg.is_full(i) for i in range(L)]
        store = [self.variable(
            "cache", f"kv_{i}",
            lambda: {"k": jnp.zeros((P, page, width), kv_dt),
                     "v": jnp.zeros((P, page, width), kv_dt)})
            if full[i] else self.variable(
                "cache", f"delta_{i}",
                lambda: {"S": jnp.zeros((B, Hv, dk, dv), st_dt),
                         "conv": jnp.zeros((B, K - 1, ch), kv_dt)})
            for i in range(L)]
        leaves = [("k", "v") if f else ("S", "conv") for f in full]
        take = lambda i: tuple(                      # noqa: E731
            store[i].value[n] for n in leaves[i])
        put = lambda i, c: dict(zip(leaves[i], c))   # noqa: E731
        bt, sb = block_table[:, :mb], block_table[:, mb]

        def head(x):
            y = ZeroCentredRMSNorm(cfg.rms_norm_eps, dt, name="norm")(x)
            w = self.param("lm_head", nn.initializers.normal(0.02), (C, V),
                           dt)
            with jax.named_scope("head"):
                return _dot(y.astype(dt), w)

        if last_pos is None:
            x = embed_tokens(self, tokens, V, C, dt)
            for i in range(L):
                x, cache = Block(cfg, full[i], name=f"layers_{i}")(
                    x, take(i), bt, sb, cache_pos)
                store[i].value = put(i, cache)
            return head(x)

        if self.is_initializing():
            raise ValueError("initialise with one token a row: a prefill "
                             "reads the parameters that a decode step "
                             "declares")
        step = pass_rows(t, cfg.prefill_rows, cfg.delta_chunk)
        p = self.variables["params"]
        n_valid = jnp.broadcast_to(last_pos + 1, (b,))
        blocks = [Block(cfg, f) for f in full]
        fresh = cache_pos == 0

        def one_pass(carry, lo):
            def run(carry):
                caches, x_last = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, lo, step, axis=1)
                x = p["embed_tokens"][tok].astype(jnp.float32)
                out = []
                for i, cache in enumerate(caches):
                    x, cache = blocks[i].apply(
                        {"params": p[f"layers_{i}"]}, x, cache, bt, sb,
                        cache_pos + lo, jnp.clip(n_valid - lo, 0, step))
                    out.append(cache)
                here = jnp.clip(last_pos - lo, 0, step - 1)
                row = jax.lax.dynamic_index_in_dim(x, here, axis=1,
                                                   keepdims=False)
                return tuple(out), jnp.where(last_pos - lo == here, row,
                                             x_last)

            # a pass past every row's prompt is a bucket's padding
            return jax.lax.cond(lo < n_valid.max(), run, lambda c: c,
                                carry), None

        # the pools of the full layers as they are; of the delta layers
        # the rows' own blocks, taken out once and put back once
        caches = tuple(take(i) if full[i]
                       else gd.load_rows(*take(i), sb, fresh)
                       for i in range(L))
        (caches, x_last), _ = jax.lax.scan(
            one_pass, (caches, jnp.zeros((b, C), jnp.float32)),
            jnp.arange(0, t, step))
        for i, cache in enumerate(caches):
            if not full[i]:
                with jax.named_scope("attn.delta.state"):
                    cache = gd.store_rows(*take(i), sb, *cache)
            store[i].value = put(i, cache)
        return head(x_last[:, None])[:, 0]


def prefill_counted(config, bucket: int, start: int, suffix: int):
    """The full layers' ``gqa_chunks`` of one dispatched prefill
    (``models/cohere2_moe.py:grouped_prefill_chunks``)."""
    from ..ops.paged_attention import KERNEL
    from .cohere2_moe import grouped_prefill_chunks
    windows = {i: 0 for i, path in enumerate(config.attend_paths())
               if path == KERNEL}
    step = pass_rows(bucket, config.prefill_rows, config.delta_chunk)
    return grouped_prefill_chunks(config, windows, step, bucket, start,
                                  suffix)


Qwen3NextConfig.prefill_counted = prefill_counted
