"""Keye-VL-2.0's language model (``model_type: KeyeVL2``), built from its
``config.json`` keys, as the serving engine runs it.

Per layer, input ``x`` [T, hidden] (float32 residual stream)::

    a  = rms(x)                                     weight, no bias
    q, k, v = a Wq, a Wk, a Wv                      grouped heads, no bias
    q, k = rms over each head (its own weight), then rotary: rotate-half
           over the whole head (mrope's three components are equal for
           text, which leaves the one-dimensional rotation)
    qI = a WqI [T, J, d]   kI = layer_norm(a WkI) [T, d]   wI = a Ww [T, J]
    qI, kI = rotary (rotate-half over all d)
    attention over the ``sa_config.topk`` keys of largest
    I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])  (``ops/sparse_attention``)
    x  = x + attention Wo
    b  = rms(x)
    x  = x + experts(b)       ``models/moe.py:HeldExperts``: softmax over
         all ``num_experts`` in float32, the ``num_experts_per_tok``
         largest renormalised, the held experts' part, no shared expert

After the last layer ``rms``, then ``logits = y W_head`` (untied) over
the rows of the vocabulary this chip holds.

The norm, the grouped projections with their q/k norms and rotation, the
embedding and the head are ``decoder_parts.py``'s, shared with
``brumby.py``. Serving only, paged only (as ``cohere2_moe.py``, whose pool
arithmetic, query blocks and prefill in passes it shares: a bucket runs
``prefill_rows`` positions a pass, padding-only passes skipped): a layer
keeps ``k``, ``v`` and ``kI`` (after norm and rotary) a position in the
pools, ``[kv_pages, page_size, kv_heads * head_dim]`` twice and a page's
index keys side by side on one row. Weights and pools in ``weights_dtype``
/ ``kv_dtype``; residual, norms, softmaxes, router, index scores float32.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .cohere2_moe import _DTYPES, by_query_block, pool_slots
from .decoder_parts import (RMSNorm, embed_tokens, key_heads, project_out,
                            qkvo_params, query_heads, rotate_half,
                            untied_head, value_heads)
from .moe import HeldExperts

FAMILY = "KeyeVL2"


@dataclasses.dataclass
class KeyeVL2Config:
    """``config.json``'s keys under their own names (``sa_config``'s
    flattened), then what the chip holds and how it is served."""

    model_type: str = FAMILY            # first: a program key's family
    vocab_size: int = 151936            # rows of embedding and head held
    hidden_size: int = 2048
    moe_intermediate_size: int = 768    # width of one expert
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    num_experts: int = 128              # the router's outputs
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    sparse_topk: int = 2048             # sa_config.topk: keys a query keeps
    # the routed experts [lo, hi) this chip holds of every layer
    held_experts: Tuple[int, int] = (0, 128)
    prefill_rows: int = 1024          # positions a pass of a prefill
    block_size: int = 36864           # positions a row may reach
    moe_chunk_rows: int = 8192
    attn_query_block: int = 1024      # queries a sparse attend of a prefill
    attn_key_block: int = 2048        # keys a step of its loops
    decode: bool = False
    page_size: int = 0
    kv_pages: int = 0
    weights_dtype: str = "bf16"
    kv_dtype: str = "bf16"

    def __post_init__(self):
        self.held_experts = tuple(int(e) for e in self.held_experts)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be whole groups of "
                             "key-value heads")
        if self.head_dim % 2 or self.indexer_head_dim % 2:
            raise ValueError("rotary turns halves: head dimensions must "
                             "be even")
        if self.sparse_topk < 1:
            raise ValueError("sparse_topk must be at least 1")

    # -- what the serving engine asks a model's config --------------------

    def build(self) -> nn.Module:
        return KeyeVL2(self)

    def program_key(self) -> tuple:
        return dataclasses.astuple(self)

    def decode_config(self) -> "KeyeVL2Config":
        return dataclasses.replace(self, decode=True)

    def program_tag(self) -> str:
        return (f",{FAMILY}:L={self.num_hidden_layers}"
                f",w={self.weights_dtype},kv={self.kv_dtype}")

    def attend_paths(self) -> Tuple[str, ...]:
        from ..ops.paged_attention import paged_attend_path
        dt, kv = _DTYPES[self.weights_dtype], _DTYPES[self.kv_dtype]
        return (paged_attend_path(
            self.num_key_value_heads * self.head_dim, self.page_size, dt,
            kv, head_dim=self.head_dim,
            sparse_topk=self.sparse_topk),) * self.num_hidden_layers

    def prepare_params(self, params):
        """Weights as served: every leaf in ``weights_dtype``."""
        dt = _DTYPES[self.weights_dtype]
        return jax.tree.map(lambda x: jnp.asarray(x, dt), params)


def write_index_keys(pool, ki, block_table, cache_pos, page: int):
    """The index keys ``ki`` [b, t, d] of every row's ``t`` new positions
    (the first at ``cache_pos``) into ``pool`` (a page's ``page * d``
    values side by side, ``sparse_attention.index_pool_shape``), whole
    pages at a time: the pages the positions fall on are read, the new
    keys laid over their places and the pages written back (a scatter of
    whole rows; a window of 64 lanes a position is a loop of as many
    single updates on the chip, 0.15 s a layer of a 32 k prefill). A
    page past the row's table is the null page."""
    b, t, d = ki.shape
    mb = block_table.shape[1]
    n = -(-t // page) + 1               # pages t positions can fall on
    lblk = (cache_pos // page)[:, None] + jnp.arange(n)[None, :]
    phys = jnp.take_along_axis(block_table, jnp.clip(lblk, 0, mb - 1),
                               axis=1)
    phys = jnp.where(lblk < mb, phys, 0)                        # [b, n]
    rows = jax.vmap(
        lambda r, new, at: jax.lax.dynamic_update_slice(r, new, (at, 0)))(
            pool[phys].reshape(b, n * page, d), ki, cache_pos % page)
    return pool.at[phys].set(rows.reshape((b, n) + pool.shape[1:]))


class SparsePagedAttention(nn.Module):
    """One layer's attention through the engine's page pools: writes the
    new positions' keys, values and index keys, then attends over the
    keys its index keeps (``ops/sparse_attention.py``)."""

    config: KeyeVL2Config

    @nn.compact
    def __call__(self, h, block_table, cache_pos):
        from ..ops import sparse_attention as sa
        from ..ops.paged_attention import paged_attend_path, report_path
        cfg = self.config
        b, t, C = h.shape
        H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        G = H // KV
        J, di, topk = (cfg.indexer_num_heads, cfg.indexer_head_dim,
                       cfg.sparse_topk)
        page, P = cfg.page_size, cfg.kv_pages
        S = block_table.shape[1] * page
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        eps, theta = cfg.rms_norm_eps, cfg.rope_theta
        init = nn.initializers.normal(0.02)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        wq, wk, wv, wo, gq, gk = qkvo_params(self, C, H, KV, hd, dt)
        wqi = self.param("index_q_proj", init, (C, J * di), dt)
        wki = self.param("index_k_proj", init, (C, di), dt)
        wwi = self.param("index_weights_proj", init, (C, J), dt)
        gki = self.param("index_k_norm_weight", ones, (di,), dt)
        bki = self.param("index_k_norm_bias", zeros, (di,), dt)
        hb = h.astype(dt)
        wpos, phys, off = pool_slots(block_table, cache_pos, t, page)

        k = key_heads(hb, wk, gk, wpos, KV, hd, eps, theta)
        v = value_heads(hb, wv)
        ki = jnp.dot(hb, wki, preferred_element_type=jnp.float32)
        mean = ki.mean(-1, keepdims=True)
        var = jnp.square(ki - mean).mean(-1, keepdims=True)
        ki = ((ki - mean) * jax.lax.rsqrt(var + eps)
              * gki.astype(jnp.float32) + bki.astype(jnp.float32))
        ki = rotate_half(ki, wpos, theta)
        ck = self.variable("cache", "k",
                           lambda: jnp.zeros((P, page, KV * hd), kv_dt))
        cv = self.variable("cache", "v",
                           lambda: jnp.zeros((P, page, KV * hd), kv_dt))
        # a page's index keys side by side on whole lane rows: a minor
        # dimension of 64 would be laid out with the page index on the
        # lanes and the whole pool copied to and fro around every scatter
        ci = self.variable(
            "cache", "ki",
            lambda: jnp.zeros(sa.index_pool_shape(P, page, di), kv_dt))
        k_pool = ck.value.at[phys, off].set(
            k.reshape(b, t, KV * hd).astype(kv_dt))
        v_pool = cv.value.at[phys, off].set(v.astype(kv_dt))
        ki_pool = write_index_keys(ci.value, ki.astype(kv_dt), block_table,
                                   cache_pos, page)
        ck.value, cv.value, ci.value = k_pool, v_pool, ki_pool

        live = block_table[:, 0] != 0
        resident = jnp.where(live, cache_pos + t, 0)
        self.sow("counters", "keys",
                 jnp.stack([jnp.minimum(resident, topk).sum(),
                            resident.sum()]).astype(jnp.int32),
                 reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros((2,), jnp.int32))
        # the pages the live rows hold, none skipped: the index reads
        # every resident position (the counter every paged layer keeps)
        self.sow("counters", "pages",
                 jnp.stack([((resident + page - 1) // page).sum(),
                            jnp.zeros((), resident.dtype)]).astype(
                                jnp.int32),
                 reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros((2,), jnp.int32))
        self.sow("counters", "sparse_rows",
                 (resident > topk).sum(dtype=jnp.int32),
                 reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros((), jnp.int32))

        path = paged_attend_path(KV * hd, page, dt, kv_dt, head_dim=hd,
                                 sparse_topk=topk)
        report_path(path, (b, KV, t, G, hd), str(jnp.dtype(dt)))
        rows = t <= sa.ROWS_MAX_T
        if not rows:
            # a prefill: the row's window, once a layer (a row is a
            # thousandth of the pool)
            k_row = k_pool[block_table].reshape(b, S, KV, hd)
            v_row = v_pool[block_table].reshape(b, S, KV, hd)
            ki_row = ki_pool[block_table].reshape(b, S, di)

        def attend(hb_c, pos_c):
            """The queries of ``hb_c`` [b, tc, C], the first at position
            ``pos_c`` [b] of its row, against the pools (every position
            of this call is in them already); their output projected."""
            tc = hb_c.shape[1]
            qpos = pos_c[:, None] + jnp.arange(tc)[None, :]
            q = query_heads(hb_c, wq, gq, qpos, KV, G, hd, eps,
                            theta).astype(dt)
            qi = jnp.einsum("btc,cjd->btjd", hb_c, wqi.reshape(C, J, di),
                            preferred_element_type=jnp.float32)
            qi = rotate_half(qi, qpos[:, :, None], theta).astype(dt)
            wi = jnp.dot(hb_c, wwi, preferred_element_type=jnp.float32)
            if rows:
                y = sa.attend_rows(q, qi, wi, k_pool, v_pool, ki_pool,
                                   block_table, pos_c, topk)
            else:
                y = sa.attend_block(q, qi, wi, k_row, v_row, ki_row, pos_c,
                                    topk, cfg.attn_key_block)
            return project_out(y, wo, KV, G, hd)

        out = by_query_block(attend, hb, cache_pos, cfg.attn_query_block)
        return jnp.where((wpos < S)[:, :, None], out, jnp.nan)


class Block(nn.Module):
    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x, block_table, cache_pos):
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        b, t, C = x.shape
        a = RMSNorm(cfg.rms_norm_eps, dt, name="input_layernorm")(x)
        x = x + SparsePagedAttention(cfg, name="self_attn")(
            a, block_table, cache_pos)
        h = RMSNorm(cfg.rms_norm_eps, dt,
                    name="post_attention_layernorm")(x)
        live = jnp.repeat(block_table[:, 0] != 0, t)
        routed, _shared = HeldExperts(
            hidden=C, width=cfg.moe_intermediate_size,
            n_experts=cfg.num_experts, topk=cfg.num_experts_per_tok,
            held=cfg.held_experts, n_shared=0, score_fn="softmax",
            norm_topk=cfg.norm_topk_prob, chunk_rows=cfg.moe_chunk_rows,
            param_dtype=dt, name="mlp")(h.reshape(b * t, C), live)
        return x + routed.reshape(b, t, C)


class KeyeVL2(nn.Module):
    """``__call__(tokens [b, t], train=False, block_table=, cache_pos=,
    last_pos=None)`` -> float32 logits [b, t, V], or [b, V] at position
    ``last_pos`` of every row when that is given (``in_passes``)."""

    config: KeyeVL2Config

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
            raise ValueError("paged decode needs block_table and cache_pos")
        for name in ("weights_dtype", "kv_dtype"):
            if getattr(cfg, name) not in _DTYPES:
                raise ValueError(f"{name} must be one of {sorted(_DTYPES)}, "
                                 f"got {getattr(cfg, name)!r}")
        dt = _DTYPES[cfg.weights_dtype]
        if last_pos is not None and tokens.shape[1] > cfg.prefill_rows:
            return self.in_passes(tokens, block_table, cache_pos, last_pos)
        x = embed_tokens(self, tokens, cfg.vocab_size, cfg.hidden_size, dt)
        for i in range(cfg.num_hidden_layers):
            x = Block(cfg, name=f"layers_{i}")(x, block_table, cache_pos)
        return untied_head(self, x, last_pos, cfg.vocab_size,
                           cfg.rms_norm_eps, dt)

    def in_passes(self, tokens, block_table, cache_pos, last_pos):
        """The prefill of a bucket longer than ``prefill_rows``: logits
        [b, V] at ``last_pos``, the bucket taken ``prefill_rows``
        positions at a time through all layers and the passes that hold
        only its padding skipped (``cohere2_moe.py:prefill_in_passes``).
        A pass is whole ``attn_query_block``s and more than
        ``sparse_attention.ROWS_MAX_T`` queries, so its attend is the
        whole bucket's, block for block; each layer gathers the row's
        window (``k_row``, ``v_row``, ``ki_row``) once a pass. It stands
        below ``__call__`` and is entered there in two lines, so that the
        line of ``__call__`` a decode step's index kernel was traced under
        is the parent's."""
        cfg = self.config
        x = prefill_in_passes(
            self, [Block(cfg)] * cfg.num_hidden_layers, tokens, block_table,
            cache_pos, last_pos, cfg.prefill_pass(tokens.shape[1]))
        return untied_head(self, x[:, None], None, cfg.vocab_size,
                           cfg.rms_norm_eps,
                           _DTYPES[cfg.weights_dtype])[:, 0]


# Down here, and the config's answer to the serving engine's question with
# it: no line at or above ``SparsePagedAttention``'s last may move, a
# decode step's index kernel carries them (tests/test_serve_hybrid_pool.py)
from .cohere2_moe import prefill_in_passes, prefill_pass  # noqa: E402

KeyeVL2Config.prefill_pass = prefill_pass
