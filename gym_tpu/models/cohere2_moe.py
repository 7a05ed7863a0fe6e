"""Cohere2-MoE decoder (``model_type: cohere2_moe``; Command A+), built
from its ``config.json`` keys, as the serving engine runs it.

Per layer, input ``x`` [T, hidden]:

* ``h = LN(x)``: mean and variance in float32, a weight, no bias; ONE
  norm feeds both branches (``use_parallel_block``);
* attention over ``num_attention_heads`` query heads that read
  ``num_key_value_heads`` key-value heads in groups, no bias, no q/k
  norm. A ``sliding_attention`` layer rotates q and k over the whole head
  in interleaved pairs ``(2i, 2i+1)`` (``rope_gptj``) and query ``i``
  sees key ``j`` iff ``0 <= i - j < sliding_window``; a
  ``full_attention`` layer takes no position at all and is causal;
* experts (``models/moe.py:HeldExperts``): sigmoid scores over
  ``num_experts``, the ``num_experts_per_tok`` largest renormalised, the
  routed experts this chip holds (``held_experts``), and
  ``num_shared_experts`` shared experts averaged;
* ``x' = x + attention + routed + shared``.

After the last layer ``LN``, then ``logits = logit_scale * y E^T`` over
the rows of the tied embedding this chip holds (``vocab_size`` here is
that count: a sliced vocabulary is a smaller vocabulary).

Serving only, paged only: keys and values in the engine's page pools,
``[kv_pages, page_size, num_key_value_heads * head_dim]`` a layer in
``kv_dtype``; weights as stored (``weights_dtype``) with float32 sums;
residual, norms, router, softmax and logits float32. A prefill runs its
bucket ``prefill_rows`` positions a pass, padding-only passes skipped.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .moe import HeldExperts

FAMILY = "cohere2_moe"
SLIDING, FULL = "sliding_attention", "full_attention"
_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@dataclasses.dataclass
class Cohere2MoeConfig:
    """``config.json``'s keys under their own names, then what the chip
    holds and how it is served."""

    model_type: str = FAMILY            # first: a program key's family
    vocab_size: int = 262144            # rows of the embedding held here
    hidden_size: int = 4096
    intermediate_size: int = 4096       # width of one expert
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ()   # one entry a layer
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    # the routed experts [lo, hi) this chip holds of every layer
    held_experts: Tuple[int, int] = (0, 128)
    prefill_rows: int = 4096          # positions a pass of a prefill
    block_size: int = 16384           # positions a row may reach
    moe_chunk_rows: int = 8192
    attn_query_block: int = 2048      # queries a paged attend of a prefill
    decode: bool = False
    page_size: int = 0
    kv_pages: int = 0
    weights_dtype: str = "bf16"
    kv_dtype: str = "bf16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else SLIDING
            for i in range(self.num_hidden_layers))
        self.held_experts = tuple(int(e) for e in self.held_experts)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be whole groups of "
                             "key-value heads")

    # -- what the serving engine asks a model's config --------------------

    def build(self) -> nn.Module:
        return Cohere2Moe(self)

    def program_key(self) -> tuple:
        return dataclasses.astuple(self)

    def decode_config(self) -> "Cohere2MoeConfig":
        return dataclasses.replace(self, decode=True)

    def program_tag(self) -> str:
        return (f",{FAMILY}:L={self.num_hidden_layers}"
                f",w={self.weights_dtype},kv={self.kv_dtype}")

    def window(self, layer: int) -> int:
        return (self.sliding_window
                if self.layer_types[layer] == SLIDING else 0)

    def kv_layout(self):
        """``(kv_heads, head_dim, window or 0)`` a layer: what a layer
        keeps a position and how far back it reads."""
        return [(self.num_key_value_heads, self.head_dim, self.window(i))
                for i in range(self.num_hidden_layers)]

    def attend_paths(self) -> Tuple[str, ...]:
        """The paged attend's implementation, layer by layer, from the
        dispatch point the layers themselves ask."""
        from ..ops.paged_attention import paged_attend_path
        dt, kv = _DTYPES[self.weights_dtype], _DTYPES[self.kv_dtype]
        return tuple(paged_attend_path(
            self.num_key_value_heads * self.head_dim, self.page_size, dt,
            kv, head_dim=self.head_dim, window=w)
            for _h, _d, w in self.kv_layout())

    def prepare_params(self, params):
        """Weights as served: every leaf in ``weights_dtype``."""
        dt = _DTYPES[self.weights_dtype]
        return jax.tree.map(lambda x: jnp.asarray(x, dt), params)


def rotate_interleaved(x, pos, theta: float, inv=None):
    """Rotary embedding over the whole last axis in pairs ``(2i, 2i+1)``
    (``rope_gptj``, ``rotary_pct`` 1): ``x`` [..., t, *, d] float32 with
    ``pos`` broadcastable to ``x``'s leading axes up to ``t``. ``inv``
    [d / 2]: the pairs' frequencies where they are not ``theta``'s own
    (yarn: ``ops/latent_attention.py:yarn_inv_freq``)."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv           # [..., d/2]
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)
    even = jnp.arange(d) % 2 == 0
    # (x0, x1) -> (-x1, x0): the partner lane, signed
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * cos + partner * sin


def pool_slots(block_table, cache_pos, t: int, page: int):
    """Where a call's ``t`` new positions of every row go in a page pool:
    ``(wpos, phys, off)`` [b, t], the position in its row, the physical
    page and the place in it. A position past the row's table lands on
    the null page (the caller poisons its output)."""
    mb = block_table.shape[1]
    wpos = cache_pos[:, None] + jnp.arange(t)[None, :]       # [b, t]
    lblk = jnp.clip(wpos // page, 0, mb - 1)
    phys = jnp.take_along_axis(block_table, lblk, axis=1)
    phys = jnp.where(wpos < mb * page, phys, 0)
    return wpos, phys, wpos % page


def by_query_block(attend, hb, cache_pos, qc: int):
    """``attend(hb_c [b, tc, C], pos_c [b]) -> [b, tc, C]`` over the
    queries of ``hb`` [b, t, C], ``qc`` at a time once ``t`` is whole
    blocks of it: 16k positions of 128 heads are a GiB in float32 before
    the rotation has made its copies."""
    b, t, C = hb.shape
    if t <= qc or t % qc:
        return attend(hb, cache_pos)
    out = jax.lax.map(
        lambda c: attend(
            jax.lax.dynamic_slice_in_dim(hb, c * qc, qc, axis=1),
            cache_pos + c * qc),
        jnp.arange(t // qc))                            # [t/qc, b, qc, C]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, C)


class LayerNormNoBias(nn.Module):
    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
        x = x.astype(jnp.float32)
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.eps) \
            * w.astype(jnp.float32)


class GroupedPagedAttention(nn.Module):
    """One layer's attention through the engine's page pool: writes the
    new positions' keys and values, then attends (``ops/paged_attention
    .py``: the Pallas page walk where ``paged_attend_path`` says so, else
    a gather of the row's pages into its logical window)."""

    config: Cohere2MoeConfig
    window: int

    @nn.compact
    def __call__(self, h, block_table, cache_pos):
        from ..ops.paged_attention import (GATHER, paged_attend_path,
                                           paged_attention_gqa,
                                           report_path)
        cfg = self.config
        b, t, C = h.shape
        H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        G = H // KV
        page, P = cfg.page_size, cfg.kv_pages
        mb = block_table.shape[1]
        S = mb * page
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        init = nn.initializers.normal(0.02)
        wq = self.param("q_proj", init, (C, H * hd), dt)
        wk = self.param("k_proj", init, (C, KV * hd), dt)
        wv = self.param("v_proj", init, (C, KV * hd), dt)
        wo = self.param("o_proj", init, (H * hd, C), dt)
        hb = h.astype(dt)
        wpos, phys, off = pool_slots(block_table, cache_pos, t, page)
        k = jnp.einsum("btc,ckd->btkd", hb, wk.reshape(C, KV, hd),
                       preferred_element_type=jnp.float32)
        v = jnp.dot(hb, wv, preferred_element_type=jnp.float32)
        if self.window:
            k = rotate_interleaved(k, wpos[:, :, None], cfg.rope_theta)
        ck = self.variable("cache", "k",
                           lambda: jnp.zeros((P, page, KV * hd), kv_dt))
        cv = self.variable("cache", "v",
                           lambda: jnp.zeros((P, page, KV * hd), kv_dt))
        k_pool = ck.value.at[phys, off].set(
            k.reshape(b, t, KV * hd).astype(kv_dt))
        v_pool = cv.value.at[phys, off].set(v.astype(kv_dt))
        ck.value, cv.value = k_pool, v_pool

        live = block_table[:, 0] != 0
        last = cache_pos + t - 1
        first_page = (jnp.maximum(cache_pos - self.window + 1, 0) // page
                      if self.window else jnp.zeros_like(cache_pos))
        read = jnp.where(live, last // page + 1 - first_page, 0)
        self.sow("counters", "pages",
                 jnp.stack([read.sum(), jnp.where(live, first_page,
                                                  0).sum()]).astype(
                                                      jnp.int32),
                 reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros((2,), jnp.int32))

        path = paged_attend_path(KV * hd, page, dt, kv_dt, head_dim=hd,
                                 window=self.window)
        report_path(path, (b, KV, t, G, hd), str(jnp.dtype(dt)))

        def attend(hb_c, pos_c):
            """The queries of ``hb_c`` [b, tc, C], the first at position
            ``pos_c`` [b] of its row, against the pool (every position of
            this call is in it already); their output projected."""
            tc = hb_c.shape[1]
            # queries leave their projection grouped by key-value head,
            # as the kernel takes them: [b, KV, tc, G, hd]
            q = jnp.einsum("btc,ckgd->bktgd", hb_c,
                           wq.reshape(C, KV, G, hd),
                           preferred_element_type=jnp.float32)
            qpos = pos_c[:, None] + jnp.arange(tc)[None, :]
            if self.window:
                q = rotate_interleaved(q, qpos[:, None, :, None],
                                       cfg.rope_theta)
            q = q.astype(dt)
            with jax.named_scope("attn.window" if self.window
                                 else "attn.full"):
                if path != GATHER:
                    y = paged_attention_gqa(q, k_pool, v_pool, block_table,
                                            pos_c, window=self.window)
                else:
                    k_all = k_pool[block_table].reshape(b, S, KV, hd)
                    v_all = v_pool[block_table].reshape(b, S, KV, hd)
                    att = jnp.einsum(
                        "bktgd,bskd->bktgs", q, k_all.astype(dt),
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
                    col = jnp.arange(S)[None, None, :]
                    seen = col <= qpos[:, :, None]              # [b, tc, S]
                    if self.window:
                        seen = seen & (col > qpos[:, :, None] - self.window)
                    att = jnp.where(seen[:, None, :, None, :], att,
                                    -jnp.inf)
                    att = jax.nn.softmax(att, axis=-1).astype(dt)
                    y = jnp.einsum("bktgs,bskd->bktgd", att,
                                   v_all.astype(dt),
                                   preferred_element_type=jnp.float32
                                   ).astype(dt)
            return jnp.einsum("bktgd,kgdc->btc", y,
                              wo.reshape(KV, G, hd, C),
                              preferred_element_type=jnp.float32)

        out = by_query_block(attend, hb, cache_pos, cfg.attn_query_block)
        return jnp.where((wpos < S)[:, :, None], out, jnp.nan)


class ParallelBlock(nn.Module):
    config: Cohere2MoeConfig
    layer: int

    @nn.compact
    def __call__(self, x, block_table, cache_pos):
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        b, t, C = x.shape
        h = LayerNormNoBias(cfg.layer_norm_eps, dt,
                            name="input_layernorm")(x)
        a = GroupedPagedAttention(cfg, cfg.window(self.layer),
                                  name="self_attn")(h, block_table,
                                                    cache_pos)
        live = jnp.repeat(block_table[:, 0] != 0, t)
        routed, shared = HeldExperts(
            hidden=C, width=cfg.intermediate_size,
            n_experts=cfg.num_experts, topk=cfg.num_experts_per_tok,
            held=cfg.held_experts, n_shared=cfg.num_shared_experts,
            norm_topk=cfg.norm_topk_prob, chunk_rows=cfg.moe_chunk_rows,
            param_dtype=dt, name="mlp")(h.reshape(b * t, C), live)
        return x + a + (routed + shared).reshape(b, t, C)


class Cohere2Moe(nn.Module):
    """``__call__(tokens [b, t], train=False, block_table=, cache_pos=,
    last_pos=None)`` -> float32 logits [b, t, V], or [b, V] at position
    ``last_pos`` of every row when that is given (a prefill wants one
    position's logits of a bucket of up to 16k: ``in_passes``)."""

    config: Cohere2MoeConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_table=None,
                 cache_pos=None, last_pos=None):
        cfg = self.config
        if train:
            raise ValueError("this decoder is served, not trained: its "
                             "smallest cut does not fit a chip's memory "
                             "under training (ROADMAP.md B1)")
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
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt)
        if last_pos is not None and tokens.shape[1] > cfg.prefill_rows:
            return self.in_passes(tokens, block_table, cache_pos, last_pos)
        x = embed[tokens].astype(jnp.float32)
        for i in range(cfg.num_hidden_layers):
            x = ParallelBlock(cfg, i, name=f"layers_{i}")(x, block_table,
                                                          cache_pos)
        if last_pos is not None:
            x = jax.lax.dynamic_index_in_dim(x, last_pos, axis=1,
                                             keepdims=False)
        return self.head(x, embed)

    def head(self, x, embed):
        """The final norm and the tied embedding's rows: float32 logits
        of ``x`` [..., hidden]."""
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        y = LayerNormNoBias(cfg.layer_norm_eps, dt, name="norm")(x)
        with jax.named_scope("head"):
            return cfg.logit_scale * jnp.dot(
                y.astype(dt), embed.T, preferred_element_type=jnp.float32)

    def in_passes(self, tokens, block_table, cache_pos, last_pos):
        """The prefill of a bucket longer than ``prefill_rows``: logits
        [b, V] at ``last_pos`` (``prefill_in_passes``). It stands below
        ``__call__`` and is entered there in two lines, so that the line
        of ``__call__`` a decode step's kernels were traced under is the
        parent's."""
        cfg = self.config
        x = prefill_in_passes(
            self, [ParallelBlock(cfg, i)
                   for i in range(cfg.num_hidden_layers)],
            tokens, block_table, cache_pos, last_pos,
            cfg.prefill_pass(tokens.shape[1]))
        return self.head(x, self.variables["params"]["embed_tokens"])


# -- a prefill in passes ----------------------------------------------------
# Below everything else: the functions above stand in the call stacks of
# kernels whose compiled bodies carry file and line, so nothing above may
# move (tests/test_serve_hybrid_pool.py). The config class takes its
# answer to ``prefill_pass`` from here for the same reason.


def prefill_pass(config, t: int) -> int:
    """Positions a pass of a prefill of ``t`` (``config.prefill_pass(t)``:
    what the serving engine asks a config, to count the positions a
    dispatched prefill runs): ``t`` itself where the bucket is no longer
    than ``prefill_rows`` and runs whole, else the largest count that
    divides both (a bucket capped at the row's extent need not be a power
    of two)."""
    rows = config.prefill_rows
    return math.gcd(t, rows) if t > rows else t


def prefill_in_passes(mod: nn.Module, blocks, tokens, block_table,
                      cache_pos, last_pos, step: int):
    """A prefill bucket ``tokens`` [b, t], ``step`` positions at a time
    through ALL of ``blocks`` (unbound layer modules, ``__call__(x,
    block_table, cache_pos)``, whose parameters and ``cache`` entries
    ``mod`` holds as ``layers_<i>``, beside ``embed_tokens``): the
    hidden row [b, hidden] of position ``last_pos`` after the last layer.
    A pass's keys and values are in the pools before the next pass
    attends, so every position sees what it saw in one run of the whole
    bucket. A pass that lies wholly past ``last_pos`` is the bucket's
    padding and is skipped (a real ``cond``): it costs nothing and writes
    nothing, and what its pages held before lies past the row's cursor,
    where a decode step writes a position before it attends to it. The
    pools ride the loop's carry and are updated in place."""
    b, t = tokens.shape
    p, caches = mod.variables["params"], mod.variables["cache"]
    names = [f"layers_{i}" for i in range(len(blocks))]
    n_valid = jnp.broadcast_to(last_pos + 1, (b,))

    def one_pass(carry, lo):
        def run(carry):
            pools, x_last = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, lo, step, axis=1)
            x = p["embed_tokens"][tok].astype(jnp.float32)
            out = []
            for name, block, pool in zip(names, blocks, pools):
                x, new = block.apply({"params": p[name], "cache": pool}, x,
                                     block_table, cache_pos + lo,
                                     mutable=["cache"])
                out.append(new["cache"])
            here = jnp.clip(last_pos - lo, 0, step - 1)
            row = jax.lax.dynamic_index_in_dim(x, here, axis=1,
                                               keepdims=False)
            return tuple(out), jnp.where(last_pos - lo == here, row, x_last)

        # a pass past every row's prompt is a bucket's padding
        return jax.lax.cond(lo < n_valid.max(), run, lambda c: c,
                            carry), None

    hidden = p["embed_tokens"].shape[1]
    (pools, x_last), _ = jax.lax.scan(
        one_pass, (tuple(caches[name] for name in names),
                   jnp.zeros((b, hidden), jnp.float32)),
        jnp.arange(0, t, step))
    for name, pool in zip(names, pools):
        mod.put_variable("cache", name, pool)
    return x_last


Cohere2MoeConfig.prefill_pass = prefill_pass


def grouped_prefill_chunks(config, windows, step: int, bucket: int,
                           start: int, suffix: int):
    """``{"layers_<i>/self_attn/gqa_chunks": [run, unmasked]}`` of one
    dispatched prefill (``suffix`` tokens from position ``start``, padded
    to ``bucket``, ``step`` positions a pass): the chunks of keys the
    grouped kernel walked in layer ``i`` and how many of them took its
    body without masks (``ops/paged_attention.py:gqa_chunks``), over the
    passes that ran and ``by_query_block``'s calls of each. ``windows``
    ``{i: window or 0}`` names the layers whose attend is that kernel.
    On the host, from the engine (``config.prefill_counted``): a prefill
    program returns no counters."""
    import numpy as np

    from ..ops.paged_attention import gqa_chunks
    qc = config.attn_query_block
    tc = step if step <= qc or step % qc else qc
    ran = min(bucket, -(-suffix // step) * step)
    pos = start + np.arange(0, ran, tc)
    kvh = config.num_key_value_heads
    by_window = {w: gqa_chunks(
        pos, tc, config.num_attention_heads // kvh, kvh, config.page_size,
        config.block_size // config.page_size, w)
        for w in set(windows.values())}
    return {f"layers_{i}/self_attn/gqa_chunks": by_window[w]
            for i, w in windows.items()}


def prefill_counted(config, bucket: int, start: int, suffix: int):
    """``grouped_prefill_chunks`` of the layers whose attend is the
    grouped kernel (none off the TPU): ``config.prefill_counted``."""
    from ..ops.paged_attention import KERNEL, KERNEL_WINDOW
    windows = {i: w for i, ((_h, _d, w), path) in enumerate(zip(
        config.kv_layout(), config.attend_paths()))
        if path in (KERNEL, KERNEL_WINDOW)}
    return grouped_prefill_chunks(config, windows,
                                  config.prefill_pass(bucket), bucket,
                                  start, suffix)


Cohere2MoeConfig.prefill_counted = prefill_counted
