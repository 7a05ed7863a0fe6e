"""nanoGPT in Flax, matching the reference model family.

Reference (``example/nanogpt/nanogpt.py``): Karpathy-style GPT with
LayerNorm (optional bias, ``:19-28``), causal self-attention (``:47-94``),
GELU MLP (``:104-123``), pre-norm residual blocks (``:126-133``),
``GPTConfig`` + size map small(4L/4H/128)/base/medium/large/xl
(``:136-179``), weight tying (``:206-208``), scaled residual init 0.02/√(2L)
(``:213-217``), ``forward(batch) -> loss`` (``:244-276``),
``crop_block_size`` (``:278-289``), HF GPT-2 weight port (``:291-360``),
decay/no-decay optimizer grouping (``:362-392``), MFU estimator (``:394-408``)
and sampling ``generate`` (``:410-439``).

TPU-first: attention goes through the ``gym_tpu.ops.attention`` interface
(dense XLA now, ring/Pallas drop-in), softmax/loss in f32 with bf16-friendly
matmuls, and everything is static-shape for XLA.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

import numpy as np
import optax

from ..ops.attention import causal_attention


@dataclasses.dataclass
class GPTConfig:
    block_size: int = 1024
    vocab_size: int = 50304  # GPT-2 50257 padded to a multiple of 64
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    dropout: float = 0.0
    bias: bool = True
    # Attention backend: 'dense' (reference behavior), 'flash' (Pallas TPU
    # kernel), or 'ring' (context-parallel over the `seq_axis` mesh axis —
    # long-context support the reference lacks, SURVEY §5.7).
    attn_impl: str = "dense"
    seq_axis: Optional[str] = None
    # Context-parallel chunk assignment (parallel/ring_attention.py):
    # 'zigzag' (default) gives each device half-chunks i and 2cp−1−i so
    # every ring step does balanced useful work (~2× step time vs
    # 'contiguous', VERDICT r4 #5); 'contiguous' keeps plain [i·Tl,(i+1)·Tl)
    # slices. Statically falls back to contiguous when the local chunk
    # cannot split in half (T/cp odd). Affects compute schedule only —
    # params, loss, and checkpoints are layout-independent.
    seq_layout: str = "zigzag"
    # Rematerialize each block in the backward pass: trades ~30% more FLOPs
    # for O(n_layer) less activation memory — the standard TPU lever for
    # fitting GPT-2 base+ shapes (HBM is the bottleneck, MXU has headroom).
    remat: bool = False
    # Mixture-of-Experts (beyond-reference; SURVEY §2.3 EP row): when
    # n_experts > 0, every `moe_every`-th block (i % moe_every == moe_every-1,
    # i.e. alternate blocks at the default 2) replaces its dense MLP with a
    # top-k routed MoEMLP (models/moe.py). `expert_axis` names a GSPMD-auto
    # mesh axis to shard experts over (expert parallelism).
    n_experts: int = 0
    expert_topk: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2
    moe_aux_weight: float = 1e-2
    moe_z_weight: float = 1e-3
    expert_axis: Optional[str] = None
    moe_impl: str = "auto"  # 'ragged'|'einsum'|'dense'|'auto' (models/moe.py)
    moe_chunk_rows: int = 16384  # grouped-matmul row blocking (models/moe.py)
    # Chunked cross-entropy: compute the lm_head matmul + CE over row
    # chunks of `loss_chunk` tokens under `jax.checkpoint`, so the full
    # [B·T, vocab] f32 logits tensor is never materialized (at GPT-2 base
    # with T=1024 that tensor is ~200 MB per sequence — 12+ GB across a
    # vmapped 8-node simulator, the actual cause of the "DeMo 8×base
    # OOM" from the round-2 review). Costs one extra head matmul in the
    # backward (remat); 0 = off (exact reference semantics, single pass).
    loss_chunk: int = 0
    # Autoregressive KV-cache decode mode (beyond-reference: the
    # reference's `generate` re-runs the FULL context every token,
    # nanogpt.py:410-439). With decode=True each __call__ consumes a chunk
    # of new tokens, appends K/V to a per-layer cache ('cache' collection),
    # and attends over cache+chunk — O(T) per new token instead of O(T²).
    decode: bool = False
    # PagedAttention-style decode cache (arXiv 2309.06180): with
    # page_size > 0 (decode mode only) each layer's K/V live in a POOL of
    # `kv_pages` fixed-size pages shared by every batch row, addressed
    # through a per-row block table of physical page ids passed into
    # __call__ (`block_table` [b, block_size//page_size], `cache_pos`
    # [b]). Rows whose tables share page ids share K/V copy-free — the
    # serving engine's prefix cache (gym_tpu/serve/engine.py) builds on
    # exactly this. Page 0 is reserved as the NULL page: writes of
    # deactivated/overflowing rows are redirected there and never read.
    page_size: int = 0
    kv_pages: int = 0
    # Quantized serving (ISSUE 11; inference-only — training always runs
    # f32 params). weights_dtype 'int8'/'int4' stores every block Dense
    # kernel as per-tile int8 + f32 scales (the strategy/compress.py
    # QuantizeCodec tiling, quantized at checkpoint load by
    # serve/load.py:quantize_params) with the dequant fused into the
    # consuming matmul (ops/grouped_matmul.py:quantized_dot).
    # quant_embed extends that to the tied wte embedding/lm_head —
    # SEPARATELY gated because the embedding dominates quality (default
    # f32). kv_dtype 'int8' makes the decode KV caches/page pools
    # int8-storable with a per-(page-slot, head) scale
    # (ops/fused_attention.py:kv_quantize) — same kv_pages budget, 4x
    # the resident payload. quant_tile is the requested codec tile
    # (clamped per-leaf to divide the trailing axis; quant_tile_for).
    weights_dtype: str = "f32"
    kv_dtype: str = "f32"
    quant_tile: int = 256
    quant_embed: bool = False

    def is_moe_layer(self, i: int) -> bool:
        return self.n_experts > 0 and i % self.moe_every == self.moe_every - 1

    # -- what the serving engine asks a model's config (models/serving.py)

    def build(self) -> "GPT":
        return GPT(self)

    def program_key(self) -> tuple:
        return dataclasses.astuple(self)

    def decode_config(self) -> "GPTConfig":
        return decode_config(self)

    def program_tag(self) -> str:
        """Name suffix for quantized-serving configs (ISSUE 11): the f32
        default keeps its historical names (grep-stable), while a
        quantized program's NAME carries its dtypes — the auditor's
        recompile guard treats same-name-different-key as a collision,
        so two dtype variants of one program must not share a name."""
        parts = []
        if self.weights_dtype != "f32":
            parts.append(f"w={self.weights_dtype}"
                         + ("+emb" if self.quant_embed else ""))
        if self.kv_dtype != "f32":
            parts.append(f"kv={self.kv_dtype}")
        return ("," + ",".join(parts)) if parts else ""

    def attend_paths(self) -> Tuple[str, ...]:
        from ..ops.paged_attention import paged_attend_path
        kv = jnp.int8 if self.kv_dtype == "int8" else jnp.float32
        return (paged_attend_path(self.n_embd, self.page_size, jnp.float32,
                                  kv),) * self.n_layer

    def prepare_params(self, params):
        """Quantize-at-load: accept either an f32 checkpoint tree or a
        pre-quantized one (``load_for_serving`` quantizes once; the
        fleet's factory rebuilds then detect and skip)."""
        if self.weights_dtype == "f32":
            return params
        from ..serve.load import params_are_quantized, quantize_params
        return (params if params_are_quantized(params)
                else quantize_params(params, self))

    def without_seq_sharding(self) -> "GPTConfig":
        """Clone with the sequence sharding stripped — for tracing outside
        the mesh (shape inference, init), where ``axis_size(seq_axis)``
        would be unbound. Param shapes are identical."""
        import dataclasses
        return dataclasses.replace(self, seq_axis=None, attn_impl="dense")

    @classmethod
    def gpt2_size_map(cls, size: str) -> "GPTConfig":
        return {
            "small": cls.gpt2_small,
            "base": cls.gpt2_base,
            "medium": cls.gpt2_medium,
            "large": cls.gpt2_large,
            "xl": cls.gpt2_xl,
        }[size]()

    @classmethod
    def gpt2_small(cls):
        # the reference's nonstandard "small": 4 layers / 4 heads / 128 dim
        return cls(n_layer=4, n_head=4, n_embd=128)

    @classmethod
    def gpt2_base(cls):
        return cls(n_layer=12, n_head=12, n_embd=768)

    @classmethod
    def gpt2_medium(cls):
        return cls(n_layer=24, n_head=16, n_embd=1024)

    @classmethod
    def gpt2_large(cls):
        return cls(n_layer=36, n_head=20, n_embd=1280)

    @classmethod
    def gpt2_xl(cls):
        return cls(n_layer=48, n_head=25, n_embd=1600)


def _init_normal(std: float):
    return nn.initializers.normal(stddev=std)


class QuantDense(nn.Module):
    """Dense layer over a per-tile-quantized kernel: params are
    ``qkernel`` (int8, the kernel's own [in, out] shape — int4 values
    are stored in int8, the 4-bit pack being a wire-format detail) and
    ``qscale`` (f32, one scale per ``tile`` consecutive flat elements,
    the QuantizeCodec tiling). The dequant is fused into the consuming
    matmul (``ops/grouped_matmul.py:quantized_dot``) — no f32 kernel is
    ever stored. Param trees are produced by
    ``serve/load.py:quantize_params`` from an f32 checkpoint; the zero/
    one initializers below exist only so ``init``/``eval_shape`` yield
    the right templates."""

    features: int
    tile: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        from ..ops.grouped_matmul import quant_tile_for, quantized_dot
        in_f = x.shape[-1]
        t = quant_tile_for((in_f, self.features), self.tile)
        q = self.param("qkernel", nn.initializers.zeros,
                       (in_f, self.features), jnp.int8)
        scale = self.param("qscale", nn.initializers.ones,
                           (in_f * self.features // t,), jnp.float32)
        y = quantized_dot(x, q, scale)
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros,
                               (self.features,), jnp.float32)
        return y


class QuantEmbed(nn.Module):
    """Tied-embedding twin of :class:`QuantDense` for the ``wte``
    table when ``quant_embed`` is on: ``qembedding`` (int8 [V, C]) +
    ``qscale`` (f32, tiles within rows — ``quant_tile_for`` clamps the
    tile to divide C, so a row's scales never straddle tokens and the
    gather dequantizes only the looked-up rows). ``attend`` is the
    lm_head (logits against the dequantized table, fused)."""

    num_embeddings: int
    features: int
    tile: int

    def setup(self):
        from ..ops.grouped_matmul import quant_tile_for
        self._t = quant_tile_for((self.num_embeddings, self.features),
                                 self.tile)
        self.qembedding = self.param(
            "qembedding", nn.initializers.zeros,
            (self.num_embeddings, self.features), jnp.int8)
        self.qscale = self.param(
            "qscale", nn.initializers.ones,
            (self.num_embeddings * self.features // self._t,),
            jnp.float32)

    def materialize(self, dtype=jnp.float32):
        """The dequantized [V, C] table — only for consumers that
        genuinely need the full matrix (the eval CE path); the hot-path
        lookups below never call it."""
        from ..ops.grouped_matmul import dequantize_tiles
        return dequantize_tiles(self.qembedding, self.qscale, dtype)

    def __call__(self, idx):
        # gather rows of q AND their row-local scales, dequantize only
        # what was looked up
        rows_q = jnp.take(self.qembedding, idx, axis=0)
        sc = self.qscale.reshape(self.num_embeddings,
                                 self.features // self._t)
        rows_s = jnp.take(sc, idx, axis=0)
        return (rows_q.astype(jnp.float32)
                .reshape(*rows_q.shape[:-1], -1, self._t)
                * rows_s[..., None]).reshape(rows_q.shape)

    def attend(self, x):
        from ..ops.grouped_matmul import quantized_attend
        return quantized_attend(x.astype(jnp.float32), self.qembedding,
                                self.qscale)


def _proj(cfg: GPTConfig, features: int, std: float, name: str):
    """Block projection dispatch: plain ``nn.Dense`` at f32 (byte-stable
    default), :class:`QuantDense` under a quantized serving config —
    SAME module name either way, so the quantized param tree is the f32
    tree with each kernel leaf swapped for (qkernel, qscale) in place."""
    if cfg.weights_dtype != "f32":
        return QuantDense(features=features, tile=cfg.quant_tile,
                          use_bias=cfg.bias, name=name)
    return nn.Dense(features, use_bias=cfg.bias,
                    kernel_init=_init_normal(std), name=name)


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, train: bool, block_table=None, cache_pos=None):
        cfg = self.config
        b, t, c = x.shape
        if c % cfg.n_head != 0:
            raise ValueError(
                f"n_embd {c} not divisible by n_head {cfg.n_head}")
        hd = c // cfg.n_head
        qkv = _proj(cfg, 3 * c, 0.02, "c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        if cfg.decode:
            if cfg.page_size > 0:
                y = self._decode_attend_paged(q, k, v, b, t, hd,
                                              block_table, cache_pos)
            else:
                y = self._decode_attend(q, k, v, b, t, hd)
            y = _proj(cfg, c, 0.02 / math.sqrt(2 * cfg.n_layer),
                      "c_proj")(y)
            return y

        drop_active = train and cfg.dropout > 0
        y = None
        if cfg.attn_impl == "flash" and not drop_active:
            # packed-layout Pallas kernel: attention directly on [B, T, C],
            # no head transposes in fwd or bwd (they show up as ~20% of
            # small-model step time otherwise); None → standard path
            from ..ops.flash_attention import packed_flash_attention_or_none
            y = packed_flash_attention_or_none(q, k, v, cfg.n_head)
        if y is None:
            def heads(z):
                return z.reshape(b, t, cfg.n_head, hd).transpose(0, 2, 1, 3)

            rng = self.make_rng("dropout") if drop_active else None
            y = causal_attention(
                heads(q), heads(k), heads(v),
                impl=cfg.attn_impl, seq_axis=cfg.seq_axis,
                seq_layout=cfg.seq_layout,
                dropout_rate=cfg.dropout, dropout_rng=rng,
                deterministic=not train,
            )
            y = y.transpose(0, 2, 1, 3).reshape(b, t, c)
        # residual projection: scaled init per GPT-2 paper (reference :213-217)
        y = _proj(cfg, c, 0.02 / math.sqrt(2 * cfg.n_layer), "c_proj")(y)
        y = nn.Dropout(cfg.dropout, deterministic=not train)(y)
        return y

    def _decode_attend(self, q, k, v, b, t, hd):
        """KV-cache attention: append this chunk's K/V at each row's cache
        cursor and attend each query over everything its row has written so
        far. Works for a multi-token prefill chunk and the 1-token decode
        steps alike.

        The cursor is PER ROW ([b] int32, not a scalar): every batch row is
        an independent sequence at its own position. Single-request
        ``generate_fast`` advances all rows in lockstep (scalar semantics
        recovered exactly); the serving engine (``gym_tpu/serve``) maps
        rows to request slots at different positions — continuous batching
        needs nothing more from the model than this masked per-row attend
        plus per-row cache resets (``serve/engine.py`` scatters a freshly
        prefillled slot row into the cache and rewinds its cursor)."""
        cfg = self.config
        H, S = cfg.n_head, cfg.block_size
        quant = cfg.kv_dtype == "int8"

        def heads(z):
            return z.reshape(b, t, H, hd)

        q, k, v = heads(q), heads(k), heads(v)
        kv_dt = jnp.int8 if quant else q.dtype
        ck = self.variable("cache", "k",
                           lambda: jnp.zeros((b, S, H, hd), kv_dt))
        cv = self.variable("cache", "v",
                           lambda: jnp.zeros((b, S, H, hd), kv_dt))
        ci = self.variable("cache", "i",
                           lambda: jnp.zeros((b,), jnp.int32))
        i = ci.value                                    # [b] per-row cursor
        rows = jnp.arange(b)[:, None]                   # [b, 1]
        wpos = i[:, None] + jnp.arange(t)[None, :]      # [b, t] write pos
        # overflow writes are clamped in-bounds (the scatter would silently
        # drop them; clamping keeps it deterministic) — the row's output is
        # poisoned below either way
        wclamp = jnp.minimum(wpos, S - 1)
        if quant:
            # int8 KV: quantize each written position's per-head vector
            # on scatter, dequantize the whole window on gather — same
            # static shapes and masks as f32, so the quantized stream is
            # the same program modulo the (deterministic) codec
            from ..ops.fused_attention import kv_dequantize, kv_quantize
            cks = self.variable("cache", "k_scale",
                                lambda: jnp.zeros((b, S, H), jnp.float32))
            cvs = self.variable("cache", "v_scale",
                                lambda: jnp.zeros((b, S, H), jnp.float32))
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            kq_all = ck.value.at[rows, wclamp].set(kq)
            vq_all = cv.value.at[rows, wclamp].set(vq)
            ks_all = cks.value.at[rows, wclamp].set(ks)
            vs_all = cvs.value.at[rows, wclamp].set(vs)
            ck.value, cv.value, ci.value = kq_all, vq_all, i + t
            cks.value, cvs.value = ks_all, vs_all
            k_all = kv_dequantize(kq_all, ks_all, q.dtype)
            v_all = kv_dequantize(vq_all, vs_all, q.dtype)
        else:
            k_all = ck.value.at[rows, wclamp].set(k)
            v_all = cv.value.at[rows, wclamp].set(v)
            ck.value, cv.value, ci.value = k_all, v_all, i + t

        # scores over the FULL cache (static shape S); mask out unwritten
        # slots and the causal future within this chunk, per row
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k_all) / math.sqrt(hd)
        col_pos = jnp.arange(S)                         # [S]
        mask = col_pos[None, None, :] <= wpos[:, :, None]   # [b, t, S]
        att = jnp.where(mask[:, None], att.astype(jnp.float32),
                        -jnp.inf)
        att = jax.nn.softmax(att, axis=-1).astype(q.dtype)
        y = jnp.einsum("bhqk,bkhd->bqhd", att, v_all)
        # cache overflow (cursor past block_size) would silently overwrite
        # recent K/V — poison that ROW's output instead so the failure is
        # loud (a traced cursor can't `assert`) without touching the other
        # rows (a full slot must not poison its batch neighbors)
        ok = (i + t <= S)[:, None, None, None]
        y = jnp.where(ok, y, jnp.nan)
        return y.reshape(b, t, H * hd)

    def _decode_attend_paged(self, q, k, v, b, t, hd, block_table,
                             cache_pos):
        """PagedAttention-style KV-cache attention: each layer's K/V live
        in a POOL of ``kv_pages`` fixed-size pages shared by every row;
        ``block_table`` [b, block_size//page_size] maps a row's logical
        blocks to physical page ids and ``cache_pos`` [b] is the row's
        cache cursor (both are ARGUMENTS, not cache variables — the
        engine owns allocation and cursor advance; the cache collection
        holds only the batch-shape-independent pools, so a 1-row prefill
        and an S-row decode run against the SAME buffers).

        Rows whose tables reference the same pages share K/V copy-free —
        the basis of prefix sharing. Invariants the caller (the serving
        engine) maintains: written blocks are uniquely owned (shared
        pages are full, read-only prefix blocks), and deactivated rows'
        tables are redirected to the NULL page 0. Writes at positions
        past ``block_size`` (speculative drafts near the window edge) go
        to the null page and their query outputs are NaN-poisoned
        PER POSITION — an emitted token can never come from an
        out-of-window position, while in-window positions of the same
        row stay clean.

        Two implementations of the attend, chosen by
        ``ops/paged_attention.py:paged_attend_path`` from backend, shapes
        and dtypes. The GATHER path (off the TPU, an int8 pool, shapes
        that do not tile) gathers each row's pages into its logical [S]
        window and reduces over the same static S axis with the same
        masks as ``_decode_attend``: paged token streams are then
        bit-identical to ``generate_fast`` (the tests' contract). The
        KERNEL path (a TPU, float32 pool) walks
        only the row's live pages in the pool, 128 positions at a time
        under a running maximum: the same bf16-rounded products and every
        live position attended, but another order of the float32 sums, so
        there the contract is a tolerance — logits within
        ``chip_smoke.py:PAGED_LOGIT_TOL`` (0.15; measured on a TPU v5e at
        GPT-2 base width: 0.049 at most where the logits' standard
        deviation is 0.55) of the gather path's, and
        ``perfbench/limits/gpt2-base.serve-closed.json`` judges the
        served cell against the float32 reference as before."""
        cfg = self.config
        H, page, P = cfg.n_head, cfg.page_size, cfg.kv_pages
        S, C = cfg.block_size, cfg.n_head * hd
        if S % page != 0:
            raise ValueError(
                f"block_size {S} not divisible by page_size {page}")
        if block_table is None or cache_pos is None:
            raise ValueError(
                "paged decode (page_size > 0) needs block_table and "
                "cache_pos passed to __call__")
        mb = S // page
        quant = cfg.kv_dtype == "int8"
        kv_dt = jnp.int8 if quant else q.dtype
        # ONE pool layout for every paged program: [pages, page, C], a
        # position's heads packed on the last axis as the projection
        # leaves them (768 lanes at GPT-2 base: whole lane tiles, so a
        # written position is one row and the kernel copies whole pages)
        ck = self.variable("cache", "k",
                           lambda: jnp.zeros((P, page, C), kv_dt))
        cv = self.variable("cache", "v",
                           lambda: jnp.zeros((P, page, C), kv_dt))
        i = cache_pos                                   # [b] per-row cursor
        wpos = i[:, None] + jnp.arange(t)[None, :]      # [b, t] write pos
        lblk = jnp.clip(wpos // page, 0, mb - 1)
        phys = jnp.take_along_axis(block_table, lblk, axis=1)  # [b, t]
        # out-of-window writes land on the null page (never read) so they
        # cannot corrupt a live page; the positions are poisoned below
        phys = jnp.where(wpos < S, phys, 0)
        off = wpos % page

        def window(pool, *tail):
            # a row's pages back in its logical [S] window
            return pool[block_table].reshape(b, S, *tail)

        from ..ops.paged_attention import (KERNEL, paged_attend_path,
                                           paged_attention, report_path)
        path = paged_attend_path(C, page, q.dtype, kv_dt)
        report_path(path, q.shape, str(q.dtype))
        if quant:
            # int8 page pool: quantize on scatter with one f32 scale per
            # (page slot, head) — write-once per position, so shared
            # prompt pages are bit-stable across readers, CoW copies the
            # (int8, scale) pair verbatim, and spec-decode rollback stays
            # a cursor rewind. The gather dequantizes into the SAME
            # static [S] reduction window as f32, which keeps quantized
            # paged streams bit-identical to the quantized unpaged
            # engine/generate_fast. (Always the gather path, by its
            # dtype.)
            from ..ops.fused_attention import kv_dequantize, kv_quantize
            cks = self.variable("cache", "k_scale",
                                lambda: jnp.zeros((P, page, H),
                                                  jnp.float32))
            cvs = self.variable("cache", "v_scale",
                                lambda: jnp.zeros((P, page, H),
                                                  jnp.float32))
            kq, ks = kv_quantize(k.reshape(b, t, H, hd))
            vq, vs = kv_quantize(v.reshape(b, t, H, hd))
            k_pool = ck.value.at[phys, off].set(kq.reshape(b, t, C))
            v_pool = cv.value.at[phys, off].set(vq.reshape(b, t, C))
            ks_pool = cks.value.at[phys, off].set(ks)
            vs_pool = cvs.value.at[phys, off].set(vs)
            ck.value, cv.value = k_pool, v_pool
            cks.value, cvs.value = ks_pool, vs_pool
        else:
            # in place: the pool is donated, a written position is a row
            k_pool = ck.value.at[phys, off].set(k)
            v_pool = cv.value.at[phys, off].set(v)
            ck.value, cv.value = k_pool, v_pool
        if path == KERNEL:
            y = paged_attention(q, k_pool, v_pool, block_table, i, H)
        else:
            k_all, v_all = window(k_pool, H, hd), window(v_pool, H, hd)
            if quant:
                k_all = kv_dequantize(k_all, window(ks_pool, H), q.dtype)
                v_all = kv_dequantize(v_all, window(vs_pool, H), q.dtype)
            # attend exactly like the unpaged path: the reductions run
            # over the same static S axis with the same masks, which is
            # what keeps paged token streams bit-identical to
            # generate_fast
            att = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(b, t, H, hd),
                             k_all) / math.sqrt(hd)
            col_pos = jnp.arange(S)                         # [S]
            mask = col_pos[None, None, :] <= wpos[:, :, None]   # [b, t, S]
            att = jnp.where(mask[:, None], att.astype(jnp.float32),
                            -jnp.inf)
            att = jax.nn.softmax(att, axis=-1).astype(q.dtype)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v_all).reshape(b, t, C)
        # per-POSITION poison (vs the unpaged path's per-row check): a
        # speculative verify may legally write drafts past the window —
        # those drafts are rejected before emission, so only the
        # out-of-window positions go NaN and the row's in-window tokens
        # stay clean
        return jnp.where((wpos < S)[:, :, None], y, jnp.nan)


class MLP(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        x = _proj(cfg, 4 * cfg.n_embd, 0.02, "c_fc")(x)
        x = nn.gelu(x)
        x = _proj(cfg, cfg.n_embd, 0.02 / math.sqrt(2 * cfg.n_layer),
                  "c_proj")(x)
        return nn.Dropout(cfg.dropout, deterministic=not train)(x)


class Block(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, train: bool, block_table=None, cache_pos=None):
        cfg = self.config
        x = x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=1e-5, use_bias=cfg.bias, name="ln_1")(x),
            train, block_table=block_table, cache_pos=cache_pos
        )
        x = x + MLP(cfg, name="mlp")(
            nn.LayerNorm(epsilon=1e-5, use_bias=cfg.bias, name="ln_2")(x), train
        )
        return x


class MoEBlock(nn.Module):
    """Pre-norm residual block with a routed MoE MLP: returns ``(x, aux)``
    where ``aux`` is the layer's weighted auxiliary router loss."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, train: bool, block_table=None, cache_pos=None):
        cfg = self.config
        from .moe import MoEMLP

        x = x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=1e-5, use_bias=cfg.bias, name="ln_1")(x),
            train, block_table=block_table, cache_pos=cache_pos
        )
        y, aux = MoEMLP(
            n_embd=cfg.n_embd, n_layer=cfg.n_layer, n_experts=cfg.n_experts,
            topk=cfg.expert_topk, capacity_factor=cfg.capacity_factor,
            dropout=cfg.dropout, bias=cfg.bias,
            aux_weight=cfg.moe_aux_weight, z_weight=cfg.moe_z_weight,
            expert_axis=cfg.expert_axis, moe_impl=cfg.moe_impl,
            chunk_rows=cfg.moe_chunk_rows, name="moe",
        )(nn.LayerNorm(epsilon=1e-5, use_bias=cfg.bias, name="ln_2")(x), train)
        return x + y, aux


class GPT(nn.Module):
    """``__call__(batch, train)``: a ``(idx, targets)`` tuple → scalar loss
    (targets == -1 are ignored); a bare ``idx`` array → logits [B, T, V].

    When ``config.seq_axis`` is set the model is context-parallel: it must
    run under ``shard_map`` with that mesh axis, each device receives the
    FULL batch, slices its own sequence chunk, attends via ring attention,
    and the returned loss is the global mean (psum over the seq axis) —
    replicated across the group. Bare-``idx`` calls return the local chunk's
    logits.
    """

    config: GPTConfig

    @nn.compact
    def __call__(self, batch, train: bool = True, block_table=None,
                 cache_pos=None, last_pos=None):
        cfg = self.config
        if cfg.weights_dtype not in ("f32", "int8", "int4"):
            raise ValueError(
                f"weights_dtype must be 'f32', 'int8' or 'int4', got "
                f"{cfg.weights_dtype!r}")
        if cfg.kv_dtype not in ("f32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'f32' or 'int8', got {cfg.kv_dtype!r}")
        if cfg.weights_dtype != "f32":
            if train:
                raise ValueError(
                    "quantized weights are inference-only — int8/int4 "
                    "params carry no gradient; train with f32 and "
                    "quantize at serving load (serve/load.py)")
            if cfg.n_experts > 0:
                raise ValueError(
                    "quantized serving does not support MoE configs yet "
                    "— serve MoE checkpoints with weights_dtype='f32'")
        if isinstance(batch, (tuple, list)):
            idx, targets = batch
        else:
            idx, targets = batch, None
        b, t = idx.shape
        if t > cfg.block_size:
            raise ValueError(
                f"sequence length {t} > block_size {cfg.block_size}")
        if cfg.decode:
            if not (cfg.seq_axis is None and targets is None):
                raise ValueError("decode mode is single-device, logits-only")
            if cfg.page_size > 0:
                # paged decode: the cursor is an ARGUMENT, not cache
                # state — the engine owns allocation and cursor advance
                # (speculative rollback is a host-side cursor rewind)
                if cache_pos is None:
                    raise ValueError(
                        "paged decode (page_size > 0) needs cache_pos")
                # clamp for the wpe gather: out-of-window speculative
                # positions are NaN-poisoned in the attend, never emitted
                pos = jnp.minimum(
                    cache_pos[:, None] + jnp.arange(t)[None, :],
                    cfg.block_size - 1)
            else:
                # per-row position cursor, mirroring the per-row cache
                # cursor in _decode_attend (rows are independent request
                # slots)
                pcache = self.variable("cache", "pos",
                                       lambda: jnp.zeros((b,), jnp.int32))
                pos = pcache.value[:, None] + jnp.arange(t)[None, :]
                pcache.value = pcache.value + t
        elif cfg.seq_axis is not None:
            # chunked sequences only see their own K/V under dense/flash —
            # block-diagonal attention that would train silently wrong
            if cfg.attn_impl != "ring":
                raise ValueError(
                    f"seq_axis requires attn_impl='ring', got "
                    f"{cfg.attn_impl!r}")
            idx, targets, pos_vec = slice_seq_chunk(
                idx, targets, cfg.seq_axis, layout=cfg.seq_layout)
            pos = pos_vec[None, :]
        else:
            pos = jnp.arange(t)[None, :]
        if cfg.weights_dtype != "f32" and cfg.quant_embed:
            # the tied embedding/lm_head quantizes SEPARATELY from the
            # block kernels (it dominates quality — default stays f32)
            wte = QuantEmbed(cfg.vocab_size, cfg.n_embd,
                             tile=cfg.quant_tile, name="wte")
        else:
            wte = nn.Embed(cfg.vocab_size, cfg.n_embd,
                           embedding_init=_init_normal(0.02), name="wte")
        wpe = nn.Embed(cfg.block_size, cfg.n_embd,
                       embedding_init=_init_normal(0.02), name="wpe")
        x = wte(idx) + wpe(pos)
        x = nn.Dropout(cfg.dropout, deterministic=not train)(x)
        block_cls = (nn.remat(Block, static_argnums=(2,)) if cfg.remat
                     else Block)
        moe_cls = (nn.remat(MoEBlock, static_argnums=(2,)) if cfg.remat
                   else MoEBlock)
        aux_total = jnp.zeros((), jnp.float32)
        # paged-decode addressing rides down to every attention layer;
        # passed only when active so the training/unpaged traces (incl.
        # the remat-wrapped positional signature) are untouched
        kw = ({"block_table": block_table, "cache_pos": cache_pos}
              if cfg.decode and cfg.page_size > 0 else {})
        for i in range(cfg.n_layer):
            if cfg.is_moe_layer(i):
                x, aux = moe_cls(cfg, name=f"h_{i}")(x, train, **kw)
                aux_total = aux_total + aux
            else:
                x = block_cls(cfg, name=f"h_{i}")(x, train, **kw)
        x = nn.LayerNorm(epsilon=1e-5, use_bias=cfg.bias, name="ln_f")(x)
        if targets is None:
            # weight tying: lm_head = wteᵀ (reference :206-208); the
            # quantized table's attend fuses its own dequant
            logits = (wte.attend(x) if isinstance(wte, QuantEmbed)
                      else wte.attend(x.astype(wte.embedding.dtype)))
            if last_pos is None:
                return logits
            # one position's logits of every row (a prefill's last)
            return jax.lax.dynamic_index_in_dim(logits, last_pos, axis=1,
                                                keepdims=False)
        emb = (wte.materialize() if isinstance(wte, QuantEmbed)
               else wte.embedding)
        loss_sum, count = ce_sum_count(x, targets, emb, cfg.loss_chunk)
        if cfg.seq_axis is not None:
            loss_sum = jax.lax.psum(loss_sum, cfg.seq_axis)
            count = jax.lax.psum(count, cfg.seq_axis)
        loss = loss_sum / jnp.maximum(count, 1.0)
        if cfg.n_experts > 0 and train:
            # router auxiliary losses (already weighted per-layer); train
            # only, so eval loss stays the pure-CE observable the reference
            # logs (`train_node.py:204-221`). Under context parallelism each
            # seq shard routes its own token chunk — average the per-shard
            # aux so the returned scalar stays replicated over `seq_axis`
            # (the invariant the cp path maintains for the CE terms above).
            if cfg.seq_axis is not None:
                aux_total = jax.lax.pmean(aux_total, cfg.seq_axis)
            loss = loss + aux_total
        return loss


# -- model utilities (reference parity helpers) ----------------------------


def slice_seq_chunk(idx, targets, seq_axis: str, axis: int = 1,
                    layout: str = "contiguous"):
    """THE context-parallel slicing contract, shared by ``GPT.__call__``
    and the pipelined loss (``parallel/pipeline_model.py``): every device
    sees the full batch and slices its own token chunk of the ``seq_axis``
    group. Returns ``(idx_chunk, targets_chunk, positions)`` where
    ``positions`` is the [Tl] vector of global token positions the local
    rows hold.

    ``layout='contiguous'``: chunk ``[i·Tl, (i+1)·Tl)``.
    ``layout='zigzag'``: half-chunks ``i`` and ``2·sp−1−i`` concatenated —
    the assignment ``ring_causal_attention(layout='zigzag')`` requires;
    loss/targets slice identically (CE is permutation-invariant under the
    psum'd sum/count reduction). Falls back to contiguous when ``Tl`` is
    odd — the same static condition the attention dispatch tests, so the
    two sides can never disagree."""
    sp = jax.lax.axis_size(seq_axis)
    t = idx.shape[axis]
    if t % sp != 0:
        raise ValueError(f"seq len {t} not divisible by cp={sp}")
    tl = t // sp
    chunk = jax.lax.axis_index(seq_axis)
    if layout == "zigzag" and tl % 2 == 0 and sp > 1:
        h = tl // 2
        lo, hi = chunk * h, (2 * sp - 1 - chunk) * h

        def take(z):
            return jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(z, lo, h, axis=axis),
                 jax.lax.dynamic_slice_in_dim(z, hi, h, axis=axis)],
                axis=axis)

        pos = jnp.concatenate([lo + jnp.arange(h), hi + jnp.arange(h)])
        return take(idx), (None if targets is None else take(targets)), pos
    idx = jax.lax.dynamic_slice_in_dim(idx, chunk * tl, tl, axis=axis)
    if targets is not None:
        targets = jax.lax.dynamic_slice_in_dim(targets, chunk * tl, tl,
                                               axis=axis)
    return idx, targets, chunk * tl + jnp.arange(tl)


def ce_sum_count(x, targets, embedding, loss_chunk: int):
    """(Σ masked CE, Σ valid) through the tied lm head — the single source
    of the loss convention (head matmul in the embedding's dtype, f32 CE,
    ``targets == -1`` masked) for both the dense ``GPT.__call__`` and the
    pipelined head (``parallel/pipeline_model.py``)."""
    if loss_chunk > 0:
        return _chunked_ce(x, targets, embedding, loss_chunk)
    v = embedding.shape[0]
    logits = (x.astype(embedding.dtype) @ embedding.T).astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.reshape(-1, v), jnp.maximum(targets.reshape(-1), 0))
    valid = (targets.reshape(-1) >= 0).astype(jnp.float32)
    return jnp.sum(losses * valid), jnp.sum(valid)


def _chunked_ce(x, targets, embedding, chunk: int):
    """(Σ masked CE, Σ valid) over `chunk`-token row blocks, never holding
    more than [chunk, vocab] logits: each block runs head-matmul → f32 CE
    under `jax.checkpoint` inside a `lax.scan`, so the backward recomputes
    a block's logits instead of storing all of them. Same math as the
    one-shot path (per-row logsumexp is independent of blocking; the sum
    accumulates in f32)."""
    V, C = embedding.shape[0], embedding.shape[1]
    # same dtype rule as the one-shot wte.attend path: the head matmul
    # runs in the embedding's dtype, CE in f32
    xf = x.reshape(-1, C).astype(embedding.dtype)
    tf = targets.reshape(-1)
    s = xf.shape[0]
    n_blocks = -(-s // chunk)
    pad = n_blocks * chunk - s
    # padded rows carry target −1 → masked out like the ignore_index rows
    xf = jnp.pad(xf, ((0, pad), (0, 0)))
    tf = jnp.pad(tf, (0, pad), constant_values=-1)
    xb = xf.reshape(n_blocks, chunk, C)
    tb = tf.reshape(n_blocks, chunk)

    @jax.checkpoint
    def block(carry, inp):
        xs, ts = inp
        logits = (xs @ embedding.T).astype(jnp.float32)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.maximum(ts, 0))
        valid = (ts >= 0).astype(jnp.float32)
        return (carry[0] + jnp.sum(losses * valid),
                carry[1] + jnp.sum(valid)), None

    (loss_sum, count), _ = jax.lax.scan(
        block, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xb, tb))
    return loss_sum, count


def num_params(params: Any, non_embedding: bool = True) -> int:
    """Parameter count; positional embeddings subtracted by default
    (token embeddings stay — they serve as lm_head via tying;
    reference ``:223-231``)."""
    total = sum(int(x.size) for x in jax.tree.leaves(params))
    if non_embedding:
        total -= int(params["wpe"]["embedding"].size)
    return total


def crop_block_size(params: Any, config: GPTConfig,
                    block_size: int) -> Tuple[Any, GPTConfig]:
    """Shrink the context window by slicing wpe (reference ``:278-289``)."""
    if block_size > config.block_size:
        raise ValueError(
            f"cannot crop block_size {config.block_size} UP to "
            f"{block_size}")
    new = jax.tree.map(lambda x: x, params)  # shallow copy
    new["wpe"] = {"embedding": params["wpe"]["embedding"][:block_size]}
    return new, dataclasses.replace(config, block_size=block_size)


def decay_mask(params: Any) -> Any:
    """optax weight-decay mask: decay only ≥2-D kernels/embeddings, never
    biases or LayerNorm scales — the reference's decay/no-decay param
    grouping (``:362-392``) expressed as a mask."""
    return jax.tree.map(lambda x: x.ndim >= 2, params)


def make_adamw(lr, betas=(0.9, 0.95), weight_decay=0.1, params=None):
    """AdamW with nanoGPT-style decay grouping (reference ``:381-390``)."""
    return optax.adamw(lr, b1=betas[0], b2=betas[1],
                       weight_decay=weight_decay,
                       mask=decay_mask(params) if params is not None else None)


#: Peak dense bf16 FLOP/s of ONE chip, keyed by the ``device_kind`` JAX
#: reports. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: bf16 per chip). A device that is not listed has no peak: ``fit``
#: reports ``mfu=None`` there and measuring scripts fail
#: (``device_peak_flops``) — the reference's A100 constant (``:394-408``)
#: and a v5e default for whatever is attached were both wrong answers.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def device_peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of ``device`` (default: the first attached one);
    ``KeyError`` naming the device when it is not in the table — for
    scripts that were asked to measure utilization."""
    kind = (device or jax.devices()[0]).device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s known for device_kind {kind!r} (table: "
            f"{sorted(PEAK_BF16_FLOPS)}) — utilization cannot be "
            f"measured on this device")
    return PEAK_BF16_FLOPS[kind]


def estimate_mfu(config: GPTConfig, params: Any, fwdbwd_per_iter: float,
                 dt: float, peak_flops: float,
                 n_params: Optional[int] = None) -> float:
    """Model FLOPs utilization against ``peak_flops`` (chips × the
    per-chip entry of ``PEAK_BF16_FLOPS``).
    ``n_params`` overrides the parameter count — used for MoE, where only
    the routed top-k fraction of expert params does FLOPs per token
    (``models.moe.moe_active_params``)."""
    n = n_params if n_params is not None else num_params(params)
    cfg = config
    l, h, q, t = cfg.n_layer, cfg.n_head, cfg.n_embd // cfg.n_head, \
        cfg.block_size
    flops_per_token = 6 * n + 12 * l * h * q * t
    flops_per_iter = flops_per_token * t * fwdbwd_per_iter
    return (flops_per_iter / dt) / peak_flops


def node_mfu(config: GPTConfig, node_params: Any, seqs_per_iter: float,
             dt: float, peak_flops: float) -> float:
    """MFU from a *node-stacked* param tree (leading [K] axis, as held by
    the runtime/bench/trainer): strips the axis to shapes and delegates to
    ``estimate_mfu``. Single place for the MFU convention. MoE configs
    count expert params at their routed ``topk/n_experts`` fraction."""
    p0 = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), node_params
    )
    n_active = None
    if config.n_experts > 0:
        from .moe import moe_active_params
        n_active = (moe_active_params(p0, config.expert_topk,
                                      config.n_experts)
                    - int(p0["wpe"]["embedding"].size))
    return estimate_mfu(config, p0, seqs_per_iter, dt,
                        peak_flops=peak_flops, n_params=n_active)


def decode_config(config: GPTConfig) -> GPTConfig:
    """Sanitize a TRAINING config for single-device KV-cache decode — THE
    shared rule for ``generate_fast`` and the serving engine
    (``gym_tpu/serve/engine.py``), so a config captured from any ``fit``
    run decodes correctly: dropout off, dense attention (no ring/flash —
    decode queries one token), no sequence sharding, no remat, and
    ``moe_impl`` reset to 'auto' alongside ``expert_axis=None`` — a
    training config pinned to the capacity-limited 'einsum' dispatch must
    not drop tokens at decode (capacity is tiny at T=1), and with
    ``expert_axis`` cleared the drop-free ragged/dense paths are always
    legal."""
    return dataclasses.replace(config, decode=True, dropout=0.0,
                               attn_impl="dense", seq_axis=None,
                               remat=False, expert_axis=None,
                               moe_impl="auto")


def sample_logits(logits, key, temperature=1.0, top_k=None, top_p=None):
    """Temperature → top-k → top-p (nucleus) → categorical, in f32: THE
    sampling kernel shared by ``generate_fast`` and the serving engine
    (``gym_tpu/serve/engine.py``).

    ``logits`` is [..., V]; one ``key`` covers the whole call (batch rows
    share its random bits — ``sample_rows`` vmaps this function to give
    each request slot its own key). ``temperature``/``top_k``/``top_p``
    may be static python scalars (``None`` disables a filter, and its
    full-vocabulary sort is not traced at all) or traced arrays
    broadcastable against ``logits[..., :1]``; the array encodings for
    "disabled" are ``top_k >= V`` and ``top_p >= 1``, which reduce to
    no-op ``where``s and reproduce the static-``None`` paths bit-exactly
    — the single-request engine-vs-``generate_fast`` oracle depends on
    this."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]    # descending
        kidx = jnp.broadcast_to(
            jnp.asarray(jnp.clip(top_k, 1, v) - 1, jnp.int32),
            (*logits.shape[:-1], 1))
        kth = jnp.take_along_axis(srt, kidx, axis=-1)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # nucleus over the (already top-k-filtered) distribution: keep the
        # smallest prefix of descending-prob tokens whose EXCLUSIVE
        # cumulative mass stays under top_p (the top token is always kept)
        srt = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)          # -inf rows → 0
        cum = jnp.cumsum(probs, axis=-1) - probs      # exclusive prefix
        p_eff = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32),
                                 (*logits.shape[:-1], 1))
        # p >= 1 means disabled and must keep EVERY token (f32 cumsum can
        # round to exactly 1.0 mid-tail, which `< 1.0` would truncate)
        keep = cum < jnp.where(p_eff >= 1.0, jnp.inf, p_eff)
        n_keep = jnp.maximum(jnp.sum(keep, axis=-1, keepdims=True), 1)
        thr = jnp.take_along_axis(srt, n_keep - 1, axis=-1)
        logits = jnp.where(logits < thr, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def sample_rows(logits, keys, temperature, top_k, top_p, live,
                sample=sample_logits):
    """``sample_logits`` for a batch of request slots, each with its own
    key and parameters: the serving programs' sampler. ``temperature``,
    ``top_k``, ``top_p`` and ``live`` are [S] in the array encodings
    above; ``logits`` is [S, V] with ``keys`` [S, 2], or [S, G, V] with
    ``keys`` [S, G, 2] (a slot's G positions share its parameters).
    Returns ``(tokens, sorted)``.

    The full-vocabulary sorts are taken ONCE A CALL FOR THE WHOLE BATCH,
    and only when a live row asks for a filter that needs an order
    (``1 < top_k < V`` or ``top_p < 1``): ``sorted`` says so. The
    predicate is a scalar outside every ``vmap``, so ``lax.cond`` stays a
    conditional on the device and the branch not taken costs nothing
    (under a ``vmap`` it would turn into a ``select`` and both would
    run). A row that is not ``live`` never switches the sorts on: what it
    samples is discarded. The other branch sorts nothing: a greedy row
    (``top_k <= 1``) keeps what equals its maximum, which is what the
    k-th value of a sort is for ``k = 1``; every other row keeps
    everything, as the sorted filters do for ``top_k >= V`` and
    ``top_p >= 1``. Both branches hand ``sample`` bit-equal filtered
    logits on the same keys, so the tokens are those of
    ``vmap(sample_logits)`` whichever branch ran (ties at a greedy row's
    maximum break as ``categorical`` breaks them).

    ``sample`` is the row sampler both branches end in; the programs hand
    in their own module's name for it (``programs/serve_defs.py``)."""
    v = logits.shape[-1]
    # ``~(top_p >= 1)`` and not ``top_p < 1``: a NaN takes today's path
    filters = ((top_k > 1) & (top_k < v)) | ~(top_p >= 1.0)
    need = jnp.any(live & filters)

    def plain_row(lg, key, temp, k, _p):
        lg = lg.astype(jnp.float32) / temp
        lg = jnp.where((k <= 1) & (lg < lg.max()), -jnp.inf, lg)
        return sample(lg, key)

    def over_rows(row):
        for _ in range(logits.ndim - 2):
            row = jax.vmap(row, in_axes=(0, 0, None, None, None))
        return lambda: jax.vmap(row)(logits, keys, temperature, top_k,
                                     top_p)

    tokens = jax.lax.cond(need, over_rows(sample), over_rows(plain_row))
    return tokens, need


def generate(params: Any, config: GPTConfig, idx: np.ndarray,
             max_new_tokens: int, temperature: float = 1.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             seed: int = 0) -> np.ndarray:
    """Autoregressive sampling (reference ``:410-439``): crop context to
    block_size, temperature-scale, optional top-k / top-p (nucleus)
    filters, categorical sample.

    Context handling is the reference's: the context is CROPPED to the
    last ``block_size`` tokens each step, so generation continues past
    the window (with a sliding context). This is the documented fallback
    when ``prompt + max_new_tokens`` exceeds ``block_size`` —
    ``generate_fast``'s KV cache cannot slide and raises ``ValueError``
    for that regime."""
    model = GPT(config)

    @jax.jit
    def logits_fn(p, tokens):
        return model.apply({"params": p}, tokens, train=False)

    key = jax.random.PRNGKey(seed)
    idx = np.asarray(idx)
    for _ in range(max_new_tokens):
        ctx = idx[:, -config.block_size:]
        logits = np.asarray(logits_fn(params, jnp.asarray(ctx)))[:, -1, :]
        logits = logits / temperature
        if top_k is not None:
            kth = np.sort(logits, axis=-1)[:, -min(top_k, logits.shape[-1])]
            logits = np.where(logits < kth[:, None], -np.inf, logits)
        if top_p is not None and top_p < 1.0:
            # same convention as sample_logits: exclusive cumulative mass
            # under top_p, top token always kept, ties at the threshold in
            srt = np.sort(logits, axis=-1)[:, ::-1]
            e = np.exp(srt - srt[:, :1])
            probs = e / e.sum(axis=-1, keepdims=True)
            cum = np.cumsum(probs, axis=-1) - probs
            n_keep = np.maximum((cum < top_p).sum(axis=-1), 1)
            thr = np.take_along_axis(srt, (n_keep - 1)[:, None], axis=-1)
            logits = np.where(logits < thr, -np.inf, logits)
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, jnp.asarray(logits), axis=-1)
        idx = np.concatenate([idx, np.asarray(nxt)[:, None]], axis=1)
    return idx


def generate_fast(params: Any, config: GPTConfig, idx: np.ndarray,
                  max_new_tokens: int, temperature: float = 1.0,
                  top_k: Optional[int] = None, top_p: Optional[float] = None,
                  seed: int = 0) -> np.ndarray:
    """KV-cache autoregressive sampling (beyond-reference perf: the
    reference's ``generate`` — and our parity ``generate`` above — re-runs
    the full context per token, ``nanogpt.py:410-439``).

    One jitted program: prefill fills the per-layer K/V caches from the
    prompt, then a ``lax.scan`` samples token-by-token with O(T) attention
    per step. Same sampling semantics as ``generate`` (temperature,
    optional top-k / top-p, categorical); the per-token key schedule is
    ``fold_in(PRNGKey(seed), j)`` so the j-th token's key does not depend
    on ``max_new_tokens`` — the serving engine reproduces it token by
    token for the single-request parity oracle."""
    idx = np.asarray(idx)
    b, t0 = idx.shape
    if t0 + max_new_tokens > config.block_size:
        raise ValueError(
            f"prompt {t0} + {max_new_tokens} new tokens exceeds the KV "
            f"cache (block_size {config.block_size}); crop the prompt to "
            f"block_size - max_new_tokens, or use `generate`, whose "
            f"full-context resampling slides the context window past "
            f"block_size (the reference's crop semantics)"
        )
    cfg = decode_config(config)
    decode_all = _cached_decode_program(
        dataclasses.astuple(cfg), b, t0, max_new_tokens, temperature,
        top_k, top_p,
    )
    new = np.asarray(decode_all(params, jnp.asarray(idx),
                                jax.random.PRNGKey(seed)))
    return np.concatenate([idx, new], axis=1)


@functools.lru_cache(maxsize=32)
def _cached_decode_program(cfg_tuple, b, t0, max_new_tokens, temperature,
                           top_k, top_p):
    """Compile the prefill+scan decode program once per (config, shape,
    sampling) signature — a fresh ``jax.jit`` per ``generate_fast`` call
    would recompile every time (~seconds of fixed overhead per call).

    Cross-config collision audit (ISSUE 9): the key leads with the FULL
    ``decode_config`` astuple, so two different model configs can never
    share an entry — every jit-static the closure bakes in (model
    architecture, prompt shape, scan length, sampling params) is in the
    key; only runtime values (params, prompt tokens, PRNG key) are not.
    Pinned by ``tests/test_programs.py::test_generate_fast_cache_
    distinguishes_configs``.  maxsize=32 bounds the distinct
    (config × shape × sampling) signatures one process holds; eviction
    costs a recompile, never wrong tokens."""
    cfg = GPTConfig(*cfg_tuple)
    model = GPT(cfg)

    @jax.jit
    def decode_all(params, prompt, key):
        logits, varsc = model.apply({"params": params}, prompt,
                                    train=False, mutable=["cache"])
        tok = sample_logits(logits[:, -1], jax.random.fold_in(key, 0),
                            temperature, top_k, top_p)

        def body(carry, j):
            cache, tok = carry
            lg, vc = model.apply({"params": params, "cache": cache},
                                 tok[:, None], train=False,
                                 mutable=["cache"])
            nxt = sample_logits(lg[:, -1], jax.random.fold_in(key, j),
                                temperature, top_k, top_p)
            return (vc["cache"], nxt), tok

        (_, last), toks = jax.lax.scan(
            body, (varsc["cache"], tok), jnp.arange(1, max_new_tokens)
        )
        toks = jnp.concatenate([toks.T, last[:, None]], axis=1)
        return toks

    return decode_all


def from_pretrained(model_type: str, override_args: Optional[dict] = None):
    """Port HF GPT-2 weights into our param tree (reference ``:291-360``).

    Requires the ``transformers`` GPT-2 checkpoint to be available locally
    (this environment has no network egress; pass a cached path via
    ``override_args={'model_path': ...}``).
    """
    config_args = {
        "gpt2": dict(n_layer=12, n_head=12, n_embd=768),
        "gpt2-medium": dict(n_layer=24, n_head=16, n_embd=1024),
        "gpt2-large": dict(n_layer=36, n_head=20, n_embd=1280),
        "gpt2-xl": dict(n_layer=48, n_head=25, n_embd=1600),
    }[model_type]
    override_args = dict(override_args or {})
    model_path = override_args.pop("model_path", model_type)
    if "dropout" in override_args:
        config_args["dropout"] = override_args.pop("dropout")
    config = GPTConfig(vocab_size=50257, block_size=1024, bias=True,
                       **config_args)

    from transformers import GPT2LMHeadModel  # lazy: optional dep
    hf = GPT2LMHeadModel.from_pretrained(model_path)
    sd = {k: v.detach().cpu().numpy() for k, v in hf.state_dict().items()}

    def dense(prefix, has_bias=True):
        # HF GPT-2 uses Conv1D ([in, out]) — same layout as flax Dense
        out = {"kernel": sd[f"{prefix}.weight"]}
        if has_bias:
            out["bias"] = sd[f"{prefix}.bias"]
        return out

    def ln(prefix):
        return {"scale": sd[f"{prefix}.weight"], "bias": sd[f"{prefix}.bias"]}

    params = {
        "wte": {"embedding": sd["transformer.wte.weight"]},
        "wpe": {"embedding": sd["transformer.wpe.weight"]},
        "ln_f": ln("transformer.ln_f"),
    }
    for i in range(config.n_layer):
        p = f"transformer.h.{i}"
        params[f"h_{i}"] = {
            "ln_1": ln(f"{p}.ln_1"),
            "ln_2": ln(f"{p}.ln_2"),
            "attn": {
                "c_attn": dense(f"{p}.attn.c_attn"),
                "c_proj": dense(f"{p}.attn.c_proj"),
            },
            "mlp": {
                "c_fc": dense(f"{p}.mlp.c_fc"),
                "c_proj": dense(f"{p}.mlp.c_proj"),
            },
        }
    params = jax.tree.map(jnp.asarray, params)
    return GPT(config), params, config
