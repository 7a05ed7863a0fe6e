"""Brumby-14B-Base (``model_type: brumby``), built from its ``config.json``
keys, as the serving engine runs it: the Qwen3 dense block with power
retention (``ops/power_retention.py``) in the place of attention.

Per layer, input ``x`` [T, hidden] (float32 residual stream)::

    a  = rms(x)                                     weight, no bias
    q, k, v = a Wq, a Wk, a Wv                      grouped heads, no bias
    q, k = rms over each head (its own weight), then rotary: rotate-half
           over the whole head
    gam = log sigmoid(a Wg + bg)                    one gate a key-value
                                                    head, float32
    y[h] = sum_s w[t, s] v_s / (sum_s w[t, s] + eps_n),   s <= t,
           w[t, s] = exp(Gam_t - Gam_s) (q_t[h] . k_s / sqrt(d))^2,
           Gam the running sum of gam
    x  = x + y Wo
    x  = x + Wdown(silu(Wgate b) * (Wup b)),        b = rms(x)

After the last layer ``rms``, then ``logits = y W_head`` (untied).

Served only. **The cache is not pages of keys and values but one block of
state a row**, of a fixed size whatever the row's length
(``fixed_row_cache``: ``models/serving.py`` says what the engine makes of
that): a layer keeps ``S`` [kv_pages, KV, head_dim, D] and ``z``
[kv_pages, KV, D] in ``kv_dtype`` (float32 as served), ``D =
power_retention.feature_dim(head_dim)``, and a row's one block-table
entry names its block. A row at cursor 0 has no past: its block reads as
zeros whatever the last row left in it. A call of one token a row is a
decode step (every live row's block decayed, updated and read in one
pass, in place); a call of more tokens with ``last_pos`` is a prefill
(``last_pos + 1`` of them are the prompt: the padding leaves the state
as it is), run ``prefill_rows`` positions at a time through all layers
with the rows' states carried from pass to pass. More tokens without
``last_pos`` would be a speculative verify, which is refused: rejected
drafts could not be taken out of the state again without a copy of it.

The norm, the projections with their q/k norms and rotation, the
embedding and the head are ``decoder_parts.py``'s, shared with
``keye_vl2.py``. Weights in ``weights_dtype``; the residual stream, the
norms, the gates and the state's arithmetic in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .cohere2_moe import _DTYPES
from .decoder_parts import (RMSNorm, embed_tokens, key_heads, qkvo_params,
                            query_heads, untied_head, value_heads)

FAMILY = "brumby"


@dataclasses.dataclass
class BrumbyConfig:
    """``config.json``'s keys under their own names, then what the
    description has no key for, then how it is served."""

    model_type: str = FAMILY            # first: a program key's family
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    retention_eps: float = 1e-6         # eps_n, added to the normaliser
    # positions a row may reach (nothing grows with it but the cursor)
    block_size: int = 20480
    # positions a step of the state: phi(Q) of a chunk is written out and
    # read back, and 128 measured fastest on the chip (17.4 ms a layer and
    # 2,048 positions against 19.5 at 256 and 21.0 at 512: PERF.md)
    retention_chunk: int = 128
    # positions a pass of a prefill through all layers: what a long
    # bucket holds at once does not grow with the bucket
    prefill_rows: int = 2048
    decode: bool = False
    page_size: int = 0                  # the engine sets it to block_size
    kv_pages: int = 0                   # blocks of state, the null one too
    weights_dtype: str = "bf16"
    kv_dtype: str = "f32"               # the state's

    # the cache is one block a row, not a run of pages: the engine makes
    # a page a whole row and serves no prefix from it (models/serving.py)
    fixed_row_cache = True

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be whole groups of "
                             "key-value heads")
        if self.head_dim % 2:
            raise ValueError("rotary turns halves and the feature map "
                             "pairs lanes: head_dim must be even")

    # -- what the serving engine asks a model's config --------------------

    def build(self) -> nn.Module:
        return Brumby(self)

    def program_key(self) -> tuple:
        return dataclasses.astuple(self)

    def decode_config(self) -> "BrumbyConfig":
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
            retention=True),) * self.num_hidden_layers

    def prepare_params(self, params):
        """Weights as served: every leaf in ``weights_dtype``."""
        dt = _DTYPES[self.weights_dtype]
        return jax.tree.map(lambda x: jnp.asarray(x, dt), params)

    def state_bytes_per_row(self) -> int:
        """Bytes of state one row holds over all layers."""
        from ..ops.power_retention import feature_dim
        return (self.num_hidden_layers * self.num_key_value_heads
                * feature_dim(self.head_dim) * (self.head_dim + 1)
                * jnp.dtype(_DTYPES[self.kv_dtype]).itemsize)


class PowerRetention(nn.Module):
    """One layer's retention as a function of arrays: the state comes in
    and goes out beside the output, and ``Brumby`` keeps it."""

    config: BrumbyConfig

    @nn.compact
    def __call__(self, h, pos, state, valid=None, bt=None, fresh=None):
        """``h`` [b, t, C] (the layer's normed input) at positions ``pos``
        [b, t]. With ``bt`` [b] (each row's block) a decode step: ``t`` is
        1 and ``state`` the layer's POOLS, read and updated in one pass;
        ``fresh`` [b]: the row has no past. Without, a prefill pass:
        ``state`` is the rows' own ``(S [b, KV, hd, D], z [b, KV, D])``
        and ``valid`` [b, t] says which positions are the prompt's.
        Returns ``(out [b, t, C] float32, S, z)``."""
        from ..ops import power_retention as pr
        from ..ops.paged_attention import report_path
        cfg = self.config
        b, t, C = h.shape
        H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        G = H // KV
        dt = _DTYPES[cfg.weights_dtype]
        eps, theta = cfg.rms_norm_eps, cfg.rope_theta
        scale = 1.0 / math.sqrt(hd)
        wq, wk, wv, wo, gq, gk = qkvo_params(self, C, H, KV, hd, dt)
        wg = self.param("g_proj", nn.initializers.normal(0.02), (C, KV), dt)
        bg = self.param("g_bias", nn.initializers.zeros, (KV,), dt)
        hb = h.astype(dt)
        k = key_heads(hb, wk, gk, pos, KV, hd, eps, theta).astype(dt)
        v = value_heads(hb, wv).reshape(b, t, KV, hd).astype(dt)
        q = query_heads(hb, wq, gq, pos, KV, G, hd, eps,
                        theta).astype(dt)                  # [b,KV,t,G,hd]
        with jax.named_scope("attn.retention.gate"):
            gam = jax.nn.log_sigmoid(
                jnp.dot(hb, wg, preferred_element_type=jnp.float32)
                + bg.astype(jnp.float32))                       # [b,t,KV]
        report_path(cfg.attend_paths()[0], (b, KV, t, G, hd),
                    str(jnp.dtype(dt)))
        S, z = state
        if bt is not None:
            n_live = (bt != 0).sum(dtype=jnp.int32)
            row_kib = (cfg.state_bytes_per_row()
                       // cfg.num_hidden_layers // 1024)
            # [live rows, KiB of state they hold in this layer] (KiB: a
            # step's bytes pass 2**31)
            self.sow("counters", "state",
                     jnp.stack([n_live, n_live * row_kib]),
                     reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((2,), jnp.int32))
            # blocks held, none skipped (what every paged layer counts)
            self.sow("counters", "pages",
                     jnp.stack([n_live, jnp.zeros((), jnp.int32)]),
                     reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((2,), jnp.int32))
            y, S, z = pr.decode_step(
                S, z, bt, q[:, :, 0], k[:, 0], v[:, 0], gam[:, 0], fresh,
                cfg.retention_eps, scale)
            y = y[:, None]                                 # [b,1,KV,G,hd]
        else:
            y, S, z = pr.prefill(
                S, z, jnp.moveaxis(q, 2, 3), jnp.moveaxis(k, 1, 2),
                jnp.moveaxis(v, 1, 2), jnp.moveaxis(gam, 1, 2), valid,
                cfg.retention_eps, scale, math.gcd(t, cfg.retention_chunk),
                mm_dtype=dt)
            y = jnp.moveaxis(y, 3, 1)      # [b,KV,G,t,hd] -> [b,t,KV,G,hd]
        # the heads side by side against ``o_proj`` as it lies: the
        # grouped einsum of ``decoder_parts.project_out`` has the chip
        # copy the 52 MB matrix into its own order every step
        out = jnp.dot(y.reshape(b, t, H * hd).astype(dt), wo,
                      preferred_element_type=jnp.float32)
        return (jnp.where((pos < cfg.block_size)[:, :, None], out, jnp.nan),
                S, z)


class SwiGLU(nn.Module):
    config: BrumbyConfig

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
    config: BrumbyConfig

    @nn.compact
    def __call__(self, x, pos, state, valid=None, bt=None, fresh=None):
        """``PowerRetention``'s arguments with the residual stream ``x``
        [b, t, C] in ``h``'s place: ``(x, S, z)``."""
        cfg = self.config
        dt = _DTYPES[cfg.weights_dtype]
        a = RMSNorm(cfg.rms_norm_eps, dt, name="input_layernorm")(x)
        y, S, z = PowerRetention(cfg, name="self_attn")(
            a, pos, state, valid, bt, fresh)
        x = x + y
        h = RMSNorm(cfg.rms_norm_eps, dt,
                    name="post_attention_layernorm")(x)
        with jax.named_scope("mlp"):
            return x + SwiGLU(cfg, name="mlp")(h), S, z


class Brumby(nn.Module):
    """``__call__(tokens [b, t], train=False, block_table=, cache_pos=,
    last_pos=None)`` -> float32 logits [b, 1, V] of a decode step (``t``
    is 1), or [b, V] at position ``last_pos`` of every row when that is
    given (a prefill: the positions past it are padding).

    The ``cache`` collection is ``state_<i>`` = ``{"S": [kv_pages, KV, hd,
    D], "z": [kv_pages, KV, D]}`` a layer. A decode step hands each
    layer its pools and takes them back updated in place. A prefill
    takes the rows' blocks out once, runs the prompt ``prefill_rows``
    positions at a time through ALL layers (a scan over passes whose
    carry is the rows' states, so that what it holds at once does not
    grow with the bucket; a pass that is all padding is skipped), and
    puts the blocks back."""

    config: BrumbyConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_table=None,
                 cache_pos=None, last_pos=None):
        from ..ops import power_retention as pr
        cfg = self.config
        if train:
            raise ValueError("this decoder is served, not trained: the "
                             "trainer runs the GPT-2 block only, and the "
                             "chunked retention has no backward pass here "
                             "(ROADMAP.md B1)")
        if not (cfg.decode and cfg.page_size > 0):
            raise ValueError("this decoder runs through the engine's pool "
                             "of state blocks only: decode=True and "
                             "page_size > 0")
        if cfg.page_size != cfg.block_size:
            raise ValueError(
                f"a row's state is one block: page_size must be the row's "
                f"extent {cfg.block_size}, got {cfg.page_size}")
        if block_table is None or cache_pos is None:
            raise ValueError("decode needs block_table and cache_pos")
        for name in ("weights_dtype", "kv_dtype"):
            if getattr(cfg, name) not in ("f32", "bf16"):
                raise ValueError(f"{name} must be 'f32' or 'bf16', got "
                                 f"{getattr(cfg, name)!r}")
        b, t = tokens.shape
        if t > 1 and last_pos is None:
            raise ValueError(
                "several tokens a row without last_pos is a speculative "
                "verify (spec_tokens > 0): refused, a recurrent state "
                "cannot be rewound past rejected drafts without a copy "
                "of it")
        dt, kv_dt = _DTYPES[cfg.weights_dtype], _DTYPES[cfg.kv_dtype]
        L, C, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        KV, hd = cfg.num_key_value_heads, cfg.head_dim
        D, P = pr.feature_dim(hd), cfg.kv_pages
        pools = [self.variable(
            "cache", f"state_{i}",
            lambda: {"S": jnp.zeros((P, KV, hd, D), kv_dt),
                     "z": jnp.zeros((P, KV, D), kv_dt)}) for i in range(L)]
        bt, fresh = block_table[:, 0], cache_pos == 0
        if last_pos is None:
            x = embed_tokens(self, tokens, V, C, dt)
            for i, pool in enumerate(pools):
                x, S, z = Block(cfg, name=f"layers_{i}")(
                    x, cache_pos[:, None], (pool.value["S"],
                                            pool.value["z"]),
                    bt=bt, fresh=fresh)
                pool.value = {"S": S, "z": z}
            return untied_head(self, x, None, V, cfg.rms_norm_eps, dt)

        if self.is_initializing():
            raise ValueError("initialise with one token a row: a prefill "
                             "reads the parameters that a decode step "
                             "declares")
        step = min(t, cfg.prefill_rows)
        if t % step:
            raise ValueError(f"a prefill of {t} positions is not whole "
                             f"passes of {step}")
        p = self.variables["params"]
        n_valid = jnp.broadcast_to(last_pos + 1, (b,))
        block = Block(cfg)

        def one_pass(carry, lo):
            def run(carry):
                states, x_last = carry
                at = lo + jnp.arange(step)
                tok = jax.lax.dynamic_slice_in_dim(tokens, lo, step, axis=1)
                x = p["embed_tokens"][tok].astype(jnp.float32)
                valid = at[None, :] < n_valid[:, None]
                out = []
                for i, state in enumerate(states):
                    x, S, z = block.apply(
                        {"params": p[f"layers_{i}"]}, x,
                        cache_pos[:, None] + at[None, :], state, valid)
                    out.append((S, z))
                here = jnp.clip(last_pos - lo, 0, step - 1)
                row = jax.lax.dynamic_index_in_dim(x, here, axis=1,
                                                   keepdims=False)
                return tuple(out), jnp.where(last_pos - lo == here, row,
                                             x_last)

            # a pass past every row's prompt is a bucket's padding
            return jax.lax.cond(lo < n_valid.max(), run, lambda c: c,
                                carry), None

        states = tuple(pr.load_rows(pool.value["S"], pool.value["z"], bt,
                                    fresh) for pool in pools)
        (states, x_last), _ = jax.lax.scan(
            one_pass, (states, jnp.zeros((b, C), jnp.float32)),
            jnp.arange(0, t, step))
        for pool, (S1, z1) in zip(pools, states):
            S, z = pr.store_rows(pool.value["S"], pool.value["z"], bt, S1,
                                 z1)
            pool.value = {"S": S, "z": z}
        return untied_head(self, x_last[:, None], None, V,
                           cfg.rms_norm_eps, dt)[:, 0]
