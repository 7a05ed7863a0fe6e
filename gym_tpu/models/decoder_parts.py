"""What the decoders built on the Qwen3 block share (``keye_vl2.py``,
``brumby.py``): the RMS norm, the grouped q/k/v/o projections with an RMS
norm over each head of q and k and the rotate-half rotary, the token
embedding and the untied head. Functions of arrays (and of the calling
module, where they declare its parameters): a decoder composes them
around its own attention and feed-forward, and its programs hold the
operations these functions trace in the order they are called.

Weights in the decoder's ``weights_dtype``; norms, rotations and every
product's accumulator in float32.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


def rotate_half(x, pos, theta: float, rotary_dim=None):
    """Rotary embedding over the whole last axis, lane ``i`` paired with
    lane ``i + d/2``: ``x`` [..., t, *, d] float32 with ``pos``
    broadcastable to ``x``'s leading axes up to ``t``. ``rotary_dim``
    given: over the first ``rotary_dim`` lanes only (lane ``i`` paired
    with lane ``i + rotary_dim/2``), the others pass."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [rotate_half(x[..., :rotary_dim], pos, theta),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv            # [..., d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def rms(x, w, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


class RMSNorm(nn.Module):
    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
        return rms(x, w, self.eps)


def qkvo_params(mod: nn.Module, C: int, H: int, KV: int, hd: int, dt):
    """Declare, on the attention module ``mod``, the grouped projections
    and the per-head norms' weights: ``(q_proj, k_proj, v_proj, o_proj,
    q_norm, k_norm)``."""
    init, ones = nn.initializers.normal(0.02), nn.initializers.ones
    return (mod.param("q_proj", init, (C, H * hd), dt),
            mod.param("k_proj", init, (C, KV * hd), dt),
            mod.param("v_proj", init, (C, KV * hd), dt),
            mod.param("o_proj", init, (H * hd, C), dt),
            mod.param("q_norm", ones, (hd,), dt),
            mod.param("k_norm", ones, (hd,), dt))


def key_heads(hb, wk, gk, wpos, KV: int, hd: int, eps: float, theta: float):
    """The new positions' keys, normed a head and rotated: ``hb`` [b, t,
    C] in the weights' dtype at positions ``wpos`` [b, t] -> float32
    [b, t, KV, hd]."""
    C = hb.shape[-1]
    k = jnp.einsum("btc,ckd->btkd", hb, wk.reshape(C, KV, hd),
                   preferred_element_type=jnp.float32)
    return rotate_half(rms(k, gk, eps), wpos[:, :, None], theta)


def value_heads(hb, wv):
    """The new positions' values: float32 [b, t, KV * hd]."""
    return jnp.dot(hb, wv, preferred_element_type=jnp.float32)


def query_heads(hb, wq, gq, qpos, KV: int, G: int, hd: int, eps: float,
                theta: float):
    """Queries grouped by key-value head, normed a head and rotated:
    ``hb`` [b, t, C] at positions ``qpos`` [b, t] -> float32
    [b, KV, t, G, hd]."""
    C = hb.shape[-1]
    q = jnp.einsum("btc,ckgd->bktgd", hb, wq.reshape(C, KV, G, hd),
                   preferred_element_type=jnp.float32)
    return rotate_half(rms(q, gq, eps), qpos[:, None, :, None], theta)


def project_out(y, wo, KV: int, G: int, hd: int):
    """``y`` [b, KV, t, G, hd] through the output projection: float32
    [b, t, C]."""
    return jnp.einsum("bktgd,kgdc->btc", y,
                      wo.reshape(KV, G, hd, wo.shape[-1]),
                      preferred_element_type=jnp.float32)


def embed_tokens(mod: nn.Module, tokens, vocab: int, hidden: int, dt):
    """The float32 residual stream of ``tokens`` [b, t] from ``mod``'s
    ``embed_tokens`` table."""
    embed = mod.param("embed_tokens", nn.initializers.normal(0.02),
                      (vocab, hidden), dt)
    return embed[tokens].astype(jnp.float32)


def untied_head(mod: nn.Module, x, last_pos, vocab: int, eps: float, dt):
    """The final norm and ``mod``'s own ``lm_head``: float32 logits of
    ``x`` [b, t, hidden], or of position ``last_pos`` of every row."""
    if last_pos is not None:
        x = jax.lax.dynamic_index_in_dim(x, last_pos, axis=1,
                                         keepdims=False)
    y = RMSNorm(eps, dt, name="norm")(x)
    head = mod.param("lm_head", nn.initializers.normal(0.02),
                     (x.shape[-1], vocab), dt)
    with jax.named_scope("head"):
        return jnp.dot(y.astype(dt), head,
                       preferred_element_type=jnp.float32)
