"""The plain reference of Xing4.0's language model (``model_type:
xing4_0``): a full forward over a whole sequence in straightforward
``jax.numpy``, float32 with ``precision=HIGHEST`` matrix products. No
kernel, no cache, no batching, nothing imported from the program: it is
what ``correct`` is decided against.

The sub-layers are the DeepSeek-V3 block's, and this file takes them from
``perfbench/references/kimi_k2.py`` (whose docstring has their equations:
the latent attention in its EXPANDED form, the SwiGLU, the sigmoid router
with a selection bias over experts beside a shared one), at this
configuration's sizes. What it adds is the residual path (``sizes`` is the
configuration file). A token's residual is ``X`` in R^{n x C}, ``n =
hc_mult`` (4), every ``X_i`` the token's embedding at the start. Each
sub-layer ``F`` (a layer's attention, then its SwiGLU, layer <
``first_k_dense_replace``, or its experts) has float32 ``phi`` [nC,
n(n+2)] (columns ``Phi_pre``, ``Phi_post``, ``Phi_res``), ``alpha`` [3],
``bias`` [n(n+2)] (``b_pre``, ``b_post``, ``B_res``)::

    u      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)       no weight
    a_pre  = alpha_pre  (u Phi_pre)  + b_pre     H_pre  = sigmoid(a_pre)
    a_post = alpha_post (u Phi_post) + b_post    H_post = 2 sigmoid(a_post)
    A_res  = alpha_res mat(u Phi_res) + B_res    n x n, row-major [j, i]
    M      = exp(clip(A_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:
        M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    H_res  = M
    h      = sum_i H_pre[i] X_i
    y      = F(rms(h; g))           F's own weighted RMS norm
    X'_j   = sum_i H_res[j, i] X_i + H_post[j] y

After the last layer ``x = sum_i X_i``, ``rms``, then ``logits = x
W_head`` (untied). ``num_nextn_predict_layers`` is 0: no module is left
out of what is served.

Departures are ``kimi_k2.py``'s (blocks of queries, of rows and of an
expert's rows; padding that costs nothing), and none changes a value
beyond the order of float32 additions. The hyper-connection is computed
for every row at once: ``[T, 4, 3584]`` float32 is 0.7 GB at the cell's
12,288 positions.

``mode``: ``"f32"`` is the reference proper; ``"fp8"`` the control for a
configuration that states bfloat16 (both operands of every matrix product,
``u Phi`` among them, rounded to float8 e4m3).

``faults`` plants a wrong reading of the description (the tests hold the
comparison to catching each): ``h_res_rows_only`` (``H_res``'s rows
divided by their sums once, a softmax, and no Sinkhorn), ``sinkhorn_2``
(2 iterations for 20: one fewer than 20 moves nothing that bfloat16
keeps), ``alpha_res_zero`` (the static ``B_res`` alone), ``h_post_halved``
(``H_post`` without its factor 2), ``bias_as_weight`` (the chosen experts
weighted by ``g + bias``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references.kimi_k2 import (_KEYS, POS_BLOCK, _attention,
                                          _experts, _freeze, _head, _mm,
                                          _rms, _swiglu, _thaw, held_range)

FAULTS = ("h_res_rows_only", "sinkhorn_2", "alpha_res_zero",
          "h_post_halved", "bias_as_weight")
_HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max")


def hyper_coefficients(X, p, sub: str, sizes: dict, mode: str = "f32",
                       faults=()):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the streams
    ``X`` [T, n, C] for the sub-layer ``sub`` of the layer's ``hc``."""
    t, n, c = X.shape
    v = X.reshape(t, n * c)
    u = v / jnp.sqrt(jnp.square(v).mean(-1, keepdims=True)
                     + float(sizes["rms_norm_eps"]))
    a = _mm(u, p[f"phi_{sub}"], mode)
    alpha, bias = p[f"alpha_{sub}"], p[f"bias_{sub}"]
    h_pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + bias[:n])
    h_post = jax.nn.sigmoid(alpha[1] * a[:, n:2 * n] + bias[n:2 * n])
    if "h_post_halved" not in faults:
        h_post = 2.0 * h_post
    alpha_res = 0.0 if "alpha_res_zero" in faults else alpha[2]
    a_res = (alpha_res * a[:, 2 * n:] + bias[2 * n:]).reshape(t, n, n)
    m = jnp.exp(jnp.clip(a_res, float(sizes["mhc_h_res_clamp_min"]),
                         float(sizes["mhc_h_res_clamp_max"])))
    eps = float(sizes["hc_eps"])
    if "h_res_rows_only" in faults:
        return h_pre, h_post, m / (m.sum(2, keepdims=True) + eps)
    iters = 2 if "sinkhorn_2" in faults else int(sizes["hc_sinkhorn_iters"])
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)     # columns: over j
        m = m / (m.sum(2, keepdims=True) + eps)     # rows: over i
    return h_pre, h_post, m


def _sub_layer(X, p_hc, sub, fn, sizes, mode, faults):
    """One sub-layer ``fn(h [T, C]) -> [T, C]`` around its
    hyper-connection."""
    h_pre, h_post, h_res = hyper_coefficients(X, p_hc, sub, sizes, mode,
                                              faults)
    y = fn((h_pre[:, :, None] * X).sum(1))
    mixed = sum(h_res[:, :, i, None] * X[:, None, i, :]
                for i in range(X.shape[1]))
    return mixed + h_post[:, :, None] * y[:, None, :]


@functools.partial(jax.jit, static_argnames=(
    "sizes_key", "mode", "faults", "room", "dense"))
def _layer(X, p, length, sizes_key, mode, faults, room, dense):
    sizes = _thaw(sizes_key)
    eps = float(sizes["rms_norm_eps"])
    p_mlp = p["mlp"]

    def attention(h):
        return _attention(_rms(h, p["input_layernorm"]["weight"], eps),
                          p["self_attn"], sizes, length, mode, faults)

    def feed_forward(h):
        b = _rms(h, p["post_attention_layernorm"]["weight"], eps)
        if dense:
            return _swiglu(b, p_mlp["gate_proj"], p_mlp["up_proj"],
                           p_mlp["down_proj"], length, mode)
        return _experts(b, p_mlp, sizes, length, mode, faults, room)

    X = _sub_layer(X, p["hc"], "attn", attention, sizes, mode, faults)
    return _sub_layer(X, p["hc"], "mlp", feed_forward, sizes, mode, faults)


def forward(params, sizes: dict, tokens, positions, length=None,
            mode: str = "f32", faults=()):
    """Float32 logits [len(positions), vocab] of the whole sequence
    ``tokens`` [T] at ``positions``; ``length``: the tokens before the
    padding (default: all). ``kimi_k2.forward``'s contract."""
    faults = tuple(sorted(faults))
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    key = _freeze({k: sizes[k] for k in _KEYS + _HC_KEYS}
                  | {"held_experts": held_range(sizes)})
    tokens = jnp.asarray(tokens, jnp.int32)
    t = int(tokens.shape[0])
    length = jnp.asarray(t if length is None else length, jnp.int32)
    # an expert's expected rows are T * k / E; a room is twice that
    room = min(t, max(64, 2 * t * int(sizes["num_experts_per_tok"])
                      // int(sizes["n_routed_experts_published"])))
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    X = jnp.broadcast_to(x[:, None, :], (t, int(sizes["hc_mult"]),
                                         x.shape[1]))
    for i in range(int(sizes["num_hidden_layers"])):
        X = _layer(X, params[f"layers_{i}"], length, key, mode, faults,
                   room, i < int(sizes["first_k_dense_replace"]))
    # the head takes whole blocks of positions (the last repeated), so
    # that requests of any length share a few compiled programs
    positions = np.asarray(positions)
    n = len(positions)
    padded = np.full(-(-n // POS_BLOCK) * POS_BLOCK, positions[-1])
    padded[:n] = positions
    return _head(X[jnp.asarray(padded)].sum(1), params["norm"]["weight"],
                 params["lm_head"], float(sizes["rms_norm_eps"]), mode)[:n]


def served_logits(params, sizes: dict, prompt, served, *, pad_multiple: int,
                  mode: str = "f32", faults=()):
    """Logits [len(served), vocab] at the positions whose next token was
    served: the last prompt position, then every served token but the
    last. The sequence is padded to a multiple of ``pad_multiple``."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    idx = np.zeros(-(-len(seq) // pad_multiple) * pad_multiple, np.int32)
    idx[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return forward(params, sizes, idx, pos, len(seq), mode, faults)


def served_gaps(params, sizes: dict, prompt, served, *, pad_multiple: int,
                mode: str = "f32", faults=(), ref=None):
    """For one finished greedy request: at every served position, how far
    the served token's reference logit lies below the reference's best
    (``kimi_k2.served_gaps``: with ``mode`` other than ``"f32"``, or
    ``faults``, the token that arithmetic puts first takes the served
    token's place, still judged by the float32 logits ``ref``)."""
    if ref is None:
        ref = served_logits(params, sizes, prompt, served,
                            pad_multiple=pad_multiple)
    ref = np.asarray(ref)
    if mode == "f32" and not faults:
        tokens = np.asarray(served, np.int64)
    else:
        tokens = np.asarray(jnp.argmax(
            served_logits(params, sizes, prompt, served,
                          pad_multiple=pad_multiple, mode=mode,
                          faults=faults), axis=-1))
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return best - got
