"""Xing4.0 language-model weights from a seed, made on the device a leaf
at a time.

The block's own leaves are ``perfbench/weights_kimi.py``'s, name for name
and scale for scale (the two configs share the DeepSeek-V3 block key for
key: ``embed_tokens``, ``lm_head``, ``norm``, a layer's two norms,
``self_attn`` with the published ``q_b_proj`` and ``kv_b_proj``, ``mlp``
dense or of experts with ``e_score_correction_bias``), from the same
draws a leaf. What this file adds is every layer's ``hc``: for each of its
two sub-layers (``attn``, ``mlp``), float32,

* ``phi_<sub>`` [hc_mult * hidden, hc_mult * (hc_mult + 2)], its columns
  ``Phi_pre`` (hc_mult), ``Phi_post`` (hc_mult), ``Phi_res`` (hc_mult^2,
  row-major ``[j, i]``): normal with standard deviation ``hc_phi_scale /
  sqrt(hc_mult * hidden)``, so that ``u Phi`` of a unit-RMS ``u`` is of
  unit scale a column;
* ``alpha_<sub>`` [3] (``alpha_pre``, ``alpha_post``, ``alpha_res``):
  ``hc_alpha``, 1;
* ``bias_<sub>`` [hc_mult * (hc_mult + 2)] (``b_pre``, ``b_post``,
  ``B_res`` row-major): normal with standard deviation ``hc_bias_scale``, 1.

NOT the paper's start (``alpha`` near zero, ``B_res`` near the identity):
there the hyper-connection is a plain residual and a program that left
the dynamic term or the projection out would serve the same tokens. At
these scales ``H_pre`` and ``H_post`` range over most of (0, 1) and (0,
2), ``exp(A_res)`` spreads over some e^6 before the projection, and the
part of ``A_res`` that depends on the token is as large as the part that
does not. The reference reads this tree as it is; the program is handed
the same tree (``Xing4Config.prepare_params``). Nothing of the program is
imported; ``tests`` hold the shapes equal to the decoder's own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import weights_kimi
from perfbench.weights import seed_key

SUBS = ("attn", "mlp")


def hc_shapes(sizes: dict) -> dict:
    """``{(layer, "hc", name): shape}`` of the hyper-connections' leaves."""
    n = int(sizes["hc_mult"])
    k = n * (n + 2)
    out = {}
    for i in range(int(sizes["num_hidden_layers"])):
        for sub in SUBS:
            out[(f"layers_{i}", "hc", f"phi_{sub}")] = (
                n * sizes["hidden_size"], k)
            out[(f"layers_{i}", "hc", f"alpha_{sub}")] = (3,)
            out[(f"layers_{i}", "hc", f"bias_{sub}")] = (k,)
    return out


def make_params(sizes: dict, seed: int, device=None):
    """The parameter tree for ``sizes`` from ``seed`` on ``device``
    (default: the first)."""
    assumed = sizes["assumed"]
    tree = weights_kimi.make_params(sizes, seed, device)
    root = jax.random.fold_in(seed_key(seed), 0x4c)
    with jax.default_device(device or jax.devices()[0]):
        for n, ((layer, _hc, name), shape) in enumerate(
                hc_shapes(sizes).items()):
            key = jax.random.fold_in(root, n)
            if name.startswith("alpha"):
                leaf = jnp.full(shape, float(assumed["hc_alpha"]),
                                jnp.float32)
            else:
                std = (float(assumed["hc_phi_scale"]) / math.sqrt(shape[0])
                       if name.startswith("phi")
                       else float(assumed["hc_bias_scale"]))
                leaf = weights_kimi._normal(key, shape, std, jnp.float32)
            tree[layer].setdefault("hc", {})[name] = leaf
    return tree
