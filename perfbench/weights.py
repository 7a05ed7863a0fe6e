"""GPT-2 weights from a seed, made on the device in one jitted call.

``Trainer.fit`` builds its own weights from ``fit(seed=...)`` and offers no
way to hand it arrays without baking them into its init program as
constants (``init_params`` is closed over by the jitted init). So that the
reference can start from the SAME weights without taking them from the
program, this module draws them the way any flax model named like GPT-2
does from ``jax.random.PRNGKey(seed)``: a skeleton of ``nn.Dense`` /
``nn.Embed`` / ``nn.LayerNorm`` under the published module names with the
published initialisers (normal 0.02; residual projections 0.02/sqrt(2L);
zero biases; unit scales). It computes nothing and imports nothing of the
program. ``tests/test_reference.py`` holds it equal to the program's init.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

SEED_MOD = 2 ** 31 - 1


def dropout_rate(sizes: dict) -> float:
    """The one dropout rate ``GPTConfig`` has, from the three the published
    configuration names: they have to agree."""
    rates = {float(sizes[k]) for k in ("resid_pdrop", "embd_pdrop",
                                       "attn_pdrop")}
    if len(rates) != 1:
        raise ValueError(f"GPTConfig has one dropout rate, the "
                         f"configuration gives {sorted(rates)}")
    return rates.pop()


def seed32(seed: int) -> int:
    """The driver's seeds pass 2**31; ``PRNGKey`` and numpy generators in
    the program take 31 bits. One fold, used everywhere a seed is handed
    on."""
    return int(seed) % SEED_MOD


def _normal(std):
    return nn.initializers.normal(stddev=std)


class _Attn(nn.Module):
    c: int
    n_layer: int

    @nn.compact
    def __call__(self):
        z = jnp.zeros((1, self.c))
        nn.Dense(3 * self.c, kernel_init=_normal(0.02), name="c_attn")(z)
        nn.Dense(self.c, name="c_proj", kernel_init=_normal(
            0.02 / math.sqrt(2 * self.n_layer)))(z)


class _MLP(nn.Module):
    c: int
    n_layer: int

    @nn.compact
    def __call__(self):
        nn.Dense(4 * self.c, kernel_init=_normal(0.02),
                 name="c_fc")(jnp.zeros((1, self.c)))
        nn.Dense(self.c, name="c_proj", kernel_init=_normal(
            0.02 / math.sqrt(2 * self.n_layer)))(jnp.zeros((1, 4 * self.c)))


class _Block(nn.Module):
    c: int
    n_layer: int

    @nn.compact
    def __call__(self):
        z = jnp.zeros((1, self.c))
        nn.LayerNorm(epsilon=1e-5, name="ln_1")(z)
        _Attn(self.c, self.n_layer, name="attn")()
        nn.LayerNorm(epsilon=1e-5, name="ln_2")(z)
        _MLP(self.c, self.n_layer, name="mlp")()


class _Skeleton(nn.Module):
    vocab: int
    block: int
    n_layer: int
    c: int

    @nn.compact
    def __call__(self):
        i = jnp.zeros((1,), jnp.int32)
        nn.Embed(self.vocab, self.c, embedding_init=_normal(0.02),
                 name="wte")(i)
        nn.Embed(self.block, self.c, embedding_init=_normal(0.02),
                 name="wpe")(i)
        for layer in range(self.n_layer):
            _Block(self.c, self.n_layer, name=f"h_{layer}")()
        nn.LayerNorm(epsilon=1e-5, name="ln_f")(jnp.zeros((1, self.c)))


def _skeleton(sizes: dict) -> _Skeleton:
    return _Skeleton(sizes["vocab_size"], sizes["n_positions"],
                     sizes["n_layer"], sizes["n_embd"])


def make_params_traced(sizes: dict, key):
    """The parameter tree as an expression of ``key`` (``seed_key(seed)``),
    for use inside a jitted call. ``sizes``: the configuration file's
    ``vocab_size``, ``n_positions``, ``n_layer``, ``n_embd``."""
    # the split a flax trainer makes between its params and dropout streams
    p_key, _ = jax.random.split(key)
    return _skeleton(sizes).init({"params": p_key})["params"]


def seed_key(seed: int):
    return jax.random.PRNGKey(seed32(seed))


def make_params(sizes: dict, seed: int, device=None,
                block_kernel_scale: float = 1.0):
    """The float32 parameter tree for ``sizes`` from ``seed``, made on
    ``device`` (default: the first) in one jitted call. The seed is an
    argument of that call, so one compiled program serves every seed.

    ``block_kernel_scale`` multiplies the four projection kernels of every
    block. At the published initialiser a model with a tied output head
    puts its own input token first by some ten logits at every position:
    greedy decoding repeats that token, and no arithmetic can be told from
    another by the tokens it serves. Scaled up, the blocks and not the
    embedding decide the next token, as in a trained model, and best and
    second-best lie close enough for a lower precision to show. Only a
    cell that hands its weights to the program (serving) may use it."""
    def init(key):
        params = make_params_traced(sizes, key)
        if block_kernel_scale == 1.0:
            return params
        return jax.tree_util.tree_map_with_path(
            lambda path, a: (a * block_kernel_scale
                             if getattr(path[-1], "key", None) == "kernel"
                             else a), params)

    with jax.default_device(device or jax.devices()[0]):
        return jax.jit(init)(seed_key(seed))
