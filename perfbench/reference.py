"""The plain reference of GPT-2: forward, loss, gradients and AdamW in
straightforward ``jax.numpy``, float32 with ``precision=HIGHEST`` matrix
products. No kernel, no cache, no batching tricks, and nothing imported
from the program: it is what ``correct`` is decided against.

Departures from the published description, all to fit one chip beside
nothing else: the layers run under ``lax.scan`` over their stacked
parameters with each layer rematerialised in the backward pass, and a
batch is taken in blocks of rows whose gradients are summed. Neither
changes a value beyond the order of float32 additions.

``mode`` selects the arithmetic of the matrix products:

* ``"f32"``  — the reference proper.
* ``"bf16"`` — the control for a configuration that states float32: every
  parameter and activation in bfloat16.
* ``"fp8"``  — the control for a configuration that states bfloat16: both
  operands of every matrix product rounded to float8 (e4m3, one scale per
  tensor), as a later PR tempted by the fp8 unit would.

The parameter tree is the published GPT-2 layout under the names the
program's checkpoints use (``wte``, ``wpe``, ``h_<i>/{ln_1,attn/{c_attn,
c_proj},ln_2,mlp/{c_fc,c_proj}}``, ``ln_f``; ``kernel``/``bias``/``scale``/
``embedding`` leaves), so leaves of the two sides pair up by path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient passes
    straight through, so the backward products see the rounded operands."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / 448.0
    q = (x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x32 + jax.lax.stop_gradient(q - x32)


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _fp8(x), _fp8(w)
    if mode == "bf16":
        return jnp.matmul(x, w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu(x):
    # GPT-2's tanh approximation
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _dense(x, p, mode):
    return _mm(x, p["kernel"], mode) + p["bias"]


def _block(x, p, n_head, mode):
    b, t, c = x.shape
    hd = c // n_head
    qkv = _dense(_layer_norm(x, p["ln_1"]), p["attn"]["c_attn"], mode)
    q, k, v = (z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)
               for z in jnp.split(qkv, 3, axis=-1))
    att = _mm(q, k.transpose(0, 1, 3, 2), mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jnp.where(causal, att, -jnp.inf)
    att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(x.dtype)
    y = _mm(att, v, mode).transpose(0, 2, 1, 3).reshape(b, t, c)
    x = x + _dense(y, p["attn"]["c_proj"], mode)
    h = _gelu(_dense(_layer_norm(x, p["ln_2"]), p["mlp"]["c_fc"], mode))
    return x + _dense(h, p["mlp"]["c_proj"], mode)


def n_layers(params) -> int:
    return sum(1 for k in params if k.startswith("h_"))


def hidden(params, idx, n_head: int, mode: str = "f32"):
    """Final hidden states ``[B, T, C]`` (after ``ln_f``) for tokens
    ``idx`` ``[B, T]``."""
    if mode == "bf16":
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    t = idx.shape[1]
    x = params["wte"]["embedding"][idx] + params["wpe"]["embedding"][:t]
    layers = [params[f"h_{i}"] for i in range(n_layers(params))]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    body = jax.checkpoint(
        lambda x, p: (_block(x, p, n_head, mode), None))
    x, _ = jax.lax.scan(body, x, stacked)
    return _layer_norm(x, params["ln_f"])


def logits_at(params, idx, positions, n_head: int, mode: str = "f32"):
    """Float32 logits ``[len(positions), V]`` of ONE sequence ``idx``
    ``[T]`` at the given positions (the tied head: ``wte`` transposed)."""
    h = hidden(params, idx[None], n_head, mode)[0][positions]
    wte = params["wte"]["embedding"]
    if mode == "bf16":
        wte = wte.astype(jnp.bfloat16)
    return _mm(h, wte.T, mode).astype(jnp.float32)


def loss_sum(params, x, y, n_head: int, mode: str = "f32"):
    """Summed cross-entropy of next-token targets ``y`` over a block of
    rows, and the number of tokens."""
    h = hidden(params, x, n_head, mode)
    wte = params["wte"]["embedding"]
    if mode == "bf16":
        wte = wte.astype(jnp.bfloat16)
    logits = _mm(h, wte.T, mode).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked), y.size


@functools.partial(jax.jit, static_argnames=("n_head", "mode"))
def _block_grad(params, x, y, n_head, mode):
    return jax.value_and_grad(
        lambda p: loss_sum(p, x, y, n_head, mode)[0])(params)


def loss_and_grad(params, x, y, n_head: int, mode: str = "f32",
                  rows_block: int = 4):
    """Mean loss and its gradient over the rows of ``x``/``y`` ``[R, T]``,
    taken ``rows_block`` rows at a time."""
    rows = x.shape[0]
    total, grads = 0.0, None
    for lo in range(0, rows, rows_block):
        s, g = _block_grad(params, x[lo:lo + rows_block],
                           y[lo:lo + rows_block], n_head, mode)
        total = total + s
        grads = g if grads is None else _tree_add(grads, g)
    n = rows * x.shape[1]
    return total / n, _tree_scale(grads, 1.0 / n)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _tree_scale(a, s):
    return jax.tree.map(lambda v: v * s, a)


def lr_at(step: int, lr: float, warmup_steps: int = 0) -> float:
    """The learning rate of update number ``step`` (from 0): linear
    warm-up ``step / warmup_steps`` (so the first update has rate 0), then
    constant."""
    if warmup_steps and step < warmup_steps:
        return lr * step / warmup_steps
    return lr


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def adamw_step(params, grads, mu, nu, t, lr, *, b1, b2, eps, wd):
    """One AdamW update (decoupled weight decay on every leaf, bias
    correction, ``eps`` outside the root); ``t`` counts from 1, ``lr`` is
    this update's rate."""
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p),
        params, mu, nu)
    return params, mu, nu


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)


@jax.jit
def leaf_diff_norms(a, b):
    return jax.tree.map(
        lambda u, v: jnp.sqrt(jnp.sum(jnp.square(
            u.astype(jnp.float32) - v.astype(jnp.float32)))), a, b)


def leaf_projections(tree, lead: int = 0):
    """Per leaf, the sum of its elements under fixed random signs (the
    same signs on both sides of a comparison; ``lead`` leading axes are
    kept). Unlike a norm, which rounding noise moves only in the second
    order, a projection moves in the first: it is what tells a lower
    precision from the stated one."""
    leaves, treedef = jax.tree.flatten(tree)
    base = jax.random.PRNGKey(20260927)
    out = []
    for i, a in enumerate(leaves):
        signs = jax.random.rademacher(jax.random.fold_in(base, i),
                                      a.shape[lead:], jnp.float32)
        out.append(jnp.sum(a.astype(jnp.float32) * signs,
                           axis=tuple(range(lead, a.ndim))))
    return jax.tree.unflatten(treedef, out)


def by_path(tree) -> dict:
    """``{path: leaf}`` of a tree, paths as ``a/b/c``: how leaves of the
    two sides pair up."""
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def flat(tree) -> dict:
    """``{path: float}`` of a tree of scalars."""
    return {k: float(np.asarray(v)) for k, v in by_path(tree).items()}


def follow_training(params0, node_batches, *, n_head, hyper, reduce: str,
                    mode: str = "f32", rows_block: int = 4):
    """Follow the first steps of a data-parallel run from ``params0``.

    ``node_batches[k][s]`` is node ``k``'s ``(x, y)`` of step ``s``.
    ``reduce="mean"``: every step the nodes' gradients are averaged and
    all take the same update (plain all-reduce). ``reduce="none"``: each
    node trains alone on its own rows (DiLoCo before its first outer
    step). Returns per step and node the loss, and per node the leaf
    norms of the parameters' change and of Adam's first moment after the
    last step.
    """
    k_nodes = len(node_batches)
    steps = len(node_batches[0])
    hyper = dict(hyper)
    base_lr, warm = hyper.pop("lr"), hyper.pop("warmup_steps", 0)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    losses = [[None] * k_nodes for _ in range(steps)]
    out_nodes = []
    if reduce == "mean":
        params, mu, nu = params0, zeros(params0), zeros(params0)
        for s in range(steps):
            grads = None
            for k in range(k_nodes):
                x, y = node_batches[k][s]
                loss, g = loss_and_grad(params, x, y, n_head, mode,
                                        rows_block)
                losses[s][k] = float(loss)
                grads = g if grads is None else _tree_add(grads, g)
            grads = _tree_scale(grads, 1.0 / k_nodes)
            params, mu, nu = adamw_step(params, grads, mu, nu, s + 1,
                                        lr_at(s, base_lr, warm), **hyper)
        node = {"dparam": flat(leaf_diff_norms(params, params0)),
                "mu": flat(leaf_norms(mu)),
                "mu_proj": flat(jax.jit(leaf_projections)(mu))}
        out_nodes = [node] * k_nodes
    elif reduce == "none":
        for k in range(k_nodes):
            params, mu, nu = params0, zeros(params0), zeros(params0)
            for s in range(steps):
                x, y = node_batches[k][s]
                loss, g = loss_and_grad(params, x, y, n_head, mode,
                                        rows_block)
                losses[s][k] = float(loss)
                params, mu, nu = adamw_step(params, g, mu, nu, s + 1,
                                            lr_at(s, base_lr, warm), **hyper)
            out_nodes.append(
                {"dparam": flat(leaf_diff_norms(params, params0)),
                 "mu": flat(leaf_norms(mu)),
                 "mu_proj": flat(jax.jit(leaf_projections)(mu))})
            del params, mu, nu
    else:
        raise ValueError(f"unknown reduce {reduce!r}")
    return {"losses": losses, "nodes": out_nodes}


def served_gaps(params, prompt, served, n_head: int, *, pad_to: int,
                n_pos: int, mode: str = "f32"):
    """For one finished greedy request: at every served position, how far
    the served token's reference logit lies below the reference's best.

    With ``mode`` other than ``"f32"`` this is the control: the token that
    the lower precision puts first takes the served token's place, still
    judged by the float32 logits. The sequence is padded to ``pad_to``
    tokens and the positions to ``n_pos`` (causal attention never looks
    at the padding), so one compiled program serves every request.
    Returns a float32 array, one gap per served token.
    """
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    n = len(served)
    if len(seq) > pad_to or n > n_pos:
        raise ValueError(f"request of {len(seq) + 1} tokens, {n} served, "
                         f"does not fit pad_to={pad_to}, n_pos={n_pos}")
    idx = np.zeros(pad_to, np.int32)
    idx[:len(seq)] = seq
    pos = np.full(n_pos, len(prompt) - 1, np.int32)
    pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    idx, pos = jnp.asarray(idx), jnp.asarray(pos)
    ref = _logits_jit(params, idx, pos, n_head, "f32")
    if mode == "f32":
        tokens = np.zeros(n_pos, np.int32)
        tokens[:n] = np.asarray(served, np.int32)
        tokens = jnp.asarray(tokens)
    else:
        tokens = jnp.argmax(_logits_jit(params, idx, pos, n_head, mode),
                            axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)[:n]


_logits_jit = jax.jit(logits_at, static_argnames=("n_head", "mode"))
