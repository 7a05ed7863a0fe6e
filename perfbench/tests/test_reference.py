"""The plain reference against ``models/nanogpt.py`` at a tiny size on the
CPU: same weights from the seed, same loss, same gradients, same AdamW."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from gym_tpu.models.base import as_loss_model
from gym_tpu.models.nanogpt import GPT, GPTConfig
from perfbench import reference, weights

SIZES = dict(vocab_size=128, n_positions=32, n_layer=2, n_embd=32, n_head=2)


def _program(seed):
    cfg = GPTConfig(block_size=32, vocab_size=128, n_layer=2, n_head=2,
                    n_embd=32)
    x = jnp.zeros((2, 32), jnp.int32)
    params, _ = as_loss_model(GPT(cfg)).init(
        jax.random.PRNGKey(weights.seed32(seed)), (x, x))
    return cfg, params


def _batch(seed, rows=4):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, 128, (rows, 32)), jnp.int32),
            jnp.asarray(rng.integers(0, 128, (rows, 32)), jnp.int32))


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_011])
def test_weights_from_seed_equal_the_programs_init(seed):
    _cfg, params = _program(seed)
    mine = weights.make_params(SIZES, seed)
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    # float32 round-off of the scaled draws, nothing more
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), params, mine)))
    assert worst < 1e-7


def test_loss_and_gradients_equal_the_programs():
    cfg, params = _program(3)
    x, y = _batch(1)
    loss, grads = reference.loss_and_grad(
        weights.make_params(SIZES, 3), x, y, 2, "f32", rows_block=2)
    want, want_g = jax.value_and_grad(lambda p: GPT(cfg).apply(
        {"params": p}, (x, y), train=False))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), grads, want_g)))
    assert worst < 1e-6


def test_logits_equal_the_programs():
    cfg, params = _program(5)
    x, _ = _batch(2, rows=1)
    got = reference.logits_at(weights.make_params(SIZES, 5), x[0],
                              jnp.arange(32), 2)
    want = GPT(cfg).apply({"params": params}, x, train=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_adamw_equals_optax():
    params = weights.make_params(SIZES, 1)
    x, y = _batch(3)
    hyper = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
    # the program's schedule: lr * step / warmup, then constant
    sched = lambda step: 6e-4 * jnp.minimum(step / 2.0, 1.0)  # noqa: E731
    tx = optax.adamw(sched, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    state, theirs = tx.init(params), params
    mine = params
    mu = nu = jax.tree.map(jnp.zeros_like, params)
    for t in range(1, 4):
        _, g = reference.loss_and_grad(mine, x, y, 2)
        mine, mu, nu = reference.adamw_step(
            mine, g, mu, nu, t, reference.lr_at(t - 1, 6e-4, 2), **hyper)
        _, g2 = reference.loss_and_grad(theirs, x, y, 2)
        upd, state = tx.update(g2, state, theirs)
        theirs = optax.apply_updates(theirs, upd)
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), mine, theirs)))
    assert worst < 1e-6


def test_follow_training_mean_equals_one_big_batch():
    params = weights.make_params(SIZES, 2)
    hyper = dict(lr=6e-4, b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
    nodes = [[_batch(10 * k + s, rows=2) for s in range(2)]
             for k in range(2)]
    got = reference.follow_training(params, nodes, n_head=2, hyper=hyper,
                                    reduce="mean", rows_block=2)
    joined = [[(jnp.concatenate([nodes[0][s][0], nodes[1][s][0]]),
                jnp.concatenate([nodes[0][s][1], nodes[1][s][1]]))
               for s in range(2)]]
    one = reference.follow_training(params, joined, n_head=2, hyper=hyper,
                                    reduce="none", rows_block=2)
    for leaf, v in one["nodes"][0]["dparam"].items():
        assert abs(got["nodes"][0]["dparam"][leaf] - v) <= 1e-6 + 1e-4 * v


def test_served_gaps_zero_for_the_references_own_choice():
    params = weights.make_params(SIZES, 4)
    prompt = list(range(5, 15))
    served = []
    for _ in range(6):
        seq = jnp.asarray(prompt + served, jnp.int32)
        lg = reference.logits_at(params, seq, jnp.asarray([len(seq) - 1]), 2)
        served.append(int(jnp.argmax(lg[0])))
    gaps = reference.served_gaps(params, prompt, served, 2, pad_to=32,
                                 n_pos=8)
    assert gaps.shape == (6,) and float(gaps.max()) == 0.0
    served[3] = (served[3] + 1) % 128       # a token altered
    assert float(reference.served_gaps(params, prompt, served, 2,
                                       pad_to=32, n_pos=8)[3]) > 0.0
