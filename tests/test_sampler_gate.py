"""``sample_rows`` (``models/nanogpt.py``): the serving programs' sampler
takes its full-vocabulary sorts only in a step in which a LIVE row
filters, and gives the tokens of ``vmap(sample_logits)`` either way.

Oracle: ``parent_sample_logits`` below is the sampler as it stood before
the gate, verbatim (two sorts a row, always). Every case compares bit
for bit, on seeded logits and keys.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.models.nanogpt import sample_logits, sample_rows


def parent_sample_logits(logits, key, temperature=1.0, top_k=None,
                         top_p=None):
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32) / temperature
    k = v if top_k is None else jnp.clip(top_k, 1, v)
    srt = jnp.sort(logits, axis=-1)[..., ::-1]        # descending
    kidx = jnp.broadcast_to(jnp.asarray(k - 1, jnp.int32),
                            (*logits.shape[:-1], 1))
    kth = jnp.take_along_axis(srt, kidx, axis=-1)
    logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)          # -inf rows → 0
        cum = jnp.cumsum(probs, axis=-1) - probs      # exclusive prefix
        p_eff = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32),
                                 (*logits.shape[:-1], 1))
        keep = cum < jnp.where(p_eff >= 1.0, jnp.inf, p_eff)
        n_keep = jnp.maximum(jnp.sum(keep, axis=-1, keepdims=True), 1)
        thr = jnp.take_along_axis(srt, n_keep - 1, axis=-1)
        logits = jnp.where(logits < thr, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _sorts(jaxpr) -> int:
    """``sort`` equations of a jaxpr, those of its sub-jaxprs counted
    where they are called."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "sort"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _sorts(sub)
    return n


S = 8
TEMPS = [1.0, 0.7, 1.3, 1.0, 2.5, 0.4, 1.0, 0.9]


def _logits(v, seed=0, scale=3.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (S, v),
                                     jnp.float32)


def _keys(seed, shape=(S,)):
    flat = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(int(np.prod(shape))))
    return flat.reshape(*shape, 2)


def _both(logits, keys, top_k, top_p, live=None, temp=TEMPS):
    """``(oracle tokens, sample_rows' tokens, whether it sorted)``."""
    temp = jnp.asarray(temp, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    live = jnp.ones(S, bool) if live is None else jnp.asarray(live, bool)
    want = jax.jit(jax.vmap(parent_sample_logits))(logits, keys, temp,
                                                   top_k, top_p)
    got, took = jax.jit(sample_rows)(logits, keys, temp, top_k, top_p,
                                     live)
    return np.asarray(want), np.asarray(got), bool(took)


def _cases(v):
    """``{id: (top_k, top_p, live, sorted)}``: rows' parameters in the
    engine's array encodings (no top-k: ``V``; no top-p: 1.0)."""
    one = [1.0] * S
    return {
        "all_default": ([v] * S, one, None, False),
        "all_greedy": ([1] * S, one, None, False),
        "greedy_and_default": ([1, v] * (S // 2), one, None, False),
        "top_k_beyond_v_and_below_1": ([v + 7, 0, -3, v, 1, v, 1, v], one,
                                       None, False),
        "one_top_k_40": ([v, v, 40, v, 1, v, 1, v], one, None, True),
        "one_top_p_0.9": ([v, 1, v, v, 1, v, v, v],
                          [1.0, 1.0, 1.0, 0.9, 1.0, 1.0, 1.0, 1.0], None,
                          True),
        "top_k_and_top_p_rows": ([5, 1, v, 7, 1, v, 3, v],
                                 [0.5, 1.0, 0.8, 1.0, 0.3, 1.0, 0.95, 1.0],
                                 None, True),
        "top_p_nan_sorts": ([v] * S, [float("nan")] + [1.0] * (S - 1),
                            None, True),
    }


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", list(_cases(0)))
def test_sample_rows_equals_the_parent_sampler(case, seed):
    v = 211
    top_k, top_p, live, sorts = _cases(v)[case]
    want, got, took = _both(_logits(v, seed), _keys(100 + seed), top_k,
                            top_p, live)
    np.testing.assert_array_equal(got, want)
    assert took is sorts


@pytest.mark.parametrize("case", ["greedy_and_default", "one_top_p_0.9"])
def test_sample_rows_equals_the_parent_sampler_at_gpt2_vocabulary(case):
    v = 50304
    top_k, top_p, live, sorts = _cases(v)[case]
    want, got, took = _both(_logits(v, 3), _keys(7), top_k, top_p, live)
    np.testing.assert_array_equal(got, want)
    assert took is sorts
    # the filters bite at this size: greedy rows are their argmax
    if case == "greedy_and_default":
        np.testing.assert_array_equal(
            got[::2], np.asarray(_logits(v, 3)).argmax(-1)[::2])


@pytest.mark.parametrize("filt", ["top_k", "top_p"])
def test_a_filtering_row_that_is_not_live_switches_no_sort_on(filt):
    """A finished row's stale parameters: the plain branch runs, and the
    LIVE rows' tokens are the oracle's (the dead row's token is
    discarded by the decode step, ``where(act, nxt, tok)``)."""
    v = 211
    top_k, top_p = [v] * S, [1.0] * S
    if filt == "top_k":
        top_k[2] = 3
    else:
        top_p[2] = 0.2
    live = np.ones(S, bool)
    live[2] = False
    want, got, took = _both(_logits(v, 4), _keys(9), top_k, top_p, live)
    assert took is False
    np.testing.assert_array_equal(got[live], want[live])
    # and it does switch them on once the row is live
    assert _both(_logits(v, 4), _keys(9), top_k, top_p)[2] is True


def test_no_live_row_sorts_nothing():
    v = 211
    took = _both(_logits(v), _keys(1), [5] * S, [0.5] * S,
                 np.zeros(S, bool))[2]
    assert took is False


@pytest.mark.parametrize("seed", range(4))
def test_ties_at_a_greedy_rows_maximum_break_as_they_did(seed):
    """Several places hold the maximum exactly: ``categorical`` decides
    among them, by the key, in both branches alike."""
    v = 211
    lg = np.asarray(_logits(v, seed)).copy()
    top = lg.max(axis=-1, keepdims=True)
    ties = np.random.default_rng(seed).integers(0, v, (S, 5))
    np.put_along_axis(lg, ties, top, axis=-1)
    want, got, took = _both(jnp.asarray(lg), _keys(20 + seed), [1] * S,
                            [1.0] * S)
    np.testing.assert_array_equal(got, want)
    assert took is False
    held = np.take_along_axis(lg, got[:, None], axis=-1)
    np.testing.assert_array_equal(held, top)
    # the key decides, not the lowest index: some row leaves its first tie
    assert (got != lg.argmax(-1)).any()


@pytest.mark.parametrize("poison", ["all_nan", "one_nan", "pos_inf",
                                    "neg_inf", "all_neg_inf"])
@pytest.mark.parametrize("greedy", [False, True], ids=["default", "greedy"])
def test_non_finite_rows_sample_what_they_did(poison, greedy):
    """The quarantine reads the logits, not the token (``serve_defs``:
    ``bad``), but a poisoned row's token is still today's."""
    v = 211
    lg = np.asarray(_logits(v, 6)).copy()
    if poison == "all_nan":
        lg[1] = np.nan
    elif poison == "one_nan":
        lg[1, 17] = np.nan
    elif poison == "pos_inf":
        lg[1, [5, 90]] = np.inf
    elif poison == "neg_inf":
        lg[1, ::2] = -np.inf
    else:
        lg[1] = -np.inf
    top_k = [v] * S
    if greedy:
        top_k[1] = 1
    want, got, took = _both(jnp.asarray(lg), _keys(30), top_k, [1.0] * S)
    np.testing.assert_array_equal(got, want)
    assert took is False


@pytest.mark.parametrize("case", ["greedy_and_default", "one_top_k_40",
                                  "top_k_and_top_p_rows"])
def test_positions_of_a_slot_share_its_parameters(case):
    """The speculative program's layout: logits [S, G, V], keys
    [S, G, 2], one set of parameters a slot."""
    v, g = 97, 3
    top_k, top_p, _live, sorts = _cases(v)[case]
    top_k = [min(k, 20) if 1 < k < v else k for k in top_k]
    lg = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (S, g, v))
    keys = _keys(40, (S, g))
    args = (jnp.asarray(TEMPS, jnp.float32), jnp.asarray(top_k, jnp.int32),
            jnp.asarray(top_p, jnp.float32))
    row = jax.vmap(parent_sample_logits, in_axes=(0, 0, None, None, None))
    want = jax.jit(jax.vmap(row))(lg, keys, *args)
    got, took = jax.jit(sample_rows)(lg, keys, *args, jnp.ones(S, bool))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert bool(took) is sorts


@pytest.mark.parametrize("top_k,top_p,sorts", [
    (None, None, 0), (5, None, 1), (None, 0.8, 1), (5, 0.8, 2)])
def test_sample_logits_traces_no_sort_a_static_none_does_not_need(
        top_k, top_p, sorts):
    """``generate_fast``'s default path sorts nothing; the tokens are the
    parent's for every static combination."""
    lg, key = _logits(211, 8), jax.random.PRNGKey(3)
    jaxpr = jax.make_jaxpr(
        lambda x, k: sample_logits(x, k, 0.8, top_k, top_p))(lg, key)
    assert _sorts(jaxpr.jaxpr) == sorts
    np.testing.assert_array_equal(
        np.asarray(sample_logits(lg, key, 0.8, top_k, top_p)),
        np.asarray(parent_sample_logits(lg, key, 0.8, top_k, top_p)))


def test_the_gate_is_a_conditional_with_the_sorts_in_one_branch():
    """Lowered for any backend: one ``cond`` on a scalar predicate, both
    sorts inside it, none beside it. (The compiled program for the chip:
    ``tests/test_chip_compile.py``.)"""
    v = 211
    args = (_logits(v), _keys(1), jnp.ones(S), jnp.full(S, v, jnp.int32),
            jnp.ones(S), jnp.ones(S, bool))
    jaxpr = jax.make_jaxpr(sample_rows)(*args).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1 and conds[0].invars[0].aval.shape == ()
    assert _sorts(jaxpr) == 2
    assert sorted(_sorts(b.jaxpr)
                  for b in conds[0].params["branches"]) == [0, 2]
