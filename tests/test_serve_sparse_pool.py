"""The engine's page machinery over a cache tree of THREE pool arrays a
layer (keys, values and the learned index's keys of
``gym_tpu/models/keye_vl2.py``): the page plan, copy-on-write, the scrub
of a quarantined row's pages, and parking are written against the tree
(``tree.map``), so they carry the third array unchanged. Tiny float32
model on the CPU, pages of 4 positions, 8 keys kept a query."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from perfbench import weights_keye
from perfbench.kinds.closed_keye import model_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 4


@pytest.fixture(scope="module")
def setup():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "keye-vl2-30b-a3b.json")) as f:
        config = json.load(f)
    sizes = {**config, **config["rehearse"], "dtype": "float32"}
    cfg = dataclasses.replace(model_config(sizes), attn_query_block=16,
                              attn_key_block=8)
    return cfg, weights_keye.make_params(sizes, 3)


def _engine(cfg, params, **kw):
    return InferenceEngine(params, cfg, num_slots=2, page_size=PAGE,
                           kv_pages=80, **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n)


def _run(eng, prompt, n_new, seed=1):
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new,
                                                top_k=1, seed=seed))
    toks = [ev.token]
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    return toks


def _layer0(eng):
    c = eng._cache["layers_0"]["self_attn"]
    assert sorted(c) == ["k", "ki", "v"]
    return c


def test_the_cache_tree_has_a_third_pool_array_and_its_bytes_count(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    c = _layer0(eng)
    assert c["k"].shape == c["v"].shape == (80, PAGE, 2 * 16)
    assert c["ki"].shape == (80, 1, PAGE * 8)
    leaves = jax.tree.leaves(eng._cache)
    assert eng.kv_pool_bytes() == {
        "payload": sum(int(x.nbytes) for x in leaves), "scales": 0}


def test_copy_on_write_copies_the_index_keys_with_the_page(setup):
    """A block-aligned resident prompt comes back through copy-on-write:
    one page copy and a one-token prefill. The copy holds the source
    page's keys, values AND index keys (the re-forwarded last token
    rewrites its own position with the same values), the source is
    untouched, and both streams are the first run's."""
    cfg, params = setup
    eng = _engine(cfg, params)
    p16 = _prompt(16, 80)
    first = _run(eng, p16, 6)
    before = jax.tree.map(np.asarray, _layer0(eng))
    fed = eng.stats.prefill_tokens
    slot, ev = eng.admit(p16, SamplingParams(max_new_tokens=6, top_k=1))
    assert eng.stats.prefill_tokens - fed == 1       # CoW: 1-token bucket
    src = next(pg for pg in range(1, 80)
               if np.asarray(before["ki"][pg]).any()
               and pg not in eng._bt[slot].tolist()
               and np.allclose(before["ki"][pg],
                               np.asarray(_layer0(eng)["ki"])[
                                   int(eng._bt[slot, 3])], atol=1e-5))
    dst = int(eng._bt[slot, 3])
    after = jax.tree.map(np.asarray, _layer0(eng))
    for name in ("k", "v", "ki"):
        np.testing.assert_allclose(after[name][dst], before[name][src],
                                   atol=1e-5)
        np.testing.assert_array_equal(after[name][src], before[name][src])
    toks = [ev.token]
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert toks == first


def test_a_quarantined_rows_pages_are_scrubbed_in_all_three_arrays(setup):
    """NaNs planted in a row's pages, in all three arrays: the row is
    quarantined (the index keys alone would not show: a NaN score only
    misplaces the selection), the quarantine writes over its pages in
    every array before they are freed, index keys included, and the same
    prompt is served again as before."""
    cfg, params = setup
    eng = _engine(cfg, params)
    prompt = _prompt(21, 40)
    want = _run(eng, prompt, 6)
    slot, _ev = eng.admit(prompt, SamplingParams(max_new_tokens=6, top_k=1))
    pages = jnp.asarray([int(pg) for pg in eng._bt[slot] if pg])
    eng._cache = jax.tree.map(lambda x: x.at[pages].set(jnp.nan),
                              eng._cache)
    assert not bool(jnp.isfinite(_layer0(eng)["ki"]).all())
    assert all(e.poisoned for e in eng.step())
    assert eng.stats.quarantined == 1
    assert eng._alloc.in_use() == 0
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(eng._cache))
    assert _run(eng, prompt, 6) == want


def test_park_and_resume_carry_the_row_through_all_three_arrays(setup):
    """A row parked mid-generation keeps its pages (no copy of any pool
    array); another request runs in between; resumed, it continues with
    the tokens of the run that was never parked."""
    cfg, params = setup
    prompt = _prompt(26, 7)
    want = _run(_engine(cfg, params), prompt, 10)
    eng = _engine(cfg, params)
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=10, top_k=1))
    toks = [ev.token]
    for _ in range(3):
        toks += [e.token for e in eng.step() if e.slot == slot]
    held = {name: np.asarray(x) for name, x in _layer0(eng).items()}
    pages = [int(pg) for pg in eng._bt[slot] if pg]
    parked = eng.park(slot)
    _run(eng, _prompt(33, 8), 5)                 # someone else's turn
    for name, x in _layer0(eng).items():         # the parked pages stood
        np.testing.assert_array_equal(np.asarray(x)[pages][:6],
                                      held[name][pages][:6])
    slot = eng.resume(parked)
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert toks == want
    assert eng._alloc.in_use() == 0
