"""The engine's ONE cache manager with two kinds of per-row cache side by
side in one process: a model whose row is a run of pages of keys and
values that grows (``keye_vl2.py``; ``cohere2_moe.py`` for the programs'
text) and a model whose row is one block of recurrent state of a fixed
size (``brumby.py``: ``fixed_row_cache``). One allocator, one refcount,
one ``kv_pool_bytes`` / fill, one set of programs a model: the state
model's page is the whole row, so that a block is a page and the page
machinery carries over (allocator counts, park / resume /
``release_parked``, the scrub); what a state cannot do is asked of the
config and left out (prefix hits, registration, the copy-on-write spare,
speculation). And the page models' programs lower to the text they had
before this model came (sha256 of ``lower().as_text()``, taken on the
parent commit of PR 33). Tiny float32 models on the CPU."""

import dataclasses
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from gym_tpu.programs import serve_defs
from gym_tpu.serve import engine as engine_mod
from gym_tpu.serve.engine import (InferenceEngine, NoFreeBlocksError,
                                  SamplingParams, fit_pool, row_cache)
from perfbench import weights_brumby, weights_keye
from perfbench.kinds import closed_brumby, closed_keye, closed_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sizes(name, **over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"], **over}


@pytest.fixture(scope="module")
def state_model():
    sizes = _sizes("brumby-14b-base")
    return (closed_brumby.model_config(sizes),
            weights_brumby.make_params(sizes, 3))


@pytest.fixture(scope="module")
def page_model():
    sizes = _sizes("keye-vl2-30b-a3b", dtype="float32")
    cfg = dataclasses.replace(closed_keye.model_config(sizes),
                              attn_query_block=16, attn_key_block=8)
    return cfg, weights_keye.make_params(sizes, 3)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n)


def _run(eng, prompt, n_new, seed=1):
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new,
                                                top_k=1, seed=seed))
    toks = [ev.token]
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    return toks


def test_one_manager_counts_blocks_for_one_model_and_pages_for_the_other(
        state_model, page_model):
    """Both engines in one process, the same allocator class, the same
    observables. The page model: page 4, 32 table entries a row, the
    default pool ``2 + slots x 32`` pages, a row holds the pages its
    prompt's bucket and its output need. The state model: whatever
    ``page_size`` is asked the page is the row (128), one table entry,
    the default pool ``2 + slots`` blocks (null, a row a slot, a spare
    for a parked row), a row holds one block whatever its length."""
    s_cfg, s_params = state_model
    p_cfg, p_params = page_model
    assert row_cache(s_cfg) and not row_cache(p_cfg)
    pages = InferenceEngine(p_params, p_cfg, num_slots=2, page_size=4)
    state = InferenceEngine(s_params, s_cfg, num_slots=2, page_size=4)
    assert type(pages._alloc) is type(state._alloc) is \
        engine_mod.BlockAllocator
    assert (pages.page_size, pages.max_blocks, pages.kv_pages) == \
        (4, 32, 2 + 2 * 32)
    assert (state.page_size, state.max_blocks, state.kv_pages) == \
        (128, 1, 2 + 2)
    assert state._bt.shape == (2, 1) and pages._bt.shape == (2, 32)
    # bytes: every array of the cache tree, whichever model's
    for eng in (pages, state):
        assert eng.kv_pool_bytes() == {
            "payload": sum(int(x.nbytes)
                           for x in jax.tree.leaves(eng._cache)),
            "scales": 0}
    assert state.kv_pool_bytes()["payload"] == \
        4 * state.config.state_bytes_per_row()
    # a long and a short row: pages grow with the row, blocks do not
    for eng, want in ((pages, (max(20 + 6, 32) // 4, -(-(5 + 6) // 4))),
                      (state, (1, 1))):
        a = eng.admit_nowait(_prompt(20, 1), SamplingParams(
            max_new_tokens=6, top_k=1))
        held_a = eng._alloc.in_use()
        eng.admit_nowait(_prompt(5, 2), SamplingParams(max_new_tokens=6,
                                                       top_k=1))
        assert (held_a, eng._alloc.in_use() - held_a) == want
        assert eng.stats.kv_blocks_in_use == eng._alloc.in_use()
        fill = eng.stats.kv_blocks_in_use / eng.kv_pages   # kv_pool_fill
        assert 0 < fill < 1
        eng.release(a)
        assert eng._alloc.in_use() == want[1]
        while eng._active.any():
            eng.step()
        assert eng._alloc.in_use() == 0


def test_fit_pool_counts_blocks_for_a_state_model(state_model, page_model):
    s_cfg, _p = state_model
    p_cfg, _q = page_model
    assert fit_pool(16, 128, 18, config=s_cfg) == (128, 18)
    assert fit_pool(16, 128, None, config=s_cfg) == (128, None)
    # a page model: the page fitted to the row, the pool scaled to as
    # many tokens (as before)
    assert fit_pool(16, 128, 18, config=p_cfg) == (16, 18)
    assert fit_pool(12, 128, 16, config=p_cfg) == fit_pool(12, 128, 16) \
        == (8, 24)


def test_no_prefix_is_served_from_a_state_and_none_is_registered(
        state_model, page_model):
    """The same prompt twice. The page model serves the second from the
    first's resident pages (copy-on-write of the last block, a one-token
    prefill); the state model finds nothing, registers nothing, keeps no
    copy-on-write spare, and prefills the whole prompt again."""
    s_cfg, s_params = state_model
    p_cfg, p_params = page_model
    prompt = _prompt(16, 80)
    pages = InferenceEngine(p_params, p_cfg, num_slots=1, page_size=4)
    first = _run(pages, prompt, 5)
    assert pages._alloc.cached() == 4 and pages._cow_room == 1
    assert pages.admit_probe(prompt, SamplingParams())[1] == 4
    fed = pages.stats.prefill_tokens
    assert _run(pages, prompt, 5) == first
    assert pages.stats.prefill_tokens - fed == 1
    assert pages.stats.prefix_hit_blocks == 4

    state = InferenceEngine(s_params, s_cfg, num_slots=1, kv_pages=2)
    assert state._cow_room == 0            # null block + one row: enough
    first = _run(state, prompt, 5)
    assert state._walk_prefix(prompt) == ([], [])
    assert state._alloc.cached() == 0
    assert state.admit_probe(prompt, SamplingParams()) == (True, 0)
    fed = state.stats.prefill_tokens
    assert _run(state, prompt, 5) == first
    assert state.stats.prefill_tokens - fed == 16
    assert state.stats.prefix_hit_blocks == 0
    assert state.stats.kv_blocks_cached == 0
    with pytest.raises(ValueError, match="kv_pages=1 too small"):
        InferenceEngine(s_params, s_cfg, num_slots=1, kv_pages=1)
    with pytest.raises(ValueError, match="cannot be rewound"):
        InferenceEngine(s_params, s_cfg, num_slots=1, spec_tokens=1)


def test_park_resume_and_release_parked_pin_a_block_not_pages(state_model):
    """Two slots, two blocks beside the null one. A parked row's block
    stays pinned (refcount 1, nothing copied); with the other block taken
    the pool is spent though a slot is free, and an admission waits
    (``NoFreeBlocksError``: the scheduler's retry) and leaves the counts
    as they were; resumed, the row continues as if never parked;
    ``release_parked`` gives a parked row's block back."""
    cfg, params = state_model
    sp = SamplingParams(max_new_tokens=8, top_k=1)
    want = _run(InferenceEngine(params, cfg, num_slots=2, kv_pages=3),
                _prompt(19, 1), 8)
    eng = InferenceEngine(params, cfg, num_slots=2, kv_pages=3)
    slot, ev = eng.admit(_prompt(19, 1), sp)
    toks = [ev.token] + [e.token for e in eng.step() if e.slot == slot]
    parked = eng.park(slot)
    block = int(parked.block_table[0])
    assert eng._alloc.in_use() == 1 and eng._alloc._ref[block] == 1
    other = eng.admit_nowait(_prompt(9, 2), sp)
    assert eng._alloc.in_use() == 2 and eng._alloc.available() == 0
    assert len(eng.free_slots()) == 1
    with pytest.raises(NoFreeBlocksError):
        eng.admit_nowait(_prompt(7, 4), sp)
    assert eng._alloc.in_use() == 2
    eng.drain()
    eng.release(other)
    slot = eng.resume(parked)
    assert int(eng._bt[slot, 0]) == block
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert toks == want
    assert eng._alloc.in_use() == 0
    assert eng.stats.preemptions == 1 and eng.stats.resumes == 1
    slot, _ev = eng.admit(_prompt(5, 6), sp)
    dropped = eng.park(slot)
    assert eng._alloc.in_use() == 1
    eng.release_parked(dropped)
    eng.release_parked(dropped)               # idempotent
    assert eng._alloc.in_use() == 0


def test_the_scrub_writes_the_null_blocks_zeros_over_both_arrays(
        state_model):
    cfg, params = state_model
    eng = InferenceEngine(params, cfg, num_slots=1, kv_pages=3)
    slot = eng.admit_nowait(_prompt(12, 9), SamplingParams(
        max_new_tokens=4, top_k=1))
    eng.drain()
    block = int(eng._bt[slot, 0])
    layer = eng._cache["state_1"]
    assert sorted(layer) == ["S", "z"]
    assert np.asarray(layer["S"][block]).any()
    eng._scrub_pages(slot)
    for leaf in jax.tree.leaves(eng._cache):
        assert not np.asarray(leaf[block]).any()
        assert not np.asarray(leaf[0]).any()


# sha256 of ``lower().as_text()`` of the page models' programs at their
# rehearsal sizes (page 4, 40 pages, 2 slots; CPU), by this file's own
# recipe. The copy-on-write programs' are the parent commit of PR 33's
# (461f5df); the decode and prefill programs' were taken again at PR 36,
# which changed the one thing they share, the sampler (``sample_rows``:
# a conditional around the sorts, and ``sorted`` in a step's download);
# all four of ``keye_vl2.py``'s were taken again at PR 38, which gave its
# pool of index keys a third axis (``sparse_attention.index_pool_shape``:
# ``[pages, 1, 32]`` here where it was ``[pages, 32]``) and wrote
# ``sortable`` in the int32 arithmetic its decode kernel shares
PARENT_TEXT = {
    "keye-vl2-30b-a3b/decode":
        "98323de8917ceb9a10fd9c19d48ebadac29d513225f5c2c32c9744384b267dfb",
    "keye-vl2-30b-a3b/prefill16":
        "a69ed4dde0fdb55cf1fd636e5b522e311f70e0becf65a6335a39a2390c962e53",
    "keye-vl2-30b-a3b/prefill64":
        "faebfa5a91e6f5d9fbdd5b8dfd99c6be94d3c7d92f6c3a30dd12a3a2796f6362",
    "keye-vl2-30b-a3b/cow":
        "79f28282bbd654508c8319fcdc470543bada6d781229464c79db2389d06edd11",
    "command-a-plus/decode":
        "f775416503389b12c33db5fc8c8afb0a6ef752015fe62076b4dc6509f5839336",
    "command-a-plus/prefill16":
        "c0bc2c2529f86cb356f589c43d93fdf94219eede8817f2efe9f3cb859ce4a592",
    "command-a-plus/prefill64":
        "446e41d89516ea5879bb2b057b3e821dc2d8d005d5c5e50ecd88e8a278fe6250",
    "command-a-plus/cow":
        "d17dfaeaba0c733fcb1123392341728f2f67ce0a2f4e6b2ca4180265c3079043",
}


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_the_page_models_programs_lower_to_the_parents_text(name):
    """``keye_vl2.py`` now takes its norm, projections, rotation and head
    from ``decoder_parts.py`` (shared with ``brumby.py``) and the engine
    asks every config whether its cache is a block a row: neither moved
    an operation of the page models' programs. (Whoever changes what
    every served program runs takes the texts again, and says so
    above.)"""
    model, prog = name.split("/")
    make = {"keye-vl2-30b-a3b": closed_keye.model_config,
            "command-a-plus": closed_model.model_config}[model]
    cfg = dataclasses.replace(make(_sizes(model)).decode_config(),
                              page_size=4, kv_pages=40)
    key = cfg.program_key()
    pdef = {"decode": lambda: serve_defs.paged_decode_def(key, 2, 1),
            "prefill16": lambda: serve_defs.paged_prefill_def(key, 16, 2),
            "prefill64": lambda: serve_defs.paged_prefill_def(key, 64, 2),
            "cow": lambda: serve_defs.cow_def(key)}[prog]()
    text = pdef.builder().lower(*pdef.args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[name]
