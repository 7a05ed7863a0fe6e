"""The engine's ONE cache manager over pages that hold latents
(``gym_tpu/models/kimi_k2.py``: one array a layer, a row of ``[c_kv ;
k_rope]`` a position, no heads): page plans, the prefix cache and
copy-on-write, park / resume / ``release_parked``, the scrub and
``kv_pool_bytes`` are the paged models' own, run unchanged, and what they
serve is still the reference's. A prefix served from latent pages is the
one case in which a PREFILL (the expanded form) attends to a past it did
not write: the pages another request left. Tiny float32 model on the
CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.serve import engine as engine_mod
from gym_tpu.serve.engine import (InferenceEngine, NoFreeBlocksError,
                                  SamplingParams, fit_pool, row_cache)
from perfbench import weights_kimi
from perfbench.kinds import closed_kimi
from perfbench.references import kimi_k2 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# as tests/test_kimi_k2.py: two orders of float32 additions
F32_TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "kimi-k2.7-code.json")) as f:
        config = json.load(f)
    sizes = {**config, **config["rehearse"], "dtype": "float32"}
    cfg = dataclasses.replace(closed_kimi.model_config(sizes),
                              prefill_rows=16)
    return sizes, cfg, weights_kimi.make_params(sizes, 3)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n)


def _run(eng, prompt, n_new, seed=1):
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new,
                                                top_k=1, seed=seed))
    toks = [ev.token]
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    return toks


def _is_reference(sizes, params, prompt, toks):
    gaps = ref.served_gaps(params, sizes, list(prompt), toks,
                           pad_multiple=32)
    return gaps.max() < F32_TOL


def test_the_manager_counts_pages_of_latents_as_any_pages(model):
    """A page model to the manager: pages that grow with the row, the
    default pool, the copy-on-write spare, and the bytes of one array a
    layer."""
    _sizes, cfg, params = model
    assert not row_cache(cfg)
    eng = InferenceEngine(params, cfg, num_slots=2, page_size=4)
    assert type(eng._alloc) is engine_mod.BlockAllocator
    assert (eng.page_size, eng.max_blocks, eng.kv_pages, eng._cow_room) == \
        (4, 32, 2 + 2 * 32, 1)
    assert fit_pool(16, 128, 18, config=cfg) == (16, 18)
    leaves = jax.tree.leaves(eng._cache)
    assert len(leaves) == cfg.num_hidden_layers
    assert eng.kv_pool_bytes() == {
        "payload": sum(int(x.nbytes) for x in leaves), "scales": 0}
    a = eng.admit_nowait(_prompt(20, 1), SamplingParams(max_new_tokens=6,
                                                        top_k=1))
    held_a = eng._alloc.in_use()
    eng.admit_nowait(_prompt(5, 2), SamplingParams(max_new_tokens=6,
                                                   top_k=1))
    assert (held_a, eng._alloc.in_use() - held_a) == (
        max(20 + 6, 32) // 4, -(-(5 + 6) // 4))
    eng.release(a)
    while eng._active.any():
        eng.step()
    assert eng._alloc.in_use() == 0


@pytest.mark.parametrize("plen,shared", [(16, 16), (41, 24), (70, 64)],
                         ids=["whole_prompt", "a_prefix_and_a_suffix",
                              "a_suffix_of_one_pass"])
def test_a_prefix_is_served_from_latent_pages_and_equals_the_reference(
        model, plen, shared):
    """A prompt, then a second that shares its first ``shared`` tokens.
    The second is prefilled from position ``shared`` (or, where all of
    it is resident, from its last token on a copy-on-write page): its
    expanded attend reads the latents the FIRST request's prefill wrote
    (gathered from the pages, expanded a head), and what it serves is
    the reference's. The prefix hit is counted; the copy moves all the
    layers' arrays."""
    sizes, cfg, params = model
    eng = InferenceEngine(params, cfg, num_slots=1, page_size=4)
    first = _prompt(plen, 80 + plen)
    toks = _run(eng, first, 5)
    assert _is_reference(sizes, params, first, toks)
    second = np.concatenate([first[:shared], _prompt(plen - shared, 7)])
    assert eng.admit_probe(second, SamplingParams())[1] == shared // 4
    fed = eng.stats.prefill_tokens
    toks2 = _run(eng, second, 6)
    # only the suffix is fed (its bucket's worth), not the prompt
    assert eng.stats.prefill_tokens - fed == engine_mod.prompt_bucket(
        max(plen - shared, 1), eng.block_size)
    assert eng.stats.prefix_hit_blocks == shared // 4
    assert _is_reference(sizes, params, second, toks2)
    if shared == plen:
        assert toks2[:5] == toks


def test_park_then_another_rows_steps_then_resume_continues_identically(
        model):
    """A row parked after three steps keeps its pages pinned and
    untouched while another row decodes in its slot; resumed, it goes on
    with the tokens of the run that was never parked. With the pool
    spent an admission waits (``NoFreeBlocksError``) and leaves the
    counts as they were; ``release_parked`` gives the pages back."""
    sizes, cfg, params = model
    prompt, n_new = _prompt(37, 5), 12
    sp = SamplingParams(max_new_tokens=n_new, top_k=1)
    want = _run(InferenceEngine(params, cfg, num_slots=1, page_size=4),
                prompt, n_new)
    eng = InferenceEngine(params, cfg, num_slots=1, page_size=4,
                          kv_pages=1 + 32 + 8)
    slot, ev = eng.admit(prompt, sp)
    toks = [ev.token]
    for _ in range(3):
        toks += [e.token for e in eng.step()]
    parked = eng.park(slot)
    pages = [int(p) for p in parked.block_table if p]
    before = [np.asarray(c[jnp.asarray(pages)])
              for c in jax.tree.leaves(eng._cache)]
    held = eng._alloc.in_use()
    assert held == len(pages) > 0
    assert len(_run(eng, _prompt(20, 6), 7)) == 7       # the same slot
    after = [np.asarray(c[jnp.asarray(pages)])
             for c in jax.tree.leaves(eng._cache)]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(NoFreeBlocksError):
        eng.admit_nowait(_prompt(60, 4), SamplingParams(max_new_tokens=60))
    assert eng._alloc.in_use() == held
    slot = eng.resume(parked)
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert toks == want
    assert _is_reference(sizes, params, prompt, toks)
    assert eng.stats.preemptions == 1 and eng.stats.resumes == 1
    slot, _ev = eng.admit(_prompt(9, 8), sp)
    dropped = eng.park(slot)
    eng.release_parked(dropped)
    eng.release_parked(dropped)               # idempotent
    assert eng._alloc.in_use() == 0


def test_the_scrub_writes_the_null_pages_zeros_over_every_layers_array(
        model):
    """NaNs planted in a live row's pages: the row is quarantined at its
    next step, its pages are written over with the null page's zeros in
    every layer's array before they are freed, and their next owner is
    served as the reference says."""
    sizes, cfg, params = model
    eng = InferenceEngine(params, cfg, num_slots=1, page_size=4)
    prompt = _prompt(21, 40)
    slot, _ev = eng.admit(prompt, SamplingParams(max_new_tokens=6, top_k=1))
    pages = [int(p) for p in eng._bt[slot] if p]
    eng._cache = jax.tree.map(
        lambda x: x.at[jnp.asarray(pages)].set(jnp.nan), eng._cache)
    assert all(e.poisoned for e in eng.step())
    assert eng.stats.quarantined == 1 and eng._alloc.in_use() == 0
    for leaf in jax.tree.leaves(eng._cache):
        assert not np.asarray(leaf[jnp.asarray([0] + pages)]).any()
    toks = _run(eng, prompt, 6)
    assert _is_reference(sizes, params, prompt, toks)


def test_speculative_verify_takes_the_absorbed_form_and_serves_the_same(
        model):
    """``spec_tokens`` 3: a verify scores four tokens a row in one call
    without ``last_pos``, so it takes the absorbed form over the pages;
    rejected drafts are rewound by the cursor alone (their latents lie
    past it, causally masked until overwritten), and the stream is the
    plain engine's."""
    _sizes, cfg, params = model
    prompt = np.tile(_prompt(6, 3), 5)          # repeats: drafts match
    want = _run(InferenceEngine(params, cfg, num_slots=1, page_size=4),
                prompt, 14)
    eng = InferenceEngine(params, cfg, num_slots=1, page_size=4,
                          spec_tokens=3)
    assert _run(eng, prompt, 14) == want
    assert eng.stats.spec_drafted > 0
