"""Paged prefix-shared KV cache + speculative decoding (ISSUE 7).

Oracles:
- TOKEN EXACTNESS: the paged engine (speculation off) emits tokens
  IDENTICAL to ``generate_fast`` for the same seed/sampling — including
  padded-bucket prompts, prompts served THROUGH shared prefix blocks,
  the copy-on-write full-hit path, and a real restored checkpoint. The
  paged attend runs the same static-[block_size] reductions and masks
  as ``generate_fast``'s, so the streams match bitwise. That is the contract
  OFF the TPU, where these tests run and the attend takes its gather
  path. On a TPU with a float32 pool the attend is the Pallas page walk
  (``gym_tpu/ops/paged_attention.py``): the same bf16-rounded products
  over every live position, float32 sums in another order (128
  positions at a time under a running maximum), so there the contract
  is a tolerance: every logit within 0.15 of the gather path's
  (``chip_smoke.py:PAGED_LOGIT_TOL``; 0.049 at most was measured on a
  v5e at GPT-2 base width, where the logits' standard deviation is
  0.55). ``tests/test_paged_attention.py`` holds the kernel to the
  gather path at 1e-5 under the interpreter, where both multiply in
  float32.
- SPECULATIVE EXACTNESS: the speculative engine equals the
  non-speculative engine token-for-token — pinned greedy (the ISSUE 7
  acceptance bar) AND under full sampling (the deterministic-draft
  scheme samples every position from the true conditional with the
  request's own key schedule, so drafts only decide how many samples a
  dispatch keeps).
- BOUNDED COMPILATION: paged prefill stays under the
  ``⌈log2(block_size)⌉ + 1`` bucket bound; decode/draft-verify are one
  program each.
- ALLOCATOR: refcounts, LRU eviction of refcount-0 cached blocks,
  double-free detection, pool-exhaustion requeue (requests wait, never
  fail), and release returning every non-cached block.
"""

import os
import threading

import numpy as np
import pytest

import jax

from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
from gym_tpu.ops import paged_attention
from gym_tpu.serve.engine import (BlockAllocator, InferenceEngine,
                                  NoFreeBlocksError, SamplingParams,
                                  fit_page_size, fit_pool,
                                  max_prefill_buckets)
from gym_tpu.serve.metrics import ServeMetrics, read_headline
from gym_tpu.serve.scheduler import RequestStatus, Scheduler


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64), train=False)["params"]
    return cfg, model, params


def _prompt(n, seed, vocab=48):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, vocab))


def _run_one(eng, prompt, sp):
    """Admit one request and drain it; returns its token stream."""
    slot, ev = eng.admit(prompt, sp)
    toks = [ev.token]
    while not ev.finished:
        evs = [e for e in eng.step() if e.slot == slot]
        toks.extend(e.token for e in evs)
        ev = evs[-1]
    return toks


def _drain(sched, handles, limit=5000):
    for _ in range(limit):
        if all(h.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for h in handles):
            return
        sched.step()
    raise AssertionError("scheduler did not drain")


# -- paged-engine token exactness ------------------------------------------


@pytest.mark.parametrize("plen,mnew,kw", [
    (8, 10, dict(temperature=0.8, top_k=5, seed=3)),
    (11, 7, dict(top_p=0.9, seed=5)),          # padded prefill bucket
    (16, 5, dict(top_k=1, seed=2)),            # greedy, block-aligned
])
def test_paged_matches_generate_fast(setup, plen, mnew, kw):
    cfg, model, params = setup
    prompt = _prompt(plen, plen)
    ref = generate_fast(params, cfg, prompt[None], mnew, **kw)
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8)
    got = _run_one(eng, prompt, SamplingParams(max_new_tokens=mnew, **kw))
    assert got == ref[0, plen:].tolist()


def test_prefix_sharing_admits_without_reprefill_and_stays_exact(setup):
    """Two prompts sharing a 24-token prefix (3 pages of 8): the second
    admit reuses the resident blocks (prefix_hit_blocks ticks, prefill
    shrinks to the suffix bucket) and BOTH streams equal their solo
    generate_fast runs — sharing is copy-free AND bit-exact."""
    cfg, model, params = setup
    shared = _prompt(24, 70)
    pa = np.concatenate([shared, _prompt(4, 71)])
    pb = np.concatenate([shared, _prompt(4, 72)])
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8)
    ra = _run_one(eng, pa, SamplingParams(max_new_tokens=6,
                                          temperature=0.8, top_k=5,
                                          seed=1))
    assert eng.stats.prefix_hit_blocks == 0      # cold cache: no hits yet
    tokens_first = eng.stats.prefill_tokens
    rb = _run_one(eng, pb, SamplingParams(max_new_tokens=6,
                                          temperature=0.8, top_k=5,
                                          seed=2))
    assert eng.stats.prefix_hit_blocks == 3
    # 28-token prompt, 24 shared -> only the 4-token suffix (bucket 4)
    # is prefilled; the PR-4 engine would redo all 28 (bucket 32)
    assert eng.stats.prefill_tokens - tokens_first == 4
    assert ra == generate_fast(params, cfg, pa[None], 6, temperature=0.8,
                               top_k=5, seed=1)[0, 28:].tolist()
    assert rb == generate_fast(params, cfg, pb[None], 6, temperature=0.8,
                               top_k=5, seed=2)[0, 28:].tolist()


def test_full_block_aligned_hit_takes_cow_path(setup):
    """A fully block-aligned resident prompt re-admits through
    copy-on-write: one page copy + a 1-token prefill (the last prompt
    token is re-forwarded for the first-token logits), and the stream
    stays exact. The shared source page is NOT perturbed: a third
    request over the same prefix is exact too."""
    cfg, model, params = setup
    p16 = _prompt(16, 80)
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8)
    r1 = _run_one(eng, p16, SamplingParams(max_new_tokens=5, top_k=4,
                                           seed=3))
    before = eng.stats.prefill_tokens
    r2 = _run_one(eng, p16, SamplingParams(max_new_tokens=5, top_k=4,
                                           seed=4))
    assert eng.stats.prefill_tokens - before == 1     # CoW: 1-token bucket
    assert eng.stats.prefix_hit_blocks == 2           # 1 shared + 1 CoW'd
    r3 = _run_one(eng, p16, SamplingParams(max_new_tokens=5, top_k=4,
                                           seed=3))
    for r, seed in ((r1, 3), (r2, 4), (r3, 3)):
        assert r == generate_fast(params, cfg, p16[None], 5, top_k=4,
                                  seed=seed)[0, 16:].tolist()


def test_paged_concurrent_churn_isolated_and_blocks_freed(setup):
    """5 mixed requests through 2 slots over ONE shared pool: every
    stream equals its solo generate_fast run (pages cannot leak across
    slots) and the pool drains back to zero live blocks."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, decode_chunk=4,
                          paged=True, page_size=8)
    sched = Scheduler(eng, max_queue=8)
    handles, wants = [], []
    for i, (plen, mnew) in enumerate([(5, 7), (9, 12), (3, 4), (17, 9),
                                      (8, 15)]):
        prompt = _prompt(plen, 100 + i)
        ref = generate_fast(params, cfg, prompt[None], mnew,
                            temperature=0.9, top_k=7, top_p=0.95, seed=i)
        wants.append(ref[0, plen:].tolist())
        handles.append(sched.submit(prompt, SamplingParams(
            max_new_tokens=mnew, temperature=0.9, top_k=7, top_p=0.95,
            seed=i)))
    _drain(sched, handles)
    for h, want in zip(handles, wants):
        assert h.result(timeout=1) == want
    assert eng.stats.kv_blocks_in_use == 0


def test_paged_restored_checkpoint_serves_exactly(setup, tmp_path):
    """The paged oracle holds on a REAL restored checkpoint, not just
    hand-built params (ISSUE 7 acceptance)."""
    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset
    from gym_tpu.serve.load import load_for_serving
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy

    cfg = GPTConfig(block_size=32, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 48, (64, 33))
    ds = ArrayDataset(toks[:, :-1].astype(np.int64),
                      toks[:, 1:].astype(np.int64))
    Trainer(GPT(cfg), ds).fit(
        strategy=SimpleReduceStrategy(optim_spec=OptimSpec("adamw",
                                                           lr=1e-3)),
        num_nodes=1, max_steps=4, batch_size=4, val_size=0,
        val_interval=0, show_progress=False, seed=1,
        checkpoint_interval=4, save_dir=str(tmp_path / "ckpts"),
        run_name="paged", log_dir=str(tmp_path / "logs"))
    params, lcfg, _ = load_for_serving(str(tmp_path / "ckpts" / "paged"))
    prompt = _prompt(9, 4, vocab=lcfg.vocab_size)
    ref = generate_fast(params, lcfg, prompt[None], 8, temperature=0.7,
                        top_k=8, seed=2)
    eng = InferenceEngine(params, lcfg, num_slots=2, paged=True,
                          page_size=8)
    got = _run_one(eng, prompt, SamplingParams(max_new_tokens=8,
                                               temperature=0.7, top_k=8,
                                               seed=2))
    assert got == ref[0, 9:].tolist()


def test_paged_teacher_forcing_logits_match_dense_forward(setup):
    """override_tokens still forces a chunk-1 program on the paged
    engine; per-step logits equal the dense forward."""
    cfg, model, params = setup
    seq = _prompt(12, 9)[None]
    full = np.asarray(model.apply({"params": params}, seq, train=False))
    eng = InferenceEngine(params, cfg, num_slots=2, decode_chunk=4,
                          paged=True, page_size=8)
    slot, _ = eng.admit(seq[0, :5], SamplingParams(max_new_tokens=12))
    eng.step(override_tokens={slot: int(seq[0, 5])})
    np.testing.assert_allclose(eng.last_logits[slot], full[0, 5],
                               rtol=1e-4, atol=1e-5)


# -- speculative decoding --------------------------------------------------


def test_speculative_greedy_exact_vs_nonspeculative(setup):
    """The ISSUE 7 pinned oracle: speculative greedy == non-speculative
    greedy == generate_fast greedy."""
    cfg, model, params = setup
    prompt = _prompt(9, 13)
    ref = generate_fast(params, cfg, prompt[None], 14, top_k=1,
                        seed=6)[0, 9:].tolist()
    plain = InferenceEngine(params, cfg, num_slots=2, paged=True,
                            page_size=8, decode_chunk=2)
    spec = InferenceEngine(params, cfg, num_slots=2, paged=True,
                           page_size=8, decode_chunk=2, spec_tokens=4)
    sp = SamplingParams(max_new_tokens=14, top_k=1, seed=6)
    got_plain = _run_one(plain, prompt, sp)
    got_spec = _run_one(spec, prompt, sp)
    assert got_plain == ref
    assert got_spec == ref
    # greedy self-drafting on a tiny model actually accepts drafts —
    # the speedup lever is real, not vacuously exact
    assert spec.stats.spec_drafted > 0
    assert spec.stats.spec_accepted > 0
    assert spec.stats.spec_accept_rate() > 0


@pytest.mark.parametrize("kw", [
    dict(temperature=0.9, top_k=7, seed=5),
    dict(temperature=1.1, top_p=0.9, seed=8),
])
def test_speculative_sampling_exact_vs_nonspeculative(setup, kw):
    """Stronger than the acceptance bar: the deterministic-draft scheme
    is exact for EVERY sampling configuration (each position is sampled
    from the true conditional with the request's own fold_in key), not
    just greedy."""
    cfg, model, params = setup
    prompt = _prompt(10, 21)
    ref = generate_fast(params, cfg, prompt[None], 12,
                        **kw)[0, 10:].tolist()
    spec = InferenceEngine(params, cfg, num_slots=2, paged=True,
                           page_size=8, decode_chunk=3, spec_tokens=3)
    got = _run_one(spec, prompt, SamplingParams(max_new_tokens=12, **kw))
    assert got == ref


def test_speculative_eos_mid_chunk(setup):
    """EOS inside an accepted draft run stops the request at the EOS
    token (inclusive), exactly like non-speculative decoding."""
    cfg, model, params = setup
    prompt = _prompt(9, 3)
    ref = generate_fast(params, cfg, prompt[None], 12, temperature=0.9,
                        top_k=7, seed=1)[0, 9:].tolist()
    eos = ref[4]
    assert eos not in ref[:4]
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8, decode_chunk=4, spec_tokens=3)
    got = _run_one(eng, prompt, SamplingParams(
        max_new_tokens=12, temperature=0.9, top_k=7, seed=1,
        eos_token=eos))
    assert got == ref[:5]


# -- one cache: the pool (ISSUE 29) ------------------------------------------


def test_engine_with_defaults_builds_the_pool(setup):
    """An engine that names no cache gets the page pool: page 16, the
    null page, a window a slot and the copy-on-write page; off the TPU
    its attend is the gather path and its stream is ``generate_fast``'s."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=3)
    assert (eng.page_size, eng.max_blocks) == (16, cfg.block_size // 16)
    assert eng.kv_pages == 2 + 3 * eng.max_blocks
    assert eng.config.page_size == 16
    assert eng.config.kv_pages == eng.kv_pages
    assert eng.kv_blocks_capacity_effective == eng.kv_pages - 1
    assert eng.attend_path == paged_attention.GATHER
    prompt = _prompt(11, 7)
    kw = dict(temperature=0.8, top_k=5, seed=3)
    got = _run_one(eng, prompt, SamplingParams(max_new_tokens=9, **kw))
    ref = generate_fast(params, cfg, prompt[None], 9, **kw)
    assert got == ref[0, 11:].tolist()
    assert eng.stats.paged_kernel_dispatches == 0
    assert eng.stats.prefill_tokens == 16      # bucket(11)
    assert eng.stats.kv_blocks_in_use == 0     # the finished row's pages
    assert eng.stats.spec_accept_rate() is None


def _engine_refuses(setup, **kw):
    cfg, model, params = setup
    InferenceEngine(params, cfg, num_slots=2, **kw)


def _server_refuses(setup):
    from gym_tpu.serve.__main__ import create_server
    cfg, model, params = setup
    create_server(params, cfg, port=0, page_size=0, warmup=False)


def _cli_refuses(module, argv, capsys):
    """The parser's own refusal: exit 2 before a checkpoint is read."""
    import importlib
    with pytest.raises(SystemExit) as exc:
        importlib.import_module(module).main(argv)
    assert exc.value.code == 2
    raise ValueError(capsys.readouterr().err)


@pytest.mark.parametrize("refuses", [
    pytest.param(lambda s, c: _engine_refuses(s, paged=False),
                 id="engine-paged-false"),
    pytest.param(lambda s, c: _engine_refuses(s, paged=False,
                                              spec_tokens=2),
                 id="engine-paged-false-spec"),
    pytest.param(lambda s, c: _engine_refuses(s, page_size=0),
                 id="engine-page-size-0"),
    pytest.param(lambda s, c: _server_refuses(s), id="create-server"),
    pytest.param(lambda s, c: _cli_refuses(
        "gym_tpu.serve.__main__",
        ["--ckpt", "/nonexistent", "--page_size", "0"], c), id="cli"),
    pytest.param(lambda s, c: _cli_refuses(
        "gym_tpu.serve.worker",
        ["--socket", "/nonexistent", "--page_size", "0"], c), id="worker"),
])
def test_paged_false_and_page_size_zero_are_refused(setup, capsys, refuses):
    """The ring is gone: whatever used to select it is refused, with a
    message that names the pool (or the page size's range)."""
    with pytest.raises(ValueError, match="only KV cache|page_size must be"):
        refuses(setup, capsys)


@pytest.mark.parametrize("asked,block,fitted", [
    (16, 64, 16), (16, 40, 10), (16, 1024, 16), (48, 64, 32),
    (7, 64, 4), (16, 17, 1), (128, 64, 64)])
def test_page_size_falls_to_a_divisor_of_block_size(asked, block, fitted):
    assert fit_page_size(asked, block) == fitted
    assert block % fitted == 0 and fitted <= asked
    # a pool size that was given keeps its tokens; none given stays none
    page, pages = fit_pool(asked, block, 9)
    assert page == fitted and (pages - 1) * page < 9 * asked <= pages * page
    assert fit_pool(asked, block) == (fitted, None)


def test_server_serves_a_checkpoint_whose_window_the_page_does_not_divide(
        capsys, tmp_path):
    """``create_server`` used to fall back to the ring here; it serves
    from the pool at the largest page that divides the window."""
    from gym_tpu.serve.__main__ import create_server
    cfg = GPTConfig(block_size=40, vocab_size=48, n_layer=1, n_head=2,
                    n_embd=16, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64), train=False)["params"]
    # 5 pages of 16 are 80 tokens, so 8 pages of 10 (5 of those would be
    # refused: a window is four, beside the null and the copy page)
    handle = create_server(params, cfg, port=0, num_slots=2, page_size=16,
                           kv_pages=5, spec_tokens=2, warmup=False,
                           metrics_dir=str(tmp_path))
    # close() waits for serve_forever to have run
    threading.Thread(target=handle.httpd.serve_forever, daemon=True).start()
    try:
        eng = handle.scheduler.engine
        assert (eng.page_size, eng.max_blocks, eng.spec_tokens) == (10, 4, 2)
        assert eng.kv_pages == 8
        assert ("serving with page_size 10 and kv_pages 8"
                in capsys.readouterr().err)
        prompt = _prompt(6, 1)
        got = handle.scheduler.submit(prompt, SamplingParams(
            max_new_tokens=5, top_k=1))
        ref = generate_fast(params, cfg, prompt[None], 5, top_k=1)
        assert got.result(timeout=120) == ref[0, 6:].tolist()
    finally:
        handle.close(drain_deadline_s=5.0)


def test_no_slot_program_is_defined_or_warmed(setup):
    """Neither the definitions, the auditor's enumeration, an engine's
    warm-up family nor the registry holds a program of the ring."""
    from gym_tpu import programs
    from gym_tpu.analysis.jaxpr_audit import engine_program_defs
    from gym_tpu.programs import serve_defs
    cfg, model, params = setup
    for gone in ("prefill_def", "slot_admit_def", "slot_decode_def",
                 "build_prefill", "build_slot_admit", "build_slot_decode",
                 "SLOT_STATE"):
        assert not hasattr(serve_defs, gone), gone
    eng = InferenceEngine(params, cfg, num_slots=2, decode_chunk=2)
    _run_one(eng, _prompt(6, 1), SamplingParams(max_new_tokens=3))
    paged = ("serve.paged_prefill", "serve.paged_decode", "serve.cow",
             "serve.spec_decode")
    names = ([d.name for d in eng.warmup_defs()]
             + [d.name for d in engine_program_defs()]
             + [n for n in programs.default_registry().keys().values()
                if n.startswith("serve.")])
    assert names and all(n.startswith(paged) for n in names), names
    families = {d.family for d in eng.warmup_defs()}
    assert families == {"serve.paged_prefill", "serve.paged_decode",
                        "serve.cow"}


# -- bounded compilation ---------------------------------------------------


def test_paged_prefill_compile_bound(setup):
    """32 distinct prompt lengths through the paged engine compile at
    most ⌈log2(block_size)⌉ + 1 prefill programs; decode and the fused
    draft/verify are one program each (registry keys cover
    (config, slots, chunk[, γ]) only)."""
    cfg, model, params = setup
    from gym_tpu.programs import compile_counter, default_registry
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8)
    sched = Scheduler(eng, max_queue=64)
    handles = [sched.submit(_prompt(n, 200 + n),
                            SamplingParams(max_new_tokens=2, seed=n))
               for n in range(1, 33)]
    _drain(sched, handles)
    for h in handles:
        assert len(h.result(timeout=1)) == 2
    bound = max_prefill_buckets(cfg.block_size)
    assert eng.stats.prefill_compiles <= bound
    assert len(eng.stats.prefill_buckets) <= bound
    # one decode program per (config, slots, chunk); one spec program
    # per (config, slots, chunk, γ) — engines over the same config
    # resolve to the SAME registry entry (same key, zero new builds)
    builds0 = compile_counter()
    eng2 = InferenceEngine(params, cfg, num_slots=2, paged=True,
                           page_size=8)
    assert eng2._decode_prog.key_hash == eng._decode_prog.key_hash
    s1 = InferenceEngine(params, cfg, num_slots=2, paged=True,
                         page_size=8, spec_tokens=3)
    s2 = InferenceEngine(params, cfg, num_slots=2, paged=True,
                         page_size=8, spec_tokens=3)
    assert s1._spec_prog.key_hash == s2._spec_prog.key_hash
    assert compile_counter() == builds0   # re-acquisition compiles nothing
    names = set(default_registry().keys().values())
    assert any(n.startswith("serve.paged_decode[") for n in names)
    assert any(n.startswith("serve.spec_decode[") for n in names)


# -- allocator semantics ---------------------------------------------------


def test_allocator_refcount_and_free_list():
    al = BlockAllocator(num_pages=5, page_size=4)
    a, b = al.alloc(), al.alloc()
    assert a != b and 0 not in (a, b)
    assert al.in_use() == 2 and al.available() == 2
    al.incref(a)
    al.decref(a)
    assert al.in_use() == 2                  # still referenced once
    al.decref(a)
    assert al.in_use() == 1 and al.available() == 3
    with pytest.raises(ValueError, match="double-freed"):
        al.decref(a)
    al.decref(b)
    assert al.available() == 4


def test_allocator_prefix_cache_lru_eviction():
    """Cached refcount-0 blocks stay resident and are evicted LRU when
    the free list runs dry; a resident block's chain survives a child
    eviction but a parent eviction orphans (and never falsely serves)
    its children."""
    al = BlockAllocator(num_pages=4, page_size=2)      # 3 real pages
    blk = lambda s: s.encode()  # noqa: E731
    p1 = al.alloc()
    c1 = al.register(0, blk("aa"), p1)
    p2 = al.alloc()
    c2 = al.register(c1, blk("bb"), p2)
    al.decref(p1)
    al.decref(p2)
    assert al.cached() == 2 and al.available() == 3
    assert al.lookup(0, blk("aa"))[0] == p1
    assert al.lookup(c1, blk("bb"))[0] == p2
    # exhaust the pool: the third page comes from the free list, the
    # fourth evicts the LRU cached page — "aa" was refreshed by the
    # lookup above, so "bb"... was too (later); evict order follows
    # recency: "aa" then "bb"
    p3 = al.alloc()
    p4 = al.alloc()
    assert {p3, p4} & {p1, p2}               # reused a cached page
    assert al.cached() == 1
    p5 = al.alloc()                          # evicts the last cached page
    assert al.cached() == 0
    with pytest.raises(NoFreeBlocksError):
        al.alloc()                           # everything referenced now
    # "aa" (LRU) was evicted first and can never be falsely served; the
    # orphaned child "bb" chain entry is unreachable from the root walk
    assert al.probe(0, blk("aa")) is None
    al.decref(p3)
    al.decref(p4)
    al.decref(p5)
    assert c2 != c1


def test_pool_exhaustion_queues_instead_of_failing(setup):
    """A pool too small for every slot at once: requests WAIT for blocks
    (NoFreeBlocksError is internal backpressure, not a failure) and all
    complete exactly."""
    cfg, model, params = setup
    # 9 real pages of 8 tokens; each 24+16-token request reserves 5
    # blocks, so two can never run concurrently despite 2 free slots
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8, kv_pages=10)
    sched = Scheduler(eng, max_queue=8)
    handles, wants = [], []
    for i in range(4):
        prompt = _prompt(24, 300 + i)
        ref = generate_fast(params, cfg, prompt[None], 16,
                            temperature=0.9, top_k=7, seed=i)
        wants.append(ref[0, 24:].tolist())
        handles.append(sched.submit(prompt, SamplingParams(
            max_new_tokens=16, temperature=0.9, top_k=7, seed=i)))
    _drain(sched, handles)
    for h, want in zip(handles, wants):
        assert h.result(timeout=1) == want
    assert eng.stats.kv_blocks_in_use == 0
    assert eng.stats.active_slots == 0


def test_undersized_pool_rejected_at_construction(setup):
    """The constructor refuses a pool that couldn't serve even one full
    window (null + window + CoW headroom) — with that floor, EVERY
    request that passes the block_size validation also fits an idle
    pool, so a queued request can wait but never deadlock."""
    cfg, model, params = setup
    with pytest.raises(ValueError, match="kv_pages"):
        InferenceEngine(params, cfg, num_slots=1, paged=True,
                        page_size=8, kv_pages=9)      # needs >= 10
    eng = InferenceEngine(params, cfg, num_slots=4, paged=True,
                          page_size=8, kv_pages=10)   # minimum pool
    # worst-case full-window request still fits the minimal pool
    eng.validate(_prompt(32, 0), SamplingParams(max_new_tokens=32))


def test_paged_nan_quarantine_catches_slot_finishing_mid_chunk(setup):
    """Regression (review): the paged decode redirects a FINISHED row's
    block table to the null page, so the unpaged trick of reading the
    last scanned step's logits cannot witness a poison that struck
    mid-chunk — the programs must LATCH non-finite logits per iteration
    instead. Poison one slot's own pages, let it finish at iteration 2
    of a 4-step chunk: its tokens must come back poisoned (and the
    neighbor slot untouched)."""
    import jax.numpy as jnp

    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8, decode_chunk=4)
    slot, _ = eng.admit(_prompt(8, 1), SamplingParams(max_new_tokens=3))
    other, _ = eng.admit(_prompt(6, 2), SamplingParams(max_new_tokens=8))
    pg = int(eng._bt[slot, 0])
    eng._cache = jax.tree.map(lambda x: x.at[pg].set(jnp.nan), eng._cache)
    evs = eng.step()
    mine = [e for e in evs if e.slot == slot]
    assert mine and all(e.poisoned for e in mine)
    assert eng.stats.quarantined == 1
    assert all(not e.poisoned for e in evs if e.slot == other)
    assert eng.stats.kv_blocks_in_use > 0     # neighbor still holds pages


def test_failed_admission_releases_every_block(setup):
    """Regression (review): an exception inside the paged admission
    (here: an injected prefill fault) must unwind every pinned/allocated
    page — a failed request cannot permanently shrink the pool."""
    from gym_tpu.utils.resilience import faults

    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8)
    # seed the prefix cache so the failing admission also PINS hit pages
    _run_one(eng, _prompt(16, 60), SamplingParams(max_new_tokens=2))
    assert eng.stats.kv_blocks_in_use == 0
    cached_before = eng.stats.kv_blocks_cached
    faults.reset()
    faults.configure("serve.prefill:oserror")
    try:
        with pytest.raises(OSError):
            eng.admit(np.concatenate([_prompt(16, 60), _prompt(4, 61)]),
                      SamplingParams(max_new_tokens=4))
    finally:
        faults.reset()
    assert eng.stats.kv_blocks_in_use == 0
    assert eng.stats.kv_blocks_cached == cached_before
    # the pool still serves a full-window request afterwards
    got = _run_one(eng, _prompt(24, 62), SamplingParams(max_new_tokens=4,
                                                        top_k=3, seed=7))
    assert len(got) == 4


def test_starvation_guard_admits_blocked_head(setup):
    """Regression (review): a large-block-need head request must not be
    starved forever by a stream of small requests that keep the pool
    partially pinned — after `starvation_rounds` skipped rounds the
    scheduler holds admissions until the head fits."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8, kv_pages=10)    # 9 real pages
    sched = Scheduler(eng, max_queue=32, prefix_window=4,
                      starvation_rounds=2)
    # head needs the WHOLE pool (8 blocks); smalls need 2 each, with
    # staggered lengths so the two slots never drain simultaneously
    big = sched.submit(_prompt(40, 1), SamplingParams(max_new_tokens=24,
                                                      seed=1))
    smalls = [sched.submit(_prompt(8, 10 + i),
                           SamplingParams(max_new_tokens=6 + 2 * i,
                                          seed=i))
              for i in range(6)]
    for _ in range(3000):
        sched.step()
        if big.status is not RequestStatus.QUEUED:
            break
    assert big.status is not RequestStatus.QUEUED
    _drain(sched, [big] + smalls)
    assert len(big.result(timeout=1)) == 24
    for i, h in enumerate(smalls):
        assert len(h.result(timeout=1)) == 6 + 2 * i
    assert eng.stats.kv_blocks_in_use == 0


def test_starvation_guard_covers_prefix_priority(setup):
    """Regression (review): the guard must also bound being outscored —
    a cold-prefix head under a sustained hot-prefix stream would
    otherwise never win the window (it always HAS capacity, so the
    capacity-only guard never armed)."""
    cfg, model, params = setup
    shared = _prompt(16, 97)
    eng = InferenceEngine(params, cfg, num_slots=1, paged=True,
                          page_size=8)
    sched = Scheduler(eng, max_queue=64, prefix_window=4,
                      starvation_rounds=3)
    warm = sched.submit(np.concatenate([shared, _prompt(2, 98)]),
                        SamplingParams(max_new_tokens=2, seed=0))
    _drain(sched, [warm])
    cold = sched.submit(_prompt(18, 99), SamplingParams(
        max_new_tokens=2, seed=1))
    hot_seed = 200
    hots = []
    for _ in range(400):
        # keep the window saturated with hot-prefix competitors
        while sum(h.status is RequestStatus.QUEUED for h in hots) < 3:
            hots.append(sched.submit(
                np.concatenate([shared, _prompt(2, hot_seed)]),
                SamplingParams(max_new_tokens=2, seed=hot_seed)))
            hot_seed += 1
        sched.step()
        if cold.status is not RequestStatus.QUEUED:
            break
    assert cold.status is not RequestStatus.QUEUED
    _drain(sched, [cold] + hots)
    assert len(cold.result(timeout=1)) == 2


def test_scheduler_prefix_aware_admit_ordering(setup):
    """With one free slot and a cold-prefix request ahead of a
    hot-prefix request in the queue, the hot one is admitted first
    (within the lookahead window); where nothing is resident the same
    queue stays strict FCFS."""
    cfg, model, params = setup
    shared = _prompt(16, 90)
    eng = InferenceEngine(params, cfg, num_slots=1, paged=True,
                          page_size=8)
    sched = Scheduler(eng, max_queue=8, prefix_window=4)
    # warm the prefix cache
    h0 = sched.submit(np.concatenate([shared, _prompt(2, 91)]),
                      SamplingParams(max_new_tokens=2, seed=0))
    _drain(sched, [h0])
    cold = sched.submit(_prompt(18, 92), SamplingParams(
        max_new_tokens=2, seed=1))
    hot = sched.submit(np.concatenate([shared, _prompt(2, 93)]),
                       SamplingParams(max_new_tokens=2, seed=2))
    sched.step()                       # admits ONE request into the slot
    assert hot.status in (RequestStatus.RUNNING, RequestStatus.DONE)
    assert cold.status is RequestStatus.QUEUED
    _drain(sched, [cold, hot])
    # a cold prefix cache: all scores 0 -> FCFS preserved
    engu = InferenceEngine(params, cfg, num_slots=1)
    schedu = Scheduler(engu, max_queue=8, prefix_window=4)
    first = schedu.submit(_prompt(6, 94), SamplingParams(
        max_new_tokens=2, seed=3))
    second = schedu.submit(_prompt(6, 95), SamplingParams(
        max_new_tokens=2, seed=4))
    schedu.step()
    assert first.status in (RequestStatus.RUNNING, RequestStatus.DONE)
    assert second.status is RequestStatus.QUEUED
    _drain(schedu, [first, second])


# -- observability ---------------------------------------------------------


def test_metrics_carry_paged_and_spec_observables(setup, tmp_path):
    """serve.csv engine rows + headline + read_headline all report
    kv_blocks_in_use / prefix_hit_blocks / spec_accept_rate."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=8, spec_tokens=2)
    metrics = ServeMetrics(str(tmp_path), engine_log_every=1)
    sched = Scheduler(eng, max_queue=8, metrics=metrics)
    shared = _prompt(16, 40)
    hs = [sched.submit(np.concatenate([shared, _prompt(2, 41 + i)]),
                       SamplingParams(max_new_tokens=4, seed=i))
          for i in range(3)]
    while any(h.status in (RequestStatus.QUEUED, RequestStatus.RUNNING)
              for h in hs):
        sched.step()
        metrics.engine_tick(eng.stats, queue_depth=sched.queue_depth())
    metrics.sync()
    head = metrics.headline()
    assert head["requests_done"] == 3
    assert head["prefix_hit_blocks"] >= 2      # requests 2 and 3 hit
    assert head["spec_accept_rate"] is not None
    with open(os.path.join(str(tmp_path), "serve.csv")) as f:
        header = f.readline().strip().split(",")
    for col in ("kv_blocks_in_use", "prefix_hit_blocks",
                "spec_accept_rate"):
        assert col in header
    post = read_headline(os.path.join(str(tmp_path), "serve.csv"))
    assert post["prefix_hit_blocks"] == head["prefix_hit_blocks"]
    assert post["spec_accept_rate"] is not None
    metrics.close()


# -- quantized serving (ISSUE 11) ------------------------------------------
#
# The oracle shape is unchanged: quantized streams are compared against
# the QUANTIZED unpaged reference (generate_fast under the same
# weights_dtype/kv_dtype config — both paths quantize identical K/V
# vectors to identical (int8, scale) pairs and attend over identical
# dequantized windows, so the streams match bitwise). f32-vs-int8
# divergence is a QUALITY observable, measured separately — never an
# exactness assert.

import dataclasses


def _quant(setup, weights_dtype="f32", kv_dtype="int8"):
    cfg, model, params = setup
    qcfg = dataclasses.replace(cfg, weights_dtype=weights_dtype,
                               kv_dtype=kv_dtype)
    from gym_tpu.serve.load import quantize_params
    return qcfg, quantize_params(params, qcfg)


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_prefix_sharing_exact_under_kv_dtype(setup, kv_dtype):
    """The kv_dtype param axis on the ISSUE 7 prefix-share oracle: the
    second admit reuses the resident (quantized) blocks — prefill
    shrinks to the suffix bucket — and both streams equal their solo
    quantized-unpaged generate_fast runs. Shared quantized pages are
    write-once (int8, scale) pairs, so sharing stays bit-stable."""
    qcfg, qparams = _quant(setup, kv_dtype=kv_dtype)
    shared = _prompt(24, 170)
    pa = np.concatenate([shared, _prompt(4, 171)])
    pb = np.concatenate([shared, _prompt(4, 172)])
    eng = InferenceEngine(qparams, qcfg, num_slots=2, paged=True,
                          page_size=8)
    ra = _run_one(eng, pa, SamplingParams(max_new_tokens=6,
                                          temperature=0.8, top_k=5,
                                          seed=1))
    tokens_first = eng.stats.prefill_tokens
    rb = _run_one(eng, pb, SamplingParams(max_new_tokens=6,
                                          temperature=0.8, top_k=5,
                                          seed=2))
    assert eng.stats.prefix_hit_blocks == 3
    assert eng.stats.prefill_tokens - tokens_first == 4
    assert ra == generate_fast(qparams, qcfg, pa[None], 6,
                               temperature=0.8, top_k=5,
                               seed=1)[0, 28:].tolist()
    assert rb == generate_fast(qparams, qcfg, pb[None], 6,
                               temperature=0.8, top_k=5,
                               seed=2)[0, 28:].tolist()


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_cow_triple_exact_under_kv_dtype(setup, kv_dtype):
    """CoW triple-exactness on the kv_dtype axis: a fully block-aligned
    re-admit copies the (int8, scale) page verbatim — the shared source
    page is not perturbed, so the third request is exact too."""
    qcfg, qparams = _quant(setup, kv_dtype=kv_dtype)
    p16 = _prompt(16, 180)
    eng = InferenceEngine(qparams, qcfg, num_slots=2, paged=True,
                          page_size=8)
    r1 = _run_one(eng, p16, SamplingParams(max_new_tokens=5, top_k=4,
                                           seed=3))
    before = eng.stats.prefill_tokens
    r2 = _run_one(eng, p16, SamplingParams(max_new_tokens=5, top_k=4,
                                           seed=4))
    assert eng.stats.prefill_tokens - before == 1
    r3 = _run_one(eng, p16, SamplingParams(max_new_tokens=5, top_k=4,
                                           seed=3))
    for r, seed in ((r1, 3), (r2, 4), (r3, 3)):
        assert r == generate_fast(qparams, qcfg, p16[None], 5, top_k=4,
                                  seed=seed)[0, 16:].tolist()


@pytest.mark.parametrize("kv_dtype", ["int8"])
def test_churn_isolated_under_int8_kv(setup, kv_dtype):
    """Churn isolation under int8 KV (weights int8 too — the full
    quantized hot path): mixed concurrent requests through one shared
    quantized pool all equal their solo quantized references, and the
    pool drains to zero."""
    qcfg, qparams = _quant(setup, weights_dtype="int8",
                           kv_dtype=kv_dtype)
    eng = InferenceEngine(qparams, qcfg, num_slots=2, decode_chunk=4,
                          paged=True, page_size=8)
    sched = Scheduler(eng, max_queue=8)
    handles, wants = [], []
    for i, (plen, mnew) in enumerate([(5, 7), (9, 12), (17, 9),
                                      (8, 15)]):
        prompt = _prompt(plen, 190 + i)
        ref = generate_fast(qparams, qcfg, prompt[None], mnew,
                            temperature=0.9, top_k=7, top_p=0.95, seed=i)
        wants.append(ref[0, plen:].tolist())
        handles.append(sched.submit(prompt, SamplingParams(
            max_new_tokens=mnew, temperature=0.9, top_k=7, top_p=0.95,
            seed=i)))
    _drain(sched, handles)
    for h, want in zip(handles, wants):
        assert h.result(timeout=1) == want
    assert eng.stats.kv_blocks_in_use == 0


def test_quantized_spec_decode_exact(setup):
    """Speculative decoding on the fully quantized path: draft/verify
    over int8 weights + int8 KV still emits the exact non-speculative
    quantized stream (rollback is a cursor rewind — quantized drafts sit
    past the cursor like f32 ones)."""
    qcfg, qparams = _quant(setup, weights_dtype="int8", kv_dtype="int8")
    prompt = _prompt(10, 121)
    ref = generate_fast(qparams, qcfg, prompt[None], 12, temperature=0.9,
                        top_k=7, seed=5)[0, 10:].tolist()
    spec = InferenceEngine(qparams, qcfg, num_slots=2, paged=True,
                           page_size=8, decode_chunk=3, spec_tokens=3)
    got = _run_one(spec, prompt, SamplingParams(max_new_tokens=12,
                                                temperature=0.9, top_k=7,
                                                seed=5))
    assert got == ref
    assert spec.stats.spec_drafted > 0


def test_quarantine_under_int8_kv(setup):
    """NaN quarantine still fails ONLY the poisoned slot under int8 KV:
    the f32 SCALE pool carries the poison (int8 payload cannot hold a
    NaN), dequant propagates it to that slot's logits, the latch
    catches it, and the neighbor stays clean."""
    import jax.numpy as jnp

    qcfg, qparams = _quant(setup, kv_dtype="int8")
    eng = InferenceEngine(qparams, qcfg, num_slots=2, paged=True,
                          page_size=8, decode_chunk=4)
    slot, _ = eng.admit(_prompt(8, 1), SamplingParams(max_new_tokens=3))
    other, _ = eng.admit(_prompt(6, 2), SamplingParams(max_new_tokens=8))
    pg = int(eng._bt[slot, 0])
    eng._cache = jax.tree.map(
        lambda x: x.at[pg].set(jnp.nan) if x.dtype == jnp.float32 else x,
        eng._cache)
    evs = eng.step()
    mine = [e for e in evs if e.slot == slot]
    assert mine and all(e.poisoned for e in mine)
    assert eng.stats.quarantined == 1
    assert all(not e.poisoned for e in evs if e.slot == other)


def test_int8_kv_capacity_4x_structural(setup):
    """The ISSUE 11 acceptance assert, structurally: at the SAME KV
    payload byte budget (4 int8 pages per f32 page) the int8 pool holds
    >= 4x the resident prefix blocks. Deterministic — sequential
    distinct one-block prompts, no timing anywhere."""
    cfg, model, params = setup
    qcfg, qparams = _quant(setup, kv_dtype="int8")

    def arm(c, p, kv_pages):
        eng = InferenceEngine(p, c, num_slots=2, paged=True, page_size=8,
                              kv_pages=kv_pages)
        for i in range(48):
            _run_one(eng, _prompt(8, 700 + i),
                     SamplingParams(max_new_tokens=2, seed=i))
        return eng

    f32_pages = 2 + cfg.block_size // 8        # minimum legal pool: 10
    int8_pages = 1 + (f32_pages - 1) * 4       # equal payload bytes: 37
    f32_eng = arm(cfg, params, f32_pages)
    int8_eng = arm(qcfg, qparams, int8_pages)
    f32_bytes = f32_eng.kv_pool_bytes()
    int8_bytes = int8_eng.kv_pool_bytes()
    assert int8_bytes["payload"] <= f32_bytes["payload"]
    assert f32_bytes["scales"] == 0 and int8_bytes["scales"] > 0
    assert (int8_eng.stats.kv_blocks_cached
            >= 4 * f32_eng.stats.kv_blocks_cached), (
        int8_eng.stats.kv_blocks_cached, f32_eng.stats.kv_blocks_cached)
    assert (int8_eng.kv_blocks_capacity_effective
            == 4 * (int8_pages - 1)
            > f32_eng.kv_blocks_capacity_effective)


def test_f32_vs_int8_divergence_measured_separately(setup):
    """The quality observable: f32 and int8 streams MAY diverge (that is
    the honest cost of the codec) — what is pinned is that each stream
    equals its OWN reference and the divergence is a measurement, not an
    exactness failure."""
    cfg, model, params = setup
    qcfg, qparams = _quant(setup, weights_dtype="int8", kv_dtype="int8")
    prompt = _prompt(12, 131)
    kw = dict(temperature=0.9, top_k=7, seed=9)
    ref_f32 = generate_fast(params, cfg, prompt[None], 16,
                            **kw)[0, 12:].tolist()
    ref_q = generate_fast(qparams, qcfg, prompt[None], 16,
                          **kw)[0, 12:].tolist()
    eng = InferenceEngine(qparams, qcfg, num_slots=2, paged=True,
                          page_size=8)
    got = _run_one(eng, prompt, SamplingParams(max_new_tokens=16, **kw))
    assert got == ref_q                       # exact vs OWN reference
    div = sum(a != b for a, b in zip(got, ref_f32)) / len(got)
    assert 0.0 <= div <= 1.0                  # measured, never asserted 0


def test_engine_rejects_bad_quant_dtypes(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="weights_dtype"):
        InferenceEngine(params, dataclasses.replace(
            cfg, weights_dtype="fp8"), num_slots=1)
    with pytest.raises(ValueError, match="kv_dtype"):
        InferenceEngine(params, dataclasses.replace(
            cfg, kv_dtype="int4"), num_slots=1)


def test_metrics_quant_columns_and_old_header_tolerance(setup, tmp_path):
    """serve.csv engine rows + headline + read_headline carry
    weights_dtype/kv_dtype; a pre-quantization CSV (old header) still
    aggregates — pinned like the paging and fleet schema bumps."""
    qcfg, qparams = _quant(setup, weights_dtype="int8", kv_dtype="int8")
    eng = InferenceEngine(qparams, qcfg, num_slots=2, paged=True,
                          page_size=8)
    metrics = ServeMetrics(str(tmp_path), engine_log_every=1)
    sched = Scheduler(eng, max_queue=8, metrics=metrics)
    h = sched.submit(_prompt(6, 41), SamplingParams(max_new_tokens=3))
    while h.status in (RequestStatus.QUEUED, RequestStatus.RUNNING):
        sched.step()
        metrics.engine_tick(eng.stats, queue_depth=sched.queue_depth())
    metrics.sync()
    head = metrics.headline()
    assert head["weights_dtype"] == "int8"
    assert head["kv_dtype"] == "int8"
    csv_path = os.path.join(str(tmp_path), "serve.csv")
    with open(csv_path) as f:
        header = f.readline().strip().split(",")
    assert "weights_dtype" in header and "kv_dtype" in header
    post = read_headline(csv_path)
    assert post["weights_dtype"] == "int8"
    assert post["kv_dtype"] == "int8"
    metrics.close()
    # old-header CSV (pre-quantization schema): aggregates fine, dtypes
    # simply absent
    old = tmp_path / "old.csv"
    old.write_text(
        "ts_s,kind,request_id,status,queue_depth,active_slots,"
        "prompt_tokens,new_tokens,ttft_s,avg_token_latency_s,"
        "cum_tokens,tokens_per_s\n"
        "0.5,request,r0,done,0,1,4,3,0.01,0.002,3,6.0\n")
    legacy = read_headline(str(old))
    assert legacy["requests_done"] == 1
    assert legacy["weights_dtype"] is None
    assert legacy["kv_dtype"] is None


# -- the sampler's gate (ISSUE 36) -----------------------------------------


@pytest.mark.parametrize("kind", ["chunk1", "chunk4", "spec"])
def test_sampler_sorts_only_in_steps_where_a_filtering_row_is_live(setup,
                                                                   kind):
    """A default and a greedy request decode for 30 tokens; beside them a
    ``top_p = 0.9`` request comes and goes. ``sampler_sorted_steps``
    counts exactly the decode steps in which that row was live (its
    first token is the prefill's), stands still before and after, and
    every stream is its own ``generate_fast`` run whichever branch the
    sampler took."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=3, page_size=8, **{
        "chunk1": {}, "chunk4": {"decode_chunk": 4},
        "spec": {"spec_tokens": 3}}[kind])
    asked, toks = {}, {}

    def admit(plen, seed, mnew, **kw):
        prompt = _prompt(plen, seed)
        slot, ev = eng.admit(prompt, SamplingParams(
            max_new_tokens=mnew, seed=seed, **kw))
        asked[slot] = (prompt, mnew, dict(seed=seed, **kw))
        toks[slot] = [ev.token]
        return slot

    def step():
        evs = eng.step()
        for ev in evs:
            toks[ev.slot].append(ev.token)
        return {ev.slot for ev in evs}

    admit(7, 1, 30, temperature=0.9)
    admit(9, 2, 30, top_k=1)
    step(), step()
    st = eng.stats
    assert st.decode_steps >= 2 and st.sampler_sorted_steps == 0
    slot = admit(5, 3, 6, top_p=0.9)
    steps_with_it = 0
    while slot not in eng.free_slots():
        steps_with_it += slot in step()
    # scanned steps, in ``decode_steps``' unit: one a token without
    # speculation, one a verified run with it
    assert st.sampler_sorted_steps == (steps_with_it if kind != "chunk4"
                                       else 5)
    assert 1 <= st.sampler_sorted_steps <= 5
    sorted_then, steps_then = st.sampler_sorted_steps, st.decode_steps
    while len(eng.free_slots()) < 3:
        step()
    assert st.decode_steps > steps_then
    assert st.sampler_sorted_steps == sorted_then
    for s, (prompt, mnew, kw) in asked.items():
        ref = generate_fast(params, cfg, prompt[None], mnew, **kw)
        assert toks[s] == ref[0, len(prompt):].tolist(), kw


def test_a_finished_rows_filter_does_not_keep_the_sorts_on(setup):
    """The finished row's ``top_k`` stays in the decode state on the
    device; ``live`` is the step's ``active``, so the steps after it
    sort nothing."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, page_size=8)
    eng.admit(_prompt(6, 4), SamplingParams(max_new_tokens=12, seed=4))
    slot, _ev = eng.admit(_prompt(6, 5), SamplingParams(
        max_new_tokens=3, top_k=5, seed=5))
    while len(eng.free_slots()) < 2:
        eng.step()
    assert int(eng._top_k[slot]) == 5            # still there
    assert eng.stats.decode_steps == 11
    assert eng.stats.sampler_sorted_steps == 2


def test_stats_serve_the_samplers_sorted_steps(setup, tmp_path):
    """``/stats`` carries the counter: 0 after a default and a greedy
    request, the filtering request's decode steps after it."""
    import json
    import urllib.request
    from gym_tpu.serve.__main__ import create_server
    cfg, model, params = setup
    handle = create_server(params, cfg, port=0, num_slots=2, page_size=8,
                           warmup=False, metrics_dir=str(tmp_path))
    threading.Thread(target=handle.httpd.serve_forever, daemon=True).start()

    def stats():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/stats", timeout=60) as r:
            return json.loads(r.read())

    try:
        plain = [handle.scheduler.submit(_prompt(6, 1), SamplingParams(
                     max_new_tokens=8, seed=1)),
                 handle.scheduler.submit(_prompt(5, 2), SamplingParams(
                     max_new_tokens=8, top_k=1))]
        for req in plain:
            req.result(timeout=120)
        got = stats()
        assert got["decode_steps"] >= 7
        assert got["sampler_sorted_steps"] == 0
        handle.scheduler.submit(_prompt(7, 3), SamplingParams(
            max_new_tokens=6, top_p=0.9, seed=3)).result(timeout=120)
        assert stats()["sampler_sorted_steps"] == 5
    finally:
        handle.close(drain_deadline_s=5.0)


# -- the allocator's bookkeeping (ISSUE 40) ---------------------------------


class _WalkingAllocator:
    """The allocator as it stood before ISSUE 40, the plain reference of
    the differential tests below: the victim is found by walking the
    cache from its oldest entry past every pinned page, supply and use
    by scanning it. ``visits`` counts the entries those walks look at;
    the ``*_many`` calls are the loops the engine used to make."""

    def __init__(self, num_pages, page_size):
        from collections import OrderedDict
        self.num_pages, self.page_size = int(num_pages), int(page_size)
        self._free = list(range(num_pages - 1, 0, -1))
        self._ref = {}
        self._cache = OrderedDict()
        self._key_of = {}
        self._cid = 0
        self.evictions = self.visits = 0

    evict_visits = property(lambda self: self.visits)

    def in_use(self):
        return sum(1 for r in self._ref.values() if r > 0)

    def cached(self):
        return len(self._cache)

    def available(self, exclude=()):
        ex = set(exclude)
        n = len(self._free)
        for _key, (pg, _cid) in self._cache.items():
            if self._ref.get(pg, 0) == 0 and pg not in ex:
                n += 1
        return n

    def alloc(self):
        if self._free:
            pg = self._free.pop()
        else:
            pg = self._evict_one()
        self._ref[pg] = 1
        return pg

    def _evict_one(self):
        for key, (pg, _cid) in self._cache.items():
            self.visits += 1
            if self._ref.get(pg, 0) == 0:
                del self._cache[key]
                del self._key_of[pg]
                self._ref.pop(pg, None)
                self.evictions += 1
                return pg
        raise NoFreeBlocksError("pool exhausted")

    def alloc_many(self, n):
        if self.available() < n:
            raise NoFreeBlocksError("pool cannot supply")
        return [self.alloc() for _ in range(n)]

    def incref(self, page):
        self._ref[page] = self._ref.get(page, 0) + 1

    def incref_many(self, pages):
        for pg in pages:
            self.incref(pg)

    def decref(self, page):
        r = self._ref.get(page, 0) - 1
        if r < 0:
            raise ValueError(f"page {page} double-freed")
        self._ref[page] = r
        if r == 0 and page not in self._key_of:
            self._ref.pop(page)
            self._free.append(page)

    def decref_many(self, pages):
        for pg in pages:
            self.decref(pg)

    def condemn(self, page):
        if self._ref.get(page, 0) != 1:
            return False
        key = self._key_of.pop(page, None)
        if key is not None:
            del self._cache[key]
        return True

    def lookup(self, parent_cid, block):
        key = (parent_cid, block)
        ent = self._cache.get(key)
        if ent is not None:
            self._cache.move_to_end(key)
        return ent

    def touch(self, page):
        key = self._key_of.get(page)
        if key is not None:
            self._cache.move_to_end(key)

    def probe(self, parent_cid, block):
        return self._cache.get((parent_cid, block))

    def register(self, parent_cid, block, page):
        key = (parent_cid, block)
        ent = self._cache.get(key)
        if ent is not None:
            return ent[1]
        self._cid += 1
        self._cache[key] = (page, self._cid)
        self._key_of[page] = key
        return self._cid


def _both(old, new, name, *args):
    """One call on both allocators: the same answer or the same error."""
    def call(al):
        try:
            return getattr(al, name)(*args)
        except (NoFreeBlocksError, ValueError) as e:
            return type(e)
    want, got = call(old), call(new)
    assert got == want, (name, args, got, want)
    return want


@pytest.mark.parametrize("seed", range(10))
def test_allocator_hands_out_what_the_walking_one_did(seed):
    """3,000 seeded operations on a pool of 23 pages that runs dry, on
    the walking allocator and on the one that keeps a heap and counters:
    every page handed out, every chain id, every ``available``,
    ``in_use`` and ``cached`` equal, and ``NoFreeBlocksError`` at the
    same operation. ``held`` has an entry a reference the test owns."""
    rng = np.random.default_rng(seed)
    old, new = _WalkingAllocator(24, 4), BlockAllocator(24, 4)
    held, cids, keys = [], [0], []
    pick = lambda seq: seq[int(rng.integers(len(seq)))]  # noqa: E731
    ran_dry = 0
    ops = ["alloc"] * 4 + ["alloc_many", "register", "register", "lookup",
                           "probe", "pin", "incref", "decref", "decref",
                           "decref_many", "touch", "condemn", "available"]
    for _ in range(3000):
        op = pick(ops)
        if op == "alloc":
            pg = _both(old, new, "alloc")
            if pg is NoFreeBlocksError:
                ran_dry += 1
            else:
                held.append(pg)
        elif op == "alloc_many":
            pages = _both(old, new, "alloc_many", int(rng.integers(1, 7)))
            if pages is NoFreeBlocksError:
                ran_dry += 1
            else:
                held.extend(pages)
        elif op == "register":
            plain = [pg for pg in set(held) if pg not in old._key_of]
            if plain:
                # few contents under few parents: a key is met again,
                # cached (the entry wins) and evicted
                key = (pick(cids[-6:]), bytes([int(rng.integers(3))]))
                cids.append(_both(old, new, "register", *key, pick(plain)))
                keys.append(key)
        elif op in ("lookup", "probe") and keys:
            _both(old, new, op, *pick(keys[-40:]))
        elif op == "pin" and old._key_of:
            # a prefix hit: a resident page, in use or evictable
            pg = pick(sorted(old._key_of))
            _both(old, new, "incref", pg)
            held.append(pg)
        elif op == "incref" and held:
            pg = pick(held)
            _both(old, new, "incref", pg)
            held.append(pg)
        elif op == "decref" and held:
            _both(old, new, "decref",
                  held.pop(int(rng.integers(len(held)))))
        elif op == "decref_many" and held:
            rng.shuffle(held)
            k = int(rng.integers(1, 9))
            _both(old, new, "decref_many", held[:k])
            del held[:k]
        elif op == "touch":
            _both(old, new, "touch", int(rng.integers(1, 24)))
        elif op == "condemn" and held:
            _both(old, new, "condemn", pick(held))
        elif op == "available":
            some = rng.integers(1, 24, int(rng.integers(0, 8))).tolist()
            _both(old, new, "available", some)
        for name in ("available", "in_use", "cached"):
            _both(old, new, name)
        assert new.evictions == old.evictions
    assert ran_dry > 20 and old.evictions > 100
    # the heap holds no more than a few entries a page of the pool
    assert len(new._heap) <= 2 * 23 + 65
    _both(old, new, "decref_many", held)
    assert _both(old, new, "available") == 23
    assert _both(old, new, "decref", 1) is ValueError     # double-freed


def test_allocator_heap_is_made_anew_where_nothing_evicts():
    """A pool that never runs dry never pops its heap: a shared page
    that is hit, stamped and released a thousand times leaves one live
    entry, not a thousand stale ones."""
    al = BlockAllocator(64, 4)
    pages = al.alloc_many(8)
    cid = 0
    for i, pg in enumerate(pages):
        cid = al.register(cid, bytes([i]), pg)
    al.decref_many(pages)
    for _ in range(1000):
        al.incref_many(pages)
        for pg in pages:
            al.touch(pg)
        al.decref_many(pages)
    assert len(al._heap) <= 2 * 8 + 65 and al.evictions == 0
    assert al.available() == 63 and al.in_use() == 0
    taken = al.alloc_many(63)                  # the free list, then the 8
    assert taken[-8:] == pages and al.evictions == 8 and al.cached() == 0


def test_an_admissions_pages_cost_a_page_each_not_a_walk_of_the_pool():
    """32 running rows of 500 registered pages each, an empty free
    list, and one finished row's 600 pages to evict: an admission of 500
    pages looks at 500 entries, where the walking allocator passes the
    16,000 pinned entries before every victim (over a million entries
    for the first 70 pages alone). No clock: the count is the test."""
    rows, each, spare = 32, 500, 600

    def full_pool(al):
        cid = 0
        for r in range(rows + 1):
            pages = al.alloc_many(spare if r == rows else each)
            for i, pg in enumerate(pages):
                cid = al.register(cid, b"%d.%d" % (r, i), pg)
        al.decref_many(pages)                  # the last row ended
        assert al.available() == spare and not al._free
        return al

    n = 1 + rows * each + spare
    new, old = full_pool(BlockAllocator(n, 16)), \
        full_pool(_WalkingAllocator(n, 16))
    assert new.available(exclude=range(1, 100)) == spare
    got = new.alloc_many(each)
    assert new.evictions == each and new.evict_visits <= 2 * each
    assert new.in_use() == (rows + 1) * each
    assert got[:70] == old.alloc_many(70)
    assert old.visits > 1_000_000


def test_engine_through_a_pool_that_evicts_is_the_walking_allocators(setup):
    """40 seeded admissions, most of them behind one of three shared
    prefixes, through a pool too small to keep them all: with the
    walking allocator in the engine's place for the reference, the
    tokens, the block tables and the prefix hits are the same, so the
    same pages were evicted, hit and copied on write."""
    cfg, model, params = setup

    def run(allocator):
        eng = InferenceEngine(params, cfg, num_slots=3, page_size=4,
                              kv_pages=28)
        if allocator is not None:
            eng._alloc = allocator(eng.kv_pages, eng.page_size)
        rng = np.random.default_rng(40)
        prefixes = [_prompt(int(n), 900 + i)
                    for i, n in enumerate((16, 12, 24))]
        out, slots = [], {}
        for i in range(40):
            kind = int(rng.integers(6))
            head = prefixes[kind % 3]
            prompt = (head if kind == 3 else       # whole: copy-on-write
                      _prompt(int(rng.integers(9, 30)), 500 + i)
                      if kind > 3 else
                      np.concatenate([head, _prompt(
                          int(rng.integers(1, 9)), 700 + i)]))
            sp = SamplingParams(max_new_tokens=int(rng.integers(2, 12)),
                                top_k=4, seed=i)
            while True:
                try:
                    slot = eng.admit_nowait(prompt, sp)
                    break
                except NoFreeBlocksError:
                    assert slots                   # someone will release
                    for ev in eng.step():
                        slots[ev.slot].append(ev.token)
                        if ev.finished:
                            out.append(slots.pop(ev.slot))
            slots[slot] = [i, eng._bt[slot].tolist(),
                           eng.stats.prefix_hit_blocks]
            while len(slots) == 3 or (i == 39 and slots):
                for ev in eng.step():
                    slots[ev.slot].append(ev.token)
                    if ev.finished:
                        out.append(slots.pop(ev.slot))
        st = eng.stats
        assert st.kv_evictions == eng._alloc.evictions
        assert st.kv_blocks_in_use == 0
        return sorted(out), (st.prefix_hit_blocks, st.kv_evictions,
                             st.kv_blocks_cached, st.prefill_tokens)

    want, got = run(_WalkingAllocator), run(None)
    assert got == want
    hits, evictions = got[1][:2]
    assert hits > 20 and evictions > 40


def test_stats_carries_the_evictions_and_the_plan_span_its_pages(setup,
                                                                 tmp_path):
    """``/stats`` has ``kv_evictions`` (and the heap entries they looked
    at), and a ``serve.prefill.plan`` span says how many pages its
    admission took and how many of them it evicted."""
    import json
    import urllib.request
    from gym_tpu.serve.__main__ import create_server
    from gym_tpu.utils import trace
    cfg, model, params = setup
    handle = create_server(params, cfg, port=0, num_slots=1, page_size=8,
                           kv_pages=10, warmup=False,
                           metrics_dir=str(tmp_path))
    threading.Thread(target=handle.httpd.serve_forever, daemon=True).start()
    try:
        for i in range(3):                  # 4 pages each, 3 registered
            handle.scheduler.submit(_prompt(24, 40 + i), SamplingParams(
                max_new_tokens=4, seed=i)).result(timeout=120)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/stats", timeout=60) as r:
            got = json.loads(r.read())
        plans = [r for r in trace.records()
                 if r.name == "serve.prefill.plan"][-3:]
        assert [r.ids["pages"] for r in plans] == [4, 4, 4]
        evicted = [r.ids["evicted"] for r in plans]
        assert evicted[0] == 0 and sum(evicted) == got["kv_evictions"] > 0
        assert got["kv_evictions"] <= got["kv_evict_visits"] \
            <= 2 * got["kv_evictions"]
    finally:
        handle.close(drain_deadline_s=5.0)
