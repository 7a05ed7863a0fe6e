"""The engine's ONE cache manager with a row that holds BOTH kinds of
cache (``models/qwen3_next.py``: pages of keys and values in the full
layers, one state block in the delta layers; ``row_state``): the block
table's last column names the row's state block, and an admission, a
parked row, a release and the scrub of a quarantined row take, pin, free
and zero both as one. What such a row cannot do is asked of the config and
left out (prefix hits, registration, the copy-on-write spare,
speculation). Beside it in one process a page-only model
(``keye_vl2.py``) and a state-only model (``brumby.py``) as they were; and
the decode, prefill and copy programs of all five accepted served models
and a small GPT-2 step program lower to the text they had on this PR's
parent commit (sha256 of ``lower().as_text()``, taken there by this file's
own recipe), and the trainer imports no module this PR adds or edits. Tiny
float32 models on the CPU."""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.programs import serve_defs
from gym_tpu.serve.engine import (InferenceEngine, NoFreeBlocksError,
                                  SamplingParams)
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from gym_tpu.utils import trace
from perfbench import weights_brumby, weights_keye, weights_qwen3_next
from perfbench.kinds import (closed_brumby, closed_keye, closed_kimi,
                             closed_model, closed_qwen3_next)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sizes(name, **over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"], **over}


@pytest.fixture(scope="module")
def hybrid():
    sizes = _sizes("qwen3-next-80b-a3b")
    return (closed_qwen3_next.model_config(sizes),
            weights_qwen3_next.make_params(sizes, 3))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n)


def _engine(model, slots=2, **kw):
    cfg, params = model
    return InferenceEngine(params, cfg, num_slots=slots, page_size=8, **kw)


def _run(eng, prompt, n_new, seed=1):
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new,
                                                top_k=1, seed=seed))
    toks = [ev.token]
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    return toks


def _state_free(eng):
    return sorted(eng._state_free)


def test_a_row_holds_a_run_of_pages_and_one_state_block(hybrid):
    """The table is a row's 16 pages and, last, its state block; the
    default pool of state blocks is the null one, one a slot and a spare
    for a parked row; ``kv_pool_bytes`` counts the two kinds apart; a
    long and a short row hold pages by their length and one block each."""
    eng = _engine(hybrid)
    cfg = eng.config
    assert eng.row_state and not eng.row_cache and eng._cow_room == 0
    assert (eng.page_size, eng.max_blocks, eng.kv_pages) == (8, 16, 34)
    assert eng.state_blocks == cfg.state_blocks == 2 + 2
    assert eng._bt.shape == (2, 17)
    names = set(cfg.row_state_names())
    assert names == {"delta_0", "delta_1", "delta_2"}
    by_kind = {"state": 0, "payload": 0}
    for name, sub in eng._cache.items():
        by_kind["state" if name in names else "payload"] += sum(
            int(x.nbytes) for x in jax.tree.leaves(sub))
    assert eng.kv_pool_bytes() == {**by_kind, "scales": 0}
    assert by_kind["state"] == 4 * cfg.state_bytes_per_row()
    sp = SamplingParams(max_new_tokens=6, top_k=1)
    a = eng.admit_nowait(_prompt(20, 1), sp)
    assert eng._alloc.in_use() == max(20 + 6, 32) // 8
    assert eng.state_block(a) == 1 and _state_free(eng) == [2, 3]
    b = eng.admit_nowait(_prompt(5, 2), sp)
    assert eng._alloc.in_use() == 4 + 2
    assert eng.state_block(b) == 2
    assert eng.stats.state_blocks_in_use == 2
    assert eng.stats.state_blocks == 4
    eng.release(a)
    assert eng._alloc.in_use() == 2 and _state_free(eng) == [1, 3]
    while eng._active.any():
        eng.step()
    assert eng._alloc.in_use() == 0 and _state_free(eng) == [1, 2, 3]
    assert eng.stats.state_blocks_in_use == 0
    # a stopped row's table is cleared on the device too, its state
    # block with its pages
    assert not np.asarray(eng._dev["bt"]).any()


def test_an_admission_needs_both_and_says_so_before(hybrid):
    """Pages to spare and no state block: ``admit_probe`` says no, the
    admission waits (``NoFreeBlocksError``: the scheduler's retry) and
    gives back the pages it took. A state block to spare and no pages:
    the same, and the state block stays free."""
    sp = SamplingParams(max_new_tokens=4, top_k=1)
    eng = _engine(hybrid, slots=3, state_blocks=3)     # two rows' blocks
    eng.admit_nowait(_prompt(9, 1), sp)
    eng.admit_nowait(_prompt(9, 2), sp)
    assert len(eng.free_slots()) == 1 and not eng._state_free
    assert eng._alloc.available() > 4
    assert eng.admit_probe(_prompt(9, 3), sp) == (False, 0)
    held = eng._alloc.in_use()
    with pytest.raises(NoFreeBlocksError, match="state block"):
        eng.admit_nowait(_prompt(9, 3), sp)
    assert eng._alloc.in_use() == held and not eng._bt[2].any()
    eng.drain()

    eng = _engine(hybrid, slots=2, kv_pages=18)        # 17 pages
    eng.admit_nowait(_prompt(100, 1), sp)              # 16 of them
    assert eng.admit_probe(_prompt(40, 2), sp) == (False, 0)
    with pytest.raises(NoFreeBlocksError):
        eng.admit_nowait(_prompt(40, 2), sp)
    assert _state_free(eng) == [2, 3]
    assert eng.admit_probe(_prompt(3, 2), sp) == (True, 0)
    eng.drain()


def test_no_prefix_is_served_to_such_a_row_and_none_is_registered(hybrid):
    """The same prompt twice: nothing is found, nothing registered, the
    whole prompt is prefilled again to the same tokens, and a released
    row's pages are on the free list, not in the evictable cache."""
    eng = _engine(hybrid, slots=1)
    prompt = _prompt(32, 80)
    first = _run(eng, prompt, 5)
    assert eng._walk_prefix(prompt) == ([], [])
    assert eng._alloc.cached() == 0 and eng._alloc._evictable == 0
    assert len(eng._alloc._free) == eng.kv_pages - 1
    assert eng.admit_probe(prompt, SamplingParams()) == (True, 0)
    fed = eng.stats.prefill_tokens
    assert _run(eng, prompt, 5) == first
    assert eng.stats.prefill_tokens - fed == 32
    assert eng.stats.prefix_hit_blocks == 0
    assert eng.stats.kv_blocks_cached == 0
    with pytest.raises(ValueError, match="cannot be rewound"):
        _engine(hybrid, spec_tokens=1)
    with pytest.raises(ValueError, match="state_blocks=1 too small"):
        _engine(hybrid, state_blocks=1)


def test_park_resume_and_release_parked_pin_both(hybrid):
    """A parked row keeps its pages and its state block pinned (nothing
    copied); with the other block taken no third row is admitted though a
    slot is free; resumed, into another slot, the row continues as if
    never parked; ``release_parked`` gives both back."""
    sp = SamplingParams(max_new_tokens=8, top_k=1)
    want = _run(_engine(hybrid), _prompt(19, 1), 8)
    eng = _engine(hybrid, slots=2, state_blocks=3)
    slot, ev = eng.admit(_prompt(19, 1), sp)
    toks = [ev.token] + [e.token for e in eng.step() if e.slot == slot]
    pages = eng._alloc.in_use()
    parked = eng.park(slot)
    assert int(parked.block_table[-1]) == 1
    assert eng._alloc.in_use() == pages and _state_free(eng) == [2]
    other = eng.admit_nowait(_prompt(9, 2), sp)
    assert other == slot                                # the freed slot
    assert len(eng.free_slots()) == 1 and not eng._state_free
    with pytest.raises(NoFreeBlocksError):
        eng.admit_nowait(_prompt(7, 4), sp)
    eng.drain()
    slot = eng.resume(parked)
    assert slot != other and eng.state_block(slot) == 1
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert toks == want
    eng.release(other)
    assert eng._alloc.in_use() == 0 and _state_free(eng) == [1, 2]
    assert eng.stats.preemptions == 1 and eng.stats.resumes == 1
    slot, _ev = eng.admit(_prompt(5, 6), sp)
    dropped = eng.park(slot)
    assert eng._alloc.in_use() == 2 and len(eng._state_free) == 1
    eng.release_parked(dropped)
    eng.release_parked(dropped)               # idempotent
    assert eng._alloc.in_use() == 0 and len(eng._state_free) == 2


def test_the_scrub_writes_zeros_over_pages_and_state_block(hybrid):
    """A quarantined row's pages take the null page's zeros and its state
    block the null block's, each by its own kind's index: page 2 of the
    pool and state block 2 are different rows' and stay as they are."""
    eng = _engine(hybrid, slots=2)
    sp = SamplingParams(max_new_tokens=4, top_k=1)
    keep = eng.admit_nowait(_prompt(12, 8), sp)
    slot = eng.admit_nowait(_prompt(12, 9), sp)
    eng.drain()
    names = set(eng.config.row_state_names())
    pages = [int(p) for p in eng._bt[slot, :-1] if p]
    block, kept = eng.state_block(slot), eng.state_block(keep)
    before = jax.tree.map(np.asarray, eng._cache)
    eng._scrub_pages(slot)
    for name, sub in eng._cache.items():
        for leaf, old in zip(jax.tree.leaves(sub),
                             jax.tree.leaves(before[name])):
            leaf = np.asarray(leaf)
            assert not leaf[0].any()
            if name in names:
                assert old[block].any() and not leaf[block].any()
                np.testing.assert_array_equal(leaf[kept], old[kept])
            else:
                assert old[pages[0]].any() and not leaf[pages].any()
                own = [int(p) for p in eng._bt[keep, :-1] if p]
                np.testing.assert_array_equal(leaf[own], old[own])


def test_steady_steps_upload_nothing_and_records_carry_the_state_block(
        hybrid):
    """Through ``Scheduler``: rows of unequal length run to their ends,
    a decode step between admissions is handed no host array (the state
    blocks ride in the device's table), and the ``serve.admit`` and
    ``serve.prefill.plan`` records name each row's state block."""
    eng = _engine(hybrid, slots=2)
    sched = Scheduler(eng, max_queue=8)
    reqs = [sched.submit(_prompt(n, n), SamplingParams(
        max_new_tokens=m, top_k=1)) for n, m in ((40, 9), (7, 12), (23, 5))]
    for _ in range(2000):
        if all(r.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for r in reqs):
            break
        sched.step()
    assert [len(r.result(timeout=1)) for r in reqs] == [9, 12, 5]
    assert eng.stats.upload_arrays == 0
    assert eng.stats.resident_steps == eng.stats.decode_steps > 0
    blocks = [trace.records("serve.admit", request=r.id)[-1].ids[
        "state_block"] for r in reqs]
    assert blocks[:2] == [1, 2] and blocks[2] in (1, 2)
    assert [r.ids["state_block"] for r in trace.records(
        "serve.prefill.plan")[-3:]] == blocks
    assert eng._alloc.in_use() == 0 and len(eng._state_free) == 3


def test_page_only_and_state_only_rows_are_as_they_were():
    """Beside the hybrid model in one process: a page model's table has
    no column more and it still serves a prefix from its pages; a state
    model's row is still one block of the page allocator's; neither has a
    state-block list, a third program or a ``state`` entry in its bytes."""
    sizes = _sizes("keye-vl2-30b-a3b", dtype="float32")
    p_cfg = dataclasses.replace(closed_keye.model_config(sizes),
                                attn_query_block=16, attn_key_block=8)
    pages = InferenceEngine(weights_keye.make_params(sizes, 3), p_cfg,
                            num_slots=1, page_size=4)
    sizes = _sizes("brumby-14b-base")
    state = InferenceEngine(weights_brumby.make_params(sizes, 3),
                            closed_brumby.model_config(sizes), num_slots=1)
    for eng, width in ((pages, 32), (state, 1)):
        assert not eng.row_state and eng.state_blocks == 0
        assert eng._bt.shape == (1, width)
        assert eng._state_cow_prog is None and not eng._state_free
        assert sorted(eng.kv_pool_bytes()) == ["payload", "scales"]
        assert eng.stats.state_blocks == 0
        assert not any(d.name.startswith("serve.cow[state")
                       for d in eng.warmup_defs())
    prompt = _prompt(16, 80)
    first = _run(pages, prompt, 5)
    assert _run(pages, prompt, 5) == first
    assert pages.stats.prefix_hit_blocks == 4
    assert _run(state, prompt, 4) == _run(state, prompt, 4)
    assert state._alloc.in_use() == 0 and state.stats.kv_blocks_cached == 0


# sha256 of ``lower().as_text()`` of the accepted served models' programs
# at their rehearsal sizes (page 4, or the row for the state model; 40
# pages, 2 slots; CPU) and of a small GPT-2 step program (2 nodes folded,
# 2 layers of width 32, batch 2 x 32, bfloat16 autocast), taken on this
# PR's parent commit (599ac95) by this file's own recipe. Whoever changes
# what every served program runs takes the texts again, and says so here.
PARENT_TEXT = {
    "brumby-14b-base/cow":
        "05add5b84f1f51ee55cae155241132a3975acfccc8e8c8655b5ab4a7ff55bdb3",
    "brumby-14b-base/decode":
        "ae59c1b34bcd4207e8c3658510357a45dff8058e7d7c668c679f1fdc51b6ae4c",
    "brumby-14b-base/prefill16":
        "12aa70f6d564876db386668e2b226f04813a3f640be2d06027440a80b5326f74",
    "brumby-14b-base/prefill64":
        "59bb54b0c89e7b44bd302b4c499ce77e37df57e7e3e8493058464e53510bf1a9",
    "command-a-plus/cow":
        "d17dfaeaba0c733fcb1123392341728f2f67ce0a2f4e6b2ca4180265c3079043",
    "command-a-plus/decode":
        "f775416503389b12c33db5fc8c8afb0a6ef752015fe62076b4dc6509f5839336",
    "command-a-plus/prefill16":
        "c0bc2c2529f86cb356f589c43d93fdf94219eede8817f2efe9f3cb859ce4a592",
    "command-a-plus/prefill64":
        "446e41d89516ea5879bb2b057b3e821dc2d8d005d5c5e50ecd88e8a278fe6250",
    "gpt2-base/cow":
        "f5fb330e6b125a00f87ff7df4b153386b0f656093dbf2d69627cedb916f35221",
    "gpt2-base/decode":
        "4a6dcec65e55f139b9766be142bdcff78190668806cb20276ec7fb0ba05dbab6",
    "gpt2-base/prefill16":
        "24df1c80f6bc578658720411b8e330229d2831e35404a53a636ee41592cbf6da",
    "gpt2-base/prefill64":
        "6ad614d65e783d1c0cdaced380b885ed7c0af49636276e5bd1f93fb772a6a14e",
    "gpt2-step/allreduce":
        "42c8504a1a1d0648a80054eb18edf91122590697f636e4a4f64998322b7579b4",
    "gpt2-step/diloco":
        "04f7918df257832fb229506d321a22b8766d12f0ea2d58be75bc3b7e2ecd789f",
    "keye-vl2-30b-a3b/cow":
        "79f28282bbd654508c8319fcdc470543bada6d781229464c79db2389d06edd11",
    "keye-vl2-30b-a3b/decode":
        "98323de8917ceb9a10fd9c19d48ebadac29d513225f5c2c32c9744384b267dfb",
    "keye-vl2-30b-a3b/prefill16":
        "a69ed4dde0fdb55cf1fd636e5b522e311f70e0becf65a6335a39a2390c962e53",
    "keye-vl2-30b-a3b/prefill64":
        "faebfa5a91e6f5d9fbdd5b8dfd99c6be94d3c7d92f6c3a30dd12a3a2796f6362",
    "kimi-k2.7-code/cow":
        "707416ad8f2d22215ad0d4926b4f6c4f365b832ad50450e7e925d89ff69493b2",
    "kimi-k2.7-code/decode":
        "c8159a5622c476421a77c2eab4186c1de985022ced0c137ee802776586065a7e",
    "kimi-k2.7-code/prefill16":
        "4a86a50b43378902311b7c927968a0d9feb995c2f81cca256d80b631987d0099",
    "kimi-k2.7-code/prefill64":
        "ead3dae5104d026a9cd9c647a62f0333efd81019016553b46023f5cd09f3ec18",
}


def _gpt2(sizes):
    from gym_tpu.models.nanogpt import GPTConfig
    return GPTConfig(block_size=sizes["n_positions"],
                     vocab_size=sizes["vocab_size"], n_layer=sizes["n_layer"],
                     n_head=sizes["n_head"], n_embd=sizes["n_embd"],
                     dropout=0.0)


def _served_text(model, prog):
    make = {"gpt2-base": _gpt2, "command-a-plus": closed_model.model_config,
            "keye-vl2-30b-a3b": closed_keye.model_config,
            "brumby-14b-base": closed_brumby.model_config,
            "kimi-k2.7-code": closed_kimi.model_config}[model]
    cfg = make(_sizes(model)).decode_config()
    page = (cfg.block_size if getattr(cfg, "fixed_row_cache", False) else 4)
    cfg = dataclasses.replace(cfg, page_size=page, kv_pages=40)
    key = cfg.program_key()
    pdef = {"decode": lambda: serve_defs.paged_decode_def(key, 2, 1),
            "prefill16": lambda: serve_defs.paged_prefill_def(key, 16, 2),
            "prefill64": lambda: serve_defs.paged_prefill_def(key, 64, 2),
            "cow": lambda: serve_defs.cow_def(key)}[prog]()
    return pdef.builder().lower(*pdef.args).as_text()


def _step_text(name):
    from gym_tpu.models.base import LossModel
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.parallel import NodeRuntime
    from gym_tpu.strategy import (DiLoCoStrategy, OptimSpec,
                                  SimpleReduceStrategy)
    from gym_tpu.train_node import make_init_fn, make_train_step
    k, b, seq = 2, 2, 32
    runtime = NodeRuntime.create(k, [jax.devices()[0]])
    model = LossModel(GPT(GPTConfig(block_size=seq, vocab_size=256,
                                    n_layer=2, n_head=2, n_embd=32,
                                    dropout=0.0)), jnp.bfloat16)
    optim = OptimSpec("adamw", lr=6e-4)
    strategy = (DiLoCoStrategy(optim, H=4) if name == "diloco"
                else SimpleReduceStrategy(optim))
    strategy.finalize(100)
    micro = (jnp.zeros((b, seq), jnp.int32),) * 2
    init_fn = make_init_fn(model, strategy, micro, 0, None, ctx=runtime.ctx)
    init = runtime.compile(lambda _: init_fn(runtime.ctx.node_index()),
                           donate_state=False)
    state = jax.eval_shape(init, jax.ShapeDtypeStruct((k,), jnp.int32))
    batch = (jax.ShapeDtypeStruct((k, 1, b, seq), jnp.int32),) * 2
    step = runtime.compile(
        make_train_step(model, strategy, runtime.ctx, None, False),
        donate_batch=True)
    return step.lower(state, batch).as_text()


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_the_accepted_programs_lower_to_the_parents_text(name):
    """The table's last column, the second copy program, the new id at the
    dispatch point and the rotary width of ``decoder_parts.rotate_half``
    moved no operation of any program the benchmark's other cells compile:
    their compile-cache keys, and so their ``setup_s``, stand where the
    parent left them."""
    model, prog = name.split("/")
    text = (_step_text(prog) if model == "gpt2-step"
            else _served_text(model, prog))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[name]


# A Pallas kernel's compiled body carries the file and LINE of every frame
# it was traced under (measured on the chip, PR 43: with the lines of
# ``serve_defs.py`` and ``paged_attention.py`` moved, every kernel-bearing
# program of every served model missed the compile cache the parent had
# filled, though the text above, lowered on the CPU where no kernel is,
# was the parent's). Where each function that can stand in such a stack
# starts in the two files this PR edits, and a hash of its source, on the
# parent commit: additions go to the END of those files.
PARENT_LINES = {
    "gym_tpu/programs/serve_defs.py": {
        "build_paged_prefill": (117, "ac047bad01767dcb"),
        "build_paged_decode": (180, "d3c49082ff3df3b9"),
        "build_spec_decode": (297, "a0e6b832fc0b90c3")},
    "gym_tpu/ops/paged_attention.py": {
        "_kernel": (163, "ac08d12742bba020"),
        "paged_attention": (242, "573809f61b05873a"),
        "_paged_attention": (257, "8a7ac96326e0d185"),
        # PR 48 (the grouped kernel's tile and its unmasked body) edits
        # what follows, on PR 48's own tree: ``command-a-plus``'s and
        # ``qwen3-next``'s programs are the only ones that hold them
        "gqa_tile": (304, "af704735725b0154"),
        "_gqa_kernel": (321, "5ff68fb9ae8b72d2"),
        "paged_attention_gqa": (434, "5b22bbbd26ed4d86"),
        "_paged_attention_gqa": (449, "d49318341d52c741"),
        "_gqa_interior": (492, "0e019adf952ba331")},
    # PR 44 (a prefill in passes) edits these two: what stands in the
    # stacks of ``qwen3-next``'s kernels and of the two models' own decode
    # programs, on PR 44's parent commit (eee31ca); a method as
    # ``Class.method``. The additions stand at the files' ends and below
    # the models' ``__call__``
    "gym_tpu/models/cohere2_moe.py": {
        "pool_slots": (158, "98830a9bbad3f7df"),
        "by_query_block": (171, "1fa993cb95b63949"),
        "GroupedPagedAttention.__call__": (212, "0c203b0b656bc634"),
        "ParallelBlock.__call__": (312, "b234a304f457c618")},
    "gym_tpu/models/keye_vl2.py": {
        "write_index_keys": (127, "09cfd5507f06b46c"),
        "SparsePagedAttention.__call__": (157, "61cdc4635754d84b"),
        "Block.__call__": (266, "806af717de8e5a7b")},
}
# and the one line of each model's own ``__call__`` (edited above it, to
# enter ``in_passes``) that a decode step's kernels were traced under
PARENT_CALLS = {
    "gym_tpu/models/cohere2_moe.py": (
        363, '            x = ParallelBlock(cfg, i, name=f"layers_{i}")'
        '(x, block_table,'),
    "gym_tpu/models/keye_vl2.py": (
        314, '            x = Block(cfg, name=f"layers_{i}")'
        '(x, block_table, cache_pos)'),
}


@pytest.mark.parametrize("path", sorted(PARENT_LINES))
def test_no_line_under_a_kernels_call_stack_moved(path):
    import ast
    with open(os.path.join(ROOT, path)) as f:
        src = f.read()
    lines, found = src.splitlines(), {}
    for top in ast.parse(src).body:
        inner = [(top.name + ".", m) for m in top.body] \
            if isinstance(top, ast.ClassDef) else [("", top)]
        for prefix, node in inner:
            name = prefix + getattr(node, "name", "")
            if isinstance(node, ast.FunctionDef) and name in \
                    PARENT_LINES[path]:
                body = "\n".join(lines[node.lineno - 1:node.end_lineno])
                found[name] = (node.lineno, hashlib.sha256(
                    body.encode()).hexdigest()[:16])
    assert found == PARENT_LINES[path]
    if path in PARENT_CALLS:
        at, text = PARENT_CALLS[path]
        assert lines[at - 1] == text


# the modules this PR adds or edits (``git diff --stat`` against the
# parent, ``gym_tpu/`` and ``perfbench/``)
TOUCHED = {
    "gym_tpu.models.qwen3_next", "gym_tpu.ops.gated_delta",
    "gym_tpu.models.decoder_parts", "gym_tpu.models.serving",
    "gym_tpu.ops.paged_attention", "gym_tpu.programs.serve_defs",
    "gym_tpu.serve.engine", "gym_tpu.serve.scheduler",
    "gym_tpu.serve.__main__", "perfbench.flops_delta",
    "perfbench.weights_qwen3_next", "perfbench.kinds.closed_qwen3_next",
    "perfbench.references.qwen3_next",
}


def test_the_trainer_imports_no_module_this_pr_adds_or_edits():
    """``gym_tpu/trainer.py``, ``train_node.py`` and the benchmark's
    training kind, imported in a process of their own: none of the
    modules above is among what they load."""
    code = ("import sys, json; import gym_tpu.trainer, gym_tpu.train_node, "
            "perfbench.kinds.fit; print(json.dumps(sorted(m for m in "
            "sys.modules if m.startswith(('gym_tpu', 'perfbench')))))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    loaded = set(json.loads(out.stdout.strip().splitlines()[-1]))
    assert "gym_tpu.trainer" in loaded and "perfbench.kinds.fit" in loaded
    assert not loaded & TOUCHED, sorted(loaded & TOUCHED)
