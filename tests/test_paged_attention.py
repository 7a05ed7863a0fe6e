"""The paged-attention kernel (``gym_tpu/ops/paged_attention.py``) against
the gather path it replaces on a TPU, on the CPU under the Pallas
interpreter (the module's ``INTERPRET`` switch, as
``tests/test_fused_attention.py`` does for the training kernels).

Under the interpreter both paths multiply in float32, so they agree to
rounding (1e-5); on the chip the kernel's products are one bf16 pass, as
XLA's default precision makes the gather path's, and the two differ by
the order of their float32 sums (the tolerance is in ``README.md``'s
serving section and ``chip_smoke.py`` holds the chip to it). Off the TPU
the dispatch picks the gather path, which is why every bit-identity test
of ``tests/test_serve_paged.py`` stands as it is.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gym_tpu.ops.paged_attention as pa
from gym_tpu.models.nanogpt import GPT, GPTConfig, decode_config
from gym_tpu.programs.registry import ProgramRegistry
from gym_tpu.programs.serve_defs import _templates
from gym_tpu.serve import engine as engine_mod
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.utils import trace

H, HD, PAGE, MB, P = 3, 8, 8, 8, 40         # 3 heads: not a power of two
C, S = H * HD, MB * PAGE
TOL = dict(atol=1e-5, rtol=1e-5)


@pytest.fixture
def interpret():
    pa.INTERPRET = True
    yield
    pa.INTERPRET = False


def _gather(q, kp, vp, bt, pos):
    """The gather path's arithmetic (``_decode_attend_paged``), alone."""
    b, t, _ = q.shape
    k = kp[bt].reshape(b, S, H, HD)
    v = vp[bt].reshape(b, S, H, HD)
    att = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(b, t, H, HD),
                     k) / np.sqrt(HD)
    wpos = pos[:, None] + jnp.arange(t)[None]
    mask = jnp.arange(S)[None, None, :] <= wpos[:, :, None]
    att = jax.nn.softmax(jnp.where(mask[:, None], att, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, t, C)


def _inputs(b, t, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, t, C)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, PAGE, C)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, PAGE, C)), jnp.float32)
    # distinct pages a row, none of them the null page
    bt = jnp.asarray(1 + rng.permutation(P - 1)[:b * MB].reshape(b, MB)
                     if b * MB < P else
                     1 + rng.integers(0, P - 1, (b, MB)), jnp.int32)
    return q, kp, vp, bt


@pytest.mark.parametrize("cursor", [0, 1, PAGE - 1, PAGE, S - 1],
                         ids=["zero", "one", "page_last", "page_first",
                              "window_last"])
def test_decode_matches_gather(interpret, cursor):
    q, kp, vp, bt = _inputs(4, 1)
    pos = jnp.asarray([cursor, 0, S - 1, cursor // 2], jnp.int32)
    out = pa.paged_attention(q, kp, vp, bt, pos, H)
    np.testing.assert_allclose(out, _gather(q, kp, vp, bt, pos), **TOL)


@pytest.mark.parametrize("t,start", [(8, 0), (32, 0), (16, 3 * PAGE),
                                     (32, 4 * PAGE), (5, 11)],
                         ids=["bucket8", "bucket32", "prefix3pages",
                              "prefix_to_window_end", "verify5_midpage"])
def test_prefill_matches_gather(interpret, t, start):
    """``b = 1, t = bucket`` from position 0 and behind a resident
    prefix read through the table; ``t = 5`` is the speculative
    verify's shape."""
    q, kp, vp, bt = _inputs(1, t, seed=t + start)
    pos = jnp.asarray([start], jnp.int32)
    out = pa.paged_attention(q, kp, vp, bt, pos, H)
    np.testing.assert_allclose(out, _gather(q, kp, vp, bt, pos), **TOL)


def test_long_prefill_spans_row_blocks(interpret):
    """More expanded rows (positions x heads) than one program takes:
    the later blocks see more pages than the first."""
    t = 2 * pa._ROWS // H + 8
    rng = np.random.default_rng(3)
    mb = -(-t // PAGE)
    q = jnp.asarray(rng.standard_normal((1, t, C)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((mb + 2, PAGE, C)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((mb + 2, PAGE, C)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(mb)[None], jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    k = kp[bt].reshape(1, mb * PAGE, H, HD)
    v = vp[bt].reshape(1, mb * PAGE, H, HD)
    att = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(1, t, H, HD),
                     k) / np.sqrt(HD)
    mask = jnp.arange(mb * PAGE)[None, :] <= jnp.arange(t)[:, None]
    att = jax.nn.softmax(jnp.where(mask[None, None], att, -jnp.inf), -1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(1, t, C)
    out = pa.paged_attention(q, kp, vp, bt, pos, H)
    np.testing.assert_allclose(out, ref, **TOL)


@pytest.mark.parametrize("t", [1, 4], ids=["decode", "chunk4"])
def test_rows_sharing_prefix_pages(interpret, t):
    q, kp, vp, bt = _inputs(3, t, seed=5)
    bt = bt.at[1, :3].set(bt[0, :3]).at[2, :2].set(bt[0, :2])
    pos = jnp.asarray([40, 29, 17], jnp.int32)
    out = pa.paged_attention(q, kp, vp, bt, pos, H)
    np.testing.assert_allclose(out, _gather(q, kp, vp, bt, pos), **TOL)
    # the shared pages hold the same numbers for every reader: row 1
    # attends row 0's first three pages exactly as row 0's own copy would
    own = bt.at[1, :3].set(jnp.asarray([P - 3, P - 2, P - 1]))
    kp2 = kp.at[own[1, :3]].set(kp[bt[0, :3]])
    vp2 = vp.at[own[1, :3]].set(vp[bt[0, :3]])
    np.testing.assert_array_equal(
        out[1], pa.paged_attention(q, kp2, vp2, own, pos, H)[1])


@pytest.mark.parametrize("null_cursor", [0, 37, S - 1])
def test_null_page_row_leaves_live_rows_unchanged(interpret, null_cursor):
    """An inactive slot (its table redirected to the null page, its
    cursor wherever it froze) beside live rows: the live rows read
    exactly what they read without it, and they match the gather path.
    Even a NaN on the null page stays with the inactive row."""
    q, kp, vp, bt = _inputs(3, 1, seed=7)
    pos = jnp.asarray([21, null_cursor, 50], jnp.int32)
    live = pa.paged_attention(q, kp, vp, bt, pos, H)
    bt0 = bt.at[1].set(0)
    kp0 = kp.at[0].set(jnp.nan)
    vp0 = vp.at[0].set(jnp.nan)
    out = pa.paged_attention(q, kp0, vp0, bt0, pos, H)
    np.testing.assert_array_equal(out[jnp.asarray([0, 2])],
                                  live[jnp.asarray([0, 2])])
    np.testing.assert_allclose(live, _gather(q, kp, vp, bt, pos), **TOL)


def test_stale_positions_past_the_cursor_cannot_poison(interpret):
    """A recycled page may hold anything past the new owner's cursor,
    NaN included; the kernel selects, it does not multiply by zero."""
    q, kp, vp, bt = _inputs(2, 1, seed=9)
    pos = jnp.asarray([10, 33], jnp.int32)
    clean = pa.paged_attention(q, kp, vp, bt, pos, H)
    kp1 = kp.at[bt[0, 1], 3:].set(jnp.nan)     # positions 11.. of row 0
    vp1 = vp.at[bt[0, 1], 3:].set(jnp.nan)
    out = pa.paged_attention(q, kp1, vp1, bt, pos, H)
    np.testing.assert_array_equal(out, clean)


# -- through the model: the write in place, the poison, the dispatch -------


def _tiny(**kw):
    cfg = GPTConfig(block_size=S, vocab_size=48, n_layer=2, n_head=H,
                    n_embd=C, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64),
                           train=False)["params"]
    dcfg = dataclasses.replace(decode_config(cfg), page_size=PAGE,
                               kv_pages=P, **kw)
    return cfg, dcfg, params


def _pool(dcfg, seed=1):
    """The engine's pool for ``dcfg``, filled with noise."""
    _, shapes = _templates(dataclasses.astuple(dcfg), 1)
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, s.shape, s.dtype)
        for k, s in zip(keys, leaves)])


def _apply(dcfg, params, cache, toks, bt, pos):
    return GPT(dcfg).apply({"params": params, "cache": cache}, toks,
                           train=False, mutable=["cache"],
                           block_table=bt, cache_pos=pos)


def test_pool_is_packed_pages(interpret):
    _, dcfg, _ = _tiny()
    pool = _pool(dcfg)
    assert {leaf.shape for leaf in jax.tree.leaves(pool)} == {(P, PAGE, C)}


@pytest.mark.parametrize("t,cursor", [(1, S - 1), (4, S - 2), (4, S - 6)],
                         ids=["decode_last", "two_past", "none_past"])
def test_positions_past_the_window_are_nan_there_only(interpret, t,
                                                      cursor):
    _, dcfg, params = _tiny()
    bt = jnp.asarray(np.arange(1, 1 + 2 * MB).reshape(2, MB), jnp.int32)
    pos = jnp.asarray([cursor, 3], jnp.int32)
    toks = jnp.asarray(np.arange(2 * t).reshape(2, t) % 48, jnp.int32)
    logits, _ = _apply(dcfg, params, _pool(dcfg), toks, bt, pos)
    finite = np.isfinite(np.asarray(logits)).all(axis=-1)      # [2, t]
    assert finite[1].all()
    np.testing.assert_array_equal(finite[0], cursor + np.arange(t) < S)
    pa.INTERPRET = False                     # the gather path, same inputs
    ref, _ = _apply(dcfg, params, _pool(dcfg), toks, bt, pos)
    keep = np.isfinite(np.asarray(ref))
    np.testing.assert_array_equal(keep.all(axis=-1), finite)
    np.testing.assert_allclose(np.asarray(logits)[keep],
                               np.asarray(ref)[keep], atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("t", [1, 6], ids=["decode", "chunk6"])
def test_pages_outside_every_table_are_bit_identical(interpret, t):
    """The write is in place and touches nothing else: after a step,
    every page no row's table names, and every position of a named page
    that was not written, holds the bits it held."""
    _, dcfg, params = _tiny()
    before = _pool(dcfg)
    bt = jnp.asarray([[3, 9, 4, 0, 0, 0, 0, 0],
                      [3, 9, 7, 11, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([17, 25], jnp.int32)
    toks = jnp.asarray(np.arange(2 * t).reshape(2, t) % 48, jnp.int32)
    _, new = _apply(dcfg, params, before, toks, bt, pos)
    written = np.zeros((P, PAGE), bool)
    for r in range(2):
        for w in range(int(pos[r]), int(pos[r]) + t):
            written[int(bt[r, w // PAGE]), w % PAGE] = True
    assert written.sum() == 2 * t
    for old, now in zip(jax.tree.leaves(before),
                        jax.tree.leaves(new["cache"])):
        old, now = np.asarray(old), np.asarray(now)
        np.testing.assert_array_equal(now[~written], old[~written])
        assert (now[written] != old[written]).any()


def _serve(params, cfg, monkeypatch, prompts, **kw):
    """Greedy streams from a paged engine whose programs are its own (a
    fresh registry: the path is decided when a program is traced), and
    the dispatch spans it recorded."""
    mark = max((r.seq for r in trace.records()), default=-1)
    reg = ProgramRegistry()
    monkeypatch.setattr(engine_mod, "default_registry", lambda: reg)
    eng = InferenceEngine(params, cfg, num_slots=3, paged=True,
                          page_size=PAGE, **kw)
    streams = {}
    for i, prompt in enumerate(prompts):
        slot, ev = eng.admit(prompt, SamplingParams(
            max_new_tokens=9 + i, top_k=1, seed=i))
        streams[slot] = [ev.token]
    while eng._active.any():
        for ev in eng.step():
            streams[ev.slot].append(ev.token)
    dispatches = [r for r in trace.records()
                  if r.seq > mark and r.name in ("serve.decode.dispatch",
                                                 "serve.prefill.dispatch")]
    return eng, [streams[s] for s in sorted(streams)], dispatches


@pytest.mark.parametrize("spec_tokens", [0, 3], ids=["plain", "spec3"])
def test_engine_on_the_kernel_serves_the_gather_path_tokens(
        monkeypatch, spec_tokens):
    cfg, _, params = _tiny()
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 48, 2 * PAGE)
    prompts = [np.concatenate([shared, rng.integers(0, 48, 5)]),
               rng.integers(0, 48, 11),
               np.concatenate([shared, rng.integers(0, 48, 2)])]
    eng_g, want, dispatches = _serve(params, cfg, monkeypatch, prompts,
                                     spec_tokens=spec_tokens)
    assert eng_g.attend_path == pa.GATHER
    assert eng_g.stats.paged_kernel_dispatches == 0
    assert {r.ids["path"] for r in dispatches} == {pa.GATHER}
    pa.INTERPRET = True
    try:
        eng_k, got, dispatches = _serve(params, cfg, monkeypatch, prompts,
                                        spec_tokens=spec_tokens)
    finally:
        pa.INTERPRET = False
    assert got == want
    assert eng_k.attend_path == pa.KERNEL
    assert eng_k.stats.paged_kernel_dispatches == len(dispatches) > 3
    assert {r.ids["path"] for r in dispatches} == {pa.KERNEL}
    assert eng_k.stats.prefix_hit_blocks >= 2      # read through the table


def test_off_the_tpu_the_dispatch_picks_the_gather_path_and_logs_it(caplog):
    _, dcfg, params = _tiny()
    assert pa.paged_attend_path(768, 16, jnp.float32,
                                jnp.float32) == pa.GATHER
    pa.report_path.cache_clear()
    toks = jnp.zeros((2, 1), jnp.int32)
    bt = jnp.asarray(np.arange(1, 1 + 2 * MB).reshape(2, MB), jnp.int32)
    with caplog.at_level(logging.INFO, logger=pa.__name__):
        _, new = _apply(dcfg, params, _pool(dcfg), toks, bt,
                        jnp.asarray([4, 9], jnp.int32))
    assert f"attention path gather for q(2, 1, {C}) float32" in caplog.text
    assert "pallas_paged" not in caplog.text
    assert "tpu_custom_call" not in jax.jit(
        lambda c: _apply(dcfg, params, c, toks, bt,
                         jnp.asarray([4, 9], jnp.int32))
    ).lower(_pool(dcfg)).as_text()


@pytest.mark.parametrize("on_tpu,n_embd,page,dtype,kv_dtype,want", [
    (True, 768, 16, jnp.float32, jnp.float32, pa.KERNEL),
    (True, 1024, 16, jnp.float32, jnp.float32, pa.KERNEL),
    (True, 768, 128, jnp.float32, jnp.float32, pa.KERNEL),
    (False, 768, 16, jnp.float32, jnp.float32, pa.GATHER),
    (True, 768, 16, jnp.float32, jnp.int8, pa.GATHER),      # its dtype
    (True, 768, 16, jnp.bfloat16, jnp.bfloat16, pa.GATHER),
    (True, 96, 16, jnp.float32, jnp.float32, pa.GATHER),    # lanes
    (True, 768, 4, jnp.float32, jnp.float32, pa.GATHER),    # sublanes
    (True, 768, 24, jnp.float32, jnp.float32, pa.GATHER),   # chunk
    (True, 768, 256, jnp.float32, jnp.float32, pa.GATHER),
], ids=["base", "medium", "page128", "off_tpu", "int8_pool", "bf16",
        "lanes", "sublanes", "chunk", "page256"])
def test_the_path_follows_backend_shape_and_dtype(monkeypatch, on_tpu,
                                                  n_embd, page, dtype,
                                                  kv_dtype, want):
    monkeypatch.setattr(pa, "_on_tpu", lambda: on_tpu)
    assert pa.paged_attend_path(n_embd, page, dtype, kv_dtype) == want


def test_int8_pool_stays_on_the_gather_path_under_the_interpreter(
        interpret):
    _, dcfg, params = _tiny(kv_dtype="int8")
    _, shapes = _templates(dataclasses.astuple(dcfg), 1)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    bt = jnp.asarray(np.arange(1, 1 + MB)[None], jnp.int32)
    text = jax.jit(lambda c: _apply(
        dcfg, params, c, jnp.zeros((1, 4), jnp.int32), bt,
        jnp.zeros((1,), jnp.int32))).lower(cache).as_text()
    assert "paged_attn" not in text
