"""A prefill in passes (``gym_tpu/models/cohere2_moe.py:prefill_in_passes``,
which ``Cohere2Moe`` and ``KeyeVL2`` run a bucket longer than their
``prefill_rows`` through) against the same bucket run whole (the pass set
to the bucket, the program as it was before there were passes), at the
configuration files' ``rehearse`` sizes on the CPU in float32.

* logits at ``last_pos`` and the pools' contents at every valid position,
  for a prompt of exactly a pass, one under, one over, of the whole bucket,
  and for a suffix behind a prefix another call wrote;
* a pool whose every page but the null one holds NaN: the skipped passes
  leave their pages as they were, and nothing reads them, in the prefill or
  in the decode step after it;
* the prefill program holds its layers only inside ONE ``cond`` of the
  loop over passes, and nothing but the slice of the tokens touches an
  array as long as the bucket;
* the engine counts the positions the passes ran
  (``EngineStats.prefill_tokens_run``), a model that does not say how it
  takes a bucket counts its buckets.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gym_tpu.ops.paged_attention as pa
from gym_tpu.models import serving
from gym_tpu.programs import serve_defs
from gym_tpu.serve.engine import (InferenceEngine, SamplingParams,
                                  prefill_positions_run, prompt_bucket)
from perfbench import weights_keye, weights_moe
from perfbench.kinds import closed_keye, closed_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, PAGES = 4, 80
TOL = 1e-5
# name: (config builder, weights, positions a pass, the bucket, what makes
# the rehearsal's attends and expert products run in blocks as the cell's)
MODELS = {
    "command-a-plus": (closed_model.model_config, weights_moe, 16, 64,
                       {"attn_query_block": 8, "moe_chunk_rows": 16}),
    # a pass above ``sparse_attention.ROWS_MAX_T``: the block attend, as
    # on the chip
    "keye-vl2-30b-a3b": (closed_keye.model_config, weights_keye, 32, 128,
                         {"attn_query_block": 16, "attn_key_block": 8}),
}
NAMES = sorted(MODELS)


def _sizes(name, **over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"], "dtype": "float32",
            "max_position_embeddings": 256, **over}


def _served(name, rows, **over):
    """``(config with the pass set, params as served)``."""
    make, weights, _rows, _bucket, blocks = MODELS[name]
    sizes = _sizes(name, **over)
    cfg = dataclasses.replace(make(sizes).decode_config(), page_size=PAGE,
                              kv_pages=PAGES, prefill_rows=rows, **blocks)
    return cfg, cfg.prepare_params(weights.make_params(sizes, 11))


@pytest.fixture(scope="module", params=NAMES)
def served(request):
    """``(name, config with the pass set, params, pass, bucket)``."""
    _make, _weights, rows, bucket, _blocks = MODELS[request.param]
    return (request.param, *_served(request.param, rows), rows, bucket)


def _pools(cfg, fill=0.0):
    """The model's ``cache`` collection: ``fill`` everywhere but the null
    page, which is zeros."""
    _, tpl = serve_defs._templates(cfg.program_key(), 1)
    return jax.tree.map(
        lambda a: jnp.full(a.shape, fill, a.dtype).at[0].set(0), tpl)


def _table(cfg, first=1):
    """One row's block table: the pages from ``first`` on, in order."""
    mb = cfg.block_size // PAGE
    return jnp.arange(first, first + mb, dtype=jnp.int32)[None]


@functools.lru_cache(maxsize=None)
def _prefill_fn(key):
    model = serving.config_from_key(key).build()

    @jax.jit
    def run(params, cache, tokens, table, start, last):
        return model.apply({"params": params, "cache": cache}, tokens,
                           train=False, mutable=["cache"], block_table=table,
                           cache_pos=start, last_pos=last)

    return run


def _prefill(cfg, params, cache, prompt, bucket, start=0):
    """``prompt`` padded to ``bucket`` behind ``start`` resident positions:
    ``(logits [V] at its last position, the pools)``."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    logits, out = _prefill_fn(cfg.program_key())(
        params, cache, jnp.asarray(tokens), _table(cfg),
        jnp.asarray([start], jnp.int32), jnp.int32(len(prompt) - 1))
    return np.asarray(logits[0]), out["cache"]


def _rows(cfg, cache, n):
    """The first ``n`` positions of the row, from every pool."""
    table = np.asarray(_table(cfg))[0]
    return [np.asarray(leaf)[table].reshape(len(table) * PAGE, -1)[:n]
            for leaf in jax.tree.leaves(cache)]


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n)


def _lengths(rows, bucket):
    return {"a_pass": rows, "one_under": rows - 1, "one_over": rows + 1,
            "whole_bucket": bucket}


@pytest.mark.parametrize("case", ["a_pass", "one_under", "one_over",
                                  "whole_bucket"])
def test_passes_give_the_whole_buckets_logits_and_pools(served, case):
    _name, cfg, params, rows, bucket = served
    n = _lengths(rows, bucket)[case]
    prompt = _prompt(n, n)
    whole = dataclasses.replace(cfg, prefill_rows=bucket)
    assert cfg.prefill_pass(bucket) == rows
    assert whole.prefill_pass(bucket) == bucket
    want, pools_w = _prefill(whole, params, _pools(whole), prompt, bucket)
    got, pools = _prefill(cfg, params, _pools(cfg), prompt, bucket)
    assert np.abs(got - want).max() < TOL
    for a, b in zip(_rows(cfg, pools, n), _rows(cfg, pools_w, n)):
        assert a.any() and np.abs(a - b).max() < TOL
    # a skipped pass wrote nothing: its positions are the zeros they were
    ran = -(-n // rows) * rows
    for a in _rows(cfg, pools, bucket):
        assert not a[ran:].any()


def test_a_suffix_in_passes_reads_the_prefix_from_the_pages(served):
    """A prefix hit: the call starts at ``cache_pos`` 12 behind positions
    another call wrote, its passes attend to them through the pages."""
    _name, cfg, params, rows, bucket = served
    start, n = 12, bucket - rows - 3
    prompt = _prompt(start + n, 5)
    whole = dataclasses.replace(cfg, prefill_rows=bucket)
    outs = []
    for c in (whole, cfg):
        _, pools = _prefill(c, params, _pools(c), prompt[:start], 16)
        outs.append(_prefill(c, params, pools, prompt[start:], bucket,
                             start))
    (want, pools_w), (got, pools) = outs
    assert np.abs(got - want).max() < TOL
    for a, b in zip(_rows(cfg, pools, start + n),
                    _rows(cfg, pools_w, start + n)):
        assert a.any() and np.abs(a - b).max() < TOL
    alone, _ = _prefill(cfg, params, _pools(cfg), prompt[start:], bucket)
    assert np.abs(alone - want).max() > 1e-3        # the prefix was read


def test_pages_a_skipped_pass_left_are_read_by_nothing(served, monkeypatch):
    """Every page but the null one holds NaN before the prefill (a free
    page holds what its last row left). The passes that ran wrote over
    theirs; the skipped ones' stay NaN, past the row's cursor. The logits
    are the clean pool's, and the decode step after it, which writes its
    position before it attends, is finite and the clean pool's too."""
    name, cfg, params, rows, bucket = served
    if name == "command-a-plus":
        # the page walk masks what lies past the cursor (``vok``); the
        # gather of the row's window, the CPU's path, multiplies it by 0
        monkeypatch.setattr(pa, "INTERPRET", True)
        _prefill_fn.cache_clear()        # traced again, under the kernel
        # and tracing the kernel for the interpreter is slow: one layer
        # of each kind, a bucket of four passes of 8
        rows, bucket = 8, 32
        cfg, params = _served(name, rows, num_hidden_layers=2, layer_types=[
            "sliding_attention", "full_attention"])
    n = rows + 1
    prompt = _prompt(n, 9)
    want, clean = _prefill(cfg, params, _pools(cfg), prompt, bucket)
    got, dirty = _prefill(cfg, params, _pools(cfg, np.nan), prompt, bucket)
    np.testing.assert_allclose(got, want, atol=TOL)
    held = _rows(cfg, dirty, bucket)
    assert all(np.isfinite(a[:2 * rows]).all() for a in held)
    assert all(np.isnan(a[2 * rows:]).all() for a in held)

    @jax.jit
    def decode(cache, tok):
        logits, _ = cfg.build().apply(
            {"params": params, "cache": cache}, tok, train=False,
            mutable=["cache", "counters"], block_table=_table(cfg),
            cache_pos=jnp.asarray([n], jnp.int32))
        return logits[0, 0]

    def step(cache):
        return np.asarray(decode(cache, jnp.asarray(
            [[int(want.argmax())]], jnp.int32)))

    after = step(dirty)
    assert np.isfinite(after).all()
    np.testing.assert_allclose(after, step(clean), atol=TOL)
    _prefill_fn.cache_clear()


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


def test_the_program_runs_its_layers_inside_one_cond_of_the_scan(served):
    """The engine's prefill program of a bucket of three passes: the loop
    over passes holds ONE ``cond`` and no product outside it, so a
    skipped pass costs a comparison; and no operation but the slice of a
    pass's tokens reads or makes an array as long as the bucket (96 or
    48: no other size of these models)."""
    _name, cfg, _params, rows, _bucket = served
    bucket = 3 * rows
    pdef = serve_defs.paged_prefill_def(cfg.program_key(), bucket, 2)
    jaxpr = jax.make_jaxpr(pdef.builder())(*pdef.args).jaxpr
    scans = [e for e in _walk(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == 3
             and any(s.primitive.name == "cond"
                     for s in e.params["jaxpr"].jaxpr.eqns)]
    assert len(scans) == 1
    body = scans[0].params["jaxpr"].jaxpr.eqns
    assert [e.primitive.name for e in body].count("cond") == 1
    assert not any(e.primitive.name in ("dot_general", "scatter", "gather",
                                        "ragged_dot", "pjit", "while")
                   for e in body)
    carriers = {"scan", "cond", "pjit", "jit", "while", "closed_call",
                "core_call", "custom_jvp_call", "custom_vjp_call"}
    over = [e for e in _walk(jaxpr) if e.primitive.name not in carriers
            and any(bucket in getattr(v.aval, "shape", ())
                    for v in list(e.invars) + list(e.outvars))]
    assert [e.primitive.name for e in over] == ["dynamic_slice"]
    assert over[0].outvars[0].aval.shape == (1, rows)


# -- the engine's count -----------------------------------------------------


@pytest.mark.parametrize("n", [70, 130, 160])
def test_the_engine_counts_the_positions_the_passes_ran(served, n):
    """``prefill_tokens_run`` is the prompt rounded up to whole passes,
    ``prefill_tokens`` its bucket; and the request's tokens are those of
    an engine whose model runs the bucket whole."""
    _name, cfg, params, rows, _bucket = served
    toks = []
    for c in (cfg, dataclasses.replace(cfg, prefill_rows=256)):
        eng = InferenceEngine(params, c, num_slots=1, paged=True,
                              page_size=PAGE, kv_pages=PAGES)
        slot, ev = eng.admit(_prompt(n, n), SamplingParams(
            max_new_tokens=4, top_k=1))
        out = [ev.token]
        while not ev.finished:
            ev = [e for e in eng.step() if e.slot == slot][-1]
            out.append(ev.token)
        toks.append(out)
        bucket = prompt_bucket(n, 256)
        assert eng.stats.prefill_tokens == bucket
        if c is cfg:
            assert eng.stats.prefill_tokens_run == -(-n // rows) * rows
            assert eng.stats.prefill_tokens_run < bucket
        else:
            assert eng.stats.prefill_tokens_run == bucket
    assert toks[0] == toks[1]


@pytest.mark.parametrize("n", [70, 160])
def test_the_engine_adds_what_a_config_counts_of_a_prefill(monkeypatch, n):
    """An admission adds ``config.prefill_counted(bucket, start, suffix)``
    to the model's counters, beside what the decode steps count; on the
    gather path (off the TPU) the grouped kernel's count is empty."""
    cfg, params = _served("command-a-plus", 16)
    assert cfg.prefill_counted(prompt_bucket(n, 256), 0, n) == {}
    asked = []
    monkeypatch.setattr(
        type(cfg), "prefill_counted", lambda _cfg, *a: asked.append(a) or {
            "layers_1/self_attn/gqa_chunks": np.asarray([7, 5])})
    eng = InferenceEngine(params, cfg, num_slots=2, paged=True,
                          page_size=PAGE, kv_pages=PAGES)
    for k in (1, 2):
        eng.admit(_prompt(n, n + k), SamplingParams(max_new_tokens=2,
                                                    top_k=1))
        eng.step()
        counted = eng.stats.model_counters
        assert counted["layers_1/self_attn/gqa_chunks"].tolist() == [
            7 * k, 5 * k]
    assert asked == [(prompt_bucket(n, 256), 0, n)] * 2
    assert counted["layers_0/self_attn/pages"][0] > 0


@pytest.mark.parametrize("n", [3, 11, 40])
def test_a_model_that_does_not_say_counts_its_buckets(n):
    """GPT-2's config has no ``prefill_pass``: its prefills run, and are
    counted as running, their whole buckets."""
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    cfg = GPTConfig(block_size=64, vocab_size=256, n_layer=1, n_head=2,
                    n_embd=32, dropout=0.0)
    assert not hasattr(cfg, "prefill_pass")
    assert prefill_positions_run(cfg, 64, n) == 64
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int32),
                           train=False)["params"]
    eng = InferenceEngine(params, cfg, num_slots=1, paged=True,
                          page_size=PAGE, kv_pages=40)
    eng.admit(_prompt(n, n), SamplingParams(max_new_tokens=2, top_k=1))
    assert eng.stats.prefill_tokens == prompt_bucket(n, 64)
    assert eng.stats.prefill_tokens_run == eng.stats.prefill_tokens


@pytest.mark.parametrize("bucket,suffix,rows,want", [
    (32768, 7608 + 2000, 2048, 10240), (32768, 32768, 2048, 32768),
    (16384, 8193, 1024, 9216), (1024, 3, 1024, 1024),
    (36864, 33000, 4096, 36864), (1, 1, 2048, 1)])
def test_positions_run_are_the_prompt_in_whole_passes(bucket, suffix, rows,
                                                      want):
    from gym_tpu.models.keye_vl2 import KeyeVL2Config
    cfg = KeyeVL2Config(prefill_rows=rows)
    assert prefill_positions_run(cfg, bucket, suffix) == want
