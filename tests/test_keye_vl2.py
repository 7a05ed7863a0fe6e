"""Keye-VL-2.0's language model (``gym_tpu/models/keye_vl2.py``) through
the serving engine against its plain reference
(``perfbench/references/keye_vl2.py``: float32, a full forward over the
whole sequence, no cache, nothing imported from the program), at a small
size on the CPU with seeded random weights.

Sizes: the configuration file's ``rehearse`` preset (hidden 64, 8 query
heads over 2 key-value heads of 16, an index of 4 heads of 8 that keeps
8 keys a query, 16 softmax-routed experts of which 4 are held, 4 a token,
256 rows of vocabulary, 2 layers) with pages of 4 positions, query blocks
of 16 and key blocks of 8, so that a row of seventy positions is far past
``topk`` and a prefill of it takes several query blocks, each over a
different number of key blocks.

* engine prefill then paged decode equals the reference's logits at every
  decoded position, in float32 (to rounding) and in bfloat16 (within a
  tolerance the fp8 control exceeds);
* the same through ``Scheduler`` for rows of mixed length;
* each planted wrong reading of the description fails the limits of the
  cell's rehearsal (the kind's own ``judge`` and ``verdict_rows``);
* the counters a decode step returns: kept and resident keys, rows past
  ``topk``;
* the config through a program key and a dict; the seeded weights have
  the decoder's own shapes.

The selection and the attend alone: ``tests/test_paged_attention_gqa.py``;
the softmax-routed shares adding up: ``tests/test_moe.py``; the third pool
array under copy-on-write, scrub and parking: ``tests/test_serve_paged.py``.
"""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.models import serving
from gym_tpu.models.keye_vl2 import KeyeVL2Config, rotate_half
from gym_tpu.ops import paged_attention as pa
from gym_tpu.ops import sparse_attention as sa
from gym_tpu.programs.registry import ProgramRegistry
from gym_tpu.serve import engine as engine_mod
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from perfbench import weights_keye
from perfbench.kinds import closed_keye
from perfbench.kinds.closed_model import verdict_rows
from perfbench.references import keye_vl2 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
PAGE = 4
# float32 program against float32 reference: the order of additions
F32_TOL = 1e-4
# bfloat16 program against the float32 reference on logits of spread 1.0,
# as the MEAN distance over the compared logits; the fp8 control reads
# several times that. The widest distance is no yardstick: an index score
# or a router score within a rounding of the next flips a kept key or an
# expert and moves single logits in either precision.
BF16_TOL = 0.08


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _sizes(dtype="float32", **over):
    config = _load("configs", "keye-vl2-30b-a3b.json")
    return {**config, **config["rehearse"], "dtype": dtype, **over}


def _config(sizes):
    return dataclasses.replace(closed_keye.model_config(sizes),
                               attn_query_block=16, attn_key_block=8)


@pytest.fixture(scope="module")
def f32():
    sizes = _sizes()
    return sizes, _config(sizes), weights_keye.make_params(sizes, 7)


@pytest.fixture(scope="module")
def bf16():
    sizes = _sizes("bfloat16")
    return sizes, _config(sizes), weights_keye.make_params(sizes, 7)


@pytest.fixture(params=["gather", "kernel"])
def index(request, monkeypatch):
    """Both paths of a decode step's index scores
    (``sparse_attention.index_path``): the gather of the row's table, as
    everywhere off the TPU, and the Pallas walk of the row's live pages
    under the interpreter, in chunks of 8 pages so that the rehearsal's
    table of 32 is whole chunks. The programs are the test's own (a fresh
    registry: the path is decided when a program is traced). Returns the
    list the kernel's calls are noted in."""
    calls = []
    if request.param == "kernel":
        monkeypatch.setattr(pa, "INTERPRET", True)
        monkeypatch.setattr(sa, "INDEX_CHUNK", 8)
        reg = ProgramRegistry()
        monkeypatch.setattr(engine_mod, "default_registry", lambda: reg)
        walk = sa.index_keys_paged
        monkeypatch.setattr(
            sa, "index_keys_paged",
            lambda *a: calls.append(a[0].shape) or walk(*a))
    return request.param, calls


def _engine(cfg, params, slots=2, kv_pages=80, page=PAGE):
    return InferenceEngine(params, cfg, num_slots=slots, page_size=page,
                           kv_pages=kv_pages)


def _prompt(n, seed, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n)


def _greedy(eng, prompt, n_new):
    """One greedy request: its tokens and the logits of every decode
    step ([n_new - 1, V]: the prefill returns a token, not logits)."""
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new,
                                                top_k=1))
    toks, logits = [ev.token], []
    while not ev.finished:
        ev = [e for e in eng.step() if e.slot == slot][-1]
        toks.append(ev.token)
        logits.append(eng.last_logits[slot].copy())
    return toks, np.stack(logits)


def _reference_logits(params, sizes, prompt, toks, **kw):
    return np.asarray(ref.served_logits(
        params, sizes, list(prompt), toks, pad_multiple=32, **kw))


# -- the engine against the reference --------------------------------------

ROWS = [(3, 14), (13, 12), (30, 6), (70, 10)]
ROW_IDS = ["under_topk", "across_topk", "past_topk", "several_blocks"]


@pytest.mark.parametrize("plen,n_new", ROWS, ids=ROW_IDS)
def test_prefill_then_paged_decode_equals_the_reference_f32(f32, index,
                                                            plen, n_new):
    """Float32 weights and pools: every decoded position's logits equal
    the full forward's to rounding (so every query kept the keys the
    reference kept); the prefill's token is the reference's best. Through
    the gathered table and through the kernel alike."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen)
    eng = _engine(cfg, params)
    assert eng.attend_path == pa.SPARSE
    toks, logits = _greedy(eng, prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL
    assert want.std() > 0.5          # logits worth comparing
    assert bool(index[1]) == (index[0] == "kernel")


@pytest.mark.parametrize("plen,n_new", ROWS[1:], ids=ROW_IDS[1:])
def test_prefill_then_paged_decode_equals_the_reference_bf16(bf16, plen,
                                                             n_new):
    """As served (bfloat16 weights and pools): near the float32
    reference, and nearer than the reference's own fp8 control."""
    sizes, cfg, params = bf16
    prompt = _prompt(plen, plen)
    toks, logits = _greedy(_engine(cfg, params), prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    fp8 = _reference_logits(params, sizes, prompt, toks, mode="fp8")
    mean = np.abs(logits - want[1:]).mean()
    assert mean < BF16_TOL
    assert np.abs(fp8 - want).mean() > 1.5 * mean


def test_scheduler_serves_rows_of_mixed_length_as_the_reference(f32, index):
    """Five greedy requests of mixed length through three slots and one
    pool (admissions between decode steps, a step always in flight):
    every served token is the reference's best at its position, whichever
    path the decode step's index takes."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=3, kv_pages=120)
    sched = Scheduler(eng, max_queue=8)
    shapes = [(5, 9), (40, 7), (21, 11), (66, 5), (12, 12)]
    prompts = [_prompt(plen, 50 + i) for i, (plen, _n) in enumerate(shapes)]
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n, top_k=1))
               for p, (_l, n) in zip(prompts, shapes)]
    for _ in range(2000):
        if all(h.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for h in handles):
            break
        sched.step()
    for h, p, (_l, n) in zip(handles, prompts, shapes):
        toks = h.result(timeout=1)
        assert len(toks) == n
        gaps = ref.served_gaps(params, sizes, list(p), toks,
                               pad_multiple=32)
        assert gaps.max() < F32_TOL
    assert eng.stats.kv_blocks_in_use == 0
    assert bool(index[1]) == (index[0] == "kernel")


# -- planted faults ---------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """Six greedy requests through the engine at the rehearsal's sizes
    (float32, as the rehearsal runs), and the context the kind's ``judge`` reads."""
    config = _load("configs", "keye-vl2-30b-a3b.json")
    traffic = _load("traffic", "serve-closed-longdoc.json")
    limits = _load("limits", "keye-vl2-30b-a3b.serve-closed-longdoc.json")
    sizes = {**config, **config["rehearse"]}
    ctx = {"traffic": {**traffic, **traffic["rehearse"]}, "sizes": sizes,
           "args": types.SimpleNamespace(seed=5), "devices": jax.devices(),
           "limits": limits["rehearse"]}
    eng = InferenceEngine(weights_keye.make_params(sizes, 5),
                          closed_keye.model_config(sizes), num_slots=2,
                          page_size=16, kv_pages=48)
    rng, picked = np.random.default_rng(5), []
    for n in (24, 37, 80, 52, 66, 29):
        prompt = rng.integers(0, sizes["vocab_size"], n)
        toks, _lg = _greedy(eng, prompt, 16)
        picked.append({"prompt": prompt.tolist(), "tokens": toks})
    sound = closed_keye.judge(ctx, picked)
    sound["lower"] = closed_keye.judge(ctx, picked, "fp8")
    return ctx, picked, sound


def test_sound_tokens_pass_and_the_fp8_control_fails(served):
    ctx, _picked, sound = served
    rows = verdict_rows(ctx, sound, 0, [])
    assert all(r["ok"] for r in rows), rows
    assert sound["tokens"] == 6 * 16 and sound["lower"]["mean"] > 0
    control = dict(sound["lower"], lower=sound["lower"])
    rows = verdict_rows(ctx, control, 0, [])
    assert rows[1]["name"] == "served_logit_gap_vs_fp8"
    assert rows[1]["value"] == 1.0 and not rows[1]["ok"]


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_wrong_reading_fails_the_rehearsals_limits(served, fault):
    """A program with one wrong reading of the description would serve
    the tokens that reading puts first: at least one limit of the cell's
    rehearsal refuses them."""
    ctx, picked, sound = served
    wrong = closed_keye.judge(ctx, picked, faults=(fault,))
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    assert not all(r["ok"] for r in rows), rows


def test_reference_refuses_an_unknown_fault(f32):
    sizes, _cfg, params = f32
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, sizes, np.arange(8), [7], faults=("typo",))


# -- counters, config, weights -------------------------------------------------


def test_decode_steps_count_kept_and_resident_keys(f32):
    """What ``/stats`` serves as ``model_counters``: over a request's
    decode steps and live rows, the keys kept (at most ``topk`` a row)
    and resident, and the rows past ``topk``; beside the expert layer's
    ``picks``, ``hit`` and ``tokens``."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params)
    _toks, logits = _greedy(eng, _prompt(5, 2), 9)
    steps = len(logits)
    c = eng.stats.model_counters
    # the eight steps write positions 5..12: 6..13 resident, 8 kept at most
    resident = [p + 1 for p in range(5, 5 + steps)]
    for i in range(sizes["num_hidden_layers"]):
        assert np.asarray(c[f"layers_{i}/self_attn/keys"]).tolist() == [
            sum(min(r, 8) for r in resident), sum(resident)]
        assert int(c[f"layers_{i}/self_attn/sparse_rows"]) == sum(
            r > 8 for r in resident)
        assert np.asarray(c[f"layers_{i}/self_attn/pages"]).tolist() == [
            sum(-(-r // PAGE) for r in resident), 0]
        assert int(c[f"layers_{i}/mlp/tokens"]) == steps
        assert np.asarray(c[f"layers_{i}/mlp/picks"]).shape == (4,)


def test_config_round_trips_through_a_program_key_and_a_dict(f32):
    _sizes_, cfg, _p = f32
    paged = dataclasses.replace(cfg.decode_config(), page_size=4,
                                kv_pages=40)
    key = paged.program_key()
    hash(key)
    assert key[0] == "KeyeVL2"
    assert serving.config_from_key(key) == paged
    again = serving.config_from_dict(
        json.loads(json.dumps(dataclasses.asdict(paged))) | {"new_key": 1})
    assert again == paged
    assert "KeyeVL2" in paged.program_tag()
    assert set(paged.attend_paths()) == {pa.SPARSE}
    assert serving.attend_path_id(paged) == "sparse_topk"
    with pytest.raises(ValueError, match="whole groups"):
        KeyeVL2Config(num_attention_heads=12, num_key_value_heads=8)
    with pytest.raises(ValueError, match="served, not trained"):
        paged.build().apply({}, jnp.zeros((1, 1), jnp.int32), train=True)


def test_weights_from_the_seed_have_the_decoders_own_shapes(bf16):
    """``perfbench/weights_keye.py`` imports nothing of the program: its
    tree is the decoder's own, name for name and shape for shape."""
    sizes, cfg, params = bf16
    paged = dataclasses.replace(cfg.decode_config(), page_size=4,
                                kv_pages=8)
    own = jax.eval_shape(lambda: paged.build().init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 1), jnp.int32),
        train=False, block_table=jnp.zeros((1, 32), jnp.int32),
        cache_pos=jnp.zeros((1,), jnp.int32)))["params"]
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), own)
            == jax.tree.map(lambda x: (x.shape, x.dtype), params))
    other = weights_keye.make_params(sizes, 8)
    leaf = lambda t: np.asarray(                # noqa: E731
        t["layers_1"]["self_attn"]["index_q_proj"], np.float32)
    assert np.abs(leaf(other) - leaf(params)).max() > 0


def test_rotary_turns_halves():
    """Lane ``i`` with lane ``i + d/2``, by ``pos * theta ** (-2i / d)``;
    position 0 leaves the vector as it is."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 8), jnp.float32)
    pos = jnp.asarray([[0, 1, 5]])
    y = np.asarray(rotate_half(x, pos[:, :, None], 100.0))
    np.testing.assert_allclose(y[0, 0], np.asarray(x)[0, 0], atol=1e-6)
    ang = 5 * 100.0 ** (-np.arange(0, 8, 2) / 8)
    x1, x2 = np.asarray(x)[0, 2, :, :4], np.asarray(x)[0, 2, :, 4:]
    np.testing.assert_allclose(
        y[0, 2], np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                 x2 * np.cos(ang) + x1 * np.sin(ang)], -1),
        atol=1e-5)
