"""Brumby-14B-Base (``gym_tpu/models/brumby.py``: power retention, a
recurrent state of fixed size a row in place of a key-value cache) through
the serving engine against its plain reference
(``perfbench/references/brumby.py``: float32, the ATTENTION form over the
whole sequence, no state, nothing imported from the program), at a small
size on the CPU with seeded random weights.

Sizes: the configuration file's ``rehearse`` preset (hidden 64, 8 query
heads over 2 key-value heads of 16: 136 features a head on 144 places, a
SwiGLU of 128, 256 rows of vocabulary, 2 layers, gates of 0.5 to 0.98)
with prefill chunks of 16, so that a prompt of seventy tokens in its
bucket of 128 is eight chunks of which three and a half are padding.

* engine prefill then decode through the state equals the reference's
  logits at every decoded position, in float32 (to rounding) and in
  bfloat16 (within a tolerance the fp8 control exceeds): prompts shorter
  than their bucket, prefills of several chunks;
* rows of unequal length decoding together through ``Scheduler``, on
  blocks other rows just left;
* a row admitted onto the one block another row just left; park, another
  row's steps, resume; a quarantined block reused;
* each planted wrong reading of the description (in the reference) and
  each planted fault of the cache manager (in the program) fails a limit
  of the cell's rehearsal (the kind's own ``judge`` and ``verdict_rows``);
* the counters a decode step returns; the config through a program key
  and a dict, and what it refuses; the seeded weights have the decoder's
  own shapes.

The feature map and the three forms alone: ``tests/test_power_retention.py``;
the engine's manager with this model and a page model side by side:
``tests/test_serve_state_pool.py``.
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
from gym_tpu.models.brumby import BrumbyConfig
from gym_tpu.ops import paged_attention as pa
from gym_tpu.ops import power_retention as pr
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from perfbench import weights_brumby
from perfbench.kinds import closed_brumby
from perfbench.kinds.closed_model import verdict_rows
from perfbench.references import brumby as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "brumby-14b-base.serve-closed-longgen"
# float32 program against float32 reference. ISSUE 33 asks for 1e-5 or the
# reason it cannot: the program sums a row's past in the order of the
# recurrence (and of its chunks), the reference in the order of one matrix
# product over all keys, and on logits of spread 1.0 the two orders lie up
# to 1.5e-5 apart (four rows, below)
F32_TOL = 5e-5
# bfloat16 program against the float32 reference on logits of spread 1.0,
# as the MEAN distance over the compared logits
BF16_TOL = 0.08


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _sizes(dtype="float32", **over):
    config = _load("configs", "brumby-14b-base.json")
    return {**config, **config["rehearse"], "dtype": dtype, **over}


def _config(sizes, **over):
    return dataclasses.replace(closed_brumby.model_config(sizes),
                               retention_chunk=16, **over)


@pytest.fixture(scope="module")
def f32():
    sizes = _sizes()
    return sizes, _config(sizes), weights_brumby.make_params(sizes, 7)


@pytest.fixture(scope="module")
def bf16():
    sizes = _sizes("bfloat16")
    return sizes, _config(sizes), weights_brumby.make_params(sizes, 7)


def _engine(cfg, params, slots=2, **kw):
    return InferenceEngine(params, cfg, num_slots=slots, **kw)


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


def _state(eng, block):
    c = eng._cache["state_0"]
    assert sorted(c) == ["S", "z"]
    return np.asarray(c["S"][block]), np.asarray(c["z"][block])


# -- the engine against the reference --------------------------------------

ROWS = [(3, 14), (13, 12), (30, 6), (70, 10)]
ROW_IDS = ["bucket4", "short_of_bucket16", "two_chunks", "eight_chunks"]


@pytest.mark.parametrize("plen,n_new", ROWS, ids=ROW_IDS)
def test_prefill_then_decode_through_the_state_equals_the_reference_f32(
        f32, plen, n_new):
    """Float32 weights and state: every decoded position's logits equal
    the attention form's full forward to rounding, so the prefill left
    the state of the prompt alone (its bucket's padding did not touch
    it) and every step decayed, updated and read it as the description
    says; the prefill's token is the reference's best."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen)
    eng = _engine(cfg, params)
    assert eng.attend_path == pa.RETENTION == "retention"
    toks, logits = _greedy(eng, prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL
    assert want.std() > 0.5          # logits worth comparing


@pytest.mark.parametrize("plen,n_new", [(70, 6), (33, 5), (120, 4)],
                         ids=["last_pass_all_padding", "two_of_four_passes",
                              "four_of_four_passes"])
def test_a_prefill_of_several_passes_carries_the_state_between_them(
        f32, plen, n_new):
    """Passes of 32 positions (two chunks each) through all layers, the
    rows' states carried from pass to pass: a prompt of 70 in its bucket
    of 128 ends inside the third pass and the fourth, all padding, is
    skipped; the first token is read in the pass that holds the prompt's
    last position. As one pass of the whole bucket gives (to rounding:
    the same chunks in the same order, products over fewer rows at once)
    and as the reference says."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, 900 + plen)
    whole = _greedy(_engine(cfg, params), prompt, n_new)
    toks, logits = _greedy(
        _engine(dataclasses.replace(cfg, prefill_rows=32), params), prompt,
        n_new)
    assert toks == whole[0]
    assert np.abs(logits - whole[1]).max() < F32_TOL
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL


@pytest.mark.parametrize("plen,n_new", ROWS[1:], ids=ROW_IDS[1:])
def test_prefill_then_decode_equals_the_reference_bf16(bf16, plen, n_new):
    """As served (bfloat16 weights and activations, float32 state and
    gates): near the float32 reference, and nearer than the reference's
    own fp8 control."""
    sizes, cfg, params = bf16
    assert cfg.weights_dtype == "bf16" and cfg.kv_dtype == "f32"
    prompt = _prompt(plen, plen)
    toks, logits = _greedy(_engine(cfg, params), prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    fp8 = _reference_logits(params, sizes, prompt, toks, mode="fp8")
    mean = np.abs(logits - want[1:]).mean()
    assert mean < BF16_TOL
    assert np.abs(fp8 - want).mean() > 1.5 * mean


def test_scheduler_serves_rows_of_unequal_length_as_the_reference(f32):
    """Five greedy requests of unequal length through three slots and
    four blocks (admissions between decode steps, a step always in
    flight, the fourth and fifth on blocks the first rows left): every
    served token is the reference's best at its position."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=3, kv_pages=4)
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
    assert eng.stats.prefix_hit_blocks == 0


def test_a_row_admitted_onto_the_block_another_left_starts_from_zero(f32):
    """One slot, one block beside the null block: the second request is
    admitted onto the block that still holds the first row's memory (it
    is not zeroed when freed), and equals the reference all the same: a
    row at cursor 0 reads its block as zeros."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=1, kv_pages=2)
    _greedy(eng, _prompt(41, 1), 6)
    left_S, _z = _state(eng, 1)
    assert np.abs(left_S).max() > 0           # the first row's memory
    for plen, n_new in ((9, 8), (33, 5)):
        prompt = _prompt(plen, 100 + plen)
        toks, logits = _greedy(eng, prompt, n_new)
        assert int(eng._bt.max()) <= 1
        want = _reference_logits(params, sizes, prompt, toks)
        assert toks[0] == int(want[0].argmax())
        assert np.abs(logits - want[1:]).max() < F32_TOL


def test_park_then_another_rows_steps_then_resume_continues_identically(f32):
    """A row parked after four steps keeps its block pinned and
    untouched while another row is admitted and decodes in its slot;
    resumed, it continues with the tokens and logits of the run that was
    never parked, and of the reference."""
    sizes, cfg, params = f32
    prompt, n_new = _prompt(37, 5), 12
    want_toks, want_logits = _greedy(
        _engine(cfg, params, slots=1, kv_pages=3), prompt, n_new)

    eng = _engine(cfg, params, slots=1, kv_pages=3)
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new,
                                                top_k=1))
    toks, logits = [ev.token], []
    for _ in range(4):
        toks += [e.token for e in eng.step()]
        logits.append(eng.last_logits[slot].copy())
    parked = eng.park(slot)
    block = int(parked.block_table[0])
    before = _state(eng, block)
    assert eng._alloc.in_use() == 1           # the block stays pinned
    other, _lg = _greedy(eng, _prompt(20, 6), 7)        # same slot
    assert len(other) == 7
    after = _state(eng, block)
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])
    slot = eng.resume(parked)
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
        logits.append(eng.last_logits[slot].copy())
    assert toks == want_toks
    np.testing.assert_array_equal(np.stack(logits), want_logits)
    ref_logits = _reference_logits(params, sizes, prompt, toks)
    assert np.abs(np.stack(logits) - ref_logits[1:]).max() < F32_TOL
    assert eng._alloc.in_use() == 0
    # a parked snapshot dropped without resuming gives its block back
    slot, _ev = eng.admit(prompt, SamplingParams(max_new_tokens=n_new))
    dropped = eng.park(slot)
    assert eng._alloc.in_use() == 1
    eng.release_parked(dropped)
    eng.release_parked(dropped)               # idempotent
    assert eng._alloc.in_use() == 0


def test_a_quarantined_rows_block_is_zeroed_before_reuse(f32):
    """NaNs planted in a live row's block: the row is quarantined at its
    next step, its block is written over with the null block's zeros
    before it is freed, the null block is still zeros, and the block's
    next owner is served as the reference says."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=1, kv_pages=2)
    prompt = _prompt(21, 40)
    slot, _ev = eng.admit(prompt, SamplingParams(max_new_tokens=6, top_k=1))
    block = int(eng._bt[slot, 0])
    eng._cache = jax.tree.map(lambda x: x.at[block].set(jnp.nan),
                              eng._cache)
    assert all(e.poisoned for e in eng.step())
    assert eng.stats.quarantined == 1
    assert eng._alloc.in_use() == 0
    for blk in (0, block):
        S, z = _state(eng, blk)
        assert not S.any() and not z.any()
    toks, logits = _greedy(eng, prompt, 6)
    want = _reference_logits(params, sizes, prompt, toks)
    assert np.abs(logits - want[1:]).max() < F32_TOL


# -- planted faults ---------------------------------------------------------

SERVED_LENGTHS = (24, 37, 80, 52, 66, 29)


def _context(sizes, seed=5):
    traffic = _load("traffic", "serve-closed-longgen.json")
    limits = _load("limits", CELL + ".json")
    return {"traffic": {**traffic, **traffic["rehearse"]}, "sizes": sizes,
            "args": types.SimpleNamespace(seed=seed),
            "devices": jax.devices(), "limits": limits["rehearse"]}


def _serve(eng, sizes, seed=5, n_new=16, lengths=SERVED_LENGTHS):
    rng, picked = np.random.default_rng(seed), []
    for n in lengths:
        prompt = rng.integers(0, sizes["vocab_size"], n)
        toks, _lg = _greedy(eng, prompt, n_new)
        picked.append({"prompt": prompt.tolist(), "tokens": toks})
    return picked


@pytest.fixture(scope="module")
def served():
    """Six greedy requests through ONE slot and ONE block at the
    rehearsal's sizes (float32, as the rehearsal runs; every prompt is
    short of its bucket and lands on the block the last one left), and
    the context the kind's ``judge`` reads."""
    config = _load("configs", "brumby-14b-base.json")
    sizes = {**config, **config["rehearse"]}
    ctx = _context(sizes)
    eng = InferenceEngine(weights_brumby.make_params(sizes, 5),
                          closed_brumby.model_config(sizes), num_slots=1,
                          kv_pages=2)
    picked = _serve(eng, sizes)
    sound = closed_brumby.judge(ctx, picked)
    sound["lower"] = closed_brumby.judge(ctx, picked, "fp8")
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
    """A program with one wrong reading of the description (degree 1, no
    gate, the gate on the entering key, no normaliser, a gate a query
    head) would serve the tokens that reading puts first: at least one
    limit of the cell's rehearsal refuses them."""
    ctx, picked, sound = served
    wrong = closed_brumby.judge(ctx, picked, faults=(fault,))
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    assert not all(r["ok"] for r in rows), rows


def _padding_updates_the_state(monkeypatch):
    real = pr.prefill

    def prefill(S0, z0, q, k, v, gam, valid, *a, **kw):
        return real(S0, z0, q, k, v, gam, jnp.ones_like(valid), *a, **kw)

    monkeypatch.setattr(pr, "prefill", prefill)


def _state_not_zeroed_at_admission(monkeypatch):
    real_rows, real_step = pr.load_rows, pr.decode_step
    monkeypatch.setattr(
        pr, "load_rows",
        lambda S, z, bt, fresh: real_rows(S, z, bt, jnp.zeros_like(fresh)))
    monkeypatch.setattr(
        pr, "decode_step",
        lambda S, z, bt, q, k, v, gam, fresh, *a: real_step(
            S, z, bt, q, k, v, gam, jnp.zeros_like(fresh), *a))


@pytest.mark.parametrize("plant", [None, _padding_updates_the_state,
                                   _state_not_zeroed_at_admission],
                         ids=["sound", "padding_updates_the_state",
                              "state_not_zeroed_at_admission"])
def test_a_fault_of_the_cache_manager_fails_the_rehearsals_limits(
        served, monkeypatch, plant):
    """Six requests, long and short prompts in turn on one block (the
    rehearsal's gates forget in some twenty tokens: a short prompt after
    a long row is where another row's memory, or three tokens of padding,
    still weigh), through a program with the fault planted (its own
    program key, so that it is traced anew): the tokens it serves fail a
    limit against the reference, and the sound program's pass."""
    ctx, _picked, sound = served
    sizes = ctx["sizes"]
    name = plant.__name__ if plant else ""
    if plant:
        plant(monkeypatch)
    cfg = dataclasses.replace(closed_brumby.model_config(sizes),
                              prefill_rows=4000 + len(name))
    eng = InferenceEngine(weights_brumby.make_params(sizes, 5), cfg,
                          num_slots=1, kv_pages=2)
    ctx = dict(ctx, reference_logits={})
    wrong = closed_brumby.judge(
        ctx, _serve(eng, sizes, lengths=(80, 5, 66, 6, 52, 7)))
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    assert all(r["ok"] for r in rows) == (plant is None), rows


def test_a_bfloat16_state_over_two_thousand_steps_fails_the_limits():
    """The rehearsal's widths with the CELL's gate draw (``sigmoid(8.3 +-
    0.5)``: a key still weighs three fifths after 2,000 tokens), one
    request of 2,000 decode steps. With the state in float32 the served
    tokens pass the rehearsal's limits; with the state kept in bfloat16 a
    gate of 1 - 1/4,000 rounds to 1, the state stops decaying and stops
    taking updates smaller than its last bit, and they fail one."""
    config = _load("configs", "brumby-14b-base.json")
    sizes = {**config, **config["rehearse"],
             "gate_bias": config["gate_bias"],
             "gate_scale": config["gate_scale"],
             "max_position_embeddings": 2304}
    ctx = _context(sizes, seed=9)
    ctx["traffic"]["reference_pad_multiple"] = 2304
    params = weights_brumby.make_params(sizes, 9)
    verdicts = {}
    for state in ("f32", "bf16"):
        cfg = dataclasses.replace(closed_brumby.model_config(sizes),
                                  kv_dtype=state)
        eng = InferenceEngine(params, cfg, num_slots=1, kv_pages=2)
        picked = _serve(eng, sizes, seed=9, n_new=2001, lengths=(200,))
        ctx.pop("reference_logits", None)
        verdicts[state] = closed_brumby.judge(ctx, picked)
        if state == "f32":
            lower = closed_brumby.judge(ctx, picked, "fp8")
        verdicts[state]["lower"] = lower
    assert all(r["ok"] for r in verdict_rows(ctx, verdicts["f32"], 0, []))
    rows = verdict_rows(ctx, verdicts["bf16"], 0, [])
    assert not all(r["ok"] for r in rows), rows


def test_reference_refuses_an_unknown_fault(f32):
    sizes, _cfg, params = f32
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, sizes, np.arange(8), [7], faults=("typo",))


# -- counters, config, weights ----------------------------------------------


def test_decode_steps_count_live_rows_and_the_state_they_hold(f32):
    """What ``/stats`` serves as ``model_counters``: over a request's
    decode steps, the live rows and the KiB of state they hold in each
    layer (the same whatever the row's length), and ``pages`` as every
    paged layer counts them (one block a live row, none skipped)."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params)
    _toks, logits = _greedy(eng, _prompt(5, 2), 9)
    steps = len(logits)
    row_bytes = 2 * pr.feature_dim(16) * 17 * 4
    assert eng.config.state_bytes_per_row() == 2 * row_bytes
    assert eng.kv_pool_bytes()["payload"] == eng.kv_pages * 2 * row_bytes
    c = eng.stats.model_counters
    for i in range(sizes["num_hidden_layers"]):
        assert np.asarray(c[f"layers_{i}/self_attn/state"]).tolist() == [
            steps, steps * (row_bytes // 1024)]
        assert np.asarray(c[f"layers_{i}/self_attn/pages"]).tolist() == [
            steps, 0]


def test_config_round_trips_and_refuses_training_and_speculation(f32):
    _sizes_, cfg, params = f32
    served = dataclasses.replace(cfg.decode_config(), page_size=128,
                                 kv_pages=4)
    key = served.program_key()
    hash(key)
    assert key[0] == "brumby"
    assert serving.config_from_key(key) == served
    again = serving.config_from_dict(
        json.loads(json.dumps(dataclasses.asdict(served))) | {"new_key": 1})
    assert again == served
    assert "brumby" in served.program_tag()
    assert set(served.attend_paths()) == {pa.RETENTION}
    assert serving.attend_path_id(served) == "retention"
    with pytest.raises(ValueError, match="whole groups"):
        BrumbyConfig(num_attention_heads=12, num_key_value_heads=8)
    model = served.build()
    one = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="served, not trained"):
        model.apply({}, one, train=True)
    with pytest.raises(ValueError, match="cannot be rewound"):
        model.apply({}, jnp.zeros((1, 3), jnp.int32),
                    block_table=jnp.ones((1, 1), jnp.int32),
                    cache_pos=jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="cannot be rewound"):
        InferenceEngine(params, cfg, num_slots=1, spec_tokens=2)
    with pytest.raises(ValueError, match="one block"):
        dataclasses.replace(served, page_size=16).build().apply(
            {}, one, block_table=jnp.ones((1, 8), jnp.int32),
            cache_pos=jnp.zeros((1,), jnp.int32))


def test_weights_from_the_seed_have_the_decoders_own_shapes(bf16):
    """``perfbench/weights_brumby.py`` imports nothing of the program:
    its tree is the decoder's own, name for name and shape for shape;
    the gate is drawn so that memory lasts."""
    sizes, cfg, params = bf16
    served = dataclasses.replace(cfg.decode_config(), page_size=128,
                                 kv_pages=3)
    own = jax.eval_shape(lambda: served.build().init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 1), jnp.int32),
        train=False, block_table=jnp.zeros((1, 1), jnp.int32),
        cache_pos=jnp.zeros((1,), jnp.int32)))["params"]
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), own)
            == jax.tree.map(lambda x: (x.shape, x.dtype), params))
    other = weights_brumby.make_params(sizes, 8)
    leaf = lambda t: np.asarray(                # noqa: E731
        t["layers_1"]["self_attn"]["g_proj"], np.float32)
    assert np.abs(leaf(other) - leaf(params)).max() > 0
    cell = _load("configs", "brumby-14b-base.json")
    gate = 1.0 / (1.0 + np.exp(-cell["gate_bias"]))
    assert 0.3 < gate ** 4000 < 0.45      # a third after 4,000 tokens
