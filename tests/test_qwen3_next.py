"""Qwen3-Next (``gym_tpu/models/qwen3_next.py``: three gated delta-rule
layers to one gated softmax-attention layer, softmax-routed held experts
beside a sigmoid-gated shared one) through the serving engine against its
plain reference (``perfbench/references/qwen3_next.py``: float32, the rule
token by token over the whole sequence, no chunks, no cache, nothing
imported from the program), at a small size on the CPU with seeded random
weights.

Sizes: the configuration file's ``rehearse`` preset (hidden 64; one period
of the pattern: three delta layers of 2 key and 4 value heads of 16 behind
a convolution of 4 taps, one full layer of 4 query heads over 2 key-value
heads of 16 with 4 rotated lanes and an output gate; 16 routed experts of
which 4 a token and 4 held, one gated shared; 256 rows of vocabulary) with
pages of 8, prefill passes of 32 and chunks of 8, so that a prompt of
seventy tokens in its bucket of 128 is several passes of several chunks of
which the last are padding.

* engine prefill (chunked) then decode (the state pass) through both
  caches equals the reference's logits at every decoded position, in
  float32 (to rounding) and in bfloat16 (within a tolerance the fp8
  control exceeds); through parking, resuming and slot reuse too;
* a prefill of several passes equals one pass of the whole bucket; rows of
  unequal length through ``Scheduler``;
* the shares of 16 experts over 4 chips, with the gated shared expert
  counted once, add up to the uncut layer (the guide's share test);
* each planted wrong reading of the description (in the reference) fails a
  limit of the cell's rehearsal (the kind's own ``judge`` and
  ``verdict_rows``);
* the counters a decode step returns; the config through a program key and
  a dict, and what it refuses; the seeded weights have the decoder's own
  shapes once ``prepare_params`` has laid them out flat.

The rule alone: ``tests/test_gated_delta.py``; the engine's manager over a
row of both kinds: ``tests/test_serve_hybrid_pool.py``.
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
from gym_tpu.models.moe import HeldExperts
from gym_tpu.models.qwen3_next import Qwen3NextConfig, pass_rows
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from perfbench import weights_qwen3_next
from perfbench.kinds import closed_qwen3_next
from perfbench.kinds.closed_model import verdict_rows
from perfbench.references import qwen3_next as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "qwen3-next-80b-a3b.serve-closed-longctx"
# float32 program against float32 reference on logits of spread 1.0: the
# program takes a prompt in chunks (a triangular inverse a chunk) and sums
# a row's past in pages, the reference token by token and in one softmax;
# the two orders of float32 additions lie up to 3e-6 apart
F32_TOL = 1e-5
# bfloat16 program against the float32 reference on logits of spread 1.0,
# as the MEAN distance over the compared logits
BF16_TOL = 0.08


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _sizes(dtype="float32", **over):
    config = _load("configs", "qwen3-next-80b-a3b.json")
    return {**config, **config["rehearse"], "dtype": dtype, **over}


def _config(sizes, **over):
    # the rehearsal's sizes say 32 positions a pass and 8 a chunk
    return dataclasses.replace(closed_qwen3_next.model_config(sizes), **over)


@pytest.fixture(scope="module")
def f32():
    sizes = _sizes()
    return sizes, _config(sizes), weights_qwen3_next.make_params(sizes, 7)


@pytest.fixture(scope="module")
def bf16():
    sizes = _sizes("bfloat16")
    return sizes, _config(sizes), weights_qwen3_next.make_params(sizes, 7)


def _engine(cfg, params, slots=2, **kw):
    return InferenceEngine(params, cfg, num_slots=slots, page_size=8, **kw)


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
        params, sizes, list(prompt), list(toks), pad_multiple=32, **kw))


# one pass of one chunk; a pass that ends mid-chunk; several passes with
# padding after; a full row of 128 positions
ROWS = [(5, 4), (33, 5), (70, 6), (100, 8), (120, 8)]
ROW_IDS = [f"p{p}n{n}" for p, n in ROWS]


@pytest.mark.parametrize("plen,n_new", ROWS, ids=ROW_IDS)
def test_prefill_then_decode_through_both_caches_equals_the_reference_f32(
        f32, plen, n_new):
    """The prompt in passes of 32 positions and chunks of 8 (the state and
    the convolution's inputs carried from pass to pass, the padding
    leaving both as they are), then decode steps that read and correct
    the row's state block in place and walk its pages: every step's
    logits are the reference's, which runs the rule token by token, and
    every served token is the reference's best."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen)
    toks, logits = _greedy(_engine(cfg, params), prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert np.abs(logits - want[1:]).max() < F32_TOL
    assert (want.argmax(-1) == np.asarray(toks)).all()


@pytest.mark.parametrize("plen,n_new", [(70, 6), (33, 5), (120, 4)],
                         ids=["p70", "p33", "p120"])
def test_a_prefill_of_several_passes_equals_one_pass(f32, plen, n_new):
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen + 1)
    assert pass_rows(128, cfg.prefill_rows, cfg.delta_chunk) == 32
    one = dataclasses.replace(cfg, prefill_rows=128, delta_chunk=16)
    assert pass_rows(128, one.prefill_rows, one.delta_chunk) == 128
    t_many, l_many = _greedy(_engine(cfg, params), prompt, n_new)
    t_one, l_one = _greedy(_engine(one, params), prompt, n_new)
    assert t_many == t_one
    assert np.abs(l_many - l_one).max() < F32_TOL


@pytest.mark.parametrize("t,rows,chunk,want", [
    (50688, 4096, 64, 2816), (32768, 4096, 64, 4096), (8192, 4096, 64, 4096),
    (128, 32, 8, 32), (4, 32, 8, 4), (96, 64, 64, 32)])
def test_a_pass_is_whole_chunks_and_divides_the_bucket(t, rows, chunk, want):
    """The row's whole extent is a bucket too (50,688 = 2^9 x 99): its
    passes are 2,816 positions, 44 chunks of 64."""
    assert pass_rows(t, rows, chunk) == want
    assert t % want == 0 and want <= max(rows, 1)


@pytest.mark.parametrize("plen,n_new", ROWS[1:4], ids=ROW_IDS[1:4])
def test_prefill_then_decode_equals_the_reference_bf16(bf16, plen, n_new):
    """As served: bfloat16 weights, pages and convolution inputs, a
    float32 state. The mean distance to the float32 reference stays under
    a tolerance that the fp8 control exceeds."""
    sizes, cfg, params = bf16
    assert (cfg.weights_dtype, cfg.kv_dtype, cfg.state_dtype) == \
        ("bf16", "bf16", "f32")
    prompt = _prompt(plen, plen)
    toks, logits = _greedy(_engine(cfg, params), prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    fp8 = _reference_logits(params, sizes, prompt, toks, mode="fp8")
    mean = np.abs(logits - want[1:]).mean()
    assert mean < BF16_TOL
    assert np.abs(fp8 - want).mean() > 1.5 * mean


def test_scheduler_serves_rows_of_unequal_length_as_the_reference(f32):
    """Five greedy requests of unequal length through three slots
    (admissions between decode steps, a step always in flight, the
    fourth and fifth on the pages and state blocks the first rows left,
    which they must read as zeros): every served token is the reference's
    best at its position."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=3, kv_pages=40)
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
    assert eng.stats.state_blocks_in_use == 0


def test_a_parked_row_resumed_into_another_slot_serves_the_reference(f32):
    """A row parked mid-generation (pages and state block pinned), its
    slot given to another request, then resumed into the other slot:
    every logit after the resume is the reference's, and the request that
    took the slot, on a state block a finished row left, is too."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=2)
    first = _prompt(9, 90)
    _greedy(eng, first, 5)                  # leaves a dirty block and pages
    prompt = _prompt(44, 91)
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=10, top_k=1))
    toks, logits = [ev.token], []
    for _ in range(3):
        ev = [e for e in eng.step() if e.slot == slot][-1]
        toks.append(ev.token)
        logits.append(eng.last_logits[slot].copy())
    parked = eng.park(slot)
    other = _prompt(30, 92)
    o_toks, o_logits = _greedy(eng, other, 6)
    slot = eng.resume(parked)
    while slot not in eng.free_slots():
        ev = [e for e in eng.step() if e.slot == slot][-1]
        toks.append(ev.token)
        logits.append(eng.last_logits[slot].copy())
    for p, t, lg in ((prompt, toks, logits), (other, o_toks, o_logits)):
        want = _reference_logits(params, sizes, p, t)
        assert np.abs(np.stack(lg) - want[1:]).max() < F32_TOL


# -- the expert layer: the shares ---------------------------------------------

E, K, C, F = 16, 4, 32, 16


def _experts(held, seed=3):
    """A ``HeldExperts`` as the model builds it (softmax scores, the 4
    largest renormalised, one shared expert returned apart) holding
    ``held`` of 16, and its parameters cut from ONE seeded full layer."""
    rng = np.random.default_rng(seed)
    full = {"router": rng.normal(0, 0.5, (C, E)),
            "gate_proj": rng.normal(0, 0.2, (E, C, F)),
            "up_proj": rng.normal(0, 0.2, (E, C, F)),
            "down_proj": rng.normal(0, 0.2, (E, F, C)),
            "shared_gate_proj": rng.normal(0, 0.2, (1, C, F)),
            "shared_up_proj": rng.normal(0, 0.2, (1, C, F)),
            "shared_down_proj": rng.normal(0, 0.2, (1, F, C)),
            "shared_expert_gate": rng.normal(0, 0.3, (C, 1))}
    lo, hi = held
    layer = HeldExperts(hidden=C, width=F, n_experts=E, topk=K, held=held,
                        n_shared=1, norm_topk=True, param_dtype=jnp.float32,
                        score_fn="softmax")
    cut = {k: jnp.asarray(v[lo:hi] if k in ("gate_proj", "up_proj",
                                            "down_proj") else v, jnp.float32)
           for k, v in full.items() if k != "shared_expert_gate"}
    return layer, {"params": cut}, full


def _plain_layer(h, full):
    """The uncut layer in float64 NumPy: softmax over 16, the 4 largest
    renormalised, every chosen expert, the shared expert behind its
    sigmoid gate."""
    x = np.asarray(h, np.float64)
    silu = lambda a: a / (1.0 + np.exp(-a))          # noqa: E731
    logits = x @ full["router"]
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1)[:, :K]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        w = p[t, top[t]] / p[t, top[t]].sum()
        for e, w_e in zip(top[t], w):
            out[t] += w_e * ((silu(x[t] @ full["gate_proj"][e])
                              * (x[t] @ full["up_proj"][e]))
                             @ full["down_proj"][e])
    shared = ((silu(x @ full["shared_gate_proj"][0])
               * (x @ full["shared_up_proj"][0]))
              @ full["shared_down_proj"][0])
    gate = 1.0 / (1.0 + np.exp(-(x @ full["shared_expert_gate"])))
    return out + gate * shared, gate


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts in 4 shares of 4. Each chip
    routes over all 16 (softmax, the 4 largest, renormalised) and
    computes its own experts' part; the four routed parts plus the shared
    expert ONCE, behind the model's own sigmoid gate, are the uncut
    layer, and no share alone is."""
    h = jax.random.normal(jax.random.PRNGKey(1), (24, C))
    parts = []
    for lo in range(0, E, 4):
        layer, variables, full = _experts((lo, lo + 4))
        routed, shared = layer.apply(variables, h)
        parts.append(np.asarray(routed))
    want, gate = _plain_layer(h, full)
    got = sum(parts) + gate * np.asarray(shared)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert np.abs(parts[0] + gate * np.asarray(shared) - want).max() > 0.01
    # the gate is the model's own: without it the layer is another
    assert np.abs(sum(parts) + np.asarray(shared) - want).max() > 0.01


# -- planted faults ---------------------------------------------------------

SERVED_LENGTHS = (24, 37, 80, 52, 66, 29)


def _context(sizes, seed=5):
    traffic = _load("traffic", "serve-closed-longctx.json")
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
    """Six greedy requests through one slot at the rehearsal's sizes and
    dtype, and the context the kind's ``judge`` reads."""
    config = _load("configs", "qwen3-next-80b-a3b.json")
    sizes = {**config, **config["rehearse"]}
    ctx = _context(sizes)
    eng = InferenceEngine(
        weights_qwen3_next.make_params(sizes, 5),
        closed_qwen3_next.model_config(sizes), num_slots=1,
        page_size=int(ctx["traffic"]["page_size"]))
    picked = _serve(eng, sizes)
    sound = closed_qwen3_next.judge(ctx, picked)
    sound["lower"] = closed_qwen3_next.judge(ctx, picked, "fp8")
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
    """A program with one wrong reading of the description (a norm scaled
    by ``w`` and not ``1 + w``, rotary over the whole head, no output
    gate, the decay after the correction, ``beta`` on ``v`` alone, no gate
    on the shared expert, the convolution's inputs dropped between
    passes, a bfloat16 state) would serve the tokens that reading puts
    first: at least one limit of the cell's rehearsal refuses them."""
    ctx, picked, sound = served
    wrong = closed_qwen3_next.judge(ctx, picked, faults=(fault,))
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    assert not all(r["ok"] for r in rows), rows


def test_a_token_altered_where_it_is_produced_fails_the_widest_limit(served):
    """One served token replaced by another where it is produced (a
    request's last, so that nothing served after it followed the other
    one): the run's widest gap is then at least that token's, and
    ``served_logit_gap_widest`` refuses it, the mean's limit need not. Of
    the vocabulary's other tokens at those positions more than nine in
    ten lie past the limit: the rest are near-ties with the best, which
    no limit on a gap tells from rounding."""
    ctx, picked, sound = served
    rng = np.random.default_rng(11)
    altered = [dict(r, tokens=r["tokens"][:-1] + [int(
        (r["tokens"][-1] + rng.integers(1, ctx["sizes"]["vocab_size"]))
        % ctx["sizes"]["vocab_size"])]) for r in picked]
    wrong = closed_qwen3_next.judge(ctx, altered)
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    limit = ctx["limits"]["served_logit_gap_widest"]
    assert rows[0]["name"] == "served_logit_gap_widest"
    assert rows[0]["value"] > limit and not rows[0]["ok"], rows
    last = np.stack([lg[-1] for lg in ctx["reference_logits"].values()])
    gaps = last.max(-1, keepdims=True) - last
    assert (gaps > limit).mean() > 0.9


# -- the reference's own departures -------------------------------------------


@pytest.mark.parametrize("segment,rows", [(32, 2048), (64, 32), (128, 16)],
                         ids=["seg32", "seg64rows32", "seg128rows16"])
def test_the_references_segments_and_row_blocks_move_no_logit(
        f32, monkeypatch, segment, rows):
    sizes, _cfg, params = f32
    prompt, toks = _prompt(90, 1), [3, 1, 4, 1, 5, 9]
    want = np.asarray(ref.served_logits(params, sizes, prompt, toks,
                                        pad_multiple=128))
    monkeypatch.setattr(ref, "KEY_SEGMENT", segment)
    monkeypatch.setattr(ref, "ROW_BLOCK", rows)
    jax.clear_caches()
    got = np.asarray(ref.served_logits(params, sizes, prompt, toks,
                                       pad_multiple=128))
    jax.clear_caches()
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("length", [37, 64, 100])
def test_the_references_padding_moves_no_logit(f32, length):
    """The recurrence stops at ``length``, the padding's blocks are
    skipped and its rows routed to no expert: a sequence padded to 128
    reads as the same sequence unpadded."""
    sizes, _cfg, params = f32
    tokens = _prompt(length, length)
    pos = np.arange(length - 5, length)
    want = np.asarray(ref.forward(params, sizes, tokens, pos))
    padded = np.concatenate([tokens, np.full(128 - length, 7)])
    got = np.asarray(ref.forward(params, sizes, padded, pos, length))
    assert np.abs(got - want).max() < F32_TOL


def test_reference_refuses_an_unknown_fault(f32):
    sizes, _cfg, params = f32
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, sizes, _prompt(8, 1), [7], faults=("typo",))


# -- counters, config, weights ------------------------------------------------


def test_decode_steps_count_live_rows_the_state_they_hold_and_pages(f32):
    """What a decode step returns beside its tokens: a delta layer's
    ``state`` = [live rows, bytes of state and convolution inputs they
    hold in the layer], the full layer's ``pages`` = [pages the live rows
    hold, 0 skipped], the experts' ``picks`` / ``hit`` / ``tokens``."""
    _sizes_, cfg, params = f32
    eng = _engine(cfg, params, slots=3)
    sp = SamplingParams(max_new_tokens=4, top_k=1)
    eng.admit_nowait(_prompt(20, 1), sp)
    eng.admit_nowait(_prompt(9, 2), sp)
    eng.step()
    got = eng.stats.model_counters
    layer = eng.config.state_bytes_per_row() // 3
    assert layer == 4 * 16 * 16 * 4 + 3 * (2 * 2 * 16 + 4 * 16) * 4
    for i in range(3):
        assert got[f"layers_{i}/linear_attn/state"].tolist() == \
            [2, 2 * layer]
    # a step at cursors 20 and 9 reads 3 and 2 pages of 8
    assert got["layers_3/self_attn/pages"].tolist() == [5, 0]
    assert "layers_3/linear_attn/state" not in got
    for i in range(4):
        assert got[f"layers_{i}/mlp/tokens"] == 2
        assert got[f"layers_{i}/mlp/picks"].shape == (4,)
        assert 0 <= got[f"layers_{i}/mlp/picks"].sum() <= 2 * 4


def test_config_round_trips_and_refuses_training(f32):
    _sizes_, cfg, params = f32
    cfg = dataclasses.replace(cfg.decode_config(), page_size=8, kv_pages=34,
                              state_blocks=4)
    key = cfg.program_key()
    assert key[0] == "qwen3_next"
    assert serving.config_from_key(key) == cfg
    assert serving.config_from_dict(
        {**dataclasses.asdict(cfg), "later_key": 1}) == cfg
    assert [cfg.is_full(i) for i in range(4)] == [False] * 3 + [True]
    assert cfg.attend_paths() == ("gated_delta",) * 3 + ("gather",)
    assert serving.attend_path_id(cfg) == "gated_delta+gather"
    assert cfg.rotary_dim == 4 and cfg.conv_channels == 2 * 32 + 64
    model = cfg.build()
    tokens = jnp.zeros((1, 1), jnp.int32)
    table = jnp.zeros((1, 17), jnp.int32)
    variables = {"params": cfg.prepare_params(params)}
    with pytest.raises(ValueError, match="served, not trained"):
        model.apply(variables, tokens, train=True, block_table=table,
                    cache_pos=jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="speculative verify"):
        model.apply(variables, jnp.zeros((1, 3), jnp.int32),
                    block_table=table, cache_pos=jnp.zeros((1,), jnp.int32),
                    mutable=["cache"])
    with pytest.raises(ValueError, match="17 columns"):
        model.apply(variables, tokens, block_table=table[:, :16],
                    cache_pos=jnp.zeros((1,), jnp.int32), mutable=["cache"])
    with pytest.raises(ValueError, match="shared expert"):
        Qwen3NextConfig(shared_expert_intermediate_size=256)
    with pytest.raises(ValueError, match="spec"):
        _engine(cfg, params, spec_tokens=2)


def test_weights_from_the_seed_have_the_decoders_own_shapes(bf16):
    """The seeded tree carries the published names and grouped layouts;
    ``prepare_params`` lays ``in_proj_qkvz`` and ``in_proj_ba`` out flat
    (value head h's columns where the reference reads them grouped),
    keeps ``A_log`` and ``dt_bias`` float32 and passes a flat tree
    through; the decay spans the configured rates."""
    sizes, cfg, params = bf16
    served = cfg.prepare_params(params)
    cfg = dataclasses.replace(cfg.decode_config(), page_size=8, kv_pages=34,
                              state_blocks=4)
    want = jax.eval_shape(lambda: cfg.build().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        block_table=jnp.zeros((1, 17), jnp.int32),
        cache_pos=jnp.zeros((1,), jnp.int32)))["params"]
    assert jax.tree.map(lambda x: (x.shape, x.dtype), served) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)
    again = cfg.prepare_params(served)
    assert jax.tree.all(jax.tree.map(lambda a, b: (a == b).all(), served,
                                     again))
    mix, flat = (params["layers_0"]["linear_attn"],
                 served["layers_0"]["linear_attn"])
    assert mix["A_log"].dtype == flat["A_log"].dtype == jnp.float32
    # key head 1 of 2: its q at grouped columns 96..111, flat 16..31; its
    # second value head (value head 3) at grouped 96+32+16.., flat v 48..
    grouped = np.asarray(mix["in_proj_qkvz"], np.float32)
    laid = np.asarray(flat["qkvz_proj"], np.float32)
    np.testing.assert_array_equal(laid[:, 16:32], grouped[:, 96:112])
    np.testing.assert_array_equal(laid[:, 64 + 48:64 + 64],
                                  grouped[:, 96 + 48:96 + 64])
    ba = np.asarray(mix["in_proj_ba"], np.float32)
    np.testing.assert_array_equal(
        np.asarray(flat["ba_proj"], np.float32)[:, [2, 3, 6, 7]],
        ba[:, [4, 5, 6, 7]])
    rate = np.exp(np.asarray(mix["A_log"])) * np.log1p(np.e)
    np.testing.assert_allclose(rate, 2.0 ** -np.linspace(1, 5, 4), rtol=1e-5)
