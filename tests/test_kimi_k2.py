"""Kimi-K2 (``gym_tpu/models/kimi_k2.py``: multi-head latent attention, a
page that holds one latent a position; absorbed decode, expanded prefill;
bias-corrected sigmoid routing beside a shared expert) through the serving
engine against its plain reference (``perfbench/references/kimi_k2.py``:
float32, the EXPANDED form over the whole sequence, no cache, nothing
imported from the program), at a small size on the CPU with seeded random
weights.

Sizes: the configuration file's ``rehearse`` preset (hidden 64, 4 heads of
16 + 8 query lanes and 16 value lanes over a latent of 32 + 8, a query
latent of 48, one dense SwiGLU layer of 128 and two expert layers of 16
routed experts of which 4 a token and 4 held, one shared, 256 rows of
vocabulary, yarn as published) with pages of 8 and prefill passes of 16 or
32, so that a prompt of seventy tokens in its bucket of 128 is several
passes of which the last are padding.

* engine prefill (expanded) then decode through the pages (absorbed)
  equals the reference's logits at every decoded position, in float32 (to
  rounding) and in bfloat16 (within a tolerance the fp8 control exceeds);
* a prefill of several passes equals one pass of the whole bucket; rows of
  unequal length through ``Scheduler``;
* the selection bias moves the choice and never a weight; the shares of
  16 experts over 4 chips, with the shared expert counted once, add up to
  the uncut layer (the guide's share test);
* each planted wrong reading of the description (in the reference) fails a
  limit of the cell's rehearsal (the kind's own ``judge`` and
  ``verdict_rows``);
* the counters a decode step returns; the config through a program key and
  a dict, and what it refuses; the seeded weights have the decoder's own
  shapes once ``prepare_params`` has split them.

The two forms of the attend alone: ``tests/test_latent_attention.py``;
the engine's manager over the latent pages: ``tests/test_serve_latent_pool
.py``.
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
from gym_tpu.models.kimi_k2 import KimiK2Config
from gym_tpu.models.moe import HeldExperts
from gym_tpu.ops import latent_attention as la
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from perfbench import weights_kimi
from perfbench.kinds import closed_kimi
from perfbench.kinds.closed_model import verdict_rows
from perfbench.references import kimi_k2 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "kimi-k2.7-code.serve-closed-repo"
# float32 program against float32 reference on logits of spread 1.0: the
# program scores a cached position through the absorbed product and sums a
# row's past in blocks, the reference through expanded keys and one
# softmax; the two orders of float32 additions lie up to 4e-6 apart
F32_TOL = 2e-5
# bfloat16 program against the float32 reference on logits of spread 1.0,
# as the MEAN distance over the compared logits
BF16_TOL = 0.08


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _sizes(dtype="float32", **over):
    config = _load("configs", "kimi-k2.7-code.json")
    return {**config, **config["rehearse"], "dtype": dtype, **over}


def _config(sizes, **over):
    # the rehearsal's sizes say 32 positions a pass of a prefill
    return dataclasses.replace(closed_kimi.model_config(sizes), **over)


@pytest.fixture(scope="module")
def f32():
    sizes = _sizes()
    return sizes, _config(sizes), weights_kimi.make_params(sizes, 7)


@pytest.fixture(scope="module")
def bf16():
    sizes = _sizes("bfloat16")
    return sizes, _config(sizes), weights_kimi.make_params(sizes, 7)


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
        params, sizes, list(prompt), toks, pad_multiple=32, **kw))


# -- the engine against the reference --------------------------------------

ROWS = [(3, 14), (13, 12), (40, 6), (70, 10)]
ROW_IDS = ["bucket4", "short_of_bucket16", "two_passes", "three_of_four"]


@pytest.mark.parametrize("plen,n_new", ROWS, ids=ROW_IDS)
def test_prefill_then_decode_through_the_pages_equals_the_reference_f32(
        f32, plen, n_new):
    """Float32 weights and pages: every decoded position's logits equal
    the expanded form's full forward to rounding, so the prefill wrote
    the prompt's latents (normed, rotated) where the absorbed decode
    reads them, its later passes attended to the pages its earlier
    passes wrote, and every step scored ``q_nope W_uk^T`` and ``q_rope``
    against the page rows; the prefill's token is the reference's best."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen)
    eng = _engine(cfg, params)
    assert eng.attend_path == la.LATENT_GATHER == "latent_gather"
    toks, logits = _greedy(eng, prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL
    assert want.std() > 0.5          # logits worth comparing


@pytest.mark.parametrize("plen,n_new", [(70, 6), (33, 5), (120, 4)],
                         ids=["last_pass_all_padding", "two_of_four_passes",
                              "eight_of_eight_passes"])
def test_a_prefill_of_several_passes_equals_one_pass(f32, plen, n_new):
    """Passes of 16 positions through all layers, the pools carried from
    pass to pass: a prompt of 70 in its bucket of 128 ends inside the
    fifth pass and the last three, all padding, are skipped; the first
    token is read in the pass that holds the prompt's last position. As
    one pass of the whole bucket gives (to rounding: the same products
    over fewer rows at once) and as the reference says."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, 900 + plen)
    whole = _greedy(_engine(dataclasses.replace(cfg, prefill_rows=128),
                            params), prompt, n_new)
    toks, logits = _greedy(
        _engine(dataclasses.replace(cfg, prefill_rows=16), params), prompt,
        n_new)
    assert toks == whole[0]
    assert np.abs(logits - whole[1]).max() < F32_TOL
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL


def test_the_kernels_under_the_interpreter_serve_the_reference(
        f32, monkeypatch):
    """The engine with both Pallas kernels (the decode walk of the live
    pages, the prefill's flash attend) under the interpreter: the path
    the dispatch spans name is ``latent_paged`` and the logits are the
    reference's."""
    monkeypatch.setattr(la, "INTERPRET", True)
    sizes, cfg, params = f32
    eng = _engine(dataclasses.replace(cfg, prefill_rows=64), params)
    assert eng.attend_path == la.LATENT_KERNEL == "latent_paged"
    prompt = _prompt(70, 11)
    toks, logits = _greedy(eng, prompt, 5)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL


@pytest.mark.parametrize("plen,n_new", ROWS[1:], ids=ROW_IDS[1:])
def test_prefill_then_decode_equals_the_reference_bf16(bf16, plen, n_new):
    """As served (bfloat16 weights, activations and pages): near the
    float32 reference, and nearer than the reference's own fp8 control."""
    sizes, cfg, params = bf16
    assert cfg.weights_dtype == "bf16" and cfg.kv_dtype == "bf16"
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
    fourth and fifth on pages the first rows left): every served token
    is the reference's best at its position."""
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


# -- the expert layer: the bias, the shares -----------------------------------

E, K, C, F = 16, 4, 32, 16


def _experts(held, n_shared=1, bias=None, seed=3):
    """A ``HeldExperts`` of 16 sigmoid-routed experts with the selection
    bias, holding ``held``, and its parameters cut from ONE seeded full
    layer (float32)."""
    rng = np.random.default_rng(seed)
    full = {"router": rng.normal(0, 0.3, (C, E)),
            "e_score_correction_bias": (rng.normal(0, 0.2, (E,))
                                        if bias is None else bias),
            "gate_proj": rng.normal(0, 0.2, (E, C, F)),
            "up_proj": rng.normal(0, 0.2, (E, C, F)),
            "down_proj": rng.normal(0, 0.2, (E, F, C)),
            "shared_gate_proj": rng.normal(0, 0.2, (1, C, F)),
            "shared_up_proj": rng.normal(0, 0.2, (1, C, F)),
            "shared_down_proj": rng.normal(0, 0.2, (1, F, C))}
    lo, hi = held
    cut = {k: (v[lo:hi] if k in ("gate_proj", "up_proj", "down_proj")
               else v) for k, v in full.items()}
    if not n_shared:
        cut = {k: v for k, v in cut.items() if not k.startswith("shared")}
    layer = HeldExperts(hidden=C, width=F, n_experts=E, topk=K, held=held,
                        n_shared=n_shared, param_dtype=jnp.float32,
                        score_fn="sigmoid", select_bias=True)
    return layer, {"params": jax.tree.map(
        lambda x: jnp.asarray(x, jnp.float32), cut)}, full


def _plain_layer(h, full, scale):
    """The uncut layer by the description, a token at a time."""
    out = []
    for x in np.asarray(h, np.float64):
        g = 1.0 / (1.0 + np.exp(-(x @ full["router"])))
        chosen = np.argsort(-(g + full["e_score_correction_bias"]),
                            kind="stable")[:K]
        w = g[chosen] / g[chosen].sum()

        def mlp(wg, wu, wd):
            a = x @ wg
            return ((a / (1.0 + np.exp(-a))) * (x @ wu)) @ wd

        y = sum(w_e * mlp(full["gate_proj"][e], full["up_proj"][e],
                          full["down_proj"][e])
                for e, w_e in zip(chosen, w))
        out.append(scale * y + mlp(full["shared_gate_proj"][0],
                                   full["shared_up_proj"][0],
                                   full["shared_down_proj"][0]))
    return np.stack(out)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts in 4 shares of 4. Each chip
    routes over all 16 (scores, bias, the 4 largest, weights
    renormalised) and computes its own experts' part; the four routed
    parts times ``routed_scaling_factor`` plus the shared expert ONCE are
    the uncut layer, and no share alone is."""
    h = jax.random.normal(jax.random.PRNGKey(1), (24, C))
    parts = []
    for lo in range(0, E, 4):
        layer, variables, full = _experts((lo, lo + 4))
        routed, shared = layer.apply(variables, h)
        parts.append(np.asarray(routed))
    want = _plain_layer(h, full, 2.827)
    got = 2.827 * sum(parts) + np.asarray(shared)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert np.abs(2.827 * parts[0] + np.asarray(shared) - want).max() > 0.01


def test_the_selection_bias_moves_the_choice_and_never_a_weight():
    """With a bias that lifts experts 12..15 over every score the router
    chooses exactly those four for every token, and their weights are
    their own sigmoid scores renormalised: what a layer without the bias
    gives when it is handed those four. A zero bias chooses as no bias
    does; a model without ``select_bias`` declares no such parameter."""
    h = jax.random.normal(jax.random.PRNGKey(2), (16, C))
    lift = np.where(np.arange(E) >= 12, 5.0, 0.0)
    layer, variables, full = _experts((12, 16), n_shared=0, bias=lift)
    routed, _shared = layer.apply(variables, h)
    g = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64) @ full["router"])))
    w = g[:, 12:] / g[:, 12:].sum(-1, keepdims=True)
    x = np.asarray(h, np.float64)
    want = np.zeros_like(x)
    for e in range(12, 16):
        a = x @ full["gate_proj"][e]
        want += w[:, e - 12:e - 11] * (
            ((a / (1.0 + np.exp(-a))) * (x @ full["up_proj"][e]))
            @ full["down_proj"][e])
    np.testing.assert_allclose(routed, want, atol=2e-5, rtol=2e-5)
    # the bias in a weight would have made every weight about a quarter
    assert np.abs(w - 0.25).max() > 0.05
    # a zero bias: the choice of the plain top-k
    zero, zvars, _ = _experts((0, 16), n_shared=0, bias=np.zeros(E))
    plain = HeldExperts(hidden=C, width=F, n_experts=E, topk=K,
                        held=(0, 16), n_shared=0, param_dtype=jnp.float32)
    pvars = {"params": {k: v for k, v in zvars["params"].items()
                        if k != "e_score_correction_bias"}}
    np.testing.assert_allclose(zero.apply(zvars, h)[0],
                               plain.apply(pvars, h)[0], atol=1e-6)
    own = plain.init(jax.random.PRNGKey(0), h)["params"]
    assert "e_score_correction_bias" not in own


# -- planted faults ---------------------------------------------------------

SERVED_LENGTHS = (24, 37, 80, 52, 66, 29)


def _context(sizes, seed=5):
    traffic = _load("traffic", "serve-closed-repo.json")
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
    config = _load("configs", "kimi-k2.7-code.json")
    sizes = {**config, **config["rehearse"]}
    ctx = _context(sizes)
    eng = InferenceEngine(
        weights_kimi.make_params(sizes, 5),
        closed_kimi.model_config(sizes), num_slots=1,
        page_size=int(ctx["traffic"]["page_size"]))
    picked = _serve(eng, sizes)
    sound = closed_kimi.judge(ctx, picked)
    sound["lower"] = closed_kimi.judge(ctx, picked, "fp8")
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
    """A program with one wrong reading of the description (the rotation
    left off ``k_rope``, the latent's norm skipped, ``W_uk`` read in the
    other matrix order, ``mscale ** 2`` left out, the bias used as a
    weight, ``routed_scaling_factor`` left out, the dense layer given
    experts) would serve the tokens that reading puts first: at least
    one limit of the cell's rehearsal refuses them."""
    ctx, picked, sound = served
    wrong = closed_kimi.judge(ctx, picked, faults=(fault,))
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    assert not all(r["ok"] for r in rows), rows


def test_a_token_altered_where_it_is_produced_fails_the_widest_limit(served):
    """One served token replaced by another where it is produced (a
    request's last, so that nothing served after it followed the other
    one): the run's widest gap is then at least that token's, and
    ``served_logit_gap_widest`` refuses it, the mean's limit need not.
    Of the vocabulary's other tokens at those positions (the reference's
    logits the judge kept) more than nine in ten lie past the limit: the
    rest are near-ties with the best, which no limit on a gap tells from
    rounding."""
    ctx, picked, sound = served
    rng = np.random.default_rng(11)
    altered = [dict(r, tokens=r["tokens"][:-1] + [int(
        (r["tokens"][-1] + rng.integers(1, ctx["sizes"]["vocab_size"]))
        % ctx["sizes"]["vocab_size"])]) for r in picked]
    wrong = closed_kimi.judge(ctx, altered)
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    limit = ctx["limits"]["served_logit_gap_widest"]
    assert rows[0]["name"] == "served_logit_gap_widest"
    assert rows[0]["value"] > limit and not rows[0]["ok"], rows
    last = np.stack([lg[-1] for lg in ctx["reference_logits"].values()])
    gaps = last.max(-1, keepdims=True) - last
    assert (gaps > limit).mean() > 0.9


@pytest.mark.parametrize("segment,rows", [(32, 2048), (64, 32), (128, 16)],
                         ids=["four_segments", "two", "one_and_row_blocks"])
def test_the_references_segments_and_row_blocks_move_no_logit(
        f32, monkeypatch, segment, rows):
    """The reference reads a block of queries against the keys up to its
    segment's end and takes its rows a block at a time: the same logits
    whatever the segment and the block (1e-5: the order of float32
    additions in a softmax over fewer, masked, columns)."""
    sizes, _cfg, params = f32
    tokens = _prompt(128, 21)
    whole = np.asarray(ref.forward(params, sizes, tokens, np.arange(128)))
    monkeypatch.setattr(ref, "KEY_SEGMENT", segment)
    monkeypatch.setattr(ref, "ROW_BLOCK", rows)
    ref._layer.clear_cache()
    try:
        cut = np.asarray(ref.forward(params, sizes, tokens, np.arange(128)))
    finally:
        monkeypatch.undo()
        ref._layer.clear_cache()
    np.testing.assert_allclose(cut, whole, atol=1e-5, rtol=0)


@pytest.mark.parametrize("length", [37, 64, 100])
def test_the_references_padding_moves_no_logit(f32, monkeypatch, length):
    """A sequence padded to the one length reads what it reads alone
    (1e-5, as above), whole blocks of padded rows skipped or not."""
    sizes, _cfg, params = f32
    tokens = _prompt(length, 22)
    alone = np.asarray(ref.forward(params, sizes, tokens,
                                   np.arange(length)))
    monkeypatch.setattr(ref, "ROW_BLOCK", 32)
    padded = np.zeros(512, np.int64)
    padded[:length] = tokens
    got = np.asarray(ref.forward(params, sizes, padded, np.arange(length),
                                 length))
    np.testing.assert_allclose(got, alone, atol=1e-5, rtol=0)


@pytest.mark.parametrize("room", [1, 3, 64], ids=lambda r: f"room{r}")
def test_the_references_experts_take_their_rows_a_room_at_a_time(
        f32, monkeypatch, room):
    """A held expert's rows are gathered a room at a time, as many times
    as they fill one: the same sums whatever the room (1e-5: the order
    of float32 additions), and the padding is routed to no expert (rows
    all alike would all take the same experts, and fill room after
    room): past ``length`` the layer adds exactly nothing."""
    sizes, _cfg, params = f32
    p = params["layers_1"]["mlp"]
    h = jnp.asarray(np.random.default_rng(23).normal(
        size=(96, sizes["hidden_size"])), jnp.float32)
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    whole = np.asarray(ref._experts(h, p, sizes, 96, "f32", (), 96))
    got = np.asarray(ref._experts(h, p, sizes, 50, "f32", (), room))
    np.testing.assert_allclose(got[:50], whole[:50], atol=1e-5, rtol=0)
    assert np.abs(whole[64:]).max() > 0.01 and not got[64:].any()


def test_reference_refuses_an_unknown_fault(f32):
    sizes, _cfg, params = f32
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, sizes, np.arange(8), [7], faults=("typo",))


# -- counters, config, weights ----------------------------------------------


def test_decode_steps_count_live_positions_and_the_cache_they_hold(f32):
    """What ``/stats`` serves as ``model_counters``: over a request's
    decode steps, the live positions and the bytes of cache they hold in
    each layer (a pool row a position: 128 lanes of float32 here), and
    ``pages`` as every paged layer counts them. The cache the engine
    allocates is the latent: one array a layer, a row a position, and
    nothing a head wide."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params)
    _toks, logits = _greedy(eng, _prompt(5, 2), 9)
    steps = len(logits)
    layers = sizes["num_hidden_layers"]
    assert sorted(eng._cache) == [f"latent_{i}" for i in range(layers)]
    lanes = la.pool_lanes(32, 8)
    assert all(c.shape == (eng.kv_pages, 8, lanes)
               for c in eng._cache.values())
    assert eng.config.latent_width == 40 and eng.config.pool_lanes == lanes
    assert eng.kv_pool_bytes() == {
        "payload": layers * eng.kv_pages * 8 * lanes * 4, "scales": 0}
    # a step of a row at cursor p reads positions 0..p: 5..12 here
    positions = sum(range(6, 6 + steps))
    pages = sum(-(-p // 8) for p in range(6, 6 + steps))
    c = eng.stats.model_counters
    for i in range(layers):
        assert np.asarray(c[f"layers_{i}/self_attn/latent"]).tolist() == [
            positions, positions * lanes * 4]
        assert np.asarray(c[f"layers_{i}/self_attn/pages"]).tolist() == [
            pages, 0]
    # the expert layers count as every held layer does; the dense does not
    assert "layers_0/mlp/picks" not in c and "layers_1/mlp/picks" in c


def test_config_round_trips_and_refuses_training(f32):
    _sizes_, cfg, params = f32
    served = dataclasses.replace(cfg.decode_config(), page_size=8,
                                 kv_pages=20)
    key = served.program_key()
    hash(key)
    assert key[0] == "kimi_k2"
    assert serving.config_from_key(key) == served
    again = serving.config_from_dict(
        json.loads(json.dumps(dataclasses.asdict(served))) | {"new_key": 1})
    assert again == served
    assert "kimi_k2" in served.program_tag()
    assert set(served.attend_paths()) == {la.LATENT_GATHER}
    assert serving.attend_path_id(served) == "latent_gather"
    assert not getattr(served, "fixed_row_cache", False)
    with pytest.raises(ValueError, match="pairs"):
        KimiK2Config(qk_rope_head_dim=7)
    with pytest.raises(ValueError, match="leading layers"):
        KimiK2Config(num_hidden_layers=2, first_k_dense_replace=3)
    model = served.build()
    one = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="served, not trained"):
        model.apply({}, one, train=True)
    with pytest.raises(ValueError, match="paged cache only"):
        cfg.build().apply({}, one)


def test_weights_from_the_seed_have_the_decoders_own_shapes(bf16):
    """``perfbench/weights_kimi.py`` imports nothing of the program and
    keeps the published layouts; ``prepare_params`` splits each layer's
    ``q_b_proj`` and ``kv_b_proj`` a head into the parts a step
    multiplies, and the tree is then the decoder's own, name for name
    and shape for shape, the selection bias float32. A tree that is
    split already passes through."""
    sizes, cfg, params = bf16
    served = dataclasses.replace(cfg.decode_config(), page_size=8,
                                 kv_pages=20)
    own = jax.eval_shape(lambda: served.build().init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 1), jnp.int32),
        train=False, block_table=jnp.zeros((1, 16), jnp.int32),
        cache_pos=jnp.zeros((1,), jnp.int32)))["params"]
    split = served.prepare_params(params)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), own)
            == jax.tree.map(lambda x: (x.shape, x.dtype), split))
    assert split["layers_1"]["mlp"]["e_score_correction_bias"].dtype \
        == jnp.float32
    again = served.prepare_params(split)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(again), jax.tree.leaves(split)))
    # head 1's parts are its columns of the published matrices
    attn, pub = split["layers_0"]["self_attn"], \
        params["layers_0"]["self_attn"]
    np.testing.assert_array_equal(attn["q_b_nope"][:, 16:32],
                                  pub["q_b_proj"][:, 24:40])
    np.testing.assert_array_equal(attn["q_b_rope"][:, 8:16],
                                  pub["q_b_proj"][:, 40:48])
    np.testing.assert_array_equal(attn["kv_b_k"][:, 16:32],
                                  pub["kv_b_proj"][:, 32:48])
    np.testing.assert_array_equal(attn["kv_b_v"][:, 16:32],
                                  pub["kv_b_proj"][:, 48:64])
    other = weights_kimi.make_params(sizes, 8)
    leaf = lambda t: np.asarray(                # noqa: E731
        t["layers_1"]["mlp"]["e_score_correction_bias"])
    assert np.abs(leaf(other) - leaf(params)).max() > 0
