"""Xing4.0 (``gym_tpu/models/xing4.py``: four residual streams a token
mixed around every sub-layer by a manifold-constrained hyper-connection;
``kimi_k2.py``'s latent attention, SwiGLU and held experts inside) through
the serving engine against its plain reference (``perfbench/references/
xing4.py``: float32, a full forward over the whole sequence, no cache,
nothing imported from the program), at a small size on the CPU with seeded
random weights.

Sizes: the configuration file's ``rehearse`` preset (hidden 64 in 4
streams, 4 heads over a latent of 32 + 8, one dense SwiGLU layer and two
expert layers of 16 routed experts, 4 a token, ALL held, one shared, 256
rows of vocabulary, 20 Sinkhorn iterations) with pages of 8 and prefill
passes of 16 or 32.

* engine prefill (in two, three or five passes) then decode through the
  pool equals the reference's logits at every decoded position in float32
  (1e-5) and in bfloat16 (to a mean distance the fp8 control exceeds);
  greedy tokens through ``InferenceEngine`` and ``Scheduler`` are the
  reference's best;
* a prefix served from another request's pages, park and resume, and
  several tokens a row without ``last_pos`` (a speculative verify) serve
  the same;
* ``HeldExperts(held=(0, E))`` is the reference's whole layer, and the two
  halves' routed parts add up to it with the shared expert counted once;
* each planted wrong reading of the description fails a limit of the
  cell's rehearsal (the kind's own ``judge`` and ``verdict_rows``);
* the counters a decode step returns; the config through a program key
  and a dict; the seeded weights have the decoder's own shapes.

The hyper-connection alone: ``tests/test_hyper_connection.py``.
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
from gym_tpu.models.xing4 import Xing4Config
from gym_tpu.ops import latent_attention as la
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from perfbench import weights_xing4
from perfbench.kinds import closed_xing4
from perfbench.kinds.closed_model import verdict_rows
from perfbench.references import xing4 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "xing4.0-29b-a4b.serve-closed-reason"
# float32 program against float32 reference on logits of spread 1.0: the
# two differ in the order of float32 additions (the absorbed product, a
# row's past summed in blocks, the norm applied after ``u Phi``)
F32_TOL = 1e-5
# bfloat16 program against the float32 reference on logits of spread 1.0,
# as the MEAN distance over the compared logits
BF16_TOL = 0.08


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _sizes(dtype="float32", **over):
    config = _load("configs", "xing4.0-29b-a4b.json")
    return {**config, **config["rehearse"], "dtype": dtype, **over}


@pytest.fixture(scope="module")
def f32():
    sizes = _sizes()
    return (sizes, closed_xing4.model_config(sizes),
            weights_xing4.make_params(sizes, 7))


@pytest.fixture(scope="module")
def bf16():
    sizes = _sizes("bfloat16")
    return (sizes, closed_xing4.model_config(sizes),
            weights_xing4.make_params(sizes, 7))


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


def _is_reference(sizes, params, prompt, toks):
    return ref.served_gaps(params, sizes, list(prompt), toks,
                           pad_multiple=32).max() < F32_TOL


# -- the engine against the reference --------------------------------------

ROWS = [(3, 14, 32), (40, 6, 32), (70, 10, 32), (70, 6, 16)]
ROW_IDS = ["bucket4", "two_passes", "three_of_four", "five_of_eight"]


@pytest.mark.parametrize("plen,n_new,rows", ROWS, ids=ROW_IDS)
def test_prefill_in_passes_then_decode_through_the_pool_equals_the_reference(
        f32, plen, n_new, rows):
    """Float32 weights and pages: every decoded position's logits equal
    the reference's full forward to rounding. So the first block made
    four streams of the embedding and the last summed them, in the decode
    program and in every pass of the prefill; each sub-layer read ``h``
    and wrote ``X'`` by ITS coefficients; a pass's latents were in the
    pool before the next attended; and the passes that hold only the
    bucket's padding were skipped. The prefill's token is the reference's
    best."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen)
    eng = _engine(dataclasses.replace(cfg, prefill_rows=rows), params)
    assert eng.attend_path == la.LATENT_GATHER == "latent_gather"
    toks, logits = _greedy(eng, prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks == want.argmax(-1).tolist()
    assert np.abs(logits - want[1:]).max() < F32_TOL
    assert want.std() > 0.5          # logits worth comparing
    if plen > rows:                  # the padding's passes ran nothing
        bucket = 1 << (plen - 1).bit_length()
        assert eng.stats.prefill_tokens == bucket
        assert eng.stats.prefill_tokens_run == -(-plen // rows) * rows


def test_the_kernels_under_the_interpreter_serve_the_reference(
        f32, monkeypatch):
    """The engine with ``kimi_k2.py``'s two Pallas kernels at this
    model's 4 heads (the decode walk of the live pages, the prefill's
    flash attend) under the interpreter: the path the dispatch spans name
    is ``latent_paged`` and the logits are the reference's."""
    monkeypatch.setattr(la, "INTERPRET", True)
    sizes, cfg, params = f32
    eng = _engine(dataclasses.replace(cfg, prefill_rows=64), params)
    assert eng.attend_path == la.LATENT_KERNEL == "latent_paged"
    prompt = _prompt(70, 11)
    toks, logits = _greedy(eng, prompt, 5)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < 2e-5


@pytest.mark.parametrize("plen,n_new", [(40, 6), (70, 10)],
                         ids=["two_passes", "three_of_four"])
def test_prefill_then_decode_equals_the_reference_bf16(bf16, plen, n_new):
    """As served (bfloat16 weights, activations and pages; the streams
    and the hyper-connections float32): near the float32 reference, and
    nearer than the reference's own fp8 control."""
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
        assert _is_reference(sizes, params, p, toks)
    assert eng.stats.kv_blocks_in_use == 0


# -- the engine's one pool manager over this model's pages ------------------


@pytest.mark.parametrize("plen,shared", [(41, 24), (70, 64)],
                         ids=["suffix_in_two_passes", "last_page_only"])
def test_a_prefix_is_served_from_another_requests_pages(f32, plen, shared):
    """A prompt, then a second that shares its first ``shared`` tokens:
    the second is prefilled from position ``shared``, its four streams
    are made of the suffix alone and its attend reads the latents the
    FIRST request's prefill wrote. What it serves is the reference's."""
    sizes, cfg, params = f32
    eng = InferenceEngine(params, dataclasses.replace(cfg, prefill_rows=16),
                          num_slots=1, page_size=4)
    first = _prompt(plen, 80 + plen)
    toks, _lg = _greedy(eng, first, 5)
    assert _is_reference(sizes, params, first, toks)
    second = np.concatenate([first[:shared], _prompt(plen - shared, 7)])
    toks2, _lg = _greedy(eng, second, 6)
    assert eng.stats.prefix_hit_blocks == shared // 4
    assert _is_reference(sizes, params, second, toks2)


def test_park_then_another_rows_steps_then_resume_continues_identically(f32):
    """A row parked after three steps keeps its pages while another row
    decodes in its slot; resumed, it goes on with the tokens of the run
    that was never parked, which are the reference's."""
    sizes, cfg, params = f32
    prompt, n_new = _prompt(37, 5), 12
    sp = SamplingParams(max_new_tokens=n_new, top_k=1)
    eng = InferenceEngine(params, cfg, num_slots=1, page_size=4)
    slot, ev = eng.admit(prompt, sp)
    toks = [ev.token]
    for _ in range(3):
        toks += [e.token for e in eng.step()]
    parked = eng.park(slot)
    assert len(_greedy(eng, _prompt(20, 6), 7)[0]) == 7      # the same slot
    slot = eng.resume(parked)
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert len(toks) == n_new
    assert _is_reference(sizes, params, prompt, toks)
    assert eng.stats.preemptions == 1 and eng.stats.resumes == 1


def test_several_tokens_a_row_without_last_pos_serve_the_same(f32):
    """``spec_tokens`` 3: a verify runs four tokens a row through the
    decode form (no ``last_pos``): four positions' streams a row, each
    mixed by its own coefficients, the attend absorbed over the pages.
    The stream is the plain engine's and the reference's."""
    sizes, cfg, params = f32
    prompt = np.tile(_prompt(6, 3), 5)          # repeats: drafts match
    eng = InferenceEngine(params, cfg, num_slots=1, page_size=4,
                          spec_tokens=3)
    slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=14, top_k=1))
    toks = [ev.token]
    while slot not in eng.free_slots():
        toks += [e.token for e in eng.step() if e.slot == slot]
    assert eng.stats.spec_drafted > 0 and len(toks) == 14
    assert _is_reference(sizes, params, prompt, toks)


# -- the expert layer held whole --------------------------------------------

E, K, C, F = 16, 4, 32, 16


def _layer_params(seed=3):
    rng = np.random.default_rng(seed)
    return {"router": rng.normal(0, 0.3, (C, E)),
            "e_score_correction_bias": rng.normal(0, 0.2, (E,)),
            "gate_proj": rng.normal(0, 0.2, (E, C, F)),
            "up_proj": rng.normal(0, 0.2, (E, C, F)),
            "down_proj": rng.normal(0, 0.2, (E, F, C)),
            "shared_gate_proj": rng.normal(0, 0.2, (1, C, F)),
            "shared_up_proj": rng.normal(0, 0.2, (1, C, F)),
            "shared_down_proj": rng.normal(0, 0.2, (1, F, C))}


def _held(full, lo, hi):
    cut = {k: (v[lo:hi] if k in ("gate_proj", "up_proj", "down_proj")
               else v) for k, v in full.items()}
    layer = HeldExperts(hidden=C, width=F, n_experts=E, topk=K,
                        held=(lo, hi), n_shared=1, param_dtype=jnp.float32,
                        score_fn="sigmoid", select_bias=True)
    return layer, {"params": jax.tree.map(
        lambda x: jnp.asarray(x, jnp.float32), cut)}


def test_the_layer_held_whole_is_the_references_and_two_halves_add_up():
    """``held = (0, E)``, as the cell holds its 64: routed times
    ``routed_scaling_factor`` plus shared is the reference's whole layer
    (``references/kimi_k2.py:_experts``, which this model's reference
    runs). Held in two halves, each routes over all 16 and computes its
    own experts' part: the two routed parts add up to the whole layer's,
    the shared expert (which each half computes alike) counted once."""
    full = _layer_params()
    h = jax.random.normal(jax.random.PRNGKey(1), (24, C))
    sizes = {"held_experts": [0, E], "num_experts_per_tok": K,
             "norm_topk_prob": True, "n_shared_experts": 1,
             "routed_scaling_factor": 2.0}
    p = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), full)
    want = np.asarray(ref._experts(h, p, sizes, 24, "f32", (), 24))
    layer, variables = _held(full, 0, E)
    routed, shared = layer.apply(variables, h)
    np.testing.assert_allclose(2.0 * routed + shared, want, atol=2e-5)
    halves = [_held(full, lo, lo + E // 2) for lo in (0, E // 2)]
    parts = [m.apply(v, h) for m, v in halves]
    np.testing.assert_allclose(parts[0][0] + parts[1][0], routed,
                               atol=2e-5)
    np.testing.assert_allclose(parts[0][1], shared, atol=1e-6)
    np.testing.assert_allclose(parts[1][1], shared, atol=1e-6)
    assert np.abs(2.0 * parts[0][0] + shared - want).max() > 0.01


# -- planted faults ---------------------------------------------------------

SERVED_LENGTHS = (24, 37, 80, 52, 66, 29)


def _context(sizes, seed=5):
    traffic = _load("traffic", "serve-closed-reason.json")
    limits = _load("limits", CELL + ".json")
    return {"traffic": {**traffic, **traffic["rehearse"]}, "sizes": sizes,
            "args": types.SimpleNamespace(seed=seed),
            "devices": jax.devices(), "limits": limits["rehearse"]}


@pytest.fixture(scope="module")
def served():
    """Six greedy requests through one slot at the rehearsal's sizes and
    dtype, and the context the kind's ``judge`` reads."""
    config = _load("configs", "xing4.0-29b-a4b.json")
    sizes = {**config, **config["rehearse"]}
    ctx = _context(sizes)
    eng = InferenceEngine(
        weights_xing4.make_params(sizes, 5),
        closed_xing4.model_config(sizes), num_slots=1,
        page_size=int(ctx["traffic"]["page_size"]))
    rng, picked = np.random.default_rng(5), []
    for n in SERVED_LENGTHS:
        prompt = rng.integers(0, sizes["vocab_size"], n)
        toks, _lg = _greedy(eng, prompt, 24)
        picked.append({"prompt": prompt.tolist(), "tokens": toks})
    sound = closed_xing4.judge(ctx, picked)
    sound["lower"] = closed_xing4.judge(ctx, picked, "fp8")
    return ctx, picked, sound


def test_sound_tokens_pass_and_the_fp8_control_fails(served):
    ctx, _picked, sound = served
    rows = verdict_rows(ctx, sound, 0, [])
    assert all(r["ok"] for r in rows), rows
    assert sound["tokens"] == 6 * 24 and sound["lower"]["mean"] > 0
    control = dict(sound["lower"], lower=sound["lower"])
    rows = verdict_rows(ctx, control, 0, [])
    assert rows[1]["name"] == "served_logit_gap_vs_fp8"
    assert rows[1]["value"] == 1.0 and not rows[1]["ok"]


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_wrong_reading_fails_the_rehearsals_limits(served, fault):
    """A program with one wrong reading of the description (``H_res``'s
    rows normalised once, a softmax, and no Sinkhorn; 2 iterations for
    20; ``alpha_res = 0``, the static matrix; ``H_post`` without its
    factor 2; the selection bias used as a weight) would serve the tokens
    that reading puts first: at least one limit of the cell's rehearsal
    refuses them."""
    ctx, picked, sound = served
    wrong = closed_xing4.judge(ctx, picked, faults=(fault,))
    wrong["lower"] = sound["lower"]
    rows = verdict_rows(ctx, wrong, 0, [])
    assert not all(r["ok"] for r in rows), rows


def test_reference_refuses_an_unknown_fault(f32):
    sizes, _cfg, params = f32
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, sizes, np.arange(8), [7], faults=("typo",))


@pytest.mark.parametrize("length", [37, 100])
def test_the_references_padding_moves_no_logit(f32, length):
    """A sequence padded to the one length reads what it reads alone
    (1e-5): the padding's streams are mixed like any row's and reach no
    real position."""
    sizes, _cfg, params = f32
    tokens = _prompt(length, 22)
    alone = np.asarray(ref.forward(params, sizes, tokens,
                                   np.arange(length)))
    padded = np.zeros(512, np.int64)
    padded[:length] = tokens
    got = np.asarray(ref.forward(params, sizes, padded, np.arange(length),
                                 length))
    np.testing.assert_allclose(got, alone, atol=1e-5, rtol=0)


# -- counters, config, weights ----------------------------------------------


def test_decode_steps_count_the_rows_their_hyper_connections_mixed(f32):
    """What ``/stats`` serves as ``model_counters``: every layer's
    ``hc/rows`` = [rows mixed, sub-layers] (one live row through two
    sub-layers a step), the dense layer's too, beside the latent layers'
    and the expert layers' own. The cache is one array of latents a
    layer, ``layers_<i>/latent``."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params)
    _toks, logits = _greedy(eng, _prompt(5, 2), 9)
    steps = len(logits)
    layers = sizes["num_hidden_layers"]
    lanes = la.pool_lanes(32, 8)
    assert sorted(eng._cache) == [f"layers_{i}" for i in range(layers)]
    assert all(list(c) == ["latent"]
               and c["latent"].shape == (eng.kv_pages, 8, lanes)
               for c in eng._cache.values())
    assert eng.kv_pool_bytes() == {
        "payload": layers * eng.kv_pages * 8 * lanes * 4, "scales": 0}
    c = eng.stats.model_counters
    positions = sum(range(6, 6 + steps))
    for i in range(layers):
        assert np.asarray(c[f"layers_{i}/hc/rows"]).tolist() == [
            2 * steps, 2 * steps]
        assert np.asarray(c[f"layers_{i}/self_attn/latent"]).tolist() == [
            positions, positions * lanes * 4]
    assert "layers_0/mlp/picks" not in c
    assert np.asarray(c["layers_1/mlp/picks"]).sum() == steps * 4
    assert int(c["layers_1/mlp/tokens"]) == steps


def test_config_round_trips_and_refuses_training(f32):
    _sizes_, cfg, _params = f32
    served = dataclasses.replace(cfg.decode_config(), page_size=8,
                                 kv_pages=20)
    key = served.program_key()
    hash(key)
    assert key[0] == "xing4_0" and served.hc_mult == 4
    assert serving.config_from_key(key) == served
    again = serving.config_from_dict(
        json.loads(json.dumps(dataclasses.asdict(served))) | {"new_key": 1})
    assert again == served
    assert "xing4_0" in served.program_tag()
    assert "hc=4" in served.program_tag()
    assert serving.attend_path_id(served) == "latent_gather"
    assert served.prefill_pass(128) == 32 and served.prefill_pass(16) == 16
    # the published sizes are the class's defaults
    pub = Xing4Config()
    assert (pub.hidden_size, pub.num_attention_heads, pub.q_lora_rank,
            pub.n_routed_experts, pub.num_experts_per_tok,
            pub.hc_sinkhorn_iters) == (3584, 32, 768, 64, 4, 20)
    model = served.build()
    one = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="served, not trained"):
        model.apply({}, one, train=True)
    with pytest.raises(ValueError, match="paged cache only"):
        cfg.build().apply({}, one)


def test_weights_from_the_seed_have_the_decoders_own_shapes(bf16):
    """``perfbench/weights_xing4.py`` imports nothing of the program;
    ``prepare_params`` splits each layer's ``q_b_proj`` and ``kv_b_proj``
    as ``kimi_k2.py`` does and keeps every layer's ``hc`` float32, from
    the tree as given (not through bfloat16). The tree is then the
    decoder's own, name for name, shape for shape and dtype for dtype."""
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
    hc = split["layers_1"]["hc"]
    assert sorted(hc) == ["alpha_attn", "alpha_mlp", "bias_attn",
                          "bias_mlp", "phi_attn", "phi_mlp"]
    assert hc["phi_mlp"].shape == (4 * 64, 24)
    assert all(v.dtype == jnp.float32 for v in hc.values())
    np.testing.assert_array_equal(hc["phi_attn"],
                                  params["layers_1"]["hc"]["phi_attn"])
    assert split["layers_1"]["mlp"]["router"].dtype == jnp.bfloat16
    # the coefficients move: biases of order one, Phi of unit scale
    assert np.asarray(hc["bias_attn"]).std() > 0.5
    assert np.asarray(hc["phi_attn"]).std() * 16 == pytest.approx(1.0,
                                                                  rel=0.1)
    other = weights_xing4.make_params(sizes, 8)
    assert np.abs(np.asarray(other["layers_1"]["hc"]["phi_attn"])
                  - np.asarray(hc["phi_attn"])).max() > 0
