"""The Cohere2-MoE decoder (``gym_tpu/models/cohere2_moe.py``) through the
serving engine against its plain reference
(``perfbench/references/command_a_plus.py``: float32, a full forward over
the whole sequence, no cache, nothing imported from the program), at a
small size on the CPU with seeded random weights.

Sizes: the configuration file's ``rehearse`` preset (hidden 64, 8 query
heads over 2 key-value heads of 16, window 8, the pattern window, window,
window, full once, 16 routed experts of which 4 are held, 4 a token, 2
shared, 256 rows of vocabulary) with pages of 4 positions, so that a row
of thirty positions crosses the window and several page boundaries.

* engine prefill then paged decode equals the reference's logits at every
  decoded position, in float32 (to rounding) and in bfloat16 (within a
  tolerance the fp8 control exceeds);
* the same through ``Scheduler`` for rows of mixed length;
* the shares add up: the routed parts of all four shares plus the shared
  experts once equal the uncut reference layer;
* a key just outside the window changes a full layer and not a window
  layer;
* the grouped kernel under the Pallas interpreter through the engine
  (alone against a gathered window: ``tests/test_paged_attention_gqa.py``);
* each planted wrong reading of the description is caught by the
  comparison.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gym_tpu.ops.paged_attention as pa
from gym_tpu.models import serving
from gym_tpu.models.cohere2_moe import (FULL, SLIDING, Cohere2MoeConfig,
                                        rotate_interleaved)
from gym_tpu.models.moe import HeldExperts
from gym_tpu.models.nanogpt import GPTConfig
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from perfbench import weights_moe
from perfbench.kinds.closed_model import model_config
from perfbench.references import command_a_plus as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 4
# float32 program against float32 reference: the order of additions
F32_TOL = 2e-4
# bfloat16 program (operands rounded to 8 bits of mantissa before every
# product) against the float32 reference on logits of spread 1.2, as the
# MEAN distance over the compared logits: nine runs (three seeds, these
# three rows) read 0.005 to 0.049, the fp8 control 0.116 to 0.209. The
# widest distance is no yardstick here: a token whose fourth and fifth
# best router scores lie within a rounding takes another expert, and one
# such flip moves single logits by 0.5 in either precision.
BF16_TOL = 0.08
# a wrong reading of the description moves single logits by 0.8 to 5.0
FAULT_MIN = 0.3


def _sizes(dtype="float32", **over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "command-a-plus.json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"], "dtype": dtype, **over}


@pytest.fixture(scope="module")
def f32():
    sizes = _sizes()
    return sizes, model_config(sizes), weights_moe.make_params(sizes, 7)


@pytest.fixture(scope="module")
def bf16():
    sizes = _sizes("bfloat16")
    return sizes, model_config(sizes), weights_moe.make_params(sizes, 7)


def _engine(cfg, params, slots=2, kv_pages=80, page=PAGE):
    return InferenceEngine(params, cfg, num_slots=slots, paged=True,
                           page_size=page, kv_pages=kv_pages)


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
    """The reference's logits at the positions whose next token the
    engine decoded: the last prompt position, then every served token
    but the last."""
    seq = np.concatenate([prompt, toks])[:-1]
    pos = np.arange(len(prompt) - 1, len(seq))
    return np.asarray(ref.forward(params, sizes, seq, pos, **kw))


# -- the engine against the reference --------------------------------------


@pytest.mark.parametrize("plen,n_new", [(3, 14), (13, 12), (30, 6)],
                         ids=["into_window", "across_window", "past_window"])
def test_prefill_then_paged_decode_equals_the_reference_f32(f32, plen,
                                                            n_new):
    """Float32 weights and cache: every decoded position's logits equal
    the full forward's to rounding; the prefill's token is the
    reference's best at the last prompt position."""
    sizes, cfg, params = f32
    prompt = _prompt(plen, plen)
    toks, logits = _greedy(_engine(cfg, params), prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL
    assert want.std() > 0.5          # logits worth comparing


@pytest.mark.parametrize("plen,n_new", [(3, 14), (13, 12), (30, 6)],
                         ids=["into_window", "across_window", "past_window"])
def test_prefill_then_paged_decode_equals_the_reference_bf16(bf16, plen,
                                                             n_new):
    """As served: bfloat16 weights and cache, float32 accumulation. The
    reference reads the same bfloat16 values in float32. The tolerance is
    one that fp8 operands exceed."""
    sizes, cfg, params = bf16
    prompt = _prompt(plen, plen)
    toks, logits = _greedy(_engine(cfg, params), prompt, n_new)
    want = _reference_logits(params, sizes, prompt, toks)
    assert np.abs(logits - want[1:]).mean() < BF16_TOL
    lower = _reference_logits(params, sizes, prompt, toks, mode="fp8")
    assert np.abs(lower - want).mean() > 1.25 * BF16_TOL


def test_a_long_prefill_taken_in_blocks_equals_the_reference(f32):
    """A prefill longer than ``attn_query_block`` queries or
    ``moe_chunk_rows`` token-picks runs in blocks (a 16k prompt on the
    chip: 8 blocks of queries, 16 of sorted picks of which those past
    the held picks are skipped); here 4 and 8 blocks of a 32 bucket."""
    sizes, cfg, params = f32
    blocks = dataclasses.replace(cfg, attn_query_block=8, moe_chunk_rows=16)
    prompt = _prompt(30, 4)
    toks, logits = _greedy(_engine(blocks, params), prompt, 5)
    want = _reference_logits(params, sizes, prompt, toks)
    assert toks[0] == int(want[0].argmax())
    assert np.abs(logits - want[1:]).max() < F32_TOL


def test_scheduler_serves_rows_of_mixed_length_as_the_reference(f32):
    """Six greedy requests of mixed length through three slots and one
    pool: every served token is the reference's best at its position
    (``served_gaps``: how far below the best its logit lies)."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params, slots=3, kv_pages=96)
    sched = Scheduler(eng, max_queue=8)
    shapes = [(5, 9), (21, 12), (9, 4), (33, 7), (2, 16), (14, 10)]
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
        gaps = ref.served_gaps(params, sizes, p, toks, pad_multiple=8)
        assert gaps.max() < F32_TOL
    assert eng.stats.kv_blocks_in_use == 0


def test_kernel_through_the_engine_equals_the_reference(bf16, monkeypatch):
    """The grouped page walk (under the interpreter) in place of the
    gathered window, in the prefill and the decode program alike: one
    window layer and one full layer, a row that crosses the window."""
    sizes, _cfg, _p = bf16
    two = {**sizes, "num_hidden_layers": 2,
           "layer_types": [SLIDING, FULL]}
    cfg, params = model_config(two), weights_moe.make_params(two, 7)
    monkeypatch.setattr(pa, "INTERPRET", True)
    # pages of 8: the interpreter unrolls a chunk's page copies
    eng = _engine(cfg, params, kv_pages=40, page=8)
    assert eng.attend_path == pa.KERNEL + "+" + pa.KERNEL_WINDOW
    prompt = _prompt(7, 3)
    toks, logits = _greedy(eng, prompt, 5)
    want = _reference_logits(params, two, prompt, toks)
    assert np.abs(logits - want[1:]).mean() < BF16_TOL
    assert eng.stats.paged_kernel_dispatches == 1 + len(logits)


# -- planted faults ---------------------------------------------------------


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_wrong_reading_of_the_description_is_caught(f32, fault):
    """The reference with one wrong reading planted is far from the
    engine: thousands of times float32's tolerance."""
    sizes, cfg, params = f32
    prompt = _prompt(13, 13)
    toks, logits = _greedy(_engine(cfg, params), prompt, 12)
    wrong = _reference_logits(params, sizes, prompt, toks, faults=(fault,))
    assert np.abs(logits - wrong[1:]).max() > FAULT_MIN


def test_reference_refuses_an_unknown_fault(f32):
    sizes, _cfg, params = f32
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, sizes, np.arange(8), [7], faults=("typo",))


# -- the shares add up -------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(f32):
    """Sixteen routed experts over four chips: the routed parts that the
    four shares compute (routing over all sixteen, each its own four
    experts), plus the shared experts once, equal what the uncut
    reference gives for the whole expert layer."""
    sizes, _cfg, _p = f32
    whole = {**sizes, "held_experts": [0, 16]}
    p = weights_moe.make_params(whole, 11)["layers_0"]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    want, fullest = ref._experts(h, p, whole, "f32", (), 24)
    assert int(fullest) <= 24

    total, shared_once = jnp.zeros_like(h), None
    for lo in range(0, 16, 4):
        layer = HeldExperts(hidden=64, width=64, n_experts=16, topk=4,
                            held=(lo, lo + 4), n_shared=2,
                            param_dtype=jnp.float32)
        share = {k: (v[lo:lo + 4] if k in ("gate_proj", "up_proj",
                                           "down_proj") else v)
                 for k, v in p.items()}
        routed, shared = layer.apply({"params": share}, h)
        total = total + routed
        if shared_once is None:
            shared_once = shared
        else:       # every chip computes the shared experts alike
            np.testing.assert_array_equal(shared, shared_once)
    np.testing.assert_allclose(total + shared_once, want, atol=2e-5)
    # and one share alone is not the layer
    assert np.abs(np.asarray(routed + shared_once - want)).max() > 0.1


def test_held_experts_count_what_they_ran(f32):
    """The counters a decode step returns: token-picks on each held
    expert (live rows only), the held experts hit, the live rows."""
    sizes, _cfg, _p = f32
    p = weights_moe.make_params(sizes, 11)["layers_0"]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(2), (10, 64), jnp.float32)
    live = jnp.arange(10) < 6
    layer = HeldExperts(hidden=64, width=64, n_experts=16, topk=4,
                        held=(4, 8), n_shared=2, param_dtype=jnp.float32)
    _out, var = layer.apply({"params": p}, h, live, mutable=["counters"])
    scores = jax.nn.sigmoid(h @ p["router"])
    top = np.asarray(jax.lax.top_k(scores, 4)[1])
    want = [(top[:6] == e).sum() for e in range(4, 8)]
    got = var["counters"]
    assert np.asarray(got["picks"]).tolist() == want
    assert int(got["tokens"]) == 6
    assert int(got["hit"]) == sum((top == e).any() for e in range(4, 8))


# -- the window ---------------------------------------------------------------


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_a_key_just_outside_the_window_moves_only_a_full_layer(f32, kind):
    """One layer, a prompt of 21 positions, window 8: the prefill's last
    query (position 20) sees keys 13..20 on a window layer, the first
    decode step's (21) keys 14..21. Another token at position 12 changes
    a full layer's logits and leaves a window layer's exactly as they
    were; another token at 14 changes both."""
    sizes, _cfg, _p = f32
    one = {**sizes, "num_hidden_layers": 1, "layer_types": [kind]}
    cfg, params = model_config(one), weights_moe.make_params(one, 5)

    def served(prompt):
        toks, logits = _greedy(_engine(cfg, params), prompt, 2)
        return toks[0], logits[0]

    base = _prompt(21, 1)
    outside, inside = base.copy(), base.copy()
    outside[12] = (outside[12] + 1) % 256
    inside[14] = (inside[14] + 1) % 256
    (tok, want), (tok_far, far), (_t, near) = (
        served(base), served(outside), served(inside))
    if kind == SLIDING:
        assert tok_far == tok
        np.testing.assert_array_equal(far, want)
    else:
        assert np.abs(far - want).max() > 1e-3
    assert np.abs(near - want).max() > 1e-3


def test_rotary_turns_interleaved_pairs():
    """``rope_gptj``: pair (2i, 2i+1) turned by ``pos * theta ** (-2i /
    d)``; position 0 is the identity; a scalar product depends on the
    distance alone."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 1, 8))
    pos = jnp.asarray([0, 1, 2, 7, 30])
    y = np.asarray(rotate_interleaved(x, pos[:, None], 50000.0))
    np.testing.assert_allclose(y[0], x[0], atol=1e-6)
    for i in range(4):
        ang = 7 * 50000.0 ** (-2 * i / 8)
        x0, x1 = x[3, 0, 2 * i], x[3, 0, 2 * i + 1]
        np.testing.assert_allclose(
            y[3, 0, 2 * i:2 * i + 2],
            [x0 * np.cos(ang) - x1 * np.sin(ang),
             x1 * np.cos(ang) + x0 * np.sin(ang)], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref._rotate(x, 50000.0))[:3],
                               y[:3], atol=1e-6)
    q = jnp.ones((2, 1, 8))
    a = rotate_interleaved(q, jnp.asarray([[3], [10]]), 50000.0)
    b = rotate_interleaved(q, jnp.asarray([[13], [20]]), 50000.0)
    np.testing.assert_allclose((a[0] * a[1]).sum(), (b[0] * b[1]).sum(),
                               rtol=1e-5)


# -- the protocol ------------------------------------------------------------


def test_config_round_trips_through_a_program_key_and_a_dict(f32):
    """The engine keys programs by ``program_key()`` and the loaders
    rebuild configs from JSON: both find the family again; GPT-2's key
    is its plain field tuple, as it was."""
    _sizes_, cfg, _p = f32
    paged = dataclasses.replace(cfg.decode_config(), page_size=4,
                                kv_pages=40)
    key = paged.program_key()
    hash(key)
    assert key[0] == "cohere2_moe"
    assert serving.config_from_key(key) == paged
    again = serving.config_from_dict(
        json.loads(json.dumps(dataclasses.asdict(paged))) | {"new_key": 1})
    assert again == paged
    gpt = GPTConfig(block_size=32, vocab_size=48, n_layer=1, n_head=2,
                    n_embd=16)
    assert gpt.program_key() == dataclasses.astuple(gpt)
    assert serving.config_from_key(gpt.program_key()) == gpt
    assert serving.config_from_dict(dataclasses.asdict(gpt)) == gpt
    assert gpt.program_tag() == "" and "cohere2_moe" in paged.program_tag()


def test_config_reads_layer_types_and_refuses_what_it_cannot_run():
    cfg = Cohere2MoeConfig(num_hidden_layers=8)
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 2
    assert [w for _h, _d, w in cfg.kv_layout()] == [4096, 4096, 4096, 0] * 2
    with pytest.raises(ValueError, match="names 2 layers"):
        Cohere2MoeConfig(num_hidden_layers=4, layer_types=(SLIDING, FULL))
    with pytest.raises(ValueError, match="unknown layer types"):
        Cohere2MoeConfig(num_hidden_layers=1, layer_types=("linear",))
    with pytest.raises(ValueError, match="whole groups"):
        Cohere2MoeConfig(num_attention_heads=12, num_key_value_heads=8)
    small = model_config(_sizes())
    with pytest.raises(ValueError, match="only KV cache"):
        InferenceEngine(weights_moe.make_params(_sizes(), 0), small,
                        num_slots=1, paged=False)


def test_weights_from_the_seed_have_the_decoders_own_shapes(bf16):
    """``perfbench/weights_moe.py`` imports nothing of the program: its
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
    again = weights_moe.make_params(sizes, 7)
    other = weights_moe.make_params(sizes, 8)
    leaf = lambda t: np.asarray(                # noqa: E731
        t["layers_2"]["mlp"]["up_proj"], np.float32)
    np.testing.assert_array_equal(leaf(again), leaf(params))
    assert np.abs(leaf(other) - leaf(params)).max() > 0


def test_decode_steps_count_picks_and_pages(f32):
    """What ``/stats`` serves as ``model_counters``: over a request's
    decode steps, the live rows, their picks on the held experts, and on
    window layers the pages read and the pages the window skipped."""
    sizes, cfg, params = f32
    eng = _engine(cfg, params)
    _toks, logits = _greedy(eng, _prompt(30, 2), 9)
    steps = len(logits)
    c = eng.stats.model_counters
    for i in range(4):
        assert int(c[f"layers_{i}/mlp/tokens"]) == steps
        picks = np.asarray(c[f"layers_{i}/mlp/picks"])
        assert picks.shape == (4,) and 0 <= picks.sum() <= 4 * steps
    # positions 30..37 are written by the eight steps: a full layer
    # reads every page of the row, a window layer the pages that hold
    # the last 8 positions
    full = sum((p // PAGE) + 1 for p in range(30, 30 + steps))
    first = [max(p - 8 + 1, 0) // PAGE for p in range(30, 30 + steps)]
    assert np.asarray(c["layers_3/self_attn/pages"]).tolist() == [full, 0]
    assert np.asarray(c["layers_0/self_attn/pages"]).tolist() == [
        full - sum(first), sum(first)]
