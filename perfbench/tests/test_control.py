"""The comparison that decides ``correct`` has been shown to fail: the
control (the reference in the nearest precision below the stated one, in
the program's place) comes out as not correct at a size a test can hold,
and a run whose timed path is broken underneath ends ``correct: false``."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import reference, weights
from perfbench.harness import load_json
from perfbench.kinds import closed, fit

MID = dict(vocab_size=4096, n_positions=128, n_layer=4, n_embd=128,
           n_head=4)
HYPER = dict(lr=6e-4, b1=0.9, b2=0.999, eps=1e-8, wd=0.01)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_gradient_projection(seed):
    """Training states bf16; the control computes in fp8. Its first-moment
    projections leave the reference's by several times what bf16 does."""
    rng = np.random.default_rng(seed)
    params = weights.make_params(MID, seed)
    batches = [[(jnp.asarray(rng.integers(0, 4096, (4, 128)), jnp.int32),
                 jnp.asarray(rng.integers(0, 4096, (4, 128)), jnp.int32))
                for _ in range(3)]]
    kw = dict(n_head=4, hyper=HYPER, reduce="mean", rows_block=4)
    ref = reference.follow_training(params, batches, **kw)
    sound = reference.follow_training(params, batches, mode="bf16", **kw)
    ctl = reference.follow_training(params, batches, mode="fp8", **kw)
    rms_sound = fit.projection_gaps(sound["nodes"][0], ref["nodes"][0])[2]
    rms_ctl = fit.projection_gaps(ctl["nodes"][0], ref["nodes"][0])[2]
    limit = load_json(os.path.join(ROOT, "perfbench", "limits",
                                   "gpt2-base.train-node1.json")
                      )["limits"]["grad_moment_proj_gap_rms"]
    assert rms_sound < limit < rms_ctl, (rms_sound, rms_ctl)
    assert rms_ctl > 3 * rms_sound


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_control_fails_the_served_logit_gap(seed):
    """Serving states float32; the control computes in bfloat16. The token
    it puts first lies below the reference's best somewhere."""
    scale = 16.0
    params = weights.make_params(MID, seed, block_kernel_scale=scale)
    rng = np.random.default_rng(seed)
    widest = mean = 0.0
    for _ in range(3):
        prompt, served = rng.integers(0, 4096, 40).tolist(), []
        for _ in range(40):
            seq = np.zeros(128, np.int32)
            seq[:len(prompt) + len(served)] = prompt + served
            lg = reference._logits_jit(
                params, jnp.asarray(seq),
                jnp.asarray([len(prompt) + len(served) - 1]), 4, "f32")
            served.append(int(jnp.argmax(lg[0])))
        own = reference.served_gaps(params, prompt, served, 4, pad_to=128,
                                    n_pos=64)
        assert float(own.max()) == 0.0
        low = reference.served_gaps(params, prompt, served, 4, pad_to=128,
                                    n_pos=64, mode="bf16")
        widest, mean = max(widest, float(low.max())), mean + float(low.mean())
    assert widest > 0.0 and mean > 0.0
    # what a run compares: the served tokens' mean gap against this one.
    # The reference's own tokens read 0, the control in the program's
    # place reads 1.0, and the cell's limit lies between
    limit = load_json(os.path.join(ROOT, "perfbench", "limits",
                                   "gpt2-base.serve-closed.json")
                      )["limits"]["served_logit_gap_vs_bf16"]
    assert closed.gap_ratio(0.0, mean) == 0.0 < limit
    assert closed.gap_ratio(mean, mean) == 1.0 > limit


BROKEN_STEP = """
import gym_tpu.trainer as t
_make = t.make_train_step
def make(*a, **k):
    step = _make(*a, **k)
    def unchanged(state, batch):
        _new, metrics = step(state, batch)
        return state, metrics
    return unchanged
t.make_train_step = make
"""

HALF_BATCH = """
import perfbench.data as d
_take = d.TokenStream.take
def take(self, idx):
    x, y = _take(self, idx)         # the whole batch is on record
    x, y = x.copy(), y.copy()
    half = len(x) // 2
    if half:                        # the program sees its first half twice
        x[half:], y[half:] = x[:half], y[:half]
    return x, y
d.TokenStream.take = take
"""

ALTERED_TOKEN = """
import gym_tpu.programs.serve_defs as d
_sample = d.sample_logits
def altered(logits, *a, **k):
    return (_sample(logits, *a, **k) + 1) % logits.shape[-1]
d.sample_logits = altered
"""


@pytest.mark.parametrize("cell,patch,failing", [
    ("gpt2-base.train-node1", BROKEN_STEP, "param_change_norm_gap"),
    ("gpt2-base.train-node1", HALF_BATCH, "loss_gap_step0"),
    ("gpt2-base.serve-closed", ALTERED_TOKEN, "served_logit_gap_widest"),
])
def test_a_broken_timed_path_ends_correct_false(run, cell, patch, failing):
    code, lines, err = run(["--workload", cell, "--seed", "11", "--seconds",
                            "1.5", "--trace", "0", "--rehearse"],
                           patch=patch)
    assert code == 0, err[-2000:]
    assert last_json(lines)["correct"] is False
    compared = {json.loads(ln)["compared"]: json.loads(ln)
                for ln in lines if '"compared"' in ln}
    assert compared[failing]["ok"] is False
