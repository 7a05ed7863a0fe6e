"""The grouped paged-attention kernel (``ops/paged_attention.py:
paged_attention_gqa``: several query heads a key-value head, float32 or
bfloat16, a window or none) under the Pallas interpreter against a
gathered window, and its dispatch point. Through the engine against the
model's reference: ``tests/test_cohere2_moe.py``; compiled for the chip at
the served sizes: ``tests/test_chip_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gym_tpu.ops.paged_attention as pa

KVH, G, HD, KPAGE, MB, POOL = 2, 4, 16, 8, 12, 60


def _gathered(q, kp, vp, bt, pos, window):
    b, kvh, t, _g, hd = q.shape
    s = bt.shape[1] * kp.shape[1]
    k = kp[bt].reshape(b, s, kvh, hd).astype(jnp.float32)
    v = vp[bt].reshape(b, s, kvh, hd).astype(jnp.float32)
    att = jnp.einsum("bktgd,bskd->bktgs", q.astype(jnp.float32),
                     k) / np.sqrt(hd)
    qpos = pos[:, None] + jnp.arange(t)[None]
    col = jnp.arange(s)[None, None]
    seen = col <= qpos[..., None]
    if window:
        seen = seen & (col > qpos[..., None] - window)
    att = jax.nn.softmax(jnp.where(seen[:, None, :, None], att, -jnp.inf),
                         -1)
    return jnp.einsum("bktgs,bskd->bktgd", att, v)


@pytest.mark.parametrize("t,pos", [(1, [0, 5, 40, 95]), (8, [0, 8, 30, 88]),
                                   (40, [0, 3, 50, 56])],
                         ids=["decode", "chunk8", "prefill40"])
@pytest.mark.parametrize("window", [0, 20], ids=["full", "window20"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_grouped_kernel_equals_a_gathered_window(monkeypatch, dtype, tol,
                                                 window, t, pos):
    """Four query heads a key-value head, rows at different depths of
    their tables (the first page, mid-page, past the window, the last
    page), under the Pallas interpreter."""
    monkeypatch.setattr(pa, "INTERPRET", True)
    rng = np.random.default_rng(t + window)
    b = len(pos)
    q = jnp.asarray(rng.standard_normal((b, KVH, t, G, HD)), dtype)
    kp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)), dtype)
    vp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)), dtype)
    bt = jnp.asarray(1 + rng.permutation(POOL - 1)[:b * MB].reshape(b, MB),
                     jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    out = pa.paged_attention_gqa(q, kp, vp, bt, pos, window=window)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = _gathered(q, kp, vp, bt, pos, window)
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < tol


def test_grouped_kernel_never_reads_pages_before_the_window(monkeypatch):
    """A window layer of a long row: poison (NaN) in every page wholly
    older than the window, as a recycled page may hold, changes nothing;
    the same poison inside the window does."""
    monkeypatch.setattr(pa, "INTERPRET", True)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, KVH, 1, G, HD)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)),
                     jnp.float32)
    bt = jnp.arange(1, MB + 1, dtype=jnp.int32)[None]
    pos = jnp.asarray([90], jnp.int32)      # window 20: keys 71..90
    want = pa.paged_attention_gqa(q, kp, vp, bt, pos, window=20)
    old = jnp.arange(1, 1 + 71 // KPAGE)    # pages of positions 0..63
    got = pa.paged_attention_gqa(q, kp.at[old].set(jnp.nan),
                                 vp.at[old].set(jnp.nan), bt, pos,
                                 window=20)
    np.testing.assert_array_equal(got, want)
    inside = pa.paged_attention_gqa(q, kp.at[10].set(jnp.nan), vp, bt, pos,
                                    window=20)
    assert np.isnan(np.asarray(inside)).any()


# -- the dispatch point ------------------------------------------------------


@pytest.mark.parametrize("dtype,kv,page,window,want", [
    (jnp.bfloat16, jnp.bfloat16, 16, 0, pa.KERNEL),
    (jnp.bfloat16, jnp.bfloat16, 16, 4096, pa.KERNEL_WINDOW),
    (jnp.float32, jnp.float32, 8, 0, pa.KERNEL),
    (jnp.bfloat16, jnp.bfloat16, 8, 0, pa.GATHER),     # half a bf16 tile
    (jnp.bfloat16, jnp.float32, 16, 0, pa.GATHER),     # mixed dtypes
    (jnp.bfloat16, jnp.int8, 16, 0, pa.GATHER),
    (jnp.bfloat16, jnp.bfloat16, 48, 0, pa.GATHER),    # 256 % 48
], ids=["bf16", "bf16_window", "f32_page8", "bf16_page8", "mixed", "int8",
        "page48"])
def test_dispatch_of_the_grouped_attend(monkeypatch, dtype, kv, page, window,
                                        want):
    """What ``paged_attend_path`` answers for a head dimension of 128 on
    a TPU; off it (all of tier-1) the gather path."""
    assert pa.paged_attend_path(1024, page, dtype, kv, head_dim=128,
                                window=window) == pa.GATHER
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    assert pa.paged_attend_path(1024, page, dtype, kv, head_dim=128,
                                window=window) == want
    # a head that does not fill a lane tile: never this kernel
    assert pa.paged_attend_path(512, page, dtype, kv, head_dim=64,
                                window=window) == pa.GATHER


