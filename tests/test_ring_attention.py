"""Ring attention / context parallelism (SURVEY §5.7 — capability the
reference lacks; first-class here).

Oracles: (1) the ring op is numerically identical to dense causal attention
on the full sequence; (2) a context-parallel GPT training run produces the
same losses and parameters as the same-seed dense run — sequence sharding is
an execution detail, not a semantics change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

shard_map = jax.shard_map

from gym_tpu import Trainer
from gym_tpu.data import ArrayDataset
from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.ops.attention import dense_causal_attention
from gym_tpu.ops.flash_attention import flash_causal_attention
from gym_tpu.parallel.ring_attention import ring_causal_attention
from gym_tpu.strategy import DiLoCoStrategy, OptimSpec, SimpleReduceStrategy


def _shard_ring(q, k, v, n, devices):
    mesh = Mesh(np.array(devices[:n]), ("seq",))
    spec = P(None, None, "seq", None)

    def f(q, k, v):
        return ring_causal_attention(q, k, v, axis_name="seq")

    return jax.jit(
        shard_map(f, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    )(q, k, v)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_matches_dense(devices8, n):
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 3, 64, 8)), jnp.float32)
        for _ in range(3)
    )
    with jax.default_matmul_precision("highest"):
        out = _shard_ring(q, k, v, n, devices8)
        ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)


def test_ring_bf16(devices8):
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.bfloat16)
        for _ in range(3)
    )
    out = _shard_ring(q, k, v, 4, devices8)
    ref = dense_causal_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=0.05
    )


def test_ring_dropout_semantics(devices8):
    """Dropout drops attention *probabilities* (dense semantics): with
    rate→0⁺ behavior intact, outputs stay finite, differ from the
    deterministic pass, and keep the softmax-denominator normalization
    (row means bounded by value range)."""
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
        for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    spec = P(None, None, "seq", None)

    def f(q, k, v):
        return ring_causal_attention(
            q, k, v, axis_name="seq", dropout_rate=0.5,
            dropout_rng=jax.random.PRNGKey(0), deterministic=False,
        )

    out = jax.jit(
        shard_map(f, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    )(q, k, v)
    ref = _shard_ring(q, k, v, 4, jax.devices())
    assert np.all(np.isfinite(np.asarray(out)))
    assert not np.allclose(np.asarray(out), np.asarray(ref))
    # denominator undropped → magnitudes stay in the value range ballpark
    assert np.abs(np.asarray(out)).max() < np.abs(np.asarray(v)).max() * 4


def test_flash_fallback_matches_dense():
    """Off-TPU the flash path must fall back to dense exactly."""
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 16, 8)), jnp.float32)
        for _ in range(3)
    )
    np.testing.assert_array_equal(
        np.asarray(flash_causal_attention(q, k, v)),
        np.asarray(dense_causal_attention(q, k, v)),
    )


def _char_stream_ds(n=512, t=32, vocab=17, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, vocab, size=(n, t), dtype=np.int64)
    tgt = np.roll(idx, -1, axis=1)
    return ArrayDataset(idx, tgt)


def _fit_gpt(cfg, cp, num_nodes=2, steps=6, seed=3):
    ds = _char_stream_ds(seed=seed)
    res = Trainer(GPT(cfg), ds, None).fit(
        strategy=SimpleReduceStrategy(OptimSpec("adamw", lr=1e-3)),
        num_nodes=num_nodes, max_steps=steps, batch_size=8,
        minibatch_size=8, cp=cp, val_interval=0, show_progress=False,
        seed=7, log_dir="/tmp/gym_tpu_test_logs",
    )
    return res


@pytest.mark.slow
def test_context_parallel_gpt_matches_dense(devices8):
    """Same seed, same data: cp=2 ring GPT ≡ cp=1 dense GPT."""
    base = dict(block_size=32, vocab_size=17, n_layer=2, n_head=2,
                n_embd=32, dropout=0.0, bias=True)
    with jax.default_matmul_precision("highest"):
        res_dense = _fit_gpt(GPTConfig(**base), cp=1)
        res_ring = _fit_gpt(
            GPTConfig(**base, attn_impl="ring", seq_axis="seq"), cp=2
        )
    l_dense = [l for _, l in res_dense.history["train_loss"]]
    l_ring = [l for _, l in res_ring.history["train_loss"]]
    np.testing.assert_allclose(l_ring, l_dense, rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree.leaves(res_dense.params),
                    jax.tree.leaves(res_ring.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3)


@pytest.mark.slow
def test_context_parallel_with_diloco(devices8):
    """CP composes with a communication strategy (seq axis orthogonal to the
    node axes): 4 nodes × cp=2 on 8 devices, DiLoCo outer loop fires."""
    cfg = GPTConfig(block_size=32, vocab_size=17, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True,
                    attn_impl="ring", seq_axis="seq")
    ds = _char_stream_ds()
    res = Trainer(GPT(cfg), ds, _char_stream_ds(seed=9)).fit(
        strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3), H=2),
        num_nodes=4, max_steps=5, batch_size=8, minibatch_size=8, cp=2,
        val_size=8, val_interval=2, show_progress=False,
        log_dir="/tmp/gym_tpu_test_logs",
    )
    losses = [l for _, l in res.history["train_loss"]]
    assert np.all(np.isfinite(losses))
    comm = [c for _, c in res.history["comm_bytes"]]
    assert any(c > 0 for c in comm)  # outer round communicated
    for leaf in jax.tree.leaves(res.params):
        assert np.all(np.isfinite(leaf))


@pytest.mark.slow
@pytest.mark.parametrize("n", [2, 4])
def test_ring_kernel_blocks_match_dense(devices8, n):
    """The Pallas-fused block path (diag causal kernel + gated full-block
    kernels merged in lse space) is the same math as dense causal
    attention — values AND gradients (the lse cotangent must flow through
    the merge into ds). Runs the TPU kernels in the Pallas interpreter;
    Tl = 512/256 ≥ 128 makes the kernel path eligible."""
    from gym_tpu.ops import fused_attention
    from gym_tpu.parallel.ring_attention import _kernel_blocks_ok

    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 1024, 16)), jnp.float32)
        for _ in range(3)
    )
    fused_attention.INTERPRET = True
    try:
        assert _kernel_blocks_ok(q[:, :, : 1024 // n])
        mesh = Mesh(np.array(devices8[:n]), ("seq",))
        spec = P(None, None, "seq", None)

        def loss_ring(q, k, v):
            def f(q, k, v):
                return ring_causal_attention(q, k, v, axis_name="seq")
            # check_vma=False: pallas_call out_shapes carry no vma info
            # (the NodeRuntime programs run with the same setting)
            out = shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False)(q, k, v)
            return (out.astype(jnp.float32) ** 2).mean(), out

        def loss_dense(q, k, v):
            out = dense_causal_attention(q, k, v)
            return (out.astype(jnp.float32) ** 2).mean(), out

        with jax.default_matmul_precision("highest"):
            (_, out), g_ring = jax.value_and_grad(
                loss_ring, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            (_, ref), g_dense = jax.value_and_grad(
                loss_dense, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    finally:
        fused_attention.INTERPRET = False
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4)


def _zigzag_perm(t, n):
    """Global row order that makes contiguous per-device shards hold the
    zig-zag layout: device i gets half-chunks i and 2n-1-i."""
    h = t // (2 * n)
    idx = []
    for i in range(n):
        idx.extend(range(i * h, (i + 1) * h))
        idx.extend(range((2 * n - 1 - i) * h, (2 * n - i) * h))
    return np.array(idx)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_zigzag_matches_dense(devices8, n):
    """Zig-zag schedule ≡ dense causal attention (rows permuted into the
    zig-zag device layout and back)."""
    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 3, 64, 8)), jnp.float32)
        for _ in range(3)
    )
    perm = _zigzag_perm(64, n)
    mesh = Mesh(np.array(devices8[:n]), ("seq",))
    spec = P(None, None, "seq", None)

    def f(q, k, v):
        return ring_causal_attention(q, k, v, axis_name="seq",
                                     layout="zigzag")

    with jax.default_matmul_precision("highest"):
        out = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec
        ))(q[..., perm, :], k[..., perm, :], v[..., perm, :])
        ref = dense_causal_attention(q, k, v)
    inv = np.argsort(perm)
    np.testing.assert_allclose(np.asarray(out)[..., inv, :],
                               np.asarray(ref), atol=2e-6, rtol=1e-5)


def test_ring_zigzag_dropout_finite(devices8):
    """The dense-zigzag dropout path: finite, differs from deterministic,
    keeps denominator normalization."""
    rng = np.random.default_rng(6)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
        for _ in range(3)
    )
    perm = _zigzag_perm(32, 4)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    spec = P(None, None, "seq", None)

    def f(det):
        def g(q, k, v):
            return ring_causal_attention(
                q, k, v, axis_name="seq", layout="zigzag",
                dropout_rate=0.5, dropout_rng=jax.random.PRNGKey(0),
                deterministic=det)
        return jax.jit(shard_map(
            g, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec
        ))(q[..., perm, :], k[..., perm, :], v[..., perm, :])

    out, det = f(False), f(True)
    assert np.all(np.isfinite(np.asarray(out)))
    assert not np.allclose(np.asarray(out), np.asarray(det))
    assert np.abs(np.asarray(out)).max() < np.abs(np.asarray(v)).max() * 4


@pytest.mark.slow
@pytest.mark.parametrize("n", [2, 4])
def test_ring_zigzag_kernel_blocks_match_dense(devices8, n):
    """Pallas-fused zig-zag blocks: same values AND gradients as dense
    causal attention (lse cotangents flow through the gated merges)."""
    from gym_tpu.ops import fused_attention

    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 1024, 16)), jnp.float32)
        for _ in range(3)
    )
    perm = _zigzag_perm(1024, n)
    inv = np.argsort(perm)
    fused_attention.INTERPRET = True
    try:
        mesh = Mesh(np.array(devices8[:n]), ("seq",))
        spec = P(None, None, "seq", None)

        def loss_ring(q, k, v):
            def f(q, k, v):
                return ring_causal_attention(q, k, v, axis_name="seq",
                                             layout="zigzag")
            out = shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False)(
                q[..., perm, :], k[..., perm, :], v[..., perm, :])
            out = out[..., inv, :]
            return (out.astype(jnp.float32) ** 2).mean(), out

        def loss_dense(q, k, v):
            out = dense_causal_attention(q, k, v)
            return (out.astype(jnp.float32) ** 2).mean(), out

        with jax.default_matmul_precision("highest"):
            (_, out), g_ring = jax.value_and_grad(
                loss_ring, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            (_, ref), g_dense = jax.value_and_grad(
                loss_dense, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    finally:
        fused_attention.INTERPRET = False
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4)
