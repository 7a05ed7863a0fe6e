"""Strategy-semantics tests against the reference algorithms' math
(citations in each strategy module). These run the pure (init, step) API
directly on tiny pytrees over the CPU node mesh — the unit-test layer the
reference never had (SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.parallel import NodeRuntime
from gym_tpu.strategy import (DiLoCoStrategy, FedAvgStrategy, OptimSpec,
                              PartitionedIndexSelector, RandomIndexSelector,
                              ShuffledSequentialIndexSelector,
                              SimpleReduceStrategy, SPARTADiLoCoStrategy,
                              SPARTAStrategy, ZeroReduceStrategy)


def _noloco_int8(**kw):
    from gym_tpu.strategy import NoLoCoStrategy
    return NoLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.1),
                          codec="int8", **kw)


def _demo_outer(**kw):
    from gym_tpu.strategy import DecoupledMomentumStrategy
    return DecoupledMomentumStrategy(optim_spec=OptimSpec("sgd", lr=0.1),
                                     frac=0.2, **kw)


def make_harness(strategy, num_nodes, params_np, max_steps=100,
                 devices=None):
    """Compile per-step strategy application over the node mesh.

    params_np: dict of [K, ...] arrays (per-node initial params).
    Returns (step_fn, params, state) with host-side step loop.
    """
    rt = NodeRuntime.create(num_nodes, devices)
    strategy.finalize(max_steps)
    strategy.bind_ctx(rt.ctx)

    init = rt.compile(lambda p: strategy.init(p), donate_state=False)
    params = rt.shard_batch(params_np)
    state = init(params)

    raw = rt.compile(
        lambda p, s, g, t: strategy.step(g, p, s, t, rt.ctx),
        donate_state=False,
    )

    def step_fn(params, state, grads_np, t):
        grads = rt.shard_batch(grads_np)
        tvec = rt.shard_batch(np.full(num_nodes, t, np.int32))
        p, s, m = raw(params, state, grads, tvec)
        return p, s, jax.device_get(m)

    return rt, step_fn, params, state


@pytest.mark.parametrize("strategy_fn", [
    lambda: SimpleReduceStrategy(OptimSpec("sgd", lr=0.1)),
    lambda: ZeroReduceStrategy(OptimSpec("sgd", lr=0.1)),
    lambda: DiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.1), H=2),
    lambda: FedAvgStrategy(inner_optim=OptimSpec("sgd", lr=0.1), H=2),
    lambda: SPARTAStrategy(inner_optim=OptimSpec("sgd", lr=0.1),
                           p_sparta=0.5),
    lambda: SPARTADiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.1),
                                 p_sparta=0.5, H=2),
    lambda: DiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.1), H=2,
                           codec="int4"),
    lambda: _noloco_int8(H=2),
    lambda: _demo_outer(H=2),
], ids=["simple_reduce", "zero_reduce", "diloco", "fedavg", "sparta",
        "sparta_diloco", "diloco_int4", "noloco_int8", "demo_outer"])
def test_comm_bytes_metric_normalized(strategy_fn):
    """Every strategy's comm_bytes metric flows through one helper
    (strategy.base.comm_metric): float32, scalar per node — the
    strategies used to return a mix of Python floats and jnp arrays,
    which the logging/trace layers then had to special-case (ISSUE 3
    satellite). DeMo is covered separately in test_demo.py (its step
    needs the DCT harness)."""
    K = 4
    params0 = {"w": np.ones((K, 6), np.float32),
               "b": np.ones((K, 3), np.float32)}
    grads = {"w": np.ones((K, 6), np.float32),
             "b": np.ones((K, 3), np.float32)}
    strat = strategy_fn()
    rt, step_fn, params, state = make_harness(strat, K, params0)
    for t in (0, 2):
        params, state, m = step_fn(params, state, grads, t)
        comm = m["comm_bytes"]
        # [K] after the harness gathers the per-node scalar metric
        assert comm.shape == (K,), comm.shape
        assert comm.dtype == np.float32, comm.dtype
        assert np.all(np.isfinite(comm))


def test_simple_reduce_is_grad_average():
    """K-node SimpleReduce with per-node grads g_k must equal a single
    SGD step on mean(g_k) — DDP correctness (reference strategy.py:128-142)."""
    K = 4
    params0 = {"w": np.tile(np.ones((1, 3), np.float32), (K, 1))}
    grads = {"w": np.arange(K * 3, dtype=np.float32).reshape(K, 3)}
    strat = SimpleReduceStrategy(OptimSpec("sgd", lr=0.1))
    rt, step_fn, params, state = make_harness(strat, K, params0)
    params, state, m = step_fn(params, state, grads, 0)
    out = jax.device_get(params)["w"]
    expect = 1.0 - 0.1 * grads["w"].mean(axis=0)
    for k in range(K):
        np.testing.assert_allclose(out[k], expect, rtol=1e-6)
    assert np.all(m["comm_bytes"] > 0)


def test_fedavg_h_gating_and_sync():
    """Nodes drift for H−1 steps then snap to the average
    (reference federated_averaging.py:108-111 gate semantics)."""
    K, H = 4, 3
    params0 = {"w": np.zeros((K, 2), np.float32)}
    strat = FedAvgStrategy(inner_optim=OptimSpec("sgd", lr=1.0), H=H)
    rt, step_fn, params, state = make_harness(strat, K, params0)
    # node k's constant grad is -k, so under lr=1 SGD node k drifts by +k
    # per step until a sync snaps everyone to the average
    grads = {"w": np.repeat(-np.arange(K, dtype=np.float32)[:, None], 2, axis=1)}
    comm_log = []
    for t in range(2 * H + 1):
        params, state, m = step_fn(params, state, grads, t)
        comm_log.append(float(m["comm_bytes"][0]))
    out = jax.device_get(params)["w"]
    # comm only on steps where t % H == 0 and t > 0  (t = pre-increment step)
    for t, c in enumerate(comm_log):
        if t % H == 0 and t > 0:
            assert c > 0, (t, comm_log)
        else:
            assert c == 0, (t, comm_log)
    # the last executed step (t=2H) fired a sync: all nodes identical
    for k in range(1, K):
        np.testing.assert_allclose(out[k], out[0], rtol=1e-5)


def test_fedavg_islands_partial_averaging():
    """island_size=2 over 4 nodes: each island averages internally; the two
    islands generally differ (reference federated_averaging.py:26-69)."""
    K = 4
    params0 = {"w": np.repeat(np.arange(K, dtype=np.float32)[:, None], 4, 1)}
    strat = FedAvgStrategy(inner_optim=OptimSpec("sgd", lr=0.0), H=1,
                           island_size=2)
    rt, step_fn, params, state = make_harness(strat, K, params0)
    zero_g = {"w": np.zeros((K, 4), np.float32)}
    params, state, m = step_fn(params, state, zero_g, 1)  # t=1 → comm fires
    out = jax.device_get(params)["w"][:, 0]  # per-node scalar value
    # Each node's value must be the mean of exactly 2 of {0,1,2,3}, the
    # global mean of values must be preserved, and each value appears twice.
    np.testing.assert_allclose(np.sort(out)[::2], np.sort(out)[1::2])
    np.testing.assert_allclose(out.sum(), np.arange(K).sum(), rtol=1e-6)
    # islands have size 2, so nodes sharing a value come in groups of 2
    # (or 4 if the two random islands happen to share the same mean)
    groups = {tuple(np.argwhere(np.isclose(out, v)).ravel()) for v in out}
    assert all(len(g) % 2 == 0 for g in groups)
    # each value is the mean of two distinct originals → 2*v is an integer
    np.testing.assert_allclose(2 * out, np.round(2 * out), atol=1e-5)


def test_diloco_outer_step_matches_manual_nesterov():
    """DiLoCo outer update: pseudo-grad = master − avg; torch-style Nesterov
    SGD (buf = μ·buf + g; update = g + μ·buf) with lr=0.7, μ=0.9
    (reference diloco.py:26-28, 43-49, 62-71), replicated on all nodes."""
    K, H = 2, 2
    w0 = np.full((K, 2), 10.0, np.float32)
    strat = DiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=1.0), H=H)
    rt, step_fn, params, state = make_harness(strat, K, {"w": w0})
    # node k gets grad +1 or -3 → after 2 inner sgd steps: w = 10 - 2*g_k
    g = np.stack([np.full(2, 1.0), np.full(2, -3.0)]).astype(np.float32)
    comm = []
    for t in range(H + 1):
        params, state, m = step_fn(params, state, {"w": g}, t)
        comm.append(float(m["comm_bytes"][0]))
    out = jax.device_get(params)["w"]
    # timeline: t=0 inner (no outer: step>0 false), t=1 inner, outer at
    # t=2 fires AFTER the t=2 inner step. inner steps applied: 3.
    # At outer time: w_k = 10 - 3*g_k → w = [7, 19]; avg = 13.
    # pseudo = master - avg = 10 - 13 = -3
    # buf = 0.9*0 + (-3) = -3 ; nesterov update = -3 + 0.9*(-3) = -5.7
    # master' = 10 - 0.7*(-5.7) = 13.99
    assert comm[0] == 0 and comm[1] == 0 and comm[2] > 0
    np.testing.assert_allclose(out, 13.99, rtol=1e-5)
    # all nodes bit-identical after outer sync
    np.testing.assert_array_equal(out[0], out[1])


def test_sparta_masked_exchange():
    """Masked entries take the node-mean; unmasked entries stay local.
    Mask agreement is by shared PRNG (replaces rank-0 broadcast,
    reference sparta.py:32-42)."""
    K = 4
    n = 1000
    w0 = np.repeat(np.arange(K, dtype=np.float32)[:, None], n, 1)
    strat = SPARTAStrategy(inner_optim=OptimSpec("sgd", lr=0.0),
                           p_sparta=0.3)
    rt, step_fn, params, state = make_harness(strat, K, {"w": w0})
    zero_g = {"w": np.zeros((K, n), np.float32)}
    params, state, m = step_fn(params, state, zero_g, 0)
    out = jax.device_get(params)["w"]
    mean = np.arange(K).mean()
    exchanged = np.isclose(out[0], mean)
    frac = exchanged.mean()
    assert 0.2 < frac < 0.4, frac  # ≈ p_sparta = 0.3
    # same entries exchanged on every node; others untouched
    for k in range(K):
        np.testing.assert_allclose(out[k][exchanged], mean, rtol=1e-6)
        np.testing.assert_allclose(out[k][~exchanged], k)
    assert 0 < float(m["comm_bytes"][0]) < 2 * 4 * n


@pytest.mark.parametrize("selector_cls", [ShuffledSequentialIndexSelector,
                                          PartitionedIndexSelector])
def test_cyclic_selectors_cover_everything_once(selector_cls):
    """Both cyclic selectors partition indices: over one full cycle every
    index is selected exactly once (reference sparta.py:88-193)."""
    sel = selector_cls(p=0.25)
    x = jnp.zeros((7, 13))  # 91 elements, doesn't divide 4
    num_partitions = 4
    total = np.zeros((7, 13), np.int32)
    for it in range(num_partitions):
        m = np.asarray(sel.mask(x, leaf_idx=0, iteration=jnp.asarray(it)))
        total += m.astype(np.int32)
    np.testing.assert_array_equal(total, 1)


def test_random_selector_rate():
    sel = RandomIndexSelector(p=0.1)
    x = jnp.zeros((100, 100))
    m = np.asarray(sel.mask(x, 0, jnp.asarray(3)))
    assert 0.07 < m.mean() < 0.13
    m2 = np.asarray(sel.mask(x, 0, jnp.asarray(4)))
    assert not np.array_equal(m, m2)  # re-randomized per iteration


def test_sparta_diloco_combo_runs():
    """The composition the reference shipped broken (SURVEY §2.1 🟡):
    sparse exchange every step + outer step every H."""
    K, H = 2, 2
    # replicas start identical (the framework invariant the reference
    # establishes by broadcast, train_node.py:101-104) and drift via
    # node-dependent gradients
    w0 = np.full((K, 8), 5.0, np.float32)
    strat = SPARTADiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.1),
                                 p_sparta=0.5, H=H)
    rt, step_fn, params, state = make_harness(strat, K, {"w": w0})
    g = np.repeat(np.arange(1, K + 1, dtype=np.float32)[:, None], 8, 1)
    for t in range(H + 1):
        params, state, m = step_fn(params, state, {"w": g}, t)
    out = jax.device_get(params)["w"]
    assert np.all(np.isfinite(out))
    # after the outer step at t=H all nodes are synced to the master
    np.testing.assert_array_equal(out[0], out[1])


def test_zero_reduce_matches_simple_reduce():
    """ZeRO-1 sharding is a memory layout, not an algorithm change: K nodes
    each updating 1/K of the flat parameter vector must produce the same
    params as every node updating all of it. Odd param count exercises the
    zero-padded last shard."""
    K = 4
    rng = np.random.default_rng(0)
    w0 = {"w": np.repeat(rng.normal(size=(1, 7, 3)).astype(np.float32),
                         K, axis=0),
          "b": np.repeat(rng.normal(size=(1, 5)).astype(np.float32),
                         K, axis=0)}

    def run(strat_cls):
        strat = strat_cls(
            optim_spec=OptimSpec("adamw", lr=1e-2, weight_decay=0.1),
            max_norm=1.0,
        )
        rt, step_fn, params, state = make_harness(strat, K, w0)
        for t in range(5):
            g = {"w": rng_g.normal(size=(K, 7, 3)).astype(np.float32),
                 "b": rng_g.normal(size=(K, 5)).astype(np.float32)}
            params, state, m = step_fn(params, state, g, t)
        return jax.device_get(params), jax.device_get(state)

    rng_g = np.random.default_rng(1)
    p_simple, _ = run(SimpleReduceStrategy)
    rng_g = np.random.default_rng(1)
    p_zero, s_zero = run(ZeroReduceStrategy)
    for key in ("w", "b"):
        np.testing.assert_allclose(p_zero[key], p_simple[key],
                                   atol=1e-6, rtol=1e-5)
    # optimizer state really is sharded: Adam moments are flat
    # [K, ceil(26/4)] (leading K = per-node axis of the harness)
    moments = [x for x in jax.tree.leaves(s_zero["opt"]) if x.ndim == 2]
    assert moments and all(x.shape == (K, -(-26 // K)) for x in moments), \
        [x.shape for x in jax.tree.leaves(s_zero["opt"])]


def test_zero_reduce_canonical_matches_vnode_schedule():
    """On a physical node mesh ZeRO-1 runs the canonical reduce-scatter +
    all-gather schedule; under vnode folding it falls back to pmean+slice.
    Same K, same grads → identical parameters (incl. the distributed
    global-norm clip), and comm_bytes reports each schedule's real cost
    ((K−1)/K·(|g|+|θ|) vs (K−1)/K·(2|g|+|θ|))."""
    K = 4
    rng = np.random.default_rng(3)
    w0 = {"w": np.repeat(rng.normal(size=(1, 7, 3)).astype(np.float32),
                         K, axis=0),
          "b": np.repeat(rng.normal(size=(1, 5)).astype(np.float32),
                         K, axis=0)}

    def run(n_devices):
        strat = ZeroReduceStrategy(
            optim_spec=OptimSpec("adamw", lr=1e-2), max_norm=1.0)
        rt, step_fn, params, state = make_harness(
            strat, K, w0, devices=jax.devices()[:n_devices])
        assert (rt.n_virt == 1) == (n_devices == K)
        rng_g = np.random.default_rng(4)
        comm = None
        for t in range(3):
            g = {"w": rng_g.normal(size=(K, 7, 3)).astype(np.float32),
                 "b": rng_g.normal(size=(K, 5)).astype(np.float32)}
            params, state, m = step_fn(params, state, g, t)
            comm = float(np.asarray(m["comm_bytes"]).ravel()[0])
        return jax.device_get(params), comm

    p_can, c_can = run(K)      # n_virt=1 → reduce-scatter
    p_vn, c_vn = run(K // 2)   # n_virt=2 → pmean+slice fallback
    for key in ("w", "b"):
        np.testing.assert_allclose(p_can[key], p_vn[key],
                                   atol=1e-6, rtol=1e-5)
    bytes_gp = (7 * 3 + 5) * 4  # |g| = |θ| = 26 f32 leaves per node
    np.testing.assert_allclose(c_can, 0.75 * 2 * bytes_gp)
    np.testing.assert_allclose(c_vn, 0.75 * 3 * bytes_gp)


def test_zero_reduce_requires_ctx():
    strat = ZeroReduceStrategy(optim_spec=OptimSpec("sgd", lr=0.1))
    strat.finalize(10)
    from gym_tpu.strategy.base import StrategyLifecycleError
    with pytest.raises(StrategyLifecycleError, match="bind_ctx"):
        strat.init({"w": jnp.zeros((4,))})


def test_diloco_shard_outer_matches_replicated():
    """shard_outer=True (1/K master + momentum slices, ZeRO on the outer
    optimizer) must reproduce the replicated outer step exactly: the outer
    input is node-identical, so slicing commutes with elementwise
    Nesterov. Odd param count exercises the padded last shard."""
    K, H = 4, 2
    rng = np.random.default_rng(9)
    w0 = {"w": np.repeat(rng.normal(size=(1, 7, 3)).astype(np.float32),
                         K, axis=0),
          "b": np.repeat(rng.normal(size=(1, 5)).astype(np.float32),
                         K, axis=0)}

    def run(shard_outer):
        strat = DiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.05), H=H,
                               shard_outer=shard_outer)
        rt, step_fn, params, state = make_harness(strat, K, w0)
        g = np.random.default_rng(10)
        for t in range(2 * H + 1):
            grads = {"w": g.normal(size=(K, 7, 3)).astype(np.float32),
                     "b": g.normal(size=(K, 5)).astype(np.float32)}
            params, state, m = step_fn(params, state, grads, t)
        return jax.device_get(params), float(m["comm_bytes"][0])

    p_rep, comm_rep = run(False)
    p_sh, comm_sh = run(True)
    for key in ("w", "b"):
        np.testing.assert_allclose(p_sh[key], p_rep[key],
                                   atol=1e-6, rtol=1e-5)
    # the sharded outer round pays the extra all_gather:
    # 3(K-1)/K·|θ| vs the replicated 2(K-1)/K·|θ| (26 f32 params = 104 B)
    assert comm_rep == 2.0 * 3 / 4 * 104
    assert comm_sh == 3.0 * 3 / 4 * 104


def test_noloco_gossip_preserves_node_mean_and_matches_host_twin():
    """One NoLoCo gossip round with a pass-through outer step (SGD
    lr=1.0, no momentum): params_i ← (p_i + p_σ(i))/2 with σ the host
    twin's permutation, so the NODE-MEAN of the params is preserved
    exactly (doubly-stochastic mixing) while nodes move toward pairwise
    consensus — and zero inner lr isolates the gossip itself."""
    from gym_tpu.strategy import NoLoCoStrategy

    K, H = 4, 2
    rng = np.random.default_rng(11)
    w0 = {"w": rng.normal(size=(K, 5)).astype(np.float32)}
    zeros = {"w": np.zeros((K, 5), np.float32)}
    strat = NoLoCoStrategy(
        optim_spec=OptimSpec("sgd", lr=0.0),
        outer_optim_spec=OptimSpec("sgd", lr=1.0, momentum=0.0,
                                   nesterov=False),
        H=H)
    rt, step_fn, params, state = make_harness(strat, K, w0)
    before = jax.device_get(params)["w"].copy()

    params, state, m = step_fn(params, state, zeros, 1)   # off-cadence
    np.testing.assert_allclose(jax.device_get(params)["w"], before,
                               atol=1e-7)
    assert np.all(m["comm_bytes"] == 0.0)

    params, state, m = step_fn(params, state, zeros, H)   # gossip round
    after = jax.device_get(params)["w"]
    sigma = strat.partner_permutation(H, K)
    assert sorted(sigma) == list(range(K))
    assert np.all(sigma != np.arange(K))                  # derangement
    for i in range(K):
        np.testing.assert_allclose(
            after[i], 0.5 * (before[i] + before[sigma[i]]),
            atol=1e-6, rtol=1e-5)
    # doubly-stochastic mixing: the fleet mean is invariant
    np.testing.assert_allclose(after.mean(axis=0), before.mean(axis=0),
                               atol=1e-6, rtol=1e-5)
    # p2p accounting: |θ| per node (5 f32 = 20 B), NOT 2(K−1)/K·|θ|
    assert np.all(m["comm_bytes"] == 20.0)


def test_noloco_consensus_emerges_over_rounds():
    """Repeated partner averaging with fresh random cycles contracts the
    node spread: after a few rounds every node is near the (preserved)
    fleet mean even though no global collective ever ran."""
    from gym_tpu.strategy import NoLoCoStrategy

    K = 8
    rng = np.random.default_rng(12)
    w0 = {"w": rng.normal(size=(K, 3)).astype(np.float32)}
    zeros = {"w": np.zeros((K, 3), np.float32)}
    strat = NoLoCoStrategy(
        optim_spec=OptimSpec("sgd", lr=0.0),
        outer_optim_spec=OptimSpec("sgd", lr=1.0, momentum=0.0,
                                   nesterov=False),
        H=1)
    rt, step_fn, params, state = make_harness(strat, K, w0)
    spread0 = jax.device_get(params)["w"].std(axis=0).max()
    for t in range(1, 13):
        params, state, _ = step_fn(params, state, zeros, t)
    after = jax.device_get(params)["w"]
    np.testing.assert_allclose(after.mean(axis=0),
                               w0["w"].mean(axis=0), atol=1e-5)
    assert after.std(axis=0).max() < 0.05 * spread0


def test_dynamiq_canonical_matches_vnode_schedule():
    """DynamiQ's two emulation schedules (psum_scatter + all_gather on a
    pure node mesh; pmean + slice under vnode folding) apply the SAME
    shared-PRNG codec noise to the same values — identical params, and
    the comm_bytes metric reports the CANONICAL compressed wire cost
    either way."""
    from gym_tpu.strategy import DynamiQStrategy

    K = 4
    rng = np.random.default_rng(13)
    w0 = {"w": np.repeat(rng.normal(size=(1, 7, 3)).astype(np.float32),
                         K, axis=0),
          "b": np.repeat(rng.normal(size=(1, 5)).astype(np.float32),
                         K, axis=0)}

    def run(n_devices):
        strat = DynamiQStrategy(optim_spec=OptimSpec("adamw", lr=1e-2),
                                codec="int8", tile=16)
        rt, step_fn, params, state = make_harness(
            strat, K, w0, devices=jax.devices()[:n_devices])
        assert (rt.n_virt == 1) == (n_devices == K)
        rng_g = np.random.default_rng(14)
        comm = None
        for t in range(3):
            g = {"w": rng_g.normal(size=(K, 7, 3)).astype(np.float32),
                 "b": rng_g.normal(size=(K, 5)).astype(np.float32)}
            params, state, m = step_fn(params, state, g, t)
            comm = float(np.asarray(m["comm_bytes"]).ravel()[0])
        return jax.device_get(params), strat, comm

    p_can, strat, c_can = run(K)      # n_virt=1 → reduce-scatter
    p_vn, _, c_vn = run(K // 2)       # n_virt=2 → pmean+slice fallback
    for key in ("w", "b"):
        np.testing.assert_allclose(p_can[key], p_vn[key],
                                   atol=1e-6, rtol=1e-5)
    # both account the canonical compressed schedule: (K−1)/K·(w1+w2)
    w1, w2 = strat._wires(26, K)
    assert c_can == c_vn == pytest.approx(3 / 4 * (w1 + w2))


def test_dynamiq_quantized_step_approximates_dense_allreduce():
    """int8 stochastic rounding perturbs the gradient by at most one
    quantization bin per hop: a DynamiQ step must land within a few bins
    of the exact SimpleReduce step on the same grads (and K=1 must be
    EXACTLY the dense update — nothing on the wire, nothing to
    compress)."""
    from gym_tpu.strategy import DynamiQStrategy

    K = 4
    w0 = {"w": np.zeros((K, 40), np.float32)}
    rng = np.random.default_rng(15)
    g = {"w": np.repeat(rng.normal(size=(1, 40)).astype(np.float32),
                        K, axis=0)}

    def run(strat_cls, **kw):
        strat = strat_cls(optim_spec=OptimSpec("sgd", lr=1.0), **kw)
        rt, step_fn, params, state = make_harness(strat, K, w0)
        params, state, m = step_fn(params, state, g, 0)
        return jax.device_get(params)["w"]

    p_dense = run(SimpleReduceStrategy)
    p_q = run(DynamiQStrategy, codec="int8", tile=64)
    bin_size = np.abs(g["w"][0]).max() / 127
    assert np.abs(p_q - p_dense).max() <= 2.5 * bin_size
    # node-identical output: every node decompresses the same payloads
    for k in range(1, K):
        np.testing.assert_array_equal(p_q[k], p_q[0])

    # K=1: bit-exact dense update
    w1 = {"w": np.zeros((1, 40), np.float32)}
    g1 = {"w": g["w"][:1]}
    strat = DynamiQStrategy(optim_spec=OptimSpec("sgd", lr=1.0),
                            codec="int8")
    rt, step_fn, params, state = make_harness(strat, 1, w1)
    params, state, m = step_fn(params, state, g1, 0)
    np.testing.assert_array_equal(jax.device_get(params)["w"],
                                  -g1["w"])
    assert np.all(m["comm_bytes"] == 0.0)


def test_dynamiq_error_feedback_conserves_dropped_mass_exactly():
    """Top-k with double error feedback: nothing is ever lost — summing
    the delivered updates of a constant gradient g over T steps gives
    EXACTLY T·g minus what the residuals still hold (hop 1: mean over
    nodes; hop 2: each node's own-chunk residual), the EF-SGD
    conservation law. SGD lr=1 makes the delivered sum directly
    observable as −params."""
    from gym_tpu.strategy import DynamiQStrategy

    K, n = 4, 40
    shard = n // K
    w0 = {"w": np.zeros((K, n), np.float32)}
    rng = np.random.default_rng(16)
    g = {"w": np.repeat(rng.normal(size=(1, n)).astype(np.float32),
                        K, axis=0)}
    strat = DynamiQStrategy(optim_spec=OptimSpec("sgd", lr=1.0),
                            codec="topk", frac=0.1)
    rt, step_fn, params, state = make_harness(strat, K, w0)
    T = 12
    for t in range(T):
        params, state, m = step_fn(params, state, g, t)
    final = jax.device_get(params)["w"]
    st = jax.device_get(state)
    # the residuals really are training state, carried across steps
    assert st["residual"].shape == (K, n) and np.any(st["residual"] != 0)
    assert st["residual2"].shape == (K, shard)
    # conservation: delivered = T·g − mean_i r_i − r2[chunk owner]
    # (node j owns chunk j, so row j of residual2 assembles in order)
    undelivered = (st["residual"].mean(axis=0)
                   + st["residual2"].reshape(-1))
    np.testing.assert_allclose(-final[0], T * g["w"][0] - undelivered,
                               rtol=1e-4, atol=1e-4)
    # and the delivered sum is genuinely converging on T·g: the lag is
    # bounded by what the residuals hold, not growing with T
    assert np.abs(undelivered).max() < T * np.abs(g["w"][0]).max()
    # all nodes decompress the same gathered payloads → identical params
    for k in range(1, K):
        np.testing.assert_array_equal(final[k], final[0])


# -- compressed outer loops (ISSUE 12: CompressedLink × strategy) ----------


def test_compressed_diloco_outer_round_within_bins_of_dense():
    """One int8 outer round must land within a few quantization bins of
    the dense DiLoCo round on the same grads (the delta is what's
    compressed, so the bin is amax(delta)/127 per tile), and the
    replicas stay bit-identical (the pmean reconstruction is a
    collective)."""
    K, H = 4, 2
    w0 = {"w": np.full((K, 64), 10.0, np.float32)}
    g = np.repeat(np.linspace(-3, 1, K, dtype=np.float32)[:, None], 64, 1)

    def run(**kw):
        strat = DiLoCoStrategy(optim_spec=OptimSpec("sgd", lr=1.0), H=H,
                               **kw)
        rt, step_fn, params, state = make_harness(strat, K, dict(w0))
        for t in range(H + 1):
            params, state, m = step_fn(params, state, {"w": g}, t)
        return jax.device_get(params)["w"], jax.device_get(state), m

    p_dense, _, _ = run()
    p_q, st_q, m = run(codec="int8", tile=64)
    # per-node delta after 3 inner steps is 3·g_k; bins per node ≤
    # amax(3·g)/127; the averaged reconstruction error is within a few
    # bins through the outer Nesterov step (factor 1.9 = 1+momentum)
    bin_size = 3 * np.abs(g).max() / 127
    assert np.abs(p_q - p_dense).max() <= 3 * 1.9 * bin_size
    for k in range(1, K):
        np.testing.assert_array_equal(p_q[k], p_q[0])
    # the residual is genuine training state on every node
    res = st_q["modules"][0]["ef_residual"]
    assert res.shape == (K, 64) and np.any(res != 0)
    # metric = the declared compressed wire cost
    from gym_tpu.strategy import CompressedLink
    wire = CompressedLink("int8", tile=64).wire_bytes(64)
    assert np.all(m["comm_bytes"] == pytest.approx(2 * 3 / 4 * wire))


def test_compressed_diloco_error_feedback_conserves_dropped_mass():
    """The EF conservation law at the strategy level, deterministic:
    with a top-k link and a pass-through outer step (SGD lr=1, no
    momentum, so ``master <- master + mean(delta_hat)`` is directly
    observable), NOTHING is ever lost: after T steps
    ``master == total_true_delta - mean_i(residual_i)`` exactly. The
    ablated link (error_feedback=False) permanently drops every
    never-selected coordinate; its master provably violates the
    conservation that the residual restores."""
    K, H, n = 2, 1, 50
    w0 = {"w": np.zeros((K, n), np.float32)}
    # one tiny coordinate (index 0), the rest large: frac=0.1 keeps 5
    g_row = np.r_[0.01, np.linspace(1, 2, n - 1)].astype(np.float32)
    g = {"w": np.repeat(g_row[None], K, 0)}
    T = 12   # steps; rounds fire at t=1..11 (H=1, step>0 gate)

    def run(error_feedback):
        strat = DiLoCoStrategy(
            optim_spec=OptimSpec("sgd", lr=1.0),
            outer_optim_spec=OptimSpec("sgd", lr=1.0, momentum=0.0,
                                       nesterov=False),
            H=H, codec="topk", frac=0.1, error_feedback=error_feedback)
        rt, step_fn, params, state = make_harness(strat, K, dict(w0))
        for t in range(T):
            params, state, _ = step_fn(params, state, g, t)
        return (jax.device_get(params)["w"],
                jax.device_get(state)["modules"][0])

    p_ef, ms = run(True)
    p_ablate, ms_ablate = run(False)
    # total true delta fed into the link per node: 2 inner steps before
    # the first round, then 1 per round -> -T*g in total
    total = -T * g_row
    # conservation: master == total - mean_i(residual_i), exactly
    undelivered = ms["ef_residual"].mean(axis=0)
    np.testing.assert_allclose(p_ef[0], total - undelivered,
                               rtol=1e-4, atol=1e-5)
    # the ablated link has no residual, and the dropped coordinate's
    # mass (~ -0.12 here) is gone for good: nothing accounts for it
    assert "ef_residual" not in ms_ablate
    assert p_ablate[0][0] == 0.0
    assert abs(p_ablate[0][0] - total[0]) > 0.1
    # the EF residual is exactly where coordinate 0's mass lives
    assert abs(undelivered[0] - total[0]) < 1e-5
    # both runs deliver the large coordinates
    assert p_ef[0][-1] < -10 and p_ablate[0][-1] < -10


def test_compressed_noloco_gossip_within_bins_and_deterministic():
    """Compressed gossip: avg_i = (p_i + p̂_σ(i))/2 with p̂ the partner's
    int8 reconstruction — within one bin of the dense gossip — and the
    whole exchange is bit-reproducible across runs (link keys are pure
    functions of (seed, step, node)), with the two partners of a pair
    drawing DIFFERENT rounding noise."""
    from gym_tpu.strategy import NoLoCoStrategy

    K, H, n = 4, 2, 64
    rng = np.random.default_rng(21)
    w0 = {"w": rng.normal(size=(K, n)).astype(np.float32)}
    zeros = {"w": np.zeros((K, n), np.float32)}

    def run(codec=None, **kw):
        strat = NoLoCoStrategy(
            optim_spec=OptimSpec("sgd", lr=0.0),
            outer_optim_spec=OptimSpec("sgd", lr=1.0, momentum=0.0,
                                       nesterov=False),
            H=H, codec=codec, **kw)
        rt, step_fn, params, state = make_harness(strat, K, dict(w0))
        params, state, m = step_fn(params, state, zeros, H)
        return jax.device_get(params)["w"], m, strat

    dense, _, _ = run()
    q1, m, strat = run(codec="int8", tile=n)
    q2, _, _ = run(codec="int8", tile=n)
    np.testing.assert_array_equal(q1, q2)          # bit-reproducible
    bin_size = np.abs(w0["w"]).max() / 127
    # only the partner half is quantized → error ≤ bin/2 per element
    assert np.abs(q1 - dense).max() <= bin_size
    sigma = strat.partner_permutation(H, K)
    # partner i's contribution was quantized with node σ(i)'s key; own
    # half is lossless: avg − p_i/2 differs from p_σ(i)/2 by the
    # partner's rounding noise, which differs BETWEEN partners
    noise = [q1[i] - 0.5 * (w0["w"][i] + w0["w"][sigma[i]])
             for i in range(K)]
    assert any(not np.array_equal(noise[0], nz) for nz in noise[1:])
    # p2p accounting: the codec's wire bytes, not |θ|
    from gym_tpu.strategy import CompressedLink
    wire = CompressedLink("int8", tile=n).wire_bytes(n)
    assert np.all(m["comm_bytes"] == wire)
    assert wire < 4.0 * n


def test_noloco_partner_permutation_odd_and_non_power_of_two():
    """ISSUE 12 satellite: the shared-PRNG partner draw at K = 3, 5, 6.
    A perfect pairing (involution) cannot exist for odd K; the module's
    documented design is a random K-CYCLE — always fixed-point-free, so
    every node still sends exactly once and receives exactly once — and
    the byte accounting (|θ| per node, pairs a permutation) must hold at
    every K, matching the jitted draw."""
    from gym_tpu.strategy import NoLoCoStrategy

    PARAMS = {"w": jax.ShapeDtypeStruct((40,), np.float32)}
    s = NoLoCoStrategy(H=2)
    for K in (3, 5, 6):
        for step in (2, 4, 8):
            sigma = s.partner_permutation(step, K)
            assert sorted(sigma) == list(range(K)), (K, step, sigma)
            assert np.all(sigma != np.arange(K)), (K, step, sigma)
            # the host twin IS the jitted draw
            jitted = np.asarray(jax.jit(
                lambda st, k=K: s._perm_jax(st, k)
            )(jnp.asarray(step, jnp.int32)))
            np.testing.assert_array_equal(sigma, jitted)
            evs = s.comm_events(step, PARAMS, K)
            assert len(evs) == 1 and evs[0].op == "p2p"
            # every node transmits exactly |θ| = 160 B
            assert evs[0].per_node_tx() == 160.0
            srcs = sorted(i for i, _ in evs[0].pairs)
            dsts = sorted(j for _, j in evs[0].pairs)
            assert srcs == dsts == list(range(K))
    # and the jitted step at an ODD node count reports the same metric
    K = 3
    strat = NoLoCoStrategy(optim_spec=OptimSpec("sgd", lr=0.0), H=2)
    w0 = {"w": np.random.default_rng(0).normal(
        size=(K, 40)).astype(np.float32)}
    rt, step_fn, params, state = make_harness(strat, K, w0)
    params, state, m = step_fn(params, state,
                               {"w": np.zeros((K, 40), np.float32)}, 2)
    assert np.all(m["comm_bytes"] == 160.0)


def test_demo_outer_dense_limit_is_parameter_averaging():
    """Decoupled momentum sanity oracle: replicas start identical (the
    framework invariant) and drift via per-node gradients; with the
    dense identity link, beta=0 and outer_lr=1, one sync is EXACTLY
    parameter averaging (master <- master + mean(drift_i) =
    mean(params_i)) -- and with a top-k link the masters stay
    node-identical while the momentum buffers keep the undelivered
    remainder."""
    from gym_tpu.strategy import DecoupledMomentumStrategy

    K, H, n = 4, 2, 30
    rng = np.random.default_rng(23)
    w0 = {"w": np.repeat(rng.normal(size=(1, n)).astype(np.float32),
                         K, 0)}
    # per-node drift: inner SGD lr=1 moves node k by -g_k per step
    g = {"w": rng.normal(size=(K, n)).astype(np.float32)}

    def run(**kw):
        strat = DecoupledMomentumStrategy(
            optim_spec=OptimSpec("sgd", lr=1.0), H=H, **kw)
        rt, step_fn, params, state = make_harness(strat, K, dict(w0))
        for t in range(H + 1):
            params, state, m = step_fn(params, state, g, t)
        return (jax.device_get(params)["w"], jax.device_get(state), m)

    p, st, m = run(codec=None, outer_lr=1.0, outer_momentum=0.0)
    # 3 inner steps before the sync at t=2: params_k = w0 - 3*g_k
    mean = (w0["w"] - 3 * g["w"]).mean(axis=0)
    for k in range(K):
        np.testing.assert_allclose(p[k], mean, atol=1e-5, rtol=1e-5)
    # dense link: everything delivered, momentum fully decoupled to 0
    np.testing.assert_allclose(st["modules"][0]["momentum"], 0.0,
                               atol=1e-6)

    p_t, st_t, m_t = run(codec="topk", frac=0.2, outer_lr=1.0,
                         outer_momentum=0.0)
    for k in range(1, K):
        np.testing.assert_array_equal(p_t[k], p_t[0])
    mom = st_t["modules"][0]["momentum"]
    assert np.any(mom != 0)          # the slow mass stayed local
    # comm: the compressed all-reduce convention over the wire bytes
    from gym_tpu.strategy import CompressedLink
    wire = CompressedLink("topk", frac=0.2).wire_bytes(n)
    assert np.all(m_t["comm_bytes"] == pytest.approx(3 / 4 * 2 * wire))
    assert np.all(m["comm_bytes"] == pytest.approx(3 / 4 * 2 * 4.0 * n))


def test_compressed_link_rejects_incoherent_compositions():
    """codec × shard_outer and codec × participation<1 are physically
    incoherent (sharded/frozen residuals) — typed rejections, not silent
    misbehavior."""
    with pytest.raises(ValueError, match="shard_outer"):
        DiLoCoStrategy(H=2, codec="int8", shard_outer=True)
    with pytest.raises(ValueError, match="participation"):
        DiLoCoStrategy(H=2, codec="int8", participation=0.5)


# -- one step counter a fold (ISSUE 32) -------------------------------------
# The step programs hand the strategies the fold's ONE counter
# (AxisCtx.fold_counter), so DiLoCo's H-gate is a real conditional under
# the vmap. These hold the arithmetic to what the spread program (one node
# a device, where the gate always was a conditional) computes.

_FOLD_K, _FOLD_H, _FOLD_STEPS = 4, 3, 7


def _fold_batches():
    rng = np.random.default_rng(32)
    x = rng.normal(size=(_FOLD_STEPS, _FOLD_K, 1, 8, 8, 8)).astype(np.float32)
    y = rng.integers(0, 10, size=(_FOLD_STEPS, _FOLD_K, 1, 8)).astype(np.int32)
    return x, y


def _fold_build(n_devices):
    from gym_tpu.models.base import LossModel
    from gym_tpu.train_node import make_init_fn
    from test_trainer_e2e import TinyLossModel
    x, y = _fold_batches()
    rt = NodeRuntime.create(_FOLD_K, jax.devices()[:n_devices])
    assert rt.n_virt == _FOLD_K // n_devices
    lm = LossModel(TinyLossModel())
    strat = DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-2), H=_FOLD_H)
    strat.finalize(_FOLD_STEPS)
    init = make_init_fn(lm, strat, (x[0, 0, 0], y[0, 0, 0]), seed=0,
                        ctx=rt.ctx)
    return rt, lm, strat, rt.init_state(init)


def _outer_state(state):
    """(master, outer momentum) of a DiLoCo TrainState, as host trees."""
    mod = jax.device_get(state.strategy_state)["modules"][0]
    return mod["master"], mod["outer_opt"]


def _fold_trajectory(n_devices):
    """7 single-step dispatches: per step (params, master, momentum,
    comm_bytes, step) on the host."""
    from gym_tpu.train_node import make_train_step
    x, y = _fold_batches()
    rt, lm, strat, state = _fold_build(n_devices)
    assert state.step.shape == (_FOLD_K,)
    step = rt.compile(make_train_step(lm, strat, rt.ctx))
    out = []
    for t in range(_FOLD_STEPS):
        state, m = step(state, rt.shard_batch((x[t], y[t])))
        master, mom = _outer_state(state)
        out.append({"params": jax.device_get(state.params),
                    "master": master, "momentum": mom,
                    "comm": np.asarray(m["comm_bytes"]),
                    "step": np.asarray(state.step)})
    return out


@pytest.fixture(scope="module")
def fold_run():
    return _fold_trajectory(1)


def test_fold_diloco_matches_spread_step_by_step(fold_run):
    """4 nodes folded on one device against the same 4 nodes on 4 devices,
    over two outer rounds: parameters, master, outer momentum and
    comm_bytes agree at every step (a mean over the vmap axis and one
    over devices may sum in another order: the tolerance of the other
    fold-against-spread tests here)."""
    spread = _fold_trajectory(_FOLD_K)
    for t, (f, s) in enumerate(zip(fold_run, spread)):
        for key in ("params", "master", "momentum"):
            for a, b in zip(jax.tree.leaves(f[key]),
                            jax.tree.leaves(s[key])):
                np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5,
                                           err_msg=f"step {t} {key}")
        np.testing.assert_array_equal(f["comm"], s["comm"])
        # the global state keeps its [K] counter on both placements
        np.testing.assert_array_equal(f["step"], np.full(_FOLD_K, t + 1))
        np.testing.assert_array_equal(s["step"], f["step"])


def test_fold_outer_step_runs_on_its_steps_only(fold_run):
    """The branch is taken at steps 3 and 6 (H=3) and nowhere else: there
    comm_bytes is positive and the master moves; on every other step
    master and outer momentum are bit-identical to the step before."""
    outer_steps = (3, 6)        # t % H == 0 and t > 0, of 7 steps
    for t in range(1, _FOLD_STEPS):
        before, now = fold_run[t - 1], fold_run[t]
        same = all(
            np.array_equal(a, b) for key in ("master", "momentum")
            for a, b in zip(jax.tree.leaves(before[key]),
                            jax.tree.leaves(now[key])))
        if t in outer_steps:
            assert np.all(now["comm"] > 0) and not same, t
        else:
            assert np.all(now["comm"] == 0) and same, t


def test_fold_multi_step_bit_identical_to_single_steps(fold_run):
    """multi_step over the same 7 batches: the counter rides the scan's
    carry, the gate fires on the same steps, and the final state equals
    the 7 single dispatches' bit for bit."""
    from gym_tpu.train_node import make_multi_train_step
    x, y = _fold_batches()
    rt, lm, strat, state = _fold_build(1)
    multi = rt.compile(make_multi_train_step(lm, strat, rt.ctx))
    state, m = multi(state, rt.shard_batch((np.moveaxis(x, 0, 1),
                                            np.moveaxis(y, 0, 1))))
    assert state.step.shape == (_FOLD_K,)
    np.testing.assert_array_equal(np.asarray(state.step),
                                  np.full(_FOLD_K, _FOLD_STEPS))
    last = fold_run[-1]
    master, mom = _outer_state(state)
    for want, got in ((last["params"], jax.device_get(state.params)),
                      (last["master"], master), (last["momentum"], mom)):
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        np.asarray(m["comm_bytes"]),
        np.stack([f["comm"] for f in fold_run], axis=1))


def test_fold_checkpoint_mid_round_keeps_the_outer_schedule(tmp_path):
    """A checkpoint written mid-round (step 4 of rounds ending at 3 and 6)
    restores into a fold whose next outer step lands on step 6, as in the
    run that never stopped; the checkpointed counter is [K]."""
    import orbax.checkpoint as ocp
    from gym_tpu import Trainer
    from test_trainer_e2e import TinyLossModel, blobs

    def fit(max_steps, save_dir):
        return Trainer(TinyLossModel(), blobs(256, seed=5), None).fit(
            strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3),
                                    H=_FOLD_H),
            num_nodes=_FOLD_K, devices=[0], max_steps=max_steps,
            batch_size=16, minibatch_size=8, val_interval=0,
            show_progress=False, seed=11, checkpoint_interval=4,
            save_dir=save_dir, run_name="fold_ckpt",
            log_dir=str(tmp_path / "logs"))

    straight = fit(7, str(tmp_path / "straight"))
    fit(4, str(tmp_path / "resume"))
    mgr = ocp.CheckpointManager(
        str(tmp_path / "resume" / "fold_ckpt"),
        options=ocp.CheckpointManagerOptions(create=False, read_only=True))
    saved = mgr.restore(4, args=ocp.args.Composite(
        state=ocp.args.StandardRestore()))["state"]
    mgr.close()
    np.testing.assert_array_equal(np.asarray(saved["step"]),
                                  np.full(_FOLD_K, 4))
    resumed = fit(7, str(tmp_path / "resume"))
    assert [s for s, _ in resumed.history["train_loss"]] == [4, 5, 6]
    taken = [s for s, c in resumed.history["comm_bytes"] if c > 0]
    assert taken == [6]
    assert [s for s, c in straight.history["comm_bytes"] if c > 0] == [3, 6]
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
