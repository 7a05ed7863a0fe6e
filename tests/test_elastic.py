"""Elastic ZeRO (ROADMAP: resume-at-any-node-count).

Property layer: K→K'→K redistribution is BIT-identical for params AND
optimizer state — including the zero pad tail of the flat ZeRO slices —
over uneven K' (shard sizes that do not divide n) and for both
checkpoint layouts (stacked and ZeRO-2 sharded). Redistributions are
registry programs: a second reshard at the same (K→K', shapes)
signature must compile NOTHING (warm registry — the zero-recompile
resume gate). The sharded layout's bytes are O(model/K) per node, the
typed ``NodeCountMismatchError`` fires both at the strategy step (a
K'-sized shard fed to a K mesh) and at reshard time (genuinely per-node
state with no generic redistribution).

Integration layer: a real ``fit`` checkpointed at K resumes at K' —
including onto a vnode-folded mesh (K'=3 on 2 devices) — continuing the
CSV/step trajectory; the controller loop (``elastic_fit``) paces
segments with the serving fleet's validated ``AutoscaleController``.
"""

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu import TrainState
from gym_tpu.elastic import (STACKED_LAYOUT, ZERO2_LAYOUT,
                             ElasticTrainController, cold_restart_events,
                             elastic_fit, elastic_meta, make_zero2_codec,
                             param_leaf_specs, reshard_events,
                             reshard_state, saved_state_template)
from gym_tpu.programs import compile_counter
from gym_tpu.programs.elastic_defs import elastic_shard_size
from gym_tpu.strategy import (NodeCountMismatchError, OptimSpec,
                              ZeroReduceStrategy)
from gym_tpu.strategy.base import StrategyLifecycleError

N = 11  # 5 + 3*2 params — odd, so every K in play pads the last shard


def _flat(params_row):
    """The concatenated raveled vector in tree-leaf order (the ZeRO
    shard order)."""
    return np.concatenate(
        [np.asarray(x).ravel() for x in jax.tree.leaves(params_row)])


def _mk_state(k, seed=0, step=6):
    """A synthetic K-node zero-strategy state: replicated params, flat
    [K, ceil(N/K)] moments with an all-zero pad tail, canonical per-node
    rng (``fold_in(key, i+1)`` — the trainer's derivation)."""
    rng = np.random.default_rng(seed)
    b = rng.normal(size=(5,)).astype(np.float32)
    w = rng.normal(size=(3, 2)).astype(np.float32)
    params = {"b": jnp.asarray(np.repeat(b[None], k, 0)),
              "w": jnp.asarray(np.repeat(w[None], k, 0))}
    s = elastic_shard_size(N, k)

    def shard_vec(v):
        pad = np.zeros(k * s, np.float32)
        pad[:N] = v
        return jnp.asarray(pad.reshape(k, s))

    mu = rng.normal(size=(N,)).astype(np.float32)
    nu = np.abs(rng.normal(size=(N,))).astype(np.float32)
    keys = jax.vmap(
        lambda i: jax.random.key_data(
            jax.random.fold_in(jax.random.PRNGKey(3), i + 1))
    )(jnp.arange(k))
    return TrainState(
        params=params,
        model_state={},
        strategy_state={"opt": {"count": jnp.full((k,), step, jnp.int32),
                                "mu": shard_vec(mu), "nu": shard_vec(nu)}},
        step=jnp.full((k,), step, jnp.int32),
        rng=keys,
    )


def _assert_states_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("k_mid", [3, 5])
def test_reshard_roundtrip_bit_identical_stacked(k_mid):
    """K→K'→K over the stacked layout: params re-replicated, flat
    moments re-partitioned — every leaf bit-identical on return,
    including the pad tail (zero by the AdamW invariant: pad moments
    start 0 and mu=nu=0 updates to 0)."""
    k = 4
    saved = _mk_state(k)
    meta_k = elastic_meta(k, STACKED_LAYOUT, N)
    mid = reshard_state(saved, meta_k, _mk_state(k_mid, seed=9))
    # the mid-membership slices carry the same vector, freshly padded
    assert mid.strategy_state["opt"]["mu"].shape == (
        k_mid, elastic_shard_size(N, k_mid))
    np.testing.assert_array_equal(
        np.asarray(mid.strategy_state["opt"]["mu"]).ravel()[:N],
        np.asarray(saved.strategy_state["opt"]["mu"]).ravel()[:N])
    back = reshard_state(mid, elastic_meta(k_mid, STACKED_LAYOUT, N),
                         _mk_state(k, seed=17))
    _assert_states_equal(back, saved)


@pytest.mark.parametrize("k_mid", [3, 5])
def test_reshard_roundtrip_bit_identical_zero2(k_mid):
    """The same round-trip through the ZeRO-2 checkpoint layout: shard
    with the codec at K, reshard the raw sharded tree onto K', then
    back — params AND moments bit-identical (f32 staging is lossless
    for f32 params)."""
    k = 4
    saved = _mk_state(k)
    to_canon, from_canon = make_zero2_codec(saved, k)
    raw = jax.device_get(to_canon(saved))
    # the codec round-trips exactly on its own
    _assert_states_equal(from_canon(raw), saved)
    # sharded params really are O(model/K) per node: [K, ceil(N/K)] f32
    assert raw["zero2"]["param_shards"].shape == (k, elastic_shard_size(N, k))
    mid = reshard_state(raw, elastic_meta(k, ZERO2_LAYOUT, N),
                        _mk_state(k_mid, seed=9))
    back = reshard_state(mid, elastic_meta(k_mid, STACKED_LAYOUT, N),
                         _mk_state(k, seed=17))
    _assert_states_equal(back, saved)


def test_reshard_registry_warm_zero_recompiles():
    """A second reshard at the same (K→K', shapes) signature acquires
    every program from the registry — zero new builds (the re-resume
    gate in ``scripts/ci_elastic.sh`` asserts the same end to end)."""
    saved = _mk_state(4)
    meta = elastic_meta(4, STACKED_LAYOUT, N)
    reshard_state(saved, meta, _mk_state(3, seed=9))
    warm = compile_counter()
    reshard_state(saved, meta, _mk_state(3, seed=23))
    assert compile_counter() == warm


def test_reshard_rejects_per_node_state():
    """State whose rows genuinely differ across nodes (e.g. a mid-cycle
    error-feedback residual) has no generic redistribution — typed
    error, not silent corruption."""
    assert issubclass(NodeCountMismatchError, StrategyLifecycleError)
    saved = _mk_state(4)
    per_node = saved.replace(model_state={
        "residual": jnp.arange(4 * 5, dtype=jnp.float32).reshape(4, 5)})
    target = _mk_state(3, seed=9).replace(
        model_state={"residual": jnp.zeros((3, 5), jnp.float32)})
    with pytest.raises(NodeCountMismatchError, match="rows differ"):
        reshard_state(per_node, elastic_meta(4, STACKED_LAYOUT, N), target)


@pytest.mark.parametrize("k_to", [6, 2, 4], ids=["join", "leave", "same"])
def test_reshard_hands_the_fold_one_counter(k_to):
    """The step programs read ONE step counter a fold
    (``AxisCtx.fold_counter``). A membership change keeps that true: the
    restored counter is the checkpoint's on every one of the K' rows, and
    a checkpoint whose rows disagree is refused instead of being handed
    to a fold (at the same K the rows are what the lockstep program
    wrote, and are passed through)."""
    saved = _mk_state(4, step=7)
    out = reshard_state(saved, elastic_meta(4, STACKED_LAYOUT, N),
                        _mk_state(k_to, seed=9, step=0))
    np.testing.assert_array_equal(np.asarray(out.step), np.full(k_to, 7))
    if k_to != 4:
        torn = saved.replace(step=jnp.asarray([7, 7, 6, 7], jnp.int32))
        with pytest.raises(NodeCountMismatchError, match="rows differ"):
            reshard_state(torn, elastic_meta(4, STACKED_LAYOUT, N),
                          _mk_state(k_to, seed=9, step=0))


def test_zero_step_rejects_mismatched_shard():
    """Satellite: feeding a K'-sized optimizer shard to a K-node step
    raises the typed error naming both sizes (instead of a shape error
    deep inside the all-gather)."""
    from gym_tpu.parallel import NodeRuntime

    k = 4
    strat = ZeroReduceStrategy(OptimSpec("adamw", lr=0.01))
    rt = NodeRuntime.create(k, None)
    strat.finalize(10)
    strat.bind_ctx(rt.ctx)
    w0 = {"w": np.zeros((k, 7, 3), np.float32),
          "b": np.zeros((k, 5), np.float32)}   # n=26: s(K=4)=7, s(K=3)=9
    params = rt.shard_batch(w0)
    state = rt.compile(lambda p: strat.init(p), donate_state=False)(params)
    stale = jax.tree.map(
        lambda x: (jnp.pad(x, ((0, 0), (0, 2)))
                   if getattr(x, "ndim", 0) == 2 and x.shape[-1] == 7
                   else x), state)
    step = rt.compile(
        lambda p, s, g, t: strat.step(g, p, s, t, rt.ctx),
        donate_state=False)
    tvec = rt.shard_batch(np.zeros(k, np.int32))
    with pytest.raises(NodeCountMismatchError, match="num_nodes=4"):
        step(params, stale, params, tvec)


def test_saved_state_template_shapes():
    """The restore template describes the checkpoint AS SAVED (K rows,
    saved-shard widths, numpy leaves) while keeping the live tree
    structure — the combination that avoids both Orbax's device-topology
    pin and the namedtuple→dict structure loss."""
    target = _mk_state(3, seed=9)
    tpl = saved_state_template(target, elastic_meta(4, STACKED_LAYOUT, N))
    assert isinstance(tpl, TrainState)
    assert tpl.params["b"].shape == (4, 5)
    assert tpl.strategy_state["opt"]["mu"].shape == (
        4, elastic_shard_size(N, 4))
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(tpl))
    z = saved_state_template(target, elastic_meta(4, ZERO2_LAYOUT, N))
    assert z["zero2"]["param_shards"].shape == (4, elastic_shard_size(N, 4))
    # saved=None (pre-elastic checkpoint): stacked at the live K
    legacy = saved_state_template(target, None)
    assert legacy.step.shape == (3,)


def test_zero2_ckpt_bytes_o_model_over_k():
    """The sharded checkpoint stores ceil(n/K) f32 per node for params
    (plus the already-sharded moments) — total O(model), i.e. per-node
    O(model/K) — where the stacked layout stores K full replicas."""
    k = 4
    saved = _mk_state(k)
    to_canon, _ = make_zero2_codec(saved, k)
    raw = jax.device_get(to_canon(saved))

    def nbytes(tree):
        return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))

    stacked_params = nbytes(saved.params)          # K * n * 4
    sharded_params = nbytes(raw["zero2"]["param_shards"])
    assert stacked_params == k * N * 4
    assert sharded_params == k * elastic_shard_size(N, k) * 4  # ~ n * 4
    assert sharded_params <= stacked_params / k + k * 4
    # moments were already 1/K shards; the codec passes them through
    assert (nbytes(raw["zero2"]["strategy_state"])
            == nbytes(saved.strategy_state))


def test_reshard_vs_cold_restart_events():
    """The analytic pricing the sweep uses: a reshard moves ~3 model
    vectors of bytes through all_gathers; a cold restart re-broadcasts
    the same volume AND recomputes lost steps (priced by the caller)."""
    ev = reshard_events(N, 4, 3)
    assert [e.op for e in ev] == ["all_gather", "all_gather"]
    assert sum(e.bytes for e in ev) == 3 * 4 * N
    assert all(e.group == 4 for e in ev)
    cold = cold_restart_events(N, 3)
    assert [e.op for e in cold] == ["broadcast"]
    assert cold[0].bytes == 3 * 4 * N and cold[0].group == 3


def test_controller_bounded_scale_up_and_down():
    """The serving fleet's controller drives training membership: two
    over-watermark ticks (up_patience) add a node, bounded by k_max;
    drained backlog eventually retires down to k_min."""
    c = ElasticTrainController(k_min=1, k_max=3)
    assert c.tick(num_nodes=2, backlog_tokens=1e6, tokens_per_s=10.0) == 2
    assert c.tick(num_nodes=2, backlog_tokens=1e6, tokens_per_s=10.0) == 3
    assert "scale up" in c.last_reason or "drain" in c.last_reason
    # at the ceiling the controller can only hold
    for _ in range(8):
        k = c.tick(num_nodes=3, backlog_tokens=1e6, tokens_per_s=10.0)
        assert k == 3


def test_elastic_fit_paces_segments_through_resume():
    """``elastic_fit`` runs max_steps in resume="auto" segments and
    records the controller's decision trail; every fit call carries the
    membership the controller chose."""
    calls = []

    class Stub:
        def fit(self, **kw):
            calls.append(kw)
            return SimpleNamespace(steps=kw["max_steps"], preempted=False)

    hist, res = elastic_fit(
        Stub(), controller=ElasticTrainController(k_min=1, k_max=4),
        num_nodes=2, max_steps=9, segment_steps=3, tokens_per_step=16,
        save_dir="/tmp/_elastic_fit_stub")
    assert res.steps == 9 and len(calls) == len(hist) == 3
    assert [c["max_steps"] for c in calls] == [3, 6, 9]
    assert all(c["resume"] == "auto" for c in calls)
    assert [h["nodes"] for h in hist] == [c["num_nodes"] for c in calls]
    with pytest.raises(ValueError, match="save_dir"):
        elastic_fit(Stub(), controller=ElasticTrainController(),
                    num_nodes=1, max_steps=1, segment_steps=1,
                    tokens_per_step=1)


def _fit_workload():
    import flax.linen as nn
    import optax

    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, batch, train=True):
            x, y = batch
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16)(x))
            return optax.softmax_cross_entropy_with_integer_labels(
                nn.Dense(10)(x).astype(jnp.float32), y).mean()

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=128).astype(np.int32)
    x = rng.normal(0, 0.3, size=(128, 8, 8)).astype(np.float32)
    for i, y in enumerate(labels):
        x[i, y % 8, :] += 1.5
    return Trainer(Tiny(), ArrayDataset(x, labels))


def test_fit_resume_at_new_node_count_vnode(tmp_path):
    """End to end: a zero2-checkpointed K=2 run resumes at K=3 on TWO
    devices — the new membership only exists as a vnode folding — and
    the step/CSV trajectory continues across the change (cum_comm_bytes
    monotone, no step replayed)."""
    t = _fit_workload()
    common = dict(batch_size=16, minibatch_size=8, val_interval=0,
                  show_progress=False, seed=3, checkpoint_interval=2,
                  save_dir=str(tmp_path / "ckpt"), run_name="el",
                  log_dir=str(tmp_path / "logs"), async_checkpoint=False,
                  devices=[0, 1])
    mk = lambda: ZeroReduceStrategy(OptimSpec("adamw", lr=0.05))
    r1 = t.fit(strategy=mk(), num_nodes=2, max_steps=4, **common)
    assert r1.steps == 4
    r2 = t.fit(strategy=mk(), num_nodes=3, max_steps=6, resume="auto",
               **common)
    assert r2.steps == 6
    assert r2.history["train_loss"][0][0] == 4  # resumed, not replayed
    csv = (tmp_path / "logs" / "el" / "train.csv").read_text().splitlines()
    steps = [int(r.split(",")[0]) for r in csv[1:]]
    cum = [int(r.split(",")[-1]) for r in csv[1:]]
    assert steps == list(range(6))
    assert cum == sorted(cum) and len(set(cum)) == len(cum)
