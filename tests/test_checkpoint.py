"""Checkpoint/resume (the reference's disabled subsystem, SURVEY §5.4).

Oracle: training N steps straight produces the same final parameters as
training k steps, "crashing", and resuming from the checkpoint for the
remaining N−k — including the data-iterator position and per-node RNG, so
the resumed run sees the exact same batch sequence.
"""

import pytest
import shutil

import jax
import numpy as np

from gym_tpu import Trainer
from gym_tpu.data import ArrayDataset
from gym_tpu.strategy import DiLoCoStrategy, OptimSpec

from test_trainer_e2e import TinyLossModel, blobs


def _fit(ds, max_steps, tmp, interval, strategy=None, run_name="ckpt_test",
         seed=11):
    if strategy is None:
        strategy = DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3),
                                  H=3)
    return Trainer(TinyLossModel(), ds, None).fit(
        strategy=strategy,
        num_nodes=4, max_steps=max_steps, batch_size=16, minibatch_size=8,
        val_interval=0, show_progress=False, seed=seed,
        checkpoint_interval=interval, save_dir=tmp, run_name=run_name,
        log_dir="/tmp/gym_tpu_test_logs",
    )


@pytest.mark.slow
def test_resume_matches_straight_run(tmp_path):
    ds = blobs(256, seed=5)
    straight_dir = str(tmp_path / "straight")
    resume_dir = str(tmp_path / "resume")

    res_straight = _fit(ds, 8, straight_dir, interval=100)  # never resumes

    _fit(ds, 4, resume_dir, interval=4)       # stops at step 4, ckpt saved
    res_resumed = _fit(ds, 8, resume_dir, interval=4)  # resumes 4 → 8

    for a, b in zip(jax.tree.leaves(res_straight.params),
                    jax.tree.leaves(res_resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
    # resumed run only logged steps 4..7
    steps = [s for s, _ in res_resumed.history["train_loss"]]
    assert min(steps) == 4 and max(steps) == 7

    shutil.rmtree(str(tmp_path), ignore_errors=True)


def test_keep_latest_pruning(tmp_path):
    ds = blobs(128, seed=6)
    d = str(tmp_path / "prune")
    _fit(ds, 6, d, interval=2)
    from gym_tpu.utils.checkpoint import CheckpointManager

    mgr = CheckpointManager(d, "ckpt_test")
    assert mgr.latest_step() == 6
    # max_to_keep=2 (ISSUE 2): older steps pruned, but TWO survive so a
    # corrupt newest checkpoint still leaves a valid fallback
    assert len(mgr.manager.all_steps()) == 2
    mgr.close()
    shutil.rmtree(str(tmp_path), ignore_errors=True)


def test_torn_only_step_dir_quarantined_and_resaveable(tmp_path):
    """The sweep's kill -9 resume path (ISSUE 3): when the ONLY step dir
    on disk is torn wreckage (Orbax lists bare numeric dirs even without
    their metadata), restore must quarantine it and raise
    CheckpointNotFoundError — the fresh-start signal — and the same step
    number must then be saveable again (not "Destination already
    exists")."""
    import os

    from gym_tpu.utils.checkpoint import (CheckpointManager,
                                          CheckpointNotFoundError)

    d = str(tmp_path / "unc")
    os.makedirs(os.path.join(d, "run", "4"))
    with open(os.path.join(d, "run", "4", "garbage"), "w") as f:
        f.write("partial write")
    mgr = CheckpointManager(d, "run", async_save=False,
                            retry_policy=_no_retries())
    state = {"w": np.zeros((2, 2), np.float32)}
    with pytest.raises(CheckpointNotFoundError, match="no valid"):
        mgr.restore(state)
    assert os.path.exists(os.path.join(d, "run", "4.corrupt-0"))
    mgr.save(4, state, {"pos": 0})
    assert mgr.latest_step() == 4
    step, _, data_state, _ = mgr.restore(state)
    assert step == 4 and data_state == {"pos": 0}
    mgr.close()
    shutil.rmtree(str(tmp_path), ignore_errors=True)


def _no_retries():
    from gym_tpu.utils.resilience import RetryPolicy
    return RetryPolicy(attempts=1)


@pytest.mark.slow
def test_resume_matches_straight_run_demo(tmp_path):
    """Same oracle with DeMo: its strategy state is the pooled chunk-layout
    momentum dict ('{a}x{b}' → [G, a, b]), a different pytree shape than
    the optax states — resume must restore it exactly."""
    from gym_tpu.strategy.demo import DeMoStrategy

    def demo():
        return DeMoStrategy(optim_spec=OptimSpec("sgd", lr=3e-3),
                            compression_topk=4, compression_chunk=8)

    ds = blobs(256, seed=7)
    straight = _fit(ds, 8, str(tmp_path / "s"), interval=100,
                    strategy=demo(), run_name="ckpt_demo", seed=13)
    _fit(ds, 4, str(tmp_path / "r"), interval=4,
         strategy=demo(), run_name="ckpt_demo", seed=13)
    resumed = _fit(ds, 8, str(tmp_path / "r"), interval=4,
                   strategy=demo(), run_name="ckpt_demo", seed=13)
    # guard against a vacuous pass: the second run must actually have
    # resumed at step 4 (a fresh same-seed 0→8 run would also match)
    steps = [s for s, _ in resumed.history["train_loss"]]
    assert min(steps) == 4 and max(steps) == 7
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
    shutil.rmtree(str(tmp_path), ignore_errors=True)


@pytest.mark.slow
def test_resume_matches_straight_run_pipeline(tmp_path):
    """Checkpoint/resume under pipeline parallelism: the pp TrainState
    (stage-sharded {'outer','stages'} params + mirrored strategy state)
    round-trips through Orbax, and a resumed fit(pp=2) reproduces the
    straight run's final parameters exactly."""
    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.models.nanogpt import GPT, GPTConfig

    rng = np.random.default_rng(6)
    data = rng.integers(0, 32, 4096, dtype=np.int64)
    ds = ContiguousGPTTrainDataset(data, block_size=16)
    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=4, n_head=2,
                    n_embd=32, dropout=0.0)

    def fit_pp(max_steps, tmp, interval):
        return Trainer(GPT(cfg), ds, None).fit(
            strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3),
                                    H=3),
            num_nodes=2, max_steps=max_steps, batch_size=8,
            minibatch_size=2, pp=2, val_interval=0, show_progress=False,
            seed=13, checkpoint_interval=interval, save_dir=tmp,
            run_name="ckpt_pp", log_dir="/tmp/gym_tpu_test_logs",
        )

    straight = fit_pp(6, str(tmp_path / "straight"), interval=100)
    fit_pp(3, str(tmp_path / "resume"), interval=3)
    resumed = fit_pp(6, str(tmp_path / "resume"), interval=3)

    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
    steps = [s for s, _ in resumed.history["train_loss"]]
    assert min(steps) == 3 and max(steps) == 5
    shutil.rmtree(str(tmp_path), ignore_errors=True)


@pytest.mark.slow
def test_cross_topology_restore_pp2_tp2_to_pp1(tmp_path):
    """Cross-topology restore (VERDICT r3 #6): checkpoints are written in
    the CANONICAL plain-GPT layout, so a run saved under fit(pp=2, tp=2)
    resumes at pp=1 with a continuous trajectory. Oracle: a straight
    pp=1 run 0→6 equals [pp=2×tp=2 run 0→3 → checkpoint → pp=1 resume
    3→6] to float tolerance (pipelining/sharding are schedules, not
    algorithm changes — pinned by the pp parity tests)."""
    import pytest

    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.models.nanogpt import GPT, GPTConfig

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (node=2 x model=2 x pipe=2)")

    rng = np.random.default_rng(8)
    data = rng.integers(0, 32, 4096, dtype=np.int64)
    ds = ContiguousGPTTrainDataset(data, block_size=16)
    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=4, n_head=2,
                    n_embd=32, dropout=0.0)

    def fit_any(max_steps, tmp, interval, pp=1, tp=1):
        return Trainer(GPT(cfg), ds, None).fit(
            strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3),
                                    H=3),
            num_nodes=2, max_steps=max_steps, batch_size=8,
            minibatch_size=2, pp=pp, tp=tp, val_interval=0,
            show_progress=False, seed=17, checkpoint_interval=interval,
            save_dir=tmp, run_name="ckpt_xtopo",
            log_dir="/tmp/gym_tpu_test_logs",
        )

    with jax.default_matmul_precision("highest"):
        straight = fit_any(6, str(tmp_path / "straight"), interval=100)
        fit_any(3, str(tmp_path / "resume"), interval=3, pp=2, tp=2)
        resumed = fit_any(6, str(tmp_path / "resume"), interval=3)  # pp=1

    steps = [s for s, _ in resumed.history["train_loss"]]
    assert min(steps) == 3 and max(steps) == 5  # genuinely resumed
    losses = [l for _, l in resumed.history["train_loss"]]
    assert np.all(np.isfinite(losses))
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
    shutil.rmtree(str(tmp_path), ignore_errors=True)
