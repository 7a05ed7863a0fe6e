"""Worker for the real two-process multi-host test.

Launched twice by ``tests/test_multiprocess.py`` (process_id 0 and 1).
Each process joins the collective world via
``gym_tpu.parallel.multihost.initialize``, contributes its single CPU
device to a 2-device global mesh, loads ONLY its own node's data
(``multihost.global_batch``), and runs the same jitted DiLoCo training
step — XLA collectives cross the process boundary (the DCN-analog path
the reference covers with its TCP process group,
``exogym/trainer.py:316-347``).

Prints one JSON line: {"pid": ..., "losses": [per-step local-node loss]}.
"""

import json
import sys


def main() -> None:
    port, pid = sys.argv[1], int(sys.argv[2])

    import jax

    # Pin the multi-process CPU world as the only platform (the pod
    # analog), whatever accelerator the host has.
    jax.config.update("jax_platforms", "cpu")

    from gym_tpu.parallel import multihost

    assert multihost.initialize(
        coordinator_address=f"localhost:{port}", num_processes=2,
        process_id=pid,
    )

    import numpy as np

    from gym_tpu.models.base import LossModel
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.parallel.mesh import NodeRuntime
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.train_node import make_init_fn, make_train_step

    devs = jax.devices("cpu")
    assert len(devs) == 2 and jax.process_count("cpu") == 2, (
        f"expected a 2-process world, got {len(devs)} devices"
    )

    num_nodes = 2
    runtime = NodeRuntime.create(num_nodes, devs)
    cfg = GPTConfig(block_size=8, vocab_size=32, n_layer=1, n_head=2,
                    n_embd=16, dropout=0.0, bias=True)
    loss_model = LossModel(GPT(cfg))
    strategy = DiLoCoStrategy(OptimSpec("adamw", lr=1e-3), H=1)
    strategy.finalize(max_steps=3)

    # every process generates the same global stream deterministically,
    # then keeps only its own node's slice — per-host data loading
    rng = np.random.default_rng(7)
    all_batches = rng.integers(
        0, cfg.vocab_size, (3, num_nodes, 1, 2, cfg.block_size),
        dtype=np.int64,
    )
    example = (all_batches[0, 0, 0], all_batches[0, 0, 0])

    init_fn = make_init_fn(loss_model, strategy, example, seed=0)
    state = runtime.init_state(init_fn)
    step = runtime.compile(make_train_step(loss_model, strategy, runtime.ctx))

    losses = []
    for t in range(3):
        mine = all_batches[t, pid:pid + 1]  # this process's node only
        batch = multihost.global_batch(runtime, (mine, np.roll(mine, -1, -1)))
        state, metrics = step(state, batch)
        local_loss = multihost.local_values(metrics["loss"])
        losses.append(round(float(local_loss[0]), 6))

    print(json.dumps({"pid": pid, "losses": losses}), flush=True)


if __name__ == "__main__":
    main()
