"""Worker for the 2-process ``Trainer.fit`` e2e test (VERDICT r3 #1).

Unlike ``_multihost_worker.py`` (which drives ``make_train_step``
directly), this runs the REAL flagship entry point — ``Trainer.fit`` —
in each process of a 2-process ``jax.distributed`` world: per-host data
loading through ``multihost.global_batch`` (each host materializes only
its own node's rows), replicated metric fetch, primary-gated CSV
logging, and a collective Orbax checkpoint written once.

Prints one JSON line with the full loss histories and a parameter
checksum; the test compares them across processes and against the same
fit in a single process.
"""

import json
import sys


def main() -> None:
    port, pid, tmp = sys.argv[1], int(sys.argv[2]), sys.argv[3]

    import jax

    # With a single-process backend as default both workers would see
    # jax.process_index() == 0 and process-index-dependent code (Orbax's
    # primary-writer election) would race on the same files. Pin the
    # default backend to the multi-process CPU world
    # — the analog of a real pod, where the default backend IS the
    # process-aware TPU client. Must run before any backend touch.
    jax.config.update("jax_platforms", "cpu")

    from gym_tpu.parallel import multihost

    assert multihost.initialize(
        coordinator_address=f"localhost:{port}", num_processes=2,
        process_id=pid,
    )
    import numpy as np

    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.trainer import Trainer

    assert len(jax.devices("cpu")) == 2, "expected a 2-process world"

    rng = np.random.default_rng(7)
    data = rng.integers(0, 32, 2048, dtype=np.int64)
    ds = ContiguousGPTTrainDataset(data, block_size=8)
    cfg = GPTConfig(block_size=8, vocab_size=32, n_layer=1, n_head=2,
                    n_embd=16, dropout=0.0, bias=True)
    res = Trainer(GPT(cfg), ds, ds).fit(
        strategy=DiLoCoStrategy(OptimSpec("adamw", lr=1e-3), H=2),
        num_nodes=2, max_steps=4, batch_size=4, minibatch_size=2,
        val_size=4, val_interval=2, device="cpu",
        checkpoint_interval=2, save_dir=tmp + "/ckpt", run_name="mh",
        log_dir=tmp + "/logs", show_progress=False, seed=3,
    )
    checksum = float(sum(np.abs(np.asarray(x)).sum()
                         for x in jax.tree.leaves(res.params)))
    print(json.dumps({
        "pid": pid,
        "train": [round(float(l), 6) for _, l in res.history["train_loss"]],
        "local": [round(float(l), 6) for _, l in res.history["local_loss"]],
        "global": [round(float(l), 6)
                   for _, l in res.history["global_loss"]],
        "final": round(float(res.final_train_loss), 6),
        "checksum": round(checksum, 4),
    }), flush=True)


if __name__ == "__main__":
    main()
