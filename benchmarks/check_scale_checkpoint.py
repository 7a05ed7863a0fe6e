"""Checkpoint/resume at realistic scale (the PARITY §5.4 scale claim).

Trains GPT-2 base (124M params + DiLoCo inner AdamW + outer
master/momentum — ~2.5 GB of state) for 4 steps on the chip with
Orbax checkpoints every 2 steps, then calls ``fit`` again with
``max_steps=8``: the second run must restore from step 4 and continue
the loss trajectory at steps 4..7.

Usage: python benchmarks/check_scale_checkpoint.py
"""

from __future__ import annotations

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def main() -> None:
    import numpy as np

    from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.strategy import DiLoCoStrategy, OptimSpec
    from gym_tpu.trainer import Trainer

    save_dir = "/tmp/gym_tpu_ckpt_scale"
    shutil.rmtree(save_dir, ignore_errors=True)

    cfg = GPTConfig.gpt2_base()
    cfg.block_size = 512
    cfg.attn_impl = "flash"
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, 300000, dtype=np.int64)

    def factory(rank, n, is_val):
        return ContiguousGPTTrainDataset(data, block_size=512)

    def fit(steps):
        return Trainer(GPT(cfg), factory, factory).fit(
            strategy=DiLoCoStrategy(OptimSpec("adamw", lr=3e-4), H=2),
            num_nodes=1, max_steps=steps, batch_size=4, minibatch_size=4,
            val_size=0, autocast=True, show_progress=False,
            checkpoint_interval=2, save_dir=save_dir,
            run_name="base_ckpt", log_dir="/tmp/gym_tpu_ckpt_logs", seed=7,
        )

    t0 = time.time()
    r1 = fit(4)
    print("first run losses:",
          [round(l, 4) for _, l in r1.history["train_loss"]], flush=True)
    r2 = fit(8)
    steps = [s for s, _ in r2.history["train_loss"]]
    print("resumed losses:",
          [(s, round(l, 4)) for s, l in r2.history["train_loss"]])
    assert steps == [4, 5, 6, 7], f"expected resume at step 4, got {steps}"
    print(f"GPT-2 base checkpoint/resume ok ({time.time() - t0:.0f} s)")


if __name__ == "__main__":
    main()
