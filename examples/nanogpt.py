"""nanoGPT CLI (reference ``example/nanogpt.py`` parity).

Full flag surface of the reference (SURVEY §5.6): dataset/pc-range/
block_size (``:36-47``), training/model-size (``:49-58``), optimization
(``:61-67``), seed/wandb/val (``:69-74``), ``--strategy`` choice (``:77-83``)
and per-strategy knobs — FedAvg ``--H --island_size`` (``:85-92``), SPARTA
``--p_sparta --sparta_interval`` (``:93-102``; unlike the reference these
flags are actually consumed), DiLoCo ``--diloco_interval --outer_lr
--nesterov --outer_momentum`` (``:104-116``), DeMo compression flags
(``:118-133``). The ``diloco_sparta`` combo works here (the reference ships
it broken — SURVEY §2.1).

TPU-native additions: ``--cp`` (context-parallel devices per node, ring
attention) and ``--attn_impl`` (dense/flash/ring).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import argparse

import numpy as np

from gym_tpu import Trainer
from gym_tpu.data import ContiguousGPTTrainDataset, get_dataset
from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.strategy import (DecoupledMomentumStrategy, DeMoStrategy,
                              DiLoCoStrategy, DynamiQStrategy,
                              FedAvgStrategy, NoLoCoStrategy, OptimSpec,
                              SimpleReduceStrategy, SPARTADiLoCoStrategy,
                              SPARTAStrategy, ZeroReduceStrategy)


def gen_run_name(args) -> str:
    """Run-name generator (reference ``example/nanogpt.py:9-28``)."""
    parts = [args.dataset, args.model_size, args.strategy,
             f"{args.num_nodes}n", f"bs{args.batch_size}"]
    if args.strategy in ("diloco", "diloco_sparta", "noloco",
                         "demo_outer"):
        parts.append(f"H{args.diloco_interval}")
    if args.strategy in ("sparta", "diloco_sparta"):
        parts.append(f"p{args.p_sparta}")
    if args.strategy == "dynamiq":
        parts.append(args.codec or "int8")
    elif args.strategy == "demo_outer":
        # the default link is top-k (create_strategy) — name it so the
        # default run and an explicit --codec topk run share a run dir
        codec = args.codec or "topk"
        if codec != "dense":
            parts.append(codec)
    elif (args.strategy in ("diloco", "noloco")
            and args.codec not in (None, "dense")):
        parts.append(args.codec)
    if getattr(args, "participation", 1.0) < 1.0:
        parts.append(f"part{args.participation}")
    if getattr(args, "n_experts", 0):
        parts.append(f"moe{args.n_experts}e{args.expert_topk}")
    return "_".join(str(p) for p in parts)


def create_strategy(args):
    """Strategy factory (reference ``example/nanogpt.py:138-245``)."""
    if (getattr(args, "participation", 1.0) < 1.0
            and args.strategy not in ("fedavg", "diloco", "sparta",
                                      "diloco_sparta")):
        # refuse rather than silently ignore — the parsed-but-unused flag
        # bug class this framework exists to kill (SURVEY §5.6)
        raise SystemExit(
            f"--participation is not supported by --strategy "
            f"{args.strategy} (fedavg/diloco/sparta/diloco_sparta only)"
        )
    optim = OptimSpec("adamw", lr=args.lr)
    sched = dict(
        lr_scheduler="lambda_cosine",
        lr_scheduler_kwargs={
            "warmup_steps": args.warmup_steps,
            "cosine_anneal": args.cosine_anneal,
        },
        max_norm=args.max_norm,
    )
    if args.strategy == "base":
        return SimpleReduceStrategy(optim_spec=optim, **sched)
    if args.strategy == "zero":
        # ZeRO-1 DDP (beyond the reference's strategy set): optimizer
        # state sharded 1/K per node — see strategy/zero_reduce.py
        return ZeroReduceStrategy(optim_spec=optim, **sched)
    if args.strategy == "fedavg":
        return FedAvgStrategy(inner_optim=optim, H=args.H,
                              island_size=args.island_size,
                              participation=args.participation, **sched)
    # the CompressedLink codec axis (ISSUE 12): shared by diloco /
    # noloco / demo_outer; "dense" (or unset) is the identity link
    link_codec = None if args.codec in (None, "dense") else args.codec
    link_kw = ({"frac": args.topk_frac} if link_codec == "topk" else {})
    if args.strategy == "diloco":
        return DiLoCoStrategy(
            optim_spec=optim,
            outer_optim_spec=OptimSpec(
                "sgd", lr=args.outer_lr, nesterov=args.nesterov,
                momentum=args.outer_momentum),
            H=args.diloco_interval,
            participation=args.participation,
            codec=link_codec, **link_kw, **sched)
    if args.strategy == "sparta":
        return SPARTAStrategy(inner_optim=optim, p_sparta=args.p_sparta,
                              interval=args.sparta_interval,
                              participation=args.participation, **sched)
    if args.strategy == "diloco_sparta":
        return SPARTADiLoCoStrategy(
            optim_spec=optim,
            outer_optim_spec=OptimSpec(
                "sgd", lr=args.outer_lr, nesterov=args.nesterov,
                momentum=args.outer_momentum),
            p_sparta=args.p_sparta, H=args.diloco_interval,
            sparta_interval=args.sparta_interval,
            participation=args.participation, **sched)
    if args.strategy == "demo":
        return DeMoStrategy(
            optim_spec=OptimSpec("sgd", lr=args.lr),
            compression_decay=args.compression_decay,
            compression_topk=args.compression_topk,
            compression_chunk=args.compression_chunk,
            weight_decay=args.weight_decay, **sched)
    if args.strategy == "noloco":
        # all-reduce-free: shared-PRNG partner gossip every
        # --diloco_interval steps (see strategy/noloco.py)
        return NoLoCoStrategy(
            optim_spec=optim,
            outer_optim_spec=OptimSpec(
                "sgd", lr=args.outer_lr, nesterov=args.nesterov,
                momentum=args.outer_momentum),
            H=args.diloco_interval,
            codec=link_codec, **link_kw, **sched)
    if args.strategy == "demo_outer":
        # decoupled outer momentum (arXiv 2510.03371; strategy/demo.py):
        # --codec defaults to the DeMo-style top-k extraction
        codec = link_codec or ("topk" if args.codec is None else None)
        ckw = {"frac": args.topk_frac} if codec == "topk" else {}
        return DecoupledMomentumStrategy(
            optim_spec=optim, H=args.diloco_interval,
            outer_lr=args.outer_lr, outer_momentum=args.outer_momentum,
            codec=codec, **ckw, **sched)
    if args.strategy == "dynamiq":
        # compressed all-reduce: DDP sync pattern, codec'd payloads
        # (see strategy/dynamiq.py)
        if args.codec == "dense":
            raise SystemExit(
                "dynamiq is compressed by definition — --codec dense "
                "is plain DDP; use --strategy base instead")
        codec = args.codec or "int8"
        kw = {"frac": args.topk_frac} if codec == "topk" else {}
        return DynamiQStrategy(optim_spec=optim, codec=codec,
                               **kw, **sched)
    raise ValueError(f"unknown strategy {args.strategy}")


def main():
    p = argparse.ArgumentParser()
    # dataset (reference :36-47)
    p.add_argument("--dataset", default="shakespeare",
                   choices=["shakespeare", "wikitext", "code", "docs", "owt"])
    p.add_argument("--start_pc", type=float, default=0.0)
    p.add_argument("--end_pc", type=float, default=1.0)
    p.add_argument("--block_size", type=int, default=1024)
    # training / model size (:49-58)
    p.add_argument("--num_nodes", type=int, default=1)
    p.add_argument("--device", default=None)
    p.add_argument("--model_size", default="small",
                   choices=["small", "base", "medium", "large", "xl"])
    p.add_argument("--num_epochs", type=int, default=1)
    p.add_argument("--max_steps", type=int, default=None)
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--minibatch_size", type=int, default=None)
    # optimization (:61-67)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--dropout", type=float, default=0.0,
                   help="model dropout rate (reference nanogpt.py:141)")
    p.add_argument("--max_norm", type=float, default=1.0)
    p.add_argument("--warmup_steps", type=int, default=100)
    p.add_argument("--cosine_anneal", action="store_true")
    p.add_argument("--weight_decay", type=float, default=0.1)
    # bookkeeping (:69-74)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--wandb_project", default=None)
    p.add_argument("--val_size", type=int, default=256)
    p.add_argument("--val_interval", type=int, default=100)
    # strategy (:77-133)
    p.add_argument("--strategy", default="base",
                   choices=["base", "zero", "fedavg", "diloco", "sparta",
                            "diloco_sparta", "demo", "noloco", "dynamiq",
                            "demo_outer"])
    p.add_argument("--H", type=int, default=1)
    p.add_argument("--island_size", type=int, default=None)
    p.add_argument("--p_sparta", type=float, default=0.005)
    p.add_argument("--sparta_interval", type=int, default=1)
    p.add_argument("--diloco_interval", type=int, default=100)
    p.add_argument("--outer_lr", type=float, default=0.7)
    p.add_argument("--nesterov",
                   type=lambda s: s.lower() in ("1", "true", "yes"),
                   default=True)
    p.add_argument("--outer_momentum", type=float, default=0.9)
    p.add_argument("--compression_decay", type=float, default=0.999)
    p.add_argument("--compression_topk", type=int, default=32)
    p.add_argument("--compression_chunk", type=int, default=64)
    p.add_argument("--codec", default=None,
                   choices=["dense", "int8", "int4", "topk"],
                   help="outer-loop payload codec (strategy/compress.py "
                        "CompressedLink): diloco/noloco/demo_outer "
                        "default dense (demo_outer: topk), dynamiq "
                        "defaults int8")
    p.add_argument("--topk_frac", type=float, default=0.01,
                   help="kept fraction for --codec topk")
    # TPU-native additions
    p.add_argument("--cp", type=int, default=1,
                   help="context-parallel devices per node (ring attention)")
    p.add_argument("--attn_impl", default=None,
                   choices=[None, "dense", "flash", "ring"])
    p.add_argument("--seq_layout", default="zigzag",
                   choices=["zigzag", "contiguous"],
                   help="cp chunk assignment (zigzag = load-balanced "
                        "halves, ~2x ring step; contiguous for A/B)")
    p.add_argument("--autocast", action="store_true",
                   help="bf16 forward pass")
    p.add_argument("--n_experts", type=int, default=0,
                   help="MoE: experts per MoE block (0 = dense)")
    p.add_argument("--expert_topk", type=int, default=2)
    p.add_argument("--moe_every", type=int, default=2,
                   help="every Nth block is MoE (2 = alternate)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel devices per node (Megatron "
                        "sharding over a GSPMD-auto 'model' axis)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel devices (shards experts)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel devices per node (GPipe stages;"
                        " grad-accum microbatches are the pipeline's M)")
    p.add_argument("--participation", type=float, default=1.0,
                   help="fraction of nodes alive per comm round "
                        "(simulated failures; fedavg/diloco/sparta)")
    p.add_argument("--skip_nonfinite", action="store_true",
                   help="quarantine non-finite per-node gradients")
    p.add_argument("--sample", type=int, default=0, metavar="N",
                   help="after training, sample N tokens from the "
                        "node-averaged model (KV-cache decoder); with "
                        "--ckpt, sample from that run dir instead of "
                        "training")
    p.add_argument("--ckpt", default=None, metavar="RUN_DIR",
                   help="skip training: params-only restore from this "
                        "checkpoint run dir (fit save_dir/<run_name>) "
                        "and --sample from it")
    # host-overlap pipeline knobs (ISSUE 1) — overlap is the default;
    # the flags select the serial paths for A/Bs and debugging
    p.add_argument("--no_prefetch", action="store_true",
                   help="assemble + device_put every batch on the "
                        "dispatch critical path (overlap off)")
    p.add_argument("--sync_checkpoint", action="store_true",
                   help="blocking checkpoint saves instead of the "
                        "writer-thread overlap")
    p.add_argument("--compilation_cache_dir", default=None, metavar="DIR",
                   help="place the persistent XLA compile cache "
                        "(default: .jax_cache in the checkout; "
                        "JAX_COMPILATION_CACHE_DIR, where set, wins)")
    # network simulation (ISSUE 3): price the strategy's collective trace
    # on a declarative topology and log sim_step_s/sim_total_s
    p.add_argument("--network", default=None, metavar="PRESET",
                   help="simulate this network topology (datacenter, wan, "
                        "federated) — logs simulated per-step and total "
                        "wall-clock alongside comm_bytes")
    p.add_argument("--network_overlap", action="store_true",
                   help="model perfect compute/comm overlap in the "
                        "network simulation (default: comm serializes)")
    args = p.parse_args()

    if args.ckpt:
        # sampling-only mode: params-only restore (gym_tpu.serve.load) —
        # no optimizer-state template, no dataset, no training. A missing
        # or fully corrupt run dir is a one-line message, not a traceback.
        from gym_tpu.serve.load import load_for_serving
        from gym_tpu.utils.checkpoint import CheckpointNotFoundError
        try:
            params, cfg, info = load_for_serving(args.ckpt)
        except (CheckpointNotFoundError, FileNotFoundError,
                ValueError) as e:
            # ValueError covers a non-GPT config.json or a num_nodes /
            # node-axis mismatch — same one-line contract, no traceback
            raise SystemExit(f"nanogpt: cannot sample from {args.ckpt}: "
                             f"{e}")
        print(f"restored step {info['step']} "
              f"({info['num_nodes']}-node average) from {args.ckpt}")
        _print_sample(params, cfg, cfg.vocab_size,
                      args.sample or 200, args.seed)
        return

    if args.device == "cpu":
        # pin the platform LIST, not just the device choice, so no
        # other backend is initialized (a chip belongs to one process)
        import jax
        jax.config.update("jax_platforms", "cpu")

    attn = args.attn_impl or ("ring" if args.cp > 1 else "dense")

    # dataset factory: per-node OWT shard convention
    # (reference example/nanogpt.py:253-281)
    if args.dataset == "owt":
        def factory(rank, num_nodes, is_val):
            if is_val:
                ds, _ = get_dataset("owt", args.block_size,
                                    start_pc=0.99, end_pc=1.0)
                return ds
            width = 0.99 / num_nodes
            ds, _ = get_dataset(
                "owt", args.block_size,
                start_pc=args.start_pc + rank * width,
                end_pc=args.start_pc + (rank + 1) * width)
            return ds
        train_data, val_data = factory, factory
        _, vocab_size = get_dataset("owt", args.block_size,
                                    start_pc=0.0, end_pc=0.001)
    else:
        ds, vocab_size = get_dataset(args.dataset, args.block_size,
                                     start_pc=args.start_pc,
                                     end_pc=args.end_pc * 0.9)
        val, _ = get_dataset(args.dataset, args.block_size,
                             start_pc=args.end_pc * 0.9, end_pc=args.end_pc)
        train_data, val_data = ds, val

    cfg = GPTConfig.gpt2_size_map(args.model_size)
    cfg.vocab_size = int(vocab_size)
    cfg.block_size = args.block_size
    cfg.attn_impl = attn
    cfg.seq_axis = "seq" if attn == "ring" else None
    cfg.seq_layout = args.seq_layout
    cfg.dropout = args.dropout
    if args.n_experts:
        cfg.n_experts = args.n_experts
        cfg.expert_topk = args.expert_topk
        cfg.moe_every = args.moe_every
        cfg.expert_axis = "expert" if args.ep > 1 else None

    res = Trainer(GPT(cfg), train_data, val_data).fit(
        num_epochs=args.num_epochs,
        max_steps=args.max_steps,
        strategy=create_strategy(args),
        num_nodes=args.num_nodes,
        device=args.device,
        batch_size=args.batch_size,
        minibatch_size=args.minibatch_size,
        cp=args.cp,
        tp=args.tp,
        ep=args.ep,
        pp=args.pp,
        skip_nonfinite=args.skip_nonfinite,
        autocast=args.autocast,
        prefetch=not args.no_prefetch,
        async_checkpoint=not args.sync_checkpoint,
        compilation_cache_dir=args.compilation_cache_dir,
        network=args.network,
        network_overlap=args.network_overlap,
        seed=args.seed,
        val_size=args.val_size,
        val_interval=args.val_interval,
        wandb_project=args.wandb_project,
        run_name=gen_run_name(args),
    )
    print(f"final train loss {res.final_train_loss:.4f} "
          f"({res.steps_per_second:.2f} it/s)")
    if res.sim is not None:
        print(f"simulated on {res.sim['topology']}: "
              f"{res.sim['sim_total_s']:.1f}s total "
              f"({res.sim['sim_comm_s']:.1f}s comm, "
              f"{res.sim['sim_compute_s']:.1f}s compute)")

    if args.sample:
        _print_sample(res.params, cfg, int(vocab_size), args.sample,
                      args.seed)


def _print_sample(params, cfg, vocab_size: int, n: int, seed: int) -> None:
    """Sample ``n`` tokens from token 0 via the KV-cache decoder and print
    them — as text for char-level corpora, token ids otherwise. Shared by
    the post-training path and ``--ckpt`` sampling-only mode."""
    from gym_tpu.data.build_dataset import CHAR_VOCAB
    from gym_tpu.models.nanogpt import generate_fast

    prompt = np.zeros((1, 1), np.int64)  # start from token 0
    n_new = min(n, cfg.block_size - 1)  # KV-cache capacity
    if n_new < n:
        print(f"(clamping sample to {n_new} tokens — the KV cache "
              f"holds block_size={cfg.block_size})")
    out = generate_fast(params, cfg, prompt, n_new,
                        temperature=0.8, top_k=40, seed=seed)
    toks = out[0, 1:].tolist()
    if int(vocab_size) <= len(CHAR_VOCAB) + 1:  # char-level corpus
        text = "".join(CHAR_VOCAB[t] if t < len(CHAR_VOCAB) else ""
                       for t in toks)
        print("--- sample ---")
        print(text)
    else:
        print("--- sample (token ids) ---")
        print(toks)


if __name__ == "__main__":
    main()
