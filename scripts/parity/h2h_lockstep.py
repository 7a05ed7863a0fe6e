"""Lockstep same-batch ablation for the GPT head-to-head band violation
(VERDICT r4 weak #4 / next #4).

``logs/head_to_head_gpt.json`` showed a 0.038-nat gap (2x the 2-run
same-init band) between the reference and gym_tpu at the tracked
``docs_4n_diloco_gpt_small`` config. The candidate causes divide into
(a) optimizer/model math (torch Adam vs optax adam semantics — reference
``nanogpt.py:362-392`` was the verdict's prime suspect) and (b) stochastic
data-order spread that the 2-run band underestimates.

``--mode adam`` (default) isolates (a) completely: one node, identical
ported init, IDENTICAL explicit batch sequence, plain Adam(lr=1e-3) both
sides, torch stepped manually, ours a jitted optax update. With dropout=0
the two trajectories are the same mathematical map, so any systematic
optimizer discrepancy shows as an immediate, growing per-step bias;
fp-chaos (the null hypothesis) shows as ~1e-6 agreement early, drifting
randomly later.

``--mode diloco [--seed N]`` runs the FULL 4-node DiLoCo pipeline in
lockstep — identical per-node batches, inner Adam + the
average/outer-Nesterov round + final node average on both sides — to
cover the outer loop too. Measured: per-step math identical (1-node,
≤1.1e-4/100 steps); the 4-node trajectory is chaotic with an
fp-reassociation floor of ~±0.01 final-eval across batch seeds with NO
systematic sign (seed 17: +0.0124, seed 18: −0.0009). The head-to-head
row of DEMONSTRATION.md sums the chain up.

Writes logs/h2h_lockstep.json (adam) /
logs/h2h_lockstep_diloco*.json (diloco):
    {"step_abs_diff": {...}, "final_eval_ref": ..., "final_eval_ours": ...}

Usage: python scripts/parity/h2h_lockstep.py [--mode adam|diloco]
           [--steps 100] [--batch 8] [--seed 17] [--out PATH]
       (CPU-only: pins jax to the host backend; torch is CPU anyway.)
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

BLOCK = 64


def _setup():
    """Shared preamble for both modes: data, mirrored configs, the
    seed-100 torch prototype, and its ported+DEEP-COPIED flax init.

    The deep copy matters: the porter's ``.detach().numpy()`` views share
    storage with the torch params, which the in-process loops below
    mutate in place (``jnp.asarray`` is NOT enough — the JAX CPU backend
    aliases aligned numpy buffers zero-copy; the h2h harness never hits
    this — its reference side trains in spawned processes)."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import torch

    from reference_head_to_head import REF, docs_tokens, port_torch_gpt

    if REF not in sys.path:
        sys.path.insert(0, REF)
    from example.nanogpt.nanogpt import GPT as RefGPT
    from example.nanogpt.nanogpt import GPTConfig as RefConfig

    from gym_tpu.models.nanogpt import GPTConfig

    ds, ev_ds, vocab = docs_tokens(BLOCK)
    rcfg = RefConfig(block_size=BLOCK, vocab_size=vocab, n_layer=4,
                     n_head=4, n_embd=128, dropout=0.0, bias=True)
    ocfg = GPTConfig(block_size=BLOCK, vocab_size=vocab, n_layer=4,
                     n_head=4, n_embd=128, dropout=0.0, bias=True)
    torch.manual_seed(100)
    proto = RefGPT(rcfg)
    ported = port_torch_gpt(proto, ocfg.n_layer)
    params0 = jax.tree.map(np.array, ported)
    return ds, ev_ds, rcfg, ocfg, proto, params0


def _our_eval(lm, params, ev_ds):
    import jax

    rng_e = np.random.default_rng(0)
    eidx = rng_e.integers(0, len(ev_ds), 64)
    ex, ey = ev_ds.take(eidx)
    return float(lm.loss(params, {}, (ex, ey),
                         jax.random.PRNGKey(0), False)[0])


def _write(out, payload):
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload))


def main_adam(args):
    import jax
    import torch

    from reference_head_to_head import TorchTokenDataset, torch_eval_loss_gpt

    from gym_tpu.models.nanogpt import GPT

    ds, ev_ds, rcfg, ocfg, rmodel, params0 = _setup()

    # identical explicit batch sequence, drawn once
    rng = np.random.default_rng(7)
    idxs = rng.integers(0, len(ds), (args.steps, args.batch))

    # ---- torch side: manual Adam loop ----
    opt = torch.optim.Adam(rmodel.parameters(), lr=1e-3)
    ref_losses = []
    for t in range(args.steps):
        x, y = ds.take(idxs[t])
        xb = torch.tensor(np.asarray(x, dtype=np.int64))
        yb = torch.tensor(np.asarray(y, dtype=np.int64))
        opt.zero_grad()
        loss = rmodel((xb, yb))
        loss.backward()
        opt.step()
        ref_losses.append(float(loss))
    ref_eval = torch_eval_loss_gpt(rmodel, TorchTokenDataset(ev_ds), BLOCK)

    # ---- gym_tpu side: jitted optax adam on the ported init ----
    import optax

    from gym_tpu.models.base import LossModel

    lm = LossModel(GPT(ocfg))
    tx = optax.adam(1e-3)
    params = params0
    opt_state = tx.init(params)
    key = jax.random.PRNGKey(0)  # dropout=0: never drawn

    @jax.jit
    def step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm.loss(p, {}, batch, key, True), has_aux=True
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    our_losses = []
    for t in range(args.steps):
        x, y = ds.take(idxs[t])
        params, opt_state, loss = step(params, opt_state, (x, y))
        our_losses.append(float(loss))
    our_eval = _our_eval(lm, params, ev_ds)

    diffs = np.abs(np.array(ref_losses) - np.array(our_losses))
    probe = {str(t): round(float(diffs[t]), 7)
             for t in (0, 1, 2, 5, 9, 24, 49, args.steps - 1)
             if t < args.steps}
    _write(args.out or "logs/h2h_lockstep.json", {
        "config": "lockstep_1n_adam_gpt_small_docs",
        "steps": args.steps,
        "first10_max_abs_diff": round(float(diffs[:10].max()), 7),
        "step_abs_diff": probe,
        "final_train_abs_diff": round(float(diffs[-1]), 6),
        "final_eval_ref": round(ref_eval, 4),
        "final_eval_ours": round(our_eval, 4),
    })


def main_diloco(args, nodes=4, H=50):
    """4-node DiLoCo lockstep: identical per-node batch sequences through
    BOTH frameworks' full DiLoCo pipelines (inner Adam + periodic
    average/outer-Nesterov + final node average). The adam mode exonerated
    the inner optimizer; this covers the outer loop and the averaging.

    The torch side replicates the reference's semantics in-process
    (``exogym/strategy/diloco.py``: inner step; at local_step % H == 0
    and > 0 [pre-increment]: average models -> master outer SGD(0.7,
    nesterov, m=0.9) on (master - avg) -> broadcast master; final =
    node average)."""
    import jax
    import torch

    from reference_head_to_head import TorchTokenDataset, torch_eval_loss_gpt

    from gym_tpu.models.nanogpt import GPT

    ds, ev_ds, rcfg, ocfg, proto, params0 = _setup()
    steps = args.steps
    rng = np.random.default_rng(args.seed)
    idxs = rng.integers(0, len(ds), (steps, nodes, args.batch))

    # ---- torch side: reference DiLoCo replicated in-process ----
    models = [copy.deepcopy(proto) for _ in range(nodes)]
    opts = [torch.optim.Adam(m.parameters(), lr=1e-3) for m in models]
    master = copy.deepcopy(proto)
    outer = torch.optim.SGD(master.parameters(), lr=0.7, nesterov=True,
                            momentum=0.9)
    local_step = 0
    for t in range(steps):
        for n in range(nodes):
            x, y = ds.take(idxs[t, n])
            xb = torch.tensor(np.asarray(x, dtype=np.int64))
            yb = torch.tensor(np.asarray(y, dtype=np.int64))
            opts[n].zero_grad()
            loss = models[n]((xb, yb))
            loss.backward()
            opts[n].step()
        if local_step % H == 0 and local_step > 0:
            with torch.no_grad():
                avg = {k: sum(m.state_dict()[k] for m in models) / nodes
                       for k in models[0].state_dict()}
            outer.zero_grad()
            for k, p in master.named_parameters():
                p.grad = p.data - avg[k]
            outer.step()
            with torch.no_grad():
                msd = master.state_dict()
                for m in models:
                    m.load_state_dict(msd)
        local_step += 1
    with torch.no_grad():
        avg = {k: sum(m.state_dict()[k] for m in models) / nodes
               for k in models[0].state_dict()}
        final = copy.deepcopy(proto)
        final.load_state_dict(avg)
    ref_eval = torch_eval_loss_gpt(final, TorchTokenDataset(ev_ds), BLOCK)

    # ---- gym_tpu side: the REAL strategy/runtime on a 4-node CPU mesh ----
    import jax.numpy as jnp

    from gym_tpu.models.base import LossModel
    from gym_tpu.parallel.mesh import NodeRuntime
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.train_node import make_init_fn, make_train_step

    devs = jax.devices("cpu")
    runtime = NodeRuntime.create(nodes, devs[:min(nodes, len(devs))])
    lm = LossModel(GPT(ocfg))
    strat = DiLoCoStrategy(OptimSpec("adam", lr=1e-3), H=H)
    strat.finalize(max_steps=steps)
    x0, y0 = ds.take(idxs[0, 0])
    init_fn = make_init_fn(lm, strat, (x0, y0), seed=0,
                           init_params=jax.tree.map(jnp.asarray, params0))
    state = runtime.init_state(init_fn)
    step_fn = runtime.compile(make_train_step(lm, strat, runtime.ctx))
    for t in range(steps):
        xs, ys = [], []
        for n in range(nodes):
            x, y = ds.take(idxs[t, n])
            xs.append(x[None])      # [1(micro), bs, T]
            ys.append(y[None])
        batch_t = runtime.shard_batch((np.stack(xs), np.stack(ys)))
        state, metrics = step_fn(state, batch_t)
    params_avg = runtime.average_over_nodes(state.params)
    our_eval = _our_eval(lm, params_avg, ev_ds)

    default_out = ("logs/h2h_lockstep_diloco.json" if args.seed == 17
                   else f"logs/h2h_lockstep_diloco_s{args.seed}.json")
    _write(args.out or default_out, {
        "config": f"lockstep_{nodes}n_diloco_H{H}_gpt_small_docs",
        "steps": steps,
        "batch_seed": args.seed,
        "final_eval_ref": round(ref_eval, 4),
        "final_eval_ours": round(our_eval, 4),
        "abs_diff": round(abs(ref_eval - our_eval), 5),
    })


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["adam", "diloco"], default="adam")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=17,
                    help="batch-sequence seed (diloco mode)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    (main_diloco if args.mode == "diloco" else main_adam)(args)
