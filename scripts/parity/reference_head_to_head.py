"""Same-data, IDENTICAL-INIT head-to-head: the ACTUAL reference (EXO
Gym, torch + gloo, CPU) vs gym_tpu, on identical offline datasets.

VERDICT r2 #5 / r3 #3: the strongest form of the reference's own oracle
(SURVEY §4) needs zero network — run `/root/reference` itself on the
offline digits / docs-char data at the tracked configs and table final
losses side by side. Both frameworks consume byte-identical training
arrays AND byte-identical initial weights: the torch model is built
first and its state_dict ported into a flax tree (conv/linear layout
transposes), which ``Trainer.fit(init_params=...)`` starts from — the
reference side trains whatever the passed module holds, so no hook is
needed there. The remaining noise is data order + dropout draws only;
the per-config ``band`` field measures it as the spread of two gym_tpu
runs from the same init with different data seeds, and the cross-
framework gap must sit inside ~2 bands.

Configs (BASELINE.md tracked trio + one GPT config):
  digits  2n SimpleReduce · 8n DiLoCo(H=50) · 8n SPARTA(p=0.005)
  docs-char 4n DiLoCo(H=50) GPT "small" (block 64)

Usage:  python scripts/parity/reference_head_to_head.py
            [--steps N] [--gpt_steps N] [--only substr] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                    os.pardir)
REF = "/root/reference"
sys.path.insert(0, REPO)
if REF not in sys.path:
    sys.path.insert(0, REF)

# 8 virtual CPU devices for the gym_tpu side; must precede jax import
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import numpy as np


# -- shared data -------------------------------------------------------------


def digits_arrays():
    """Deterministic (unaugmented) digits train/eval splits — the same
    numpy arrays feed both frameworks."""
    from gym_tpu.data.offline import load_digits_mnist

    tr = load_digits_mnist(True, augment=False)
    ev = load_digits_mnist(False)
    return (tr.arrays[0], tr.arrays[1]), (ev.arrays[0], ev.arrays[1])


def docs_tokens(block: int):
    """The docs-char token stream both frameworks window over."""
    from gym_tpu.data import get_dataset

    ds, vocab = get_dataset("docs", block, end_pc=0.9)
    ev, _ = get_dataset("docs", block, start_pc=0.9)
    return ds, ev, int(vocab)


# -- torch side (the reference) ---------------------------------------------


try:
    import torch as _torch
    import torch.nn as _tnn
    import torch.nn.functional as _tF
except ImportError:  # pragma: no cover
    _torch = None


def _cnn_block(cin, cout):
    return [_tnn.Conv2d(cin, cout, 3, padding=1), _tnn.BatchNorm2d(cout),
            _tnn.ReLU(),
            _tnn.Conv2d(cout, cout, 3, padding=1), _tnn.BatchNorm2d(cout),
            _tnn.ReLU(), _tnn.MaxPool2d(2), _tnn.Dropout2d(0.25)]


class TorchCNNWrapper(_tnn.Module if _torch else object):
    """torch mirror of gym_tpu/models/mnist_cnn.py (itself the reference
    example's architecture): two conv blocks (64, 128; 3x3 convs + BN +
    ReLU x2, maxpool, Dropout2d 0.25) -> Linear 256 -> Dropout 0.5 ->
    Linear 10, wrapped as forward(batch) -> cross-entropy. Module-level
    (mp.spawn pickles the model)."""

    def __init__(self):
        super().__init__()
        self.net = _tnn.Sequential(
            *_cnn_block(1, 64), *_cnn_block(64, 128), _tnn.Flatten(),
            _tnn.Linear(128 * 7 * 7, 256), _tnn.ReLU(), _tnn.Dropout(0.5),
            _tnn.Linear(256, 10))

    def forward(self, batch):
        imgs, labels = batch
        return _tF.cross_entropy(self.net(imgs), labels)


def torch_cnn():
    return TorchCNNWrapper()


class TorchArrayDataset:
    """(x, y) tuples from numpy arrays, NCHW images."""

    def __init__(self, imgs_nhwc, labels):
        import torch
        self.x = torch.tensor(np.transpose(imgs_nhwc, (0, 3, 1, 2)))
        self.y = torch.tensor(labels.astype(np.int64))

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class TorchTokenDataset:
    """Contiguous (x, y) int64 blocks over a token stream — the torch
    twin of gym_tpu ContiguousGPTTrainDataset.

    ``order_seed``: permutes the index→window mapping (same window SET,
    different draw of data order). The reference's DistributedSampler is
    deterministically seeded, so this is the only fair way to measure the
    reference's own data-order noise band — the r5 lockstep ablation
    proved the per-step optimizer math identical, leaving data order as
    the sole noise source in the head-to-head."""

    def __init__(self, ours, order_seed: int = 0):
        import torch
        self.data = torch.tensor(np.asarray(ours.data, dtype=np.int64))
        self.block = ours.block_size
        self.perm = (np.random.default_rng(order_seed).permutation(len(self))
                     if order_seed else None)

    def __len__(self):
        return len(self.data) - self.block - 1

    def __getitem__(self, i):
        if self.perm is not None:
            i = int(self.perm[i])
        x = self.data[i:i + self.block]
        y = self.data[i + 1:i + self.block + 1]
        return x, y


def ref_strategy(name: str):
    import torch
    from exogym.strategy.diloco import DiLoCoStrategy
    from exogym.strategy.optim import OptimSpec
    from exogym.strategy.sparta import SPARTAStrategy
    from exogym.strategy.strategy import SimpleReduceStrategy

    optim = OptimSpec(torch.optim.Adam, lr=1e-3)
    return {
        "simple_reduce": lambda: SimpleReduceStrategy(optim_spec=optim),
        "diloco": lambda: DiLoCoStrategy(optim_spec=optim, H=50),
        "sparta": lambda: SPARTAStrategy(inner_optim=optim, p_sparta=0.005),
    }[name]()


def run_reference(model, train_ds, val_ds, strategy, num_nodes, steps,
                  batch, port):
    from exogym.trainer import LocalTrainer

    trainer = LocalTrainer(model, train_ds, val_ds, start_port=port)
    final = trainer.fit(
        num_epochs=1, strategy=strategy, num_nodes=num_nodes,
        max_steps=steps, device="cpu", batch_size=batch,
        minibatch_size=batch, val_size=max(256, batch),
        val_interval=max(1, steps // 2), run_name="h2h",
        log_dir="/tmp/h2h_ref_logs",
    )
    return final


def torch_eval_loss(model, ds, n=1024, batch=256):
    import torch
    model.eval()
    tot, cnt = 0.0, 0
    with torch.no_grad():
        for lo in range(0, min(n, len(ds)), batch):
            items = [ds[i] for i in range(lo, min(lo + batch, n, len(ds)))]
            xs = torch.stack([a for a, _ in items])
            ys = torch.stack([b for _, b in items])
            tot += float(model((xs, ys))) * len(items)
            cnt += len(items)
    return tot / cnt


# -- torch → flax weight porting (identical-init, VERDICT r3 #3) -------------


def port_torch_cnn(model) -> dict:
    """TorchCNNWrapper state_dict → MnistLossModel flax param tree.

    Layout transposes: conv [out, in, kh, kw] → [kh, kw, in, out]; the
    flatten boundary differs (torch NCHW flattens C-major, flax NHWC
    flattens H-major) so the first Linear's kernel is permuted through
    [out, C, H, W] → [H, W, C, out]; plain Linear transposes. BN running
    stats are fresh zeros/ones in both frameworks at init — only params
    port."""
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}

    def conv(i):
        return {"kernel": np.transpose(sd[f"net.{i}.weight"], (2, 3, 1, 0)),
                "bias": sd[f"net.{i}.bias"]}

    def bn(i):
        return {"scale": sd[f"net.{i}.weight"], "bias": sd[f"net.{i}.bias"]}

    w17 = sd["net.17.weight"]                       # [256, 128*7*7] C-major
    dense0 = {"kernel": np.transpose(
        w17.reshape(256, 128, 7, 7), (2, 3, 1, 0)).reshape(-1, 256),
        "bias": sd["net.17.bias"]}
    dense1 = {"kernel": sd["net.20.weight"].T, "bias": sd["net.20.bias"]}
    return {"CNN_0": {
        "Conv_0": conv(0), "BatchNorm_0": bn(1),
        "Conv_1": conv(3), "BatchNorm_1": bn(4),
        "Conv_2": conv(8), "BatchNorm_2": bn(9),
        "Conv_3": conv(11), "BatchNorm_3": bn(12),
        "Dense_0": dense0, "Dense_1": dense1,
    }}


def port_torch_gpt(ref_model, n_layer):
    """Reuse the parity test's porter (tests/test_reference_parity.py)."""
    tests_dir = os.path.join(REPO, "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    from test_reference_parity import _port_weights
    return _port_weights(ref_model, n_layer)


# -- gym_tpu side ------------------------------------------------------------


def run_ours(model, train_ds, val_ds, strategy, num_nodes, steps, batch,
             init_params=None, seed=42, device=None):
    """device=None: the default accelerator (the chip when present — a
    K-node fold on one device; the single host core crawls at ~20 s/step
    on the CNN mesh). The comparison is mathematical, not hardware."""
    from gym_tpu import Trainer

    return Trainer(model, train_ds, val_ds).fit(
        strategy=strategy, num_nodes=num_nodes, max_steps=steps,
        batch_size=batch, minibatch_size=batch,
        val_size=256, val_interval=max(1, steps // 2),
        show_progress=False, run_name="h2h", log_dir="/tmp/h2h_logs",
        init_params=init_params, seed=seed, device=device,
    )


def ours_strategy(name: str):
    from gym_tpu.strategy import (DiLoCoStrategy, OptimSpec,
                                  SimpleReduceStrategy, SPARTAStrategy)

    optim = OptimSpec("adam", lr=1e-3)
    return {
        "simple_reduce": lambda: SimpleReduceStrategy(optim),
        "diloco": lambda: DiLoCoStrategy(optim, H=50),
        "sparta": lambda: SPARTAStrategy(optim, p_sparta=0.005),
    }[name]()


def ours_eval_loss_mnist(res, ev):
    import jax
    from gym_tpu.models import MnistLossModel
    from gym_tpu.models.base import LossModel

    lm = LossModel(MnistLossModel())
    imgs, labels = ev
    tot, cnt = 0.0, 0
    for lo in range(0, min(1024, len(imgs)), 256):
        mb = (imgs[lo:lo + 256], labels[lo:lo + 256])
        loss, _ = lm.loss(res.params, res.model_state, mb,
                          jax.random.PRNGKey(0), False)
        tot += float(loss) * len(mb[1])
        cnt += len(mb[1])
    return tot / cnt


def ours_eval_loss_gpt(res, ev, model):
    import jax
    from gym_tpu.models.base import LossModel

    lm = LossModel(model)
    rng = np.random.default_rng(0)
    idxs = rng.integers(0, len(ev), 64)
    xs, ys = ev.take(idxs)
    loss, _ = lm.loss(res.params, res.model_state, (xs, ys),
                      jax.random.PRNGKey(0), False)
    return float(loss)


def torch_eval_loss_gpt(model, ds, block):
    import torch
    model.eval()
    rng = np.random.default_rng(0)
    idxs = rng.integers(0, len(ds), 64)
    with torch.no_grad():
        xs = torch.stack([ds[i][0] for i in idxs])
        ys = torch.stack([ds[i][1] for i in idxs])
        return float(model((xs, ys)))


# -- configs -----------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    # defaults reproduce logs/head_to_head.json (DEMONSTRATION.md's row)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--gpt_steps", type=int, default=100)
    ap.add_argument("--band_seeds", type=int, default=2,
                    help="gym_tpu runs (data seeds 42..42+N-1) whose "
                         "max-min loss spread is the band; 2 reproduces "
                         "the historic band, >=4 gives a spread that a "
                         "2-sigma-ish cross-framework gap can be judged "
                         "against honestly (VERDICT r4 #4)")
    ap.add_argument("--ref_orders", type=int, default=1,
                    help="reference-side GPT runs with index-permuted "
                         "train windows (same window set, different data "
                         "order) — measures the reference's OWN "
                         "data-order band, which its deterministically "
                         "seeded DistributedSampler otherwise hides")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="logs/head_to_head.json")
    ap.add_argument("--device", default=None,
                    help="device for the gym_tpu side ('cpu' pins the "
                         "CPU; the comparison is mathematical)")
    args = ap.parse_args()

    if args.device == "cpu":
        # pin the DEFAULT backend too: a stray default-backend touch
        # (jnp.asarray in the weight porters) would otherwise take the
        # chip
        import jax
        jax.config.update("jax_platforms", "cpu")

    results = []
    port = 29811

    mnist_cfgs = [("simple_reduce", 2), ("diloco", 8), ("sparta", 8)]
    (tr_imgs, tr_labels), ev = digits_arrays()
    from gym_tpu.data.sampler import ArrayDataset

    for name, nodes in mnist_cfgs:
        cfg_name = f"digits_{nodes}n_{name}"
        if args.only and args.only not in cfg_name:
            continue
        port += 1
        # identical init: the torch model's weights are the run's weights
        import torch
        torch.manual_seed(100)
        model0 = torch_cnn()
        ported = port_torch_cnn(model0)
        print(f"=== {cfg_name} (reference) ===", flush=True)
        ref_model = run_reference(
            model0, TorchArrayDataset(tr_imgs, tr_labels),
            TorchArrayDataset(ev[0], ev[1]), ref_strategy(name),
            nodes, args.steps, 64, port)
        ref_loss = torch_eval_loss(ref_model, TorchArrayDataset(*ev))
        print(f"=== {cfg_name} (gym_tpu) ===", flush=True)
        from gym_tpu.models import MnistLossModel
        res = run_ours(MnistLossModel(), ArrayDataset(tr_imgs, tr_labels),
                       ArrayDataset(*ev), ours_strategy(name), nodes,
                       args.steps, 64, init_params=ported, seed=42,
                       device=args.device)
        our_loss = ours_eval_loss_mnist(res, ev)
        # band: same init, different data seed — the residual noise the
        # cross-framework gap is judged against (data order + dropout)
        res_b = run_ours(MnistLossModel(), ArrayDataset(tr_imgs, tr_labels),
                         ArrayDataset(*ev), ours_strategy(name), nodes,
                         args.steps, 64, init_params=ported, seed=43,
                         device=args.device)
        band = abs(our_loss - ours_eval_loss_mnist(res_b, ev))
        results.append({"config": cfg_name, "reference_loss":
                        round(ref_loss, 4), "gym_tpu_loss":
                        round(our_loss, 4), "band": round(band, 4),
                        "identical_init": True})
        print(json.dumps(results[-1]), flush=True)

    cfg_name = "docs_4n_diloco_gpt_small"
    if not args.only or args.only in cfg_name:
        import torch
        from example.nanogpt.nanogpt import GPT as RefGPT
        from example.nanogpt.nanogpt import GPTConfig as RefConfig

        from gym_tpu.models.nanogpt import GPT, GPTConfig

        block = 64
        ds, ev_ds, vocab = docs_tokens(block)
        rcfg = RefConfig(block_size=block, vocab_size=vocab, n_layer=4,
                         n_head=4, n_embd=128, dropout=0.0, bias=True)
        ocfg = GPTConfig(block_size=block, vocab_size=vocab, n_layer=4,
                         n_head=4, n_embd=128, dropout=0.0, bias=True)
        torch.manual_seed(100)
        rmodel = RefGPT(rcfg)
        ported = port_torch_gpt(rmodel, ocfg.n_layer)
        ref_losses = []
        for order in range(max(1, args.ref_orders)):
            port += 1
            # identical init for every order draw
            torch.manual_seed(100)
            rmodel = RefGPT(rcfg)
            print(f"=== {cfg_name} (reference, order {order}) ===",
                  flush=True)
            tds = TorchTokenDataset(ds, order_seed=order)
            ref_model = run_reference(
                rmodel, tds, TorchTokenDataset(ev_ds),
                ref_strategy("diloco"), 4, args.gpt_steps, 8, port)
            ref_losses.append(
                torch_eval_loss_gpt(ref_model, TorchTokenDataset(ev_ds),
                                    block))
            print(f"  order {order}: {ref_losses[-1]:.4f}", flush=True)
        ref_loss = ref_losses[0]
        print(f"=== {cfg_name} (gym_tpu) ===", flush=True)
        losses = []
        for s in range(max(2, args.band_seeds)):
            res = run_ours(GPT(ocfg), ds, ev_ds, ours_strategy("diloco"), 4,
                           args.gpt_steps, 8, init_params=ported,
                           seed=42 + s, device=args.device)
            losses.append(ours_eval_loss_gpt(res, ev_ds, GPT(ocfg)))
            print(f"  seed {42 + s}: {losses[-1]:.4f}", flush=True)
        our_loss = losses[0]
        band = max(losses) - min(losses)
        row = {"config": cfg_name, "reference_loss": round(ref_loss, 4),
               "gym_tpu_loss": round(our_loss, 4), "band": round(band, 4),
               "band_seeds": len(losses),
               "gym_tpu_losses": [round(l, 4) for l in losses],
               "identical_init": True}
        if len(ref_losses) > 1:
            row["reference_losses"] = [round(l, 4) for l in ref_losses]
            row["reference_band"] = round(max(ref_losses) - min(ref_losses),
                                          4)
            # honest cross-framework statistics from both sides' raw
            # runs: gap of means, each side's mean, and whether the two
            # samples' ranges overlap at all (rank separation at n+n is
            # the strongest small-sample signal of a residual offset —
            # a pooled max−min would be ≥ the mean gap BY CONSTRUCTION
            # and can never flag a violation, so it is not reported)
            rm = sum(ref_losses) / len(ref_losses)
            om = sum(losses) / len(losses)
            row["gap_of_means"] = round(abs(rm - om), 4)
            row["reference_mean"] = round(rm, 4)
            row["gym_tpu_mean"] = round(om, 4)
            row["ranges_overlap"] = bool(
                max(losses) >= min(ref_losses)
                and max(ref_losses) >= min(losses))
        results.append(row)
        print(json.dumps(results[-1]), flush=True)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
