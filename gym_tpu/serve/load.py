"""Params-only checkpoint → serveable (params, config).

A training run dir (``fit(save_dir=..., checkpoint_interval=...)``) holds
step-numbered Orbax checkpoints of the FULL train state — per-node
params, optimizer state, strategy state — plus, since the serve
subsystem landed, a ``config.json`` snapshot written next to the step
dirs (``trainer.py``). Serving needs none of the training machinery:

1. ``utils.checkpoint.restore_params`` reads the newest valid step
   template-free and hands back the node-stacked ``params`` tree.
2. The [K] node axis is averaged away — the same node-averaged model a
   ``FitResult.params`` returns (the reference averages final state
   dicts across ranks).
3. ``GPTConfig`` is rebuilt from ``config.json``'s ``model_config`` and
   sanitized for decode by the engine (``models.nanogpt.decode_config``)
   — sharding axes and the pinned MoE dispatch are training-time
   concerns.

``CheckpointNotFoundError`` propagates typed (CLIs surface it as a
one-line message, not a traceback).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import traceback
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.serving import config_from_dict
from ..utils.checkpoint import CheckpointNotFoundError, restore_params

PyTree = Any


# -- quantize-at-load (ISSUE 11: quantized serving) -----------------------


def params_are_quantized(params: PyTree) -> bool:
    """True when the tree already carries quantized leaves (``qkernel``/
    ``qembedding``) — lets every construction path (engine, fleet
    factory, hot-swap reload) accept either an f32 checkpoint tree or a
    pre-quantized one without re-quantizing."""
    found = False

    def walk(node):
        nonlocal found
        if hasattr(node, "items"):
            for name, sub in node.items():
                if name in ("qkernel", "qembedding"):
                    found = True
                walk(sub)

    walk(params)
    return found


def quantize_params(params: PyTree, config) -> PyTree:
    """Quantize an f32 GPT param tree for serving under ``config``
    (``weights_dtype`` 'int8'/'int4', optional ``quant_embed``): every
    2-D block ``kernel`` — and the ``wte`` embedding when
    ``quant_embed`` — becomes ``(qkernel|qembedding, qscale)`` via the
    SAME per-tile max-abs codec the compressed collectives use
    (``strategy/compress.py:QuantizeCodec``, ``stochastic=False`` —
    weights are quantized once, deterministically, not per-step
    gradients). The tile is clamped per-leaf to divide the trailing
    axis (``ops/grouped_matmul.py:quant_tile_for``) so the codec pads
    nothing and scales never straddle rows; biases, LayerNorms and
    ``wpe`` stay f32. The resulting tree is exactly what a
    ``weights_dtype``-configured ``GPT`` consumes (QuantDense /
    QuantEmbed param names) — a no-op at ``weights_dtype='f32'``."""
    wd = getattr(config, "weights_dtype", "f32")
    if wd == "f32":
        return params
    if wd not in ("int8", "int4"):
        raise ValueError(
            f"weights_dtype must be 'f32', 'int8' or 'int4', got {wd!r}")
    from ..ops.grouped_matmul import quant_tile_for
    from ..strategy.compress import QuantizeCodec
    bits = {"int8": 8, "int4": 4}[wd]
    tile = int(getattr(config, "quant_tile", 256))

    def q_leaf(w):
        t = quant_tile_for(w.shape, tile)
        codec = QuantizeCodec(bits=bits, tile=t, stochastic=False)
        q, scale = codec.compress(
            jnp.asarray(w, jnp.float32).reshape(-1), None)
        return q.reshape(w.shape), scale.reshape(-1)

    def walk(node, name=None):
        if not hasattr(node, "items"):
            return node
        d = dict(node)
        kern = d.get("kernel")
        if kern is not None and getattr(kern, "ndim", 0) == 2:
            q, scale = q_leaf(kern)
            out = {"qkernel": q, "qscale": scale}
            if "bias" in d:
                out["bias"] = jnp.asarray(d["bias"], jnp.float32)
            return out
        if (name == "wte" and getattr(config, "quant_embed", False)
                and "embedding" in d):
            q, scale = q_leaf(d["embedding"])
            return {"qembedding": q, "qscale": scale}
        return {k: walk(v, k) for k, v in d.items()}

    return walk(params)


def read_run_config(run_dir: str,
                    config_path: Optional[str] = None) -> Dict[str, Any]:
    """Load the run's captured ``config.json``. Looked up in the run dir
    itself (where the trainer writes it next to the step dirs); an
    explicit ``config_path`` overrides — e.g. for run dirs from before
    the snapshot existed, point at ``logs/<run_name>/config.json``."""
    path = config_path or os.path.join(run_dir, "config.json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no config.json at {path} — pass config_path= (the CSVLogger "
            f"copy under logs/<run_name>/ works) or an explicit GPTConfig")
    with open(path) as f:
        return json.load(f)


def gpt_config_from_run(config: Dict[str, Any]):
    """Rebuild the model's config from a captured run config
    (``trainer._model_config`` flattens the module's nested ``config``
    dataclass into ``model_config.config``; the family is the snapshot's
    ``model_type``, GPT-2 where it names none:
    ``models/serving.py:config_from_dict``). Unknown keys are ignored so
    an older server binary can read a newer run's snapshot."""
    model_cfg = (config.get("model_config") or {}).get("config")
    if not isinstance(model_cfg, dict):
        raise ValueError(
            "config.json carries no model_config.config — was this run's "
            "model one the serving stack knows (models/serving.py)?")
    return config_from_dict(model_cfg)


def load_for_serving(run_dir: str, step: Optional[int] = None,
                     config: Optional[Any] = None,
                     config_path: Optional[str] = None,
                     weights_dtype: Optional[str] = None,
                     kv_dtype: Optional[str] = None,
                     quant_embed: bool = False
                     ) -> Tuple[PyTree, Any, Dict[str, Any]]:
    """Restore a ``fit()`` run dir for inference.

    Returns ``(params, config, info)``: the node-AVERAGED f32 param tree
    (device arrays), the run's ``GPTConfig`` (training sharding intact —
    the engine sanitizes via ``decode_config``), and an info dict
    (``step``, ``num_nodes``, the raw run config). ``config=`` skips the
    ``config.json`` lookup entirely (e.g. serving hand-built params).

    ``weights_dtype`` ('int8'/'int4') runs the quantize-at-load step —
    the returned params are the per-tile-quantized tree and the returned
    config carries the dtype (with ``quant_embed`` optionally extending
    quantization to the tied embedding/lm_head); ``kv_dtype`` ('int8')
    just stamps the config — the KV pools quantize online at decode.
    """
    if not os.path.isdir(run_dir):
        raise CheckpointNotFoundError(
            f"checkpoint run dir {run_dir} does not exist")
    raw: Dict[str, Any] = {}
    if config is None:
        raw = read_run_config(run_dir, config_path)
        config = gpt_config_from_run(raw)
    at_step, node_params, _extra = restore_params(run_dir, step=step)
    leaves = jax.tree.leaves(node_params)
    if not leaves:
        raise CheckpointNotFoundError(
            f"checkpoint step {at_step} under {run_dir} restored an "
            f"empty params tree")
    k = int(leaves[0].shape[0])
    want_k = raw.get("num_nodes")
    if want_k is not None and int(want_k) != k:
        raise ValueError(
            f"checkpoint params carry a [{k}]-node axis but config.json "
            f"says num_nodes={want_k} — wrong run dir / config pairing?")
    # node-average on device (the FitResult.params convention); params
    # are float, so a plain mean is exact in intent and f32 in practice
    avg = jax.jit(
        lambda t: jax.tree.map(lambda x: jnp.mean(x, axis=0), t)
    )(node_params)
    if weights_dtype or kv_dtype or quant_embed:
        config = dataclasses.replace(
            config,
            weights_dtype=weights_dtype or config.weights_dtype,
            kv_dtype=kv_dtype or config.kv_dtype,
            quant_embed=bool(quant_embed) or config.quant_embed)
        avg = quantize_params(avg, config)
    info = {"step": at_step, "num_nodes": k, "run_config": raw}
    return avg, config, info


# -- checkpoint-dir watching (fleet weight hot-swap) ----------------------


def latest_checkpoint_step(run_dir: str) -> Optional[int]:
    """Newest COMMITTED checkpoint step in a run dir, from directory
    names alone — cheap enough to poll. Orbax writes into a
    tmp-suffixed dir and renames on commit, and quarantined dirs carry
    a ``.corrupt-k`` suffix, so "committed" is exactly "the name is a
    bare integer". None when the dir is missing/empty (a trainer that
    has not checkpointed yet is not an error for a watcher)."""
    try:
        names = os.listdir(run_dir)
    except OSError:
        return None
    steps = [int(n) for n in names if n.isdigit()]
    return max(steps) if steps else None


class CheckpointWatcher:
    """Poll a trainer's run dir and fire ``on_new_step(step)`` whenever
    a NEWER committed checkpoint appears — the push half of the fleet's
    zero-downtime weight hot-swap (``python -m gym_tpu.serve
    --reload-watch S`` wires the callback to a rolling
    ``Router.reload``). Callback failures are logged, not fatal: a
    single unreadable checkpoint must not kill the watcher — the
    trainer's NEXT checkpoint gets its own attempt."""

    def __init__(self, run_dir: str,
                 on_new_step: Callable[[int], None],
                 poll_s: float = 10.0,
                 initial_step: Optional[int] = None):
        """``initial_step``: the step already being served — only
        strictly newer checkpoints fire (None = the first committed
        checkpoint seen fires)."""
        self.run_dir = run_dir
        self.on_new_step = on_new_step
        self.poll_s = float(poll_s)
        self.last_step = initial_step
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="gym-tpu-serve-ckpt-watcher",
            daemon=True)

    def start(self) -> "CheckpointWatcher":
        self._thread.start()
        return self

    def stop(self, join_timeout_s: float = 5.0) -> None:
        self._stop.set()
        self._thread.join(timeout=join_timeout_s)

    def poll_once(self) -> Optional[int]:
        """One poll (also the testable unit): fire the callback iff a
        newer step committed; returns the step fired, else None."""
        step = latest_checkpoint_step(self.run_dir)
        if step is None or (self.last_step is not None
                            and step <= self.last_step):
            return None
        self.last_step = step
        try:
            self.on_new_step(step)
        except Exception:  # noqa: BLE001 — a failed reload must not
            # kill the watcher; the next checkpoint retries
            sys.stderr.write(
                f"gym_tpu.serve: checkpoint watcher — on_new_step"
                f"({step}) raised:\n{traceback.format_exc()}")
        return step

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            self.poll_once()
