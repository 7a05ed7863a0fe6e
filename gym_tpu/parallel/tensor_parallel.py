"""Tensor-parallel training via GSPMD sharding annotations.

The simulator's node mesh replicates the model per simulated node — right
for communication-strategy research, wrong when ONE model no longer fits a
chip. This module is the other regime: a ``('data', 'model')`` mesh where
XLA partitions the network Megatron-style from sharding annotations
(the "pick a mesh, annotate shardings, let XLA insert collectives" recipe):

- attention qkv / mlp up-projection kernels: column-sharded ``P(None,'model')``
- attention out / mlp down-projection:       row-sharded   ``P('model',None)``
- embeddings: vocab-sharded ``P('model',None)`` (tied lm_head → logits
  sharded over vocab; XLA all-gathers where needed)
- norms/biases: replicated; batch: sharded over ``'data'``

No shard_map needed — ``jax.jit`` with in/out shardings compiles one SPMD
program; collectives (all-reduce after row-sharded matmuls, all-gather on
logits) are inserted by the partitioner and ride ICI.

This composes with the simulator conceptually (a future mesh
('node','data','model')); here it stands alone for big-model training,
exposed as ``fit_tensor_parallel`` below and exercised by
``tests/test_tensor_parallel.py``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_tp_mesh(devices=None, dp: Optional[int] = None,
                 tp: Optional[int] = None) -> Mesh:
    """Build a [dp, tp] mesh. Defaults: tp = all devices, dp = 1."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if tp is None:
        tp = n if dp is None else n // dp
    if dp is None:
        dp = n // tp
    if dp * tp > n:
        raise ValueError(f"dp={dp}×tp={tp} > {n} devices")
    grid = np.asarray(devices[: dp * tp]).reshape(dp, tp)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def _spec_for_path(path: str, ndim: int) -> P:
    """Megatron-style sharding rule for a GPT param, by its tree path."""
    if "embedding" in path:               # wte [V, D] / wpe [T, D]
        # substring, not startswith: the pipeline layout prefixes paths
        # with "outer/" (gpt_pipeline_param_specs)
        if "wte" in path:
            return P(MODEL_AXIS, None)    # vocab-sharded (tied lm_head)
        return P()                        # wpe: small, replicate
    if ndim < 2:
        return P()                        # biases, norm scales
    if "c_attn" in path or "c_fc" in path:
        return P(None, MODEL_AXIS)        # column parallel
    if "c_proj" in path:
        return P(MODEL_AXIS, None)        # row parallel
    return P()


def _tree_paths(tree: PyTree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, _ in flat
    ]
    leaves = [v for _, v in flat]
    return paths, leaves, treedef


def gpt_param_specs(params: PyTree) -> PyTree:
    """Mesh-less ``PartitionSpec`` tree for a ``gym_tpu.models.nanogpt.GPT``
    param tree (Megatron rules above) — usable both as jit shardings (with a
    mesh) and as ``with_sharding_constraint`` specs inside the simulator's
    hybrid node×model program (``NodeRuntime.create(tp=...)``)."""
    paths, leaves, treedef = _tree_paths(params)
    return jax.tree_util.tree_unflatten(
        treedef,
        [_spec_for_path(p, getattr(x, "ndim", 0))
         for p, x in zip(paths, leaves)],
    )


def gpt_pipeline_param_specs(pipe_params: PyTree) -> PyTree:
    """Megatron specs for the PIPELINE param layout
    (``parallel/pipeline_model.py``: ``{"outer", "stages"}``): outer
    leaves take the plain rules; stage-stacked leaves ([S_tile, L/S, ...]
    per device) take the rule for their path with two leading ``None``
    dims prepended (the stage tile + per-stage layer axes are never
    tensor-sharded — ``'pipe'`` owns the stage axis)."""
    paths, leaves, treedef = _tree_paths(pipe_params)
    out = []
    for path, leaf in zip(paths, leaves):
        ndim = getattr(leaf, "ndim", 0)
        if path.startswith("stages/"):
            base = _spec_for_path(path, ndim - 2)
            out.append(P(None, None, *base) if len(base) else P())
        else:
            out.append(_spec_for_path(path, ndim))
    return jax.tree_util.tree_unflatten(treedef, out)


def gpt_param_shardings(params: PyTree, mesh: Mesh) -> PyTree:
    """NamedSharding tree for a `gym_tpu.models.nanogpt.GPT` param tree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), gpt_param_specs(params),
        is_leaf=lambda x: isinstance(x, P),
    )


def fit_tensor_parallel(
    model,
    params: PyTree,
    tx: optax.GradientTransformation,
    batch_iter,
    mesh: Mesh,
    steps: int,
) -> Tuple[PyTree, list]:
    """Minimal TP training loop: params sharded per `gpt_param_shardings`,
    batch sharded over the data axis, one jitted SPMD step.

    ``batch_iter`` yields ``(idx, targets)`` numpy arrays [B, T]."""
    p_shard = gpt_param_shardings(params, mesh)
    params = jax.device_put(params, p_shard)
    opt_state = jax.jit(
        tx.init, out_shardings=None
    )(params)
    b_shard = NamedSharding(mesh, P(DATA_AXIS, None))

    @jax.jit
    def step(params, opt_state, idx, tgt):
        def loss_fn(p):
            return model.apply({"params": p}, (idx, tgt), train=False)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    losses = []
    for _ in range(steps):
        idx, tgt = next(batch_iter)
        idx = jax.device_put(jnp.asarray(idx), b_shard)
        tgt = jax.device_put(jnp.asarray(tgt), b_shard)
        params, opt_state, loss = step(params, opt_state, idx, tgt)
        losses.append(float(loss))
    return params, losses
