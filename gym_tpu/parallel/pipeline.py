"""GPipe-style pipeline parallelism over a mesh axis.

The reference has no pipeline parallelism (SURVEY §2.3 ❌ row — its model is
fully replicated per process). This module is the TPU-native extension that
completes the parallelism suite (dp = node axis, tp = `tensor_parallel`,
cp = `ring_attention`, ZeRO = `strategy/zero_reduce`, pp = here).

Design: the classic fill-drain (GPipe) schedule expressed as ONE
`lax.scan` of ticks under `shard_map`, with `lax.ppermute` carrying
activations stage→stage over the ``pipe`` mesh axis. The backward pass is
NOT hand-written: reverse-mode autodiff of `scan` + `ppermute` *is* the
reverse pipeline (ppermute's transpose is the reversed permutation), so
gradients flow stage S−1 → 0 exactly like a hand-scheduled GPipe backward.
This is the compiler-friendly formulation the scaling-book recipe
recommends: annotate the data motion, let XLA schedule it on ICI.

SPMD notes:
- every stage executes `stage_fn` every tick (lockstep); the (S−1) bubble
  ticks do masked garbage compute instead of idling — same wall time, no
  divergent control flow for the compiler to fight;
- bubble fraction is (S−1)/(M+S−1) with M microbatches, the GPipe number;
- `stage_fn` must preserve activation shape (a transformer trunk does).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


PIPE_AXIS = "pipe"


def pipeline_apply(
    stage_fn: Callable[..., Any],
    stage_params: Any,
    xs: jnp.ndarray,
    n_stages: int,
    axis_name: str = PIPE_AXIS,
    replicate_out: bool = True,
    with_aux: bool = False,
) -> jnp.ndarray:
    """Run M microbatches through S = ``n_stages`` pipeline stages.

    Must be called inside ``shard_map`` over ``axis_name`` (size S), with
    ``stage_params`` already sharded to this device's stage (e.g. a
    stacked-layer tree whose leading stage axis the mesh consumed).

    ``stage_fn(stage_params, x, m_idx) -> y`` (or ``(y, aux)`` under
    ``with_aux``): ``m_idx`` is the index of the microbatch this stage is
    processing this tick — fold it into per-microbatch rng (dropout).
    During the (S−1) bubble ticks ``m_idx`` is clipped into [0, M−1] and
    the garbage compute is masked out of the output and the aux sum.

    ``xs``: [M, ...] microbatch activations fed to stage 0 (replicated on
    every stage; only stage 0 reads them). Returns [M, ...] — the last
    stage's outputs, shared to every stage via a masked ``psum`` so the
    caller can continue with replicated compute (loss head, logging).
    Under ``with_aux`` returns ``(out, aux_sum)`` where ``aux_sum`` is
    THIS STAGE's sum of per-microbatch aux scalars over its valid ticks
    (``psum`` it over the pipe axis for the model total — stage-local by
    design so the loss head can keep single-source gradient seeding).

    ``replicate_out=False`` skips that psum and returns each stage's raw
    output buffer — only the LAST stage's is meaningful. Use when the
    caller masks the downstream compute to the last stage anyway (the
    trainer's pipelined loss head does, so that replicated-parameter
    gradients can be combined with ONE psum over the pipe axis without
    double-counting the tied embedding: see
    ``train_node.make_pipeline_train_step``).
    """
    if lax.axis_size(axis_name) != n_stages:
        raise ValueError(
            f"pipe axis '{axis_name}' has size {lax.axis_size(axis_name)} "
            f"but n_stages={n_stages}: a mismatch would make the is_last "
            f"mask never fire and the masked psum return silent zeros")
    m = xs.shape[0]
    sid = lax.axis_index(axis_name)
    is_first = sid == 0
    is_last = sid == n_stages - 1
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        inbox, out, aux_sum = carry
        x0 = lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, m - 1), 0,
                                      keepdims=False)
        xin = jnp.where(is_first, x0, inbox)
        # microbatch index at this stage this tick (garbage during bubble
        # ticks, clipped so rng folding stays in range)
        m_idx = jnp.clip(t - sid, 0, m - 1)
        res = stage_fn(stage_params, xin, m_idx)
        if with_aux:
            y, aux = res
            valid = jnp.logical_and(t >= sid, t - sid <= m - 1)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        else:
            y = res
        # the microbatch leaving the LAST stage at tick t is t-(S-1)
        widx = jnp.clip(t - (n_stages - 1), 0, m - 1)
        prev = lax.dynamic_index_in_dim(out, widx, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(t >= n_stages - 1, y, prev), widx, 0)
        inbox = lax.ppermute(y, axis_name, fwd)
        return (inbox, out, aux_sum), None

    # the carry is stage-varying (each stage holds different activations):
    # mark the zero init as varying over the pipe axis or the scan's carry
    # typing rejects it
    def _vary(x):
        return lax.pcast(x, (axis_name,), to="varying")

    out0 = _vary(jnp.zeros_like(xs))
    inbox0 = _vary(jnp.zeros_like(xs[0]))
    aux0 = _vary(jnp.zeros((), jnp.float32))
    (_, out, aux_sum), _ = lax.scan(tick, (inbox0, out0, aux0),
                                    jnp.arange(m + n_stages - 1))
    if replicate_out:
        # only the last stage holds real outputs; share them everywhere
        out = lax.psum(jnp.where(is_last, out, jnp.zeros_like(out)),
                       axis_name)
    return (out, aux_sum) if with_aux else out


def take_stage(stage_params: Any) -> Any:
    """Inside ``shard_map`` a `P('pipe')`-sharded stacked tree arrives with
    a leading stage axis of length 1 — squeeze it to get THIS device's
    stage. Use this instead of hand-rolled ``x[0]`` maps: forgetting the
    squeeze (or stacking for a different S than the mesh) is the
    silent-zeros foot-gun `pipeline_apply`'s axis-size assert guards."""
    return jax.tree.map(lambda x: jnp.squeeze(x, 0), stage_params)


def stack_stage_params(per_layer_params: list, n_stages: int) -> Any:
    """[L identical-structure layer trees] → one tree with leading axes
    [S, L/S, ...] — shard axis 0 over the ``pipe`` mesh axis and each
    stage scans axis 1 (`apply_stage_layers`)."""
    n_layer = len(per_layer_params)
    if n_layer % n_stages != 0:
        raise ValueError(
            f"n_layer={n_layer} not divisible by n_stages={n_stages}")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer_params)
    return jax.tree.map(
        lambda x: x.reshape((n_stages, n_layer // n_stages) + x.shape[1:]),
        stacked,
    )


def apply_stage_layers(layer_fn: Callable[..., jnp.ndarray],
                       stage_params: Any, x: jnp.ndarray) -> jnp.ndarray:
    """Apply a stage's stacked layers ([L/S, ...] leading axis) in order —
    a `lax.scan` so the stage compiles once regardless of depth.
    ``layer_fn(layer_params, h, li)``: ``li`` is the layer's index WITHIN
    the stage (traced int32 — fold into per-layer rng for dropout)."""
    n_local = jax.tree.leaves(stage_params)[0].shape[0]

    def body(h, inp):
        li, layer_params = inp
        return layer_fn(layer_params, h, li), None

    out, _ = lax.scan(body, x, (jnp.arange(n_local), stage_params))
    return out
