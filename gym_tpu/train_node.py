"""Compiled per-node training/eval step builders.

The reference's ``TrainNode`` (``exogym/train_node.py``) is a Python hot loop:
grad-accum microbatches, grad rescale, ``strategy.step()``, per-step barrier.
Here the whole per-step computation is one traced function compiled once over
the node mesh; grad accumulation is a ``lax.scan`` over microbatches
(keeps the MXU fed without re-tracing), and the barrier disappears — SPMD
programs are lockstep by construction.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flax.struct
import jax
import jax.numpy as jnp

from .models.base import LossModel
from .parallel.axis import AxisCtx
from .strategy.base import Strategy

PyTree = Any


@flax.struct.dataclass
class TrainState:
    params: PyTree
    model_state: PyTree          # non-param collections (batch_stats, ...)
    strategy_state: PyTree
    step: jnp.ndarray            # int32 scalar
    rng: jax.Array               # per-node PRNG key


def constrain_params(params: PyTree, param_specs) -> PyTree:
    """Apply tensor-parallel ``with_sharding_constraint`` specs (a mesh-less
    PartitionSpec tree, e.g. ``tensor_parallel.gpt_param_specs``) — no-op
    when ``param_specs`` is None. Used under the hybrid node-manual /
    model-auto program: GSPMD partitions the annotated matmuls and inserts
    the Megatron collectives."""
    if param_specs is None:
        return params
    import jax.sharding as shd
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s),
        params, param_specs,
        is_leaf=lambda x: isinstance(x, shd.PartitionSpec),
    )


def make_init_fn(loss_model: LossModel, strategy: Strategy, example_micro,
                 seed: int, param_specs=None, ctx: AxisCtx = None,
                 init_params=None):
    """Per-node state init. Params are built from the *same* seed on every
    node — replicas start identical by determinism, replacing the reference's
    initial broadcast from rank 0 (``train_node.py:101-104``). The dropout/
    data RNG is folded with the node index so noise decorrelates across
    nodes.

    ``ctx``: pass ``runtime.ctx`` for strategies whose state layout depends
    on the mesh (ZeRO sharding); harmless otherwise.

    ``init_params``: start from THESE weights instead of the seed init —
    the analog of the reference training whatever weights the passed
    ``nn.Module`` instance holds (fine-tuning, ported checkpoints,
    identical-init comparisons). Tree structure must match the model's."""
    if ctx is not None:
        strategy.bind_ctx(ctx)

    def init_fn(node_index: jnp.ndarray) -> TrainState:
        base = jax.random.PRNGKey(seed)
        params, model_state = loss_model.init(base, example_micro)
        if init_params is not None:
            params = jax.tree.map(
                lambda ref, given: jnp.asarray(given, ref.dtype),
                params, init_params)
        params = constrain_params(params, param_specs)
        return TrainState(
            params=params,
            model_state=model_state,
            strategy_state=strategy.init(params),
            step=jnp.zeros((), jnp.int32),
            rng=jax.random.fold_in(base, node_index + 1),
        )

    return init_fn


def make_train_step(loss_model: LossModel, strategy: Strategy, ctx: AxisCtx,
                    param_specs=None, skip_nonfinite: bool = False):
    """Build ``node_step(state, batch) -> (state, metrics)``.

    ``batch`` leaves are [n_micro, micro_bs, ...]; the scan accumulates
    gradients and the sum is rescaled by n_micro, matching the reference's
    grad-accumulation loop and rescale (``train_node.py:157-171``).

    ``param_specs``: tensor-parallel sharding constraints (see
    ``constrain_params``); applied to params at step entry and exit so the
    whole state (grads, opt state) inherits the Megatron layout.

    ``skip_nonfinite``: failure detection + containment (beyond-reference,
    SURVEY §5.3 — the reference has none): a node whose loss or gradients
    go non-finite this step contributes ZERO gradient instead, so one
    diverged replica cannot poison the collective mean; the event is
    surfaced as ``metrics['nonfinite']`` (per-node 0/1) for the logger.
    Recovery is checkpoint/resume (SURVEY §5.4).
    """

    def node_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if param_specs is not None:
            state = state.replace(
                params=constrain_params(state.params, param_specs)
            )
        # one counter a fold, unbatched under its vmap: the strategies'
        # gates on it stay conditionals (AxisCtx.fold_counter)
        step = ctx.fold_counter(state.step)
        step_rng = jax.random.fold_in(state.rng, step)
        if ctx.seq_axes:
            # decorrelate dropout across a node's sequence chunks
            step_rng = jax.random.fold_in(step_rng, ctx.seq_index())
        n_micro = jax.tree.leaves(batch)[0].shape[0]

        grad_fn = jax.value_and_grad(loss_model.loss, has_aux=True)

        def micro(carry, mb):
            model_state, gsum, lsum, i = carry
            (loss, new_ms), g = grad_fn(
                state.params, model_state, mb,
                jax.random.fold_in(step_rng, i), True,
            )
            gsum = jax.tree.map(jnp.add, gsum, g)
            return (new_ms, gsum, lsum + loss, i + 1), None

        gzero = jax.tree.map(jnp.zeros_like, state.params)
        # scopes name the step's parts in a device trace (metadata only)
        with jax.named_scope("fwd_bwd"):
            (model_state, gsum, lsum, _), _ = jax.lax.scan(
                micro, (state.model_state, gzero, jnp.zeros(()), 0), batch
            )
        # Context parallelism: a seq-sharded model returns the *global* loss
        # (psum'd in-model) but each seq device's backward pass carries only
        # its chunk's gradient contribution — combine them here.
        gsum = ctx.seq_psum(gsum)
        grads = jax.tree.map(lambda g: g / n_micro, gsum)
        loss = lsum / n_micro

        if skip_nonfinite:
            ok = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
            # quarantine: zero the whole gradient (select, not multiply —
            # NaN·0 is NaN) so this node's divergence can't poison the
            # collective mean in strategy.step
            grads = jax.tree.map(
                lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads
            )

        with jax.named_scope("strategy"):
            params, sstate, metrics = strategy.step(
                grads, state.params, state.strategy_state, step, ctx
            )
        params = constrain_params(params, param_specs)
        new_state = state.replace(
            params=params,
            model_state=model_state,
            strategy_state=sstate,
            step=step + 1,
        )
        metrics = dict(metrics)
        metrics["loss"] = loss
        if skip_nonfinite:
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
        return new_state, metrics

    return node_step


def make_multi_train_step(loss_model: LossModel, strategy: Strategy,
                          ctx: AxisCtx, param_specs=None,
                          skip_nonfinite: bool = False):
    """S training steps per dispatch: ``node_multi(state, batches)`` where
    batch leaves are [S, n_micro, micro_bs, ...]; returns metrics with a
    leading [S] axis.

    TPU-native throughput lever with no reference analog: host→device
    dispatch latency is amortized over
    S compiled steps chained by ``lax.scan``, keeping the chip busy
    back-to-back. Semantics are identical to S single dispatches — the
    per-step strategy schedule (H gates, step counter) advances inside the
    scan.
    """
    node_step = make_train_step(loss_model, strategy, ctx, param_specs,
                                skip_nonfinite)

    return scan_steps(node_step, ctx)


def scan_steps(node_step, ctx: AxisCtx):
    """``node_step`` chained over a leading [S] axis of batches. The step
    counter enters the scan's carry as the fold's one value
    (``AxisCtx.fold_counter``), so it rides the carry unbatched and every
    iteration's gate is a conditional, as in a single dispatch."""

    def node_multi(state: TrainState, batches):
        state = state.replace(step=ctx.fold_counter(state.step))
        return jax.lax.scan(node_step, state, batches)

    return node_multi


def _static_index_ctx(ctx: AxisCtx) -> AxisCtx:
    """Shape-inference twin of an AxisCtx: ``node_index`` pinned to 0 so
    strategy inits that slice by node index (DiLoCo ``shard_outer``) can
    be traced OUTSIDE the mesh program (``jax.eval_shape`` for the
    pipeline state specs), where ``lax.axis_index`` is unbound. State
    SHAPES don't depend on the index, which is all the shape pass reads."""
    import dataclasses

    class _Static(type(ctx)):
        def node_index(self):
            return jnp.zeros((), jnp.int32)

    return _Static(**dataclasses.asdict(ctx))


def make_pipeline_init_fn(pipe_model, strategy: Strategy, example_micro,
                          seed: int, ctx: AxisCtx = None,
                          static_stage=None, param_specs=None,
                          init_params=None):
    """Per-node init for the pipelined model (``parallel/pipeline_model``):
    same seed ⇒ same full-model weights as a ``pp=1`` run, each device
    keeping its own stage slice. ``static_stage`` pins the slice for
    shape inference (``jax.eval_shape``) outside the mesh program.
    ``param_specs`` (pp×tp): Megatron constraints applied BEFORE
    ``strategy.init`` so the whole state inherits the 'model'-axis layout
    from the start — same contract as ``make_init_fn``."""
    if ctx is not None:
        strategy.bind_ctx(ctx if static_stage is None
                          else _static_index_ctx(ctx))

    def init_fn(node_index: jnp.ndarray) -> TrainState:
        base = jax.random.PRNGKey(seed)
        params, model_state = pipe_model.init(base, example_micro,
                                              static_stage=static_stage,
                                              init_params=init_params)
        params = constrain_params(params, param_specs)
        return TrainState(
            params=params,
            model_state=model_state,
            strategy_state=strategy.init(params),
            step=jnp.zeros((), jnp.int32),
            rng=jax.random.fold_in(base, node_index + 1),
        )

    return init_fn


def make_pipeline_train_step(pipe_model, strategy: Strategy, ctx: AxisCtx,
                             skip_nonfinite: bool = False,
                             param_specs=None):
    """Pipelined ``node_step``: the grad-accum microbatches [n_micro, ...]
    are consumed in ONE ``pipe_loss`` call — they are the GPipe schedule's
    M — and the backward pass is autodiff of the schedule. Gradients of
    stage params stay stage-local; gradients of the replicated "outer"
    params (embeddings: stage 0; tied head: stage S−1) are combined with
    one ``pp_psum``. Everything downstream (strategy collectives over the
    node axes, metrics) is unchanged — pipeline composes with any
    tree-mapped strategy.

    ``param_specs``: Megatron constraints for the pipeline layout
    (``tensor_parallel.gpt_pipeline_param_specs``) — the pp×tp
    composition: stages stay manual over 'pipe' while GSPMD shards each
    stage's matmuls over the auto 'model' axis."""

    def node_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if param_specs is not None:
            state = state.replace(
                params=constrain_params(state.params, param_specs))
        step = ctx.fold_counter(state.step)  # as in make_train_step
        step_rng = jax.random.fold_in(state.rng, step)
        if ctx.seq_axes:
            # decorrelate dropout across a node's sequence chunks (same
            # contract as make_train_step — without it, pp×cp×dropout
            # would draw identical masks on every chunk)
            step_rng = jax.random.fold_in(step_rng, ctx.seq_index())

        def loss_fn(params):
            # the LOCAL masked loss: single-source gradient seed (see
            # pipe_loss_local's docstring)
            return pipe_model.pipe_loss_local(params, state.model_state,
                                              batch, step_rng, True)

        (loss_local, model_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        loss = jax.lax.psum(loss_local, ctx.pp_axes)  # replicated metric
        # cp composition: each seq device's backward carries only its
        # token chunk's contribution — combine, same as make_train_step
        grads = ctx.seq_psum(grads)
        grads = {"outer": ctx.pp_psum(grads["outer"]),
                 "stages": grads["stages"]}

        if skip_nonfinite:
            ok = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
            # one quarantine decision PER NODE: stage-local grads differ
            # per pipe device, so a stage-local NaN must zero the grads on
            # EVERY stage of that node — a split decision would desync the
            # replicated outer params across the pipe group forever
            if ctx.pp_axes:
                ok = jax.lax.psum(ok.astype(jnp.float32),
                                  ctx.pp_axes) >= float(ctx.pp)
            grads = jax.tree.map(
                lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads
            )

        params, sstate, metrics = strategy.step(
            grads, state.params, state.strategy_state, step, ctx
        )
        params = constrain_params(params, param_specs)
        new_state = state.replace(
            params=params,
            model_state=model_state,
            strategy_state=sstate,
            step=step + 1,
        )
        metrics = dict(metrics)
        metrics["loss"] = loss
        if skip_nonfinite:
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
        return new_state, metrics

    return node_step


def make_pipeline_eval_step(pipe_model, ctx: AxisCtx):
    """Pipelined local/global eval — the same observable pair as
    ``make_eval_step``, with the forward pass through the schedule."""

    def node_eval(state: TrainState, batch):
        avg_params = ctx.pmean(state.params)
        dummy_rng = jax.random.PRNGKey(0)
        l_loc, _ = pipe_model.pipe_loss(
            state.params, state.model_state, batch, dummy_rng, False)
        l_glob, _ = pipe_model.pipe_loss(
            avg_params, state.model_state, batch, dummy_rng, False)
        return l_loc, l_glob

    return node_eval


def make_eval_step(loss_model: LossModel, ctx: AxisCtx):
    """Build ``node_eval(state, batch) -> (local_loss, global_loss)``.

    Reference protocol (``train_node.py:181-246``): rank 0 evaluates its own
    replica ("local"), rank 1 evaluates the node-averaged model ("global").
    SPMD version: every node computes both — local loss of its own params and
    loss of ``pmean(params)`` — on its own val stream; the trainer logs
    local[0] and global[min(1, K-1)], preserving the reference's observable.
    Buffers (batch_stats) stay local, as in the reference (only
    ``named_parameters`` are all_reduced, ``train_node.py:187-189``).
    """

    def node_eval(state: TrainState, batch):
        avg_params = ctx.pmean(state.params)
        dummy_rng = jax.random.PRNGKey(0)

        def body(carry, mb):
            l_loc, l_glob = carry
            loc, _ = loss_model.loss(
                state.params, state.model_state, mb, dummy_rng, False
            )
            glob, _ = loss_model.loss(
                avg_params, state.model_state, mb, dummy_rng, False
            )
            return (l_loc + loc, l_glob + glob), None

        n = jax.tree.leaves(batch)[0].shape[0]
        (l_loc, l_glob), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(())), batch
        )
        return l_loc / n, l_glob / n

    return node_eval
