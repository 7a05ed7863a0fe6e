#!/usr/bin/env python3
"""Ahead-of-time compile of a cell's programs for a described ``v5e:2x2``,
at full width, with no chip (``on-chip-measurement`` guide, section 2.3):

    JAX_PLATFORMS=cpu python3 perfbench/aot.py [--workload <name>]

What the chip's compiler would refuse (a program that does not fit 16 GB,
a kernel it cannot tile) it refuses here, at no chip time. Nothing runs:
a compile that passes is NOT a chip run and this prints no measurement,
only the compiler's own memory analysis and whether the Pallas kernel and
the cross-chip collectives are in the program.

Training cells: the step program exactly as ``Trainer.fit`` builds it
(``NodeRuntime`` over the described devices, ``make_init_fn`` for shapes,
``make_train_step``). The one platform check inside the ops
(``flash_attention._on_tpu``) is steered here, as ``tests/
test_chip_compile.py`` does. The served cell: the engine's decode program
and largest prefill bucket through the registry's own program
definitions.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_fit(spec, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from gym_tpu.models.base import LossModel
    from gym_tpu.models.nanogpt import GPT
    from gym_tpu.ops import flash_attention
    from gym_tpu.parallel.mesh import NodeRuntime
    from gym_tpu.train_node import make_init_fn, make_train_step
    from perfbench.kinds import fit

    flash_attention._on_tpu = lambda: True      # the chip is described
    t, sizes = spec["traffic"], spec["config"]
    k, b, seq = t["num_nodes"], t["batch_size"], sizes["n_positions"]
    devices = list(topo.devices)[:1 if t["placement"] == "fold" else
                                 int(spec["cell"]["chips"])]
    runtime = NodeRuntime.create(k, devices)
    model = LossModel(GPT(fit.gpt_config(sizes, t)),
                      jnp.bfloat16 if t["autocast"] else None)
    strategy = fit.make_strategy(t["strategy"])
    strategy.finalize(1000)
    micro = (jnp.zeros((b, seq), jnp.int32),) * 2
    init_fn = make_init_fn(model, strategy, micro, 0, None, ctx=runtime.ctx)
    init = runtime.compile(lambda _: init_fn(runtime.ctx.node_index()),
                           donate_state=False)
    node = runtime.node_sharding
    state = jax.eval_shape(
        init, jax.ShapeDtypeStruct((k,), jnp.int32, sharding=node))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=node),
        state)
    batch = (jax.ShapeDtypeStruct((k, 1, b, seq), jnp.int32,
                                  sharding=node),) * 2
    step = runtime.compile(
        make_train_step(model, strategy, runtime.ctx, None, False),
        donate_batch=True)
    t0 = time.monotonic()
    compiled = step.lower(state, batch).compile()
    return report(compiled, time.monotonic() - t0, len(devices))


def compile_closed(spec, topo) -> dict:
    """The decode step and the largest prefill bucket at the cell's slot
    count, built by the program's own definitions for one described
    chip."""
    import dataclasses
    import jax
    from jax.sharding import SingleDeviceSharding
    from gym_tpu.models.nanogpt import GPTConfig, decode_config
    from gym_tpu.programs import serve_defs
    from perfbench import weights

    t, sizes = spec["traffic"], spec["config"]
    chip = SingleDeviceSharding(topo.devices[0])
    slots, page = int(t["num_slots"]), int(t["page_size"])
    cfg = dataclasses.replace(
        decode_config(GPTConfig(
            block_size=sizes["n_positions"], vocab_size=sizes["vocab_size"],
            n_layer=sizes["n_layer"], n_head=sizes["n_head"],
            n_embd=sizes["n_embd"],
            dropout=weights.dropout_rate(sizes))),
        page_size=page,
        # the engine's default pool: null page, one window a slot, one spare
        kv_pages=2 + slots * (sizes["n_positions"] // page))
    cfg_tuple = dataclasses.astuple(cfg)
    defs = {"decode": serve_defs.paged_decode_def(
                cfg_tuple, slots, int(t["decode_chunk"])),
            "prefill_largest_bucket": serve_defs.paged_prefill_def(
                cfg_tuple, sizes["n_positions"])}
    out = {}
    for name, pdef in defs.items():
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            pdef.args)
        t0 = time.monotonic()
        compiled = pdef.builder().lower(*args).compile()
        out[name] = report(compiled, time.monotonic() - t0, 1)
    return out


def report(compiled, seconds: float, chips: int) -> dict:
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "compiled_for": f"described v5e:2x2, {chips} chip(s); nothing ran",
        "compile_s_on_this_cpu": round(seconds, 1),
        "per_chip_bytes": {"arguments": mem.argument_size_in_bytes,
                           "outputs": mem.output_size_in_bytes,
                           "temporaries": mem.temp_size_in_bytes,
                           "aliased": mem.alias_size_in_bytes,
                           "live_GiB": round(live / 2 ** 30, 2)},
        "pallas_kernel_in_program": "tpu_custom_call" in text,
        "cross_chip_collectives": len(re.findall(
            r"\b(all-reduce|all-gather|reduce-scatter|collective-permute)"
            r"(-start)?\(", text)),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=None)
    a = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from perfbench import harness
    # a compile for a described device is written to the cache but cannot
    # be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = [a.workload] if a.workload else [w["name"]
                                             for w in bench["workloads"]]
    failed = 0
    for name in names:
        spec = harness.load_cell(name)
        try:
            fn = (compile_fit if spec["traffic"]["kind"] == "fit"
                  else compile_closed)
            print(json.dumps({"workload": name, "aot": fn(spec, topo)}),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            failed += 1
            print(json.dumps({"workload": name, "refused":
                              f"{type(e).__name__}: {e}"[:1500]}),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
