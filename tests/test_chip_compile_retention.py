"""The served programs of the power-retention decoder (``models/brumby.py``)
compiled for a described v5e at the benchmark cell's sizes, without the
chip: that they fit, that the decode step's pass over the state is the
Pallas kernel in place on the donated pool, that nothing of a state
array's size is moved beside it, and that a prefill's chunks read the
state through their kernel with no ``phi(Q)`` written out.
``tests/test_chip_compile.py``'s rule for the page pools, for the pool of
state blocks; a file of its own so that the two compiles (a minute each)
run beside that file's five minutes and not after them."""

import math
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from gym_tpu.ops import paged_attention

from _hlo import compile_def, ops_by_gate


@pytest.fixture(scope="module")
def v5e_chip():
    """Sharding on one chip of a described ``v5e:2x2`` host. The compile
    cache is off around the module: a compile for a described device is
    written to it but cannot be read back without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# the Brumby cell as served (perfbench/configs/brumby-14b-base.json,
# perfbench/traffic/serve-closed-longgen.json): 8 layers, the whole
# vocabulary, bfloat16 weights, a float32 state of 8 x 128 x 8,320 (+ 8 x
# 8,320) a layer and row, 16 slots, rows of 20,480 positions, 18 blocks
def _brumby_cfg(kv_pages=18):
    import dataclasses
    from gym_tpu.models.brumby import BrumbyConfig
    return dataclasses.replace(
        BrumbyConfig(num_hidden_layers=8, block_size=20480).decode_config(),
        page_size=20480, kv_pages=kv_pages)


@pytest.fixture(scope="module")
def brumby_served(v5e_chip):
    """``compiled(program) -> (config, Compiled)``: ``decode`` or
    ``prefill<bucket>`` as the cell's engine compiles it (16 slots, the
    Pallas state pass), each compiled once for the module."""
    import functools
    from gym_tpu.ops import power_retention
    from gym_tpu.programs import serve_defs
    cfg = _brumby_cfg()
    key = cfg.program_key()

    @functools.cache
    def compiled(program):
        pdef = (serve_defs.paged_decode_def(key, 16, 1)
                if program == "decode"
                else serve_defs.paged_prefill_def(key, int(program[7:]), 16))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(power_retention, "_on_tpu", lambda: True)
            return cfg, compile_def(pdef, v5e_chip)

    return compiled


@pytest.mark.parametrize("program", ["decode", "prefill16384"])
def test_brumby_programs_fit_the_chip_and_move_no_state(brumby_served,
                                                        program):
    """The decode program and the longest prefill bucket as the cell's
    engine compiles them: arguments (8.4 GB of weights, 4.95 GB of
    state), outputs and temporaries stay under 15.0 GiB of the chip's
    15.75 (ISSUE 33's line; a prefill a layer at a time over the whole
    bucket asked 17.5 GB: it runs 2,048 positions at a time through all
    layers). The decode step's pass over the state is the Pallas kernel,
    in place on the donated pool, and besides it no float32 instruction's
    result is as large as ONE row's block of a layer (8.5 M elements):
    no copy, convert, transpose, slice, update or scatter of a state
    array. A prefill takes the row's block out and puts it back (one
    block, by design) and moves nothing of a pool's size."""
    import re
    from gym_tpu.ops import power_retention
    cfg, compiled = brumby_served(program)
    assert set(cfg.attend_paths()) == {paged_attention.RETENTION}
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15.0 * 1024 ** 3, (total / 2 ** 30, mem)
    hlo = compiled.as_text()
    assert ("retention_state_decode" in hlo) == (program == "decode")
    D = power_retention.feature_dim(128)
    block = 8 * 128 * D
    # decode: any float32 result of a block's size; a prefill: a result
    # in the state's own shape as large as a pool, but for the update in
    # place that puts the row's block back
    moved = ("copy|gather|transpose|scatter|dynamic-slice|convert"
             + ("|dynamic-update-slice" if program == "decode" else ""))
    shape = r"[\d,]+" if program == "decode" else rf"[\d,]*8,128,{D}"
    least = block if program == "decode" else cfg.kv_pages * block
    big = []
    for m in re.finditer(rf"= f32\[({shape})\]\S* ({moved})\(", hlo):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n >= least:
            big.append(m.group(0))
    assert not big, big[:3]
    pool = f"f32[{cfg.kv_pages},8,128,{D}]"
    assert hlo.count(pool) >= 8
    assert re.search(r"input_output_alias=\{.*may-alias", hlo)


def test_brumby_prefill_reads_the_state_without_writing_phi_q(brumby_served):
    """The longest prefill bucket: each layer's chunk loop reads the
    state through the Pallas kernel ``retention_prefill_read``, which
    builds ``phi(Q)`` a lane tile at a time in VMEM, and no instruction
    of the program writes an array along the feature axis (a minor
    dimension of 8,320, or 16,640 for the lanes beside their rotations)
    as large as ``phi(Q)`` of a chunk (``KV x G x C x D`` elements, 85 MB
    in bfloat16) but the pools themselves: the key side's ``[8, 128,
    8,320]`` and ``[8, 128, 16,640]`` stay (34 and 68 MB in float32)."""
    import re
    from gym_tpu.ops import power_retention
    cfg, compiled = brumby_served("prefill16384")
    hlo = compiled.as_text()
    assert "retention_prefill_read" in hlo
    D = power_retention.feature_dim(cfg.head_dim)
    least = cfg.num_attention_heads * cfg.retention_chunk * D
    pool = f"{cfg.kv_pages},{cfg.num_key_value_heads},{cfg.head_dim},{D}"
    big = set()
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* [\w\-]+\(", hlo):
        dims = [int(d) for d in m.group(1).split(",")]
        if (dims[-1] in (D, 2 * D) and math.prod(dims) >= least
                and m.group(1) != pool):
            big.add(m.group(0))
    assert not big, sorted(big)[:3]
    # the key side is still there to be found: the check can see
    assert re.search(rf"= f32\[[\d,]*{cfg.retention_chunk},{2 * D}\]", hlo)


@pytest.mark.parametrize("program", ["decode", "prefill16384"])
def test_brumby_programs_sort_only_inside_the_samplers_conditional(
        brumby_served, program):
    """The cell whose round is the device's step, at the published
    vocabulary: both sorts of ``[16, 151936]`` (7.35 of the step's 32.8
    ms while every step ran them) sit in one branch of the sampler's
    ``conditional``, none at the step's top level
    (``tests/test_chip_compile.py`` has the GPT-2 cell's programs and
    what a batched gate compiles to)."""
    _cfg, compiled = brumby_served(program)
    hlo = compiled.as_text()
    assert ops_by_gate(hlo, "sort") == (2, 0)
