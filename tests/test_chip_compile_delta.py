"""The served programs of the hybrid decoder (``models/qwen3_next.py``:
three gated delta-rule layers to one gated softmax-attention layer)
compiled for a described v5e at the benchmark cell's sizes, without the
chip: that they fit, that the decode step's pass over the state is the
Pallas kernel and its attend the Pallas page walk, and that the pools of
both kinds are written in place with nothing of a pool's size moved beside
them. ``tests/test_chip_compile_latent.py``'s rule, for a row that holds a
state block beside its pages; a file of its own so that its compiles run
beside the others' and not after them."""

import os
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from _hlo import compile_def


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


# the cell as served (perfbench/configs/qwen3-next-80b-a3b.json,
# perfbench/traffic/serve-closed-longctx.json): two periods of the pattern
# at the published widths, 128 of 512 experts and 37,984 rows of
# vocabulary held, bfloat16 weights and pages, a float32 state, 32 slots,
# rows of 50,688 positions, 65,536 pages of 16 and 34 state blocks
PAGES, SLOTS, BLOCKS = 65536, 32, 34


def _cfg():
    import dataclasses
    from gym_tpu.models.qwen3_next import Qwen3NextConfig
    return dataclasses.replace(
        Qwen3NextConfig(vocab_size=37984, num_hidden_layers=8,
                        held_experts=(0, 128)).decode_config(),
        page_size=16, kv_pages=PAGES, state_blocks=BLOCKS)


@pytest.fixture(scope="module")
def served(v5e_chip):
    """``compiled(program) -> (config, Compiled)``: ``decode`` or
    ``prefill<bucket>`` as the cell's engine compiles it (32 slots, the
    Pallas kernels), each compiled once for the module."""
    import functools
    from gym_tpu.ops import gated_delta, paged_attention
    from gym_tpu.programs import serve_defs
    cfg = _cfg()
    key = cfg.program_key()

    @functools.cache
    def compiled(program):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(gated_delta, "_on_tpu", lambda: True)
            mp.setattr(paged_attention, "_on_tpu", lambda: True)
            pdef = (serve_defs.paged_decode_def(key, SLOTS, 1)
                    if program == "decode" else
                    serve_defs.paged_prefill_def(key, int(program[7:]),
                                                 SLOTS))
            return cfg, compile_def(pdef, v5e_chip)

    return compiled


@pytest.mark.parametrize("program", ["decode", "prefill50688"])
def test_programs_fit_the_chip_and_move_no_pool(served, program):
    """The decode program and the longest prefill bucket (the row's whole
    extent, 18 passes of 2,816 positions) as the cell's engine compiles
    them: arguments (7.3 GB of weights, 4.3 GB of pages, 0.44 GB of state
    blocks), outputs and temporaries stay under 15.0 GiB of the chip's
    15.75. The decode step's state pass is the Pallas kernel
    (``gated_delta_state_decode``) and both programs' attend the grouped
    page walk. Every pool, of pages or of state blocks, is aliased from
    argument to result, and no copy, transpose, convert or gather has a
    pool's shape."""
    from gym_tpu.ops.gated_delta import GATED_DELTA
    cfg, compiled = served(program)
    assert set(cfg.attend_paths()) == {GATED_DELTA, "gather"}  # off the chip
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15.0 * 1024 ** 3, (total / 2 ** 30, mem)
    print(program, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    hlo = compiled.as_text()
    assert ("gated_delta_state_decode" in hlo) == (program == "decode")
    assert ("paged_gqa_decode_full" in hlo) == (program == "decode")
    assert ("paged_gqa_prefill_full" in hlo) == (program != "decode")
    # (the three convolution inputs a row, [34, 3, 8192], are 1.6 MB a
    # layer: XLA may lay them out anew around their scatter)
    pools = (rf"bf16\[{PAGES},16,512\]", rf"f32\[{BLOCKS},32,128,128\]")
    for pool in pools:
        moved = re.findall(
            rf"= {pool}\S* (copy|transpose|convert|gather|copy-start)\(",
            hlo)
        assert not moved, (pool, moved[:3])
    # 2 x 2 page pools and 6 x 2 state pools come back in their arguments'
    # buffers
    assert len(re.findall(r"may-alias", hlo.split("\n", 1)[0])) >= 16
