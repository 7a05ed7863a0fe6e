"""The served programs of the latent-attention decoder
(``models/kimi_k2.py``) compiled for a described v5e at the benchmark
cell's sizes, without the chip: that they fit, that the decode step's
attend is the Pallas walk of the live pages and the prefill's the flash
kernel, that the pools are written in place and nothing of a pool's size
is moved beside them, and WHY a pool row is 640 lanes and not 576.
``tests/test_chip_compile.py``'s rule for the page pools, for the pool of
latents; a file of its own so that its compiles (half a minute each) run
beside that file's five minutes and not after them."""

import os
import re

import jax
import jax.numpy as jnp
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


# the Kimi cell as served (perfbench/configs/kimi-k2.7-code.json,
# perfbench/traffic/serve-closed-repo.json): 1 dense + 4 expert layers at
# the published widths, 12 of 384 experts and 20,480 rows of vocabulary
# held, bfloat16 weights and pages, 32 slots, rows of 36,864 positions,
# 49,152 pages of 16 positions x 640 lanes
PAGES, SLOTS = 49152, 32


def _kimi_cfg():
    import dataclasses
    from gym_tpu.models.kimi_k2 import KimiK2Config
    return dataclasses.replace(
        KimiK2Config(vocab_size=20480, num_hidden_layers=5,
                     held_experts=(0, 12)).decode_config(),
        page_size=16, kv_pages=PAGES)


@pytest.fixture(scope="module")
def kimi_served(v5e_chip):
    """``compiled(program) -> (config, Compiled)``: ``decode`` or
    ``prefill<bucket>`` as the cell's engine compiles it (32 slots, the
    Pallas kernels), each compiled once for the module."""
    import functools
    from gym_tpu.ops import latent_attention
    from gym_tpu.programs import serve_defs
    cfg = _kimi_cfg()
    key = cfg.program_key()

    @functools.cache
    def compiled(program):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(latent_attention, "_on_tpu", lambda: True)
            pdef = (serve_defs.paged_decode_def(key, SLOTS, 1)
                    if program == "decode" else
                    serve_defs.paged_prefill_def(key, int(program[7:]),
                                                 SLOTS))
            return cfg, compile_def(pdef, v5e_chip)

    return compiled


@pytest.mark.parametrize("program", ["decode", "prefill32768"])
def test_kimi_programs_fit_the_chip_and_move_no_pool(kimi_served, program):
    """The decode program and the longest prefill bucket as the cell's
    engine compiles them: arguments (7.0 GB of weights, 5.0 GB of pages),
    outputs and temporaries stay under 15.0 GiB of the chip's 15.75 (a
    32,768-token bucket a layer at a time would hold 2.4 GB of the dense
    layer's gate and up alone: it runs 4,096 positions at a time through
    all layers, and its largest temporaries are the 1.2 GB of one pass's
    expanded keys and values). The decode step's attend is the Pallas
    walk (``latent_paged_decode``), the prefill's the flash kernel
    (``latent_prefill``). Every layer's pool is one array of 640 lanes a
    row in the default tiled layout, aliased from argument to result,
    and no copy, transpose, convert or gather has a pool's shape."""
    from gym_tpu.ops import latent_attention as la
    cfg, compiled = kimi_served(program)
    assert set(cfg.attend_paths()) == {la.LATENT_GATHER}   # off the chip
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15.0 * 1024 ** 3, (total / 2 ** 30, mem)
    hlo = compiled.as_text()
    assert ("latent_paged_decode" in hlo) == (program == "decode")
    assert ("latent_prefill" in hlo) == (program != "decode")
    pool = rf"bf16\[{PAGES},16,640\]"
    layouts = set(re.findall(pool + r"(\{[^}]*\})", hlo))
    assert layouts <= {"{2,1,0:T(8,128)(2,1)}", "{2,1,0}"}, layouts
    moved = re.findall(
        rf"= {pool}\S* (copy|transpose|convert|gather|copy-start)\(", hlo)
    assert not moved, moved[:3]
    assert len(re.findall(r"may-alias", hlo.split("\n", 1)[0])) >= 5


def test_a_pool_row_of_576_lanes_is_copied_whole_around_a_scatter(v5e_chip):
    """Why the pool's rows are the latent's 576 numbers in 640 lanes
    (``ops/latent_attention.py:pool_lanes``): a scatter of rows into a
    donated ``[pages, 16, 576]`` array is compiled with the page index on
    the lanes and the whole pool copied to that layout and back (a
    temporary of the pool's size, every step of every layer), while the
    same scatter into ``[pages, 16, 640]`` is in place; and Mosaic copies
    no part of a lane tile, so a kernel could not read a 576-wide page at
    all."""
    def scatter(pool, page, rows):
        return pool.at[page, page % 16].set(rows)

    temps = {}
    for lanes in (576, 640):
        args = (jax.ShapeDtypeStruct((PAGES, 16, lanes), jnp.bfloat16,
                                     sharding=v5e_chip),
                jax.ShapeDtypeStruct((SLOTS,), jnp.int32,
                                     sharding=v5e_chip),
                jax.ShapeDtypeStruct((SLOTS, lanes), jnp.bfloat16,
                                     sharding=v5e_chip))
        compiled = jax.jit(scatter, donate_argnums=(0,)).lower(
            *args).compile()
        temps[lanes] = compiled.memory_analysis().temp_size_in_bytes
    assert temps[640] < 1 << 20
    assert temps[576] > PAGES * 16 * 576 * 2


# the Xing4.0 cell as served (perfbench/configs/xing4.0-29b-a4b.json,
# perfbench/traffic/serve-closed-reason.json): 1 dense + 5 expert layers
# at the published widths, all 64 experts and the whole vocabulary held,
# four float32 residual streams a token, 32 slots, 16,384 pages
XING4_PAGES = 16384


def test_xing4_decode_unrolls_the_sinkhorn_iterations_and_moves_no_pool(
        v5e_chip):
    """The decode program of ``models/xing4.py`` as the cell's engine
    compiles it (``kimi_k2.py``'s latent layer at 32 heads inside a block
    that carries four streams): 9.6 GB of weights and 2.0 GB of pages fit
    with room; the attend is the Pallas walk; every layer's pool is
    aliased from argument to result and nothing has a pool's shape but
    the pools; and the twelve hyper-connections' 20 Sinkhorn iterations
    are unrolled operations under ``hc.sinkhorn``: the program holds no
    ``while`` at all."""
    import dataclasses
    from gym_tpu.models.xing4 import Xing4Config
    from gym_tpu.ops import latent_attention
    from gym_tpu.programs import serve_defs
    cfg = dataclasses.replace(
        Xing4Config(num_hidden_layers=6,
                    first_k_dense_replace=1).decode_config(),
        page_size=16, kv_pages=XING4_PAGES)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(latent_attention, "_on_tpu", lambda: True)
        compiled = compile_def(
            serve_defs.paged_decode_def(cfg.program_key(), SLOTS, 1),
            v5e_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 10.0 * 1024 ** 3 < total < 12.0 * 1024 ** 3, total / 2 ** 30
    hlo = compiled.as_text()
    assert "latent_paged_decode" in hlo
    assert not re.findall(r" while\(", hlo)
    assert len(re.findall(r"hc\.sinkhorn/div", hlo)) >= 12 * 40
    assert "hc.coef" in hlo and "hc.mix" in hlo
    pool = rf"bf16\[{XING4_PAGES},16,640\]"
    moved = re.findall(
        rf"= {pool}\S* (copy|transpose|convert|gather|copy-start)\(", hlo)
    assert not moved, moved[:3]
    assert len(re.findall(r"may-alias", hlo.split("\n", 1)[0])) >= 6
