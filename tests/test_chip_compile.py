"""The main path's kernels, compiled by the chip's own compiler at real
widths for a TPU v5e that is described, not attached
(``jax.experimental.topologies``; ``on-chip-measurement`` guide §2.3).

Interpret-mode tests cannot see what the chip's compiler refuses: a slice
that is not aligned to the tiling, a kernel that wants more scoped VMEM
than it may use, a program that does not fit 16 GB. These compiles can,
at no chip time, a second or two each. Nothing runs: a compile that
passes is not a chip run.

The platform checks inside the ops (``flash_attention._on_tpu``) still see
the CPU here, so the tests call the kernels themselves or steer that one
check with ``monkeypatch``.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gym_tpu.ops import flash_attention, fused_attention, paged_attention
from gym_tpu.ops.dct import codec_for, sparse_decode_chunks
from gym_tpu.ops.grouped_matmul import quant_tile_for, quantized_dot
from gym_tpu.ops.topk_compress import topk_compress

from _hlo import compile_def, ops_by_gate

HBM_BYTES = 16 * 1024 ** 3


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


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled.as_text()


def _fwd_bwd(attend):
    """Forward and backward of ``attend(q, k, v)`` in one program."""
    def fn(q, k, v):
        return jax.value_and_grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    return fn


def _block(causal):
    def attend(q, k, v):
        o, lse = fused_attention.fused_block_attention(q, k, v, causal)
        return o.astype(jnp.float32) + lse      # both outputs carry grads
    return attend


# GPT-2 base trains at [B=16, H=12, T=1024, D=64]; a ring step at cp=2
# sees half that sequence per device; the toy cell is 4 heads of 32.
BASE = (16, 12, 1024, 64)
KERNELS = {
    "fused_per_head_bf16": (
        _fwd_bwd(fused_attention.fused_causal_attention),
        [(BASE, jnp.bfloat16)] * 3),
    "fused_per_head_f32": (
        _fwd_bwd(fused_attention.fused_causal_attention),
        [(BASE, jnp.float32)] * 3),
    "ring_block_full_bf16": (
        _fwd_bwd(_block(False)), [((4, 12, 512, 64), jnp.bfloat16)] * 3),
    "ring_block_causal_bf16": (
        _fwd_bwd(_block(True)), [((4, 12, 512, 64), jnp.bfloat16)] * 3),
    "fused_packed_toy_bf16": (
        _fwd_bwd(functools.partial(
            fused_attention.fused_causal_attention_packed, n_head=4)),
        [((16, 256, 128), jnp.bfloat16)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_attention_kernel_compiles_for_v5e(v5e_chip, name):
    fn, shapes = KERNELS[name]
    assert "tpu_custom_call" in _compile(fn, v5e_chip, *shapes)


def test_flash_dispatch_compiles_tuned_blocks_at_2048(v5e_chip,
                                                      monkeypatch):
    """T=2048 is past the whole-context kernel, so the ``flash`` entry
    takes JAX's bundled kernel with the block sizes tuned in
    ``ops/flash_attention.py`` — forward and backward."""
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    hlo = _compile(_fwd_bwd(flash_attention.flash_causal_attention),
                   v5e_chip, *[((2, 12, 2048, 64), jnp.bfloat16)] * 3)
    assert "tpu_custom_call" in hlo


# the served cell's pool: 128 slots x 64 pages of 16 positions, plus the
# null page and a spare, heads packed on the lanes
@pytest.mark.parametrize("b,t,c,heads,kernel", [
    (128, 1, 768, 12, "paged_attn_decode"),
    (1, 128, 768, 12, "paged_attn_prefill"),
    (1, 1024, 768, 12, "paged_attn_prefill"),
    (128, 5, 768, 12, "paged_attn_prefill"),         # speculative verify
    (128, 1, 1024, 16, "paged_attn_decode"),         # GPT-2 medium
], ids=["decode", "prefill128", "prefill1024", "verify5", "medium"])
def test_paged_attention_kernel_compiles_for_v5e(v5e_chip, b, t, c, heads,
                                                 kernel):
    """The page walk at the served cell's sizes: every in-kernel slice on
    a tile boundary, the scratch inside scoped VMEM, and nothing in the
    program the size of a pool array but the two pools themselves."""
    pages, page, mb = 8194, 16, 64
    hlo = _compile(
        functools.partial(paged_attention.paged_attention, n_head=heads),
        v5e_chip, ((b, t, c), jnp.float32),
        ((pages, page, c), jnp.float32), ((pages, page, c), jnp.float32),
        ((b, mb), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo and kernel in hlo
    pool = f"f32[{pages},{page},{c}]"
    moved = [line for line in hlo.splitlines()
             if pool in line.split("(")[0] and "parameter(" not in line]
    assert not moved, moved[:3]


@pytest.fixture(scope="module")
def gpt2_served(v5e_chip):
    """``compiled(program) -> (config, Compiled)``: a serving program as
    the GPT-2 cell's engine compiles it (128 slots, pages of 16, the
    Pallas page walk), each compiled once for the module: ``decode``,
    ``prefill<bucket>``, ``spec<gamma>``."""
    import dataclasses
    from gym_tpu.models.nanogpt import GPTConfig, decode_config
    from gym_tpu.programs import serve_defs
    slots, page = 128, 16
    cfg = dataclasses.replace(
        decode_config(GPTConfig(block_size=1024, vocab_size=50304,
                                n_layer=12, n_head=12, n_embd=768,
                                dropout=0.0)),
        page_size=page, kv_pages=2 + slots * (1024 // page))
    cfg_tuple = dataclasses.astuple(cfg)

    @functools.cache
    def compiled(program):
        if program == "decode":
            pdef = serve_defs.paged_decode_def(cfg_tuple, slots, 1)
        elif program.startswith("spec"):
            pdef = serve_defs.spec_decode_def(cfg_tuple, slots, 1,
                                              int(program[4:]))
        else:
            pdef = serve_defs.paged_prefill_def(cfg_tuple,
                                                int(program[7:]), slots)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(paged_attention, "_on_tpu", lambda: True)
            return cfg, compile_def(pdef, v5e_chip)

    return compiled


@pytest.mark.parametrize("program", ["decode", "prefill256"])
def test_served_programs_move_nothing_pool_sized(gpt2_served, program):
    """``serve.paged_decode[slots=128,chunk=1]`` and a paged prefill as
    the served cell's engine compiles them: besides the in-place scatter
    of the new positions, no instruction's result is the size of a pool
    array (403 MB) or of a ``[b, S, H, hd]`` window, and the temporaries
    stay far under the 5 GiB the gathered windows took."""
    import re
    cfg, compiled = gpt2_served(program)
    page = cfg.page_size
    hlo = compiled.as_text()
    assert ("paged_attn_decode" if program == "decode"
            else "paged_attn_prefill") in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    pool_bytes = cfg.kv_pages * page * 768 * 4
    big = []
    for m in re.finditer(
            r"= (\w+)\[([\d,]+)\]\S* (copy|gather|transpose|"
            r"dynamic-update-slice|dynamic-slice|convert)\(", hlo):
        n = 1
        for d in m.group(2).split(","):
            n *= int(d)
        if n * 4 >= pool_bytes // 2:
            big.append(m.group(0))
    assert not big, big[:3]
    # the write of the new positions is a scatter into the donated pool
    assert hlo.count(f"f32[{cfg.kv_pages},{page},768]") > 24
    assert re.search(r"input_output_alias=\{.*may-alias", hlo)


# -- the sampler's gate (ISSUE 36) ------------------------------------------


@pytest.mark.parametrize("program", ["decode", "prefill256", "spec4"])
def test_served_programs_sort_only_inside_the_samplers_conditional(
        gpt2_served, program):
    """``sample_rows`` decides once a step, for the whole batch, whether
    a live row's ``top_k`` / ``top_p`` needs an order; the predicate
    reaches ``lax.cond`` unbatched, so the compiled step holds an XLA
    ``conditional`` and both full-vocabulary sorts (``[128, 50304]``: the
    largest operation of the GPT-2 cell's decode step while every step
    ran them) sit in ONE of its branches: none at the step's top level,
    where a later ``vmap`` over the gate would put them back as the
    operands of a ``select``."""
    _cfg, compiled = gpt2_served(program)
    hlo = compiled.as_text()
    assert ops_by_gate(hlo, "sort") == (2, 0)
    assert ops_by_gate(hlo, "conditional") == (0, 1)


@pytest.mark.parametrize("batched", [False, True],
                         ids=["scalar_gate", "gate_under_vmap"])
def test_ops_by_gate_sees_a_gate_that_vmap_turned_into_a_select(
        v5e_chip, batched):
    """What the test above is for, at a toy size: the same ``cond`` with
    its predicate batched compiles to no conditional, and its sort runs
    at the top level."""
    def gate(p, x):
        return jax.lax.cond(p, lambda: jnp.sort(x), lambda: x)

    fn = jax.vmap(gate) if batched else gate
    hlo = _compile(fn, v5e_chip, (((8,) if batched else ()), jnp.bool_),
                   ((8, 4096), jnp.float32))
    assert ops_by_gate(hlo, "sort") == ((0, 1) if batched else (1, 0))
    assert _conditionals(hlo) == (0 if batched else 1)


def test_quantized_dot_compiles_at_gpt2_base_mlp(v5e_chip):
    """The int8 MLP up-projection of GPT-2 base (768 x 3072) under a
    decode batch: the dequantize must fuse, not overflow anything."""
    tile = quant_tile_for((768, 3072), 256)
    _compile(quantized_dot, v5e_chip, ((8, 768), jnp.float32),
             ((768, 3072), jnp.int8), ((768 * 3072 // tile,), jnp.float32))


@pytest.mark.parametrize("shape", [(768, 3072), (50304, 768)])
def test_demo_dct_topk_compiles_at_gpt2_base_leaves(v5e_chip, shape):
    """DeMo's per-leaf path at its defaults (chunk 64, top 32): chunked
    DCT, top-k of every chunk, sparse decode — at GPT-2 base's MLP
    kernel and its embedding table."""
    codec = codec_for(shape, 64)

    def fn(x):
        idx, val = topk_compress(codec.encode(x), 32)
        tiles = sparse_decode_chunks(idx, val, codec.d_a, codec.d_b)
        return codec.from_chunks(tiles)

    _compile(fn, v5e_chip, (shape, jnp.float32))


# the Cohere2-MoE cell as served (perfbench/configs/command-a-plus.json,
# perfbench/traffic/serve-closed-rag.json): 4 layers, 16 held experts,
# 32,768 rows of the vocabulary, bfloat16, 32 slots, 12,288 pages of 16
CHIP_BYTES = int(15.75 * 1024 ** 3)


def _command_a_plus_cfg(kv_pages=12288):
    import dataclasses
    from gym_tpu.models.cohere2_moe import Cohere2MoeConfig
    return dataclasses.replace(
        Cohere2MoeConfig(
            vocab_size=32768, num_hidden_layers=4, held_experts=(0, 16),
            block_size=16384).decode_config(),
        page_size=16, kv_pages=kv_pages)


# ``command-a-plus`` (8 key-value heads of 16 query heads, head size 128,
# a pool row of 1,024 lanes, 12,288 pages) and ``qwen3-next`` (2 of 8,
# head size 256, a row of 512 lanes, 65,536 pages): q's shape less the
# batch, pool pages, a table's pages
CMDA = ((8, 16, 128), 12288, 1024)
QWEN = ((2, 8, 256), 65536, 3168)


@pytest.mark.parametrize("model,t,window,tile", [
    (CMDA, 1, 0, (1, 256)), (CMDA, 1, 4096, (1, 256)),
    (CMDA, 16, 0, (16, 256)),                        # speculative verify
    (CMDA, 4096, 4096, (32, 256)), (CMDA, 16384, 0, (32, 256)),
    (CMDA, 16384, 4096, (32, 256)),
    (QWEN, 1, 0, (1, 256)), (QWEN, 2048, 0, (128, 512)),
    (QWEN, 4096, 0, (128, 512))],
    ids=["decode_full", "decode_window", "verify16", "prefill4k_window",
         "prefill16k_full", "prefill16k_window", "qwen_decode",
         "qwen_prefill2k", "qwen_prefill4k"])
def test_grouped_paged_kernel_compiles_for_v5e(v5e_chip, model, t, window,
                                               tile):
    """The grouped page walk at the two cells' sizes, with and without a
    window: a decode step and a speculative verify keep PR 27's tile (all
    of a row's ``t`` positions a program, 256 keys a chunk), a prefill
    of two key-value heads takes 1,024 rows a head by 512 keys and one of
    eight what it had (512 by 256: 2**20 scores a chunk either way), and
    all fit the kernel's scoped VMEM."""
    (kvh, group, hd), pages, mb = model
    assert paged_attention.gqa_tile(t, group, kvh, 16) == tile
    b = 32 if t == 1 else 1
    hlo = _compile(
        functools.partial(paged_attention.paged_attention_gqa,
                          window=window),
        v5e_chip, ((b, kvh, t, group, hd), jnp.bfloat16),
        ((pages, 16, kvh * hd), jnp.bfloat16),
        ((pages, 16, kvh * hd), jnp.bfloat16),
        ((b, mb), jnp.int32), ((b,), jnp.int32))
    name = ("paged_gqa_" + ("decode" if t == 1 else "prefill")
            + ("_window" if window else "_full"))
    assert "tpu_custom_call" in hlo and name in hlo


@pytest.mark.parametrize("program", ["decode", "prefill4096",
                                     "prefill16384"])
def test_command_a_plus_programs_fit_the_chip(v5e_chip, monkeypatch,
                                              program):
    """The decode program and the 4,096 and 16,384 prefill buckets as the
    cell's engine compiles them: arguments (9.5 GB of weights, 3 GiB of
    pool), outputs and temporaries fit the 15.75 GiB the chip gives; both
    grouped kernels are in, the expert products are XLA's grouped-matmul
    kernel; and besides the in-place scatter of the new positions no
    instruction's result is the size of a pool array or of a gathered
    ``[b, S, kv_heads, head_dim]`` window."""
    import re
    from gym_tpu.programs import serve_defs
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    cfg = _command_a_plus_cfg()
    key = cfg.program_key()
    assert set(cfg.attend_paths()) == {paged_attention.KERNEL,
                                       paged_attention.KERNEL_WINDOW}
    pdef = (serve_defs.paged_decode_def(key, 32, 1) if program == "decode"
            else serve_defs.paged_prefill_def(key, int(program[7:])))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        pdef.args)
    compiled = pdef.builder().lower(*args).compile()
    mem = compiled.memory_analysis()
    # the donated pool is argument and output alike: counted once
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < CHIP_BYTES, (total / 2 ** 30, mem)
    hlo = compiled.as_text()
    kind = "decode" if program == "decode" else "prefill"
    assert f"paged_gqa_{kind}_full" in hlo
    assert f"paged_gqa_{kind}_window" in hlo
    assert "ragged-dot" in hlo
    pool_elems = cfg.kv_pages * 16 * 1024
    big = []
    for m in re.finditer(
            r"= (\w+)\[([\d,]+)\]\S* (copy|gather|transpose|"
            r"dynamic-update-slice|dynamic-slice|convert)\(", hlo):
        n = 1
        for d in m.group(2).split(","):
            n *= int(d)
        if n >= pool_elems:
            big.append(m.group(0))
    assert not big, big[:3]
    # and no row's pages are gathered out of a pool into a window
    pool = f"bf16[{cfg.kv_pages},16,1024]"
    gathered = [line for line in hlo.splitlines()
                if " gather(" in line and pool in line]
    assert not gathered, gathered[:2]
    assert re.search(r"input_output_alias=\{.*may-alias", hlo)


# the KeyeVL2 cell as served (perfbench/configs/keye-vl2-30b-a3b.json,
# perfbench/traffic/serve-closed-longdoc.json): 8 layers, 16 held experts,
# 18,992 rows of the vocabulary, bfloat16, 16 slots, rows of 36,864
# positions, 32,768 pages of 16
def _keye_cfg(kv_pages=32768):
    import dataclasses
    from gym_tpu.models.keye_vl2 import KeyeVL2Config
    return dataclasses.replace(
        KeyeVL2Config(vocab_size=18992, num_hidden_layers=8,
                      held_experts=(0, 16), block_size=36864
                      ).decode_config(),
        page_size=16, kv_pages=kv_pages)


@pytest.mark.parametrize("program", ["decode", "prefill32768"])
def test_keye_programs_fit_the_chip_and_copy_no_pool(v5e_chip, monkeypatch,
                                                     program):
    """The decode program and the longest prefill bucket as the cell's
    engine compiles them: arguments (1.7 GB of weights, 9.1 GB of pools),
    outputs and temporaries fit the 15.75 GiB the chip gives; the expert
    products are XLA's grouped-matmul kernel; and besides the in-place
    writes of the new positions no instruction's result is the size of a
    key or value pool array, and the index keys' pool (a page's keys side
    by side, one whole tile) is neither copied, transposed nor converted.
    The prefill's attend over its masks is the Pallas kernel
    (``ops/sparse_attention.py:masked_attend``). The decode step's index
    is the Pallas walk of the rows' live pages (``index_keys_paged``,
    which a bucket never takes): nothing gathers out of the index keys'
    pool and nothing moved is as large as the 16 rows' whole tables of
    them."""
    import re
    from gym_tpu.programs import serve_defs
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    cfg = _keye_cfg()
    key = cfg.program_key()
    assert set(cfg.attend_paths()) == {paged_attention.SPARSE}
    pdef = (serve_defs.paged_decode_def(key, 16, 1) if program == "decode"
            else serve_defs.paged_prefill_def(key, int(program[7:]), 16))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        pdef.args)
    compiled = pdef.builder().lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < CHIP_BYTES, (total / 2 ** 30, mem)
    hlo = compiled.as_text()
    assert "ragged-dot" in hlo
    assert ("sparse_masked_prefill" in hlo) == (program != "decode")
    assert ("sparse_index_decode" in hlo) == (program == "decode")
    pool_elems = (16 * 2304 * 1024 if program == "decode"
                  else cfg.kv_pages * 16 * 512)
    big = []
    for m in re.finditer(
            r"= (\w+)\[([\d,]+)\]\S* (copy|gather|transpose|"
            r"dynamic-slice|convert)\(", hlo):
        n = 1
        for d in m.group(2).split(","):
            n *= int(d)
        if n >= pool_elems:
            big.append(m.group(0))
    assert not big, big[:3]
    index_pool = f"bf16[{cfg.kv_pages},8,128]"
    assert index_pool in hlo
    moved = [line for line in hlo.splitlines()
             if re.search(re.escape(index_pool)
                          + r"\S* (copy|transpose|convert)\(", line)]
    assert not moved, moved[:2]
    if program == "decode":
        # pages of index keys (whole [8, 128] tiles) are gathered only
        # where the new positions are laid over them, two a row
        gathered = re.findall(r"= (bf16\[[\d,]*8,128\])\S* gather\(", hlo)
        assert set(gathered) == {"bf16[16,2,8,128]"}, gathered
    assert re.search(r"input_output_alias=\{.*may-alias", hlo)


@pytest.mark.parametrize("t,dtype", [(4, jnp.bfloat16), (16, jnp.bfloat16),
                                     (1, jnp.float32)],
                         ids=["spec4", "spec16", "float32"])
def test_sparse_index_kernel_compiles_for_v5e(v5e_chip, t, dtype):
    """The decode index's page walk at the cell's sizes beyond what the
    decode program above holds (one bfloat16 query a row): a speculative
    step's 4 and 16 queries a row, the pages fetched once for all of
    them, and a float32 pool."""
    from gym_tpu.ops import sparse_attention
    hlo = _compile(
        functools.partial(sparse_attention._index_keys_paged, page=16,
                          ppc=sparse_attention.INDEX_CHUNK,
                          slots=sparse_attention.INDEX_SLOTS,
                          interpret=False),
        v5e_chip, ((16, t, 16, 64), dtype), ((16, t, 16), jnp.float32),
        (sparse_attention.index_pool_shape(32768, 16, 64), dtype),
        ((16, 2304), jnp.int32), ((16,), jnp.int32))
    assert "tpu_custom_call" in hlo and "sparse_index_decode" in hlo


# -- the fold's H-gate (ISSUE 32) -------------------------------------------
# The fold cell as trained (perfbench/configs/gpt2-base.json,
# perfbench/traffic/train-fold4-diloco.json): GPT-2 base, 4 nodes folded by
# vmap on one chip, batch 2 x 1,024 a node with remat, flash attention, bf16
# autocast, AdamW 6e-4 under DiLoCo H=100.
FOLD_NODES, FOLD_BATCH = 4, 2
# the smallest matrix leaf of GPT-2 base over the fold's four nodes
# (attn.c_proj's [4, 768, 768]): a select this large under the `strategy`
# scope is the gate choosing between old and new state
FOLD_LEAF_ELEMS = FOLD_NODES * 768 * 768


def _strategy_selects(hlo):
    """Element counts of the selects under the ``strategy`` scope."""
    import re
    sizes = []
    for m in re.finditer(
            r"= \w+\[([\d,]*)\]\S* select\([^\n]*op_name=\"([^\"]*)\"", hlo):
        if re.search(r"(^|[/(])strategy([/)]|$)", m.group(2)):
            n = 1
            for d in filter(None, m.group(1).split(",")):
                n *= int(d)
            sizes.append(n)
    return sizes


def _conditionals(hlo):
    return hlo.count(" conditional(")


@pytest.mark.parametrize("program", ["train_step", "multi_step"])
def test_fold_cell_keeps_the_h_gate_a_conditional(v5e_chip, monkeypatch,
                                                  program):
    """The fold cell's step program, and ``multi_step`` over two steps,
    compiled for the described chip: the step counter reaches the gate
    unbatched (``AxisCtx.fold_counter``), so the program holds an XLA
    ``conditional`` and, under the ``strategy`` scope, no ``select`` of a
    parameter leaf's size. With the counter batched over the fold the
    gate was 150 such selects and no conditional: both branches every
    step, every leaf of parameters, master and momentum rewritten."""
    from gym_tpu.models.base import LossModel
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.parallel import NodeRuntime
    from gym_tpu.strategy import DiLoCoStrategy, OptimSpec
    from gym_tpu.train_node import (make_init_fn, make_multi_train_step,
                                    make_train_step)
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    k, b, seq = FOLD_NODES, FOLD_BATCH, 1024
    (device,) = v5e_chip.device_set
    runtime = NodeRuntime.create(k, [device])
    assert runtime.n_virt == k
    model = LossModel(
        GPT(GPTConfig(block_size=seq, vocab_size=50304, n_layer=12,
                      n_head=12, n_embd=768, dropout=0.0,
                      attn_impl="flash", remat=True)), jnp.bfloat16)
    strategy = DiLoCoStrategy(
        OptimSpec("adamw", lr=6e-4), H=100, lr_scheduler="lambda_cosine",
        lr_scheduler_kwargs={"warmup_steps": 100})
    strategy.finalize(1000)
    micro = (jnp.zeros((b, seq), jnp.int32),) * 2
    init_fn = make_init_fn(model, strategy, micro, 0, None, ctx=runtime.ctx)
    init = runtime.compile(lambda _: init_fn(runtime.ctx.node_index()),
                           donate_state=False)
    node = runtime.node_sharding
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=node),
        jax.eval_shape(init, jax.ShapeDtypeStruct((k,), jnp.int32,
                                                  sharding=node)))
    assert state.step.shape == (k,)
    make, lead = ((make_train_step, ()) if program == "train_step"
                  else (make_multi_train_step, (2,)))
    batch = (jax.ShapeDtypeStruct((k,) + lead + (1, b, seq), jnp.int32,
                                  sharding=node),) * 2
    step = runtime.compile(make(model, strategy, runtime.ctx, None, False),
                           donate_batch=True)
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < HBM_BYTES
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _conditionals(hlo) >= 1
    sizes = _strategy_selects(hlo)
    assert max(sizes, default=0) < FOLD_LEAF_ELEMS, sorted(sizes)[-3:]


def _gated(name):
    from gym_tpu.strategy import (DecoupledMomentumStrategy, FedAvgStrategy,
                                  NoLoCoStrategy, OptimSpec, SPARTAStrategy)
    sgd = OptimSpec("sgd", lr=0.1)
    return {
        "fedavg": lambda: FedAvgStrategy(inner_optim=sgd, H=2),
        # SPARTA exchanges every step at its default interval of 1 and
        # has no gate then; the gate exists from interval 2
        "sparta": lambda: SPARTAStrategy(inner_optim=sgd, p_sparta=0.5,
                                         interval=2),
        "demo": lambda: DecoupledMomentumStrategy(optim_spec=sgd, frac=0.2,
                                                  H=2),
        "noloco": lambda: NoLoCoStrategy(optim_spec=sgd, H=2),
    }[name]()


@pytest.mark.parametrize("name", ["fedavg", "sparta", "demo", "noloco"])
def test_gated_strategies_keep_a_conditional_on_a_fold(name):
    """Every strategy that gates its communication on the step counter
    takes it from ``node_step``: on a 4-node fold (this sandbox's CPU, a
    tiny model) the compiled step holds the ``conditional`` and the
    communication runs on its steps only. None of these gates is per
    node: aliveness and participation masks inside the branches are, and
    stay ``where``s."""
    import numpy as np
    from gym_tpu.models.base import LossModel
    from gym_tpu.parallel import NodeRuntime
    from gym_tpu.train_node import make_init_fn, make_train_step
    from test_trainer_e2e import TinyLossModel
    k = 4
    rng = np.random.default_rng(0)
    x = rng.normal(size=(k, 1, 8, 8, 8)).astype(np.float32)
    y = rng.integers(0, 10, size=(k, 1, 8)).astype(np.int32)
    rt = NodeRuntime.create(k, jax.devices("cpu")[:1])
    assert rt.n_virt == k
    lm, strat = LossModel(TinyLossModel()), _gated(name)
    strat.finalize(4)
    state = rt.init_state(make_init_fn(lm, strat, (x[0, 0], y[0, 0]),
                                       seed=0, ctx=rt.ctx))
    step = rt.compile(make_train_step(lm, strat, rt.ctx))
    batch = rt.shard_batch((x, y))
    assert _conditionals(step.lower(state, batch).compile().as_text()) >= 1
    comm = []
    for _ in range(3):
        state, m = step(state, batch)
        comm.append(float(np.asarray(m["comm_bytes"])[0]))
    # steps 0, 1, 2: FedAvg, DeMo and NoLoCo communicate at 2 (H=2, never
    # at 0); SPARTA's interval has no `step > 0` and exchanges at 0 and 2
    assert [c > 0 for c in comm] == [name == "sparta", False, True]
    assert state.step.shape == (k,)
