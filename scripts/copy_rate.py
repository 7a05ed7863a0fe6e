"""What a page copy issued from a Pallas kernel costs on this chip, beside
XLA's gather of the same pages (PERF.md §6, PR 38; ROADMAP A11).

A kernel walks 16 rows' block tables as ``ops/sparse_attention.py``'s
decode index does (scalar-prefetched table, the pool left in HBM, chunks
of pages copied into VMEM slots) and does nothing with the pages but add
one tile of each chunk, so the time is the copies'. Prints nanoseconds a
live page by chunk size, chunks in flight, how far the loop that starts
a chunk's copies is unrolled and the way a chunk is waited for (once, or
page by page), and the gather's nanoseconds a TABLE row; then one layer's
index of a decode step through the kernel and through the gather.

    chiprun -- python3 scripts/copy_rate.py
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from gym_tpu.ops import sparse_attention as sa  # noqa: E402

ROWS, MB, PAGES = 16, 2304, 32768     # the Keye cell's slots, table, pool
PAGE, HEADS, DIM, TOPK = 16, 16, 64, 2048


def _walk(bt_ref, pos_ref, pool, o_ref, buf, sem, *, ppc, slots, once,
          unroll, group):
    r = pl.program_id(0)
    n_pages = pl.cdiv(jnp.minimum(pos_ref[r] + 1, MB * PAGE), PAGE)
    n_chunks = pl.cdiv(n_pages, ppc)

    def copy(phys, slot, p):
        if group:       # the aligned group of `group` table rows around it
            src = pool.at[pl.ds(pl.multiple_of(phys // group * group,
                                               group), group)]
        else:
            src = pool.at[phys]
        return pltpu.make_async_copy(src, buf.at[slot, p], sem.at[slot])

    def start(c, slot):
        live = jnp.minimum(ppc, n_pages - c * ppc)

        def one(p, carry):
            copy(bt_ref[r * MB + c * ppc + p], slot, p).start()
            return carry

        def several(g, carry):
            for u in range(unroll):
                one(g * unroll + u, carry)
            return carry

        pl.when(live == ppc)(lambda: jax.lax.fori_loop(
            0, ppc // unroll, several, None))
        pl.when(live < ppc)(lambda: jax.lax.fori_loop(0, live, one, None))

    def wait(c, slot):
        live = jnp.minimum(ppc, n_pages - c * ppc)

        def one(p, carry):
            copy(0, slot, p).wait()
            return carry

        if once:
            pl.when(live == ppc)(pltpu.make_async_copy(
                buf.at[(slot + 1) % slots], buf.at[slot],
                sem.at[slot]).wait)
            pl.when(live < ppc)(
                lambda: jax.lax.fori_loop(0, live, one, None))
        else:
            jax.lax.fori_loop(0, live, one, None)

    for i in range(slots - 1):
        pl.when(i < n_chunks)(functools.partial(start, i, i))

    def body(c, acc):
        ahead = c + slots - 1
        pl.when(ahead < n_chunks)(
            lambda: start(ahead, jax.lax.rem(ahead, slots)))
        slot = jax.lax.rem(c, slots)
        wait(c, slot)
        tile = buf[slot, 0]
        return acc + tile.reshape(-1, tile.shape[-1])[:8, :128].astype(
            jnp.float32)

    o_ref[0] = jax.lax.fori_loop(0, n_chunks, body,
                                 jnp.zeros((8, 128), jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def walk(bt, pos, pool, ppc, slots, once, unroll, group):
    page_shape = ((group,) if group else ()) + pool.shape[1:]
    return pl.pallas_call(
        functools.partial(_walk, ppc=ppc, slots=slots, once=once,
                          unroll=unroll, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 8, 128), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots, ppc) + page_shape, pool.dtype),
                pltpu.SemaphoreType.DMA((slots,))]),
        out_shape=jax.ShapeDtypeStruct((ROWS, 8, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=96 << 20),
        name="copy_rate")(bt.reshape(-1), pos, pool)


def timed(fn, *args, n=30):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def index_step(rng, bt, pos, flat, tiled, out):
    """One layer's index of a decode step through both paths of
    ``attend_rows``: milliseconds, and how far the kernel's keys and kept
    sets are from the gather's."""
    qi = jnp.asarray(rng.standard_normal((ROWS, 1, HEADS, DIM)),
                     jnp.bfloat16)
    wi = jnp.asarray(rng.standard_normal((ROWS, 1, HEADS)), jnp.float32)

    @jax.jit
    def by_gather(pool):
        scores = sa.index_scores_paged(
            qi, wi, pool[bt].reshape(ROWS, MB, -1), PAGE)
        seen = jnp.arange(MB * PAGE)[None, None, :] <= pos[:, None, None]
        return jnp.where(seen, sa.sortable(scores), 0)

    for name, pool in (("flat", flat), ("tiled", tiled)):
        out["index"][f"gather_{name}_ms"] = 1e3 * timed(by_gather, pool)
    want = by_gather(tiled)
    kept = jax.jit(lambda k: sa.kept_mask(k, TOPK))
    for ppc, slots, unroll in ((128, 3, 16), (128, 2, 16), (128, 3, 8),
                               (64, 3, 16), (64, 3, 8), (64, 3, 1),
                               (32, 3, 8)):
        sa.INDEX_UNROLL = unroll        # read when the kernel is traced
        sa._index_keys_paged.clear_cache()
        fn = functools.partial(sa._index_keys_paged, qi, wi, tiled, bt, pos,
                               PAGE, ppc, slots, False)
        try:
            ms = 1e3 * timed(fn)
        except Exception as e:  # noqa: BLE001 — report and go on
            print(f"index ppc={ppc} slots={slots} unroll={unroll}: "
                  f"{str(e)[:300]}", flush=True)
            continue
        got = fn()
        row = {"chunk_pages": ppc, "slots": slots, "unroll": unroll,
               "ms": ms,
               "zeros_differ": int(((got == 0) != (want == 0)).sum()),
               "keys_differ": int((got != want).sum()),
               "kept_differ": int((kept(got) != kept(want)).sum())}
        out["index"]["kernel"].append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({k: v for k, v in out["index"].items()
                      if k != "kernel"}), flush=True)


def main():
    dev = jax.devices()[0]
    rng = np.random.default_rng(38)
    # the cell's rows: prompts of 6 k-32 k (log-normal about 16 k) plus
    # what was generated: about 18.8 k positions a row
    pos = np.clip(rng.lognormal(np.log(17500), 0.5, ROWS), 6144,
                  MB * PAGE - 1).astype(np.int32)
    perm = rng.permutation(np.arange(1, PAGES))
    bt = np.zeros((ROWS, MB), np.int32)
    at = 0
    for r in range(ROWS):
        n = -(-(int(pos[r]) + 1) // PAGE)
        bt[r, :n] = perm[at:at + n]
        at += n
    live = int(sum(-(-(int(p) + 1) // PAGE) for p in pos))
    flat = jnp.asarray(rng.standard_normal((PAGES, 1024)), jnp.bfloat16)
    tiled = flat.reshape(PAGES, 8, 128)
    bt_d, pos_d = jnp.asarray(bt), jnp.asarray(pos)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": ROWS, "table_pages": ROWS * MB, "live_pages": live,
           "kernel": [], "gather": {}, "index": {"kernel": []}}

    @jax.jit
    def gather(pool, table):
        return pool[table].astype(jnp.float32).sum(axis=(0, 1))

    for name, pool in (("flat [P,1024] bf16", flat),
                       ("tiled [P,8,128] bf16", tiled)):
        s = timed(gather, pool, bt_d)
        out["gather"][name] = {"ms": 1e3 * s,
                               "ns_per_table_row": 1e9 * s / (ROWS * MB)}
        print(f"gather {name}: {1e3 * s:.3f} ms, "
              f"{1e9 * s / (ROWS * MB):.1f} ns a table row", flush=True)

    cases = [(tiled, ppc, slots, once, unroll, 0)
             for ppc in (32, 64, 128) for slots in (2, 3)
             for once, unroll in ((False, 1), (True, 1), (True, 8),
                                  (True, 16))]
    # the pool as PR 31 laid it: a page is one row of [P, 1024], which
    # Mosaic slices only in aligned groups of 8 rows (16 KB a page wanted)
    cases += [(flat, 64, 3, True, 8, 8)]
    for pool, ppc, slots, once, unroll, group in cases:
        try:
            s = timed(walk, bt_d, pos_d, pool, ppc, slots, once, unroll,
                      group)
        except Exception as e:  # noqa: BLE001 — report and go on
            print(f"kernel ppc={ppc} slots={slots} once={once} "
                  f"unroll={unroll} group={group}: {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)
            continue
        row = {"pool": "flat" if group else "tiled", "chunk_pages": ppc,
               "slots": slots, "wait_once": once, "unroll": unroll,
               "ms": 1e3 * s, "ns_per_live_page": 1e9 * s / live}
        out["kernel"].append(row)
        print(json.dumps(row), flush=True)
    index_step(rng, bt_d, pos_d, flat, tiled, out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/copy_rate.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
