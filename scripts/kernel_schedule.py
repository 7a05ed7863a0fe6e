"""What the chip's compiler scheduled for one call of the grouped paged
attention kernel, read without a chip (PERF.md §6, PR 45 and PR 48).

A child process compiles ``ops/paged_attention.py:paged_attention_gqa``
at the shapes given for a described ``v5e:2x2`` with libtpu's dump flags
(``--xla_jf_dump_to``, ``--xla_jf_dump_llo_text``); the child aborts when
the compile is done, the files are whole. This process reads the kernel's
``*-final_bundles.txt`` (one VLIW bundle a line, a ``>`` a loop it stands
in) and ``*-final_hlo-static-per-bundle-utilization.txt`` (how many of
each slot the bundle fills) and prints, for every loop of the kernel: its
bundles, the fill of the slots, vector stores and loads and how many of
them spill and fill registers, and for the loops that walk chunks the
bundles a SCORE VREG (8 rows x 128 keys of float32: ``kv_heads * tq *
group * chunk / 1024`` a chunk). The matrix unit's floor at head size
256 is 8 bundles a score vreg, at 128 it is 4. Nothing runs: a count of
bundles is not a time.

    python3 scripts/kernel_schedule.py                     # qwen3-next
    python3 scripts/kernel_schedule.py --q 1,8,4096,16,128 --pages 12288 \\
        --table 1024 --window 4096                         # command-a-plus
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
         "VSTORE:SPILL", "SALU")
CAPACITY = (4, 3, 4, 1, 3, 3, 1, 1, 2)      # the utilization file's own
_BUNDLE = re.compile(r"^\s*(?:0x[0-9a-f]+|\d+)\s+([A-Z]{2})?:\s*(>*)\s*\{")


def compile_in_child(args, dump: str) -> dict:
    """The child's last line: the tile the wrapper took."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled",
           "LIBTPU_INIT_ARGS": f"--xla_jf_dump_to={dump} "
                               "--xla_jf_dump_llo_text=true"}
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--q", args.q, "--pages", str(args.pages), "--page",
         str(args.page), "--table", str(args.table), "--window",
         str(args.window), "--dtype", args.dtype],
        env=env, capture_output=True, text=True)
    said = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if not said or not glob.glob(os.path.join(dump, "*-final_bundles.txt")):
        sys.exit(out.stderr[-4000:])
    return json.loads(said[-1])


def child(args) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    sys.path.insert(0, ROOT)
    from gym_tpu.ops import paged_attention as pa
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    b, kvh, t, group, hd = (int(x) for x in args.q.split(","))
    dt = jnp.dtype(args.dtype)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)

    tq, chunk = pa.gqa_tile(t, group, kvh, args.page)
    print(json.dumps({"tq": tq, "chunk": chunk,
                      "kv_heads": kvh, "group": group, "head_dim": hd,
                      "t": t}), flush=True)
    pool = shape((args.pages, args.page, kvh * hd), dt)
    # the dump's last file wants a template libtpu does not ship: the
    # process aborts here, after the kernel's files are written
    jax.jit(functools.partial(pa.paged_attention_gqa,
                              window=args.window)).lower(
        shape((b, kvh, t, group, hd), dt), pool, pool,
        shape((b, args.table), jnp.int32), shape((b,), jnp.int32)).compile()


def loops_of(bundles_path: str, util_path: str):
    """Every loop of the kernel in program order: ``depth``, ``bundles``
    (the loops inside it too), and of its own lines ``own``, the slots'
    counts, the commonest operations and ``regions``: its own lines cut
    where a predicated region falls through (``PF``), which is where one
    body of the chunk loop ends and the other begins."""
    lines = []
    with open(bundles_path) as f:
        for ln in f:
            if (m := _BUNDLE.match(ln)):
                # an empty bundle (a branch's delay) carries no marks
                depth = (lines[-1][1] if lines and "{}" in ln
                         else len(m.group(2)))
                lines.append((m.group(1), depth, ln))
    with open(util_path) as f:
        util = [[int(x) for x in ln.split()] for ln in
                f.read().split("== UTILIZATION:\n")[1].strip().splitlines()]
    assert len(util) == len(lines), (len(util), len(lines))
    loops, open_ = [], []
    for (mark, depth, text), fill in zip(lines, util):
        del open_[depth - (mark == "LB"):]
        if mark == "LB" and depth:
            loops.append({"depth": depth, "bundles": 0, "ops": {},
                          "regions": [[0] * (1 + len(SLOTS))]})
            open_.append(loops[-1])
        for loop in open_:
            loop["bundles"] += 1
        if open_:
            loop = open_[-1]
            if mark == "PF":
                loop["regions"].append([0] * (1 + len(SLOTS)))
            loop["regions"][-1][0] += 1
            for i, n in enumerate(fill):
                loop["regions"][-1][1 + i] += n
            for op in re.findall(r" = ([a-z][\w.]*)", text):
                op = re.sub(r"\.(mxu|xlu)\d$", "", op)
                loop["ops"][op] = loop["ops"].get(op, 0) + 1
    return loops


def counts(region, vregs=0) -> dict:
    """A region's (or a loop's own) bundles and slots as printed."""
    n, slots = region[0], dict(zip(SLOTS, region[1:]))
    out = {"bundles": n,
           "fill_pct": {s: round(100.0 * slots[s] / (n * cap), 1)
                        for s, cap in zip(SLOTS, CAPACITY) if ":" not in s},
           "stores": slots["VSTORE"], "spill_stores": slots["VSTORE:SPILL"],
           "loads": slots["VLOAD"], "fill_loads": slots["VLOAD:FILL"]}
    if vregs:
        out["bundles_a_score_vreg"] = round(n / vregs, 2)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--q", default="1,2,4096,8,256",
                    help="b,kv_heads,t,group,head_dim of the queries")
    ap.add_argument("--pages", type=int, default=16384)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--table", type=int, default=3168,
                    help="pages a row's block table names")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--keep", default="",
                    help="a directory to leave the compiler's dump in")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args)
        return 0
    with tempfile.TemporaryDirectory() as tmp:
        dump = args.keep or tmp
        os.makedirs(dump, exist_ok=True)
        tile = compile_in_child(args, dump)
        found = sorted(glob.glob(os.path.join(
            dump, "*-paged_gqa_*-final_bundles.txt")))
        found = [p for p in found if "schedule-analysis" not in p]
        if len(found) != 1:
            sys.exit(f"expected one kernel in {dump}, found {found}")
        stem = found[0].rsplit("-", 2)[0]
        loops = loops_of(found[0], glob.glob(
            stem + "-*-final_hlo-static-per-bundle-utilization.txt")[0])
    vregs = (tile["kv_heads"] * tile["tq"] * tile["group"] * tile["chunk"]
             // 1024)
    print(json.dumps({"kernel": os.path.basename(stem).split("-", 1)[1],
                      **tile, "score_vregs_a_chunk": vregs}))
    for loop in loops:
        own = [sum(col) for col in zip(*loop["regions"])]
        ops = sorted(loop["ops"].items(), key=lambda kv: -kv[1])[:12]
        print(json.dumps({"loop_depth": loop["depth"],
                          "bundles_with_inner_loops": loop["bundles"],
                          **counts(own), "top": dict(ops)}))
        # what a chunk runs: the regions of a loop below the grid's that
        # are long enough to hold its page copies or its matrix products
        for region in loop["regions"]:
            if loop["depth"] >= 2 and region[0] * 8 > vregs:
                print(json.dumps({"body_of_loop_depth": loop["depth"],
                                  **counts(region, vregs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
