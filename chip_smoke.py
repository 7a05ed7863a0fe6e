#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once at full GPT-2 base width (12 layers,
12 heads, 768, block 1024, vocabulary 50304; random weights and synthetic
tokens, both from ``--seed``) through the entry points a user calls:

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # the path across four chips, nothing else
    python chip_smoke.py --rehearse # tiny sizes, any backend; never passes

Default run: ``Trainer.fit`` on one node, then four nodes folded on the chip
under DiLoCo with a checkpoint (flash attention, bf16 autocast); from that
checkpoint ``load_for_serving`` -> ``create_server`` on a thread of this
process, a handful of ``/generate`` requests over loopback (one streamed);
``/stats``; shutdown; one request under int8 weights if the time allows.

The exactness contract of the served section. The server's stream equals
``generate_fast`` token for token wherever its attend takes the gather path
(off the TPU, an int8 pool): same reductions, bit for bit. On a TPU with a
float32 pool the attend is the Pallas page walk
(``gym_tpu/ops/paged_attention.py``), which sums in another order, so there
the engine is judged by its logits: forced along ``generate_fast``'s tokens,
every step's logits lie within ``PAGED_LOGIT_TOL`` of the model's plain
forward over what was fed (on every backend), and its first token is
``generate_fast``'s. The equality of its later tokens is then reported, not
required.

``--chips 4``: ``Trainer.fit`` with four nodes, one per chip, under DiLoCo
and under plain all-reduce, each compared with the same four nodes folded on
one device (same seed); asserts the state sits on four distinct devices and
the step program holds collectives.

Stdout carries one JSON object per phase and, as its LAST line, exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything else the program says goes to stderr: the process's file
descriptor 1 is pointed at stderr before JAX is imported, and only this
script holds the real stdout. Exit code 0 iff ``ok``. ``ok`` is false when
the first device is not a TPU, when a phase raises, when a loss is not
finite or does not fall, when a served stream breaks the exactness contract
above, when a Pallas attention kernel (training's, or on the chip the paged
attend's) did not run, or when the program registry had to retry a compile.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import traceback
import urllib.request
import warnings

BUDGET_S = 1200.0          # the contract's limit for the whole run
INT8_START_BY_S = 720.0    # the optional int8 phase starts before this or not at all


def last_line(ok: bool, devices) -> str:
    """The contract's final stdout line, built from the device list as JAX
    reports it (``jax.devices()``; empty when JAX could not be asked)."""
    first = devices[0] if devices else None
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": getattr(first, "platform", None),
                   "kind": getattr(first, "device_kind", None),
                   "count": len(devices)}})


def claim_stdout():
    """Point fd 1 at stderr and return the real stdout as a private file:
    whatever a library, a thread, a child or an exit hook prints can no
    longer land on stdout, before or after the last line."""
    sys.stdout.flush()
    real = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    return real


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run drives. ``FULL`` is the contract's; ``TINY`` is the
    CPU rehearsal of the same control flow."""

    n_layer: int
    n_head: int
    n_embd: int
    block_size: int
    vocab_size: int
    batch_1node: int       # sequences per step, single node
    batch_4node: int       # sequences per node per step, four nodes
    steps: int
    num_slots: int
    prompt_lens: tuple
    max_new_tokens: int


# Four GPT-2 base nodes under DiLoCo hold 9.3 GB of state on one 16 GB
# chip; batch 2 per node with remat (every four-node fit below) is what
# the chip's compiler fits beside it: 12.8 GB live, where batch 4
# without remat is refused.
FULL = Sizes(n_layer=12, n_head=12, n_embd=768, block_size=1024,
             vocab_size=50304, batch_1node=16, batch_4node=2, steps=5,
             num_slots=4, prompt_lens=(5, 48, 300, 900), max_new_tokens=12)
TINY = Sizes(n_layer=1, n_head=2, n_embd=32, block_size=32, vocab_size=128,
             batch_1node=4, batch_4node=2, steps=3, num_slots=2,
             prompt_lens=(3, 20), max_new_tokens=5)

SAMPLING = {"temperature": 0.8, "top_k": 50}
# Largest gap allowed between a logit of the engine and the same logit of
# the model's plain forward. Set from the page walk against the gather path
# (the unpaged engine, until PR 29), measured on the chip
# (PR 26, TPU v5 lite, GPT-2 base width): 0.049 at most over a 300-token
# prompt and 8 decode steps with every block kernel times four (logits of
# standard deviation 0.55), 0.0025 over 11 steps on this script's own
# checkpoint (five training steps: standard deviation 0.37). The
# two paths multiply the same bf16-rounded operands; they differ in the
# order of the float32 sums (128 positions at a time under a running
# maximum against one softmax over the window), and at one token a row XLA
# lowers the gather path's products through the vector units in float32.
PAGED_LOGIT_TOL = 0.15


class Smoke:
    """One run: the phases, their shared scratch directory and verdicts."""

    def __init__(self, args, out):
        self.args = args
        self.out = out
        self.t0 = time.monotonic()
        self.sizes = TINY if args.rehearse else FULL
        self.failed = []
        self.tmp = tempfile.mkdtemp(prefix="chip_smoke_")
        self.retry_warnings = []
        self.attn_paths = []
        self.paged_paths = []
        self.run_dir = None    # the checkpointed run the server restores

    # -- reporting ---------------------------------------------------------

    def emit(self, phase: str, ok: bool, **fields) -> None:
        if not ok:
            self.failed.append(phase)
        self.out.write(json.dumps(
            {"phase": phase, "ok": bool(ok),
             "t_s": round(time.monotonic() - self.t0, 1), **fields},
            default=str) + "\n")
        self.out.flush()

    def run_phase(self, phase: str, fn) -> bool:
        """Run one phase; an exception is that phase's failure (traceback
        to stderr), never the script's last stdout line."""
        try:
            ok, fields = fn()
        except Exception as e:  # noqa: BLE001 — boundary: report, go on
            traceback.print_exc(file=sys.stderr)
            self.emit(phase, False, error=f"{type(e).__name__}: {e}"[:500])
            return False
        self.emit(phase, ok, **fields)
        return ok

    # -- shared pieces -----------------------------------------------------

    def gpt_config(self, **over):
        from gym_tpu.models.nanogpt import GPTConfig
        s = self.sizes
        return GPTConfig(block_size=s.block_size, vocab_size=s.vocab_size,
                         n_layer=s.n_layer, n_head=s.n_head,
                         n_embd=s.n_embd, dropout=0.0, attn_impl="flash",
                         **over)

    def dataset(self):
        """A token stream with something to learn (one random motif,
        repeated), so a few steps must lower the loss."""
        import numpy as np
        from gym_tpu.data.gpt_datasets import ContiguousGPTTrainDataset
        s = self.sizes
        rng = np.random.default_rng(self.args.seed)
        motif = rng.integers(0, s.vocab_size, 97)
        n = 64 * s.block_size + 1
        stream = np.tile(motif, n // motif.size + 1)[:n].astype(np.uint16)
        return ContiguousGPTTrainDataset(stream, s.block_size)

    def fit(self, name, strategy, *, num_nodes, batch, remat=False,
            devices=None, checkpoint=False):
        """``Trainer.fit`` plus the checks every training phase makes.
        Returns ``(ok, fields, result)``."""
        import math
        from gym_tpu import Trainer
        from gym_tpu import programs
        from gym_tpu.models.nanogpt import GPT

        s = self.sizes
        reg = programs.default_registry()
        before = reg.counters()
        res = Trainer(GPT(self.gpt_config(remat=remat)),
                      self.dataset()).fit(
            strategy=strategy, num_nodes=num_nodes, devices=devices,
            max_steps=s.steps, batch_size=batch, autocast=True,
            val_size=0, val_interval=0, show_progress=False,
            seed=self.args.seed, run_name=name,
            log_dir=os.path.join(self.tmp, "logs"),
            save_dir=os.path.join(self.tmp, "ckpt") if checkpoint else None,
            checkpoint_interval=s.steps if checkpoint else None)
        after = reg.counters()
        losses = [loss for _, loss in res.history["train_loss"]]
        step_name = f"trainer.step[{type(strategy).__name__}]"
        hlo = reg.lowered_text(step_name)
        checks = {
            "steps_ran": len(losses) == s.steps,
            "loss_finite": all(math.isfinite(x) for x in losses),
            "loss_falling": len(losses) > 1 and losses[-1] < losses[0],
            "pallas_kernel_in_step": "tpu_custom_call" in hlo,
        }
        fields = {
            "losses": [round(x, 4) for x in losses],
            "checks": checks,
            # fit's own rate after its first dispatch; a checkpoint
            # save inside the window counts against it
            "steps_per_s_after_first": res.steps_per_second_steady,
            "tokens_per_s_after_first": (
                res.steps_per_second_steady * batch * num_nodes
                * s.block_size if res.steps_per_second_steady else None),
            "rate_includes_checkpoint_save": checkpoint,
            "first_dispatch_s": round(
                after["compile_seconds"] - before["compile_seconds"], 2),
            "disk_hits": after["disk_hits"] - before["disk_hits"],
            "xla_compiles": after["xla_compiles"] - before["xla_compiles"],
            "cross_device_collectives_in_step": _cross_device_collectives(
                hlo),
        }
        return all(checks.values()), fields, res

    # -- phases ------------------------------------------------------------

    def phase_env(self, devices):
        import flax
        import jax
        import jaxlib
        import numpy as np
        import orbax.checkpoint as ocp
        from gym_tpu import programs
        from gym_tpu.native import native_available
        from gym_tpu.ops import fused_attention
        from importlib import metadata
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = None
        d0 = devices[0]
        stats = d0.memory_stats() or {}
        checks = {"platform_is_tpu": d0.platform == "tpu",
                  "pallas_not_interpreted": fused_attention.INTERPRET is False}
        if self.args.chips is not None:
            checks["device_count"] = len(devices) == self.args.chips
        return all(checks.values()), {
            "checks": checks, "python": sys.version.split()[0],
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "flax": flax.__version__,
            "orbax": ocp.__version__, "device_kind": d0.device_kind,
            "devices": len(devices),
            "hbm_bytes_limit": stats.get("bytes_limit"),
            "cache_dir": programs.enable_disk_tier(
                min_compile_time_secs=None),
            "JAX_COMPILATION_CACHE_DIR": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR"),
            "native_gather": ("g++ build" if native_available(np.uint16)
                              else "numpy fallback"),
            "rehearsal": self.args.rehearse}

    def phase_train_1node(self):
        from gym_tpu.strategy.optim import OptimSpec
        from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy
        ok, fields, _res = self.fit(
            "smoke_1node", SimpleReduceStrategy(OptimSpec("adamw", lr=6e-4)),
            num_nodes=1, batch=self.sizes.batch_1node)
        return ok, fields

    def phase_train_4fold(self):
        from gym_tpu.strategy.diloco import DiLoCoStrategy
        from gym_tpu.strategy.optim import OptimSpec
        s = self.sizes
        ok, fields, _res = self.fit(
            "smoke_4fold", DiLoCoStrategy(OptimSpec("adamw", lr=6e-4), H=2),
            num_nodes=4, batch=s.batch_4node, remat=True, devices=[0],
            checkpoint=True)
        run_dir = os.path.join(self.tmp, "ckpt", "smoke_4fold")
        steps = sorted(int(n) for n in os.listdir(run_dir) if n.isdigit())
        fields["checkpoint_steps"] = steps
        fields["checks"]["checkpoint_written"] = steps == [s.steps]
        if steps:
            self.run_dir = run_dir
        return ok and steps == [s.steps], fields

    def _serve(self, phase, params, cfg, requests, *, warmup):
        """Serve ``requests`` over loopback HTTP from a server thread of
        this process and compare each stream with ``generate_fast``."""
        import numpy as np
        from gym_tpu.models.nanogpt import generate_fast
        from gym_tpu.serve.__main__ import create_server

        s = self.sizes
        handle = create_server(
            params, cfg, port=0, num_slots=s.num_slots, warmup=warmup,
            metrics_dir=os.path.join(self.tmp, phase))
        http = threading.Thread(target=handle.httpd.serve_forever,
                                name="chip-smoke-http")
        http.start()
        served, stats = [], None
        try:
            if handle.warmup is not None:
                handle.warmup.wait(timeout=BUDGET_S)
            base = f"http://127.0.0.1:{handle.port}"
            rng = np.random.default_rng(self.args.seed + 1)
            for i, (plen, n_new, stream) in enumerate(requests):
                prompt = rng.integers(0, s.vocab_size, plen).tolist()
                body = {"prompt": prompt, "max_new_tokens": n_new,
                        "seed": self.args.seed + i, "stream": stream,
                        **SAMPLING}
                t_req = time.monotonic()
                reply = urllib.request.urlopen(urllib.request.Request(
                    base + "/generate", json.dumps(body).encode(),
                    {"Content-Type": "application/json"}), timeout=600)
                if stream:
                    events = [json.loads(line[6:]) for line in reply
                              if line.startswith(b"data: ")]
                    tokens = [t for e in events for t in e.get("tokens", [])]
                    complete = bool(events) and events[-1].get("done") is True
                else:
                    tokens = json.loads(reply.read())["tokens"]
                    complete = True
                wall = time.monotonic() - t_req
                ref = generate_fast(
                    params, cfg, np.asarray(prompt)[None], n_new,
                    seed=self.args.seed + i, **SAMPLING)[0, plen:].tolist()
                served.append({"prompt_len": plen, "streamed": stream,
                               "tokens": len(tokens), "complete": complete,
                               "equals_generate_fast": tokens == ref,
                               "wall_s": round(wall, 3)})
            stats = json.loads(urllib.request.urlopen(
                base + "/stats", timeout=60).read())
        finally:
            handle.close()
            http.join(timeout=60)
        # the gather path keeps the streams bit-identical; the page walk
        # (a TPU, f32 pool) is judged by its logits in phase_serve
        on_kernel = bool(stats and stats.get("paged_kernel_dispatches"))
        checks = {
            "all_complete": all(r["complete"] for r in served),
            "all_equal_generate_fast": on_kernel or all(
                r["equals_generate_fast"] for r in served),
            "token_counts": all(r["tokens"] == n for r, (_, n, _s)
                                in zip(served, requests)),
            "stats_ok": stats is not None and stats.get("status") == "ok",
            "http_thread_joined": not http.is_alive(),
        }
        return all(checks.values()), {
            "checks": checks, "requests": served,
            "tokens_served": sum(r["tokens"] for r in served),
            "streams_equal_generate_fast": sum(
                r["equals_generate_fast"] for r in served),
            "stats": {k: stats.get(k) for k in (
                "paged", "page_size", "kv_pages", "num_slots",
                "weights_dtype", "kv_dtype", "weights_bytes",
                "tokens_generated", "prefills", "prefill_buckets",
                "decode_steps", "paged_kernel_dispatches",
                "programs_compiled", "warmup",
                "requests_done", "requests_failed")} if stats else None}

    def phase_serve(self):
        from gym_tpu.serve.load import load_for_serving
        s = self.sizes
        params, cfg, info = load_for_serving(self.run_dir)
        requests = [(plen, s.max_new_tokens, False) for plen in s.prompt_lens]
        requests[1] = (s.prompt_lens[1], s.max_new_tokens, True)
        ok, fields = self._serve("serve", params, cfg, requests, warmup=True)
        fields["restored_step"] = info["step"]
        agree = self._engines_agree(params, cfg, s.prompt_lens[-2],
                                    s.max_new_tokens)
        fields["checks"].update(agree.pop("checks"))
        fields["engines"] = agree
        return ok and all(fields["checks"].values()), fields

    def _engines_agree(self, params, cfg, plen, n_new):
        """The exactness contract, the engine against the model's own two
        references in this process. The engine is forced along
        ``generate_fast``'s tokens: on the gather path every token it
        samples on the way must be ``generate_fast``'s next (bit for bit
        the same logits under the same key schedule), on the Pallas page
        walk the first must, and the others are reported. Its logits at
        every step lie within ``PAGED_LOGIT_TOL`` of the model's plain
        forward (no cache) over what was fed."""
        import numpy as np
        from gym_tpu.models.nanogpt import GPT, generate_fast
        from gym_tpu.ops.paged_attention import KERNEL
        from gym_tpu.serve.engine import InferenceEngine, SamplingParams

        s, seed = self.sizes, self.args.seed + 100
        prompt = np.random.default_rng(seed).integers(0, s.vocab_size, plen)
        sp = SamplingParams(max_new_tokens=n_new, seed=seed, **SAMPLING)
        ref = generate_fast(params, cfg, prompt[None], n_new, seed=seed,
                            **SAMPLING)[0, plen:].tolist()
        eng = InferenceEngine(params, cfg, num_slots=s.num_slots)
        slot, ev = eng.admit(prompt, sp)
        stream, logits = [ev.token], []
        while not ev.finished:
            ev, = eng.step(override_tokens={slot: ref[len(logits)]})
            logits.append(eng.last_logits[slot].copy())
            stream.append(ev.token)
        # one forward over everything fed: position plen + i holds the
        # logits after ref[i]
        plain = GPT(dataclasses.replace(cfg.decode_config(), decode=False))
        fed = np.concatenate([prompt, ref[:len(logits)]])
        want = np.asarray(plain.apply({"params": params}, fed[None],
                                      train=False))[0, plen:]
        gaps = [float(np.abs(got - w).max()) for got, w in zip(logits, want)]
        on_kernel = eng.attend_path == KERNEL
        return {"paged_attend_path": eng.attend_path,
                "prompt_len": plen, "steps": len(gaps),
                "paged_logit_gap_max": max(gaps),
                "paged_logit_tolerance": PAGED_LOGIT_TOL,
                "logit_std": float(np.std(logits[-1])),
                "paged_first_token_equal": stream[0] == ref[0],
                "paged_stream_equal": stream == ref,
                "checks": {
                    "paged_equals_generate_fast": on_kernel or stream == ref,
                    "paged_logits_within_tolerance":
                        max(gaps) <= PAGED_LOGIT_TOL,
                    "paged_first_token": stream[0] == ref[0]}}

    def phase_serve_int8(self):
        from gym_tpu.serve.load import load_for_serving
        s = self.sizes
        params, cfg, _info = load_for_serving(self.run_dir,
                                              weights_dtype="int8")
        return self._serve("serve_int8", params, cfg,
                           [(s.prompt_lens[1], s.max_new_tokens, False)],
                           warmup=False)

    def four_chip_phases(self) -> None:
        from gym_tpu.strategy.diloco import DiLoCoStrategy
        from gym_tpu.strategy.optim import OptimSpec
        from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy
        for label, make in (
                ("diloco", lambda: DiLoCoStrategy(
                    OptimSpec("adamw", lr=6e-4), H=2)),
                ("allreduce", lambda: SimpleReduceStrategy(
                    OptimSpec("adamw", lr=6e-4)))):
            self.run_phase(f"four_chips_{label}",
                           lambda: self.phase_four_chips(label, make))

    def phase_four_chips(self, label, make_strategy):
        """The path across chips and what it is compared with: the same
        four nodes, same seed, folded on device 0. Not bit-exact: the
        folded program batches the four nodes' matrix products in one
        vmapped product and sums their gradients in one local reduction,
        the spread program runs them per chip and reduces over the
        interconnect, so the bf16 products are tiled and the f32 sums
        ordered differently. Tolerance: 2e-2 relative on every step's
        loss, stated before the first four-chip run."""
        s = self.sizes
        rtol = 2e-2
        ok4, f4, res = self.fit(
            f"smoke_4chip_{label}", make_strategy(), num_nodes=4,
            batch=s.batch_4node, remat=True)
        placed = {"params": _shard_devices(res.node_state.params),
                  "strategy_state": _shard_devices(
                      res.node_state.strategy_state)}
        spread = res.history["train_loss"]
        del res
        gc.collect()
        ok1, f1, res = self.fit(
            f"smoke_4fold_{label}", make_strategy(), num_nodes=4,
            batch=s.batch_4node, remat=True, devices=[0])
        folded = res.history["train_loss"]
        del res
        gc.collect()
        dev = max(abs(a - b) / abs(b)
                  for (_, a), (_, b) in zip(spread, folded))
        checks = {
            "four_chip_fit": ok4, "folded_fit": ok1,
            "state_on_4_devices": all(len(v) == 4 for v in placed.values()),
            "collectives_in_step":
                f4["cross_device_collectives_in_step"] > 0,
            "losses_agree": dev <= rtol,
        }
        return all(checks.values()), {
            "checks": checks, "rtol": rtol, "max_rel_loss_deviation": dev,
            "devices_holding": placed, "four_chips": f4,
            "folded_on_one": f1}

    # -- the run -----------------------------------------------------------

    def run(self, devices) -> bool:
        try:
            if not self.run_phase("env", lambda: self.phase_env(devices)) \
                    and not self.args.rehearse:
                return False
            with self._watch():
                if self.args.chips is not None:
                    self.four_chip_phases()
                else:
                    self.one_chip_phases()
            self.run_phase("wrap_up", self.phase_wrap_up)
        finally:
            shutil.rmtree(self.tmp, ignore_errors=True)
        return not self.failed

    def one_chip_phases(self) -> None:
        self.run_phase("train_1node", self.phase_train_1node)
        gc.collect()
        self.run_phase("train_4fold_diloco", self.phase_train_4fold)
        gc.collect()
        if self.run_dir is None:
            self.emit("serve", False, not_run="no checkpoint to serve")
            return
        self.run_phase("serve", self.phase_serve)
        gc.collect()
        if time.monotonic() - self.t0 < INT8_START_BY_S:
            self.run_phase("serve_int8", self.phase_serve_int8)
        else:
            self.emit("serve_int8", True, not_run=(
                f"past {INT8_START_BY_S:.0f}s of the {BUDGET_S:.0f}s "
                f"budget"))

    @contextlib.contextmanager
    def _watch(self):
        """Record what the phases would otherwise hide: registry compile
        retries (warnings) and the attention path each shape took (log
        records of ``gym_tpu.ops.flash_attention`` for training and of
        ``gym_tpu.ops.paged_attention`` for the paged attend)."""
        import logging
        watched = []
        for name, seen in (("gym_tpu.ops.flash_attention", self.attn_paths),
                           ("gym_tpu.ops.paged_attention",
                            self.paged_paths)):
            log = logging.getLogger(name)
            handler = logging.Handler(level=logging.INFO)
            handler.emit = lambda rec, seen=seen: seen.append(
                rec.getMessage())
            watched.append((log, handler, log.level))
            log.addHandler(handler)
            log.setLevel(logging.INFO)
        old_show = warnings.showwarning

        def show(message, category, filename, lineno, file=None, line=None):
            if "retrying with the persistent compile cache bypassed" \
                    in str(message):
                self.retry_warnings.append(str(message)[:300])
            old_show(message, category, filename, lineno, file, line)

        try:
            with warnings.catch_warnings():
                warnings.simplefilter("always", UserWarning)
                warnings.showwarning = show
                yield
        finally:
            for log, handler, old_level in watched:
                log.removeHandler(handler)
                log.setLevel(old_level)

    def phase_wrap_up(self):
        from gym_tpu import programs
        left = [t.name for t in threading.enumerate()
                if t is not threading.main_thread()
                and t.name.startswith(("gym-tpu", "chip-smoke"))]
        checks = {"no_compile_retries": not self.retry_warnings,
                  "threads_joined": not left}
        if not self.args.rehearse:
            checks["pallas_attention_ran"] = any(
                "pallas" in p for p in self.attn_paths)
            checks["pallas_paged_attention_ran"] = any(
                "pallas_paged" in p for p in self.paged_paths)
        return all(checks.values()), {
            "checks": checks, "attention_paths": self.attn_paths,
            "paged_attention_paths": self.paged_paths,
            "compile_retry_warnings": self.retry_warnings,
            "threads_alive": left,
            "cache": programs.disk_event_counters(),
            "registry": programs.default_registry().counters(),
            "wall_s": round(time.monotonic() - self.t0, 1)}


def _cross_device_collectives(stablehlo: str) -> int:
    """Collectives in a lowered program whose replica groups span more
    than one device (a one-device fold lowers its node-axis psums too,
    over groups of one, which the compiler then drops)."""
    return sum(int(m.group(1)) > 1 for m in re.finditer(
        r"replica_groups = dense<[^>]*> : tensor<\d+x(\d+)xi64>", stablehlo))


def _shard_devices(tree) -> list:
    """Ids of the devices that hold shards of the first leaf of ``tree``."""
    import jax
    leaf = jax.tree.leaves(tree)[0]
    return sorted({sh.device.id for sh in leaf.addressable_shards})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(4,), default=None,
                    help="run ONLY the path across four chips and the "
                         "one-device fold it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the tokens and the prompts")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is, to "
                         "rehearse the control flow; never ends ok")
    args = ap.parse_args(argv)

    out = claim_stdout()
    ok, devices = False, []
    try:
        import jax
        devices = jax.devices()
        ok = Smoke(args, out).run(devices) and not args.rehearse
    except Exception:  # noqa: BLE001 — boundary: traceback to stderr
        traceback.print_exc(file=sys.stderr)
    finally:
        # owed whatever happened above, an interrupt included
        out.write(last_line(ok, devices) + "\n")
        out.close()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
