"""Subprocess worker for the process-restart zero-compile seam
(ISSUE 9, acceptance seam 4).

Builds a tiny serving engine with the device-program registry's
persistent executable tier pointed at ``argv[1]``, warms the COMPLETE
program family (unless ``argv[2]`` is ``nowarm``: the request then pays
its builds itself), serves one request, and prints the registry counters
as one JSON line.  The parent test runs this twice against the same cache
directory: the first (cold-disk) run must compile, the second
(warm-disk "process restart") must report ``xla_compiles == 0`` — every
build answered by deserializing a persisted executable, zero XLA on the
hot path.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np

from gym_tpu import programs
from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import Scheduler

cache_dir = sys.argv[1]
programs.enable_disk_tier(cache_dir)

cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=2, n_head=2,
                n_embd=32, dropout=0.0)
model = GPT(cfg)
params = model.init({"params": jax.random.PRNGKey(0)},
                    np.zeros((1, 4), np.int64), train=False)["params"]

eng = InferenceEngine(params, cfg, num_slots=2, decode_chunk=2)
warmup = sys.argv[2:] != ["nowarm"]
warm = programs.warm_engine_programs(eng, start=warmup)
if warmup:
    assert warm.wait(timeout=600), "warmup did not finish"

builds0 = programs.default_registry().counters()["builds"]
sched = Scheduler(eng, max_queue=4)
h = sched.submit(np.array([1, 2, 3]),
                 SamplingParams(max_new_tokens=4, temperature=0.9,
                                top_k=8, seed=0))
while h.status.value in ("queued", "running"):
    sched.step()
tokens = h.result(timeout=10)
assert len(tokens) == 4

counters = programs.default_registry().counters()
print(json.dumps({
    "counters": counters,
    "on_path_builds": counters["builds"] - builds0,
    "xla_compiles": programs.xla_compile_counter(),
    "warm": warm.stats(),
    "tokens": tokens,
}))
