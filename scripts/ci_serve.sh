#!/usr/bin/env bash
# Serving gate (ISSUE 4 + ISSUE 7) — the serve/decode/paged unit suites
# plus one CLI smoke run through the real HTTP entry point, run NEXT TO
# scripts/ci_tier1.sh, ci_faults.sh and ci_sim.sh. The unit suites pin
# the engine-vs-generate_fast parity oracle (the page pool: an engine
# built with defaults, prefix-shared, speculative), teacher-forcing
# logits, bounded prefill
# compilation and the params-only restore; the smoke run proves
# `python -m gym_tpu.serve` end to end: train a tiny checkpoint, serve
# it (from the page pool, the only cache), answer 4 CONCURRENT requests, prove PREFIX
# SHARING live (two requests sharing a prompt prefix ->
# prefix_hit_blocks > 0 in /stats), then the SIGTERM drill — the server
# must exit rc=0 with a clean-shutdown line and a tokens_per_s
# headline. A second pass re-serves QUANTIZED (ISSUE 11: --quant int8
# --kv-quant int8 → 200s, /stats echoes the dtypes, and a warmed
# restart serves with programs_compiled=0). CPU-only; sized for the
# 2-core container.
#
# Usage: scripts/ci_serve.sh   (from the repo root or anywhere)
set -o pipefail
cd "$(dirname "$0")/.."
REPO="$(pwd)"

rm -f /tmp/_serve.log
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_serve.py tests/test_serve_paged.py tests/test_decode.py \
    -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_serve.log
rc=${PIPESTATUS[0]}
echo SERVE_DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
    /tmp/_serve.log | tr -cd . | wc -c)
[ "$rc" -ne 0 ] && exit "$rc"

# CLI smoke: tiny checkpoint -> HTTP server -> 4 concurrent requests ->
# SIGTERM drill. Fresh dir per run.
OUT=${GYM_TPU_CI_SERVE_OUT:-/tmp/gym_tpu_ci_serve}
PORT=${GYM_TPU_CI_SERVE_PORT:-8741}
rm -rf "$OUT"; mkdir -p "$OUT"

timeout -k 10 300 env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" python - "$OUT" <<'EOF'
import sys, numpy as np
from gym_tpu import Trainer
from gym_tpu.data import ArrayDataset
from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.strategy.optim import OptimSpec
from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy

out = sys.argv[1]
cfg = GPTConfig(block_size=32, vocab_size=48, n_layer=2, n_head=2,
                n_embd=32, dropout=0.0)
rng = np.random.default_rng(0)
toks = rng.integers(0, 48, (64, 33))
ds = ArrayDataset(toks[:, :-1].astype(np.int64),
                  toks[:, 1:].astype(np.int64))
Trainer(GPT(cfg), ds).fit(
    strategy=SimpleReduceStrategy(optim_spec=OptimSpec("adamw", lr=1e-3)),
    num_nodes=1, max_steps=4, batch_size=4, val_size=0, val_interval=0,
    show_progress=False, seed=1, checkpoint_interval=4,
    save_dir=out + "/ckpts", run_name="ci", log_dir=out + "/logs")
print("ci_serve: checkpoint trained")
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: training the smoke ckpt failed"; exit "$rc"; }

# bare `python ... &` so $! is the server pid, not a subshell's.
# --program-cache-dir seeds the device-program registry's persistent
# executable tier — the restart drill below re-serves against it.
env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" python -m gym_tpu.serve \
    --ckpt "$OUT/ckpts/ci" --port "$PORT" --num_slots 2 --device cpu \
    --program-cache-dir "$OUT/progcache" \
    > "$OUT/server.log" 2>&1 &
SRV=$!
for _ in $(seq 1 90); do
    grep -q "listening" "$OUT/server.log" && break
    kill -0 "$SRV" 2>/dev/null || { echo "ci_serve: server died at startup";
        cat "$OUT/server.log"; exit 1; }
    sleep 1
done
grep -q "listening" "$OUT/server.log" || {
    echo "ci_serve: server never started"; kill -9 "$SRV"; exit 1; }

timeout -k 10 180 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import concurrent.futures, json, os, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]

def gen(seed):
    body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6,
                       "top_k": 4, "seed": seed}).encode()
    r = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", body,
        {"Content-Type": "application/json"}), timeout=120)
    return json.loads(r.read())

with concurrent.futures.ThreadPoolExecutor(4) as ex:
    outs = list(ex.map(gen, range(4)))
assert len(outs) == 4
for o in outs:
    assert len(o["tokens"]) == 6, o
    print("ci_serve: completion", o["tokens"], "ttft", o["ttft_s"])
stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/stats", timeout=10).read())
assert stats["requests_done"] == 4, stats
print("ci_serve: tokens_per_s =", stats["tokens_per_s"])

# ISSUE 7 smoke: two requests sharing a 16-token prefix (one page at
# the default page_size 16 on this block-32 checkpoint) -> the second
# admit must hit the prefix cache, observable via /stats
assert stats.get("paged"), f"server not paged: {stats}"
shared = list(range(1, 17))
for tail in ([17], [18]):
    body = json.dumps({"prompt": shared + tail, "max_new_tokens": 4,
                       "top_k": 4, "seed": 9}).encode()
    r = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", body,
        {"Content-Type": "application/json"}), timeout=120)
    assert len(json.loads(r.read())["tokens"]) == 4
stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/stats", timeout=10).read())
assert stats["prefix_hit_blocks"] > 0, stats
print("ci_serve: prefix_hit_blocks =", stats["prefix_hit_blocks"],
      "kv_blocks_in_use =", stats["kv_blocks_in_use"])
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: HTTP requests failed";
    cat "$OUT/server.log"; kill -9 "$SRV"; exit "$rc"; }

# let the background AOT warmup finish before killing the server: the
# restart drill needs EVERY program persisted to the cache dir, not
# just the ones the requests above happened to touch
timeout -k 10 120 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import json, os, time, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]
deadline = time.monotonic() + 110
while time.monotonic() < deadline:
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/stats", timeout=10).read())
    w = stats.get("warmup")
    if w is None or w.get("done"):
        print("ci_serve: warmup done:", w)
        break
    time.sleep(1)
else:
    raise SystemExit(f"warmup never finished: {stats.get('warmup')}")
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: warmup wait failed";
    cat "$OUT/server.log"; kill -9 "$SRV"; exit "$rc"; }

# SIGTERM drill: clean exit 0, shutdown line, headline line
kill -TERM "$SRV"
wait "$SRV"; rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: server exit rc=$rc after SIGTERM";
    cat "$OUT/server.log"; exit 1; }
grep -q "shut down cleanly" "$OUT/server.log" || {
    echo "ci_serve: no clean-shutdown line"; cat "$OUT/server.log"; exit 1; }
grep -q "tokens_per_s" "$OUT/server.log" || {
    echo "ci_serve: no tokens_per_s headline"; cat "$OUT/server.log"; exit 1; }
head -1 "$OUT/ckpts/ci/serve/serve.csv" | grep -q "ts_s,kind" || {
    echo "ci_serve: serve.csv missing/markerless"; exit 1; }

# Restart drill (ISSUE 9): re-serve the SAME config against the seeded
# program cache — the device-program registry must deserialize every
# executable instead of compiling. Gate: first request returns 200 AND
# /stats reports programs_compiled=0 (zero XLA compiles in the whole
# restarted process).
env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" python -m gym_tpu.serve \
    --ckpt "$OUT/ckpts/ci" --port "$PORT" --num_slots 2 --device cpu \
    --program-cache-dir "$OUT/progcache" \
    > "$OUT/server2.log" 2>&1 &
SRV=$!
for _ in $(seq 1 90); do
    grep -q "listening" "$OUT/server2.log" && break
    kill -0 "$SRV" 2>/dev/null || { echo "ci_serve: restarted server died";
        cat "$OUT/server2.log"; exit 1; }
    sleep 1
done
grep -q "listening" "$OUT/server2.log" || {
    echo "ci_serve: restarted server never started"; kill -9 "$SRV"; exit 1; }

timeout -k 10 180 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import json, os, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]
body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6,
                   "top_k": 4, "seed": 0}).encode()
r = urllib.request.urlopen(urllib.request.Request(
    f"http://127.0.0.1:{port}/generate", body,
    {"Content-Type": "application/json"}), timeout=120)
assert r.status == 200, r.status
assert len(json.loads(r.read())["tokens"]) == 6
stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/stats", timeout=10).read())
assert stats["programs_compiled"] == 0, (
    f"restart recompiled {stats['programs_compiled']} programs "
    f"(registry: {stats.get('programs')})")
print("ci_serve: restart drill — first request 200,",
      "programs_compiled =", stats["programs_compiled"])
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: restart drill failed";
    cat "$OUT/server2.log"; kill -9 "$SRV"; exit "$rc"; }
kill -TERM "$SRV"
wait "$SRV"; rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: restarted server exit rc=$rc";
    cat "$OUT/server2.log"; exit 1; }

# Quantized live smoke (ISSUE 11): serve the SAME checkpoint with
# --quant int8 --kv-quant int8 — requests answer 200, /stats echoes the
# dtypes and the f32-normalized pool capacity, and after the background
# warmup a process RESTART against the quantized program cache serves
# with programs_compiled=0 (the warmup family covers the quantized
# programs too).
env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" python -m gym_tpu.serve \
    --ckpt "$OUT/ckpts/ci" --port "$PORT" --num_slots 2 --device cpu \
    --quant int8 --kv-quant int8 \
    --program-cache-dir "$OUT/progcache_q" \
    > "$OUT/server3.log" 2>&1 &
SRV=$!
for _ in $(seq 1 90); do
    grep -q "listening" "$OUT/server3.log" && break
    kill -0 "$SRV" 2>/dev/null || { echo "ci_serve: quantized server died";
        cat "$OUT/server3.log"; exit 1; }
    sleep 1
done
grep -q "listening" "$OUT/server3.log" || {
    echo "ci_serve: quantized server never started"; kill -9 "$SRV"; exit 1; }

timeout -k 10 180 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import json, os, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]
for seed in range(2):
    body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6,
                       "top_k": 4, "seed": seed}).encode()
    r = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", body,
        {"Content-Type": "application/json"}), timeout=120)
    assert r.status == 200, r.status
    assert len(json.loads(r.read())["tokens"]) == 6
stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/stats", timeout=10).read())
assert stats["weights_dtype"] == "int8", stats.get("weights_dtype")
assert stats["kv_dtype"] == "int8", stats.get("kv_dtype")
assert stats["kv_blocks_capacity_effective"] == 4 * (stats["kv_pages"] - 1), \
    (stats["kv_blocks_capacity_effective"], stats["kv_pages"])
assert stats["requests_done"] == 2, stats["requests_done"]
print("ci_serve: quantized smoke — weights", stats["weights_dtype"],
      "kv", stats["kv_dtype"],
      "capacity_eff", stats["kv_blocks_capacity_effective"],
      "weights_bytes", stats["weights_bytes"])
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: quantized smoke failed";
    cat "$OUT/server3.log"; kill -9 "$SRV"; exit "$rc"; }

# wait for the quantized warmup so every quantized program persists
timeout -k 10 120 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import json, os, time, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]
deadline = time.monotonic() + 110
while time.monotonic() < deadline:
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/stats", timeout=10).read())
    w = stats.get("warmup")
    if w is None or w.get("done"):
        print("ci_serve: quantized warmup done:", w)
        break
    time.sleep(1)
else:
    raise SystemExit(f"quantized warmup never finished: {stats.get('warmup')}")
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: quantized warmup wait failed";
    cat "$OUT/server3.log"; kill -9 "$SRV"; exit "$rc"; }

kill -TERM "$SRV"
wait "$SRV"; rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: quantized server exit rc=$rc";
    cat "$OUT/server3.log"; exit 1; }

# quantized restart drill: a warmed restart must serve quantized with
# ZERO XLA compiles (the ISSUE 11 acceptance bar)
env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" python -m gym_tpu.serve \
    --ckpt "$OUT/ckpts/ci" --port "$PORT" --num_slots 2 --device cpu \
    --quant int8 --kv-quant int8 \
    --program-cache-dir "$OUT/progcache_q" \
    > "$OUT/server4.log" 2>&1 &
SRV=$!
for _ in $(seq 1 90); do
    grep -q "listening" "$OUT/server4.log" && break
    kill -0 "$SRV" 2>/dev/null || {
        echo "ci_serve: quantized restart died";
        cat "$OUT/server4.log"; exit 1; }
    sleep 1
done
grep -q "listening" "$OUT/server4.log" || {
    echo "ci_serve: quantized restart never started"; kill -9 "$SRV"; exit 1; }

timeout -k 10 180 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import json, os, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]
body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6,
                   "top_k": 4, "seed": 0}).encode()
r = urllib.request.urlopen(urllib.request.Request(
    f"http://127.0.0.1:{port}/generate", body,
    {"Content-Type": "application/json"}), timeout=120)
assert r.status == 200, r.status
assert len(json.loads(r.read())["tokens"]) == 6
stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/stats", timeout=10).read())
assert stats["weights_dtype"] == "int8" and stats["kv_dtype"] == "int8"
assert stats["programs_compiled"] == 0, (
    f"quantized restart recompiled {stats['programs_compiled']} programs "
    f"(registry: {stats.get('programs')})")
print("ci_serve: quantized restart drill — first request 200,",
      "programs_compiled =", stats["programs_compiled"])
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: quantized restart drill failed";
    cat "$OUT/server4.log"; kill -9 "$SRV"; exit "$rc"; }
kill -TERM "$SRV"
wait "$SRV"; rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: quantized restart exit rc=$rc";
    cat "$OUT/server4.log"; exit 1; }

# Out-of-process fleet drill (ISSUE 13): re-serve the same checkpoint
# with --out-of-process --replicas 2 against the f32 program cache the
# first server seeded. Gate: spawned replica WORKER PROCESSES each
# report programs_compiled=0 in /stats (zero XLA compiles off the warm
# persistent tier — what makes autoscaler spawns cheap), a streamed
# request delivers chunked SSE, and SIGTERM reaps both workers (exit 0).
env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" python -m gym_tpu.serve \
    --ckpt "$OUT/ckpts/ci" --port "$PORT" --num_slots 2 --device cpu \
    --out-of-process --replicas 2 \
    --program-cache-dir "$OUT/progcache" \
    > "$OUT/server5.log" 2>&1 &
SRV=$!
for _ in $(seq 1 180); do
    grep -q "listening" "$OUT/server5.log" && break
    kill -0 "$SRV" 2>/dev/null || { echo "ci_serve: process-fleet server died";
        cat "$OUT/server5.log"; exit 1; }
    sleep 1
done
grep -q "listening" "$OUT/server5.log" || {
    echo "ci_serve: process-fleet server never started"; kill -9 "$SRV"; exit 1; }

timeout -k 10 180 env GYM_TPU_CI_SERVE_PORT="$PORT" python - <<'EOF'
import json, os, urllib.request

port = os.environ["GYM_TPU_CI_SERVE_PORT"]
# streamed request: chunked SSE, done event carries ttft
body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6,
                   "top_k": 4, "seed": 0, "stream": True}).encode()
r = urllib.request.urlopen(urllib.request.Request(
    f"http://127.0.0.1:{port}/generate", body,
    {"Content-Type": "application/json"}), timeout=120)
assert r.headers["Content-Type"] == "text/event-stream", dict(r.headers)
events = [json.loads(line[6:]) for line in r
          if line.strip().startswith(b"data: ")]
toks = [t for e in events if not e.get("done")
        for t in e.get("tokens", [])]
fin = events[-1]
assert fin.get("done") is True and len(toks) == 6, events
print("ci_serve: process-fleet streamed request ok, ttft", fin["ttft_s"])

stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/stats", timeout=10).read())
assert stats.get("fleet") == "process", stats.get("fleet")
live = [r for r in stats["replicas"] if not r["retired"]]
assert len(live) == 2 and stats["healthy_replicas"] == 2, stats["replicas"]
pids = {r["pid"] for r in live}
assert len(pids) == 2 and os.getpid() not in pids, pids
for rep in live:
    assert rep["programs_compiled"] == 0, (
        f"worker {rep['id']} (pid {rep['pid']}) compiled "
        f"{rep['programs_compiled']} programs — persistent tier miss")
assert stats["replicas_spawned"] == 2, stats["replicas_spawned"]
print("ci_serve: spawned workers report programs_compiled=0, pids", pids)
EOF
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: process-fleet drill failed";
    cat "$OUT/server5.log"; kill -9 "$SRV"; exit "$rc"; }

kill -TERM "$SRV"
wait "$SRV"; rc=$?
[ "$rc" -ne 0 ] && { echo "ci_serve: process-fleet exit rc=$rc";
    cat "$OUT/server5.log"; exit 1; }
grep -q "shut down cleanly" "$OUT/server5.log" || {
    echo "ci_serve: no clean-shutdown line (process fleet)";
    cat "$OUT/server5.log"; exit 1; }
pgrep -f "gym_tpu.serve.worker" > /dev/null && {
    echo "ci_serve: leaked worker processes:"; pgrep -af "gym_tpu.serve.worker";
    exit 1; }
echo "ci_serve: process-fleet drill OK"

echo "ci_serve: OK (log at $OUT/server.log)"
exit 0
