#!/usr/bin/env bash
# Continuous-deployment gate (ISSUE 15) — the seventh CI gate, run NEXT
# TO ci_tier1 / ci_faults / ci_sim / ci_serve / ci_chaos / ci_analyze:
#
# 1. the servesim unit suite (trace determinism, cost-model policy
#    invariants, replay, serve.csv schema satellites);
# 2. the serving-policy FRONTIER regression gate against the committed
#    baseline (logs/servesim/frontier_baseline.json) — deterministic
#    cost-model path, seconds;
# 3. the CLOSED TRAIN->DEPLOY LOOP drill: a live trainer (SIGKILLed
#    mid-run and resumed — the PR-2 kill harness) streams checkpoints
#    into a reload-watching OUT-OF-PROCESS fleet while a trace replays
#    open-loop. Gates: zero dropped requests, zero recompiles across
#    every hot-swap (per-worker program counters), post-swap streams
#    byte-exact vs generate_fast;
# 4. the sim-vs-live agreement at the overload point (the `slow` case
#    `tests/test_servesim.py::test_sim_vs_live_smoke[overload]`): one
#    flash-crowd trace through a real replica and through the cost
#    model, p99 TTFT and shed rate inside the stated tolerances;
# 5. the TENANT frontier gate (ISSUE 17): the class-mix x quota-policy
#    grid re-priced on the cost model against the committed baseline
#    (logs/servesim/tenant/tenant_baseline.json) — every workload group
#    that met the interactive SLO must still meet it, batch goodput
#    must not collapse, and isolation ON must not hurt the victim.
#
# CPU-only; sized for the 2-core container.
#
# Usage: scripts/ci_deploy.sh   (from the repo root or anywhere)
set -o pipefail
cd "$(dirname "$0")/.."
REPO="$(pwd)"

rm -f /tmp/_deploy.log
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_servesim.py -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_deploy.log
rc=${PIPESTATUS[0]}
echo DEPLOY_DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
    /tmp/_deploy.log | tr -cd . | wc -c)
[ "$rc" -ne 0 ] && exit "$rc"

# policy-frontier regression gate (deterministic cost-model path)
timeout -k 10 300 env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" \
    python -m gym_tpu.servesim.frontier_gate \
    --baseline logs/servesim/frontier_baseline.json || {
    echo "ci_deploy: serving frontier regression"; exit 1; }

# tenant-isolation frontier gate (ISSUE 17, deterministic cost-model
# path): per-class SLO attainment + kept batch goodput vs the baseline
timeout -k 10 300 env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" \
    python -m gym_tpu.servesim.tenant_gate \
    --baseline logs/servesim/tenant/tenant_baseline.json || {
    echo "ci_deploy: tenant-isolation frontier regression"; exit 1; }

# the closed train->deploy loop: trainer (killed + resumed) ->
# --reload-watch process fleet -> open-loop trace replay; the drill
# asserts zero dropped / zero recompiles / post-swap streams exact and
# exits nonzero otherwise
OUT=${GYM_TPU_CI_DEPLOY_OUT:-/tmp/gym_tpu_ci_deploy}
rm -rf "$OUT"; mkdir -p "$OUT"
timeout -k 10 900 env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" \
    python -m gym_tpu.servesim.drill --out "$OUT/drill" \
    --replicas 2 --out-of-process --kill-trainer \
    2>&1 | tee "$OUT/drill.log" | grep -v '"POST /generate'
rc=${PIPESTATUS[0]}
[ "$rc" -ne 0 ] && { echo "ci_deploy: closed-loop drill failed";
    tail -40 "$OUT/drill.log"; exit "$rc"; }
grep -q '"ok": true' "$OUT/drill.log" || {
    echo "ci_deploy: drill reported not-ok"; tail -40 "$OUT/drill.log";
    exit 1; }
pgrep -f "gym_tpu.serve.worker" > /dev/null && {
    echo "ci_deploy: leaked worker processes:";
    pgrep -af "gym_tpu.serve.worker"; exit 1; }

# the sim-vs-live agreement contract at the overload point: a 24 s
# replay in real time, run alone so that no other load shares its clock
timeout -k 10 900 env JAX_PLATFORMS=cpu python -m pytest \
    "tests/test_servesim.py::test_sim_vs_live_smoke[overload]" -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_deploy: sim-vs-live agreement failed";
    exit "$rc"; }

echo "ci_deploy: OK"
exit 0
