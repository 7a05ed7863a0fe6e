#!/usr/bin/env bash
# Static-analysis gate (ISSUE 6) — the analysis unit suite plus the CLI
# over the real package, run NEXT TO ci_tier1/ci_faults/ci_sim/ci_serve/
# ci_chaos. The unit suite pins the walker/auditor/linter semantics on
# crafted programs and snippets; the CLI run proves the shipped tree is
# clean end to end: jaxpr audit (zero unconsumed donations, zero
# hot-path host callbacks, zero f64 upcasts for trainer + engine
# programs), static comm reconciliation for all 16 strategy configs
# (incl. the ISSUE 10 noloco/dynamiq low-comm family and the ISSUE 12
# compressed outer loops), and the
# host-concurrency lint with zero unsuppressed violations. Pure host
# work — nothing is compiled or executed on a device; <90 s on the
# 2-core container.
#
# Usage: scripts/ci_analyze.sh   (from the repo root or anywhere)
set -o pipefail
cd "$(dirname "$0")/.."

rm -f /tmp/_analyze.log
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_analysis.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_analyze.log
rc=${PIPESTATUS[0]}
echo ANALYZE_DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
    /tmp/_analyze.log | tr -cd . | wc -c)
[ "$rc" -ne 0 ] && exit "$rc"

# CLI over the real package: machine-readable summary, grep the gate.
OUT=${GYM_TPU_CI_ANALYZE_OUT:-/tmp/gym_tpu_ci_analysis.json}
rm -f "$OUT"
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m gym_tpu.analysis \
    --json "$OUT"
rc=$?
[ "$rc" -ne 0 ] && { echo "ci_analyze: CLI reported violations"; exit "$rc"; }
python - "$OUT" <<'EOF'
import json, sys
report = json.load(open(sys.argv[1]))
assert report["violations"] == 0, report
sections = report["sections"]
assert set(sections) == {"lint", "trace", "audit"}
for name, summ in sections["trace"]["strategies"].items():
    assert summ["ok"], (name, summ)
# ISSUE 12 bump: + the compressed outer loops (diloco int8/topk,
# noloco int4, decoupled-momentum outer)
assert len(sections["trace"]["strategies"]) >= 16
# ISSUE 11 bump: + the quantized serving family (int8 weights + int8
# paged KV — paged prefill x2, CoW, paged decode, spec decode);
# ISSUE 12: + the 4 compressed-outer-loop trainer steps;
# ISSUE 16: + the 6 elastic redistribution programs (reshard_flat x3,
# replicate_rows x2, unshard_params)
# ISSUE 29: - the unpaged slot ring's four (prefill x2, admit, decode)
assert len(sections["audit"]["programs"]) >= 32
# ISSUE 9 gate: the auditor's serve+elastic key set and the
# device-program registry's key set are THE SAME set — enumeration and
# acquisition cannot drift apart
recon = sections["audit"]["registry"]
assert recon["key_set_match"], recon
assert recon["n_registry_keys"] == recon["n_audit_serve_keys"] >= 16, recon
# ISSUE 16 gate: the elastic reshard family is enumerated, audited and
# donation-clean (violations==0 above covers the findings)
enames = [p["name"] for p in sections["audit"]["programs"]
          if p["name"].startswith("elastic.")]
assert len(enames) >= 6, enames
# ISSUE 11 gate: quantized programs are registered + audited with
# dtype-tagged names, donation-clean (violations==0 above covers them)
qnames = [p["name"] for p in sections["audit"]["programs"]
          if "w=int8" in p["name"]]
assert len(qnames) >= 4, qnames
print("ci_analyze: violations=0 across",
      len(sections["trace"]["strategies"]), "strategy configs and",
      len(sections["audit"]["programs"]), "programs;",
      "registry reconciliation:", recon["n_registry_keys"], "keys match")
EOF
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"
echo "ci_analyze: OK (report at $OUT)"
exit 0
