#!/usr/bin/env bash
# One-shot CI gate: style lint (ruff) + tune table gate (checked-in
# kernel-config legality + stale structural winners) + structural
# kernel-search smoke + the `analysis all` umbrella (rocketlint +
# every audit family — shard/prec/sched/serve/calib/mem/repro/fault —
# one process, one merged findings list, budgets diffed per family) +
# seeded-bad true-positive legs (badoverlap, drifted calib, badmem,
# badrepro, badfault) + obs telemetry smoke + resilience smoke
# (supervised restart / drain) + the tier-1 test suite (command from
# ROADMAP.md; CPU only — it carries the described-chip kernel compiles
# and the chip_smoke.py rehearsal).
# Exits non-zero on the first failing stage.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== ruff (style / imports) =="
if command -v ruff >/dev/null 2>&1; then
    ruff check rocket_tpu tests scripts examples bench.py
else
    echo "ruff not installed - skipping style lint (config in pyproject.toml)"
fi

echo "== tune table gate (schema + legality of checked-in kernel configs) =="
# Validates every entry in rocket_tpu/tune/configs/*.json: schema
# fields, known device kinds, bucket/shape consistency, a fresh
# legality re-verification against each kernel's TuneSpace, and the
# stale-structural-winner check — a stale or hand-edited table cannot
# ship an illegal launch config or a retired kernel variant.
JAX_PLATFORMS=cpu python -m rocket_tpu.tune --check-table

echo "== structural kernel search smoke (enumerate -> verify -> table round-trip + seeded-bad rejection) =="
# The generate-and-verify loop on CPU interpret mode (ISSUE 14): the
# structural TuneSpaces (fused_conv / block_attn) must enumerate their
# variant candidates and pass fwd+bwd parity on every one, a written
# structural winner must round-trip through the runtime lookup and
# tables_summary, a seeded wrong-but-fast fake variant must be REJECTED
# by the parity gate before timing, and a table entry pinning a retired
# variant must fail the table gate loudly.
JAX_PLATFORMS=cpu python scripts/tune_structural_smoke.py

echo "== analysis all (rocketlint + every audit family, one invocation) =="
# Replaces the per-family invocations: rocketlint over rocket_tpu/
# plus shard/prec/sched/serve/calib/mem/repro/fault, each family
# diffed against its canonical subdirectory of tests/fixtures/budgets/
# (>10% growth fails; calib uses tolerance 0.5 because its measured
# side is a live timing on a CPU container; repro fingerprints gate on
# exact equality). The merged findings land in
# runs/audit_reports/check.json — the artifact CI uploads on failure.
mkdir -p runs/audit_reports
JAX_PLATFORMS=cpu python -m rocket_tpu.analysis all rocket_tpu/ \
    --budgets tests/fixtures/budgets --calib-tolerance 0.5 \
    --json-report runs/audit_reports/check.json

echo "== overlap true-positive (seeded-bad badoverlap demo) =="
# The overlapped-collective rules must still FIND the unoverlapped
# shape they were built to kill: the seeded-bad per-param grad-psum
# convoy + sync all-gather demo must report RKT501 AND RKT502.
if JAX_PLATFORMS=cpu python -m rocket_tpu.analysis sched \
        --target badoverlap >/tmp/_badoverlap.txt 2>&1; then
    echo "badoverlap demo reported no findings - rules are broken"
    exit 1
fi
grep -q "RKT501" /tmp/_badoverlap.txt && grep -q "RKT502" /tmp/_badoverlap.txt || {
    echo "badoverlap demo missing RKT501/RKT502:"; cat /tmp/_badoverlap.txt; exit 1;
}

echo "== calibration drift true-positive (seeded-bad drifted budget) =="
# The drift gate must still FIND things: a committed budget claiming
# far tighter calibration than this machine can produce (the drifted
# fixture) must fail with RKT701.
if JAX_PLATFORMS=cpu python -m rocket_tpu.analysis calib \
        --target gpt2_sentinel \
        --budgets tests/fixtures/budgets/calib_drifted \
        --tolerance 0.5 >/tmp/_calib_drift.txt 2>&1; then
    echo "drifted calib budget passed the gate - RKT701 is broken"
    exit 1
fi
grep -q "RKT701" /tmp/_calib_drift.txt || {
    echo "drifted-budget leg missing RKT701:"; cat /tmp/_calib_drift.txt; exit 1;
}

echo "== memory true-positive (seeded-bad badmem demo) =="
# The memory rules must still FIND the failure they were built to
# kill: the undonated, remat-free long-chain demo must report exactly
# the seeded set - RKT801 (undonated state), RKT802 (remat
# ineffective) and RKT804 (over the seeded 2 MiB capacity).
if JAX_PLATFORMS=cpu python -m rocket_tpu.analysis mem \
        --target badmem --format json >/tmp/_badmem.json 2>&1; then
    echo "badmem demo reported no findings - rules are broken"
    exit 1
fi
python - <<'PY' || { echo "badmem demo rule set drifted:"; cat /tmp/_badmem.json; exit 1; }
import json
rules = {f["rule"] for f in json.load(open("/tmp/_badmem.json"))}
assert rules == {"RKT801", "RKT802", "RKT804"}, rules
PY

echo "== repro true-positive (seeded-bad badrepro demo) =="
# The determinism rules must still FIND what they were built to kill:
# the seeded reused key + unfolded loop key + non-unique float scatter
# demo must report exactly RKT901 and RKT902 — no more (rule precision)
# and no less (rule sensitivity).
if JAX_PLATFORMS=cpu python -m rocket_tpu.analysis repro \
        --target badrepro --format json >/tmp/_badrepro.json 2>&1; then
    echo "badrepro demo reported no findings - rules are broken"
    exit 1
fi
python - <<'PY' || { echo "badrepro demo rule set drifted:"; cat /tmp/_badrepro.json; exit 1; }
import json
rules = {f["rule"] for f in json.load(open("/tmp/_badrepro.json"))}
assert rules == {"RKT901", "RKT902"}, rules
PY

echo "== fault true-positive (seeded-bad badfault demo) =="
# The crash-consistency rules must still FIND what they were built to
# kill: the marker-first / unsynced-rename save order plus the
# drained-without-checkpoint transition function must report exactly
# RKT1001 + RKT1002 + RKT1003 — no more (RKT1004 precision: the demo
# keeps every terminal reachable) and no less.
if JAX_PLATFORMS=cpu python -m rocket_tpu.analysis fault \
        --target badfault --format json >/tmp/_badfault.json 2>&1; then
    echo "badfault demo reported no findings - rules are broken"
    exit 1
fi
python - <<'PY' || { echo "badfault demo rule set drifted:"; cat /tmp/_badfault.json; exit 1; }
import json
rules = {f["rule"] for f in json.load(open("/tmp/_badfault.json"))}
assert rules == {"RKT1001", "RKT1002", "RKT1003"}, rules
PY

echo "== obs smoke (telemetry + health sentinels + strict step path) =="
# Tier-1 example run with telemetry AND health sentinels on:
# telemetry.json must exist and parse, goodput categories must sum to
# wall-clock, the span file must be valid Chrome-trace JSON, the health
# gauges must be populated with zero anomalies, and the strict transfer
# guard stays green with all instrumentation active.
JAX_PLATFORMS=cpu python scripts/obs_smoke.py

echo "== blackbox smoke (injected NaN -> skip_step / forensic bundle) =="
# A poisoned batch under anomaly_action=skip_step must finish with finite
# params and a counted skip; under dump_and_halt it must halt and leave a
# complete runs/**/blackbox/ bundle the post-mortem CLI renders.
JAX_PLATFORMS=cpu python scripts/blackbox_smoke.py

echo "== resilience smoke (supervised restart after injected kill + SIGTERM drain) =="
# The supervised launcher must survive deterministic fault injection:
# one leg SIGKILLs the worker mid-run (supervisor restarts from the
# latest checkpoint, training reaches the target step, goodput_fraction
# >= 0.5 in supervisor.json), one leg SIGTERMs the supervisor (worker
# drains: emergency checkpoint + distinguished drained exit code, and a
# fresh supervised launch resumes from it).
JAX_PLATFORMS=cpu python scripts/resilience_smoke.py

echo "== serve smoke (continuous batching + paged KV + compiled-once + k-wave scan + request timelines) =="
# A 50-request synthetic workload through rocket_tpu.serve plus the
# python -m rocket_tpu.serve CLI: every request must complete, the decode
# wave / prefill chunk must each compile exactly ONCE (zero retraces
# across admissions/evictions — checked against the obs gauges in
# telemetry.json), and greedy outputs must match generate(). The scanned
# leg re-serves an identical workload with decode_waves_per_dispatch=4:
# greedy outputs bit-identical to k=1, zero retraces, and exactly one
# jax.device_get per dispatch of k waves (the dispatch amortization). The
# timeline leg (obs.reqtrace) preempts+resumes requests on a starved
# pool and gates the tail-forensics chain: one waterfall spanning both
# residencies, phases summing to wall time within 5%, the seeded SLO
# violation naming the window's exemplars, obs timeline rendering them.
JAX_PLATFORMS=cpu python scripts/serve_smoke.py

echo "== tier-1 tests =="
# CPU only. The suite includes tests/test_tpu_compile.py (the main paths'
# pallas kernels compiled for a DESCRIBED v5e chip — skips where no TPU
# compiler is installed) and tests/test_chip_smoke.py (chip_smoke.py's
# phases rehearsed at a tiny width). Nothing here sets
# ALLOW_MULTIPLE_LIBTPU_LOAD or touches libtpu's lock file: one process
# at a time loads the TPU library, and that file's fixture is the only
# place that does.
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit $rc
