#!/usr/bin/env bash
# CI entry point: the tier-1 suite on CPU plus the benchmark smoke step.
#
# The suite already includes the multi-device distributed tests --
# tests/test_dist.py and tests/test_serve_policy.py spawn subprocesses with
# --xla_force_host_platform_device_count so the main pytest process keeps
# the single-device view (see the module docstrings there).
#
# PYTHONPATH=src is exported for parity with ROADMAP's tier-1 command, but
# either `pip install -e .` or tests/conftest.py makes it optional.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== tier-1: pytest =="
python -m pytest -x -q "$@"

echo "== smoke: benchmark harness (--dry) =="
python -m benchmarks.run --dry

echo "== smoke: overlap collectives (--dry, 4 host devices) =="
# Exercise the serpentine ring path end to end on every run: the forced
# 4-device host mesh lets the plan printout AND the lowered HLO (both
# ppermute directions) come from a real mesh, not a degenerate one.
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m benchmarks.run --dry --collectives=serpentine

echo "== smoke: hierarchical planner (forced 2-host x 4-chip dry plan) =="
# The recursive planner (repro.plan) end to end on every run: the forced
# DCN level must appear in the printed tree, and the synthetic 65 GiB
# state (np*=5 on 16 GiB chips) must show the divisor-quantized FSDP
# degree (5 -> 8 on the 8-chip extent).
plan_out="$(python -m benchmarks.run --only plan --hosts 2 --chips 4)"
printf '%s\n' "$plan_out"
printf '%s\n' "$plan_out" | grep -q 'DCN\[mesh\]' \
    || { echo "FAIL: plan tree is missing the DCN level"; exit 1; }
printf '%s\n' "$plan_out" | grep -q 'np_raw=5 quantized=8' \
    || { echo "FAIL: plan tree is missing the quantized FSDP degree"; exit 1; }

echo "== smoke: plan-driven serving (forced 4-device dry) =="
# The serving engine's decode plan end to end on every run: a single-host
# 4-way TP mesh must produce a DCN-free plan whose KV page, as the paged
# kernel lays it out in VMEM, fits the VMEM leaf (DESIGN.md §7).
serve_out="$(XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m benchmarks.run --only serve --dry)"
printf '%s\n' "$serve_out"
printf '%s\n' "$serve_out" | grep -q 'dcn_free=True' \
    || { echo "FAIL: serve plan is not DCN-free"; exit 1; }
printf '%s\n' "$serve_out" | grep -q 'page_fits_vmem=True' \
    || { echo "FAIL: serve plan page does not fit VMEM"; exit 1; }

echo "== smoke: paged KV pool (geometry vs page_plan) =="
# The paged engine end to end on every run: the pool's page size, table
# width and physical page count must come verbatim from plan_run's page
# level (DESIGN.md §8), and the drained pool must reconcile.
paged_out="$(python -m benchmarks.run --only paged --dry)"
printf '%s\n' "$paged_out"
printf '%s\n' "$paged_out" | grep -q 'pool_matches_plan=True' \
    || { echo "FAIL: paged pool geometry does not match page_plan"; exit 1; }

echo "== smoke: chunked prefill (chunk == planned page) =="
# Chunked prefill end to end on every run: every full prefill chunk the
# engine cuts must be exactly the planner's page -- the VMEM-fitting KV
# slice doubles as the prefill quantum (DESIGN.md §10).
prefill_out="$(python -m benchmarks.run --only prefill --dry)"
printf '%s\n' "$prefill_out"
printf '%s\n' "$prefill_out" | grep -q 'chunk_matches_page=True' \
    || { echo "FAIL: prefill chunk does not match the planned page"; exit 1; }

echo "== smoke: radix prefix cache (capacity vs plan budget) =="
# The cross-request prefix cache end to end on every run: the radix
# cache's byte capacity must be exactly the mesh-level HBM leftover the
# planner recorded (plan.prefix_budget(), DESIGN.md §11), and a request
# sharing a published prefix must hit it.
prefix_out="$(python -m benchmarks.run --only prefix --dry)"
printf '%s\n' "$prefix_out"
printf '%s\n' "$prefix_out" | grep -q 'prefix_budget_matches_plan=True' \
    || { echo "FAIL: radix cache capacity does not match the plan"; exit 1; }

echo "== smoke: tuning sweep (--dry: enumerate + VMEM filter) =="
# The autotuning harness end to end on every run, without timing anything:
# every swept candidate -- the analytic center and all its power-of-two
# neighbors -- must pass the planner's own VMEM working-set filter
# (DESIGN.md §9).
tune_out="$(python -m benchmarks.run --only tune --dry)"
printf '%s\n' "$tune_out"
printf '%s\n' "$tune_out" | grep -q 'all_candidates_fit_vmem=True' \
    || { echo "FAIL: a swept candidate exceeds the level budget"; exit 1; }

echo "== smoke: cluster fleet (replicas == DCN np, pool == plan) =="
# Multi-replica serving end to end on every run (DESIGN.md §12): the
# cluster must stand up exactly the DCN level's np replicas, each
# replica's pool geometry must be the single-host plan's page_table
# (the DCN level chooses width, never reshapes the per-replica
# subtree), and a DCN-bearing plan without cluster= must raise the
# structured PlanError.
cluster_out="$(python -m benchmarks.run --only cluster --dry)"
printf '%s\n' "$cluster_out"
printf '%s\n' "$cluster_out" | grep -q 'replicas_match_plan=True' \
    || { echo "FAIL: fleet width does not match the DCN level"; exit 1; }
printf '%s\n' "$cluster_out" | grep -q 'pool_matches_plan=True' \
    || { echo "FAIL: per-replica pool differs from the plan page_table"; exit 1; }
printf '%s\n' "$cluster_out" | grep -q 'dcn_guard_raises=True' \
    || { echo "FAIL: single-replica DCN guard did not raise PlanError"; exit 1; }

echo "== smoke: observability (trace schema + plan-vs-actual) =="
# The obs spine end to end on every run (DESIGN.md §13): the tracer's
# Chrome export must validate against the trace_event schema, every
# plan-vs-actual residual must be finite, and the pool's observed peak
# must land inside the plan's page_table budget.
obs_out="$(python -m benchmarks.run --only obs --dry)"
printf '%s\n' "$obs_out"
printf '%s\n' "$obs_out" | grep -q 'trace_schema_ok=True' \
    || { echo "FAIL: Chrome trace export does not validate"; exit 1; }
printf '%s\n' "$obs_out" | grep -q 'plan_vs_actual_ok=True' \
    || { echo "FAIL: a plan-vs-actual residual is not finite"; exit 1; }
printf '%s\n' "$obs_out" | grep -q 'pool_peak_within_plan=True' \
    || { echo "FAIL: observed pool peak exceeds the planned page_table"; exit 1; }

echo "== smoke: BENCH json emitter (schema repro-bench-v1) =="
# Every benchmark run must be able to write a committable perf artifact:
# run the cheap dry sections through --json and check the schema keys.
bench_json="$(mktemp /tmp/bench_ci_XXXX.json)"
python -m benchmarks.run --dry --only serve,paged,prefill,prefix,tune,cluster,obs \
    --json "$bench_json" > /dev/null
python - "$bench_json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "repro-bench-v1", doc.get("schema")
assert isinstance(doc["rows"], list) and doc["rows"], "no rows"
for row in doc["rows"]:
    assert set(row) == {"section", "name", "us_per_call", "derived"}, row
    assert isinstance(row["derived"], dict), row
assert {"created_unix", "argv", "backend", "device"} <= set(doc)
print(f"BENCH json OK: {len(doc['rows'])} rows")
EOF
rm -f "$bench_json"

echo "== smoke: committed BENCH_10.json (obs artifact) =="
# The committed observability benchmark artifact must stay parseable
# against the same schema.
python - BENCH_10.json <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "repro-bench-v1", doc.get("schema")
assert {"created_unix", "argv", "backend", "device"} <= set(doc)
rows = doc["rows"]
assert rows, "no rows"
for row in rows:
    assert set(row) == {"section", "name", "us_per_call", "derived"}, row
assert any(r["name"].startswith("obs_planvsactual_") for r in rows), \
    "missing plan-vs-actual rows"
assert any(r["name"].startswith("obs_ab_trace_") for r in rows), \
    "missing tracing A/B rows"
print(f"BENCH_10 OK: {len(rows)} rows")
EOF

echo "CI OK"
