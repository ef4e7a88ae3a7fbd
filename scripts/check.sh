#!/usr/bin/env bash
# Pre-merge gate: the cheap, hermetic checks that must pass before any
# test run is worth starting.  Used locally and as the first CI stage.
#
#   scripts/check.sh
#
# 1. kflint        — all nineteen project-invariant checkers, including
#                    the kf-verify interprocedural rules and the
#                    kf-shard axis-environment rules (docs/lint.md),
#                    over kungfu_tpu/, scripts/, benchmarks/, examples/,
#                    and __graft_entry__.py.  Findings fingerprinted
#                    in tests/lint_baseline.json are suppressed (legacy
#                    debt being ratcheted down); anything NOT in the
#                    baseline fails the gate.
# 1b. kf-shard +   — shard-axis / shard-spec / recompile-hazard /
#     handles        handle-discipline rerun WITHOUT the baseline: the
#                    sharding rules and the async-handle lifetime rule
#                    gate with an empty baseline (a mesh-axis typo, a
#                    resize hazard, or a leaked in-flight collective
#                    can never land as "legacy debt").
# 1c. kf-verify    — proto-verify rerun WITHOUT the baseline: the SPMD
#     protocol       protocol verifier (collective ordering, p2p tag
#                    pairing, deadlock-freedom over every ParallelPlan
#                    geometry <= 16 ranks, docs/lint.md) also gates
#                    empty — a divergent collective or an orphan tag is
#                    a distributed hang waiting to happen, never debt.
# 1e. ledger-schema— decision-ledger field names literal + declared in
#                    LEDGER_FIELDS, rerun WITHOUT the baseline: a typo'd
#                    field silently drops a decision's evidence from the
#                    kfhist --decisions replay — never debt.
# 1d. kf-det       — replay-taint / rng-discipline / reduction-order
#                    rerun WITHOUT the baseline: entropy reaching a
#                    consensus/rendezvous/commit/manifest sink, a
#                    reused PRNG key, or an unordered float fold breaks
#                    bitwise replay (docs/determinism.md) — never debt.
# 2. kftrace       — flight-recorder dump schema self-check (recorder
#                    and reader must agree byte-for-byte, docs/tracing.md)
# 3. kftop         — live-plane /cluster schema self-check (push wire
#                    format, view schema, and renderer must agree,
#                    docs/monitoring.md)
# 3b. adapt-demo   — kf-adapt interference A/B: chaos-degraded link,
#                    bandit majority vote, consensus-fenced lockstep
#                    strategy swap on every rank (docs/adaptation.md)
# 3c. persist-demo — kf-persist drill: preempt:all kills every rank,
#                    the -restore-from supervisor relaunches from the
#                    newest complete manifest, a halved world restores
#                    bitwise from the same directory
#                    (docs/persistence.md)
# 3d. kfhist       — durable sentinel history self-check: segmented
#                    ring write/seal/GC, torn-record skip, replayed
#                    changepoint verdict (docs/sentinel.md)
# 3e. sentinel     — kf-sentinel e2e gate: mid-run chaos onset, online
#                    changepoint alert, incident flight record naming
#                    the planted edge, offline kfhist replay identical
# 4. compileall    — every .py parses/compiles on this interpreter
# 5. flag stamps   — no sanitizer flags leaked into the production
#                    .buildflags stamp (variants must never mix)
# 6. tier-1 budget — the 'not slow' suite finishes green inside
#                    tests/tier1_budget.json budget_s (new heavy tests
#                    must be slow-marked, not squeezed into tier-1);
#                    KF_CHECK_SKIP_TIER1=1 skips for local iteration
set -euo pipefail

ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$ROOT"
fail=0

echo "== kflint (incl. kf-verify: collective-consistency, wire-contract, lock-order)"
KFLINT_ARGS=()
if [ -f tests/lint_baseline.json ]; then
    KFLINT_ARGS+=(--baseline tests/lint_baseline.json)
fi
if ! python3 scripts/kflint "${KFLINT_ARGS[@]}"; then
    fail=1
fi

echo "== empty-baseline gate (shard-axis, shard-spec, recompile-hazard, handle-discipline)"
# no --baseline on purpose: sharding/resize hazards and leaked async
# collective handles never ratchet
if ! python3 scripts/kflint --checker shard-axis --checker shard-spec \
        --checker recompile-hazard --checker handle-discipline; then
    fail=1
fi

echo "== empty-baseline gate (proto-verify: ordering, tag pairing, deadlock-freedom)"
# no --baseline on purpose: a protocol divergence never ratchets
if ! python3 scripts/kflint --proto; then
    fail=1
fi

echo "== empty-baseline gate (kf-det: replay-taint, rng-discipline, reduction-order)"
# no --baseline on purpose: replay divergence never ratchets — a
# finding here means a restart or replica would not reproduce bitwise
if ! python3 scripts/kflint --checker replay-taint \
        --checker rng-discipline --checker reduction-order; then
    fail=1
fi

echo "== empty-baseline gate (ledger-schema: decision-ledger field literacy)"
# no --baseline on purpose: a schema typo in a decision record never
# ratchets — the offline effect replay would silently lose evidence
if ! python3 scripts/kflint --checker ledger-schema; then
    fail=1
fi

echo "== kftrace self-check (dump schema round-trip)"
if ! python3 scripts/kftrace --self-check; then
    fail=1
fi

echo "== kftop self-check (/cluster schema round-trip)"
if ! python3 scripts/kftop --self-check; then
    fail=1
fi

echo "== kfhist self-check (durable history ring + offline verdict)"
# kf-sentinel's offline reader: segmented-ring write/seal/GC round-trip,
# torn-record skip, and the replayed changepoint verdict over a planted
# shift (docs/sentinel.md)
if ! python3 scripts/kfhist --self-check; then
    fail=1
fi

echo "== multislice-demo (emulated 2-slice slice-kill e2e)"
# the slice-loss recovery ladder, end to end: 2 emulated slices, chaos
# kills slice 1 whole at step 3, the surviving slice shrinks around it
# and finishes (docs/multislice.md).  Bounded: a wedged recovery must
# fail the gate, not hang it.
rm -f /tmp/_kf_multislice_demo.log
if ! timeout -k 10 240 python3 -m kungfu_tpu.runner.cli -np 4 \
        -num-slices 2 -tolerate-failures \
        -chaos 'die_slice:slice=1,step=3' \
        python3 examples/multislice_shrink.py --n-steps 8 \
        > /tmp/_kf_multislice_demo.log 2>&1 \
        || ! grep -q "multislice survived to step 8 on 2 workers" \
        /tmp/_kf_multislice_demo.log; then
    echo "ERROR: multislice demo did not survive the slice kill"
    tail -40 /tmp/_kf_multislice_demo.log || true
    fail=1
fi

echo "== adapt-demo (bandit abandons a chaos-degraded strategy, fenced swap)"
# kf-adapt end to end: chaos `delay` clauses throttle one link, the UCB
# bandit's windows degrade, the majority vote agrees, and the
# consensus-fenced lockstep swap fires on every rank (docs/adaptation.md).
# Bounded: a wedged fence must fail the gate, not hang it.
rm -f /tmp/_kf_adapt_demo.log
if ! timeout -k 10 150 python3 examples/adapt_interference.py \
        > /tmp/_kf_adapt_demo.log 2>&1 \
        || ! grep -q "adapt-demo: swap fired" /tmp/_kf_adapt_demo.log; then
    echo "ERROR: adapt demo did not fire the fenced swap"
    tail -40 /tmp/_kf_adapt_demo.log || true
    fail=1
fi

echo "== serve-demo (request completes through a chaos worker kill)"
# kf-serve end to end: continuous-batching workers + router over real
# host channels, chaos kills a worker mid-decode, the router replays
# its in-flight requests from their committed positions on survivors —
# zero lost accepted requests, replayed tokens bitwise-equal to the
# greedy reference (docs/serving.md).  Bounded: a wedged replay must
# fail the gate, not hang it.
rm -f /tmp/_kf_serve_demo.log
if ! timeout -k 10 240 python3 examples/serve_demo.py \
        > /tmp/_kf_serve_demo.log 2>&1 \
        || ! grep -q "serve-demo: survived worker kill" \
        /tmp/_kf_serve_demo.log; then
    echo "ERROR: serve demo did not survive the worker kill"
    tail -40 /tmp/_kf_serve_demo.log || true
    fail=1
fi

echo "== overlap-demo (bucketed communication/computation overlap measured)"
# kf-overlap end to end: chaos-injected wire latency, serial vs depth-k
# pipelined ZeRO-2 bucket loop — asserts measured overlap > 0,
# bitwise-identical final params, and the in-flight gauge back at 0
# (docs/overlap.md).  Bounded: a wedged window must fail the gate.
rm -f /tmp/_kf_overlap_demo.log
if ! timeout -k 10 150 python3 examples/overlap_pipeline.py \
        > /tmp/_kf_overlap_demo.log 2>&1 \
        || ! grep -q "overlap-demo: overlap" /tmp/_kf_overlap_demo.log; then
    echo "ERROR: overlap demo did not measure positive overlap"
    tail -40 /tmp/_kf_overlap_demo.log || true
    fail=1
fi

echo "== pp-demo (1F1B beats sequential; elastic stage merge bitwise)"
# kf-pipeline end to end: 2 emulated slices with 30 ms chaos delay on
# every cross-stage send — naive sequential vs 1F1B over async p2p
# handles must produce BITWISE-identical finals with a measured 1F1B
# win, and the planned 2->1 stage merge must restore bitwise from the
# ring-mirrored StageBoundary (docs/pipeline.md).  Bounded: a wedged
# schedule or re-carve must fail the gate, not hang it.
rm -f /tmp/_kf_pp_demo.log
if ! timeout -k 10 240 python3 examples/pp_demo.py \
        > /tmp/_kf_pp_demo.log 2>&1 \
        || ! grep -q "pp-demo OK" /tmp/_kf_pp_demo.log; then
    echo "ERROR: pp demo did not pass (schedule A/B or stage merge)"
    tail -40 /tmp/_kf_pp_demo.log || true
    fail=1
fi

echo "== persist-demo (preempt:all -> supervised relaunch -> 4->2 cold restart)"
# kf-persist end to end: every rank killed at the same step boundary
# (preempt:all), the kfrun -restore-from supervisor relaunches from the
# newest COMPLETE manifest (a write torn by the preemption must be
# skipped, not restored), then a halved world cold-restarts from the
# same directory via the shape-agnostic reshard_plan restore — final
# params bitwise vs a fixed-world numpy replay (docs/persistence.md).
# Bounded: a wedged supervisor round must fail the gate, not hang it.
rm -f /tmp/_kf_persist_demo.log
if ! timeout -k 10 300 python3 examples/preempt_restore.py \
        > /tmp/_kf_persist_demo.log 2>&1 \
        || ! grep -q "PERSIST DEMO OK" /tmp/_kf_persist_demo.log; then
    echo "ERROR: persist demo did not restore bitwise through preemption"
    tail -40 /tmp/_kf_persist_demo.log || true
    fail=1
fi

echo "== xray-gate (causal attribution + perf budget on the chaos mesh)"
# kf-xray end to end: 3-rank mesh with a planted 30 ms link delay — the
# offline kftrace --critical-path verdict and the online aggregator
# verdict must be IDENTICAL and must name the planted edge, and the
# per-phase medians must sit inside the checked-in ceilings of
# tests/xray_budget.json (docs/xray.md).  Bounded: a wedged mesh must
# fail the gate, not hang it.
# the drill exits non-zero when any check is false
log=$(mktemp)
if ! timeout -k 10 300 python3 examples/xray_gate.py > "$log" 2>&1; then
    echo "ERROR: xray gate failed (attribution checks or perf budget)"
    tail -5 "$log" || true
    fail=1
fi
rm -f "$log"

echo "== sentinel-gate (mid-run chaos onset -> online alert == offline replay)"
# kf-sentinel end to end: 3-rank paced mesh, delay clauses armed
# MID-RUN (after_step) on the 0<->1 link — the clean baseline must stay
# silent, the regress:step_time_s changepoint alert must fire online
# within K=2 windows, the incident flight record's xray verdict must
# name the planted rank/edge, and kfhist --verdict over the durable
# history must reproduce the identical verdicts (docs/sentinel.md).
# Bounded: a wedged mesh must fail the gate, not hang it.
# the drill exits non-zero when any check is false
log=$(mktemp)
if ! timeout -k 10 300 python3 examples/sentinel_gate.py > "$log" 2>&1; then
    echo "ERROR: sentinel gate failed (detection, incident, or replay)"
    tail -5 "$log" || true
    fail=1
fi
rm -f "$log"

echo "== pallas-check (ICI ring kernels bitwise vs the lax references)"
# the make pallas-check gate: interpreter-path kernels pinned bitwise
# against the order-matched lax emulation and the psum_scatter/
# all_gather references (docs/pallas_collectives.md).  Bounded: a hung
# interpret kernel must fail the gate, not wedge it.
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu python3 -m pytest \
        tests/test_pallas_collectives.py -q -m 'not slow' \
        -p no:cacheprovider > /tmp/_kf_pallas_check.log 2>&1; then
    echo "ERROR: pallas collectives bitwise suite failed"
    tail -20 /tmp/_kf_pallas_check.log || true
    fail=1
fi

echo "== compileall"
if ! python3 -m compileall -q kungfu_tpu scripts benchmarks examples tests; then
    fail=1
fi

echo "== native build-stamp check"
# the production stamp must never carry sanitizer flags — that would
# mean a tsan/asan .so is about to be (re)used as the production lib
for stamp in kungfu_tpu/native/.buildflags; do
    if [ -f "$stamp" ] && grep -q "fsanitize" "$stamp"; then
        echo "ERROR: $stamp contains sanitizer flags: $(cat "$stamp")"
        fail=1
    fi
done
# and the variant stamps, when present, must carry exactly their own
if [ -f kungfu_tpu/native/.buildflags-tsan ] \
    && ! grep -q "fsanitize=thread" kungfu_tpu/native/.buildflags-tsan; then
    echo "ERROR: .buildflags-tsan lost -fsanitize=thread"
    fail=1
fi
if [ -f kungfu_tpu/native/.buildflags-asan ] \
    && ! grep -q "fsanitize=address" kungfu_tpu/native/.buildflags-asan; then
    echo "ERROR: .buildflags-asan lost -fsanitize=address"
    fail=1
fi

echo "== tier-1 time budget (suite green inside the checked-in cap)"
# the tier-1 suite must FINISH, green, inside tests/tier1_budget.json's
# budget_s — the cap the CI runner enforces with a hard timeout.  A new
# e2e test that pushes the suite past this line belongs in tier-2
# (@pytest.mark.slow), not inside the budget.  Opt out for quick local
# iterations with KF_CHECK_SKIP_TIER1=1 (CI must not).
if [ "${KF_CHECK_SKIP_TIER1:-0}" = "1" ]; then
    echo "   skipped (KF_CHECK_SKIP_TIER1=1): tier-1 budget not verified"
else
    T1_BUDGET=$(python3 -c "import json; \
print(int(json.load(open('tests/tier1_budget.json'))['budget_s']))")
    rm -f /tmp/_kf_tier1_budget.log
    t1_start=$(date +%s)
    if ! timeout -k 10 "$T1_BUDGET" env JAX_PLATFORMS=cpu \
            python3 -m pytest tests/ -q -m 'not slow' \
            --continue-on-collection-errors -p no:cacheprovider \
            -p no:xdist -p no:randomly \
            > /tmp/_kf_tier1_budget.log 2>&1; then
        echo "ERROR: tier-1 failed or blew the ${T1_BUDGET}s wall budget"
        tail -15 /tmp/_kf_tier1_budget.log || true
        fail=1
    else
        echo "   tier-1 green in $(( $(date +%s) - t1_start ))s" \
            "(budget ${T1_BUDGET}s)"
    fi
fi

if [ "$fail" -ne 0 ]; then
    echo "check.sh: FAILED"
    exit 1
fi
echo "check.sh: all gates green"
