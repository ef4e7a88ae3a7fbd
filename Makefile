# Top-level developer entry points.  The native transport has its own
# Makefile (kungfu_tpu/native/Makefile) for the .so variants; this one
# wraps the repo-wide gates so "the linters" is one command.

PY ?= python3
BASELINE := tests/lint_baseline.json

.PHONY: lint verify protocheck shardcheck detcheck pallas-check check test native \
    trace-demo \
    zero-demo multislice-demo adapt-demo overlap-demo serve-demo pp-demo \
    persist-demo xray-gate sentinel-gate help

## lint: all eighteen kf-lint rules — the Python suite (env-contract,
## jit-sync, blocking-io, retry-discipline, handle-discipline,
## collective-consistency, wire-contract, lock-order, trace-vocab,
## agg-schema, shard-axis, shard-spec, recompile-hazard, proto-verify,
## replay-taint, rng-discipline, reduction-order) AND the transport.cpp
## lockcheck (lock-discipline) in one command, honoring the baseline.
lint:
	$(PY) scripts/kflint $(if $(wildcard $(BASELINE)),--baseline $(BASELINE))

## verify: just the interprocedural kf-verify rules (fast iteration on
## protocol changes).
verify:
	$(PY) scripts/kflint --checker collective-consistency \
	    --checker wire-contract --checker lock-order \
	    $(if $(wildcard $(BASELINE)),--baseline $(BASELINE))

## protocheck: just the proto-verify SPMD protocol verifier (fast
## iteration on comm-protocol changes) — deliberately NO baseline: a
## protocol divergence never lands as legacy debt (the check.sh
## empty-baseline gate).
protocheck:
	$(PY) scripts/kflint --proto

## shardcheck: just the kf-shard axis-environment rules (fast iteration
## on sharding/mesh changes) — deliberately NO baseline: the tree must
## hold these rules clean (the check.sh empty-baseline gate).
shardcheck:
	$(PY) scripts/kflint --checker shard-axis --checker shard-spec \
	    --checker recompile-hazard

## detcheck: just the kf-det replay-determinism rules (fast iteration
## on consensus/persist/RNG changes) — deliberately NO baseline: a
## replay-divergent flow never lands as legacy debt (the check.sh
## empty-baseline gate, docs/determinism.md).
detcheck:
	$(PY) scripts/kflint --checker replay-taint \
	    --checker rng-discipline --checker reduction-order

## pallas-check: the Pallas ICI collectives interpreter-path bitwise
## suite (docs/pallas_collectives.md): every ring kernel form — uni/
## bidirectional reduce-scatter and all-gather, 1-chunk, padded-tail,
## non-divisible world sizes — pinned bitwise against the order-matched
## lax emulation and the lax references, plus the vjp pair, the
## pallas_ring schedule plumbing (flat buckets, eager communicator,
## ZeRO, ring attention) and the traced-bytes parity rows.
pallas-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pallas_collectives.py \
	    -q -m 'not slow' -p no:cacheprovider

## check: the full pre-merge gate (lint + compileall + build stamps).
check:
	bash scripts/check.sh

## test: tier-1 (CPU backend, slow tests excluded).
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
	    -p no:cacheprovider

## native: production build of the native transport.
native:
	$(MAKE) -C kungfu_tpu/native

## xray-gate: the kf-xray attribution + perf-budget gate (the same
## stanza scripts/check.sh runs): 3-rank chaos mesh with a planted
## 30 ms link delay — offline `kftrace --critical-path` and the online
## aggregator verdict must be identical and name the planted edge, and
## the per-phase medians must sit inside tests/xray_budget.json
## (docs/xray.md).
xray-gate:
	$(PY) examples/xray_gate.py

## sentinel-gate: the kf-sentinel detection gate (the same stanza
## scripts/check.sh runs): 3-rank paced mesh, chaos delay clauses armed
## MID-RUN via after_step — the clean baseline must stay silent, the
## regress:step_time_s changepoint alert must fire online within K=2
## windows, the incident flight record's xray verdict must name the
## planted rank/edge, and `kfhist --verdict` over the durable history
## must reproduce the identical verdicts offline (docs/sentinel.md).
sentinel-gate:
	$(PY) examples/sentinel_gate.py

## trace-demo: 4-peer local run with an injected 400 ms straggler on
## rank 2 (every 9th matching send, so most collectives stay clean and
## the stalls read as spikes) and the flight recorder on; merges the
## per-rank dumps into trace-demo/trace.json (chrome://tracing /
## ui.perfetto.dev) and prints the straggler report — the fault-overlap
## section should attribute the spikes to chaos:delay on rank 2.
trace-demo:
	rm -rf trace-demo && mkdir -p trace-demo
	$(PY) -m kungfu_tpu.runner.cli -np 4 -H 127.0.0.1:4 \
	    -trace -trace-dump trace-demo \
	    -chaos 'delay:ms=400,rank=2,every=9' \
	    $(PY) examples/mnist_slp.py --n-epochs 1
	$(PY) scripts/kftrace merge -o trace-demo/trace.json trace-demo/*.jsonl
	$(PY) scripts/kftrace report trace-demo/*.jsonl

## zero-demo: 4-process host-plane ZeRO-2 run through a LIVE 4->2
## shrink (rank 3 dies at step 3, rank 1 at step 5): reduce-scatter
## gradient chunks, 1/n momentum per rank with ring-buddy mirrors, and
## a leaderless optimizer-state re-carve on each death — survivors
## finish on 2 workers and print the final params (bitwise-checkable
## against a fixed-world numpy replay; see docs/zero.md).
zero-demo:
	$(PY) -m kungfu_tpu.runner.cli -np 4 -tolerate-failures \
	    -chaos 'die:step=3,rank=3;die:step=5,rank=1' \
	    $(PY) examples/zero_shrink.py --n-steps 8

## multislice-demo: emulated 2-slice pod (4 workers, slice-major) losing
## a WHOLE slice in flight: chaos kills both ranks of slice 1 at step 3;
## the surviving slice widens the dead set to the slice, passes the
## slice-granular quorum (1 of 2 + lowest-slice tie-break — rank-level
## strict majority would have refused 2-of-4), agrees over slice
## leaders, re-carves the mesh + the ZeRO momentum from CROSS-SLICE
## buddy mirrors, and finishes — final params bitwise vs a fixed-world
## replay (docs/multislice.md).
multislice-demo:
	$(PY) -m kungfu_tpu.runner.cli -np 4 -num-slices 2 \
	    -tolerate-failures -chaos 'die_slice:slice=1,step=3' \
	    $(PY) examples/multislice_shrink.py --n-steps 8

## adapt-demo: kf-adapt scripted interference A/B (3 in-process ranks,
## chaos `delay` clauses throttling the 0<->1 link on send AND ping):
## the UCB bandit measures its windows, majority-votes, and performs the
## consensus-fenced lockstep swap onto the measured-latency MST — the
## script asserts the swap fires on EVERY rank and the step time
## recovers (docs/adaptation.md).
adapt-demo:
	$(PY) examples/adapt_interference.py

## serve-demo: kf-serve fault drill (3 in-process serving workers + a
## router over real host channels): a steady request stream while chaos
## kills worker 1 at its 10th decode iteration — the router's
## progress-deadline ladder excludes it and replays its in-flight
## requests from their committed positions on the survivors.  Asserts
## zero lost accepted requests, >=1 replay, replayed tokens equal to
## the greedy reference, and measured prefix reuse (docs/serving.md;
## a whole-slice kill is tests/test_serve.py::TestRouterLive).
serve-demo:
	$(PY) examples/serve_demo.py

## overlap-demo: kf-overlap A/B (3 in-process ranks, chaos `delay`
## injecting 25 ms wire latency on every send): the ZeRO-2 bucket loop
## runs serial (issue, wait, compute) then depth-k pipelined
## (host_bucket_pipeline over the engine's async handle window) — the
## script asserts measured overlap > 0, BITWISE-identical final params,
## and the kf_overlap_inflight gauge back at 0 (docs/overlap.md).
overlap-demo:
	$(PY) examples/overlap_pipeline.py

## pp-demo: kf-pipeline drill (2 in-process ranks = 2 emulated slices,
## chaos `delay` injecting 30 ms on every cross-stage send): the same
## steps run under naive sequential microbatching and under 1F1B with
## async-handle prefetch — the script asserts BITWISE-identical final
## params between the schedules, a measured 1F1B win, and a planned
## 2->1 elastic stage merge restored bitwise from the ring-mirrored
## StageBoundary (docs/pipeline.md).
pp-demo:
	$(PY) examples/pp_demo.py

## persist-demo: kf-persist drill: 4 kfrun workers stream async sharded
## manifests, chaos `preempt:all,step=3` kills EVERY rank mid-run, the
## `-restore-from` supervisor relaunches from the newest complete
## manifest (a torn mid-preemption write is detected and skipped), then
## a separate 2-worker launch cold-restarts from the SAME directory —
## the 4-rank manifest re-carves onto the halved world and the final
## params are asserted BITWISE against a fixed-world numpy replay
## (docs/persistence.md).
persist-demo:
	$(PY) examples/preempt_restore.py

help:
	@grep -E '^## ' Makefile | sed 's/^## //'
