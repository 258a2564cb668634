#!/usr/bin/env bash
# Single CI entrypoint: moolint static analysis, then the tier-1 test
# suite (the exact command ROADMAP.md specifies). Fails fast on lint so a
# new async-safety/trace-hygiene violation is reported in seconds, not
# after a full test run.
set -euo pipefail
cd "$(dirname "$0")/.."

# On GitHub runners, emit ::error workflow annotations so new findings
# surface inline on the PR diff; plain text everywhere else.
fmt=text
if [ -n "${GITHUB_ACTIONS:-}" ]; then fmt=gha; fi

echo "== moolint: moolib_tpu/ =="
# --rule-times: per-rule wall-time for the 10-family suite rides the run
# that lints the tree anyway, so a rule that goes quadratic shows by name
# here. (The hot family memoizes its cross-module jit-binding resolution
# on the lint context, so its five data-flow rules bill the whole-tree
# walk once.) The `timeout`s are the linter's wall-clock budget (a cold
# run of the package tree is ~90 s on an idle 8-core sandbox, a cached
# one seconds): a slow linter stops being run. Tier-1 pins the count
# instead (one parse per file: tests/test_tools.py::
# test_moolint_whole_repo_parses_each_file_once).
timeout -k 10 400 python tools/moolint.py --check --format="$fmt" \
  --rule-times moolib_tpu/

echo "== moolint: tools/ tests/ =="
# Separate baseline section for the non-package trees: they are held to
# their own (currently empty) grandfather list so debt there can never
# hide behind the package baseline — and vice versa.
timeout -k 10 400 python tools/moolint.py --check --format="$fmt" \
  --baseline moolib_tpu/analysis/baseline_tools.json tools/ tests/

echo "== moolint: baselines must stay empty =="
# The burn-down hit 0 in PR 3 (racelint joined at 0 in PR 9);
# --fail-nonempty turns any regression (a re-grandfathered finding
# sneaking back in) into a hard CI failure.
python tools/moolint.py --baseline-stats --fail-nonempty
python tools/moolint.py --baseline-stats --fail-nonempty \
  --baseline moolib_tpu/analysis/baseline_tools.json

echo "== lint enforcement tests (slow-marked) =="
# The two whole-package lint tests — the in-process lint_paths diff
# against the baseline and the CLI exit-zero pin — are ~150s of pure
# moolint wall, the same sweep the three stages above just ran. They
# are slow-marked out of the tier-1 pytest window (ISSUE 19 headroom)
# and run here as their own named stage: coverage is unchanged, only
# the budget it bills against moved.
timeout -k 10 400 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_lint.py -q -m slow -p no:cacheprovider

echo "== perf smoke =="
# One stage, two layers (docs/perf.md):
# 1. telemetry_smoke.py — live __telemetry scrape of a two-Rpc cohort
#    (JSON + Prometheus through the strict parser, trace-id propagation)
#    plus the disabled-mode instrumentation overhead budget (<5% of echo
#    latency, measured at the gate so loopback noise can't flake it).
# 2. perf.py --suite cpu-proxy --smoke — the CPU-proxy perf suite (RPC
#    echo/payload, loopback tree allreduce, batcher fill, envpool
#    steps/s, serial encode/decode) on OS-assigned ports, gated on
#    telemetry-derived budgets and the trend-store regression detector.
#    Emits GHA ::error annotations on breach (fmt is auto-picked from
#    GITHUB_ACTIONS inside perf.py). The outer `timeout` is the hard
#    wall-clock cap; perf.py's own --smoke cap (300s) nulls-and-fails
#    stragglers before that. bench/trends.jsonl is the trend artifact —
#    upload it from CI so history accretes across runs.
env JAX_PLATFORMS=cpu python tools/telemetry_smoke.py
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/perf.py \
  --suite cpu-proxy --smoke --trends bench/trends.jsonl

echo "== stepscope smoke =="
# Step-phase attribution end to end (docs/observability.md, "Step-phase
# attribution"): a short instrumented A2C cohort (real EnvPool workers,
# the examples' learner loop under StepScope), asserting every loop's
# phase ledger sums to its measured wall time within 5%, rendering the
# per-peer + merged phase report (text + Chrome composition tracks),
# and appending stepscope_<loop>_*_fraction rows to the same trend
# artifact as the perf suite — gated by the same regression detector,
# so a creeping exposed-comms share fails CI with a reproduce command
# exactly like a throughput drop. The stepscope disabled-mode cost
# rides the telemetry_smoke budget above (one fully disabled
# instrumented step is charged per echo call).
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/stepscope_report.py \
  --smoke --trends bench/trends.jsonl

echo "== hotwatch gate =="
# hotlint's dynamic mirror (docs/analysis.md, "hotlint"): the Hotwatch
# window contracts themselves (planted .item() caught with its site
# stack, staged copies free, compile flatness, thread scoping) plus the
# two e2e rows — the real donating IMPALA train step under a
# zero-D2H/zero-H2D/zero-compile window, and the examples' actor
# boundary with its two designed per-step syncs exactly budgeted.
timeout -k 10 180 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_hotwatch.py -q -p no:cacheprovider

echo "== parity gate =="
# numlint's dynamic mirror (docs/analysis.md, "numlint"): ParityWatch
# runs the seeded A2C update twice in-process (donate=False) and
# demands bit-identical params/opt-state/metrics, with the divergence
# report itself pinned (first divergent leaf path, dtype, ULP
# distance — what a numerics bisect runs on). The integration row
# spins a real 4-peer loopback cohort and permutes peer arrival order:
# every peer in every round must return the SAME BITS, equal to the
# documented fixed fold in rpc/group.py (node i merges own ⊕
# subtree(2i+1) ⊕ subtree(2i+2) in child-index order) — pinning the
# reduction-order contract as executable spec, with order-SENSITIVE
# payloads so a symmetric input can't make the check vacuous.
timeout -k 10 180 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_parity.py -q -p no:cacheprovider

echo "== chaos + serving smoke =="
# Bounded seeded fault-injection pass (18 scenarios, well under 90s,
# CPU-only): loss storm, partition+heal, leader loss, the survivable-
# training trio (learner SIGKILL + same-name restart rejoin with loss
# continuity; broker kill + standby promotion adopting the epoch from
# gossip with an in-flight op surviving; straggler slow-link quorum
# commit with exactly-once late re-contribution), the serving
# tier's replica-kill (router + in-process replicas on OS-assigned
# ports, one killed mid-load: bounded completion, served-p99 ceiling,
# metric-family consistency) and router-partition (health-gated drain
# from rotation + return after heal), plus the env tier's survivable
# trio (worker SIGKILL mid-batch: typed retry-safe failure, exactly-
# once retry, steps/s recovery; SIGSTOP wedge reaped by the hung-step
# watchdog within its deadline; poison env quarantined while the
# cohort keeps stepping — process-level ProcFaultPlan faults with the
# same seed-replay discipline as the wire faults), plus the fleet
# tier's trio (controller SIGKILL mid-rollout: standby adopts behind
# the epoch fence and the canary completes; bad canary: SLO-gated
# auto-rollback within the settle window with an incident bundle;
# replica crash-loop past its restart budget: permanent-down +
# route-around). A failure prints
# the seed + replay command (long-run version: chaos_soak.py
# --minutes; --scenario GLOB selects a subset; per-scenario wall time
# rides the JSON report).
# The pass also covers the same-host shm transport lane:
# shm_lane_fallback (segment death mid-call -> exactly-once TCP
# fallback, /dev/shm unlink, deterministic event log) rides the
# scenario list, so the ring's lock discipline runs under locktrace
# like everything else.
# --locktrace additionally runs the whole pass under instrumented locks
# (testing/locktrace.py): the OBSERVED acquires-while-holding graph must
# stay acyclic (no lock-order inversion ever executed) and inside
# racelint's static over-approximation (docs/analysis.md).
# --restrack runs it under the resource tracker too (testing/restrack.py,
# lifelint's dynamic mirror): every thread/SharedMemory/Rpc/gauge
# acquisition a scenario makes must be released by its end, so the
# 18-scenario pass doubles as a leak soak — a leak fails the scenario
# with the acquisition-site stack.
env JAX_PLATFORMS=cpu python tools/chaos_soak.py --smoke --locktrace --restrack

# shm transport interop tests (same-host selection, cross-host refusal,
# MOOLIB_TPU_SHM=0 interop, /dev/shm leak hygiene, zero-copy receive):
# run as their own step in this stage so a lane regression is named
# here, minutes before the full tier-1 sweep would catch it.
timeout -k 10 180 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_shmring.py -q -p no:cacheprovider

echo "== statestore restore smoke =="
# Durable-state round trip (docs/reliability.md, "Durable state"):
# publish a model-sized version to two live replicas over the
# StateStoreService wire family, wipe the publisher's disk (host loss),
# and restore it on the same member via quorum-2 negotiation + verified
# chunk pull — with the statestore_* counters and ss_* flightrec events
# checked as evidence. The chaos pass above already runs the three
# statestore scenarios (host-loss trajectory continuity, ENOSPC
# mid-checkpoint, bit-flipped chunk refetch) under locktrace; this
# stage pins the plain-path restore in isolation so a wire-family or
# negotiation regression is named here, in seconds.
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/statestore_smoke.py

echo "== fleet smoke =="
# The fleet tier end to end (docs/fleet.md): a FleetSpec.small cohort
# (broker, learner, env worker, 3 replicas, router) materializes from
# its JSON-round-tripped spec, a healthy version promotes through the
# canary state machine under closed-loop load (zero dropped requests),
# a poisoned version auto-rolls-back on the error-rate SLO gate with
# the exact promoted version restored on every replica and a
# re-validating incident bundle — with the fleet_* counters and
# fleet_* flightrec events checked as evidence. The chaos pass above
# already runs the three fleet scenarios (controller SIGKILL
# mid-rollout, bad canary, replica crash-loop) under locktrace +
# restrack; this stage pins the plain promote/rollback path in
# isolation so a rollout regression is named here, in seconds.
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/fleet_smoke.py

echo "== incident smoke =="
# flightrec end-to-end (docs/incidents.md): an in-process cohort under a
# seeded FaultPlan is deliberately driven through faults, then crawled
# over a real --connect like a production incident — every pulled bundle
# must pass the strict schema validator and the merged cross-peer
# timeline must be non-empty, time-ordered, and causally consistent
# (injected chaos events + conn lifecycle present, call/handle span
# pairs ordered). The recorder's disabled-mode overhead budget rides the
# telemetry_smoke stage above (flight gates share the <5% echo budget).
# chaos_soak above already exercises the failure-path capture: any
# scenario failure writes a bundle into incidents/ and prints its path
# next to the seed-replay command (upload incidents/ as a CI artifact).
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/incident_report.py --smoke

echo "== tier-1 tests =="
rm -f /tmp/_t1.log
rc=0
# `|| rc=$?` keeps set -e from aborting before the DOTS_PASSED line —
# which exists precisely for the failing runs (pipefail makes the
# pipeline status the pytest/timeout status, not tee's).
# MOOLIB_FAULTHANDLER_TIMEOUT pairs with the outer `timeout -k 10 870`:
# conftest.py arms faulthandler.dump_traceback_later at that many
# seconds, so a real deadlock prints EVERY thread's stack to the log
# shortly before SIGKILL instead of silently eating the window.
timeout -k 10 870 env JAX_PLATFORMS=cpu MOOLIB_FAULTHANDLER_TIMEOUT=840 \
  python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log || rc=$?
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit "$rc"
