#!/usr/bin/env bash
# Tier-0 smoke: a <8-minute subset to run BEFORE the ~50-minute full
# suite — the lint gate, the observability schemas (trace/heartbeat/
# metrics/dispatch_log consumers parse these), one fused-vs-single
# exactness pin (the engine's semantic contract), one packed-model
# end-to-end check, a <30s kill-and-resume crash drill (SIGKILL a
# supervised worker, resume from its auto-checkpoint, exact pinned
# counts — the recovery stack's tier-0 proof), the <30s SERVICE
# crash drill (a CheckerService job SIGKILLed mid-superstep requeues,
# resumes from its per-job checkpoint, exact counts + Chrome trace — the
# multi-tenant pool's tier-0 proof), and the <30s SERVICE RESTART drill
# (the service process itself dies right after journaling `started`; the
# restart replays the job journal, kills the orphaned worker, requeues,
# and converges to exact counts — the durability tier's tier-0 proof),
# and the <30s TELEMETRY drill (one packed model with the metrics
# recorder on, /.metrics scraped from a make_app instance and validated
# with the OpenMetrics test parser, counters cross-checked exactly),
# and the <30s FLEET FAILOVER drill (a 2-device FleetService;
# device.lost kills one device's pool mid-job, the victim migrates to
# the survivor and completes bit-identical — the fleet tier's tier-0
# proof), and the <30s MUX BATCHING drill (a mux_k=3 pool runs three
# co-queued same-spec jobs as ONE worker.py --mux invocation — exact
# pinned counts per member, per-lane mux provenance, pool gauges,
# journaled mux_group starts — the batched-scheduling tier's tier-0
# proof), and the <30s TRACE MERGE drill (a traced packed model
# plus a traced 2-job service round merge via obs/collect.py into one
# Chrome trace: schema valid, monotonic timeline, flow arrows resolve,
# dispatch:* sub-spans nest in their dispatch — the distributed-tracing tier's
# tier-0 proof), and the <30s QOS SHED drill (class-aware admission on a
# saturated pool: best_effort sheds first with a measured Retry-After,
# batch sheds at its own threshold, interactive admits until the hard
# cap, quotas/gauges/deadline validation pinned — the QoS tier's tier-0
# proof), and the <30s SYMMETRY drill (device symmetry reduction on one
# packed model: the spec-compiled canonicalization collapses 2pc rm=3's
# 288 states to the pinned 80 equivalence classes, bit-equal to the host
# object-state oracle, with the spec tag in metrics — the symmetry
# tier's tier-0 proof).
# A red here means don't bother starting the full run.
#
# Usage: tools/smoke.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

# Lint stage (stpu-lint, docs/static-analysis.md): the pinned
# backend-miscompile rules enforced over every shipped kernel surface —
# CPU-only, no device, <60 s. The JSON verdict lands in runs/lint.json,
# which bench.py folds into bench_detail.json provenance as lint_ok.
mkdir -p runs
timeout -k 5 60 python tools/stpu_lint.py --json-out runs/lint.json

# Perf-regression gate self-test (tools/bench_regress.py, ISSUE 13): the
# gate proves its three typed verdicts against the committed
# runs/archive trajectory — pass on the real lines, fail on a
# synthetically degraded one, "no_baseline" on an empty dir. Pure JSON,
# no jax, <5 s.
timeout -k 5 60 python tools/bench_regress.py --self-test

exec timeout -k 10 480 python -m pytest \
  tests/test_obs.py \
  tests/test_promexport.py::test_smoke_metrics_endpoint \
  tests/test_fused_dispatch.py::test_fused_matches_single_full_coverage \
  tests/test_packed_increment.py \
  tests/test_supervise.py::test_smoke_kill_resume \
  tests/test_service.py::test_smoke_service_kill_resume \
  tests/test_service.py::test_smoke_fleet_failover \
  tests/test_service.py::test_smoke_qos_shed \
  tests/test_service_durability.py::test_smoke_service_restart_resume \
  tests/test_mux.py::test_smoke_mux \
  tests/test_trace_collect.py::test_smoke_trace_merge \
  tests/test_symmetry.py::test_smoke_symmetry \
  -x -q -p no:cacheprovider "$@"
