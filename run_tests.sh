#!/bin/bash
# Run the test suite on the CPU (JAX_PLATFORMS=cpu; tests/conftest.py
# adds 8 virtual devices). See .claude/skills/verify/SKILL.md.
#
# Modes:
#   ./run_tests.sh [pytest args...]    plain pytest passthrough
#   ./run_tests.sh --fast [args...]    skip slow + stress markers
#   ./run_tests.sh --tier1             the ROADMAP.md tier-1 command verbatim
#   ./run_tests.sh --faults [args...]  deterministic fault-injection suite
#                                      across a fixed seed matrix
#                                      (PIXIE_TPU_FAULT_SEED; see
#                                      tests/test_fault_injection.py and
#                                      docs/RESILIENCE.md)
#   ./run_tests.sh --lint-metrics      metrics-name lint only: the pxlint
#                                      metrics-naming rule (static) + the
#                                      dynamic registration checks in
#                                      tests/test_metrics_lint.py. Alias of
#                                      the shared rule engine since the
#                                      lint framework unification (see
#                                      docs/ANALYSIS.md).
#   ./run_tests.sh --analyze           static analysis gate: pxlint over
#                                      pixie_tpu/ (all rules, baseline
#                                      applied) + the plan verifier over
#                                      every replay shape's compiled
#                                      plan + the pxbound soundness
#                                      gate (see --bounds). Non-zero
#                                      exit on any non-baselined
#                                      finding. Also runs inside
#                                      --tier1.
#   ./run_tests.sh --bounds            resource-bound gate: pytest
#                                      tests/test_bounds.py + the
#                                      pxbound soundness check
#                                      (analysis/bound_check.py):
#                                      replays the 9 small shapes and
#                                      the bundled self-monitoring
#                                      scripts asserting observed
#                                      QueryResourceUsage <= predicted,
#                                      verifies over-budget rejection
#                                      at compile time, and reports the
#                                      pass's compile overhead (<5%
#                                      budget). Runs inside --analyze /
#                                      --tier1.
#   ./run_tests.sh --obs               self-observability gate: the
#                                      self-telemetry + trace-stitching
#                                      + device-tier program-registry
#                                      + storage-tier + transport-tier
#                                      suites (tests/test_telemetry.py,
#                                      tests/test_trace_stitching.py,
#                                      tests/test_programs.py,
#                                      tests/test_table_obs.py,
#                                      tests/test_bus_obs.py)
#                                      plus plan-verifier compilation of
#                                      the bundled self-monitoring PxL
#                                      scripts against the telemetry
#                                      table schemas (see
#                                      pixie_tpu/analysis/obs_check.py;
#                                      incl. px/program_cost,
#                                      px/bound_accuracy,
#                                      px/table_health, px/ingest_lag,
#                                      px/bus_health, px/rpc_latency).
#                                      The script-compile half also runs
#                                      inside --tier1.
#   ./run_tests.sh --profile           continuous-profiling gate: the
#                                      attributed-profiler suite
#                                      (tests/test_profiling.py —
#                                      thread attribution, cluster
#                                      merge, pprof/flamez endpoints,
#                                      differential profiles, sampler
#                                      overhead A/B; see
#                                      docs/OBSERVABILITY.md "Profiling
#                                      tier") plus the obs_check script
#                                      compile of px/query_cpu,
#                                      px/tenant_cpu and px/flame_diff.
#                                      Both halves also run inside
#                                      --obs and --tier1.
#   ./run_tests.sh --tenancy           multi-tenant overload gate: the
#                                      full tests/test_tenancy.py suite
#                                      INCLUDING the slow-marked p99
#                                      isolation gate (a saturating
#                                      noisy tenant must not move the
#                                      victim tenant's p99 beyond 25%
#                                      of its bracketed solo baseline,
#                                      fixed seeds; see
#                                      docs/RESILIENCE.md "Overload &
#                                      multi-tenancy"). The fast half
#                                      of the suite also runs inside
#                                      the --tier1 sweep; the isolation
#                                      gate runs via the explicit
#                                      "$0" --tenancy step there.
#   ./run_tests.sh --locks             pxlock concurrency gate (see
#                                      docs/ANALYSIS.md "pxlock"):
#                                      static half = the lock-order /
#                                      request-from-handler /
#                                      blocking-call-under-lock pxlint
#                                      rules repo-green; dynamic half =
#                                      the concurrency-heavy suites
#                                      (lockdep unit tests, the
#                                      concurrent-serving certification
#                                      in tests/test_concurrency.py,
#                                      fault/tenancy/telemetry) under
#                                      PIXIE_TPU_LOCKDEP=1 — runtime
#                                      lock-order validation that fails
#                                      on the first acquisition that
#                                      would close a cycle. Runs inside
#                                      --analyze (and so --tier1).
#   ./run_tests.sh --cache             repeat-serving gate: the result
#                                      cache / materialized view /
#                                      push-down partial-agg suite
#                                      (tests/test_result_cache.py; see
#                                      docs/CACHING.md). The file also
#                                      runs inside the --tier1 sweep.
#   ./run_tests.sh --storage           storage-tier gate: the cold-tier
#                                      suite (tests/test_storage_tier.py
#                                      — encoding round-trips,
#                                      hot-vs-cold bit-identity,
#                                      demote->evict monotonicity on
#                                      both ring backends, zone-map
#                                      skipping, decode-error
#                                      propagation; see
#                                      docs/STORAGE.md). The file also
#                                      runs inside the --tier1 sweep.
#   ./run_tests.sh --soak              chaos-soak gate: a fixed-seed
#                                      32-agent / 2-broker soak driving
#                                      faults x tenancy x concurrency x
#                                      a leader-broker kill together
#                                      (pixie_tpu/services/chaos.py;
#                                      see docs/RESILIENCE.md "Broker
#                                      HA"). Exit 0 iff zero lost
#                                      queries, zero leaked threads, a
#                                      failover was observed, and the
#                                      victim tenant's p99 held its
#                                      isolation bound. Also runs
#                                      inside --tier1.
#   ./run_tests.sh --soak-full         the long soak: 128 agents, 3
#                                      brokers, 3x offered load. NOT
#                                      part of --tier1 (wall-clock).
case "$1" in
  --obs)
    shift
    rc=0
    env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.analysis.obs_check || rc=$?
    env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_telemetry.py \
      tests/test_trace_stitching.py tests/test_programs.py \
      tests/test_table_obs.py tests/test_profiling.py \
      tests/test_bus_obs.py "$@" || rc=$?
    exit $rc
    ;;
  --profile)
    shift
    rc=0
    env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.analysis.obs_check || rc=$?
    env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_profiling.py "$@" || rc=$?
    exit $rc
    ;;
  --tenancy)
    shift
    exec env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_tenancy.py "$@"
    ;;
  --locks)
    shift
    rc=0
    # Static half: the pxlock rules must be repo-green (zero
    # unbaselined findings — suppressions/baseline entries carry their
    # written justification in-line / in baseline.json).
    python tools/pxlint.py \
      --rules lock-order,request-from-handler,blocking-call-under-lock \
      || rc=$?
    # Dynamic half: lockdep-instrumented concurrency suites. The
    # conftest enables lockdep at session start (PIXIE_TPU_LOCKDEP=1)
    # and fails any test whose run recorded a violation, even one a
    # handler swallowed.
    env JAX_PLATFORMS=cpu PIXIE_TPU_LOCKDEP=1 \
      python -m pytest -q -m 'not slow' tests/test_lockdep.py \
      tests/test_concurrency.py tests/test_fault_injection.py \
      tests/test_tenancy.py tests/test_telemetry.py "$@" || rc=$?
    exit $rc
    ;;
  --cache)
    shift
    exec env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_result_cache.py "$@"
    ;;
  --storage)
    shift
    exec env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_storage_tier.py "$@"
    ;;
  --soak)
    shift
    exec env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.services.chaos \
      --agents 32 --brokers 2 --seed 0 "$@"
    ;;
  --soak-full)
    shift
    exec env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.services.chaos \
      --agents 128 --brokers 3 --seed 0 --full "$@"
    ;;
  --bounds)
    shift
    rc=0
    env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.analysis.bound_check || rc=$?
    env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_bounds.py "$@" || rc=$?
    exit $rc
    ;;
  --analyze)
    shift
    rc=0
    python tools/pxlint.py "$@" || rc=$?
    env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.analysis.bench_check || rc=$?
    env JAX_PLATFORMS=cpu \
      python -m pixie_tpu.analysis.bound_check || rc=$?
    # pxlock gate: static lock rules + lockdep-instrumented
    # concurrency suites (also reaches --tier1 through this step).
    "$0" --locks || rc=$?
    exit $rc
    ;;
  --faults)
    shift
    rc=0
    for seed in 0 7 1337; do
      echo "== fault-injection suite, seed $seed =="
      env JAX_PLATFORMS=cpu \
        PIXIE_TPU_FAULT_SEED=$seed \
        python -m pytest -q tests/test_fault_injection.py "$@" || rc=$?
    done
    exit $rc
    ;;
  --lint-metrics)
    shift
    rc=0
    # One lint framework: the static half is the pxlint metrics-naming
    # rule; the dynamic half exercises the live registration surface.
    python tools/pxlint.py --rules metrics-naming || rc=$?
    env JAX_PLATFORMS=cpu \
      python -m pytest -q tests/test_metrics_lint.py "$@" || rc=$?
    exit $rc
    ;;
  --fast)
    shift
    [ $# -eq 0 ] && set -- tests/
    exec env JAX_PLATFORMS=cpu \
      python -m pytest -q -m 'not slow and not stress' "$@"
    ;;
  --tier1)
    # Static-analysis gate first (fast; see --analyze): a non-baselined
    # lint finding or a replay-shape verification failure fails tier 1.
    "$0" --analyze; rc_analyze=$?
    # Self-observability script gate (the pytest half of --obs already
    # runs inside the main sweep below).
    env JAX_PLATFORMS=cpu python -m pixie_tpu.analysis.obs_check \
      || rc_analyze=1
    # Multi-tenant overload gate: the slow-marked p99 isolation test is
    # excluded from the 'not slow' sweep below, so run the tenancy
    # suite explicitly here.
    "$0" --tenancy || rc_analyze=1
    # Chaos-soak gate (broker HA): fixed-seed 32-agent/2-broker soak
    # with a leader kill — zero lost queries, zero leaked threads,
    # isolation bound held while faults are active.
    "$0" --soak || rc_analyze=1
    # ROADMAP.md "Tier-1 verify", verbatim:
    set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); [ $rc -eq 0 ] && rc=$rc_analyze; exit $rc
    ;;
esac
exec env JAX_PLATFORMS=cpu python -m pytest "$@"
