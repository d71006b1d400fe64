#!/bin/bash
# Test runner: every lane here runs on the virtual 8-device CPU platform
# (JAX_PLATFORMS=cpu) — tests and evidence lanes check correctness and
# counts, never the chip. The chip is chip_smoke.py's (README "Running").
#
# After the unit suite, the telemetry smoke test runs a tiny train loop with
# telemetry enabled and validates every emitted JSONL step record against
# the schema (scripts/telemetry_smoke.py exits nonzero on violation).
# dslint gate (docs/static_analysis.md): the AST invariant checker must
# report ZERO unsuppressed, un-baselined findings on the package —
# host-sync/trace-hygiene in traced code, recompile hazards, lock
# discipline (region -> cell -> fleet -> replica, nothing blocking
# under a held lock), exception discipline, and the dsrace lockset
# races rule (shared attributes reachable from >= 2 thread roles with
# no common lock). It prints its own findings-count summary line.
env JAX_PLATFORMS=cpu \
    python -m deepspeed_tpu.analysis --check --baseline dslint_baseline.json
dslint_rc=$?

# -m "not slow" matches the tier-1 lane (ROADMAP.md): the slow-marked
# autotuner grid searches would otherwise add minutes per run
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest "${@:-tests/}" -q -m "not slow"
pytest_rc=$?

smoke_rc=0
if [ "$#" -eq 0 ]; then
    # full-suite runs only: a targeted ./run_tests.sh tests/test_x.py
    # shouldn't pay the smoke loops' engine builds
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python scripts/telemetry_smoke.py
    smoke_rc=$?

    # chaos smoke: seeded kill mid-train + ElasticAgent auto-resume; the
    # final loss must be bit-identical to an uninterrupted run
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python scripts/chaos_smoke.py
    chaos_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$chaos_rc
    fi

    # DST soak (CPU evidence lane, docs/dst.md): >= 200 seeded
    # randomized fault schedules through the real serving fleet on
    # virtual time — zero invariant violations (block balance, request
    # state machine, no-lost-request conservation, span/SLO ledger,
    # stream delivery, monotone time), and a replay sample must produce
    # bit-identical event-trace hashes. Failures are auto-shrunk to
    # minimal repro JSONs.
    env JAX_PLATFORMS=cpu \
        python scripts/dst_soak.py
    dst_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$dst_rc
    fi

    # dsrace cross-validation lane (docs/static_analysis.md "races"):
    # fleet + region DST schedules re-run with the runtime lock-order
    # sanitizer installed. Gates: zero sanitizer violations (order
    # inversions / cycles / same-tier nesting), every runtime-observed
    # lock edge present in dslint's STATIC lock graph (a miss is a
    # static-model false negative), every documented-tier static edge
    # exercised, sanitized replays bit-identical, and the dslint races
    # rule repo-clean. Writes RACE_r01.json.
    env JAX_PLATFORMS=cpu \
        python scripts/race_lane.py
    race_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$race_rc
    fi

    # dslint findings-count trend artifact (DSLINT_TREND.json, fixed
    # name): per-rule live/suppressed/baselined counts so suppression
    # and baseline growth show up as a reviewable diff per PR
    env JAX_PLATFORMS=cpu \
        python scripts/dslint_trend.py
    trend_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$trend_rc
    fi

    # region soak (CPU evidence lane, docs/serving.md "Region & cells",
    # docs/dst.md "Region-scale events"): >= 200 seeded REGION chaos
    # schedules — whole-cell outages, inter-cell partitions + heals,
    # autoscaler lag, plus every fleet-tier fault — through the real
    # two-tier serving stack on virtual time. Gates: zero invariant
    # violations (incl. heal convergence / single ownership and
    # shed-span), bit-identical (trace_hash, span_hash) replay, every
    # fault kind exercised, brownout shedding strictly priority-ordered.
    env JAX_PLATFORMS=cpu \
        python scripts/region_soak.py
    region_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$region_rc
    fi

    # gray-failure lane (CPU evidence lane, docs/fault_tolerance.md
    # "Gray failures", docs/dst.md): the scripted straggler experiment
    # (one replica degraded k-fold on virtual time) must quarantine the
    # straggler within the vtick budget, fire hedged backup legs, and
    # beat the plane-off p99 TTFT by the gated ratio without losing
    # work; plus >= 200 seeded gray-chaos schedules (degraded_tick /
    # stall_burst / flaky_import draws) with zero invariant violations
    # — hedge conservation, quarantine convergence + capacity floor,
    # and no-flap included — and bit-identical sampled replays.
    # Writes GRAY_r01.json.
    env JAX_PLATFORMS=cpu \
        python scripts/gray_lane.py
    gray_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$gray_rc
    fi

    # global KV tier lane (CPU evidence lane, docs/serving.md "Global
    # KV tier", docs/dst.md): the scripted shared-prefix A/B (global
    # tier ON vs per-replica caching only, virtual time) must beat the
    # baseline's global prefix hit rate and mean TTFT by the gated
    # ratios with zero KV page leaks on BOTH legs; plus >= 200 seeded
    # kv-chaos schedules (stale_directory / corrupt_adopt /
    # cold_pressure draws) with zero invariant violations — directory-
    # residency containment, cold-tier accounting, and verify-before-
    # import included — and bit-identical sampled replays.
    # Writes KVTIER_r01.json.
    env JAX_PLATFORMS=cpu \
        python scripts/kvtier_lane.py
    kvtier_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$kvtier_rc
    fi

    # SLO lane (CPU evidence lane, docs/observability.md "Region
    # rollups & SLO alerting"): >= 200 seeded region chaos schedules
    # with every digest observation mirrored into a pooled ground-truth
    # stream. Gates: merged region sketch sample counts exactly equal
    # pooled counts (outages/partitions/salvage included), p50/p99
    # within the sketch's documented relative-error bound, digest +
    # alert streams bit-identical on replay, rollup cost independent of
    # replica count, and the scripted two-tenant burst fires/clears
    # per-tenant burn-rate alerts deterministically. Writes SLO_r01.json.
    env JAX_PLATFORMS=cpu \
        python scripts/slo_lane.py
    slo_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$slo_rc
    fi

    # rollout smoke (CPU evidence lane, docs/serving.md "Rollout,
    # canary, and migration"): a scripted end-to-end canary -> promote
    # rollout with a live migration riding along, plus the seeded
    # versioned-serving chaos sweep (rollout / migrate / canary SLO
    # regression / corrupt swap / death-at-flip). Gates: zero invariant
    # violations (incl. version-stream atomicity, per-tenant version
    # monotonicity, rollback convergence), zero lost requests, the
    # availability dip vs a fault-free baseline bounded, bit-identical
    # replay. Writes ROLLOUT_r01.json.
    env JAX_PLATFORMS=cpu \
        python scripts/rollout_smoke.py
    rollout_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$rollout_rc
    fi

    # serving-scheduler smoke (CPU evidence lane, docs/serving.md): on
    # VIRTUAL time (SimClock; deterministic, no calibration or jitter
    # bands) the SLO-aware policy must serve every offered request
    # in-SLA while FCFS head-of-line blocking misses every interactive
    # deadline, and allocator block balance must be exactly zero after
    # drain() on every leg — including injected tick faults and
    # mid-stream cancellations
    env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py
    serve_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$serve_rc
    fi

    # speculative-serving + quantized-KV smoke (CPU evidence lane,
    # docs/serving.md "Speculative scheduling" / "KV quantization"): on
    # virtual time, the pinned workload served with speculation ON must
    # emit TOKEN-IDENTICAL greedy streams in strictly fewer engine
    # ticks than with it off (drafts proposed AND accepted); an int8 KV
    # pool at the same byte budget must sustain >= 1.8x the concurrent
    # decode sequences; the quantized export_kv hand-off must book a
    # >= 1.8x wire reduction in the comm ledger and adopt bit-equal;
    # zero leaked KV blocks on every leg
    env JAX_PLATFORMS=cpu \
        python scripts/serve_spec_smoke.py
    spec_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$spec_rc
    fi

    # serving-fleet smoke (CPU evidence lane, docs/serving.md): in-SLA
    # goodput must scale EXACTLY 2x from 1 -> 2 replicas under the
    # seeded overload on virtual time (one full wave per replica, exact
    # tick-count TTFT gate); prefix-affinity routing must beat
    # least-loaded on prefix-cache hit rate; injected replica death
    # (failover) and the disaggregated prefill->decode handoff must be
    # bit-identical to an uninterrupted single-engine run (real
    # threads); zero leaked KV pages on every replica on every leg
    env JAX_PLATFORMS=cpu \
        python scripts/fleet_smoke.py
    fleet_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$fleet_rc
    fi

    # host-overhead perf smoke (CPU evidence lane, docs/performance.md):
    # steady-state host overhead with prefetch + train_steps(8) must stay
    # >= 2x lower than the synchronous per-step path, with zero
    # shape-churn recompiles. The bench sizes its own device mesh.
    env JAX_PLATFORMS=cpu \
        python scripts/host_overhead_bench.py --check
    perf_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$perf_rc
    fi

    # quant-comm gate (CPU evidence lane, docs/communication.md): the
    # compressed-collectives facade must show >= 2x wire-byte reduction
    # on the int8 weight all-gather and >= 4x on the int4 inter-slice
    # gradient hop per the bytes-on-wire ledger, quantization error
    # within the documented QuantSpec bound, the staged T3 overlap
    # schedule bit-exact to serial with compression off, zero recompiles
    # in the overlapped fused scan, and the committed NORTHSTAR
    # projection's overlapped zero3 comm exposure cut >= 50% vs the
    # serial booking. The smoke sizes its own 8-device mesh.
    env JAX_PLATFORMS=cpu \
        python scripts/quant_comm_smoke.py
    qc_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$qc_rc
    fi

    # trace lane (CPU evidence lane, docs/observability.md "Tracing &
    # flight recorder"): a seeded DST schedule run twice must produce
    # bit-identical canonical span-tree hashes; the Chrome-trace export
    # must pass the schema check; a planted tick-fault with a spent
    # retry budget must auto-dump the flight recorder to disk; and
    # engine.overlap_report()'s MEASURED comm exposure must agree with
    # modeled_exposure within the documented band (TIMELINE_r01.json)
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python scripts/trace_smoke.py
    trace_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        smoke_rc=$trace_rc
    fi
fi

if [ "$dslint_rc" -ne 0 ]; then
    exit "$dslint_rc"
fi
if [ "$pytest_rc" -ne 0 ]; then
    exit "$pytest_rc"
fi
exit "$smoke_rc"
