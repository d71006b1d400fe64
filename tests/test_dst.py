"""Deterministic simulation testing (resilience/dst.py + clock.py).

Covers: the SimClock virtual-time event loop, the clock seam through
the serving layer (exact virtual-tick TTFTs, clocked span timestamps),
bit-identical trace hashes for replayed seeds, the regression corpus
(schedules exercising every fault kind must audit clean), the auditor's
teeth (planted engine leaks and lost-request mutations ARE caught), and
shrinker minimality. See docs/dst.md.
"""

import json
import threading

import pytest

from deepspeed_tpu.resilience.clock import SimClock, WallClock, use_clock
from deepspeed_tpu.resilience.dst import (Schedule, SimConfig, SimEngine,
                                          SimEvent, generate_schedule,
                                          dump_repro, load_repro,
                                          run_schedule, shrink_schedule,
                                          spec_identity_problems)


# ----------------------------------------------------------------------
# SimClock: the virtual-time event loop
# ----------------------------------------------------------------------

def test_simclock_advances_only_on_request():
    c = SimClock()
    assert c.now() == 0.0
    c.advance(2.5)
    assert c.now() == 2.5
    assert c.time() == pytest.approx(1_700_000_000.0 + 2.5)


def test_simclock_rejects_rewind():
    c = SimClock()
    with pytest.raises(ValueError):
        c.advance(-0.1)


def test_simclock_timers_fire_in_order_at_exact_instants():
    c = SimClock()
    fired = []
    c.call_at(3.0, lambda: fired.append(("b", c.now())))
    c.call_at(1.0, lambda: fired.append(("a", c.now())))
    c.advance(2.0)
    assert fired == [("a", 1.0)]
    c.advance(2.0)
    assert fired == [("a", 1.0), ("b", 3.0)]
    assert c.now() == 4.0


def test_simclock_wait_event_pumps_until_set():
    c = SimClock()
    evt = threading.Event()
    steps = []

    def pump():
        steps.append(c.now())
        if len(steps) >= 3:
            evt.set()

    c.pump = pump
    assert c.wait_event(evt, timeout=100.0)
    assert len(steps) == 3
    assert c.now() < 100.0


def test_simclock_wait_event_times_out_virtually():
    c = SimClock()
    evt = threading.Event()
    assert not c.wait_event(evt, timeout=7.0)
    assert c.now() == 7.0          # burned virtually, instantly


def test_simclock_untimed_wait_gives_up_on_idle_pump():
    # a pump that reports "no work" (False) over and over cannot set the
    # event: the wait must burn its budget in one jump, not grind
    # through ~1e6 pump iterations
    c = SimClock()
    calls = []
    c.pump = lambda: calls.append(1) is not None and False
    evt = threading.Event()
    assert not c.wait_event(evt, timeout=None)
    assert len(calls) <= c.idle_pump_limit + 1
    assert c.now() >= c.max_untimed_wait


def test_simclock_nested_sleep_does_not_reenter_pump():
    c = SimClock()
    depth = []

    def pump():
        depth.append(1)
        c.sleep(0.5)               # a sleep INSIDE the pumped step
        depth.pop()

    c.pump = pump
    c.sleep(1.0)
    assert depth == []             # pump ran once, not recursively


# ----------------------------------------------------------------------
# the clock seam through the serving layer
# ----------------------------------------------------------------------

def test_serving_on_virtual_time_exact_ttft():
    from deepspeed_tpu.serving import ServingEngine

    clock = SimClock()
    with use_clock(clock):
        srv = ServingEngine(SimEngine(), {"policy": "slo",
                                          "stuck_tick_timeout_s": 0.0},
                            start=False)
        req = srv.submit([1, 2, 3], max_new_tokens=4,
                         ttft_deadline_s=2.0, deadline_s=10.0)
        assert req.t_submit == 0.0
        while not req.is_terminal:
            srv.step()
            clock.advance(1.0)
        srv.close()
    # prompt prefills on the tick at t=0, so TTFT is exactly 0 virtual
    # seconds and the whole request takes one tick per decode token:
    # deterministic to the bit, no jitter band needed
    assert req.ttft_s == 0.0
    assert req.t_finish == 3.0
    assert req.in_slo() is True


def test_request_span_timestamps_ride_the_sim_clock():
    from deepspeed_tpu.telemetry.spans import RequestStats, StepStats

    clock = SimClock()
    with use_clock(clock):
        clock.advance(42.0)
        assert RequestStats(uid=1, state="finished").timestamp == \
            pytest.approx(1_700_000_000.0 + 42.0)
        assert StepStats(step=1, wall_time_s=0.1).timestamp == \
            pytest.approx(1_700_000_000.0 + 42.0)
    # wall clock restored outside the context
    assert isinstance(
        __import__("deepspeed_tpu.resilience.clock",
                   fromlist=["get_clock"]).get_clock(), WallClock)


def test_constructor_injected_clock_rules_the_request_lifecycle():
    """A fleet given clock=SimClock() WITHOUT use_clock(): requests are
    constructed under the wall clock but must be re-based onto their
    owner's clock at submit, or t_submit (virtual) vs t_finish (wall)
    would corrupt every SLO verdict."""
    from deepspeed_tpu.serving import ServingFleet

    clock = SimClock()
    fleet = ServingFleet(lambda: SimEngine(), {"replicas": 1},
                         {"policy": "slo", "stuck_tick_timeout_s": 0.0},
                         start=False, clock=clock)
    req = fleet.submit([1, 2, 3], max_new_tokens=3, deadline_s=20.0)
    while not req.is_terminal:
        fleet.step()
        clock.advance(1.0)
        assert clock.now() < 100
    fleet.close()
    assert req.t_submit == 0.0
    assert req.t_finish == 2.0            # virtual, not perf_counter
    assert req.in_slo() is True


def test_run_schedule_restores_the_default_registry():
    from deepspeed_tpu.telemetry.registry import get_registry

    before = get_registry()
    run_schedule(generate_schedule(0))
    assert get_registry() is before


def test_retry_backoff_advances_virtual_time():
    from deepspeed_tpu.resilience.retry import RetryPolicy, retry_call

    clock = SimClock()
    calls = []

    def flaky():
        calls.append(clock.now())
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    with use_clock(clock):
        out = retry_call(flaky, policy=RetryPolicy(
            max_attempts=3, backoff_s=2.0, backoff_multiplier=2.0))
    assert out == "ok"
    assert calls == [0.0, 2.0, 6.0]    # exact virtual backoff instants


def test_chaos_collective_delay_advances_virtual_time():
    from deepspeed_tpu.resilience.chaos import FaultInjector

    inj = FaultInjector(collective_delay_s=3.0, collective_delay_every=2)
    clock = SimClock()
    with use_clock(clock):
        inj.on_collective("all_reduce")
        assert clock.now() == 0.0
        inj.on_collective("all_reduce")    # every 2nd call delays
        assert clock.now() == 3.0


# ----------------------------------------------------------------------
# determinism: same seed, same trace hash
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 7])
def test_same_seed_same_trace_hash(seed):
    r1 = run_schedule(generate_schedule(seed))
    r2 = run_schedule(generate_schedule(seed))
    assert r1.trace_hash == r2.trace_hash
    assert r1.tokens == r2.tokens
    assert r1.ok and r2.ok


def test_different_seeds_diverge():
    hashes = {run_schedule(generate_schedule(s)).trace_hash
              for s in range(6)}
    assert len(hashes) == 6


def test_schedule_json_roundtrip_replays_identically(tmp_path):
    sched = generate_schedule(2)
    path = str(tmp_path / "repro.json")
    dump_repro(sched, ["demo"], path)
    loaded, viol = load_repro(path)
    assert viol == ["demo"]
    assert json.dumps(loaded.to_dict(), sort_keys=True) == \
        json.dumps(sched.to_dict(), sort_keys=True)
    assert run_schedule(loaded).trace_hash == \
        run_schedule(sched).trace_hash


# ----------------------------------------------------------------------
# regression corpus: seeds exercising every fault kind audit clean.
# Soak-found failing seeds land HERE (none survive today: every seed in
# the corpus was picked because its schedule composes the risky paths —
# injected tick faults, replica death + failover + respawn, the
# preemption latch, scale events, disaggregated hand-off, FCFS
# head-of-line, cancels racing all of the above).
# ----------------------------------------------------------------------

REGRESSION_SEEDS = [
    0,    # latch + stall + cancels under SLO policy
    1,    # disaggregated prefill/decode + injected tick faults
    2,    # tick faults + replica death + cancels (failover resume)
    3,    # scale events under load
    4,    # autoscale controller live
    10,   # FCFS head-of-line under the same fault surface
    14,   # replica death in a disaggregated fleet (handoff failover)
    # speculative-serving + quantized-KV draws (ISSUE 14): the token-
    # identity invariant (#10) audits every one of these against the
    # pure-function greedy expectation on every event
    23,   # spec drafts + int8 pool + replica death + tick faults
    38,   # spec drafts + int4 pool + latch + scale + tick faults
    43,   # int8 pool in a disaggregated fleet (quantized hand-off wire)
    55,   # spec drafts + int4 pool + disaggregated hand-off
    # gray-failure draws (ISSUE 18): the hedge-conservation (#14),
    # quarantine/capacity-floor (#15) and no-flap (#16) invariants audit
    # these against the live gray plane on every event
    5,    # degraded_tick + stall_burst with the gray plane OFF (pinned
          # baseline: the new fault kinds alone must not violate)
    7,    # flaky_import with quarantine + breakers + hedge all drawn on
    17,   # degraded_tick straggler actually quarantined (and held by
          # the dwell hysteresis — the seed that caught the flap bug)
    46,   # stall_burst + hedged dispatch fired (one backup leg raced)
    47,   # route failures open a circuit breaker mid-schedule
    79,   # degraded_tick + hedged dispatch on the slowed replica
]


@pytest.mark.parametrize("seed", REGRESSION_SEEDS)
def test_regression_corpus_audits_clean(seed):
    report = run_schedule(generate_schedule(seed))
    assert report.ok, report.violations
    assert report.submitted > 0
    # everything submitted is accounted for: the three terminal bins
    # partition the submitted set (no-lost-request, end-state view)
    assert (report.finished + report.cancelled + report.rejected
            == report.submitted)


def test_mini_soak_window():
    """A slice of the CI soak inline: 20 consecutive seeds, zero
    violations (the full >= 200-schedule lane runs in
    scripts/dst_soak.py)."""
    for seed in range(100, 120):
        report = run_schedule(generate_schedule(seed))
        assert report.ok, (seed, report.violations)


@pytest.mark.parametrize("seed", [4, 23, 38])
def test_spec_on_off_token_identity(seed):
    """The spec-decode identity gate on regression seeds that draw
    drafting: the same schedule run with speculation FORCED on and
    forced off must emit per-request streams agreeing on their common
    prefix, exactly for requests finished in both runs (docs/serving.md
    token-identity contract; the soak samples this every CI run)."""
    s_on = generate_schedule(seed)
    s_on.serving_cfg.update(speculative=True, spec_ngram=2,
                            spec_lookahead=4)
    s_off = generate_schedule(seed)
    s_off.serving_cfg["speculative"] = False
    rep_on, rep_off = run_schedule(s_on), run_schedule(s_off)
    assert rep_on.ok, rep_on.violations
    assert rep_off.ok, rep_off.violations
    assert spec_identity_problems(rep_on, rep_off) == []


def test_auditor_catches_token_identity_violation():
    """Teeth for invariant #10: an engine whose verify rows diverge from
    the pure-function greedy stream (an off-by-one context bug planted
    in put_spec's row builder) must trip the token-identity audit."""
    from deepspeed_tpu.resilience.dst import _next_token

    class _DivergentSpecEngine(SimEngine):
        def put_spec(self, uids, tokens, drafts):
            out, verified = super().put_spec(uids, tokens, drafts)
            bad = {}
            for uid, (chain, rows) in verified.items():
                rows = rows.copy()
                for j in range(rows.shape[0]):
                    t = int(rows[j].argmax())
                    rows[j, t] = 0.0
                    rows[j, (t + 1) % rows.shape[1]] = 1.0   # wrong token
                bad[uid] = (chain, rows)
            return out, bad

    sched = generate_schedule(4)              # draws speculative serving
    sched.serving_cfg.update(speculative=True, spec_ngram=2,
                             spec_lookahead=4, spec_accept_floor=0.0)
    report = run_schedule(
        sched,
        engine_factory=lambda: _DivergentSpecEngine(
            SimConfig(**sched.engine_cfg)))
    assert not report.ok
    assert any("token-identity" in v for v in report.violations), \
        report.violations


# ----------------------------------------------------------------------
# the auditor has teeth
# ----------------------------------------------------------------------

class _LeakyEngine(SimEngine):
    """discard() drops the descriptor without releasing its pages."""

    def discard(self, uid):
        seq = self.seqs.pop(uid, None)
        if seq is None:
            return
        self.cache.give_slot(seq.slot)        # slot back, blocks leaked
        self._resume_uids.add(uid)


def test_auditor_catches_block_leak():
    sched = generate_schedule(3)              # hits the discard path
    report = run_schedule(
        sched,
        engine_factory=lambda: _LeakyEngine(SimConfig(**sched.engine_cfg)))
    assert not report.ok
    assert any("block-balance" in v or "leak" in v
               for v in report.violations), report.violations


def test_auditor_catches_lost_requests(monkeypatch):
    """Mutate failover to DROP orphans instead of re-routing them: the
    conservation invariant must fire at the next audit point."""
    from deepspeed_tpu.serving.fleet import ServingFleet

    monkeypatch.setattr(ServingFleet, "_failover_orphans",
                        lambda self, orphans, source: None)
    sched = generate_schedule(5)   # replica death with in-flight orphans
    report = run_schedule(sched)
    assert not report.ok
    assert any("conservation" in v or "liveness" in v
               for v in report.violations), report.violations


# ----------------------------------------------------------------------
# shrinking
# ----------------------------------------------------------------------

def test_shrinker_minimizes_to_the_triggering_pair():
    """Synthetic failure predicate: the run 'fails' iff one specific
    submit AND its cancel are both present. The shrinker must reduce an
    arbitrary schedule to exactly that pair, and the result must be
    1-minimal."""
    sched = generate_schedule(0)
    target = next(e.payload["target"] for e in sched.events
                  if e.kind == "cancel")

    def fails(s: Schedule) -> bool:
        kinds = {(e.kind, e.payload.get("ix", e.payload.get("target")))
                 for e in s.events}
        return ("submit", target) in kinds and ("cancel", target) in kinds

    assert fails(sched)

    shrunk = shrink_schedule(sched, fails=fails)
    assert fails(shrunk)
    assert len(shrunk.events) == 2
    for i in range(len(shrunk.events)):
        remaining = shrunk.events[:i] + shrunk.events[i + 1:]
        assert not fails(shrunk.replace_events(remaining)), \
            "shrunk schedule is not 1-minimal"


def test_shrinker_requires_a_failing_schedule():
    with pytest.raises(ValueError):
        shrink_schedule(generate_schedule(0), fails=lambda s: False)


def test_shrunk_real_violation_still_reproduces(tmp_path):
    """End-to-end repro workflow on a real (planted) violation: shrink
    a leaky-engine failure, dump it, reload it, and watch it fail
    again."""
    sched = generate_schedule(3)

    def fails(s: Schedule) -> bool:
        return bool(run_schedule(
            s, engine_factory=lambda: _LeakyEngine(
                SimConfig(**s.engine_cfg))).violations)

    shrunk = shrink_schedule(sched, fails=fails)
    assert len(shrunk.events) < len(sched.events)
    path = dump_repro(shrunk, ["planted leak"], str(tmp_path / "r.json"))
    loaded, _ = load_repro(path)
    assert fails(loaded)
