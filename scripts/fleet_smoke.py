#!/usr/bin/env python
"""Serving-fleet smoke: goodput scaling, prefix-affinity routing,
replica-death failover, and disaggregated prefill/decode hand-off
(docs/serving.md, docs/dst.md).

CPU evidence lane for the fleet subsystem (run by run_tests.sh):

* **scaling** — the SERVE_SCHED-style seeded overload (a burst of
  equal-priority interactive requests with a tight TTFT SLO) replayed
  against a 1-replica and a 2-replica fleet, on **virtual time**
  (SimClock + manual ``fleet.step()`` driving — the DST clock seam):
  one fleet step is one virtual second, the TTFT deadline is an exact
  tick count, and the verdict is deterministic. A TTFT deadline of 6
  ticks admits exactly one wave of ``max_seqs`` requests per replica
  (wave 1 sees first tokens on the first tick; wave 2's first token
  cannot arrive before wave 1's ~25-tick decode finishes), so doubling
  replicas exactly doubles the in-SLA count. The pre-DST design needed
  a per-host tick calibration, a 12-tick deadline and a documented
  0.5x..6x jitter-tolerance band; all three are deleted — the gates are
  exact counts and the scaling ratio gate is tightened from >= 1.8x to
  exactly 2.0x;
* **affinity** — repeat-prefix traffic (P shared full-block prefixes,
  R rounds each, shuffled per round) routed once by least-loaded and
  once by the prefix-affinity consistent hash, also on virtual time.
  Gate: exact deterministic hit rates — affinity keeps every repeat
  round on its prefix's home replica (5/6 rounds hit) while
  least-loaded scatters them;
* **failover** — a seeded replica death (chaos ``replica_die_at_tick``)
  mid-decode under REAL threads: the fleet harvests the dead replica's
  in-flight requests and re-queues them on the survivor via the
  bit-exact resume path. Gate: every greedy token stream is IDENTICAL
  to an uninterrupted single-engine run, and the dead replica's
  allocator balances (suspect KV discarded, never published);
* **disaggregated** — 1 prefill + 1 decode replica: prompt KV crosses
  the export/import seam, decode continues elsewhere. Gate: greedy
  streams identical to the single-engine run, one hand-off per request;
* zero leaked KV pages on EVERY replica of EVERY leg after drain
  (prefix caches dropped, every page back on the free list).

Writes FLEET_<round>.json (round via DST_ROUND, default r07).

    JAX_PLATFORMS=cpu python scripts/fleet_smoke.py
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DST_ROUND", "r07")

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "scripts"))

SEED = 0
PROMPT_LEN = 12

# scaling leg: one wave of max_seqs requests per replica meets the TTFT
# deadline, the second structurally cannot: wave-1 TTFT is 0-1 virtual
# ticks, wave-2 TTFT >= the ~25-tick wave-1 decode. 6 ticks sits between
# them with deterministic margin on BOTH sides (no jitter band needed on
# virtual time).
N_SCALE = 16
SCALE_OUT = 24
SCALE_TTFT_DEADLINE_TICKS = 6.0

# affinity leg
N_PREFIXES = 6
N_ROUNDS = 6                    # round 0 is the cold fill
AFFINITY_OUT = 4

# failover / disaggregation legs
N_EXACT = 8
EXACT_OUT = 16

#: liveness rail for the manually-driven virtual-time legs
MAX_VTICKS = 4000


def _build_engine():
    import jax.numpy as jnp

    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    model, params = _build_engine._cache
    cfg = RaggedConfig(token_budget=64, max_seqs=4, kv_block_size=8,
                       n_kv_blocks=96, max_context=64, dtype=jnp.float32,
                       enable_prefix_cache=True)
    return RaggedInferenceEngine(model, cfg, params=params)


def _init_model():
    import jax

    from deepspeed_tpu.models import Llama

    model = Llama("tiny", d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
                  vocab_size=256, max_seq_len=128, use_flash=False,
                  remat=False)
    _build_engine._cache = (model, model.init(jax.random.PRNGKey(0)))


def _reset(eng) -> None:
    """Between-leg reset: engine must already be drained/empty."""
    assert not eng.seqs, f"engine still holds {list(eng.seqs)}"
    if eng.prefix_cache is not None:
        eng.prefix_cache.drop_all(eng.allocator)
        eng.prefix_cache.hits = 0
        eng.prefix_cache.misses = 0
    eng._resume_uids.clear()


def _leak_check(engines) -> dict:
    from deepspeed_tpu.inference.kv_cache import block_balance_report

    problems = []
    free_ok = True
    for i, eng in enumerate(engines):
        rep = block_balance_report(eng)
        problems += [f"engine{i}: {p}" for p in rep["problems"]]
        if eng.prefix_cache is not None:
            eng.prefix_cache.drop_all(eng.allocator)
        free_ok = free_ok and (eng.allocator.free_blocks
                               == eng.allocator.n_blocks)
    return {"problems": problems, "all_pages_free": free_ok,
            "zero_leak": not problems and free_ok}


def _fleet_over(engines, fleet_cfg: dict, serving_cfg: dict,
                start: bool = True):
    from deepspeed_tpu.serving import ServingFleet

    pool = list(engines)
    return ServingFleet(lambda: pool.pop(0), fleet_cfg, serving_cfg,
                        start=start)


def _drive_until_terminal(fleet, clock, reqs) -> None:
    """Virtual-time driving loop: one fleet step per virtual second."""
    while not all(r.is_terminal for r in reqs):
        fleet.step()
        clock.advance(1.0)
        assert clock.now() < MAX_VTICKS, "virtual-time leg did not quiesce"


def _reference_tokens(eng, prompts, max_new) -> list:
    """Uninterrupted single-engine run: the bit-exactness oracle."""
    from deepspeed_tpu.serving import ServingEngine

    srv = ServingEngine(eng, {"policy": "slo", "drain_timeout_s": 300.0})
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        r.wait(timeout=300.0)
    srv.close()
    assert all(r.state.value == "finished" for r in reqs), \
        [r.state.value for r in reqs]
    out = [list(r.tokens) for r in reqs]
    _reset(eng)
    return out


# ----------------------------------------------------------------------
def _scaling_leg(engines) -> dict:
    """Seeded burst overload against a fleet of len(engines) replicas,
    manually stepped on a fresh SimClock."""
    from deepspeed_tpu.resilience import SimClock, use_clock

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, 256, (PROMPT_LEN,)).tolist()
               for _ in range(N_SCALE)]
    clock = SimClock()
    with use_clock(clock):
        fleet = _fleet_over(engines, {"replicas": len(engines)},
                            {"policy": "slo", "max_queue": 256,
                             "stuck_tick_timeout_s": 0.0,
                             "drain_timeout_s": 300.0}, start=False)
        clock.pump = fleet.step
        reqs = [fleet.submit(p, max_new_tokens=SCALE_OUT,
                             ttft_deadline_s=SCALE_TTFT_DEADLINE_TICKS)
                for p in prompts]
        _drive_until_terminal(fleet, clock, reqs)
        vticks = clock.now()
        drained = fleet.drain(timeout=300.0)
        fleet.close()
    in_sla = sum(r.state.value == "finished" and r.in_slo() is True
                 for r in reqs)
    leak = _leak_check(engines)
    for eng in engines:
        _reset(eng)
    return {"replicas": len(engines), "offered": N_SCALE,
            "finished": sum(r.state.value == "finished" for r in reqs),
            "rejected": sum(r.state.value == "rejected" for r in reqs),
            "in_sla": in_sla, "virtual_ticks": round(vticks),
            "drained": drained, "leak_check": leak}


def _affinity_leg(engines, router: str) -> dict:
    """Repeat-prefix traffic on virtual time; measures the aggregate
    prefix-cache hit rate under the given router."""
    from deepspeed_tpu.resilience import SimClock, use_clock

    rng = np.random.default_rng(SEED + 1)
    bs = engines[0].config.kv_block_size
    prefixes = [rng.integers(1, 256, (2 * bs,)).tolist()
                for _ in range(N_PREFIXES)]
    h0 = sum(e.prefix_cache.hits for e in engines)
    m0 = sum(e.prefix_cache.misses for e in engines)
    n_ok = 0
    clock = SimClock()
    with use_clock(clock):
        fleet = _fleet_over(engines, {"replicas": len(engines),
                                      "router": router},
                            {"policy": "slo", "max_queue": 256,
                             "stuck_tick_timeout_s": 0.0,
                             "drain_timeout_s": 300.0}, start=False)
        clock.pump = fleet.step
        for _rnd in range(N_ROUNDS):
            order = rng.permutation(N_PREFIXES)     # break accidental
            reqs = []                               # least-loaded stickiness
            for i in order:
                tail = rng.integers(1, 256, (4,)).tolist()
                reqs.append(fleet.submit(prefixes[int(i)] + tail,
                                         max_new_tokens=AFFINITY_OUT))
            # round barrier: repeats only hit PUBLISHED KV
            _drive_until_terminal(fleet, clock, reqs)
            n_ok += sum(r.state.value == "finished" for r in reqs)
        vticks = clock.now()
        drained = fleet.drain(timeout=300.0)
        fleet.close()
    hits = sum(e.prefix_cache.hits for e in engines) - h0
    misses = sum(e.prefix_cache.misses for e in engines) - m0
    leak = _leak_check(engines)
    for eng in engines:
        _reset(eng)
    return {"router": router, "offered": N_PREFIXES * N_ROUNDS,
            "finished": n_ok, "cache_hits": hits, "cache_misses": misses,
            "hit_rate": round(hits / max(1, hits + misses), 3),
            "virtual_ticks": round(vticks), "drained": drained,
            "leak_check": leak}


def _failover_leg(engines, prompts, ref) -> dict:
    """Chaos-injected replica death mid-decode (REAL threads); survivors
    absorb the in-flight work bit-exactly."""
    from deepspeed_tpu.resilience import FaultInjector, install_fault_injector

    inj = FaultInjector(replica_die_at_tick=10, replica_die_index=0)
    install_fault_injector(inj)
    fleet = _fleet_over(engines, {"replicas": len(engines),
                                  "health_interval_s": 0.01},
                        {"policy": "slo", "drain_timeout_s": 300.0})
    reqs = [fleet.submit(p, max_new_tokens=EXACT_OUT) for p in prompts]
    for r in reqs:
        r.wait(timeout=300.0)
    drained = fleet.drain(timeout=300.0)
    dead = [r.name for r in fleet.replicas if r.state == "dead"]
    fleet.close()
    install_fault_injector(None)
    got = [list(r.tokens) for r in reqs]
    leak = _leak_check(engines)
    for eng in engines:
        _reset(eng)
    return {"offered": len(prompts),
            "finished": sum(r.state.value == "finished" for r in reqs),
            "death_injected": inj.injected.get("replica_death", 0),
            "dead_replicas": dead,
            "bit_exact": got == ref,
            "drained": drained, "leak_check": leak}


def _disagg_leg(engines, prompts, ref) -> dict:
    """1 prefill + 1 decode replica (REAL threads): KV crosses the
    export/import seam."""
    from deepspeed_tpu.telemetry import get_telemetry

    handoffs = get_telemetry().registry.counter("serving/fleet/handoffs")
    h0 = handoffs.value
    fleet = _fleet_over(engines, {"disaggregated": True,
                                  "prefill_replicas": 1, "replicas": 1},
                        {"policy": "slo", "drain_timeout_s": 300.0})
    reqs = [fleet.submit(p, max_new_tokens=EXACT_OUT) for p in prompts]
    for r in reqs:
        r.wait(timeout=300.0)
    drained = fleet.drain(timeout=300.0)
    fleet.close()
    got = [list(r.tokens) for r in reqs]
    leak = _leak_check(engines)
    for eng in engines:
        _reset(eng)
    return {"offered": len(prompts),
            "finished": sum(r.state.value == "finished" for r in reqs),
            "handoffs": handoffs.value - h0,
            "bit_exact": got == ref,
            "drained": drained, "leak_check": leak}


def main() -> int:
    _init_model()
    e1, e2 = _build_engine(), _build_engine()

    rng = np.random.default_rng(SEED + 2)
    exact_prompts = [rng.integers(1, 256, (PROMPT_LEN,)).tolist()
                     for _ in range(N_EXACT)]
    ref = _reference_tokens(e1, exact_prompts, EXACT_OUT)

    legs = {}
    legs["scale_1"] = _scaling_leg([e1])
    legs["scale_2"] = _scaling_leg([e1, e2])
    legs["affinity_least_loaded"] = _affinity_leg([e1, e2], "least_loaded")
    legs["affinity_prefix"] = _affinity_leg([e1, e2], "prefix_affinity")
    legs["failover"] = _failover_leg([e1, e2], exact_prompts, ref)
    legs["disaggregated"] = _disagg_leg([e1, e2], exact_prompts, ref)

    for name, leg in legs.items():
        extras = {k: leg[k] for k in ("in_sla", "hit_rate", "handoffs",
                                      "death_injected", "bit_exact")
                  if k in leg}
        print(f"[fleet-smoke] {name}: finished={leg['finished']}"
              f"/{leg['offered']} {extras} "
              f"zero_leak={leg['leak_check']['zero_leak']}")

    in1, in2 = legs["scale_1"]["in_sla"], legs["scale_2"]["in_sla"]
    ratio = in2 / in1 if in1 else float("inf")
    max_seqs = e1.config.max_seqs
    gates = {
        # strictly tighter than the pre-DST (FLEET_r06) ">= 1.8x with
        # jitter band" gate: EXACT wave counts, EXACT 2x scaling
        "goodput_scales_exactly_2x":
            in1 == max_seqs and in2 == 2 * max_seqs,
        "affinity_beats_least_loaded_hit_rate":
            legs["affinity_prefix"]["hit_rate"]
            > legs["affinity_least_loaded"]["hit_rate"],
        "failover_bit_exact": legs["failover"]["bit_exact"]
            and legs["failover"]["death_injected"] == 1
            and legs["failover"]["dead_replicas"] == ["replica-0"]
            and legs["failover"]["finished"] == N_EXACT,
        "disagg_bit_exact": legs["disaggregated"]["bit_exact"]
            and legs["disaggregated"]["handoffs"] == N_EXACT
            and legs["disaggregated"]["finished"] == N_EXACT,
        "all_legs_drained": all(l["drained"] for l in legs.values()),
        "zero_leak_all_legs": all(l["leak_check"]["zero_leak"]
                                  for l in legs.values()),
    }
    report = {
        "metric": "fleet_in_sla_goodput_scaling_1_to_2_replicas",
        "seed": SEED,
        "clock": "virtual for scaling/affinity legs (SimClock; 1 fleet "
                 "step = 1 virtual second); real threads for "
                 "failover/disaggregated legs",
        "workload": {"n_scale": N_SCALE, "scale_out": SCALE_OUT,
                     "scale_ttft_deadline_ticks": SCALE_TTFT_DEADLINE_TICKS,
                     "prompt_len": PROMPT_LEN,
                     "n_prefixes": N_PREFIXES, "n_rounds": N_ROUNDS,
                     "n_exact": N_EXACT, "exact_out": EXACT_OUT},
        "legs": legs,
        "gates": gates,
        "value": round(ratio, 3),
    }
    from _artifact import write_artifact

    import jax

    path = write_artifact("FLEET", report,
                          device=jax.devices()[0].device_kind)
    print(f"[fleet-smoke] artifact: {path}")
    failed = [g for g, ok in gates.items() if not ok]
    if failed:
        print(f"fleet smoke: FAILED gates {failed}")
        return 1
    print(f"fleet smoke: OK — in-SLA goodput {in1} -> {in2} "
          f"(exactly {ratio:.2f}x) from 1 -> 2 replicas on virtual time; "
          f"affinity hit rate {legs['affinity_prefix']['hit_rate']} > "
          f"least-loaded {legs['affinity_least_loaded']['hit_rate']}; "
          f"failover and disaggregated hand-off bit-exact; zero leaked "
          f"KV pages everywhere")
    return 0


if __name__ == "__main__":
    sys.exit(main())
