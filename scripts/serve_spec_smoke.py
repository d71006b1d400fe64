#!/usr/bin/env python
"""Speculative-serving + quantized-KV smoke: tick-count and capacity
gates on virtual time (docs/serving.md "Speculative scheduling" /
"KV quantization").

CPU evidence lane (run by run_tests.sh), three legs on the REAL ragged
engine + ServingEngine, every leg on SimClock (1 engine tick = 1
virtual second — deterministic, no calibration):

* spec A/B: the same seeded request set served with speculation OFF
  then ON. Gates: every request's greedy stream is TOKEN-IDENTICAL
  across the two legs (the serving tick's headline contract), drafts
  actually proposed AND accepted, and the spec-on leg finishes the
  whole workload in strictly fewer engine ticks;
* kv-quant capacity: the same admission workload against an fp pool
  and an int8 pool sized to the SAME byte budget
  (``kv_blocks_for_bytes``). Gate: the quantized pool sustains >= 1.8x
  the concurrent decode sequences;
* quantized hand-off wire: ``export_kv`` under ``kv_quant=int8`` books
  a ``kv_handoff`` ledger row whose wire bytes are ~half the fp
  logical bytes (the disaggregated hand-off's compression, audited in
  the same bytes-on-wire ledger as the collectives).
* every leg: zero leaked KV blocks after drain.

Writes SERVE_SPEC_<round>.json (round via DST_ROUND, default r01).

    JAX_PLATFORMS=cpu python scripts/serve_spec_smoke.py
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DST_ROUND", "r01")

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "scripts"))

SEED = 0
MAX_VTICKS = 4000        # liveness rail for the virtual-time drive loops
# spec A/B workload: four pinned prompts whose greedy continuations on
# the seeded tiny model enter cycles early, so prompt-lookup drafting
# accepts on EVERY request (measured acceptance 8..25 of ~25 proposed
# each at lookahead 4) — the tick-count gate is deterministic, not a
# lucky draw over random prompts
SPEC_PROMPTS = ([5, 6, 7, 8], [9, 3, 9, 3, 9, 3],
                [40, 41, 40, 41], [64, 65, 64, 65])
N_SPEC_REQS = len(SPEC_PROMPTS)
SPEC_OUT = 48
N_CAP_REQS = 32          # capacity leg: admission pressure
CAP_PROMPT = 16
CAP_OUT = 4


def _model():
    import jax

    from deepspeed_tpu.models import Llama

    model = Llama("tiny", d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=128, max_seq_len=512, use_flash=False,
                  remat=False)
    return model, model.init(jax.random.PRNGKey(5))


def _engine(model, params, **kw):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    kw.setdefault("token_budget", 64)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("n_kv_blocks", 96)
    kw.setdefault("max_context", 256)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("enable_prefix_cache", True)
    return RaggedInferenceEngine(model, RaggedConfig(**kw), params=params)


def _drive(srv, clock, reqs) -> int:
    """Tick until every request is terminal; returns virtual ticks."""
    while not all(r.is_terminal for r in reqs):
        srv.step()
        clock.advance(1.0)
        assert clock.now() < MAX_VTICKS, \
            "virtual-time leg did not quiesce (stranded request?)"
    return round(clock.now())


def _leak_check(eng) -> bool:
    from deepspeed_tpu.inference.kv_cache import block_balance_report

    rep = block_balance_report(eng)
    if eng.prefix_cache is not None:
        eng.prefix_cache.drop_all(eng.allocator)
    return (not rep["problems"]
            and eng.allocator.free_blocks == eng.allocator.n_blocks)


def _run_spec_leg(model, params, speculative: bool) -> dict:
    """One spec A/B leg: N seeded requests, manual virtual-time drive.
    Short varied prompts; the tiny model's greedy continuations cycle,
    so prompt-lookup drafting engages on the ON leg."""
    from deepspeed_tpu.resilience import SimClock, use_clock
    from deepspeed_tpu.serving import ServingEngine

    prompts = [list(p) for p in SPEC_PROMPTS]
    eng = _engine(model, params)
    clock = SimClock()
    with use_clock(clock):
        srv = ServingEngine(eng, {"policy": "slo", "max_queue": 64,
                                  "speculative": speculative,
                                  "spec_ngram": 2, "spec_lookahead": 4,
                                  "drain_timeout_s": 300.0},
                            start=False)
        clock.pump = srv.step
        reqs = [srv.submit(p, max_new_tokens=SPEC_OUT) for p in prompts]
        vticks = _drive(srv, clock, reqs)
        drained = srv.drain()
        srv.close()
    return {
        "speculative": speculative,
        "virtual_ticks": vticks,
        "drained": drained,
        "streams": [list(r.tokens) for r in reqs],
        "request_latency_ticks": [round(r.t_finish - r.t_submit)
                                  for r in reqs],
        "finished": sum(r.state.value == "finished" for r in reqs),
        "spec_proposed": sum(r.spec_proposed for r in reqs),
        "spec_accepted": sum(r.spec_accepted for r in reqs),
        "engine_spec_stats": dict(eng.spec_stats),
        "zero_leak": _leak_check(eng),
    }


def _run_capacity_leg(model, params, kv_quant: str, budget: int) -> dict:
    """Admission pressure against a pool sized to ``budget`` BYTES under
    ``kv_quant``: every request submitted at t=0, the measured figure is
    the peak number of concurrently-live decode sequences."""
    from deepspeed_tpu.inference.kv_cache import kv_blocks_for_bytes
    from deepspeed_tpu.resilience import SimClock, use_clock
    from deepspeed_tpu.serving import ServingEngine

    rng = np.random.default_rng(SEED + 1)
    probe = _engine(model, params, n_kv_blocks=1, kv_quant=kv_quant,
                    enable_prefix_cache=False, max_seqs=N_CAP_REQS)
    n_blocks = kv_blocks_for_bytes(budget, model.config, probe.config)
    eng = _engine(model, params, n_kv_blocks=n_blocks, kv_quant=kv_quant,
                  enable_prefix_cache=False, max_seqs=N_CAP_REQS,
                  token_budget=256)
    clock = SimClock()
    with use_clock(clock):
        srv = ServingEngine(eng, {"policy": "slo", "max_queue": 64,
                                  "kv_quant": kv_quant,
                                  "reserve_output_blocks": True,
                                  "drain_timeout_s": 300.0},
                            start=False)
        clock.pump = srv.step
        reqs = [srv.submit(rng.integers(1, 128, (CAP_PROMPT,)).tolist(),
                           max_new_tokens=CAP_OUT)
                for _ in range(N_CAP_REQS)]
        peak = 0
        while not all(r.is_terminal for r in reqs):
            srv.step()
            peak = max(peak, len(eng.seqs))
            clock.advance(1.0)
            assert clock.now() < MAX_VTICKS, "capacity leg stranded"
        drained = srv.drain()
        srv.close()
    return {
        "kv_quant": kv_quant,
        "pool_pages": n_blocks,
        "pool_bytes_budget": budget,
        "peak_concurrent_seqs": peak,
        "finished": sum(r.state.value == "finished" for r in reqs),
        "drained": drained,
        "zero_leak": _leak_check(eng),
    }


def _run_handoff_leg(model, params) -> dict:
    """Quantized KV export books its wire reduction in the comm ledger:
    prefill one sequence on an int8 engine, export, and read the
    ``kv_handoff`` row (logical = fp bytes, wire = payload + scales)."""
    from deepspeed_tpu.comm.comm import get_comms_logger
    from deepspeed_tpu.inference.kv_cache import assert_block_balance

    ledger = get_comms_logger()
    ledger.reset()
    ledger.enabled = True       # the ledger is opt-in (telemetry-driven)
    rng = np.random.default_rng(SEED + 2)
    prompt = rng.integers(1, 128, (24,)).tolist()

    eng_q = _engine(model, params, kv_quant="int8",
                    enable_prefix_cache=False)
    t0 = int(np.argmax(eng_q.put([1], [prompt])[0]))
    export_q = eng_q.export_kv(1)
    # adopt on a second quantized engine: the payload is adopted
    # bit-identically, so the greedy continuations match exactly
    eng_b = _engine(model, params, kv_quant="int8",
                    enable_prefix_cache=False)
    eng_b.import_kv(2, export_q)
    cont_a = eng_q.decode_steps({1: t0}, 4)[1]
    cont_b = eng_b.decode_steps({2: t0}, 4)[2]
    eng_q.flush([1])
    eng_b.flush([2])
    assert_block_balance(eng_q)
    assert_block_balance(eng_b)

    totals = ledger.snapshot_totals().get("kv_handoff", {})
    ledger.enabled = False
    ledger.reset()
    logical = totals.get("bytes", 0)
    wire = totals.get("wire_bytes", 0)
    return {
        "export_pages": export_q.n_pages,
        "logical_bytes": int(logical),
        "wire_bytes": int(wire),
        "wire_reduction": round(logical / wire, 2) if wire else None,
        "adopted_continuation_bit_equal": cont_a == cont_b,
    }


def main() -> int:
    from deepspeed_tpu.inference.kv_cache import kv_page_bytes

    model, params = _model()

    leg_off = _run_spec_leg(model, params, speculative=False)
    leg_on = _run_spec_leg(model, params, speculative=True)
    print(f"[serve-spec-smoke] spec off: {leg_off['virtual_ticks']} vticks; "
          f"on: {leg_on['virtual_ticks']} vticks "
          f"(proposed {leg_on['spec_proposed']}, "
          f"accepted {leg_on['spec_accepted']})")

    fp_probe = _engine(model, params, n_kv_blocks=1,
                       enable_prefix_cache=False)
    budget = 16 * kv_page_bytes(model.config, fp_probe.config)
    cap_fp = _run_capacity_leg(model, params, "none", budget)
    cap_q8 = _run_capacity_leg(model, params, "int8", budget)
    ratio = (cap_q8["peak_concurrent_seqs"]
             / max(1, cap_fp["peak_concurrent_seqs"]))
    print(f"[serve-spec-smoke] capacity at {budget} B: fp "
          f"{cap_fp['peak_concurrent_seqs']} concurrent "
          f"({cap_fp['pool_pages']} pages) vs int8 "
          f"{cap_q8['peak_concurrent_seqs']} ({cap_q8['pool_pages']} "
          f"pages) -> {ratio:.2f}x")

    handoff = _run_handoff_leg(model, params)
    print(f"[serve-spec-smoke] kv_handoff wire: "
          f"{handoff['logical_bytes']} logical -> "
          f"{handoff['wire_bytes']} wire "
          f"({handoff['wire_reduction']}x)")

    gates = {
        # THE contract: greedy spec-on streams bit-equal spec-off
        "spec_token_identity": leg_on["streams"] == leg_off["streams"],
        "spec_drafts_accepted": leg_on["spec_accepted"] > 0,
        # same workload, strictly fewer engine ticks on virtual time —
        # AND every request individually at least as fast (accepted
        # drafts shorten exactly the requests that draft)
        "spec_fewer_ticks":
            leg_on["virtual_ticks"] < leg_off["virtual_ticks"],
        "spec_no_request_slower": all(
            a <= b for a, b in zip(leg_on["request_latency_ticks"],
                                   leg_off["request_latency_ticks"])),
        "spec_all_finished":
            leg_on["finished"] == N_SPEC_REQS
            and leg_off["finished"] == N_SPEC_REQS,
        # >= 1.8x concurrent decode sequences at the same pool bytes
        "kv_quant_concurrency_1p8x": ratio >= 1.8,
        # the disaggregated hand-off's wire is ~halved and ledger-booked
        "kv_handoff_wire_halved":
            (handoff["wire_reduction"] or 0) >= 1.8,
        "kv_handoff_adoption_bit_equal":
            handoff["adopted_continuation_bit_equal"],
        "zero_leak_all_legs": all([leg_off["zero_leak"],
                                   leg_on["zero_leak"],
                                   cap_fp["zero_leak"],
                                   cap_q8["zero_leak"]]),
        "all_legs_drained": all([leg_off["drained"], leg_on["drained"],
                                 cap_fp["drained"], cap_q8["drained"]]),
    }
    report = {
        "metric": "spec_tick_reduction_and_kv_quant_capacity",
        "seed": SEED,
        "clock": "virtual (SimClock; 1 engine tick = 1 virtual second)",
        "spec_off": leg_off,
        "spec_on": leg_on,
        "spec_tick_ratio": round(leg_off["virtual_ticks"]
                                 / leg_on["virtual_ticks"], 3),
        "capacity_fp": cap_fp,
        "capacity_int8": cap_q8,
        "kv_quant_concurrency_ratio": round(ratio, 2),
        "kv_handoff": handoff,
        "gates": gates,
        "value": round(ratio, 2),
    }
    # streams are the identity witness, not artifact payload — drop the
    # token dumps from the committed JSON to keep it readable
    for leg in (report["spec_off"], report["spec_on"]):
        leg.pop("streams")
    from _artifact import write_artifact

    import jax

    path = write_artifact("SERVE_SPEC", report,
                          device=jax.devices()[0].device_kind)
    print(f"[serve-spec-smoke] artifact: {path}")
    failed = [g for g, ok in gates.items() if not ok]
    if failed:
        print(f"serve-spec smoke: FAILED gates {failed}")
        return 1
    print(f"serve-spec smoke: OK — token-identical spec streams in "
          f"{leg_on['virtual_ticks']} vs {leg_off['virtual_ticks']} "
          f"ticks, int8 pool {ratio:.2f}x concurrent decodes at the "
          f"same byte budget, hand-off wire "
          f"{handoff['wire_reduction']}x reduced, zero leaked blocks")
    return 0


if __name__ == "__main__":
    sys.exit(main())
