"""Serving fleet: consistent-hash routing properties, KV export/import
round-trips, failover + disaggregated-handoff bit-exactness, autoscaler
sizing policy, and fleet-wide leak audits (docs/serving.md).

Engine-backed tests drive the fleet deterministically via
``ServingFleet(start=False)`` + ``fleet.step()`` — one monitor poll and
one tick per replica per call, no thread scheduling in the assertions.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.elasticity import (
    ElasticityError,
    ServingElasticityConfig,
    compute_serving_replicas,
    serving_replica_candidates,
)
from deepspeed_tpu.inference.kv_cache import (
    PoolExhausted,
    assert_block_balance,
)
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import Llama
from deepspeed_tpu.resilience import FaultInjector, install_fault_injector
from deepspeed_tpu.serving import (
    LeastLoadedRouter,
    PrefixAffinityRouter,
    ReplicaState,
    RequestState,
    ServingEngine,
    ServingFleet,
    make_router,
    prefix_key,
)

pytestmark = pytest.mark.fleet


@pytest.fixture(autouse=True)
def _no_injector():
    install_fault_injector(None)
    yield
    install_fault_injector(None)


@pytest.fixture(scope="module")
def model_and_params():
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=128, max_seq_len=256, use_flash=False,
                  remat=False)
    return model, model.init(jax.random.PRNGKey(5))


def _make_factory(model_and_params, **kw):
    model, params = model_and_params
    kw.setdefault("token_budget", 32)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("n_kv_blocks", 64)
    kw.setdefault("max_context", 128)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("enable_prefix_cache", True)

    def factory():
        return RaggedInferenceEngine(model, RaggedConfig(**kw), params=params)

    return factory


def _prompts(seed, n, length=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, length).tolist() for _ in range(n)]


def _reference_tokens(model_and_params, prompts, max_new):
    """Uninterrupted single-engine greedy run — the bit-exactness oracle
    for failover and disaggregated hand-off."""
    srv = ServingEngine(_make_factory(model_and_params)(),
                        {"policy": "slo"}, start=False)
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    for _ in range(500):
        if all(r.is_terminal for r in reqs):
            break
        srv._tick()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    return [list(r.tokens) for r in reqs]


def _run_fleet(fleet, reqs, limit=500):
    for _ in range(limit):
        if all(r.is_terminal for r in reqs):
            return
        fleet.step()
    raise AssertionError(f"fleet made no progress within {limit} steps: "
                         f"{[r.state.value for r in reqs]}")


# ----------------------------------------------------------------------
# consistent-hash routing (pure: no engines)
def _keys(n, seed=0, length=24):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 1000, length).tolist() for _ in range(n)]


def test_prefix_key_full_block_semantics():
    # 20 tokens at block 8 -> key is the 16-token full-block prefix
    p = list(range(100, 120))
    assert prefix_key(p, 8) == tuple(p[:16])
    # exactly 2 blocks: cap at len-1 keeps one token to prefill -> 1 block
    assert prefix_key(p[:16], 8) == tuple(p[:8])
    # shorter than a block: whole prompt (identical shorts co-locate)
    assert prefix_key([7, 8, 9], 8) == (7, 8, 9)


def test_ring_join_moves_bounded_fraction():
    r = PrefixAffinityRouter(block_size=8, vnodes=64)
    names = [f"rep{i}" for i in range(4)]
    for n in names:
        r.on_join(n)
    keys = _keys(400)
    before = {i: r.owner(k) for i, k in enumerate(keys)}
    r.on_join("rep4")
    after = {i: r.owner(k) for i, k in enumerate(keys)}
    moved = [i for i in before if before[i] != after[i]]
    # expectation 1/5 of keys move to the new node; bound it at 2x
    assert len(moved) / len(keys) <= 0.40
    # every moved key moved TO the new replica, never between old ones
    assert all(after[i] == "rep4" for i in moved)


def test_ring_leave_moves_only_its_keys():
    r = PrefixAffinityRouter(block_size=8, vnodes=64)
    for i in range(4):
        r.on_join(f"rep{i}")
    keys = _keys(400, seed=1)
    before = {i: r.owner(k) for i, k in enumerate(keys)}
    r.on_leave("rep2")
    after = {i: r.owner(k) for i, k in enumerate(keys)}
    for i, k in enumerate(keys):
        if before[i] != "rep2":
            assert after[i] == before[i]     # survivors keep their keys
        else:
            assert after[i] != "rep2"        # orphans land elsewhere


def test_ring_same_prefix_same_replica():
    r = PrefixAffinityRouter(block_size=8, vnodes=32)
    for i in range(3):
        r.on_join(f"rep{i}")
    shared = list(range(1, 17))              # two full blocks
    view = {f"rep{i}": 0 for i in range(3)}
    picks = {r.route(view, shared + [t]) for t in range(50, 60)}
    assert len(picks) == 1                   # same prefix -> same replica


def test_ring_skips_unhealthy_and_reports_miss():
    r = PrefixAffinityRouter(block_size=8, vnodes=32)
    for i in range(3):
        r.on_join(f"rep{i}")
    prompt = list(range(2, 30))
    primary = r.owner(prompt)
    others = {f"rep{i}": 0 for i in range(3) if f"rep{i}" != primary}
    chosen = r.route(others, prompt)         # primary not in the view
    assert chosen != primary
    assert r.last_was_primary is False
    full = {f"rep{i}": 0 for i in range(3)}
    assert r.route(full, prompt) == primary
    assert r.last_was_primary is True


def test_ring_spill_to_least_loaded_under_imbalance():
    r = PrefixAffinityRouter(block_size=8, vnodes=32, spill_load=4)
    for i in range(2):
        r.on_join(f"rep{i}")
    prompt = list(range(3, 30))
    primary = r.owner(prompt)
    other = next(n for n in ("rep0", "rep1") if n != primary)
    # primary at/over the spill threshold and an emptier peer exists
    assert r.route({primary: 4, other: 0}, prompt) == other
    assert r.last_was_primary is False
    # under the threshold affinity wins even when imbalanced
    assert r.route({primary: 3, other: 0}, prompt) == primary


def test_least_loaded_router_and_factory():
    r = make_router("least_loaded")
    assert isinstance(r, LeastLoadedRouter)
    assert r.route({"a": 3, "b": 1, "c": 2}, [1, 2]) == "b"
    assert r.route({"a": 1, "b": 1}, [1]) == "a"    # deterministic tie
    with pytest.raises(ValueError):
        make_router("nope")


# ----------------------------------------------------------------------
# autoscaler sizing policy (pure: elasticity/)
def test_serving_replica_candidates_and_validation():
    cfg = ServingElasticityConfig(min_replicas=2, max_replicas=5)
    assert serving_replica_candidates(cfg) == [2, 3, 4, 5]
    with pytest.raises(ElasticityError):
        ServingElasticityConfig(min_replicas=0)
    with pytest.raises(ElasticityError):
        ServingElasticityConfig(min_replicas=4, max_replicas=2)
    with pytest.raises(ElasticityError):
        ServingElasticityConfig(scale_up_queue_per_replica=1.0,
                                scale_down_queue_per_replica=2.0)


def test_autoscaler_scales_up_on_queue_depth():
    cfg = ServingElasticityConfig(max_replicas=8,
                                  scale_up_queue_per_replica=8.0)
    assert compute_serving_replicas(1, queue_depth=20, config=cfg) == 2
    # bounded step: a huge backlog still moves one replica per decision
    assert compute_serving_replicas(1, queue_depth=500, config=cfg) == 2
    assert compute_serving_replicas(2, queue_depth=500, config=cfg) == 3
    cfg_big = ServingElasticityConfig(max_replicas=8, max_step=4,
                                      scale_up_queue_per_replica=8.0)
    assert compute_serving_replicas(1, queue_depth=30, config=cfg_big) == 4


def test_autoscaler_pressure_overrides_shallow_queue():
    cfg = ServingElasticityConfig(max_replicas=4, kv_high=0.85, sla_low=0.9)
    assert compute_serving_replicas(2, queue_depth=0, kv_occupancy=0.95,
                                    config=cfg) == 3
    assert compute_serving_replicas(2, queue_depth=0, in_sla_ratio=0.5,
                                    config=cfg) == 3
    # pressure also vetoes shrinking
    assert compute_serving_replicas(2, queue_depth=0, kv_occupancy=0.95,
                                    in_sla_ratio=1.0, config=cfg) == 3


def test_fleet_config_validates_autoscale_band_at_parse():
    from deepspeed_tpu.config import Config, ConfigError

    with pytest.raises(ConfigError, match="scale_down_queue_per_replica"):
        Config.from_dict({"serving": {"fleet": {
            "scale_down_queue_per_replica": 10.0,
            "scale_up_queue_per_replica": 8.0}}})


def test_autoscaler_hysteresis_band_holds():
    cfg = ServingElasticityConfig(scale_up_queue_per_replica=8.0,
                                  scale_down_queue_per_replica=1.0,
                                  max_replicas=4)
    # 2 replicas, queue 6: 1 replica would absorb it (6 <= 8) but the
    # queue is above the down threshold at size 1 (6 > 1) -> hold
    assert compute_serving_replicas(2, queue_depth=6, config=cfg) == 2
    # genuinely idle -> shrink
    assert compute_serving_replicas(2, queue_depth=0, config=cfg) == 1
    # never below min / above max
    assert compute_serving_replicas(1, queue_depth=0, config=cfg) == 1
    assert compute_serving_replicas(4, queue_depth=10_000, config=cfg) == 4
    # hysteresis is judged at the STEPPED-TO size: a couple of queued
    # requests must not freeze an oversized fleet (4 -> 3 is fine even
    # though 2 > down_threshold * smallest-absorbing-count)
    assert compute_serving_replicas(4, queue_depth=2, config=cfg) == 3


# ----------------------------------------------------------------------
# KV export / import (engine-level hand-off seam)
def test_kv_export_import_roundtrip_bit_exact(model_and_params):
    a = _make_factory(model_and_params)()
    b = _make_factory(model_and_params)()
    prompt = _prompts(3, 1, length=13)[0]
    logits = a.put([7], [prompt])
    assert not np.isnan(logits[0]).any()
    t0 = int(np.argmax(logits[0]))

    export = a.export_kv(7)
    assert export.n_pages == len(a.seqs[7].blocks)
    assert export.seen == len(prompt)
    b.import_kv(7, export)
    assert_block_balance(a)
    assert_block_balance(b)
    # imported pages are privately held: one allocator ref each
    assert all(b.allocator.refcount(blk) == 1 for blk in b.seqs[7].blocks)

    # decoding the SAME next token on both engines yields identical bits:
    # the pages crossed engines losslessly
    la = np.asarray(a.put([7], [[t0]]))
    lb = np.asarray(b.put([7], [[t0]]))
    assert np.array_equal(la, lb)
    a.flush([7])
    b.flush([7])
    for eng in (a, b):
        eng.prefix_cache.drop_all(eng.allocator)
        assert_block_balance(eng, expect_free=eng.config.n_kv_blocks)


def test_kv_import_validates_geometry_and_state(model_and_params):
    a = _make_factory(model_and_params)()
    prompt = _prompts(4, 1, length=9)[0]
    a.put([1], [prompt])
    export = a.export_kv(1)
    # same engine still holds the uid
    with pytest.raises(ValueError, match="already live"):
        a.import_kv(1, export)
    # block-size mismatch refused before anything is allocated
    b = _make_factory(model_and_params, kv_block_size=16,
                      n_kv_blocks=32)()
    free0 = b.allocator.free_blocks
    with pytest.raises(ValueError, match="geometry"):
        b.import_kv(1, export)
    assert b.allocator.free_blocks == free0 and 1 not in b.seqs
    a.flush([1])


def test_kv_export_refuses_mid_prefill(model_and_params):
    # a prompt longer than the token budget stays pending after one put
    a = _make_factory(model_and_params, token_budget=8)()
    long_prompt = _prompts(5, 1, length=20)[0]
    logits = a.put([2], [long_prompt])
    assert np.isnan(logits[0]).any() and a.seqs[2].pending > 0
    with pytest.raises(ValueError, match="pending"):
        a.export_kv(2)
    a.flush([2])
    assert_block_balance(a)


def test_kv_import_pool_exhausted_leaves_engine_clean(model_and_params):
    a = _make_factory(model_and_params)()
    prompt = _prompts(6, 1, length=30)[0]           # 4 pages
    a.put([3], [prompt])
    export = a.export_kv(3)
    b = _make_factory(model_and_params, n_kv_blocks=16, max_context=128,
                      enable_prefix_cache=False)()
    # occupy B so fewer than n_pages blocks remain
    filler = _prompts(7, 1, length=110)[0]
    while np.isnan(b.put([9], [filler])[0]).any():
        filler = []
    assert b.allocator.free_blocks < export.n_pages
    free0 = b.allocator.free_blocks
    with pytest.raises(PoolExhausted):
        b.import_kv(3, export)
    assert b.allocator.free_blocks == free0 and 3 not in b.seqs
    assert_block_balance(b)
    a.flush([3])
    b.flush([9])


# ----------------------------------------------------------------------
# fleet behavior (deterministic manual stepping)
def test_fleet_routes_and_completes(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 2},
                         {"policy": "slo"}, start=False)
    prompts = _prompts(10, 4)
    ref = _reference_tokens(model_and_params, prompts, max_new=6)
    reqs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
    # least-loaded routing spreads a burst across both replicas
    assert {name for _, name in fleet._requests.values()} == \
        {"replica-0", "replica-1"}
    _run_fleet(fleet, reqs)
    assert [list(r.tokens) for r in reqs] == ref
    assert fleet.drain(timeout=5.0)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_failover_bit_exact(model_and_params):
    prompts = _prompts(11, 4)
    ref = _reference_tokens(model_and_params, prompts, max_new=8)
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 2},
                         {"policy": "slo"}, start=False)
    reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):
        fleet.step()
    assert any(len(r.tokens) > 0 for r in reqs)     # mid-decode
    victims = [r for r in reqs
               if fleet._requests.get(r.uid, (None, ""))[1] == "replica-0"]
    assert victims                                   # someone to fail over
    assert fleet.kill_replica("replica-0")
    _run_fleet(fleet, reqs)
    # greedy streams identical to the uninterrupted single-engine run
    assert [list(r.tokens) for r in reqs] == ref
    assert all(r.state is RequestState.FINISHED for r in reqs)
    # zero leaks everywhere, INCLUDING the dead (evacuated) replica
    assert fleet.drain(timeout=5.0)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_chaos_replica_death_via_injector(model_and_params):
    install_fault_injector(FaultInjector(replica_die_at_tick=2,
                                         replica_die_index=0))
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 2},
                         {"policy": "slo"}, start=False)
    prompts = _prompts(12, 3)
    ref = _reference_tokens(model_and_params, prompts, max_new=6)
    reqs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
    _run_fleet(fleet, reqs)
    assert [list(r.tokens) for r in reqs] == ref
    dead = [r for r in fleet.replicas if r.state == ReplicaState.DEAD]
    assert [r.name for r in dead] == ["replica-0"]
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_disaggregated_handoff_bit_exact(model_and_params):
    prompts = _prompts(13, 4)
    ref = _reference_tokens(model_and_params, prompts, max_new=8)
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"disaggregated": True, "prefill_replicas": 1,
                          "replicas": 1},
                         {"policy": "slo"}, start=False)
    from deepspeed_tpu.telemetry import get_telemetry

    handoffs = get_telemetry().registry.counter("serving/fleet/handoffs")
    h0 = handoffs.value
    reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
    _run_fleet(fleet, reqs)
    assert [list(r.tokens) for r in reqs] == ref
    # every request crossed the prefill -> decode seam exactly once
    assert handoffs.value - h0 == 4
    decode = next(r for r in fleet.replicas if r.role == "decode")
    assert decode.serving.live_requests == 0
    assert fleet.drain(timeout=5.0)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_disagg_affinity_routes_repeat_prefixes_to_one_prefill_replica(
        model_and_params):
    # affinity composes with disaggregation: the ring hashes the PREFILL
    # pool (where the prefix cache pays off), so repeats of one prefix
    # all land on the same prefill replica
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"disaggregated": True, "prefill_replicas": 2,
                          "replicas": 1, "router": "prefix_affinity"},
                         {"policy": "slo"}, start=False)
    shared = list(range(1, 17))                 # two full blocks at bs=8
    reqs = [fleet.submit(shared + [50 + i], max_new_tokens=2)
            for i in range(6)]
    placed = {fleet._requests[r.uid][1] for r in reqs}
    assert len(placed) == 1                     # one prefix, one replica
    assert fleet.replicas[int(placed.pop().rsplit("-", 1)[-1])].role \
        == "prefill"
    _run_fleet(fleet, reqs)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_handoff_import_failure_falls_back_to_reprefill(
        model_and_params):
    # a decode replica that cannot land the KV import (here: mismatched
    # page geometry; PoolExhausted takes the same path) falls back to the
    # re-prefill resume edge — the request degrades to recompute on the
    # decode replica, never gets lost, and stays bit-exact
    prompts = _prompts(14, 1, length=30)
    ref = _reference_tokens(model_and_params, prompts, max_new=4)

    calls = {"n": 0}
    prefill_f = _make_factory(model_and_params)
    decode_f = _make_factory(model_and_params, kv_block_size=16,
                             n_kv_blocks=32)

    def factory():
        calls["n"] += 1
        return prefill_f() if calls["n"] == 1 else decode_f()

    fleet = ServingFleet(factory, {"disaggregated": True,
                                   "prefill_replicas": 1, "replicas": 1},
                         {"policy": "slo"}, start=False)
    req = fleet.submit(prompts[0], max_new_tokens=4)
    _run_fleet(fleet, [req])
    assert req.state is RequestState.FINISHED
    assert list(req.tokens) == ref[0]
    from deepspeed_tpu.telemetry import get_telemetry

    reg = get_telemetry().registry
    assert reg.counter("serving/replica-1/adopt_fallbacks").value >= 1
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_client_request_id_survives_failover(model_and_params,
                                                   tmp_path):
    from deepspeed_tpu.telemetry import (Telemetry, set_telemetry,
                                         validate_request_record)

    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "fleet")

    t = Telemetry(config=Cfg())
    set_telemetry(t)
    try:
        fleet = ServingFleet(_make_factory(model_and_params),
                             {"replicas": 2}, {"policy": "slo"},
                             start=False)
        prompts = _prompts(15, 2)
        reqs = [fleet.submit(p, max_new_tokens=6,
                             client_request_id=f"logical-{i}")
                for i, p in enumerate(prompts)]
        for _ in range(3):
            fleet.step()
        fleet.kill_replica("replica-0")
        _run_fleet(fleet, reqs)
        fleet.close(timeout=5.0)
    finally:
        t.close()
        set_telemetry(None)
    recs = [json.loads(ln) for ln in
            open(os.path.join(str(tmp_path / "fleet"),
                              "requests.jsonl")).read().splitlines()]
    for rec in recs:
        assert validate_request_record(rec) == [], rec
    # one span per LOGICAL request, ids intact, regardless of which
    # replica (or how many) ended up serving it
    finished = [r for r in recs if r["state"] == "finished"]
    assert sorted(r["client_request_id"] for r in finished) == \
        ["logical-0", "logical-1"]


def test_fleet_scale_up_and_graceful_scale_down(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 1},
                         {"policy": "slo"}, start=False)
    assert len(fleet.healthy_replicas) == 1
    fleet.scale_to(3)
    assert len(fleet.healthy_replicas) == 3
    # idle replicas drain immediately and leave the healthy set
    fleet.scale_to(1)
    assert len(fleet.healthy_replicas) == 1
    states = {r.name: r.state for r in fleet.replicas}
    assert list(states.values()).count(ReplicaState.DEAD) == 2
    fleet.close(timeout=5.0)


def test_fleet_autoscale_once_uses_shared_policy(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"replicas": 1, "autoscale": True,
                          "max_replicas": 3,
                          "scale_up_queue_per_replica": 2.0},
                         {"policy": "slo"}, start=False)
    # a backlog deeper than one replica's allowance grows the fleet by
    # one step (policy: elasticity.compute_serving_replicas)
    for p in _prompts(16, 6, length=8):
        fleet.submit(p, max_new_tokens=4)
    target = fleet.autoscale_once()
    assert target == 2
    assert len(fleet.healthy_replicas) == 2
    reqs = [ent[0] for ent in list(fleet._requests.values())]
    _run_fleet(fleet, reqs)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_autoscale_interval_check_is_atomic_and_single_fire(
        model_and_params):
    """Regression (PR 15 dsrace fix): poll()'s autoscale interval
    check-then-stamp runs under the fleet lock — N concurrent polls
    within one interval produce exactly one decision, and the next
    interval fires exactly once again."""
    import threading as th

    from deepspeed_tpu.resilience.clock import SimClock

    clock = SimClock()
    clock.advance(100.0)
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"replicas": 1, "autoscale": True,
                          "autoscale_interval_s": 10.0},
                         {"policy": "slo"}, start=False, clock=clock)
    calls = []
    fleet.autoscale_once = lambda: (calls.append(1), 1)[1]
    threads = [th.Thread(target=fleet.poll) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 1          # one interval, one decision
    fleet.poll()
    assert len(calls) == 1          # still inside the interval
    clock.advance(10.0)
    fleet.poll()
    assert len(calls) == 2          # next interval: exactly once more
    fleet.close(timeout=5.0)


def test_kv_demand_ignores_reclaimable_cache(model_and_params):
    # a warm prefix cache is capacity, not pressure: kv_occupancy counts
    # it (allocator truth), kv_demand must not (autoscaler signal)
    eng = _make_factory(model_and_params)()
    prompt = _prompts(23, 1, length=17)[0]
    logits = eng.put([4], [prompt])
    t0 = int(np.argmax(logits[0]))
    eng.put([4], [[t0]])
    eng.flush([4])                      # publishes full blocks into cache
    assert eng.cache.occupancy() > 0.0     # cache holds pages
    assert eng.cache.demand() == 0.0       # ...all reclaimable: zero demand
    eng.prefix_cache.drop_all(eng.allocator)
    assert_block_balance(eng, expect_free=eng.config.n_kv_blocks)


def test_fleet_respawns_dead_prefill_pool(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"disaggregated": True, "prefill_replicas": 1,
                          "replicas": 1, "min_replicas": 1,
                          "respawn": True},
                         {"policy": "slo"}, start=False)
    fleet._respawn_delay = 0.0
    fleet.kill_replica("replica-0")     # the prefill replica
    assert not any(r.role == "prefill" and r.state == ReplicaState.HEALTHY
                   for r in fleet.replicas)
    fleet.poll()
    spawned = [r for r in fleet.replicas
               if r.role == "prefill" and r.state == ReplicaState.HEALTHY]
    assert len(spawned) == 1            # prefill pool restored, not decode
    fleet.close(timeout=5.0)


def test_fleet_respawn_restores_min_replicas(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"replicas": 2, "min_replicas": 2,
                          "respawn": True},
                         {"policy": "slo"}, start=False)
    fleet._respawn_delay = 0.0                      # no backoff in tests
    fleet.kill_replica("replica-0")
    assert len(fleet.healthy_replicas) == 1
    fleet.poll()
    assert len(fleet.healthy_replicas) == 2
    assert {r.name for r in fleet.healthy_replicas} == \
        {"replica-1", "replica-2"}
    fleet.close(timeout=5.0)


def test_fleet_block_leaks_names_the_replica(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 2},
                         {"policy": "slo"}, start=False)
    eng = fleet.replicas[1].engine
    # simulate a leak: a page vanishes from both the free list and the
    # refcount map
    page = eng.allocator._free.pop()
    problems = fleet.block_leaks()
    assert problems and all(p.startswith("replica-1:") for p in problems)
    eng.allocator._free.append(page)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_rejects_when_no_healthy_replica(model_and_params):
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 1,
                                                           "failover": True,
                                                           "respawn": False},
                         {"policy": "slo"}, start=False)
    fleet.kill_replica("replica-0")
    req = fleet.submit(_prompts(17, 1)[0], max_new_tokens=4)
    assert req.state is RequestState.REJECTED
    assert "no healthy replica" in req.error
    fleet.close(timeout=5.0)


def test_fleet_drain_serves_out_inflight_handoffs(model_and_params):
    # graceful shutdown with a request still mid-prefill on the prefill
    # replica: admission closes everywhere, but the hand-off is the
    # CONTINUATION of admitted work — it must land on the (draining)
    # decode replica and finish, not get shed
    prompts = _prompts(19, 1)
    ref = _reference_tokens(model_and_params, prompts, max_new=6)
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"disaggregated": True, "prefill_replicas": 1,
                          "replicas": 1},
                         {"policy": "slo"}, start=False)
    req = fleet.submit(prompts[0], max_new_tokens=6)
    assert not fleet.drain(timeout=0.01)    # closes admission fleet-wide
    _run_fleet(fleet, [req])
    assert req.state is RequestState.FINISHED
    assert list(req.tokens) == ref[0]
    assert fleet.drain(timeout=5.0)
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_fleet_level_reject_emits_span_and_sla_miss(model_and_params,
                                                    tmp_path):
    from deepspeed_tpu.telemetry import (Telemetry, set_telemetry,
                                         validate_request_record)

    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "shed")

    t = Telemetry(config=Cfg())
    set_telemetry(t)
    try:
        fleet = ServingFleet(_make_factory(model_and_params),
                             {"replicas": 1, "respawn": False},
                             {"policy": "slo"}, start=False)
        fleet.kill_replica("replica-0")
        req = fleet.submit(_prompts(20, 1)[0], max_new_tokens=4,
                           deadline_s=1.0, client_request_id="shed-1")
        assert req.state is RequestState.REJECTED
        # the shed feeds the autoscaler's quality signal as a miss
        assert fleet.in_sla_ratio() == 0.0
        fleet.close(timeout=5.0)
    finally:
        t.close()
        set_telemetry(None)
    recs = [json.loads(ln) for ln in
            open(os.path.join(str(tmp_path / "shed"),
                              "requests.jsonl")).read().splitlines()]
    rec = next(r for r in recs if r["client_request_id"] == "shed-1")
    assert validate_request_record(rec) == [], rec
    assert rec["state"] == "rejected" and rec["in_slo"] is False
    assert "no healthy replica" in rec["error"]


def test_failover_of_cancel_pending_orphan_emits_span(model_and_params,
                                                      tmp_path):
    # a live request with a cancel pending when its replica dies must
    # still get the full terminal contract (span in requests.jsonl),
    # not vanish silently in the evacuation
    from deepspeed_tpu.telemetry import (Telemetry, set_telemetry,
                                         validate_request_record)

    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "orphan")

    t = Telemetry(config=Cfg())
    set_telemetry(t)
    try:
        fleet = ServingFleet(_make_factory(model_and_params),
                             {"replicas": 2}, {"policy": "slo"},
                             start=False)
        req = fleet.submit(_prompts(21, 1)[0], max_new_tokens=8,
                           client_request_id="orphan-1")
        for _ in range(2):
            fleet.step()                 # live and decoding on replica-0
        assert fleet.cancel(req)         # flag set; retire would be next tick
        fleet.kill_replica("replica-0")  # ...but the replica dies first
        assert req.state is RequestState.CANCELLED
        fleet.close(timeout=5.0)
    finally:
        t.close()
        set_telemetry(None)
    recs = [json.loads(ln) for ln in
            open(os.path.join(str(tmp_path / "orphan"),
                              "requests.jsonl")).read().splitlines()]
    rec = next(r for r in recs if r["client_request_id"] == "orphan-1")
    assert validate_request_record(rec) == [], rec
    assert rec["state"] == "cancelled"


def test_disagg_failover_decodes_on_prefill_as_last_resort(
        model_and_params):
    # the only decode replica dies: the request re-queues through the
    # prefill replica, whose handoff finds no decode target and decodes
    # locally (flag cleared — no prefill->prefill ping-pong), bit-exact
    prompts = _prompts(22, 2)
    ref = _reference_tokens(model_and_params, prompts, max_new=8)
    fleet = ServingFleet(_make_factory(model_and_params),
                         {"disaggregated": True, "prefill_replicas": 1,
                          "replicas": 1, "respawn": False},
                         {"policy": "slo"}, start=False)
    reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):
        fleet.step()                     # handed off, decoding on replica-1
    assert fleet.kill_replica("replica-1")
    _run_fleet(fleet, reqs)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert [list(r.tokens) for r in reqs] == ref
    assert fleet.block_leaks() == []
    fleet.close(timeout=5.0)


def test_requeue_bypasses_queue_bound_and_stopped_refuses(model_and_params):
    # backpressure sheds NEW work only: a failed-over continuation queues
    # past max_queue; a stopped (killed/closed) replica refuses it
    # without going terminal so the fleet can place it elsewhere
    from deepspeed_tpu.serving import Request

    srv = ServingEngine(_make_factory(model_and_params)(),
                        {"policy": "slo", "max_queue": 1}, start=False)
    srv.submit([1, 2, 3], max_new_tokens=2)            # fills the queue
    fresh = srv.submit([4, 5, 6], max_new_tokens=2)    # new work: shed
    assert fresh.state is RequestState.REJECTED
    cont = Request(prompt=[4, 5, 6], max_new_tokens=4)
    cont.tokens = [7]                                  # admitted elsewhere
    srv.submit_request(cont, requeue=True)
    assert cont.state is RequestState.QUEUED and cont in srv._queue
    srv.kill()
    assert srv.adopt(Request(prompt=[8]), object()) is False
    # a stopped replica refuses a requeue NON-terminally: the fleet
    # re-places the continuation on another replica
    late = Request(prompt=[9, 10], max_new_tokens=2)
    assert srv.submit_request(late, requeue=True) is None
    assert late.state is RequestState.QUEUED and late not in srv._queue


def test_cancel_while_parked_in_adoption_pen(model_and_params):
    # a hand-off arrival cancelled before its import must retire cleanly
    # at the next tick — not crash cancel() (it is QUEUED but not in the
    # admission queue) and not import anything
    from deepspeed_tpu.serving import Request

    srv = ServingEngine(_make_factory(model_and_params)(),
                        {"policy": "slo"}, start=False)
    req = Request(prompt=[1, 2, 3], max_new_tokens=4)
    req.tokens = [5]
    srv.adopt(req, object())          # export never touched before cancel
    assert srv.cancel(req) is True
    srv._tick()
    assert req.state is RequestState.CANCELLED
    assert srv._adoptions == [] and srv._live == {}
    assert not srv._engine.seqs


def test_fleet_background_threads_end_to_end(model_and_params):
    # the one threaded test: real drivers + monitor, streaming surface
    fleet = ServingFleet(_make_factory(model_and_params), {"replicas": 2},
                         {"policy": "slo"}, start=True)
    try:
        toks = list(fleet.stream(_prompts(18, 1)[0], max_new_tokens=5))
        assert len(toks) == 5
        assert fleet.drain(timeout=30.0)
        assert fleet.block_leaks() == []
    finally:
        fleet.close(timeout=10.0)
