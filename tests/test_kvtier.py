"""Global KV tier: directory, cold tier, residency routing, adoption.

Covers deepspeed_tpu/serving/kvtier.py plus its seams (config parsing,
the residency-aware router, the fleet wiring, eviction racing in-flight
export/import on the real ragged engine) and the DST invariant teeth
(#17 directory-residency containment, #18 cold-tier accounting, #19
verify-before-import). docs/serving.md "Global KV tier" / docs/dst.md.
"""

import numpy as np
import pytest

from deepspeed_tpu.config import ConfigError, KVTierConfig, ServingConfig
from deepspeed_tpu.resilience.dst import (SimConfig, SimEngine,
                                          generate_schedule, run_schedule)
from deepspeed_tpu.serving.kvtier import (ColdTier, CorruptExport, KVTier,
                                          PrefixDirectory, PrefixExport,
                                          export_checksum, prefix_hash)
from deepspeed_tpu.serving.router import (PrefixAffinityRouter,
                                          ResidencyAwareRouter, make_router)


def _export(tokens, n_pages=None, *, block_size=4, kv_quant="sim",
            source="a"):
    toks = tuple(int(t) for t in tokens)
    pages = (len(toks) // block_size) if n_pages is None else n_pages
    return PrefixExport(tokens=toks, n_pages=pages, block_size=block_size,
                        n_layers=1, n_kv_heads=1, head_dim=1, dtype="sim",
                        kv_quant=kv_quant, source=source)


# ----------------------------------------------------------------------
# checksums and exports
# ----------------------------------------------------------------------

def test_prefix_hash_is_stable_and_distinct():
    assert prefix_hash([1, 2, 3]) == prefix_hash((1, 2, 3))
    assert prefix_hash([1, 2, 3]) != prefix_hash([1, 2, 4])
    assert prefix_hash([1, 2, 3]) != prefix_hash([1, 2])


def test_export_checksum_flags_token_flip():
    e = _export(range(1, 9))
    assert e.verify()
    e.tokens = (e.tokens[0] ^ 0x1,) + e.tokens[1:]
    assert not e.verify()


def test_export_checksum_covers_payload_bytes():
    toks = (1, 2, 3, 4)
    assert export_checksum(toks, [b"abcd"]) != export_checksum(toks,
                                                              [b"abce"])
    assert export_checksum(toks, [b"abcd"]) == export_checksum(toks,
                                                               [b"abcd"])


def test_export_with_pages_detects_payload_corruption():
    pages = [np.arange(16, dtype=np.int8)]
    e = PrefixExport(tokens=(1, 2, 3, 4), n_pages=1, block_size=4,
                     n_layers=1, n_kv_heads=1, head_dim=1, dtype="int8",
                     kv_quant="int8", pages=pages)
    assert e.verify()
    pages[0][3] ^= 0x1
    assert not e.verify()


def test_corrupt_export_is_a_value_error():
    # importers catch ValueError for the generic fallback path and
    # CorruptExport specifically for the corruption counter — the
    # subclass relation keeps both handlers honest
    assert issubclass(CorruptExport, ValueError)


# ----------------------------------------------------------------------
# PrefixDirectory: bounded-staleness residency map
# ----------------------------------------------------------------------

def test_directory_holders_respect_staleness_bound():
    d = PrefixDirectory(staleness_s=5.0)
    d.publish("a", [11, 22], now=0.0)
    d.publish("b", [22], now=3.0)

    assert d.holders(22, now=4.0) == (["a", "b"], False)
    # a's publish is now 6s old: past the bound, b still fresh
    assert d.holders(22, now=6.0) == (["b"], False)
    # both stale: entries exist but none trustworthy -> stale_only
    assert d.holders(22, now=9.0) == ([], True)
    # unknown hash is a plain miss, NOT stale_only
    assert d.holders(33, now=0.0) == ([], False)
    assert d.has_fresh(11, now=4.0)
    assert not d.has_fresh(11, now=9.0)


def test_directory_publish_is_full_replacement():
    d = PrefixDirectory(staleness_s=5.0)
    d.publish("a", [1, 2], now=0.0)
    d.publish("a", [2, 3], now=1.0)
    assert d.entries_for("a") == {2, 3}
    assert d.holders(1, now=1.0) == ([], False)
    # empty publish wipes the member entirely
    d.publish("a", [], now=2.0)
    assert d.members() == []
    assert d.size() == 0


def test_directory_invalidate_and_drop_member():
    d = PrefixDirectory(staleness_s=5.0)
    d.publish("a", [1, 2], now=0.0)
    d.publish("b", [2], now=0.0)
    d.invalidate("a", 2)
    assert d.entries_for("a") == {1}
    assert d.holders(2, now=0.0) == (["b"], False)
    d.invalidate("a", 999)                    # unknown hash: no-op
    assert d.drop_member("b") == 1
    assert d.drop_member("b") == 0            # idempotent
    assert d.members() == ["a"]
    snap = d.snapshot()
    assert snap["entries"] == 1
    assert snap["members"] == {"a": 1}
    assert snap["publishes"] == 2
    assert snap["invalidations"] == 2


# ----------------------------------------------------------------------
# ColdTier: host-memory LRU with page-capacity accounting
# ----------------------------------------------------------------------

def test_cold_tier_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        ColdTier(0)


def test_cold_tier_lru_eviction_and_accounting():
    cold = ColdTier(capacity_pages=4)
    a = _export(range(0, 8))      # 2 pages
    b = _export(range(8, 16))     # 2 pages
    c = _export(range(16, 24))    # 2 pages
    assert cold.put(a) and cold.put(b)
    assert cold.used_pages == 4 == sum(cold.entry_pages())
    assert cold.get(a.key) is a           # refresh a: b is now LRU
    assert cold.put(c)
    assert cold.keys() == [a.key, c.key]  # b evicted, not a
    assert cold.used_pages == 4 <= cold.capacity_pages
    st = cold.stats()
    assert st["evictions"] == 1 and st["hits"] == 1
    assert cold.get(b.key) is None
    assert cold.stats()["misses"] == 1


def test_cold_tier_refuses_oversized_entries():
    cold = ColdTier(capacity_pages=2)
    assert not cold.put(_export(range(16)))   # 4 pages > whole tier
    assert cold.used_pages == 0
    assert cold.stats()["rejects"] == 1


def test_cold_tier_entries_snapshot_does_not_touch_recency():
    cold = ColdTier(capacity_pages=8)
    a, b = _export(range(0, 8)), _export(range(8, 16))
    cold.put(a)
    cold.put(b)
    before = cold.keys()
    snap = cold.entries_snapshot()
    assert [e.key for e in snap] == before == cold.keys()
    assert cold.stats()["hits"] == 0          # snapshot is not a get()
    cold.get(a.key)                           # get() DOES reorder
    assert cold.keys() == [b.key, a.key]


def test_cold_tier_invalidate_and_drop_all():
    cold = ColdTier(capacity_pages=8)
    a = _export(range(0, 8))
    cold.put(a)
    assert cold.contains(a.key)
    assert cold.invalidate(a.key)
    assert not cold.invalidate(a.key)
    assert cold.used_pages == 0
    cold.put(a)
    cold.drop_all()
    assert len(cold) == 0 and cold.used_pages == 0


# ----------------------------------------------------------------------
# config: serving.kv_tier validated at parse time (default OFF)
# ----------------------------------------------------------------------

def test_kv_tier_config_defaults_off():
    cfg = ServingConfig.from_dict({})
    assert cfg.kv_tier.enabled is False
    tier = KVTierConfig()
    assert tier.enabled is False
    assert tier.adoption and tier.cold_tier


def test_kv_tier_config_parses_through_serving_block():
    cfg = ServingConfig.from_dict({"kv_tier": {
        "enabled": True, "publish_interval_s": 0.5,
        "directory_staleness_s": 2.0, "adoption": False,
        "cold_tier": True, "cold_capacity_pages": 32}})
    t = cfg.kv_tier
    assert t.enabled and not t.adoption
    assert t.publish_interval_s == 0.5
    assert t.directory_staleness_s == 2.0
    assert t.cold_capacity_pages == 32


def test_kv_tier_config_rejects_bad_values_at_parse_time():
    with pytest.raises(ConfigError, match="publish_interval_s must be > 0"):
        KVTierConfig.from_dict({"publish_interval_s": 0})
    with pytest.raises(ConfigError,
                       match="directory_staleness_s must be >= "):
        KVTierConfig.from_dict({"publish_interval_s": 2.0,
                                "directory_staleness_s": 1.0})
    with pytest.raises(ConfigError,
                       match="cold_capacity_pages must be >= 1"):
        KVTierConfig.from_dict({"cold_tier": True,
                                "cold_capacity_pages": 0})
    # cold tier off: capacity is irrelevant, parse succeeds
    t = KVTierConfig.from_dict({"cold_tier": False,
                                "cold_capacity_pages": 0})
    assert not t.cold_tier


# ----------------------------------------------------------------------
# ResidencyAwareRouter: the fallback matrix
# ----------------------------------------------------------------------

def _residency_router(spill_load=0):
    r = make_router("residency", block_size=4, spill_load=spill_load)
    assert isinstance(r, ResidencyAwareRouter)
    for name in ("a", "b", "c"):
        r.on_join(name)
    return r


def test_residency_router_without_directory_is_plain_affinity():
    r = _residency_router()
    base = PrefixAffinityRouter(block_size=4)
    for name in ("a", "b", "c"):
        base.on_join(name)
    replicas = {"a": 0.0, "b": 0.0, "c": 0.0}
    prompt = list(range(1, 9))
    assert r.route(replicas, prompt) == base.route(replicas, prompt)
    assert r.route_info()["outcome"] == "affinity"


def test_residency_router_prefers_fresh_holder_over_ring():
    r = _residency_router()
    d = PrefixDirectory(staleness_s=5.0)
    now = [0.0]
    r.set_directory(d, lambda: now[0])
    replicas = {"a": 0.0, "b": 0.0, "c": 0.0}
    prompt = list(range(1, 9))
    ring_pick = r.owner(prompt)
    holder = next(n for n in sorted(replicas) if n != ring_pick)
    d.publish(holder, [r._hash_for(prompt)], now=0.0)

    assert r.route(replicas, prompt) == holder
    assert r.route_info()["outcome"] == "residency"

    # stale entry: back to the ring, metered as directory_stale
    now[0] = 10.0
    assert r.route(replicas, prompt) == ring_pick
    assert r.route_info()["outcome"] == "directory_stale"

    # entry gone entirely: plain affinity outcome
    d.drop_member(holder)
    assert r.route(replicas, prompt) == ring_pick
    assert r.route_info()["outcome"] == "affinity"


def test_residency_router_picks_least_loaded_holder():
    r = _residency_router()
    d = PrefixDirectory(staleness_s=5.0)
    r.set_directory(d, lambda: 0.0)
    prompt = list(range(1, 9))
    h = r._hash_for(prompt)
    d.publish("a", [h], now=0.0)
    d.publish("b", [h], now=0.0)
    assert r.route({"a": 3.0, "b": 1.0, "c": 0.0}, prompt) == "b"
    assert r.route_info()["outcome"] == "residency"


def test_residency_router_spill_valve_overrides_residency():
    r = _residency_router(spill_load=2)
    d = PrefixDirectory(staleness_s=5.0)
    r.set_directory(d, lambda: 0.0)
    prompt = list(range(1, 9))
    d.publish("a", [r._hash_for(prompt)], now=0.0)
    # the only holder is saturated while others idle: residency yields
    chosen = r.route({"a": 5.0, "b": 0.0, "c": 0.0}, prompt)
    assert chosen != "a"
    assert r.route_info()["outcome"] == "affinity"


def test_make_router_rejects_unknown_name():
    with pytest.raises(ValueError):
        make_router("galactic")


# ----------------------------------------------------------------------
# KVTier facade + fleet wiring
# ----------------------------------------------------------------------

def test_kv_tier_facade_builds_from_config():
    tier = KVTier(KVTierConfig.from_dict({
        "enabled": True, "cold_capacity_pages": 8}))
    assert tier.cold is not None
    tier.directory.publish("a", [1, 2], now=0.0)
    assert tier.drop_member("a") == 2
    no_cold = KVTier(KVTierConfig.from_dict({"enabled": True,
                                             "cold_tier": False}))
    assert no_cold.cold is None


def test_fleet_upgrades_router_and_gates_tier_on_config():
    from deepspeed_tpu.serving.fleet import ServingFleet

    def factory():
        return SimEngine(SimConfig())

    fleet = ServingFleet(factory, config={"replicas": 2,
                                          "router": "prefix_affinity"},
                         serving_config={"kv_tier": {"enabled": True}},
                         start=False)
    try:
        assert isinstance(fleet.router, ResidencyAwareRouter)
        assert fleet.kv_tier is not None
        assert fleet.kv_tier.directory is fleet.router.directory
    finally:
        fleet.close()

    off = ServingFleet(factory, config={"replicas": 2,
                                        "router": "prefix_affinity"},
                       serving_config={}, start=False)
    try:
        # default OFF: no tier, no router upgrade — old configs replay
        # bit-identically
        assert off.kv_tier is None
        assert not isinstance(off.router, ResidencyAwareRouter)
    finally:
        off.close()


# ----------------------------------------------------------------------
# real engine: eviction racing in-flight export/import (satellite 4)
# ----------------------------------------------------------------------

def test_eviction_races_inflight_export_and_adoption_real_engine():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from deepspeed_tpu.inference.kv_cache import block_balance_report
    from deepspeed_tpu.inference.ragged import (
        RaggedConfig,
        RaggedInferenceEngine,
    )
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.serving import ServingFleet
    from deepspeed_tpu.serving.router import prefix_key

    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=128, max_seq_len=256, use_flash=False,
                  remat=False)
    params = model.init(jax.random.PRNGKey(5))

    def factory():
        return RaggedInferenceEngine(
            model, RaggedConfig(token_budget=32, max_seqs=4,
                                kv_block_size=8, n_kv_blocks=64,
                                max_context=128, dtype=jnp.float32,
                                enable_prefix_cache=True, kv_quant="int8"),
            params=params)

    fleet = ServingFleet(
        factory,
        config={"replicas": 2, "router": "prefix_affinity",
                "health_interval_s": 0.01},
        serving_config={"policy": "slo",
                        "kv_tier": {"enabled": True,
                                    "publish_interval_s": 0.001,
                                    "directory_staleness_s": 60.0,
                                    "cold_capacity_pages": 32}},
        start=False)
    try:
        rng = np.random.default_rng(7)
        shared = rng.integers(1, 128, 24).tolist()
        req = fleet.submit(shared + rng.integers(1, 128, 4).tolist(),
                           max_new_tokens=4)
        for _ in range(200):
            fleet.step()
            if req.is_terminal:
                break
        assert req.state.name == "FINISHED"
        for _ in range(5):
            fleet.step()
        assert fleet.kv_tier.directory.size() > 0

        key = prefix_key(shared + [1, 2, 3, 4], 8)
        h = prefix_hash(key)
        fresh, _stale = fleet.kv_tier.directory.holders(
            h, fleet._clock.now())
        assert fresh
        donor = next(r for r in fleet.replicas if r.name == fresh[0])
        target = next(r for r in fleet.replicas if r.name != fresh[0])

        # race 1: eviction lands AFTER the export request is penned but
        # BEFORE the driver services it — the prefetch must degrade to
        # on_ready(None), never dangle freed pages
        got = []
        assert donor.serving.request_prefix_export(list(key), got.append)
        donor.engine.prefix_cache.drop_all(donor.engine.allocator)
        assert fleet.kv_tier.directory.entries_for(donor.name) == set()
        for _ in range(3):
            fleet.step()
        assert got == [None]
        assert block_balance_report(donor.engine)["problems"] == []

        # re-prefill the prefix on the donor, then a clean export/adopt
        req2 = donor.serving.submit(
            shared + rng.integers(1, 128, 4).tolist(), max_new_tokens=4)
        for _ in range(200):
            fleet.step()
            if req2.is_terminal:
                break
        assert req2.state.name == "FINISHED"
        got2 = []
        assert donor.serving.request_prefix_export(list(key), got2.append)
        for _ in range(3):
            fleet.step()
        assert got2 and got2[0] is not None
        export = got2[0]
        assert export.verify()
        assert export.n_pages == 3
        assert 0 < export.wire_bytes < export.logical_bytes

        # race 2: adoption import races target-side eviction pressure —
        # the import path either lands (evict_for made room) or falls
        # back, and block balance holds either way
        assert target.serving.adopt_prefix(export)
        for _ in range(3):
            fleet.step()
        assert target.engine.kvtier_adopt_imports == 1
        assert target.engine.kvtier_corrupt_landed == 0

        # adopted pages are bit-identical to the donor's
        d_blocks = donor.engine.prefix_cache._entries[tuple(export.tokens)]
        t_blocks = target.engine.prefix_cache._entries[
            tuple(export.tokens)]
        d2 = donor.engine._gather_prefix_export(tuple(export.tokens),
                                                d_blocks)
        t2 = target.engine._gather_prefix_export(tuple(export.tokens),
                                                 t_blocks)
        for a, b in zip(d2._payload_buffers(), t2._payload_buffers()):
            assert a == b

        # corrupt wire: verify-before-import refuses, nothing leaks
        bad = donor.engine.export_prefix(list(key))
        bad.tokens = (bad.tokens[0] ^ 0x1,) + tuple(bad.tokens[1:])
        with pytest.raises(CorruptExport):
            target.engine.import_prefix(bad)
        assert target.engine.kvtier_corrupt_landed == 0

        for r in fleet.replicas:
            r.engine.prefix_cache.drop_all(r.engine.allocator)
            assert block_balance_report(r.engine)["problems"] == []
    finally:
        fleet.close()


# ----------------------------------------------------------------------
# DST: the kv-tier invariants have teeth
# ----------------------------------------------------------------------

def _tiered_schedule(seed):
    sched = generate_schedule(seed)
    assert sched.serving_cfg.get("kv_tier", {}).get("enabled"), \
        f"seed {seed} is not a tiered seed; re-pin the teeth seeds"
    return sched


class _NoInvalidateEngine(SimEngine):
    """Planted bug: eviction spills to the cold tier but SKIPS the
    directory invalidation — the entry outlives its pages (#17)."""

    def _on_prefix_evict(self, key, blocks):
        if self._cold_tier is not None:
            if self._cold_tier.put(self._make_prefix_export(key, blocks)):
                self.kvtier_cold_spills += 1


def test_auditor_catches_directory_entry_outliving_pages():
    sched = _tiered_schedule(20)              # seed 20: eviction-heavy
    report = run_schedule(
        sched,
        engine_factory=lambda: _NoInvalidateEngine(
            SimConfig(**sched.engine_cfg)))
    assert not report.ok
    assert any("[kv-directory]" in v for v in report.violations), \
        report.violations


class _ColdCorruptingEngine(SimEngine):
    """Planted bug: flips a token AFTER the checksum is stamped, so
    every spilled entry fails verification inside the cold tier (#18)."""

    def _make_prefix_export(self, key, blocks):
        export = super()._make_prefix_export(key, blocks)
        export.tokens = (export.tokens[0] ^ 0x1,) + tuple(export.tokens[1:])
        return export


def test_auditor_catches_cold_tier_corruption():
    sched = _tiered_schedule(20)              # seed 20: spill-heavy
    report = run_schedule(
        sched,
        engine_factory=lambda: _ColdCorruptingEngine(
            SimConfig(**sched.engine_cfg)))
    assert not report.ok
    assert any("[kv-cold]" in v for v in report.violations), \
        report.violations


class _BlindImporterEngine(SimEngine):
    """Planted bug: corrupts every outgoing export AND skips the
    importer's checksum — a corrupt export lands (#19)."""

    _kvtier_skip_verify = True

    def export_prefix(self, tokens):
        export = super().export_prefix(tokens)
        if export is not None:
            export.tokens = ((export.tokens[0] ^ 0x1,)
                             + tuple(export.tokens[1:]))
        return export


def test_auditor_catches_corrupt_import_landing():
    sched = _tiered_schedule(49)              # seed 49: adoption fires
    report = run_schedule(
        sched,
        engine_factory=lambda: _BlindImporterEngine(
            SimConfig(**sched.engine_cfg)))
    assert not report.ok
    assert any("[kv-adopt]" in v for v in report.violations), \
        report.violations


def test_tiered_seeds_audit_clean_and_replay_bit_identical():
    for seed in (20, 49):
        sched = _tiered_schedule(seed)
        r1 = run_schedule(sched)
        assert r1.ok, (seed, r1.violations)
        r2 = run_schedule(generate_schedule(seed))
        assert r1.trace_hash == r2.trace_hash, seed
