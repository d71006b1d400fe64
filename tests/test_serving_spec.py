"""Speculative decoding inside the serving tick + quantized KV cache
(docs/serving.md "Speculative scheduling" / "KV quantization").

The contracts under test:

* greedy serving with speculation ON is TOKEN-IDENTICAL to serving with
  it off (row 0 of every verify chain is exactly the plain tick's
  logits), while completing the same workload in fewer engine ticks;
* drafting consumes only token-budget SLACK (`CapacityView.draft_budget`
  charges prefill's claim off the top) and is sized by the per-class
  acceptance-credit EMA (`chain_len_for`);
* a request whose rolling acceptance EMA falls below the configured
  floor latches to plain decode (stream unchanged);
* `NgramIndex` (the memoized draft index) proposes exactly what the
  O(context) `_prompt_lookup` rescan would, through appends and trims;
* quantized pools (`kv_quant=int8/int4`) hold ~2x/~4x the pages at a
  fixed byte budget, round-trip within the documented `scale/2` bound,
  export/import bit-identically (payload adopted, never re-quantized),
  and recover from PoolExhausted with zero leaked blocks.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.config import ServingConfig
from deepspeed_tpu.inference.drafter import NgramIndex, _prompt_lookup
from deepspeed_tpu.inference.kv_cache import (
    assert_block_balance,
    kv_blocks_for_bytes,
    kv_page_bytes,
)
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import Llama
from deepspeed_tpu.ops.quantizer import dequantize_kv, quantize_kv
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.serving.scheduler import CapacityView


@pytest.fixture(scope="module")
def model_and_params():
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=128, max_seq_len=256, use_flash=False,
                  remat=False)
    return model, model.init(jax.random.PRNGKey(5))


def _cfg(**kw):
    kw.setdefault("token_budget", 64)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("n_kv_blocks", 64)
    kw.setdefault("max_context", 256)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("enable_prefix_cache", True)
    return RaggedConfig(**kw)


def _engine(model_and_params, **kw):
    model, params = model_and_params
    return RaggedInferenceEngine(model, _cfg(**kw), params=params)


# ----------------------------------------------------------------------
# NgramIndex: the memoized form of _prompt_lookup
# ----------------------------------------------------------------------

def test_ngram_index_matches_prompt_lookup():
    """Randomized equivalence: for any stream + virtual suffix, the
    incremental index proposes exactly what the full rescan would."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        stream = [int(t) for t in rng.integers(0, 6, rng.integers(5, 60))]
        ngram = int(rng.integers(1, 4))
        k = int(rng.integers(1, 6))
        idx = NgramIndex(ngram)
        # grow in random chunk sizes, checking at every growth point
        i = 0
        while i < len(stream):
            i = min(len(stream), i + int(rng.integers(1, 7)))
            idx.sync(stream[:i])
            extra = [int(t) for t in rng.integers(0, 6, rng.integers(0, 3))]
            want = _prompt_lookup(stream[:i] + extra, ngram, k)
            got = idx.lookup(extra, k)
            assert got == want, (trial, i, ngram, k, stream[:i], extra)


def test_ngram_index_truncate_invalidates():
    """A trim of the stream's tail pops exactly the invalidated windows:
    lookups after truncate equal a fresh index over the short stream."""
    rng = np.random.default_rng(1)
    for trial in range(10):
        stream = [int(t) for t in rng.integers(0, 5, 50)]
        idx = NgramIndex(2)
        idx.sync(stream)
        cut = int(rng.integers(3, 40))
        idx.truncate(cut)
        fresh = NgramIndex(2)
        fresh.sync(stream[:cut])
        for nt in range(5):
            assert idx.lookup([nt], 4) == fresh.lookup([nt], 4), (trial, cut)
        # and the index keeps extending correctly after the trim
        regrow = stream[:cut] + [int(t) for t in rng.integers(0, 5, 10)]
        idx.sync(regrow)
        fresh2 = NgramIndex(2)
        fresh2.sync(regrow)
        assert idx.lookup([1], 4) == fresh2.lookup([1], 4)


# ----------------------------------------------------------------------
# acceptance-credit admission math (pure unit)
# ----------------------------------------------------------------------

def test_chain_len_scales_with_acceptance():
    assert CapacityView.chain_len_for(1.0, 4) == 4       # hot class: full
    assert CapacityView.chain_len_for(0.5, 4) == 2
    # a cold class keeps a 1-token probe — with zero proposals the EMA
    # could never update and the class would freeze drafting forever
    assert CapacityView.chain_len_for(0.0, 4) == 1
    assert CapacityView.chain_len_for(0.1, 4) == 1
    assert CapacityView.chain_len_for(2.0, 4) == 4       # clamped to [0,1]
    assert CapacityView.chain_len_for(0.13, 8) == 1
    assert CapacityView.chain_len_for(1.0, 0) == 0       # lookahead off


def test_draft_budget_prefill_claim_comes_off_the_top(model_and_params):
    eng = _engine(model_and_params)          # token_budget=64
    cap = CapacityView(eng, reserve_output=False)
    # no prefill backlog: slack = budget - one lane per decode
    assert cap.draft_budget(4, 0) == 60
    # prefill claims come first; drafting never starves prompt progress
    assert cap.draft_budget(4, 40) == 20
    # a prompt longer than the budget claims the whole tick (SplitFuse
    # spreads it); zero slack degrades the tick to plain decode
    assert cap.draft_budget(4, 1000) == 0
    assert cap.draft_budget(64, 0) == 0


# ----------------------------------------------------------------------
# quantize_kv: the storage format + error bound
# ----------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_kv_roundtrip_bound(bits):
    """Each dequantized element is within scale/2 of the input, where
    scale = absmax(head-vector)/qmax — the bound docs/serving.md states
    and the greedy-argmax-preservation argument rests on."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((3, 5, 16, 64)), jnp.float32)
    q, scale = quantize_kv(x, bits)
    back = dequantize_kv(q, scale, bits=bits)
    assert back.shape == x.shape
    bound = np.asarray(scale)[..., None] / 2 + 1e-7
    assert np.all(np.abs(np.asarray(back - x)) < bound)
    if bits == 4:
        assert q.dtype == jnp.uint8 and q.shape[-1] == 32   # nibble-packed
    else:
        assert q.dtype == jnp.int8 and q.shape[-1] == 64


def test_quantize_kv_zero_vector_safe():
    q, scale = quantize_kv(jnp.zeros((2, 8)), 8)
    assert np.all(np.asarray(dequantize_kv(q, scale, bits=8)) == 0.0)


def test_kv_page_bytes_capacity_ratios():
    """The capacity arithmetic at a production shape (head_dim 128, the
    per-head fp32 scale is ~3% overhead): at a fixed pool byte budget an
    int8 pool holds >= 1.8x the pages of the bf16 pool (the serving
    claim), int4 >= 3x."""
    from types import SimpleNamespace

    mc = SimpleNamespace(n_layers=32, n_kv_heads=8, head_dim=128)
    fp = _cfg(dtype=jnp.bfloat16)
    q8 = _cfg(dtype=jnp.bfloat16, kv_quant="int8")
    q4 = _cfg(dtype=jnp.bfloat16, kv_quant="int4")
    budget = 64 * kv_page_bytes(mc, fp)
    n_fp = kv_blocks_for_bytes(budget, mc, fp)
    n_q8 = kv_blocks_for_bytes(budget, mc, q8)
    n_q4 = kv_blocks_for_bytes(budget, mc, q4)
    assert n_fp == 64
    assert n_q8 >= 1.8 * n_fp
    assert n_q4 >= 3.0 * n_fp


# ----------------------------------------------------------------------
# serving tick: token identity, fewer ticks, fallback
# ----------------------------------------------------------------------

def _serve_one(model_and_params, spec: bool, n_new=48, prompt=(5, 6, 7, 8),
               scfg=None, **ecfg):
    eng = _engine(model_and_params, **ecfg)
    cfg = ServingConfig(speculative=spec, spec_ngram=2, spec_lookahead=4,
                        **(scfg or {}))
    srv = ServingEngine(eng, cfg, start=False)
    streamed = []
    req = Request(prompt=list(prompt), max_new_tokens=n_new,
                  on_token=lambda t: streamed.append(t))
    srv.submit_request(req)
    for _ in range(300):
        if req.is_terminal:
            break
        srv._tick()
    assert req.is_terminal, req.state
    toks, ticks = list(req.tokens), srv._tick_count
    srv.close()
    assert_block_balance(eng)
    return toks, streamed, ticks, req


def test_spec_token_identity_and_fewer_ticks(model_and_params):
    """THE headline contract: same greedy stream, fewer engine ticks.
    The tiny model's greedy continuation enters a cycle, so prompt-
    lookup drafts fire and accept."""
    t_off, s_off, n_off, _ = _serve_one(model_and_params, spec=False)
    t_on, s_on, n_on, req = _serve_one(model_and_params, spec=True)
    assert t_on == t_off                      # token-identical
    assert s_on == t_on and s_off == t_off    # streamed in order, complete
    assert req.spec_proposed > 0              # drafting actually engaged
    assert req.spec_accepted > 0
    assert n_on < n_off                       # and it actually paid
    # the per-request ledger reaches the terminal record
    assert req.spec_accepted <= req.spec_proposed


def test_spec_token_identity_quantized_pool(model_and_params):
    """Speculation composes with quantized storage: int8-pool spec-on
    equals int8-pool spec-off (identity is about WHAT the pool stores,
    not about fp-vs-quantized numerics)."""
    t_off, _, n_off, _ = _serve_one(model_and_params, spec=False,
                                    scfg={"kv_quant": "int8"},
                                    kv_quant="int8")
    t_on, _, n_on, req = _serve_one(model_and_params, spec=True,
                                    scfg={"kv_quant": "int8"},
                                    kv_quant="int8")
    assert t_on == t_off
    assert req.spec_proposed > 0
    assert n_on <= n_off


def test_spec_fallback_below_floor(model_and_params):
    """A request whose acceptance EMA can't clear an absurd floor latches
    to plain decode — and the stream is unchanged (identity holds through
    the latch)."""
    scfg = {"spec_accept_floor": 0.99, "spec_floor_min_proposed": 4,
            "spec_ema": 0.5}
    t_off, _, _, _ = _serve_one(model_and_params, spec=False)
    t_on, _, _, req = _serve_one(model_and_params, spec=True, scfg=scfg)
    assert t_on == t_off
    assert req.spec_proposed > 0              # drafted until the latch
    assert req._spec_disabled                 # then stopped for good


def test_spec_kv_quant_mode_mismatch_raises(model_and_params):
    eng = _engine(model_and_params)           # stores fp
    with pytest.raises(ValueError, match="kv_quant"):
        ServingEngine(eng, ServingConfig(kv_quant="int8"), start=False)


def test_serving_config_validates_spec_knobs():
    from deepspeed_tpu.config import ConfigError

    assert ServingConfig.from_dict(
        {"speculative": True, "kv_quant": "int4"}).kv_quant == "int4"
    for bad in ({"spec_lookahead": 0}, {"spec_ngram": 0},
                {"spec_accept_floor": 1.5}, {"spec_ema": 0.0},
                {"kv_quant": "fp8"}):
        with pytest.raises(ConfigError):
            ServingConfig.from_dict(bad)


def test_verify_trim_failure_takes_tick_fault_path(model_and_params):
    """The rejected-tail trim can allocate (copy-on-write boundary page)
    and so can raise PoolExhausted: the failure must be contained as a
    per-request tick fault — engine state discarded, request requeued,
    stream still token-identical — never an escaped exception that
    leaves trimmed/untrimmed streams diverged from their requests."""
    from deepspeed_tpu.inference.kv_cache import PoolExhausted

    t_plain, _, _, _ = _serve_one(model_and_params, spec=False)

    eng = _engine(model_and_params)
    real_trim = type(eng).trim
    fails = {"n": 0}

    def flaky_trim(self, uid, length):
        if fails["n"] == 0:
            fails["n"] += 1
            raise PoolExhausted("injected: COW page allocation failed")
        return real_trim(self, uid, length)

    eng.trim = flaky_trim.__get__(eng)
    srv = ServingEngine(eng, ServingConfig(speculative=True, spec_ngram=2,
                                           spec_lookahead=4,
                                           tick_retry_limit=3),
                        start=False)
    req = Request(prompt=[5, 6, 7, 8], max_new_tokens=48)
    srv.submit_request(req)
    for _ in range(300):
        if req.is_terminal:
            break
        srv._tick()
    assert req.state.value == "finished", (req.state, req.error)
    assert fails["n"] == 1                       # the failure actually fired
    assert req.retries == 1                      # took the tick-fault path
    assert list(req.tokens) == t_plain           # stream still identical
    srv.close()
    assert_block_balance(eng)


def test_put_spec_invalid_chain_leaves_no_draft_tokens(model_and_params):
    """A pending!=1 chain must raise BEFORE any uid's drafts touch a
    stream: a raise mid-append would leave earlier uids' unverified
    proposals as real context for the next plain put()."""
    eng = _engine(model_and_params)
    eng.put([1], [[5, 6, 7, 8]])          # uid 1: pending 0 after prefill
    eng.put([2], [[9, 3, 9, 3]])
    len1 = len(eng.seqs[1].tokens)
    # uid 1 drafts legally (one pending token); uid 2 is fed TWO tokens,
    # so its chain is illegal — the whole call must reject atomically
    with pytest.raises(ValueError, match="pending"):
        eng.put_spec([1, 2], [[11], [12, 13]], [[21, 22], [23]])
    assert len(eng.seqs[1].tokens) == len1 + 1        # fed token only
    assert eng.seqs[1].tokens[-1] == 11               # no draft residue
    eng.flush([1, 2])
    assert_block_balance(eng)


# ----------------------------------------------------------------------
# quantized pool: capacity, export/import, PoolExhausted recovery
# ----------------------------------------------------------------------

def test_quantized_pool_admits_more_sequences(model_and_params):
    """At a FIXED byte budget, the int8 pool admits >= 1.8x the
    concurrent sequences (same prompt workload, count admissions until
    PoolExhausted)."""
    from deepspeed_tpu.inference.kv_cache import PoolExhausted

    model, _ = model_and_params
    fp_cfg = _cfg(max_seqs=32, n_kv_blocks=1, enable_prefix_cache=False)
    budget = 16 * kv_page_bytes(model.config, fp_cfg)

    def admit_until_full(kv_quant):
        cfg = _cfg(max_seqs=32, kv_quant=kv_quant,
                   enable_prefix_cache=False)
        cfg.n_kv_blocks = kv_blocks_for_bytes(budget, model.config, cfg)
        eng = RaggedInferenceEngine(model, cfg,
                                    params=model_and_params[1])
        n = 0
        try:
            for uid in range(32):
                eng.put([uid], [[1 + uid % 100] * 16])    # 2 pages each
                n += 1
        except PoolExhausted:
            pass
        assert_block_balance(eng)
        return n

    n_fp = admit_until_full("none")
    n_q = admit_until_full("int8")
    assert n_fp == 8                          # 16 pages / 2 per seq
    assert n_q >= 1.8 * n_fp


def test_quantized_export_import_bit_exact(model_and_params):
    """The disaggregated hand-off under kv_quant: the importer adopts
    the QUANTIZED payload bit-identically (no re-quantization), so the
    greedy continuation after import equals the uninterrupted one —
    and the wire moves about half the fp bytes."""
    P = [9, 3, 9, 3, 9, 3, 7, 7]
    eng_a = _engine(model_and_params, kv_quant="int8")
    logits = eng_a.put([1], [list(P)])
    t0 = int(np.argmax(logits[0]))
    export = eng_a.export_kv(1)
    assert export.kv_quant == "int8"
    assert export.k_scales is not None
    # wire accounting: quantized payload + scales vs what fp32 would move
    c = model_and_params[0].config
    fp_bytes = (2 * export.n_pages * c.n_layers * c.n_kv_heads
                * eng_a.config.kv_block_size * c.head_dim * 4)
    assert export.nbytes < 0.6 * fp_bytes
    # uninterrupted continuation on A
    cont_a = eng_a.decode_steps({1: t0}, 6)[1]
    # adopted continuation on B (fresh engine, same config/params)
    eng_b = _engine(model_and_params, kv_quant="int8")
    eng_b.import_kv(7, export)
    cont_b = eng_b.decode_steps({7: t0}, 6)[7]
    assert cont_a == cont_b
    eng_b.flush([7])
    assert_block_balance(eng_b)
    # mode mismatch is typed: an fp engine refuses a quantized export
    eng_c = _engine(model_and_params)
    with pytest.raises(ValueError, match="kv_quant"):
        eng_c.import_kv(8, export)
    assert_block_balance(eng_c, expect_free=64)


def test_pool_exhausted_recovery_quantized(model_and_params):
    """Mid-tick pool exhaustion under quantized pages takes the same
    preempt-cheapest-and-retry path; every request finishes and the
    pool balances to zero leaks."""
    eng = _engine(model_and_params, kv_quant="int8", n_kv_blocks=10,
                  max_seqs=3, enable_prefix_cache=False)
    srv = ServingEngine(eng, ServingConfig(kv_quant="int8",
                                           reserve_output_blocks=0),
                        start=False)
    reqs = [srv.submit([1 + i] * 12, max_new_tokens=16, priority=i)
            for i in range(3)]
    for _ in range(400):
        if all(r.is_terminal for r in reqs):
            break
        srv._tick()
    srv.close()
    for r in reqs:
        assert r.state.value == "finished", (r.state, r.error)
        assert len(r.tokens) == 16
    assert_block_balance(eng, expect_free=10)


# ----------------------------------------------------------------------
# telemetry: spec fields in the request record schema
# ----------------------------------------------------------------------

def test_request_record_spec_fields_optional():
    from deepspeed_tpu.telemetry import RequestStats, validate_request_record

    rec = RequestStats(uid=1, state="finished", prompt_tokens=4,
                       new_tokens=8, spec_proposed=12,
                       spec_accepted=7).to_record()
    assert validate_request_record(rec) == []
    # archived records predate speculative serving: still valid
    rec2 = RequestStats(uid=2, state="finished", prompt_tokens=4,
                        new_tokens=8).to_record()
    rec2.pop("spec_proposed", None)
    rec2.pop("spec_accepted", None)
    assert validate_request_record(rec2) == []
    bad = dict(rec, spec_proposed="twelve")
    assert any("spec_proposed" in e for e in validate_request_record(bad))


def test_record_spec_reaches_registry(model_and_params, tmp_path):
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "tel")

    t = Telemetry(config=Cfg())
    set_telemetry(t)
    try:
        eng = _engine(model_and_params)
        eng.record_spec(proposed=8, accepted=5, rounds=2)
        r = t.registry
        assert r.counter("inference/spec_proposed").value == 8
        assert r.counter("inference/spec_accepted").value == 5
        assert r.counter("inference/spec_rounds").value == 2
        assert r.gauge("inference/spec_acceptance").value == 5 / 8
        assert eng.spec_stats == {"proposed": 8, "accepted": 5, "rounds": 2}
    finally:
        set_telemetry(None)
