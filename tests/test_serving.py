"""Serving layer: request state machine, scheduler policies, backpressure,
cancellation at every lifecycle stage, preempt-then-resume bit-exactness,
tick-fault recovery, and zero-leak KV block accounting (docs/serving.md).

Driver-dependent tests construct the ServingEngine with ``start=False``
and call ``_tick()`` by hand — one deterministic tick at a time, no
thread scheduling in the assertions. A couple of end-to-end tests run the
real background driver."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (
    assert_block_balance,
    block_balance_report,
)
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import Llama
from deepspeed_tpu.resilience import FaultInjector, install_fault_injector
from deepspeed_tpu.serving import (
    FCFSPolicy,
    InvalidTransition,
    Request,
    RequestState,
    SLOPolicy,
    ServingEngine,
    make_policy,
)


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


def _cfg(**kw):
    kw.setdefault("token_budget", 32)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("n_kv_blocks", 64)
    kw.setdefault("max_context", 128)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("enable_prefix_cache", True)
    return RaggedConfig(**kw)


def _engine(model_and_params, **kw):
    model, params = model_and_params
    return RaggedInferenceEngine(model, _cfg(**kw), params=params)


def _prompt(seed, n):
    return list(np.random.default_rng(seed).integers(1, 128, n))


def _tick_until(srv, done, limit=200):
    for _ in range(limit):
        if done():
            return
        srv._tick()
    raise AssertionError(f"no progress after {limit} ticks")


# ----------------------------------------------------------------------
# request state machine (pure unit)
def test_state_machine_legal_path():
    r = Request(prompt=[1, 2, 3])
    assert r.state is RequestState.QUEUED and not r.is_terminal
    r.transition(RequestState.PREFILL)
    r.transition(RequestState.DECODE)
    assert r.is_live
    r.transition(RequestState.QUEUED)        # preemption edge
    r.transition(RequestState.PREFILL)
    r.transition(RequestState.DECODE)
    r.transition(RequestState.FINISHED)
    assert r.is_terminal and r.t_finish is not None
    assert r.wait(0.1)


def test_state_machine_illegal_transitions():
    r = Request(prompt=[1])
    with pytest.raises(InvalidTransition):
        r.transition(RequestState.DECODE)    # QUEUED -> DECODE skips prefill
    r.transition(RequestState.REJECTED)
    for s in RequestState:
        with pytest.raises(InvalidTransition):
            r.transition(s)                  # terminal states are absorbing


def test_request_validation():
    with pytest.raises(ValueError):
        Request(prompt=[])
    with pytest.raises(ValueError):
        Request(prompt=[1], max_new_tokens=0)


def test_request_slo_judgment():
    r = Request(prompt=[1], deadline_s=1.0, ttft_deadline_s=0.5)
    assert Request(prompt=[1]).in_slo() is None      # no SLO attached
    r.t_submit = 100.0
    r.t_first_token = 100.4
    r.transition(RequestState.PREFILL)
    r.transition(RequestState.DECODE)
    r.transition(RequestState.FINISHED)
    r.t_finish = 100.9
    assert r.in_slo() is True
    r.t_finish = 101.1                               # e2e deadline missed
    assert r.in_slo() is False


# ----------------------------------------------------------------------
# scheduler policies (pure unit)
def _req(uid, t_submit, priority=0, deadline_s=None):
    r = Request(prompt=[1, 2], uid=uid, priority=priority,
                deadline_s=deadline_s)
    r.t_submit = t_submit
    return r


def test_slo_admission_order_priority_then_edf():
    a = _req(1, t_submit=0.0, priority=0, deadline_s=1.0)   # dl 1.0
    b = _req(2, t_submit=0.1, priority=0, deadline_s=0.5)   # dl 0.6
    c = _req(3, t_submit=0.2, priority=5, deadline_s=9.0)   # top tier
    d = _req(4, t_submit=0.05, priority=0)                  # no deadline
    order = SLOPolicy().admission_order([a, b, c, d], now=0.3)
    assert [r.uid for r in order] == [3, 2, 1, 4]


def test_slo_rejects_expired_deadline():
    pol = SLOPolicy()
    fresh = _req(1, t_submit=0.0, deadline_s=10.0)
    stale = _req(2, t_submit=0.0, deadline_s=0.5)
    assert pol.should_reject(fresh, now=1.0) is None
    assert "expired" in pol.should_reject(stale, now=1.0)
    assert SLOPolicy(reject_expired=False).should_reject(stale, 1.0) is None


def test_fcfs_is_arrival_order_and_never_rejects():
    pol = FCFSPolicy()
    a, b = _req(1, t_submit=0.5), _req(2, t_submit=0.1, priority=9,
                                       deadline_s=0.01)
    assert [r.uid for r in pol.admission_order([a, b], now=99.0)] == [2, 1]
    assert pol.should_reject(b, now=99.0) is None      # hopeless but FCFS
    assert pol.head_of_line_blocking is True
    assert pol.preemption_victims(a, [b], None, 99.0) == []


def test_make_policy():
    assert make_policy("fcfs").name == "fcfs"
    assert make_policy("slo", kv_pressure=0.5).kv_pressure == 0.5
    with pytest.raises(ValueError):
        make_policy("lifo")


# ----------------------------------------------------------------------
# admission backpressure
def test_reject_on_full_queue(model_and_params):
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, {"max_queue": 2, "default_max_new_tokens": 4},
                        start=False)
    reqs = [srv.submit(_prompt(i, 6)) for i in range(3)]
    assert [r.state for r in reqs[:2]] == [RequestState.QUEUED] * 2
    assert reqs[2].state is RequestState.REJECTED
    assert "full" in reqs[2].error
    # rejected requests never held engine state: balance intact
    assert_block_balance(eng, expect_free=eng.allocator.n_blocks)


def test_reject_oversized_request(model_and_params):
    eng = _engine(model_and_params)          # max_context 128
    srv = ServingEngine(eng, start=False)
    r = srv.submit(_prompt(0, 100), max_new_tokens=64)
    assert r.state is RequestState.REJECTED
    assert "max_context" in r.error
    with pytest.raises(RuntimeError, match="REJECTED"):
        r.result(timeout=0.1)


def test_reject_request_exceeding_kv_pool(model_and_params):
    # fits max_context but can never hold all its pages at once: admitting
    # it would head-of-line-block FCFS forever
    eng = _engine(model_and_params, n_kv_blocks=8, max_context=128)
    srv = ServingEngine(eng, {"policy": "fcfs"}, start=False)
    r = srv.submit(_prompt(0, 40), max_new_tokens=48)   # needs 12 > 8 blocks
    assert r.state is RequestState.REJECTED
    assert "KV pool" in r.error


def test_output_reservation_binds_across_ticks(model_and_params):
    # pool of 8 blocks (64 tokens). A reserves 7 blocks at admission but
    # only holds 2 after its first ticks; B (needs 4) must stay QUEUED
    # until A's reserved growth drains — admitting it would exhaust the
    # pool mid-decode and force an eviction even under no-preempt FCFS
    eng = _engine(model_and_params, n_kv_blocks=8, max_context=64,
                  enable_prefix_cache=False)
    srv = ServingEngine(eng, {"policy": "fcfs",
                              "reserve_output_blocks": True}, start=False)
    preempted_pre = srv._telemetry.registry.counter("serving/preempted").value
    a = srv.submit(_prompt(60, 8), max_new_tokens=40)   # total 48 -> 7 blocks
    _tick_until(srv, lambda: len(a.tokens) >= 1)
    b = srv.submit(_prompt(61, 8), max_new_tokens=16)   # total 24 -> 4 blocks
    srv._tick()
    assert b.state is RequestState.QUEUED               # reservation held
    _tick_until(srv, lambda: a.is_terminal and b.is_terminal, limit=300)
    assert a.state is RequestState.FINISHED
    assert b.state is RequestState.FINISHED
    reg = srv._telemetry.registry
    assert reg.counter("serving/preempted").value == preempted_pre  # no evictions
    assert_block_balance(eng, expect_free=eng.allocator.n_blocks)


# ----------------------------------------------------------------------
# end-to-end correctness against the bare engine
def test_serving_output_matches_direct_engine(model_and_params):
    p = _prompt(3, 9)
    ref = _engine(model_and_params).generate({1: p}, max_new_tokens=6)[1]

    eng = _engine(model_and_params)
    with ServingEngine(eng, {"policy": "slo"}) as srv:
        out = srv.submit(p, max_new_tokens=6).result(timeout=60)
        assert out == ref
        # streaming surface yields the identical token sequence
        assert list(srv.stream(p, max_new_tokens=6)) == ref
        assert srv.drain(timeout=30)
    assert srv.block_leaks() == []


def test_eos_finishes_early(model_and_params):
    p = _prompt(3, 9)
    ref = _engine(model_and_params).generate({1: p}, max_new_tokens=6)[1]
    eos = ref[2]                     # third generated token acts as EOS
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, start=False)
    req = srv.submit(p, max_new_tokens=6, eos_token_id=eos)
    _tick_until(srv, lambda: req.is_terminal)
    assert req.state is RequestState.FINISHED
    # stops at the FIRST occurrence of eos in the greedy stream
    assert req.result() == ref[:ref.index(eos) + 1]
    assert len(req.tokens) < len(ref)
    assert_block_balance(eng)


# ----------------------------------------------------------------------
# cancellation at every lifecycle stage, with block-balance proof
def test_cancel_queued(model_and_params):
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, start=False)
    r = srv.submit(_prompt(0, 6), max_new_tokens=4)
    assert srv.cancel(r) is True
    assert r.state is RequestState.CANCELLED
    assert srv.cancel(r) is False            # already terminal
    assert srv.queue_depth == 0
    assert_block_balance(eng, expect_free=eng.allocator.n_blocks)


def test_cancel_during_prefill(model_and_params):
    # prompt longer than the token budget (32): prefill spans ticks, so
    # after one tick the request is mid-prefill holding KV blocks
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, start=False)
    r = srv.submit(_prompt(1, 50), max_new_tokens=4)
    srv._tick()
    assert r.state is RequestState.PREFILL
    assert eng.seqs[r.uid].pending > 0       # genuinely mid-prefill
    held_before = block_balance_report(eng)["held"]
    assert held_before > 0
    srv.cancel(r)
    srv._tick()                              # driver releases at tick edge
    assert r.state is RequestState.CANCELLED
    assert_block_balance(eng)
    assert srv.live_requests == 0 and r.uid not in eng.seqs


def test_cancel_during_decode_by_uid(model_and_params):
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, start=False)
    r = srv.submit(_prompt(2, 8), max_new_tokens=32)
    _tick_until(srv, lambda: len(r.tokens) >= 3)
    assert r.state is RequestState.DECODE
    assert srv.cancel(r.uid) is True         # cancel accepts bare uids
    srv._tick()
    assert r.state is RequestState.CANCELLED
    assert len(r.tokens) >= 3                # partial output retained
    assert_block_balance(eng)


def test_stream_raises_on_post_admission_reject(model_and_params):
    # a request shed AFTER submit (expired deadline, drain, latch) must
    # surface as an error from stream(), never as an empty generation
    eng = _engine(model_and_params)
    with ServingEngine(eng, {"policy": "slo"}) as srv:
        with pytest.raises(RuntimeError, match="rejected"):
            list(srv.stream(_prompt(5, 8), max_new_tokens=4,
                            deadline_s=1e-9))
    assert srv.block_leaks() == []


def test_stream_break_cancels(model_and_params):
    eng = _engine(model_and_params)
    with ServingEngine(eng) as srv:
        got = []
        for tok in srv.stream(_prompt(4, 8), max_new_tokens=40):
            got.append(tok)
            if len(got) == 2:
                break                        # consumer walks away
        deadline = time.perf_counter() + 10
        while srv.live_requests and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert srv.live_requests == 0
    assert srv.block_leaks() == []


# ----------------------------------------------------------------------
# preemption and bit-exact resume
def test_preempt_then_resume_bit_exact(model_and_params):
    p_low = _prompt(10, 9)
    p_high = _prompt(11, 8)
    ref = _engine(model_and_params).generate({1: p_low}, max_new_tokens=8)[1]

    # one sequence slot: admitting the high-priority request REQUIRES
    # evicting the low-priority decode (slot preemption)
    eng = _engine(model_and_params, max_seqs=1)
    srv = ServingEngine(eng, {"policy": "slo", "kv_pressure": 0.0,
                              "reserve_output_blocks": True}, start=False)
    low = srv.submit(p_low, max_new_tokens=8, priority=0)
    _tick_until(srv, lambda: len(low.tokens) >= 3)
    high = srv.submit(p_high, max_new_tokens=4, priority=5)
    srv._tick()                              # admission preempts `low`
    assert low.state is RequestState.QUEUED
    assert low.preemptions == 1
    assert high.state in (RequestState.PREFILL, RequestState.DECODE)
    _tick_until(srv, lambda: high.is_terminal and low.is_terminal)
    assert high.state is RequestState.FINISHED
    assert low.state is RequestState.FINISHED
    # the preempted request re-prefilled prompt+emitted (riding the prefix
    # cache) and continued the identical greedy stream
    assert low.tokens == ref
    assert eng.prefix_cache.hits >= 1        # resume rode cached KV pages
    assert_block_balance(eng)


def test_preempt_then_cancel_clears_resume_marker(model_and_params):
    # a preempted request that dies without re-admission must not leave
    # a resume marker: a later sequence reusing the uid (direct engine
    # use after serving) would silently skip its telemetry
    eng = _engine(model_and_params, max_seqs=1)
    srv = ServingEngine(eng, {"policy": "slo", "kv_pressure": 0.0},
                        start=False)
    low = srv.submit(_prompt(14, 8), max_new_tokens=8, priority=0)
    _tick_until(srv, lambda: len(low.tokens) >= 2)
    high = srv.submit(_prompt(15, 8), max_new_tokens=2, priority=5)
    srv._tick()
    assert low.state is RequestState.QUEUED          # preempted
    assert low.uid in eng._resume_uids
    srv.cancel(low)
    assert low.state is RequestState.CANCELLED
    assert low.uid not in eng._resume_uids
    _tick_until(srv, lambda: high.is_terminal)
    assert_block_balance(eng)


def test_no_preemption_among_equal_priority(model_and_params):
    eng = _engine(model_and_params, max_seqs=1)
    srv = ServingEngine(eng, {"policy": "slo", "kv_pressure": 0.0},
                        start=False)
    a = srv.submit(_prompt(12, 8), max_new_tokens=6, priority=1)
    _tick_until(srv, lambda: len(a.tokens) >= 2)
    b = srv.submit(_prompt(13, 8), max_new_tokens=4, priority=1)
    srv._tick()
    assert a.preemptions == 0                # equal tier never thrashes
    assert b.state is RequestState.QUEUED
    _tick_until(srv, lambda: a.is_terminal and b.is_terminal)
    assert a.state is RequestState.FINISHED
    assert b.state is RequestState.FINISHED
    assert_block_balance(eng)


# ----------------------------------------------------------------------
# tick faults: retry-or-fail, never a leaked block
def test_tick_fault_retries_and_stays_bit_exact(model_and_params):
    p = _prompt(20, 8)
    ref = _engine(model_and_params).generate({1: p}, max_new_tokens=6)[1]
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, {"tick_retry_limit": 1}, start=False)
    install_fault_injector(FaultInjector(serving_tick_fail_at=3))
    req = srv.submit(p, max_new_tokens=6)
    _tick_until(srv, lambda: req.is_terminal)
    assert req.state is RequestState.FINISHED
    assert req.retries == 1
    assert req.result() == ref               # replay from the token stream
    assert_block_balance(eng)


def test_tick_fault_budget_exhausted_fails_request(model_and_params):
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, {"tick_retry_limit": 1}, start=False)
    install_fault_injector(FaultInjector(serving_tick_fail_every=1))
    req = srv.submit(_prompt(21, 8), max_new_tokens=6)
    _tick_until(srv, lambda: req.is_terminal, limit=10)
    assert req.state is RequestState.CANCELLED
    assert "tick fault" in req.error
    assert req.retries == 2                  # initial + 1 retry, both died
    assert_block_balance(eng, expect_free=eng.allocator.n_blocks)


def test_tick_fault_never_publishes_suspect_kv(model_and_params):
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, {"tick_retry_limit": 0}, start=False)
    install_fault_injector(FaultInjector(serving_tick_fail_at=2))
    req = srv.submit(_prompt(22, 20), max_new_tokens=4)
    _tick_until(srv, lambda: req.is_terminal, limit=10)
    assert req.state is RequestState.CANCELLED
    # discard path: the faulted sequence's KV never entered the cache
    assert len(eng.prefix_cache) == 0
    assert_block_balance(eng, expect_free=eng.allocator.n_blocks)


# ----------------------------------------------------------------------
# drain / shutdown
def test_drain_serves_backlog_then_refuses(model_and_params):
    eng = _engine(model_and_params)
    with ServingEngine(eng) as srv:
        reqs = [srv.submit(_prompt(i, 8), max_new_tokens=4)
                for i in range(6)]
        assert srv.drain(timeout=60)
        assert all(r.state is RequestState.FINISHED for r in reqs)
        late = srv.submit(_prompt(9, 8), max_new_tokens=4)
        assert late.state is RequestState.REJECTED
    assert srv.block_leaks() == []


def test_preemption_latch_drains_queue(model_and_params):
    class Latch:
        should_stop = False

    latch = Latch()
    eng = _engine(model_and_params, max_seqs=1)
    srv = ServingEngine(eng, {"default_max_new_tokens": 8},
                        preemption_guard=latch, start=False)
    live = srv.submit(_prompt(30, 8))
    srv._tick()                              # `live` is now in the engine
    assert live.state in (RequestState.PREFILL, RequestState.DECODE)
    queued = [srv.submit(_prompt(31 + i, 8)) for i in range(3)]
    latch.should_stop = True
    srv.start()                              # driver sees the latch first
    assert srv.drain(timeout=60)
    # graceful: in-flight work finishes, the queue is rejected
    assert live.state is RequestState.FINISHED
    assert all(q.state is RequestState.REJECTED for q in queued)
    assert all("preemption" in q.error for q in queued)
    srv.close()
    assert srv.block_leaks() == []


def test_watchdog_flags_stuck_tick(model_and_params):
    eng = _engine(model_and_params)
    real_put = eng.put
    slow = {"done": False}

    def sticky_put(uids, toks):
        if not slow["done"]:
            slow["done"] = True
            time.sleep(0.4)
        return real_put(uids, toks)

    eng.put = sticky_put
    with ServingEngine(eng, {"stuck_tick_timeout_s": 0.05}) as srv:
        req = srv.submit(_prompt(40, 8), max_new_tokens=3)
        req.wait(timeout=60)
        counter = srv._telemetry.registry.counter("serving/stuck_ticks")
        assert counter.value >= 1


# ----------------------------------------------------------------------
# the auditor itself must catch real imbalances
def test_block_balance_report_detects_corruption(model_and_params):
    eng = _engine(model_and_params)
    srv = ServingEngine(eng, start=False)
    r = srv.submit(_prompt(50, 8), max_new_tokens=8)
    _tick_until(srv, lambda: len(r.tokens) >= 1)
    seq = eng.seqs[r.uid]
    stolen = seq.blocks.pop()                # sequence loses a held page
    assert any("refcount" in p
               for p in block_balance_report(eng)["problems"])
    seq.blocks.append(stolen)
    assert block_balance_report(eng)["problems"] == []
    eng.allocator._free.append(stolen)       # page both free and held
    assert any("free and referenced" in p
               for p in block_balance_report(eng)["problems"])
    eng.allocator._free.pop()
    with pytest.raises(AssertionError):
        assert_block_balance(eng, expect_free=-1)


# ----------------------------------------------------------------------
# randomized soak: interleaved cancels, preemptions and faults never leak
def test_soak_random_lifecycle_zero_leak(model_and_params):
    eng = _engine(model_and_params, max_seqs=2, n_kv_blocks=24)
    srv = ServingEngine(eng, {"policy": "slo", "kv_pressure": 0.5,
                              "tick_retry_limit": 1}, start=False)
    install_fault_injector(FaultInjector(serving_tick_fail_every=11))
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(14):
        reqs.append(srv.submit(_prompt(100 + i, int(rng.integers(4, 14))),
                               max_new_tokens=int(rng.integers(2, 7)),
                               priority=int(rng.integers(0, 3)),
                               deadline_s=30.0))
        srv._tick()
        if rng.random() < 0.3 and reqs:
            srv.cancel(reqs[int(rng.integers(0, len(reqs)))])
    _tick_until(srv, lambda: all(r.is_terminal for r in reqs), limit=500)
    assert_block_balance(eng)
    eng.prefix_cache.drop_all(eng.allocator)
    assert_block_balance(eng, expect_free=eng.allocator.n_blocks)


# ----------------------------------------------------------------------
# the engine's result in its two forms: token ids chosen inside the jitted
# step, or logits rows and the server's argmax (docs/serving.md)
def _served(eng, prompts, max_new_tokens, as_ids, cfg=None):
    """Serves ``prompts`` to the end by hand on ``eng``. Returns the token
    streams, every first result the engine handed the server, and the
    server."""
    results = []
    inner = eng.put

    def put(uids, toks):
        results.append(inner(uids, toks))
        return results[-1]

    eng.put = put
    srv = ServingEngine(eng, cfg or {"policy": "fcfs"}, start=False)
    if hasattr(eng, "return_token_ids"):     # the server declared ids
        eng.return_token_ids(as_ids)
    reqs = [srv.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
    _tick_until(srv, lambda: all(r.is_terminal for r in reqs), limit=400)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    srv.close()
    return [list(r.tokens) for r in reqs], results, srv


def _tied_head(model_and_params):
    """Every logit has an exact twin 64 columns on: argmax meets a tie at
    every token, and the first index wins on the host and on the device."""
    model, params = model_and_params
    head = np.asarray(params["lm_head"]).copy()
    head[:, 64:] = head[:, :64]
    return model, dict(params, lm_head=jnp.asarray(head))


class _IdsSim:
    """``SimEngine`` (one-hot rows, no ``return_token_ids``) behind the
    declaring method and the ids form: what the server sees of a real
    engine, from a fake."""

    def __init__(self, sim):
        self._sim = sim
        self.declared = False

    def __getattr__(self, name):
        return getattr(self._sim, name)

    def return_token_ids(self, on=True):
        self.declared = on

    def put(self, uids, tokens):
        rows = self._sim.put(uids, tokens)
        if not self.declared:
            return rows
        return np.asarray([-1 if np.isnan(r[0]) else int(np.argmax(r))
                           for r in rows], np.int32)


IDS_CASES = {
    # name: (engine kwargs, prompt lengths, max_new_tokens, serving config)
    "one_tick": (dict(), [9], 6, None),
    "prompt_split_over_ticks": (dict(), [70], 5, None),
    "two_sequences": (dict(), [9, 17], 6, None),
    "max_seqs_sequences": (dict(), [9, 17, 5, 30], 6, None),
    "preempt_resume_pool_exhausted": (
        dict(n_kv_blocks=6, enable_prefix_cache=False), [16, 16], 20,
        {"policy": "fcfs", "reserve_output_blocks": False}),
    "tie_first_index_wins": (dict(), [9, 17], 8, None),
    "spy_around_step_fn": (dict(), [9, 40], 5, None),
    "tp2": (dict(), [9, 17], 6, None),
    "fake_engine_one_hot_rows": (None, [9, 17, 40], 6, None),
}


@pytest.mark.parametrize("case", list(IDS_CASES))
def test_ids_form_streams_equal_rows_form(model_and_params, case):
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.resilience.dst import SimConfig, SimEngine

    kw, lens, max_new, scfg = IDS_CASES[case]
    prompts = [_prompt(40 + i, n) for i, n in enumerate(lens)]
    if case == "tie_first_index_wins":
        model_and_params = _tied_head(model_and_params)

    def build():
        if kw is None:
            return SimEngine(SimConfig())
        if case == "tp2":
            mesh_mod.reset_topology()
            model, params = model_and_params
            return RaggedInferenceEngine(
                model, _cfg(**kw), params=params,
                topology=mesh_mod.Topology.build_virtual({"model": 2}))
        eng = _engine(model_and_params, **kw)
        if case == "spy_around_step_fn":
            # what benchmarks/sweep.py and the span tests put there: a
            # plain function around the step, two results
            step = eng._build_step()
            eng._step_fn = lambda *a: step(*a)
        return eng

    preempted = lambda srv: srv._telemetry.registry.counter(
        "serving/preempted").value
    before = preempted(ServingEngine(build(), start=False))
    if kw is None:
        sim = build()
        assert not hasattr(sim, "return_token_ids")
        rows_streams, rows_results, _ = _served(sim, prompts, max_new, False)
        ids_streams, ids_results, _ = _served(_IdsSim(build()), prompts,
                                              max_new, True)
    else:
        rows_streams, rows_results, _ = _served(build(), prompts, max_new,
                                                False, scfg)
        ids_streams, ids_results, srv = _served(build(), prompts, max_new,
                                                True, scfg)
        assert_block_balance(srv._engine)
    assert ids_streams == rows_streams
    assert all(len(s) == max_new for s in ids_streams)
    assert all(r.ndim == 2 and r.dtype == np.float32 for r in rows_results)
    assert all(r.ndim == 1 and r.dtype == np.int32 for r in ids_results)
    assert len(ids_results) == len(rows_results)
    # -1 exactly where the rows form has a NaN row
    for got, want in zip(ids_results, rows_results):
        np.testing.assert_array_equal(got < 0, np.isnan(want[:, 0]))
    if case in ("prompt_split_over_ticks", "fake_engine_one_hot_rows"):
        assert any((r < 0).any() for r in ids_results)
    if case == "preempt_resume_pool_exhausted":
        assert preempted(srv) >= before + 2      # once a form at least
    if case == "tie_first_index_wins":
        assert all(t < 64 for s in ids_streams for t in s)
        ties = [r for r in rows_results for r in r if not np.isnan(r[0])]
        assert all(row[np.argmax(row) + 64] == row.max() for row in ties)


def test_put_default_form_is_logits_and_fetch_bytes_count(model_and_params,
                                                          tmp_path):
    """``put`` without a declaration returns float32 rows (NaN while a
    prompt is mid-prefill), the same values whether or not another engine
    serves ids, and the ids are their argmax; ``inference/fetch_bytes``
    counts what each form brings back."""
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    class Cfg:
        enabled = True
        output_dir = str(tmp_path)

    tel = Telemetry(config=Cfg())
    set_telemetry(tel)
    try:
        fetched = tel.registry.counter("inference/fetch_bytes")
        a, b = _engine(model_and_params), _engine(model_and_params)
        b.return_token_ids()
        long, short = _prompt(7, 40), _prompt(8, 5)
        rows = a.put([1, 2], [short, long]).copy()
        assert fetched.value == 4 * 128 * 4        # [max_seqs, vocab] f32
        ids = b.put([1, 2], [short, long])
        assert fetched.value == 4 * 128 * 4 + 4 * 4    # [max_seqs] int32
        assert rows.dtype == np.float32 and rows.shape == (2, 128)
        assert np.isfinite(rows[0]).all() and np.isnan(rows[1]).all()
        assert ids.dtype == np.int32 and ids.tolist() == [
            int(np.argmax(rows[0])), -1]
        rows = a.put([1, 2], [[int(ids[0])], []])
        ids = b.put([1, 2], [[int(ids[0])], []])
        assert np.isfinite(rows).all()
        assert ids.tolist() == np.argmax(rows, -1).tolist()
        # the engine's own generate reads logits whatever was declared
        assert b.generate({9: short}, max_new_tokens=4) \
            == a.generate({9: short}, max_new_tokens=4)
    finally:
        tel.close()
        set_telemetry(None)
