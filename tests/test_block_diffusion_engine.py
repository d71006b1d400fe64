"""Generation by diffusion over blocks (SDAR, ``attn_block`` > 1) through
``RaggedInferenceEngine`` and ``ServingEngine``, at a tiny size in float32
on the CPU, against the plain reference ``benchmarks/reference/sdar_moe.py``
(full forwards, no cache) pass by pass: the logits of every denoise pass
as the engine fed it, and the tokens a plain loop of full forwards
generates."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, weights  # noqa: E402
from benchmarks.reference import sdar_moe as ref  # noqa: E402
from deepspeed_tpu.inference.ragged import (RaggedConfig,  # noqa: E402
                                            RaggedInferenceEngine,
                                            assert_block_balance)
from deepspeed_tpu.parallel import mesh as mesh_mod  # noqa: E402
from deepspeed_tpu.serving import ServingEngine  # noqa: E402

B, MASK, VOCAB, WIDTH = 4, 127, 128, 96
CFG = dict(model_type="sdar_moe", hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
           num_experts=8, num_experts_per_tok=2, num_hidden_layers=2,
           vocab_size=VOCAB, max_position_embeddings=256, rms_norm_eps=1e-6,
           rope_theta=1e6, tie_word_embeddings=False, norm_topk_prob=True,
           assumed=dict(block_length=B, mask_token_id=MASK,
                        denoising_steps=2))


@pytest.fixture(scope="module")
def tiny():
    mesh_mod.reset_topology()
    model = harness.find("architectures", "sdar_moe").build(CFG, 2)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, 3, jnp.float32, 2)


def engine_of(tiny, **kw):
    model, params = tiny
    cfg = dict(token_budget=32, max_seqs=4, kv_block_size=8, n_kv_blocks=64,
               max_context=128, dtype=jnp.float32)
    cfg.update(kw)
    return RaggedInferenceEngine(model, RaggedConfig(**cfg), params=params)


def prompt(seed, n):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, MASK, n)]


def reference_logits(params, states):
    """The reference's logits at the last block of each state, a row a
    state (what follows the block is invisible to it: zeros)."""
    tokens = np.zeros((len(states), WIDTH), np.int32)
    for i, s in enumerate(states):
        tokens[i, :len(s)] = s
    rows = np.repeat(np.arange(len(states)), B)
    cols = np.concatenate([np.arange(len(s) - B, len(s)) for s in states])
    out = ref.logits_at(params, tokens, rows, cols, CFG, 2)
    return np.asarray(out).reshape(len(states), B, -1)


def decide(logits, state):
    """The rule on the host: the two masked positions of the block of
    highest confidence, ties to the lower position."""
    l = logits.astype(np.float64)
    l[:, MASK] = -np.inf                 # no position decides the mask id
    p = np.exp(l - l.max(-1, keepdims=True))
    conf = p.max(-1) / p.sum(-1)
    masked = [j for j in range(B) if state[len(state) - B + j] == MASK]
    return {j: int(np.argmax(l[j]))
            for j in sorted(masked, key=lambda j: (-conf[j], j))[:2]}


def reference_generate(params, stream, n_new):
    """A plain loop of full forwards over the whole sequence: no cache, so
    no commit; a block is open until no mask id is left in it."""
    toks, start = list(stream), len(stream)
    while len(toks) < start + n_new:
        toks += [MASK] * (B - len(toks) % B)
        while MASK in toks[-B:]:
            for j, t in decide(reference_logits(params, [toks])[0],
                               toks).items():
                toks[len(toks) - B + j] = t
    return toks[start:start + n_new]


def fed_state(engine, uid, stream):
    """The sequence as the next pass will see it."""
    seq = engine.seqs.get(uid)
    if seq is not None:
        return list(seq.tokens)
    return list(stream) + [MASK] * (B - len(stream) % B)


def run_passes(engine, streams, limits, passes=40):
    """Drives ``put`` in the logits form to the end; returns every denoise
    pass as (uid, state fed, logits [B, vocab]) and the spans' attributes."""
    for u, n in limits.items():
        engine.limit_stream(u, n)
    seen, out = [], []
    for _ in range(passes):
        live = [u for u in streams
                if u not in engine.seqs or engine.seqs[u].pending]
        if not live:
            return out, seen
        states = {u: fed_state(engine, u, streams[u]) for u in live}
        got = engine._put_logits(
            live, [streams[u] if u not in engine.seqs else [] for u in live])
        for u in live:
            assert engine.seqs[u].seen % B == 0   # never cut inside a block
        seen.append([(engine.seqs[u].seen, len(engine.seqs[u].tokens))
                     for u in live])
        out += [(u, states[u], got[i]) for i, u in enumerate(live)
                if not np.isnan(got[i, 0, 0])]
    raise AssertionError("the streams did not end")


def test_passes_match_the_reference_and_the_plain_loop(tiny):
    """A prompt of 4k + r tokens split over two ticks, three blocks and
    more, commits folded into the next block's first pass, two sequences
    at different stages in one tick: every denoise pass's logits against
    the reference fed the same state, and the final streams against the
    plain loop's."""
    engine = engine_of(tiny)
    streams = {1: prompt(1, 6), 2: prompt(2, 41), 3: prompt(3, 8)}
    n_new = {1: 13, 2: 9, 3: 12}
    limits = {u: len(p) + n_new[u] for u, p in streams.items()}
    passes, seen = run_passes(engine, streams, limits)
    # the 41-token prompt did not fit the first tick's budget beside the
    # others: its first pass ended inside the prompt, at a whole block
    assert seen[0][1][0] in range(4, 40, 4)
    want = reference_logits(tiny[1], [s for _, s, _ in passes])
    got = np.stack([l for _, _, l in passes])
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 2e-4, err.max()
    # two passes a block of four masks, one where a prompt's tail left two
    assert len([1 for u, _, _ in passes if u == 3]) == 2 * 3
    for u, p in streams.items():
        final = engine.seqs[u].tokens
        assert engine.seqs[u].seen == len(final) and MASK not in final
        assert final[len(p):len(p) + n_new[u]] == \
            reference_generate(tiny[1], p, n_new[u])
    engine.flush(list(streams))
    assert_block_balance(engine)


def test_generate_cuts_the_last_block_and_reuses_a_slot(tiny):
    """``max_new_tokens`` not a multiple of four; a slot taken again after
    its sequence retired gives the same tokens as a fresh engine."""
    engine = engine_of(tiny, max_seqs=1)
    p, q = prompt(4, 10), prompt(5, 7)
    first = engine.generate({7: p}, max_new_tokens=7)[7]
    assert first == reference_generate(tiny[1], p, 7)
    again = engine.generate({8: q}, max_new_tokens=6)[8]      # the same slot
    assert again == reference_generate(tiny[1], q, 6)
    assert engine.generate({9: p}, max_new_tokens=7)[9] == first
    assert_block_balance(engine)


def test_preempt_and_resume_inside_a_block(tiny):
    """Preempted with a block half decided: the stream handed back ends at
    the last committed block, and the resumed sequence ends as the one
    never preempted."""
    p = prompt(6, 9)
    want = reference_generate(tiny[1], p, 12)
    engine = engine_of(tiny)
    engine.limit_stream(1, len(p) + 12)
    engine.put([1], [p])
    for _ in range(2):
        engine.put([1], [[]])
    seq = engine.seqs[1]
    assert 0 < len(engine._masked(seq)) < B           # inside a block
    kept = engine.preempt(1)
    assert len(kept) % B == 0 and len(kept) >= len(p) - len(p) % B
    assert kept[:len(p)] == p[:len(kept)] and MASK not in kept
    resumed = kept if len(kept) > len(p) else p
    out = engine.generate({1: resumed},
                          max_new_tokens=len(p) + 12 - len(resumed))[1]
    assert (resumed + out)[len(p):] == want
    assert_block_balance(engine)


def serve(engine, requests, cfg=None):
    srv = ServingEngine(engine, dict({"policy": "slo"}, **(cfg or {})),
                        start=False)
    got = {i: [] for i in range(len(requests))}
    sizes = {i: [] for i in range(len(requests))}
    reqs = []
    for i, (p, n) in enumerate(requests):
        reqs.append(srv.submit(p, max_new_tokens=n,
                               on_token=lambda t, i=i: got[i].append(t)))
    for _ in range(400):
        if all(r.is_terminal for r in reqs):
            break
        before = {i: len(g) for i, g in got.items()}
        srv._tick()
        for i, g in got.items():
            if len(g) > before[i]:
                sizes[i].append(len(g) - before[i])
    else:
        raise AssertionError("the requests did not finish")
    srv.close()
    return reqs, got, sizes


def test_the_server_yields_blocks_and_counts_tokens(tiny):
    """A tick yields a request no token or a block's: the first block the
    ``4 - r`` tokens after the prompt's tail, the last cut at
    ``max_new_tokens``; the streams are the plain loop's."""
    engine = engine_of(tiny)
    requests = [(prompt(10, 6), 9), (prompt(11, 41), 5), (prompt(12, 8), 8)]
    reqs, got, sizes = serve(engine, requests)
    for i, (p, n) in enumerate(requests):
        assert got[i] == reference_generate(tiny[1], p, n), i
        assert reqs[i].tokens == got[i] and len(got[i]) == n
    assert sizes[0] == [2, 4, 3]          # r = 2; the last block cut at 9
    assert sizes[1] == [3, 2]             # r = 1; cut at 5
    assert sizes[2] == [4, 4]
    assert not engine.seqs
    assert_block_balance(engine)


def test_page_reservation_covers_the_last_whole_block(tiny):
    """A pool that holds exactly the pages ``prompt + max_new_tokens`` is
    charged serves the request whose last block runs past that length."""
    engine = engine_of(tiny, n_kv_blocks=3, max_seqs=1)
    p = prompt(13, 9)                     # 9 + 5 = 14 tokens: 2 pages + 1
    assert engine.blocks_needed(14) == 3
    reqs, got, _ = serve(engine, [(p, 5)])
    assert got[0] == reference_generate(tiny[1], p, 5)


def test_span_attributes_count_blocks(tiny, tmp_path):
    from deepspeed_tpu.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    tel = Telemetry(TelemetryConfig(enabled=True, output_dir=str(tmp_path),
                                    jsonl_path="", stall_detection=False))
    set_telemetry(tel)
    try:
        engine = engine_of(tiny)
        seen = []
        attrs = engine._sched_attrs
        engine._sched_attrs = lambda *a: seen.append(attrs(*a)) or seen[-1]
        out = engine.generate({1: prompt(14, 8)}, max_new_tokens=8)[1]
        reg = tel.registry
        assert reg.counter("inference/tokens_decided").value == len(out) == 8
        assert reg.counter("inference/blocks_committed").value == 2
        assert reg.counter("inference/denoise_passes").value == 4
        assert reg.gauge("inference/block_length").value == B
    finally:
        set_telemetry(None)
    # prefill 8 + first pass; a second pass; commit folded into the second
    # block's first pass; its second pass; the last block's own commit
    assert [a["lanes"] for a in seen] == [32] * 5    # the budget
    assert [(a["prefill"], a["decode"], a["block_seqs"], a["decided"],
             a["commits"]) for a in seen] == [
        (8, 4, 1, 0, 0), (0, 4, 1, 2, 0), (0, 8, 1, 2, 1), (0, 4, 1, 2, 0),
        (0, 4, 0, 2, 1)]
    assert all(a["block"] == B for a in seen)


@pytest.mark.parametrize("what,call", [
    ("put_spec", lambda e: e.put_spec([1], [[]], [[3]])),
    ("trim", lambda e: e.trim(1, 4)),
    ("export_kv", lambda e: e.export_kv(1)),
    ("the KV tier", lambda e: e.enable_kv_tier(member="a")),
    ("decode_steps", lambda e: e.decode_steps({1: 3}, 2)),
    ("generate_speculative", lambda e: e.generate_speculative({2: [1, 2]})),
    ("stream", lambda e: next(e.stream(2, [1, 2, 3]))),
])
def test_refused_by_message(tiny, what, call):
    engine = engine_of(tiny)
    engine.put([1], [prompt(15, 8)])
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        call(engine)


def test_refused_at_construction(tiny):
    with pytest.raises(NotImplementedError, match="enable_prefix_cache"):
        engine_of(tiny, enable_prefix_cache=True)
    with pytest.raises(ValueError, match="multiple of the model's attn_block"):
        engine_of(tiny, kv_block_size=2, max_context=128)
    engine = engine_of(tiny)
    engine.put([1], [prompt(16, 5)])
    with pytest.raises(ValueError, match="decides its own tokens"):
        engine.put([1], [[3]])
    with pytest.raises(ValueError, match="speculative"):
        ServingEngine(engine, {"speculative": True}, start=False)


def test_the_pallas_kernel_path_gives_the_same_stream(tiny, monkeypatch):
    """The step with the paged kernel (interpret mode) in the gather
    path's place: the same tokens, block by block."""
    p = prompt(17, 13)
    want = engine_of(tiny).generate({1: p}, max_new_tokens=10)[1]
    monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    engine = engine_of(tiny)
    assert engine.attention_path == "pallas_interpret"
    assert engine.generate({1: p}, max_new_tokens=10)[1] == want


def test_the_expert_kernel_gives_the_same_decisions(tiny, monkeypatch):
    """On the kernel path the experts' products are the Pallas grouped
    matmul's (8 experts, 2 a token, 32 lanes: 8 rows an expert): every
    denoise pass's logits are the ``gather`` path's, so every decision is,
    and the span says which product the program holds."""
    streams, limits = {1: prompt(18, 11), 2: prompt(19, 6)}, {1: 20, 2: 18}
    want, _ = run_passes(engine_of(tiny), streams, limits)
    monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    engine = engine_of(tiny)
    assert engine._expert_product(32) == "kernel"
    assert engine._sched_attrs([], 32, 1)["expert_kernel"] == 1
    got, _ = run_passes(engine, streams, limits)
    assert len(got) == len(want) > 8
    for (u, state, logits), (u0, state0, logits0) in zip(got, want):
        assert (u, state) == (u0, state0)       # the same decisions so far
        np.testing.assert_allclose(logits, logits0, rtol=2e-4, atol=2e-5)
        assert decide(logits, state) == decide(logits0, state0)


def test_the_rule_on_the_device():
    """``decide_masked`` against hand-made logits: the two masked positions
    of highest confidence, a tie to the lower one, never a position that
    is not masked, and never the mask id itself."""
    from deepspeed_tpu.inference.sampling import decide_masked

    logits = np.full((2, 4, 8), -4.0, np.float32)
    for j, (tok, top) in enumerate([(1, 3.0), (2, 5.0), (3, 5.0), (4, 9.0)]):
        logits[0, j, tok] = top
    masked = np.array([[True, True, True, False], [True, False, False, True]])
    # row 1: position 0 would decide the mask id (7): its next best is 5
    logits[1, 0, 7], logits[1, 0, 5], logits[1, 3, 6] = 9.0, 2.0, 1.0
    got = np.asarray(decide_masked(jnp.asarray(logits), jnp.asarray(masked),
                                   2, 7))
    assert got.tolist() == [[-1, 2, 3, -1], [5, -1, -1, 6]]
    one = np.asarray(decide_masked(jnp.asarray(logits), jnp.asarray(masked),
                                   1, 7))
    assert one.tolist() == [[-1, 2, -1, -1], [5, -1, -1, -1]]


def test_configuration_is_checked():
    from deepspeed_tpu.models.moe import MoETransformerConfig

    kw = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
              mask_token_id=63, denoise_tokens=2)
    assert MoETransformerConfig(attn_block=4, **kw).head_dim == 16
    assert MoETransformerConfig(attn_block=4, head_size=32, **kw).head_dim == 32
    with pytest.raises(ValueError, match="power of two"):
        MoETransformerConfig(attn_block=3, **kw)
    with pytest.raises(ValueError, match="block diffusion"):
        MoETransformerConfig(attn_block=4, **dict(kw, mask_token_id=-1))
    with pytest.raises(ValueError, match="block diffusion"):
        MoETransformerConfig(attn_block=4, **dict(kw, denoise_tokens=5))
    with pytest.raises(ValueError, match="block diffusion"):
        MoETransformerConfig(attn_block=4, attn_windows=(8,), **kw)
    with pytest.raises(ValueError, match="qk_norm_heads"):
        MoETransformerConfig(qk_norm_heads=True, **kw)


def test_a_request_preempted_by_the_server_resumes_its_stream(tiny):
    """The server's eviction between two ticks, a block half decided: the
    request re-queues with the tokens delivered so far (whole blocks), is
    re-prefilled, and ends with the stream of one never preempted."""
    engine = engine_of(tiny)
    srv = ServingEngine(engine, {"policy": "slo"}, start=False)
    p = prompt(18, 11)
    got = []
    req = srv.submit(p, max_new_tokens=14, on_token=got.append)
    for _ in range(4):
        srv._tick()
    assert 0 < len(got) < 14 and engine._masked(engine.seqs[req.uid])
    with srv._lock:
        srv._preempt(req)
    assert not engine.seqs and req.preemptions == 1
    for _ in range(200):
        if req.is_terminal:
            break
        srv._tick()
    srv.close()
    assert got == req.tokens == reference_generate(tiny[1], p, 14)
    assert_block_balance(engine)
