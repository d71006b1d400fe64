"""Olmo-Hybrid at a small size on the CPU: the gated delta rule's chunked and
ragged forms against its token recurrence; the dense forward and the ragged
engine (a prompt split over ticks, preempt and resume, ``decode_steps``, a
slot reused) against the plain reference ``benchmarks/reference/
olmo_hybrid.py`` on seeded float32 weights; three controls that the same
comparison must fail; what a model with recurrent layers refuses; and the
``olmo_hybrid`` translation of ``checkpoint/hf.py``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import olmo_hybrid as ref
from deepspeed_tpu.checkpoint import hf
from deepspeed_tpu.inference.kv_cache import (
    kv_blocks_for_bytes,
    kv_page_bytes,
    state_pool_bytes,
)
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models.transformer import Transformer
from deepspeed_tpu.ops import gated_delta as gd

SEED = 3_000_000_019
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
HC = {"model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 128, "num_hidden_layers": 4,
      "num_attention_heads": 4, "num_key_value_heads": 2,
      "max_position_embeddings": 512, "attention_bias": False,
      "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
      "layer_types": PERIOD * 2, "linear_num_key_heads": 4,
      "linear_num_value_heads": 4, "linear_key_head_dim": 8,
      "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
      "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
N_LAYERS = 4


# ----------------------------------------------------------------------
# the recurrence: chunked and ragged forms against the token scan
H, DK, DV = 3, 8, 16


def _lanes(key, n):
    ks = jax.random.split(key, 6)
    q = gd.l2norm(jax.random.normal(ks[0], (n, H, DK))) * DK ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (n, H, DK)))
    v = jax.random.normal(ks[2], (n, H, DV))
    g = -jnp.exp(0.3 * jax.random.normal(ks[3], (n, H))) \
        * jax.nn.softplus(jax.random.normal(ks[4], (n, H)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (n, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("n", [1, 63, 64, 150])
def test_chunked_form_is_the_token_recurrence(n):
    x = _lanes(jax.random.PRNGKey(n), n)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (H, DK, DV))
    o1, s1 = gd.delta_recurrent(*x, s0)
    o2, s2 = jax.jit(gd.delta_chunked)(*x, s0)
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)


# (run length, slot, first position): position 0 starts from zeros, any
# other from the slot's state
RAGGED = {
    "mixed": [(1, 4, 10), (1, 0, 0), (1, 2, 3), (70, 5, 0), (5, 1, 7),
              (130, 3, 64)],
    "decode_only": [(1, s, 5 + s) for s in (3, 0, 5, 1)],
    "one_long_run_from_a_state": [(200, 2, 17)],
    "runs_that_fill_every_lane": [(64, 0, 0), (128, 1, 9), (64, 2, 1)],
    "no_live_lane": [],
}


@pytest.mark.parametrize("case", list(RAGGED))
def test_ragged_lanes_against_the_token_recurrence(case):
    S, T, K, ch = 6, 256, 4, 5
    state = jax.random.normal(jax.random.PRNGKey(7), (S + 1, H, DK, DV))
    rows = jax.random.normal(jax.random.PRNGKey(13), (S + 1, K - 1, ch))
    xs = _lanes(jax.random.PRNGKey(9), T)
    xc = jax.random.normal(jax.random.PRNGKey(11), (T, ch))
    w = jax.random.normal(jax.random.PRNGKey(12), (K, ch))
    slots = np.full(T, -1, np.int32)
    positions = np.zeros(T, np.int32)
    want_o = np.zeros((T, H, DV), np.float32)
    want_y = np.zeros((T, ch), np.float32)
    want_s, want_r = np.array(state), np.array(rows)
    t = 0
    for n, sl, p0 in RAGGED[case]:
        slots[t:t + n], positions[t:t + n] = sl, np.arange(p0, p0 + n)
        st = jnp.zeros((H, DK, DV)) if p0 == 0 else state[sl]
        want_o[t:t + n], want_s[sl] = gd.delta_recurrent(
            *(a[t:t + n] for a in xs), st)
        past = np.zeros((K - 1, ch)) if p0 == 0 else np.asarray(rows[sl])
        full = np.concatenate([past, np.asarray(xc[t:t + n])])
        for i in range(n):
            want_y[t + i] = sum(np.asarray(w)[j] * full[i + j]
                                for j in range(K))
        want_r[sl] = full[-(K - 1):]
        t += n
    runs = gd.runs_of(jnp.asarray(slots), jnp.asarray(positions), S)
    o, st = jax.jit(gd.delta_ragged)(*xs, state, runs)
    y, r = jax.jit(gd.conv_ragged)(xc, w, rows, runs)
    np.testing.assert_allclose(np.asarray(o)[:t], want_o[:t], atol=3e-5)
    np.testing.assert_allclose(st, want_s, atol=3e-5)   # the sink's too
    np.testing.assert_allclose(np.asarray(y)[:t], want_y[:t], atol=1e-5)
    np.testing.assert_allclose(r, want_r, atol=1e-6)


# ----------------------------------------------------------------------
# the step kernel (interpret mode) against ``delta_step`` on the same rows
@pytest.mark.parametrize("n", [1, 20, 64])
def test_step_kernel_against_delta_step(n):
    """``n`` runs of one lane among 65 slots (every third starts a
    sequence), one run of five lanes and, where slots are left, slots with
    no run: the kernel gives each single-lane slot ``delta_step``'s output
    and state (from zeros where the run starts a sequence), and leaves
    every other slot's bits as they were: the idle slots, the sink, and the
    slot whose run is longer, which the chunk loop serves behind it."""
    from deepspeed_tpu.ops.pallas.gated_delta import delta_step_slots

    S, T = 65, 128
    rng = np.random.default_rng(n)
    order = rng.permutation(S)
    single, long_slot = order[:n], order[n]
    slots = np.full(T, -1, np.int32)
    positions = np.zeros(T, np.int32)
    at = n // 2                     # the long run sits between single lanes
    lane_of = {}
    t = 0
    for i, sl in enumerate(single):
        if i == at:
            slots[t:t + 5], positions[t:t + 5] = long_slot, np.arange(9, 14)
            t += 5
        slots[t], positions[t] = sl, 0 if i % 3 == 0 else 3 + i
        lane_of[sl] = t
        t += 1
    state = jax.random.normal(jax.random.PRNGKey(7), (S + 1, H, DK, DV))
    xs = _lanes(jax.random.PRNGKey(9), T)
    runs = gd.runs_of(jnp.asarray(slots), jnp.asarray(positions), S)
    steps = np.asarray(runs.steps)
    assert steps[3].tolist() == [n] * min(S, T)
    assert steps[0, :n].tolist() == sorted(single.tolist())
    assert steps[1, :n].tolist() == [lane_of[sl] for sl in steps[0, :n]]
    assert steps[2, :n].tolist() == [int(positions[l] == 0)
                                     for l in steps[1, :n]]
    assert set(steps[0, n:].tolist()) <= {steps[0, n - 1]}
    rows = [a[steps[1]] for a in xs]
    o, st = delta_step_slots(*rows, state, runs.steps, interpret=True)
    old = jnp.where(jnp.asarray(steps[2, :n] > 0)[:, None, None, None], 0.0,
                    state[steps[0, :n]])
    want_o, want_s = gd.delta_step(*(a[:n] for a in rows), old)
    np.testing.assert_allclose(np.asarray(o)[:n], want_o, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st)[steps[0, :n]], want_s,
                               atol=1e-5)
    untouched = np.setdiff1d(np.arange(S + 1), single)
    assert long_slot in untouched and S in untouched
    assert np.array_equal(np.asarray(st)[untouched],
                          np.asarray(state)[untouched])
    # behind it the chunk loop serves the longer run, as on the XLA path
    o1, s1 = jax.jit(gd.delta_ragged, static_argnums=7)(
        *xs, state, runs, "gather")
    o2, s2 = jax.jit(gd.delta_ragged, static_argnums=7)(
        *xs, state, runs, "pallas_interpret")
    np.testing.assert_allclose(np.asarray(o2)[:t], np.asarray(o1)[:t],
                               atol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    assert not np.array_equal(np.asarray(s2)[long_slot],
                              np.asarray(state)[long_slot])


def test_step_kernel_with_no_single_lane_run_changes_nothing():
    """An empty batch (the runner's warm-up) and a batch of one long run:
    every entry names the sink, which comes back bit for bit."""
    from deepspeed_tpu.ops.pallas.gated_delta import delta_step_slots

    S, T = 6, 64
    state = jax.random.normal(jax.random.PRNGKey(7), (S + 1, H, DK, DV))
    xs = _lanes(jax.random.PRNGKey(9), T)
    for live in (0, 40):
        slots = np.where(np.arange(T) < live, 2, -1).astype(np.int32)
        runs = gd.runs_of(jnp.asarray(slots), jnp.arange(T, dtype=jnp.int32),
                          S)
        assert np.asarray(runs.steps)[[0, 3]].tolist() == [[S] * S, [0] * S]
        _, st = delta_step_slots(*(a[runs.steps[1]] for a in xs), state,
                                 runs.steps, interpret=True)
        assert np.array_equal(np.asarray(st), np.asarray(state))


# ----------------------------------------------------------------------
# the model against the plain reference
@pytest.fixture(scope="module")
def built():
    c = hf.olmo_hybrid_config(HC, N_LAYERS)
    c.remat, c.use_flash = False, False
    model = Transformer(c)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, SEED, jnp.float32, N_LAYERS)


def _reference(params, fed, cols_of):
    """Reference logits of sequence i at the positions ``cols_of[i]``."""
    width = max(map(len, fed))
    tokens = np.zeros((len(fed), width), np.int32)
    for i, f in enumerate(fed):
        tokens[i, :len(f)] = f
    rows = np.concatenate([[i] * len(c) for i, c in enumerate(cols_of)])
    return np.asarray(ref.logits_at(
        params, jnp.asarray(tokens), jnp.asarray(rows),
        jnp.asarray(np.concatenate(cols_of)), HC, N_LAYERS))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _dense_error(model, params):
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0,
                                        256))
    got = jax.jit(model.apply)(params, jnp.asarray(tok))
    want = _reference(params, tok.tolist(), [np.arange(100)] * 2)
    return _rel(got.reshape(200, -1), want).max()


def test_dense_forward_agrees_with_the_reference(built):
    assert _dense_error(*built) < 1e-4


CONTROLS = {
    # each drops one thing from the PROGRAM; the reference keeps it
    "beta_without_its_2": lambda mp, c: setattr(c, "linear_neg_eigval", False),
    "no_l2_norm": lambda mp, c: mp.setattr(gd, "l2norm",
                                           lambda x, eps=1e-6: x),
    "no_decay": lambda mp, c: mp.setattr(
        gd, "_project", lambda x, lp, c, f=gd._project:
        (lambda qkv, g, b, z: (qkv, 0.0 * g, b, z))(*f(x, lp, c))),
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_control_fails_the_same_comparison(built, monkeypatch, control):
    _, params = built
    c = hf.olmo_hybrid_config(HC, N_LAYERS)
    c.remat, c.use_flash = False, False
    CONTROLS[control](monkeypatch, c)
    assert _dense_error(Transformer(c), params) > 1e-2


def _engine(built, **kw):
    model, params = built
    cfg = dict(token_budget=128, max_seqs=4, kv_block_size=16,
               n_kv_blocks=64, max_context=256, dtype=jnp.float32)
    cfg.update(kw)
    return RaggedInferenceEngine(model, RaggedConfig(**cfg), params=params)


def _prefill(eng, uids, prompts):
    """put() until every prompt is in; returns (rows, puts made)."""
    rows, puts = eng.put(uids, prompts), 1
    while np.isnan(rows[:, 0]).any():
        todo = [i for i in range(len(uids)) if np.isnan(rows[i, 0])]
        rows[todo] = eng.put([uids[i] for i in todo], [[] for _ in todo])
        puts += 1
    return rows, puts


def _prompts(*lens):
    rng = np.random.default_rng(0)
    return [rng.integers(1, 256, (n,)).tolist() for n in lens]


def _split_prompt(built):
    """175 prompt tokens against a budget of 128: one prompt's state
    crosses a tick; then 4 decode steps through both caches."""
    eng, uids, prompts = _engine(built), [1, 2, 3], _prompts(100, 70, 5)
    rows, puts = _prefill(eng, uids, prompts)
    assert puts == 2
    fed, got = [list(p) for p in prompts], [rows]
    for _ in range(4):
        nxt = np.argmax(got[-1], -1)
        for f, t in zip(fed, nxt):
            f.append(int(t))
        got.append(eng.put(uids, [[int(t)] for t in nxt]))
    return fed, np.stack(got, 1), [np.arange(len(p) - 1, len(p) + 4)
                                   for p in prompts]


def _preempt_and_resume(built):
    """Decode, preempt (slot and pages freed), resume by re-prefilling the
    tokens the engine gave back, decode on: the state is rebuilt."""
    eng, (a, b) = _engine(built), _prompts(40, 33)
    rows, _ = _prefill(eng, [1, 2], [a, b])
    fed = [list(a), list(b)]
    for _ in range(3):
        nxt = np.argmax(rows, -1)
        for f, t in zip(fed, nxt):
            f.append(int(t))
        rows = eng.put([1, 2], [[int(t)] for t in nxt])
    nxt = int(np.argmax(rows[0]))
    kept = eng.preempt(1)
    assert kept == fed[0] and 1 not in eng.seqs
    eng.put([2], [[int(np.argmax(rows[1]))]])   # the other decodes meanwhile
    fed[0].append(nxt)
    again, _ = _prefill(eng, [1], [fed[0]])
    return [fed[0]], again[None], [np.array([len(fed[0]) - 1])]


def _decode_steps(built):
    """``decode_steps`` shares the core: 6 greedy tokens in one call are
    the reference's own argmax continuation."""
    eng, prompts = _engine(built), _prompts(30, 21)
    rows, _ = _prefill(eng, [1, 2], prompts)
    first = {u: int(np.argmax(r)) for u, r in zip([1, 2], rows)}
    chains = eng.decode_steps(first, 6)
    fed = [p + [first[u]] + chains[u][:-1]
           for u, p in zip([1, 2], prompts)]
    cols = [np.arange(len(p), len(p) + 6) for p in prompts]
    want = _reference(built[1], fed, cols).reshape(2, 6, -1)
    assert [np.argmax(w, -1).tolist() for w in want] \
        == [chains[1], chains[2]]
    more = eng.put([1, 2], [[chains[1][-1]], [chains[2][-1]]])
    fed = [f + [chains[u][-1]] for f, u in zip(fed, [1, 2])]
    return fed, more[:, None], [np.array([len(f) - 1]) for f in fed]


def _slot_reused(built):
    """One slot: a second sequence takes it after the first is flushed and
    starts from zeros, not from what the first left."""
    eng, (a, b) = _engine(built, max_seqs=1), _prompts(50, 20)
    _prefill(eng, [1], [a])
    eng.flush([1])
    rows, _ = _prefill(eng, [2], [b])
    assert eng.seqs[2].slot == 0
    nxt = int(np.argmax(rows[0]))
    return [b + [nxt]], np.stack([rows, eng.put([2], [[nxt]])], 1), \
        [np.array([len(b) - 1, len(b)])]


SCENARIOS = {"split_prompt": _split_prompt,
             "preempt_and_resume": _preempt_and_resume,
             "decode_steps": _decode_steps, "slot_reused": _slot_reused}


@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_ragged_engine_agrees_with_the_reference(built, scenario):
    fed, got, cols = SCENARIOS[scenario](built)
    want = _reference(built[1], fed, cols)
    err = _rel(np.concatenate([g.reshape(len(c), -1)
                               for g, c in zip(got, cols)]), want)
    assert err.max() < 1e-4, err


# ----------------------------------------------------------------------
# the engine as the TPU runs it (kernels in interpret mode) against the
# engine as the CPU runs it
@pytest.fixture(scope="module")
def two_paths(built):
    """{path: (tokens fed, logits [3, 9, vocab], ``ragged.put``'s
    attributes a tick, ``inference/state_slots_stepped``'s rise)}: 175
    prompt tokens against a budget of 128, so a prompt's state crosses a
    tick, then eight decode steps, then four in one ``decode_steps``."""
    from deepspeed_tpu.config import TelemetryConfig
    from deepspeed_tpu.inference import ragged as ragged_mod
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    seen = []

    class Span:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **attrs):
            seen.append(attrs)

    def drive(eng):
        uids, prompts = [1, 2, 3], _prompts(100, 70, 5)
        rows, puts = _prefill(eng, uids, prompts)
        assert puts == 2
        fed, got = [list(p) for p in prompts], [rows]
        for _ in range(8):
            nxt = np.argmax(got[-1], -1)
            for f, t in zip(fed, nxt):
                f.append(int(t))
            got.append(eng.put(uids, [[int(t)] for t in nxt]))
        # and ``decode_steps``, which scans the same core: four more
        chains = eng.decode_steps(
            {u: int(t) for u, t in zip(uids, np.argmax(got[-1], -1))}, 4)
        return [f + chains[u] for f, u in zip(fed, uids)], np.stack(got, 1)

    out = {}
    real = ragged_mod.annotate
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ragged_mod, "annotate",
                   lambda name, **attrs: Span() if name == "ragged.put"
                   else real(name, **attrs))
        tel = Telemetry(TelemetryConfig(enabled=True, output_dir="",
                                        jsonl_path="", stall_detection=False))
        set_telemetry(tel)
        try:
            counter = tel.registry.counter("inference/state_slots_stepped")
            for path in ("gather", "pallas_interpret"):
                if path != "gather":
                    mp.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
                eng = _engine(built)
                assert eng.attention_path == path
                before = counter.value
                fed, got = drive(eng)
                out[path] = (fed, got, list(seen), counter.value - before)
                del seen[:]
        finally:
            set_telemetry(None)
    return out


def test_kernel_engine_decodes_what_the_gather_engine_decodes(two_paths):
    """Same tokens, and logits equal to float32 reduction order, at the
    prompts' last positions and over eight decode steps; the same four
    tokens more from ``decode_steps`` (the kernel under its scan)."""
    (fed_a, got_a, _, _), (fed_b, got_b, _, _) = \
        two_paths["gather"], two_paths["pallas_interpret"]
    assert fed_a == fed_b
    assert np.isfinite(got_b).all()
    assert _rel(got_b.reshape(27, -1), got_a.reshape(27, -1)).max() < 1e-5


def test_put_counts_the_slots_the_step_kernel_serves(two_paths):
    """``step_slots``: the schedule's entries of one lane, which the kernel
    serves in each linear layer; 0 where the step runs in XLA. The
    registry's ``inference/state_slots_stepped`` sums them x the 3 linear
    layers."""
    *_, attrs, stepped = two_paths["gather"]
    assert [a["step_slots"] for a in attrs] == [0] * 10 and stepped == 0
    *_, attrs, stepped = two_paths["pallas_interpret"]
    # tick 1: the 5- and 70-token prompts whole and 53 tokens of the third;
    # tick 2: its other 47; then three decode lanes a tick
    assert [a["step_slots"] for a in attrs] == [0, 0] + [3] * 8
    assert [a["decode"] for a in attrs] == [0, 0] + [3] * 8
    assert {a["state_slots"] for a in attrs} == {3}
    assert stepped == 3 * 8 * 3


# ----------------------------------------------------------------------
# what cannot be done without a snapshot of the state fails loudly
def _live(built):
    eng = _engine(built)
    _prefill(eng, [1], _prompts(20))
    return eng


REFUSED = {
    "enable_prefix_cache": lambda b: _engine(b, enable_prefix_cache=True),
    "put_spec": lambda b: _live(b).put_spec([1], [[5]], [[6, 7]]),
    "trim": lambda b: _live(b).trim(1, 10),
    "export_kv": lambda b: _live(b).export_kv(1),
    "import_kv": lambda b: _engine(b).import_kv(9, None),
    "kv_tier": lambda b: _engine(b).enable_kv_tier(member="a"),
    "generate_speculative": lambda b: _engine(b).generate_speculative(
        {1: _prompts(20)[0]}, max_new_tokens=4),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_recurrent_model_refuses_what_needs_a_state_snapshot(built, what):
    with pytest.raises(NotImplementedError, match="state snapshot"):
        REFUSED[what](built)


def test_dense_kv_cache_refuses_a_recurrent_model(built):
    model, params = built
    cache = jnp.zeros((N_LAYERS, 1, 8, 2, 16))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32),
                    kv_caches=(cache, cache), cache_pos=0)


def test_pool_sizing_counts_each_kind_of_layer_once(built):
    """A page is charged for the layers that hold pages; the state pool,
    a fixed cost of the slots, comes out of the byte budget first."""
    model, _ = built
    c, cfg = model.config, RaggedConfig(max_seqs=4, kv_block_size=16,
                                        dtype=jnp.bfloat16)
    assert kv_page_bytes(c, cfg) == 2 * 1 * 2 * 16 * 16 * 2
    fixed = state_pool_bytes(c, cfg)
    assert fixed == 3 * 5 * (4 * 4 * 8 * 16 + 2 * 3 * (2 * 32 + 64))
    assert kv_blocks_for_bytes(fixed + 10 * kv_page_bytes(c, cfg), c, cfg) == 10
    eng = _engine(built)
    assert [a.shape for a in eng.kv_pool[-2]] == [(5, 4, 8, 16)] * 3
    assert [a.shape for a in eng.kv_pool[-1]] == [(5, 3, 128)] * 3
    assert len(eng.kv_pool[0]) == len(eng.kv_pool[1]) == 1


# ----------------------------------------------------------------------
# checkpoint/hf.py
def test_hf_config_reads_the_published_config(tmp_path):
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Olmo-Hybrid-7B"' in l) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    hc = row["config"] if row else dict(HC, layer_types=PERIOD * 8,
                                        num_hidden_layers=32)
    (tmp_path / "config.json").write_text(json.dumps(hc))
    family, c = hf.hf_config(str(tmp_path))
    assert family == "olmo_hybrid" and c.n_layers == 32
    assert c.layer_types == ("linear", "linear", "linear", "full") * 8
    assert c.branch_norm and c.qk_norm and c.position == "none"
    if row:   # 7.43 B parameters, as ISSUE 28 counts them
        assert round(c.param_count() / 1e9, 2) == 7.43
        assert (c.linear_n_k_heads, c.linear_k_dim, c.linear_v_dim,
                c.linear_conv_kernel, c.linear_neg_eigval) == (30, 96, 192, 4,
                                                               True)


def test_hf_state_maps_onto_the_models_tree(built):
    """A state dict under the assumed tensor names (OLMo-2's and FLA's)
    maps onto the tree ``init`` builds, values in the right places."""
    model, params = built
    c = model.config
    L, A = "model.layers.{}.", "linear_attn."
    lay = jax.tree_util.tree_map(np.asarray, params["layers"])
    state = {"model.embed_tokens.weight": np.asarray(params["tok_embed"]),
             "model.norm.weight": np.asarray(params["final_norm_w"]),
             "lm_head.weight": np.asarray(params["lm_head"]).T}
    names = {"attn_norm_w": "post_attention_layernorm.weight",
             "mlp_norm_w": "post_feedforward_layernorm.weight"}
    for li in range(c.n_layers):
        for k, n in names.items():
            state[L.format(li) + n] = lay[k][li]
        for k, n in (("w_gate", "gate"), ("w_up", "up"), ("w_down", "down")):
            state[L.format(li) + f"mlp.{n}_proj.weight"] = lay[k][li].T
    for at, li in enumerate(c.layers_of("full")):
        for x in "qkvo":
            state[L.format(li) + f"self_attn.{x}_proj.weight"] = \
                lay["full"]["w" + x][at].T
        for x in "qk":
            state[L.format(li) + f"self_attn.{x}_norm.weight"] = \
                lay["full"][x + "_norm_w"][at]
    lin = lay["linear"]
    cut = np.cumsum([0, 32, 32, 64])
    for at, li in enumerate(c.layers_of("linear")):
        p = L.format(li) + A
        for x, k in (("q", "wq"), ("k", "wk"), ("v", "wv"), ("o", "wo"),
                     ("a", "w_a"), ("b", "w_beta"), ("g", "w_z")):
            state[p + f"{x}_proj.weight"] = lin[k][at].T
        for i, x in enumerate("qkv"):     # [channels, 1, K] a stream
            state[p + f"{x}_conv1d.weight"] = \
                lin["conv_w"][at][:, cut[i]:cut[i + 1]].T[:, None, :]
        state[p + "A_log"], state[p + "dt_bias"] = \
            lin["A_log"][at], lin["dt_bias"][at]
        state[p + "o_norm.weight"] = lin["o_norm_w"][at]
    mapped = hf.map_hf_params(state, "olmo_hybrid", c)
    jax.tree_util.tree_map(np.testing.assert_array_equal, mapped,
                           jax.tree_util.tree_map(np.asarray, params))
