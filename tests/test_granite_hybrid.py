"""Granite-4.0-H at a small size on the CPU: the program against
``transformers.GraniteMoeHybridForCausalLM`` through ``checkpoint/hf.py``
(every scalar multiplier off 1, a convolution bias that is not zero);
Mamba-2's chunked, stepped and ragged forms against its token recurrence from
a state that is not zero; the step kernel ``ops/pallas/mamba2.py`` in
interpret mode against ``ssd_step`` (live counts, a rolled leaf's later
period, groups, heads in blocks; every slot that does not decode bit for
bit); the dense forward and the ragged engine (a prompt
split over ticks, decode, a slot reused, preempt and resume, two kinds of run
in one tick) against the plain reference ``benchmarks/reference/
granite_hybrid.py`` on seeded float32 weights; the pools at the cell's widths
by shapes alone; the new span attribute and gauge; and what is refused, by
message."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import granite_hybrid as ref
from deepspeed_tpu.checkpoint import hf
from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models.transformer import Transformer
from deepspeed_tpu.ops import gated_delta as gd
from deepspeed_tpu.ops import mamba2

SEED = 4_300_000_043
HC = {"model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "shared_intermediate_size": 96,
      "num_hidden_layers": 6, "num_attention_heads": 4,
      "num_key_value_heads": 2, "attention_bias": False,
      "attention_multiplier": 0.07, "embedding_multiplier": 3.0,
      "residual_multiplier": 0.4, "logits_scaling": 2.5, "hidden_act": "silu",
      "layer_types": ["mamba", "mamba", "attention"] * 2,
      "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_d_conv": 4,
      "mamba_d_head": 16, "mamba_d_state": 8, "mamba_expand": 2,
      "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
      "max_position_embeddings": 512, "normalization_function": "rmsnorm",
      "num_experts_per_tok": 0, "num_local_experts": 0,
      "position_embedding_type": "nope", "rms_norm_eps": 1e-5,
      "rope_theta": 10000, "tie_word_embeddings": True}
N_LAYERS = 6     # two periods of three: the engine's step is rolled
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


# ----------------------------------------------------------------------
# the published modelling code itself
@pytest.mark.parametrize("groups,position", [(1, "nope"), (2, "rope")])
def test_logits_are_those_of_the_published_modelling_code(tmp_path, groups,
                                                          position):
    """A seeded ``GraniteMoeHybridForCausalLM`` (its naive path), saved and
    read back through ``hf.from_pretrained``: 40 tokens are three pieces of
    ``mamba_chunk_size`` 16. The vectors the module initialises to
    constants (conv bias 0, D 1, norms 1) are moved off them first."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    hc = dict(HC, mamba_n_groups=groups, position_embedding_type=position)
    torch.manual_seed(0)
    theirs = tf.GraniteMoeHybridForCausalLM(tf.GraniteMoeHybridConfig(
        **{k: v for k, v in hc.items() if k != "model_type"})).eval()
    with torch.no_grad():
        for name, p in theirs.named_parameters():
            if name.endswith(("conv1d.bias", "dt_bias", "A_log", ".D",
                              "norm.weight", "layernorm.weight")):
                p.add_(0.3 * torch.randn_like(p))
    theirs.save_pretrained(str(tmp_path), safe_serialization=True)
    model, params = hf.from_pretrained(str(tmp_path), dtype=jnp.float32)
    c = model.config
    c.remat, c.use_flash = False, False
    assert c.layer_types == ("mamba", "mamba", "full") * 2
    assert (c.attn_scale, c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == (0.07, 3.0, 0.4, 2.5)
    assert np.abs(np.asarray(params["layers"]["mamba"]["conv_b"])).min() > 0
    tok = np.random.default_rng(0).integers(0, 256, (2, 40))
    with torch.no_grad():
        want = theirs(torch.tensor(tok)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)(params, jnp.asarray(tok))
    assert _rel(got.reshape(80, -1), want.reshape(80, -1)).max() < 1e-5


def test_hf_config_reads_the_published_config(tmp_path):
    row = next(json.loads(l) for l in open(CATALOG)
               if '"granite-4.0-h-micro"' in l) \
        if os.path.exists(CATALOG) else None
    hc = row["config"] if row else json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "granite-4.0-h-micro.json")))
    (tmp_path / "config.json").write_text(json.dumps(hc))
    family, c = hf.hf_config(str(tmp_path))
    assert family == "granitemoehybrid" and c.n_layers == 40
    assert c.layers_of("full") == (5, 15, 25, 35)
    assert len(c.layers_of("mamba")) == len(c.state_layers) == 36
    assert c.position == "none" and c.tie_embeddings and c.head_dim == 64
    assert (c.attn_scale, c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == (1 / 64, 12.0, 0.22, 8.0)
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
            c.mamba_n_groups, c.mamba_d_conv, c.mamba_chunk) \
        == (64, 64, 128, 1, 4, 256)
    assert c.layer_period == 10            # served as four periods of ten
    # ISSUE 43: 36 x 76.18 M + 4 x 60.82 M + 205.5 M
    assert round(c.param_count() / 1e9, 2) == 3.19


# ----------------------------------------------------------------------
# the recurrence: chunked, stepped and ragged forms against the token scan
H, P, N = 4, 8, 16


def _lanes(key, n, groups=1):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (n, H, P))
    B = jax.random.normal(ks[1], (n, groups, N))
    C = jax.random.normal(ks[2], (n, groups, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (n, H)))
    g = -jnp.exp(0.3 * jax.random.normal(ks[4], (H,))) * dt
    return x, B, C, dt, g


def _per_head(a):
    return jnp.repeat(a, H // a.shape[1], axis=1)


D = jnp.linspace(0.5, 1.5, H)


def _scan(xs, s0):
    x, B, C, dt, g = xs
    return mamba2.ssd_recurrent(x, _per_head(B), _per_head(C), dt, g, D, s0)


@pytest.mark.parametrize("n,groups", [(1, 1), (31, 1), (32, 2), (150, 1)])
def test_chunked_form_is_the_token_recurrence(n, groups):
    xs = _lanes(jax.random.PRNGKey(n), n, groups)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (H, P, N))
    y1, s1 = _scan(xs, s0)
    y2, s2 = jax.jit(mamba2.ssd_chunked, static_argnums=7)(*xs, D, s0, 32)
    np.testing.assert_allclose(y2, y1, atol=5e-5)
    np.testing.assert_allclose(s2, s1, atol=5e-5)


def test_step_is_one_token_of_the_recurrence_from_a_state():
    xs = _lanes(jax.random.PRNGKey(3), 1)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (H, P, N))
    x, B, C, dt, g = (a[0] for a in xs)
    y, s1 = mamba2.ssd_step(x, _per_head(B[None])[0], _per_head(C[None])[0],
                            dt, g, D, s0)
    a = np.exp(np.asarray(g))[:, None, None]
    want = a * np.asarray(s0) + (np.asarray(dt)[:, None] * np.asarray(x))[
        ..., None] * np.asarray(B)[0]
    np.testing.assert_allclose(s1, want, atol=1e-6)
    np.testing.assert_allclose(
        y, want @ np.asarray(C)[0] + np.asarray(D)[:, None] * np.asarray(x),
        atol=1e-5)
    y2, s2 = _scan(xs, s0)
    np.testing.assert_allclose(y2[0], y, atol=1e-6)
    np.testing.assert_allclose(s2, s1, atol=1e-6)


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


@pytest.mark.parametrize("path", ["gather", "pallas_interpret"])
@pytest.mark.parametrize("case", list(RAGGED))
def test_ragged_lanes_against_the_token_recurrence(case, path):
    """``path``: the runs of one lane through XLA's form over every slot,
    or through the step kernel (interpret mode) over those slots alone."""
    S, T, K, ch, chunk = 6, 256, 4, 5, 32
    state = jax.random.normal(jax.random.PRNGKey(7), (S + 1, H, P, N))
    rows = jax.random.normal(jax.random.PRNGKey(13), (S + 1, K - 1, ch))
    xs = _lanes(jax.random.PRNGKey(9), T)
    xc = jax.random.normal(jax.random.PRNGKey(11), (T, ch))
    w = jax.random.normal(jax.random.PRNGKey(12), (K, ch))
    bias = jax.random.normal(jax.random.PRNGKey(14), (ch,))
    slots = np.full(T, -1, np.int32)
    positions = np.zeros(T, np.int32)
    want_y = np.zeros((T, H, P), np.float32)
    want_c = np.zeros((T, ch), np.float32)
    want_s, want_r = np.array(state), np.array(rows)
    t = 0
    for n, sl, p0 in RAGGED[case]:
        slots[t:t + n], positions[t:t + n] = sl, np.arange(p0, p0 + n)
        st = jnp.zeros((H, P, N)) if p0 == 0 else state[sl]
        want_y[t:t + n], want_s[sl] = _scan([a[t:t + n] for a in xs], st)
        past = np.zeros((K - 1, ch)) if p0 == 0 else np.asarray(rows[sl])
        full = np.concatenate([past, np.asarray(xc[t:t + n])])
        for i in range(n):
            want_c[t + i] = np.asarray(bias) + sum(
                np.asarray(w)[j] * full[i + j] for j in range(K))
        want_r[sl] = full[-(K - 1):]
        t += n
    runs = gd.runs_of(jnp.asarray(slots), jnp.asarray(positions), S, chunk)
    y, st = jax.jit(mamba2.ssd_ragged, static_argnums=(8, 10))(
        *xs, D, state, runs, chunk, None, path)
    c, r = jax.jit(gd.conv_ragged)(xc, w, rows, runs, bias)
    np.testing.assert_allclose(np.asarray(y)[:t], want_y[:t], atol=1e-4)
    np.testing.assert_allclose(st, want_s, atol=1e-4)   # the sink's too
    np.testing.assert_allclose(np.asarray(c)[:t], want_c[:t], atol=1e-5)
    np.testing.assert_allclose(r, want_r, atol=1e-6)


# ----------------------------------------------------------------------
# the step kernel (interpret mode) against ``ssd_step`` on the same rows
def _step_case(n, S, T, periods, period, groups, key=9):
    """``n`` runs of one lane among ``S`` slots (every third starts a
    sequence) and, where a slot is left, one run of five lanes between
    them; a leaf of ``periods`` runs of ``S + 1`` slots, of which the
    kernel is given the one at ``period``."""
    rng = np.random.default_rng(n + 10 * period)
    order = rng.permutation(S)
    single, long_slot = order[:n], order[n] if n < S else None
    slots = np.full(T, -1, np.int32)
    positions = np.zeros(T, np.int32)
    t = 0
    for i, sl in enumerate(single):
        if i == n // 2 and long_slot is not None:
            slots[t:t + 5], positions[t:t + 5] = long_slot, np.arange(9, 14)
            t += 5
        slots[t], positions[t] = sl, 0 if i % 3 == 0 else 3 + i
        t += 1
    state = jax.random.normal(jax.random.PRNGKey(7),
                              (periods * (S + 1), H, P, N))
    xs = _lanes(jax.random.PRNGKey(key), T, groups)
    runs = gd.runs_of(jnp.asarray(slots), jnp.asarray(positions), S, 32)
    base = None if periods == 1 else jnp.int32(period * (S + 1))
    return single, long_slot, xs, state, runs, base, t


def _kernel_against_ssd_step(n, S, periods, period, xs, state, runs, base):
    """The kernel's rows and leaf against ``ssd_step`` on the ``n`` live
    entries' rows (from zeros where the run starts a sequence; B and C a
    group's, not repeated to its heads), each slot where it lies in the
    leaf: at ``period * (S + 1) + slot``. Every other row of the leaf keeps
    its bits. Returns those rows' indices."""
    from deepspeed_tpu.ops.pallas.mamba2 import ssd_step_slots

    steps = np.asarray(runs.steps)
    rows = [a[steps[1]] for a in xs]
    y, st = ssd_step_slots(*rows, D, state, runs.steps, base, interpret=True)
    at = period * (S + 1) + steps[0, :n]
    old = jnp.where(jnp.asarray(steps[2, :n] > 0)[:, None, None, None], 0.0,
                    state[at])
    x, B, C, dt, g = (a[:n] for a in rows)
    want_y, want_s = mamba2.ssd_step(x, _per_head(B), _per_head(C), dt, g, D,
                                     old)
    np.testing.assert_allclose(np.asarray(y)[:n], want_y, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st)[at], want_s, atol=1e-6)
    untouched = np.setdiff1d(np.arange(periods * (S + 1)), at)
    assert np.array_equal(np.asarray(st)[untouched],
                          np.asarray(state)[untouched])
    return untouched


@pytest.mark.parametrize("groups", [1, 2], ids=["one_group", "two_groups"])
@pytest.mark.parametrize("periods,period", [(1, 0), (3, 2)],
                         ids=["flat_leaf", "rolled_leaf_period_2"])
@pytest.mark.parametrize("n", [0, 1, 3, 6],
                         ids=["none_live", "one_live", "some_live",
                              "all_live"])
def test_step_kernel_against_ssd_step(n, periods, period, groups):
    """The kernel gives each single-lane slot ``ssd_step``'s output and
    state, where the slot lies in the leaf: at ``base + slot`` under a
    rolled stack. Every other row of the leaf keeps its bits: the idle
    slots, the sinks, the other periods' runs, and the slot whose run is
    longer, which the chunk loop serves behind it."""
    S, T = 6, 32
    single, long_slot, xs, state, runs, base, t = _step_case(
        n, S, T, periods, period, groups)
    steps = np.asarray(runs.steps)
    assert steps.shape == (4, S) and steps[3].tolist() == [n] * S
    assert steps[0, :n].tolist() == sorted(single.tolist())
    untouched = _kernel_against_ssd_step(n, S, periods, period, xs, state,
                                         runs, base)
    assert period * (S + 1) + S in untouched
    # behind it the chunk loop serves the longer run, as on the XLA path
    both = [jax.jit(mamba2.ssd_ragged, static_argnums=(8, 10))(
        *xs, D, state, runs, 32, base, path)
        for path in ("gather", "pallas_interpret")]
    np.testing.assert_allclose(np.asarray(both[1][0])[:t],
                               np.asarray(both[0][0])[:t], atol=1e-5)
    np.testing.assert_allclose(both[1][1], both[0][1], atol=1e-6)
    if long_slot is not None and n:
        held = period * (S + 1) + long_slot
        assert held in untouched
        assert not np.array_equal(np.asarray(both[1][1])[held],
                                  np.asarray(state)[held])


def test_step_kernel_takes_the_heads_in_blocks(monkeypatch):
    """A state too large for one grid step goes ``hb`` heads at a time:
    the entry's block of ``y`` is one over its grid steps, and a later
    block of heads finds the earlier ones' columns in it."""
    from deepspeed_tpu.ops.pallas import gated_delta as kernels

    S, T = 5, 16      # shapes of their own: the kernel is jitted by shape
    monkeypatch.setattr(kernels, "STATE_VMEM_BYTES", 4 * 2 * 4 * P * 128)
    assert kernels.head_block(H, P, N) == 2
    _, _, xs, state, runs, base, _ = _step_case(4, S, T, 2, 1, 2)
    _kernel_against_ssd_step(4, S, 2, 1, xs, state, runs, base)


@pytest.mark.parametrize("periods,period", [(1, 0), (4, 3)],
                         ids=["flat_leaf", "rolled_leaf_period_3"])
@pytest.mark.parametrize("live", [0, 40], ids=["empty_batch", "one_long_run"])
def test_step_kernel_with_no_single_lane_run_changes_nothing(live, periods,
                                                             period):
    """An empty batch (the runner's warm-up) and a batch of one long run:
    every entry names the period's sink, which comes back bit for bit, as
    does every other slot of the leaf."""
    from deepspeed_tpu.ops.pallas.mamba2 import ssd_step_slots

    S, T = 6, 64
    state = jax.random.normal(jax.random.PRNGKey(7),
                              (periods * (S + 1), H, P, N))
    xs = _lanes(jax.random.PRNGKey(9), T)
    slots = np.where(np.arange(T) < live, 2, -1).astype(np.int32)
    runs = gd.runs_of(jnp.asarray(slots), jnp.arange(T, dtype=jnp.int32), S,
                      32)
    assert np.asarray(runs.steps)[[0, 3]].tolist() == [[S] * S, [0] * S]
    base = None if periods == 1 else jnp.int32(period * (S + 1))
    _, st = ssd_step_slots(*(a[runs.steps[1]] for a in xs), D, state,
                           runs.steps, base, interpret=True)
    assert np.array_equal(np.asarray(st), np.asarray(state))


# ----------------------------------------------------------------------
# the model against the plain reference
@pytest.fixture(scope="module")
def built():
    c = hf.granite_hybrid_config(HC, N_LAYERS)
    c.remat, c.use_flash = False, False
    model = Transformer(c)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, SEED, jnp.float32, N_LAYERS)


def _reference(params, fed, cols_of, quant=None):
    """Reference logits of sequence i at the positions ``cols_of[i]``."""
    width = max(map(len, fed))
    tokens = np.zeros((len(fed), width), np.int32)
    for i, f in enumerate(fed):
        tokens[i, :len(f)] = f
    rows = np.concatenate([[i] * len(c) for i, c in enumerate(cols_of)])
    return np.asarray(ref.logits_at(
        params, jnp.asarray(tokens), jnp.asarray(rows),
        jnp.asarray(np.concatenate(cols_of)), HC, N_LAYERS, quant))


def test_rolled_engine_is_the_unrolled_engine(built, monkeypatch):
    """The engine's step loops over the two periods (one leaf a layer of a
    period, the periods' runs end to end in it); steered to one period of
    six (in the test, not through an option of the program) it unrolls the
    six layers over six leaves: the same logits, prefill split over two
    ticks and then decode."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    model, params = built
    assert model.config.layer_period == 3
    got = []
    for rolled in (True, False):
        if not rolled:
            monkeypatch.setattr(TransformerConfig, "layer_period",
                                property(lambda self: self.n_layers))
        eng = _engine(built)
        assert eng._periods == (2 if rolled else 1)
        assert [a.shape[0] for a in eng.kv_pool.state] \
            == ([10, 10] if rolled else [5] * 4)
        assert [a.shape[0] for a in eng.kv_pool.k] \
            == ([130] if rolled else [65] * 2)
        uids, prompts = [1, 2, 3], _prompts(100, 70, 5)
        rows, puts = _prefill(eng, uids, prompts)
        assert puts == 2
        steps = [rows]
        for _ in range(3):
            steps.append(eng.put(uids, [[int(t)] for t in
                                        np.argmax(steps[-1], -1)]))
        got.append(np.stack(steps, 1))
    assert _rel(got[0].reshape(12, -1), got[1].reshape(12, -1)).max() < 1e-5


def test_the_seeded_tree_tests_the_bias_and_ties_the_head(built):
    _, params = built
    lm = params["layers"]["mamba"]
    assert "lm_head" not in params
    assert float(jnp.abs(lm["conv_b"]).mean()) > 0.1     # not zeroed
    assert abs(float(lm["ssm_norm_w"].mean()) - 1.0) < 0.05
    assert set(lm) == {"w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                       "ssm_norm_w", "w_out"}


def _dense_error(model, params, ref_params=None):
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0,
                                        256))
    got = jax.jit(model.apply)(params, jnp.asarray(tok))
    want = _reference(ref_params or params, tok.tolist(),
                      [np.arange(100)] * 2)
    return _rel(got.reshape(200, -1), want).max()


def test_dense_forward_agrees_with_the_reference(built):
    assert _dense_error(*built) < 1e-4


CONTROLS = {
    # each drops one thing from the PROGRAM; the reference keeps it
    "no_conv_bias": lambda c, p: p["layers"]["mamba"].update(
        conv_b=0 * p["layers"]["mamba"]["conv_b"]),
    "no_skip": lambda c, p: p["layers"]["mamba"].update(
        D=0 * p["layers"]["mamba"]["D"]),
    "residual_multiplier_left_out": lambda c, p: setattr(
        c, "residual_multiplier", 1.0),
    "softmax_scale_of_the_head_size": lambda c, p: setattr(
        c, "attn_scale", None),
    "logits_not_scaled": lambda c, p: setattr(c, "logits_scaling", 1.0),
    "embedding_not_multiplied": lambda c, p: setattr(
        c, "embedding_multiplier", 1.0),
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_control_fails_the_same_comparison(built, control):
    _, sound = built
    params = jax.tree_util.tree_map(lambda a: a, sound)
    c = hf.granite_hybrid_config(HC, N_LAYERS)
    c.remat, c.use_flash = False, False
    CONTROLS[control](c, params)
    assert _dense_error(Transformer(c), params, sound) > 2e-3


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
    """175 prompt tokens against a budget of 128: one prompt's state and
    convolution rows cross a tick; then 4 decode steps through both
    caches."""
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


def _two_kinds_of_run_in_one_tick(built):
    """A sequence that decodes and a prompt that arrives share a tick: the
    step serves the first and the chunked form the second, through the
    same leaves."""
    eng, (a, b) = _engine(built), _prompts(37, 45)
    rows, _ = _prefill(eng, [1], [a])
    nxt = int(np.argmax(rows[0]))
    both = eng.put([1, 2], [[nxt], b])
    assert not np.isnan(both[:, 0]).any()
    return [a + [nxt], b], [both[0][None], both[1][None]], \
        [np.array([len(a)]), np.array([len(b) - 1])]


SCENARIOS = {"split_prompt": _split_prompt,
             "preempt_and_resume": _preempt_and_resume,
             "decode_steps": _decode_steps, "slot_reused": _slot_reused,
             "two_kinds_of_run_in_one_tick": _two_kinds_of_run_in_one_tick}


@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_ragged_engine_agrees_with_the_reference(built, scenario):
    fed, got, cols = SCENARIOS[scenario](built)
    want = _reference(built[1], fed, cols)
    err = _rel(np.concatenate([np.asarray(g).reshape(len(c), -1)
                               for g, c in zip(got, cols)]), want)
    assert err.max() < 1e-4, err


def test_fp8_control_differs_from_the_reference(built):
    fed = _prompts(60, 41)
    cols = [np.arange(len(f)) for f in fed]
    want = _reference(built[1], fed, cols)
    low = _reference(built[1], fed, cols, "fp8")
    assert np.median(_rel(low, want)) > 1e-2


# ----------------------------------------------------------------------
# the kernel paths in interpret mode, with Granite's softmax scale: two KV
# heads of 16 fill a quarter of a 128-lane row, so that pool keeps a head a
# row, the lane grid and the scatter; two of 64 share a row, as the cell's
# eight do, and take the tiled grid and the row writer
def _built_with(**changed):
    c = hf.granite_hybrid_config(dict(HC, **changed), N_LAYERS)
    c.remat, c.use_flash = False, False
    model = Transformer(c)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, SEED, jnp.float32, N_LAYERS)


@pytest.mark.parametrize("hidden,shared", [(64, False), (256, True)],
                         ids=["lane_grid", "two_heads_a_row"])
def test_kernel_path_engine_decodes_what_the_gather_engine_decodes(
        monkeypatch, hidden, shared):
    built = _built_with(hidden_size=hidden)
    assert built[0].config.head_dim == hidden // 4

    def drive(eng):
        uids, prompts = [1, 2, 3], _prompts(100, 70, 5)
        rows, _ = _prefill(eng, uids, prompts)
        got = [rows]
        for _ in range(3):
            nxt = np.argmax(got[-1], -1)
            got.append(eng.put(uids, [[int(t)] for t in nxt]))
        # and ``decode_steps``, which scans the same core: three more
        chains = eng.decode_steps(
            {u: int(t) for u, t in zip(uids, np.argmax(got[-1], -1))}, 3)
        return np.stack(got, 1), [chains[u] for u in uids]

    a, tokens_a = drive(_engine(built))
    monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    eng = _engine(built)
    assert eng.attention_path == "pallas_interpret"
    # ... and the Mamba layers' one-token step is the kernel over the slots
    # that decode, in place in each period's run of the rolled leaf
    assert eng._steps_live_slots
    assert eng._writes_pages == shared == (not eng._pages_key)
    # both periods' runs of pages in the one leaf, a row of 128 lanes a
    # pair of heads
    assert eng.kv_pool.k[0].shape == \
        ((130, 1, 16, 128) if shared else (130, 2, 16, 16))
    b, tokens_b = drive(eng)
    assert tokens_b == tokens_a
    assert np.isfinite(b).all()
    assert _rel(b.reshape(12, -1), a.reshape(12, -1)).max() < 1e-5


# ----------------------------------------------------------------------
# spans and counters
@pytest.mark.parametrize("path", ["gather", "pallas_interpret"])
def test_put_says_how_many_layers_hold_a_state(built, monkeypatch, path):
    """... and how many slots the step kernel serves: ``step_slots``, the
    schedule's entries of one lane, on the kernel paths; 0 where the step
    runs in XLA over every slot. The registry's
    ``inference/state_slots_stepped`` sums them x the 4 Mamba layers."""
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

    real = ragged_mod.annotate
    monkeypatch.setattr(ragged_mod, "annotate",
                        lambda name, **attrs: Span() if name == "ragged.put"
                        else real(name, **attrs))
    tel = Telemetry(TelemetryConfig(enabled=True, output_dir="",
                                    jsonl_path="", stall_detection=False))
    set_telemetry(tel)
    if path != "gather":
        monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    stepped = 2 if path != "gather" else 0    # the tick of two decode lanes
    try:
        r = tel.registry        # one a process: counters by their growth
        resets, steps = (r.counter("inference/state_resets"),
                         r.counter("inference/state_slots_stepped"))
        was = resets.value, steps.value
        eng = _engine(built)
        assert eng.attention_path == path
        assert eng._steps_live_slots == (path != "gather")
        rows, _ = _prefill(eng, [1, 2], _prompts(20, 9))
        eng.put([1, 2], [[int(t)] for t in np.argmax(rows, -1)])
        # 4 Mamba layers x (8 x 16 x 8 float32 + 3 rows of 144 float32)
        assert eng.state_bytes_per_slot == 4 * (8 * 16 * 8 * 4 + 3 * 144 * 4)
        assert r.gauge("inference/state_bytes_per_slot").value \
            == eng.state_bytes_per_slot
        assert resets.value - was[0] == 2
        assert r.gauge("inference/state_slots_live").value == 2
        # in XLA over every slot there are no kernel's entries to count
        assert steps.value - was[1] == stepped * 4
    finally:
        set_telemetry(None)
    assert [a["state_layers"] for a in seen] == [4, 4]
    assert [a["state_slots"] for a in seen] == [2, 2]
    assert [a["step_slots"] for a in seen] == [0, stepped]
    assert [a["decode"] for a in seen] == [0, 2]
    assert [a["kv_layers"] for a in seen] == [2, 2]
    assert [a["passes"] for a in seen] == [1, 1]


# ----------------------------------------------------------------------
# the pools at the cell's widths, by shapes alone
def test_pools_at_the_cells_widths():
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "granite-4.0-h-micro.json")))
    c = hf.granite_hybrid_config(cfg)
    e = cfg["engine"]
    rc = RaggedConfig(token_budget=e["token_budget"], max_seqs=e["max_seqs"],
                      kv_block_size=e["kv_block_size"],
                      n_kv_blocks=e["max_kv_blocks"],
                      max_context=e["max_context"], dtype=jnp.bfloat16)
    kinds = kv_cache.pool_leaves(c, rc)
    # a leaf for each layer of ONE period, the four periods' runs of slots
    # and of pages end to end in it
    assert (kinds.state.n, kinds.state.shape, kinds.state.passes) \
        == (9, (4 * 65, 64, 64, 128), 4)
    assert (kinds.conv_rows.n, kinds.conv_rows.shape) == (9, (4 * 65, 3, 4352))
    # eight KV heads of 64 as four rows of 128 lanes: the leaf the tiled
    # paged kernel and the row writer take (the same bytes a page)
    assert (kinds.k.n, kinds.k.shape, kinds.k.passes) \
        == (1, (4 * 4097, 4, 16, 128), 4)
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_grid
    assert tiled_grid(kinds.k, kinds.v)
    # ... where the heads of one device fill whole rows: 8 / 8 do not
    assert kv_cache.pool_leaves(c, rc, 8).k.shape == (4 * 4097, 8, 16, 64)
    assert kv_cache.pool_leaves(c, rc, 4).k.shape == (4 * 4097, 4, 16, 128)
    assert kv_cache.cache_layers(c) == 4 and kv_cache.cache_passes(c) == 1
    # ISSUE 43: 2,097,152 B of state + 26,112 B of rows a layer a sequence
    assert kv_cache.state_slot_bytes(c, rc) == 36 * (2097152 + 26112) \
        == 76_437_504
    assert kv_cache.state_pool_bytes(c, rc) == 65 * 76_437_504
    assert kv_cache.kv_page_bytes(c, rc) == 16 * 8192
    assert 4096 * kv_cache.kv_page_bytes(c, rc) == 536_870_912
    budget = kv_cache.state_pool_bytes(c, rc) + 100 * 16 * 8192
    assert kv_cache.kv_blocks_for_bytes(budget, c, rc) == 100
    # weights + state pool + pages: what the chip holds
    assert 2 * c.param_count() + kv_cache.state_pool_bytes(c, rc) \
        + 4096 * kv_cache.kv_page_bytes(c, rc) > 11.8e9


# ----------------------------------------------------------------------
# what is refused, by message
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
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_mamba_model_refuses_what_needs_a_state_snapshot(built, what):
    with pytest.raises(NotImplementedError, match="state snapshot"):
        REFUSED[what](built)


def test_a_tensor_parallel_state_is_refused(built):
    model, params = built

    class Topo:
        model_parallel_size, world_size = 2, 2

    with pytest.raises(NotImplementedError,
                       match="recurrent layers are not sharded"):
        RaggedInferenceEngine(model, RaggedConfig(max_seqs=4, max_context=256),
                              params=params,
                              topology=Topo())


def test_dense_kv_cache_refuses_a_mamba_model(built):
    model, params = built
    cache = jnp.zeros((N_LAYERS, 1, 8, 2, 16))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32),
                    kv_caches=(cache, cache), cache_pos=0)


@pytest.mark.parametrize("key,value,message", [
    ("num_local_experts", 8, "num_local_experts=8 not supported"),
    ("attention_bias", True, "attention_bias=true not supported"),
    ("mamba_proj_bias", True, "mamba_proj_bias=true not supported"),
    ("mamba_conv_bias", False, "mamba_conv_bias=false not supported")])
def test_hf_config_refuses_by_name(key, value, message):
    with pytest.raises(NotImplementedError, match=message):
        hf.granite_hybrid_config(dict(HC, **{key: value}))


def test_two_recurrent_kinds_in_one_model_are_refused():
    from deepspeed_tpu.models.transformer import TransformerConfig

    with pytest.raises(AssertionError, match="one recurrent kind"):
        TransformerConfig(n_layers=2, layer_types=("linear", "mamba"),
                          linear_n_k_heads=2, linear_k_dim=8, linear_v_dim=8,
                          mamba_n_heads=2, mamba_d_head=8, mamba_d_state=8)


def test_ragged_engine_serves_a_softmax_scale_override():
    """``attn_scale`` reaches the paged attention of the ragged step (it
    was refused before Granite needed it): a plain model with a scale of
    its own decodes what its dense forward gives."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    c = TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, max_seq_len=256,
                          attn_scale=0.6, remat=False, use_flash=False)
    model = Transformer(c)
    params = model.init(jax.random.PRNGKey(3))
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=64, max_seqs=2, n_kv_blocks=16,
                            max_context=128, dtype=jnp.float32),
        params=params)
    prompt = _prompts(23)[0]
    rows = eng.put([1], [prompt])
    want = model.apply(params, jnp.asarray([prompt]))[0, -1]
    assert _rel(rows[0], want) < 1e-4
    c.attn_scale = None
    other = Transformer(c).apply(params, jnp.asarray([prompt]))[0, -1]
    assert _rel(other, want) > 1e-3
