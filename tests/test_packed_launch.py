"""A serving tick's five int32 arrays go to the device as one packed buffer
that the step program cuts up (``ragged.pack_fields``, ``_fields``), behind
the eight-argument call the step has always had. Here, on the CPU at tiny
sizes: that call with five real arrays (what the benchmark's warm-up and
``warm_step`` make) and ``put``'s packed launch are one program with one
result, for every form the selection rows take; and a wrapper set in
``_step_fn``'s place still reads a tick's shapes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, weights
from benchmarks.sweep import spy_on_shapes
from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                            RaggedInferenceEngine,
                                            pack_fields)
from deepspeed_tpu.models import Llama
from deepspeed_tpu.parallel import mesh as mesh_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 128


def _dense():
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=VOCAB, max_seq_len=256, use_flash=False,
                  remat=False)
    return model, model.init(jax.random.PRNGKey(5))


def _block():
    """SDAR at a tiny size: blocks of four, selection rows [max_seqs, 4]."""
    cfg = dict(model_type="sdar_moe", hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
               num_experts=8, num_experts_per_tok=2, num_hidden_layers=2,
               vocab_size=VOCAB, max_position_embeddings=256,
               rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=False,
               norm_topk_prob=True,
               assumed=dict(block_length=4, mask_token_id=VOCAB - 1,
                            denoising_steps=2))
    model = harness.find("architectures", "sdar_moe").build(cfg, 2)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, 3, jnp.float32, 2)


def _share():
    """A.X-K1's file with every size cut, holding 6 of 48 routed experts:
    the step's ids carry the two tallies behind them."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "a.x-k1.json")))
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
               kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=64,
               v_head_dim=32, vocab_size=VOCAB, num_experts_per_tok=4,
               n_routed_experts=6, experts_held=[0, 6], num_hidden_layers=3,
               max_position_embeddings=512)
    cfg["published"] = dict(cfg["published"], n_routed_experts=48)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    model = harness.find("architectures", "axk1").build(cfg, 3)
    model.config.use_flash = model.config.remat = False
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, 5, jnp.float32, 3)


def _engine(model, params):
    return RaggedInferenceEngine(model, RaggedConfig(
        token_budget=32, max_seqs=4, kv_block_size=16, n_kv_blocks=48,
        max_context=128, dtype=jnp.float32), params=params)


def _prompt(seed, n):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, VOCAB - 1, n)]


def _spy_on_launch(eng):
    """Every ``_launch`` of ``eng``: the five host arrays, the live-page
    bucket and the program's first result."""
    seen, real = [], eng._launch

    def launch(step, host, live_pages):
        out = real(step, host, live_pages)
        seen.append((host, live_pages, out[0]))
        return out

    eng._launch = launch
    return seen


CASES = {
    # name: (model, the selection rows' shape behind max_seqs)
    "dense": (_dense, ()),
    "block_model": (_block, (4,)),
    "expert_share": (_share, ()),
    "verify_step": (_dense, (4,)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_eight_argument_call_and_packed_launch_are_one_program(case):
    """Two engines of one model: ``a`` runs a tick through ``put`` (or
    ``put_spec``), whose ``_launch`` sends one packed buffer; ``b`` is
    called as ``serve_open_loop.py::warm`` calls the step, eight positional
    arguments with the tick's five arrays each on the device. Logits, ids
    (an expert share's two tallies behind them) and every pool leaf agree
    bit for bit, and ``b``'s own tick then finds that call's program."""
    mesh_mod.reset_topology()
    make, sel_tail = CASES[case]
    model, params = make()
    a, b = _engine(model, params), _engine(model, params)
    seen = _spy_on_launch(a)
    prompts = [_prompt(1, 9), _prompt(2, 14)]
    if case == "verify_step":
        ticks = [lambda e: e.put([1, 2], prompts),
                 lambda e: e.put_spec([1, 2], [[7], [9]], [[3, 4, 5], []])]
    else:
        ticks = [lambda e: e.put([1, 2], prompts)]
    for tick in ticks[:-1]:
        tick(a), tick(b)
    ticks[-1](a)
    host, pages, logits_a = seen[-1]
    assert [x.shape for x in host] == [(32,)] * 3 + [
        (4, a.max_pages), (4,) + sel_tail]
    assert all(x.dtype == np.int32 for x in host)

    program, build = ("_verify_fn", b._build_verify) \
        if case == "verify_step" else ("_step_fn", b._build_step)
    if getattr(b, program) is None:
        setattr(b, program, build())
    logits_b, b.kv_pool = getattr(b, program)(
        b.params, b.kv_pool, *(jnp.asarray(x) for x in host), pages)
    np.testing.assert_array_equal(np.asarray(logits_a), np.asarray(logits_b))
    assert logits_b.shape[:-1] == (4,) + sel_tail
    if case != "verify_step":
        ids = np.asarray(a._step_ids)
        np.testing.assert_array_equal(ids, np.asarray(b._step_ids))
        assert ids.shape == ((6,) if case == "expert_share"
                             else (4,) + sel_tail)
    leaves_a = jax.tree_util.tree_leaves(a.kv_pool)
    leaves_b = jax.tree_util.tree_leaves(b.kv_pool)
    assert len(leaves_a) == len(leaves_b) > 0
    for x, y in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    # one program, whichever way it was reached
    compiled = getattr(b, program)._cache_size()
    assert compiled == getattr(a, program)._cache_size() == 1
    ticks[-1](b)
    assert getattr(b, program)._cache_size() == compiled
    b.warm_step(32, pages)
    assert b._step_fn._cache_size() == 1


def test_pack_fields_lays_the_five_end_to_end():
    """The order and the offsets the step program counts on, and a device
    array taken like a host one."""
    host = [np.arange(8, dtype=np.int32) + 100 * i for i in range(3)]
    host += [np.arange(12, dtype=np.int32).reshape(4, 3) + 1000,
             np.arange(8, dtype=np.int32).reshape(4, 2) + 2000]
    packed = pack_fields(host)
    assert packed.dtype == np.int32 and packed.shape == (3 * 8 + 12 + 8,)
    for i in range(3):
        np.testing.assert_array_equal(packed[8 * i:8 * (i + 1)], host[i])
    np.testing.assert_array_equal(packed[24:36].reshape(4, 3), host[3])
    np.testing.assert_array_equal(packed[36:].reshape(4, 2), host[4])
    np.testing.assert_array_equal(
        pack_fields([jnp.asarray(x) for x in host]), packed)
    host[0][0] = -7                     # a new buffer, not a view
    assert packed[0] == 0


@pytest.mark.parametrize("entry", ["put", "server"])
def test_a_wrapper_in_step_fns_place_counts_a_ticks_shape(entry):
    """``benchmarks/sweep.py::spy_on_shapes`` itself, set where it sets
    itself: called once a tick, the third argument's ``shape[0]`` the lane
    bucket and the last the live-page bucket, and the tick's result what it
    is without the wrapper."""
    from deepspeed_tpu.serving import ServingEngine

    mesh_mod.reset_topology()
    model, params = _dense()
    eng, plain = _engine(model, params), _engine(model, params)
    eng._step_fn = eng._build_step()
    used = spy_on_shapes(eng)
    prompts = [_prompt(1, 9), _prompt(2, 40)]       # 49 tokens: two ticks
    if entry == "put":
        rows = eng.put([1, 2], prompts)
        np.testing.assert_array_equal(rows, plain.put([1, 2], prompts))
        assert dict(used) == {(32, 2): 1}       # 9 + 23 of the 40: 2 pages
        rows = eng.put([1, 2], [[], []])
        np.testing.assert_array_equal(rows, plain.put([1, 2], [[], []]))
        assert dict(used) == {(32, 2): 1, (32, 4): 1}
        return
    streams = []
    for e in (eng, plain):
        srv = ServingEngine(e, {"policy": "fcfs"}, start=False)
        reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        while not all(r.is_terminal for r in reqs):
            srv._tick()
        streams.append(([list(r.tokens) for r in reqs], srv._tick_count))
        srv.close()
    assert streams[0] == streams[1]
    assert sum(used.values()) == streams[0][1]
    assert set(used) == {(32, 2), (32, 4)}
