"""Fused compute–collective kernel backends (comm/backends.py,
ops/pallas/fused_collectives.py, docs/communication.md "Kernel
backends").

The ISSUE-11 acceptance surface, all in Pallas interpret mode on the CPU
mesh: the Pallas backend must be BIT-exact to the unfused XLA backend at
the same QuantSpec (and to dense with compression off) for all three
fused entry points; non-dividing/contraction-dim shapes must take the
metered fallback; the staged engine must pick fusion up through the
Zero3BlockSchedule seam with losses and params bit-identical to the XLA
backend; the TP decode path must route the MLP all-reduce through the
backend; and the quantizer edge cases (ISSUE-11 satellite) are pinned.

All references are computed under jax.jit: XLA:CPU folds division-by-
constant differently in jitted vs op-by-op execution (1-ulp scale
drift), and jit is the only configuration the engine ever runs.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as dst
from deepspeed_tpu.comm import compressed as cc
from deepspeed_tpu.comm.backends import (CollectiveBackend,
                                         PallasFusedBackend,
                                         XlaCollectiveBackend,
                                         resolve_backend)
from deepspeed_tpu.ops.quantizer import (pack_int4, quantize_blockwise,
                                         quantized_nbytes, unpack_int4)
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import Topology
from deepspeed_tpu.parallel.zero import (SequentialBlockModel,
                                         Zero3BlockSchedule)
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)


@pytest.fixture(autouse=True)
def _fresh_topology():
    mesh_mod.reset_topology()
    yield
    mesh_mod.reset_topology()


@pytest.fixture()
def reg():
    old = get_registry()
    r = set_registry(MetricsRegistry())
    yield r
    set_registry(old)


def _spmd(topo, fn, *args, in_specs, out_specs, axes={"data"}):
    return jax.jit(jax.shard_map(
        fn, mesh=topo.mesh, axis_names=axes,
        in_specs=in_specs, out_specs=out_specs, check_vma=False))(*args)


XLA = XlaCollectiveBackend()
PAL = PallasFusedBackend(interpret=True)


# ---------------------------------------------------------- quantizer
# ISSUE-11 satellite: wire accounting rounds UP, pack_int4 edge cases

def test_quantized_nbytes_rounds_up():
    # even/dividing: unchanged exact accounting
    assert quantized_nbytes(512, 8, 256) == 512 + 2 * 4
    assert quantized_nbytes(512, 4, 256) == 256 + 2 * 4
    # odd numel at int4 occupies the trailing half-filled byte
    assert quantized_nbytes(511, 4, 256) == 256 + 2 * 4
    # ragged final block still carries a full fp32 scale
    assert quantized_nbytes(257, 8, 256) == 257 + 2 * 4
    assert quantized_nbytes(1, 4, 256) == 1 + 4


def test_pack_int4_odd_length_raises():
    with pytest.raises(ValueError, match="even number of elements"):
        pack_int4(jnp.zeros((7,), jnp.int8))


def test_pack_int4_non_contiguous_roundtrip():
    # a transposed (non-contiguous) view must pack its ROW-MAJOR flatten
    # and round-trip exactly
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, size=(6, 4)), jnp.int8)
    qt = q.T  # [4, 6], non-contiguous view of q's buffer
    packed = pack_int4(qt)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(qt).reshape(-1))


# ---------------------------------------------------- backend resolution

def test_resolve_backend():
    assert resolve_backend("xla").name == "xla"
    b = resolve_backend("pallas")
    assert b.name == "pallas" and b.interpret  # off-TPU -> interpret mode
    assert resolve_backend("auto").name == "xla"  # off-TPU default
    with pytest.raises(ValueError, match="kernel backend"):
        resolve_backend("cuda")


def test_kernel_backend_config_validation():
    from deepspeed_tpu.config import CommCompressionConfig, ConfigError

    assert CommCompressionConfig.from_dict(
        {"kernel_backend": "pallas"}).kernel_backend == "pallas"
    assert CommCompressionConfig.from_dict({}).kernel_backend == "auto"
    with pytest.raises(ConfigError, match="kernel_backend"):
        CommCompressionConfig.from_dict({"kernel_backend": "cuda"})


# ------------------------------------------- all-gather-matmul parity

def _run_ag(backend, qspec, h, ws, topo, dim=1, dtype=jnp.float32):
    def spmd(w):
        y = backend.all_gather_matmul(h.astype(dtype), w[0].astype(dtype),
                                      "data", dim=dim, qspec=qspec)
        return y[None]

    return np.asarray(_spmd(topo, spmd, ws, in_specs=(P("data"),),
                            out_specs=P("data")))[0]


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_all_gather_matmul_bitexact(bits, dtype):
    """Fused ring dequant+matmul == unfused facade gather + matmul, bit
    for bit, across dtypes and QuantSpecs."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    spec = cc.QuantSpec(bits, 32)
    a = _run_ag(XLA, spec, h, ws, topo, dtype=dtype)
    b = _run_ag(PAL, spec, h, ws, topo, dtype=dtype)
    np.testing.assert_array_equal(a, b)


def test_fused_all_gather_matmul_dense_bitexact():
    """Compression off: the dense ring matmul must equal the dense
    gather + matmul bit for bit."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    np.testing.assert_array_equal(_run_ag(XLA, None, h, ws, topo),
                                  _run_ag(PAL, None, h, ws, topo))


def test_fused_all_gather_matmul_mixed_dtype_falls_back(reg):
    """Mixed-dtype operands (bf16 h, f32 w) must NOT fuse — the XLA
    reference feeds the weight at its own dtype into the dot, so a
    ring tile cast to h's dtype would silently diverge. Fallback is
    metered and stays bit-exact."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(16, 32)), jnp.bfloat16)
    ws = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)

    def spmd(backend):
        def f(w):
            return backend.all_gather_matmul(h, w[0], "data", dim=1,
                                             qspec=None)[None]
        return np.asarray(_spmd(topo, f, ws, in_specs=(P("data"),),
                                out_specs=P("data")))[0]

    a, b = spmd(XLA), spmd(PAL)
    np.testing.assert_array_equal(a, b)
    assert reg.counter("comm/facade/fused").value == 0
    assert reg.counter("comm/facade/fallbacks").value >= 1


def test_fused_all_gather_matmul_fallbacks_metered(reg):
    """Contraction-dim (dim=0) gathers and non-dividing shards must fall
    back to the unfused path bit-exactly, counted in
    comm/facade/fallbacks; clean fusions count under comm/facade/fused."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(4, 8, 64)), jnp.float32)  # dim-0 shard
    spec = cc.QuantSpec(8, 32)

    def spmd(w):
        # h [16, 32] @ gather(w [8, 64], dim=0) -> contraction-dim shard
        return PAL.all_gather_matmul(h, w[0], "data", dim=0, qspec=spec)[None]

    a = np.asarray(_spmd(topo, spmd, ws, in_specs=(P("data"),),
                         out_specs=P("data")))[0]

    def spmd_ref(w):
        return XLA.all_gather_matmul(h, w[0], "data", dim=0, qspec=spec)[None]

    b = np.asarray(_spmd(topo, spmd_ref, ws, in_specs=(P("data"),),
                         out_specs=P("data")))[0]
    np.testing.assert_array_equal(a, b)
    assert reg.counter("comm/facade/fallbacks").value >= 1
    assert reg.counter("comm/facade/fused").value == 0

    # non-dividing shard (numel % block != 0): the facade's dense
    # fallback runs and is counted
    before = reg.counter("comm/facade/fallbacks").value
    ws2 = jnp.asarray(rng.normal(size=(4, 32, 5)), jnp.float32)
    a2 = _run_ag(PAL, cc.QuantSpec(8, 256), h, ws2, topo)
    b2 = _run_ag(XLA, cc.QuantSpec(8, 256), h, ws2, topo)
    np.testing.assert_array_equal(a2, b2)
    assert reg.counter("comm/facade/fallbacks").value > before
    assert reg.counter("comm/facade/fused").value == 0

    # and a clean fusion increments the fused counter
    ws3 = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    _run_ag(PAL, cc.QuantSpec(8, 32), h, ws3, topo)
    assert reg.counter("comm/facade/fused/qwz_all_gather").value >= 1


# ------------------------------------- matmul-reduce-scatter parity

def _run_rs(backend, qspec, hs, gs, topo, **kw):
    def spmd(hh, gg):
        out = backend.matmul_reduce_scatter(
            hh[0], gg[0], outer_axis="data", outer_world=4, qspec=qspec,
            **kw)
        return out[None]

    return np.asarray(_spmd(topo, spmd, hs, gs,
                            in_specs=(P("data"), P("data")),
                            out_specs=P("data")))[0]


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_matmul_reduce_scatter_bitexact(bits):
    """In-kernel epilogue quantization + chunk exchange == unfused
    matmul + hierarchical_pmean, bit for bit."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(5)
    hs = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
    gs = jnp.asarray(rng.normal(size=(4, 16, 64)), jnp.float32)
    spec = cc.QuantSpec(bits, 32)
    np.testing.assert_array_equal(_run_rs(XLA, spec, hs, gs, topo),
                                  _run_rs(PAL, spec, hs, gs, topo))


def test_fused_matmul_reduce_scatter_dense_and_tiny_fallback(reg):
    """qspec=None and small-leaf floors delegate to the unfused backend
    bit-exactly."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(6)
    hs = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
    gs = jnp.asarray(rng.normal(size=(4, 16, 64)), jnp.float32)
    np.testing.assert_array_equal(_run_rs(XLA, None, hs, gs, topo),
                                  _run_rs(PAL, None, hs, gs, topo))
    # below the min_quant_size floor both paths take the dense mean
    spec = cc.QuantSpec(8, 32)
    kw = dict(min_quant_size=1 << 20)
    np.testing.assert_array_equal(_run_rs(XLA, spec, hs, gs, topo, **kw),
                                  _run_rs(PAL, spec, hs, gs, topo, **kw))
    assert reg.counter("comm/facade/fused").value == 0


# ----------------------------------------- matmul-all-reduce (decode)

def _run_ar(backend, qspec, xs, ws, topo):
    def spmd(xx, ww):
        return backend.matmul_all_reduce(xx[0], ww[0], "data",
                                         qspec=qspec)[None]

    return np.asarray(_spmd(topo, spmd, xs, ws,
                            in_specs=(P("data"), P("data")),
                            out_specs=P("data")))[0]


@pytest.mark.parametrize("qspec", [None, cc.QuantSpec(8, 32),
                                   cc.QuantSpec(4, 32)])
def test_fused_matmul_all_reduce_bitexact(qspec):
    """Decode MLP primitive: fused partial-matmul + chunked exchange ==
    unfused, bit for bit, dense and quantized."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    np.testing.assert_array_equal(_run_ar(XLA, qspec, xs, ws, topo),
                                  _run_ar(PAL, qspec, xs, ws, topo))


def test_dense_chunked_all_reduce_matches_psum():
    """The deterministic rank-ordered sum must agree with psum to fp32
    tolerance (order differs, values don't meaningfully)."""
    topo = Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(8)
    xs = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    got = _run_ar(XLA, None, xs, ws, topo)

    def spmd(xx, ww):
        y = jnp.matmul(xx[0], ww[0])
        return jax.lax.psum(y, "data")[None]

    ref = np.asarray(_spmd(topo, spmd, xs, ws,
                           in_specs=(P("data"), P("data")),
                           out_specs=P("data")))[0]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------- schedule + engine seam

def _batch(n=32, din=64, dout=64, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(n, din)).astype(np.float32),
            "y": rng.normal(size=(n, dout)).astype(np.float32)}


def _engine(kernel_backend, enabled=True, overlap="staged",
            dims=(64, 256, 512, 64), seed=0):
    mesh_mod.reset_topology()
    model = SequentialBlockModel(dims)
    engine, _, _, _ = dst.initialize(model=model, config={
        "train_batch_size": 32,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "comm_compression": {"enabled": enabled, "weight_bits": 8,
                             "grad_bits": 4, "overlap": overlap,
                             "kernel_backend": kernel_backend},
        "steps_per_print": 1000,
    }, rng=jax.random.PRNGKey(seed))
    return engine


@pytest.mark.parametrize("enabled", [True, False])
def test_engine_fused_backend_bitexact_vs_xla(reg, enabled):
    """The staged engine on the Pallas backend — fused gather-in-matmul
    forward, fused reduce-in-epilogue backward — must produce
    bit-identical losses AND parameters to the XLA-backend engine, with
    fusion actually engaging (counter) and contraction-dim blocks
    falling back (counter)."""
    batch = _batch()
    e_x = _engine("xla", enabled=enabled)
    e_p = _engine("pallas", enabled=enabled)
    l_x = [float(e_x.train_batch(batch)["loss"]) for _ in range(3)]
    l_p = [float(e_p.train_batch(batch)["loss"]) for _ in range(3)]
    assert l_x == l_p
    for a, b in zip(jax.tree_util.tree_leaves(e_x.params),
                    jax.tree_util.tree_leaves(e_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # dims (64,256,512,64): blocks 0/1 shard W on the output dim (fused)
    assert reg.counter("comm/facade/fused").value > 0
    if enabled:
        # block 2 shards W on the contraction dim: its weight never
        # enters the fused path (structural), and its quantized facade
        # ops still meter their own block-divide fallbacks
        assert reg.counter("comm/facade/fallbacks").value > 0


def test_engine_fused_serial_vs_overlapped_bitexact():
    """Issue order must stay semantics-free on the fused backend too."""
    batch = _batch()
    e_s = _engine("pallas", overlap="serial")
    e_o = _engine("pallas", overlap="staged")
    l_s = [float(e_s.train_batch(batch)["loss"]) for _ in range(2)]
    l_o = [float(e_o.train_batch(batch)["loss"]) for _ in range(2)]
    assert l_s == l_o
    for a, b in zip(jax.tree_util.tree_leaves(e_s.params),
                    jax.tree_util.tree_leaves(e_o.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_fused_one_trace_in_scan(reg):
    """The fused backend must not retrace inside the fused train_steps
    scan (the recompile gate of run_tests.sh, fused leg)."""
    batch = _batch()
    e = _engine("pallas")
    e.train_steps([batch, batch])
    e.train_steps([batch, batch])
    assert e.trace_count("train_steps_2") == 1
    assert reg.counter("train/recompiles").value == 0


def test_schedule_fused_ops_seam():
    """Zero3BlockSchedule honors the fused dict: fused blocks bypass
    gather/reduce entirely and return already-reduced grads."""
    from deepspeed_tpu.parallel.zero import FusedBlockOps

    model = SequentialBlockModel((8, 8, 8))
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(4, 8, 8)
    prog = model.zero3_blocks(params, batch)
    calls = {"gather": [], "reduce": [], "fwd": 0, "bwd": 0}

    def gather(i, blk):
        calls["gather"].append(i)
        return blk

    def reduce(i, g):
        calls["reduce"].append(i)
        return g

    def fwd(blk, h):
        calls["fwd"] += 1
        return prog.block_fns[0](blk, h)

    def bwd(blk, h_in, g_out):
        calls["bwd"] += 1
        _, vjp = jax.vjp(prog.block_fns[0], blk, h_in)
        g_blk, g_h = vjp(g_out)
        return g_blk, g_h

    sched = Zero3BlockSchedule(gather, reduce, overlapped=True,
                               fused={0: FusedBlockOps(fwd, bwd)})
    loss, grads = sched.loss_and_grads(prog, jnp.ones([]))
    assert calls["fwd"] == 1 and calls["bwd"] == 1
    # block 0 never gathered/reduced by the schedule; block 1 is
    assert 0 not in calls["gather"] and 0 not in calls["reduce"]
    assert 1 in calls["gather"] and 1 in calls["reduce"]
    assert grads[0] is not None and grads[1] is not None
    # and the result matches the all-generic schedule bit for bit
    sched_ref = Zero3BlockSchedule(lambda i, b: b, lambda i, g: g,
                                   overlapped=True)
    loss_ref, grads_ref = sched_ref.loss_and_grads(prog, jnp.ones([]))
    np.testing.assert_array_equal(np.asarray(loss), np.asarray(loss_ref))
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- decode path

def test_tp_decode_fused_mlp(reg):
    """Under TP the inference engine binds the fused backend and the
    decode MLP all-reduce runs through it (fused counter); greedy decode
    tokens match the default GSPMD path."""
    from deepspeed_tpu.inference.engine import (InferenceConfig,
                                                InferenceEngine)
    from deepspeed_tpu.models import Llama

    def gen(kb):
        mesh_mod.reset_topology()
        model = Llama("tiny", d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, d_ff=128, vocab_size=256,
                      max_seq_len=128, use_flash=False)
        eng = InferenceEngine(
            model, InferenceConfig(tensor_parallel=2, dtype="float32",
                                   kernel_backend=kb),
            rng=jax.random.PRNGKey(0))
        ids = np.arange(1, 9, dtype=np.int32)[None].repeat(4, 0)
        return np.asarray(eng.generate(jnp.asarray(ids), max_new_tokens=8))

    ref = gen("xla")
    assert reg.counter("comm/facade/fused/decode_mlp_all_reduce").value == 0
    got = gen("pallas")
    assert reg.counter("comm/facade/fused/decode_mlp_all_reduce").value >= 1
    np.testing.assert_array_equal(ref, got)


# ----------------------------------------------------- analytic model

def test_modeled_exposure_per_tile_below_per_layer():
    """Per-tile stage counts must cut the modeled exposure strictly
    below the PR-10 per-layer number whenever any fill/drain remains,
    and tiles_per_block=1 must reproduce the old model exactly."""
    kw = dict(param_bytes=14e9, grad_bytes=14e9, n_blocks=32,
              compute_s=1.1, link_bps=300e9, world=64,
              weight_qspec=cc.QuantSpec(8, 256),
              grad_qspec=cc.QuantSpec(4, 256),
              weight_itemsize=2, grad_itemsize=2)
    base = cc.modeled_exposure(**kw)
    tiled = cc.modeled_exposure(tiles_per_block=63, **kw)
    assert base["tiles_per_block"] == 1.0
    assert tiled["overlapped_compressed_s"] < base["overlapped_compressed_s"]
    # backward compat: the tiles=1 model is the PR-10 model
    legacy = {k: v for k, v in base.items() if k != "tiles_per_block"}
    again = {k: v for k, v in cc.modeled_exposure(tiles_per_block=1,
                                                  **kw).items()
             if k != "tiles_per_block"}
    assert legacy == again


def test_modeled_decode_ab():
    out = cc.modeled_decode_ab(d_model=4096, d_ff=11008, tp=8,
                               link_bps=300e9, peak_flops=459e12)
    assert out["decode_mlp_fused_s"] < out["decode_mlp_unfused_s"]
    assert out["fused_speedup"] > 1.0
    assert out["exposed_comm_fused_s"] <= out["exposed_comm_unfused_s"]
    # degenerate: no TP, no comm, no speedup
    solo = cc.modeled_decode_ab(d_model=4096, d_ff=11008, tp=1,
                                link_bps=300e9, peak_flops=459e12)
    assert solo["t_allreduce_s"] == 0.0
    assert solo["decode_mlp_fused_s"] == solo["decode_mlp_unfused_s"]
