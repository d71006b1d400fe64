"""Compressed-collectives facade + T3 staged overlap schedule
(comm/compressed.py, parallel/zero.py Zero3BlockSchedule,
docs/communication.md).

Covers the ISSUE-10 acceptance surface on the CPU mesh: int8/int4
round-trip error bounds, hierarchical two-hop reduce vs single-hop
equivalence, serial-vs-overlapped bit-exactness (compression off) and
tolerance (compression on), compressed-vs-dense convergence parity,
one-trace staged scans, and the bytes-on-wire ledger schema (v2
wire_bytes, backward-compatible with archived v1 records)."""

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as dst
from deepspeed_tpu.comm import compressed as cc
from deepspeed_tpu.comm.comm import (CommsLogger, configure_comms_logger,
                                     get_comms_logger)
from deepspeed_tpu.ops.quantizer import (dequantize_blockwise, pack_int4,
                                         quantize_blockwise,
                                         quantized_nbytes, unpack_int4)
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import Topology
from deepspeed_tpu.parallel.zero import (BlockProgram, SequentialBlockModel,
                                         Zero3BlockSchedule)


@pytest.fixture(autouse=True)
def _fresh_topology():
    mesh_mod.reset_topology()
    yield
    mesh_mod.reset_topology()


def _batch(n=32, in_dim=64, out_dim=64, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(n, in_dim)).astype(np.float32),
            "y": rng.normal(size=(n, out_dim)).astype(np.float32)}


def _staged_engine(cc_cfg, dims=(64, 256, 256, 64), lr=1e-2, extra=None,
                   seed=0):
    mesh_mod.reset_topology()
    model = SequentialBlockModel(dims)
    cfg = {
        "train_batch_size": 32,
        "optimizer": {"type": "adamw", "params": {"lr": lr}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "comm_compression": cc_cfg,
        "steps_per_print": 1000,
        **(extra or {}),
    }
    engine, _, _, _ = dst.initialize(model=model, config=cfg,
                                     rng=jax.random.PRNGKey(seed))
    return engine


def _param_leaves(engine):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(engine.params)]


# ---------------------------------------------------------------- quant
@pytest.mark.parametrize("bits", [8, 4])
def test_roundtrip_within_documented_bound(bits):
    """|x - deq(q(x))| <= scale/2 per element — the bound QuantSpec
    advertises and the quant-comm gate enforces."""
    spec = cc.QuantSpec(bits, 256)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8192,)) * 3,
                    jnp.float32)
    q, s, deq = cc._quant_roundtrip(x, spec)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    per_block_bound = np.repeat(np.asarray(s) * 0.5, spec.block)
    assert (err <= per_block_bound + 1e-6).all()
    # and the rel-to-block-absmax form matches the spec's constant
    blocks = np.asarray(x).reshape(-1, spec.block)
    absmax = np.abs(blocks).max(axis=1)
    rel = (err.reshape(-1, spec.block).max(axis=1)
           / np.maximum(absmax, 1e-12))
    assert (rel <= spec.rel_error_bound + 1e-6).all()


def test_int4_pack_unpack_roundtrip_exact():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.integers(-8, 8, size=4096), jnp.int8)
    packed = pack_int4(q)
    assert packed.size == q.size // 2 and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(q))


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


def test_quant_spec_validation():
    with pytest.raises(ValueError):
        cc.QuantSpec(5, 256)
    with pytest.raises(ValueError):
        cc.QuantSpec(8, 255)
    assert cc.QuantSpec(4, 256).rel_error_bound == pytest.approx(0.5 / 7)
    assert not cc.QuantSpec(8, 256).divides(100)
    assert cc.QuantSpec(8, 256).divides(2048, world=4)
    assert not cc.QuantSpec(8, 256).divides(2048, world=3)


# ------------------------------------------------------------ collectives
def _run_spmd(topo, fn, *args, axes={"data"}, in_specs=None, out_specs=None):
    return jax.jit(jax.shard_map(
        fn, mesh=topo.mesh, axis_names=axes,
        in_specs=in_specs, out_specs=out_specs, check_vma=False))(*args)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits,tol", [(8, 0.005), (4, 0.08)])
def test_quantized_all_gather_matches_dense(bits, tol, dtype):
    topo = Topology.build_virtual({"data": 4})
    n = 2048
    xs = jnp.asarray(np.random.default_rng(2).normal(size=(4, n)), dtype)
    # the gather dequantizes into the shard's own dtype: a bf16 weight
    # adds its rounding (2^-8 of the value) to the quantization error
    tol += 2.0 ** -8 if dtype == jnp.bfloat16 else 0.0

    def spmd(x):
        g = cc.quantized_all_gather(x[0], "data", dim=0,
                                    qspec=cc.QuantSpec(bits, 256))
        return g[None]

    g = _run_spmd(topo, spmd, xs, in_specs=(P("data"),),
                  out_specs=P("data"))
    assert g.dtype == dtype
    ref = np.asarray(xs, np.float32).reshape(-1)
    got = np.asarray(g, np.float32)[0]
    assert np.abs(got - ref).max() / np.abs(ref).max() < tol
    # rank order must be preserved exactly (rank-major concat)
    assert np.abs(got[:n] - ref[:n]).max() < tol * np.abs(ref).max()


def test_quantized_all_gather_fallback_is_dense_bitexact():
    """Indivisible shard -> clean fallback: bit-identical to the dense
    gather, wire == logical in the ledger, fallback counted."""
    from deepspeed_tpu.telemetry import MetricsRegistry, get_registry, set_registry

    topo = Topology.build_virtual({"data": 4})
    n = 100   # not block-divisible
    xs = jnp.asarray(np.random.default_rng(3).normal(size=(4, n)),
                     jnp.float32)
    log = get_comms_logger()
    old_enabled = log.enabled
    configure_comms_logger(True)
    # the ledger is process-global and cumulative: start from a clean
    # slate or any earlier test that recorded a COMPRESSED qwz row
    # (e.g. the overlap profiler's measurement drives) breaks the
    # wire == logical assertion below
    log.reset()
    old_reg = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        def spmd(x):
            g = cc.quantized_all_gather(x[0], "data", dim=0,
                                        qspec=cc.QuantSpec(8, 256))
            return g[None]

        g = _run_spmd(topo, spmd, xs, in_specs=(P("data"),),
                      out_specs=P("data"))
        np.testing.assert_array_equal(np.asarray(g)[0],
                                      np.asarray(xs).reshape(-1))
        assert reg.counter("comm/facade/fallbacks").value >= 1
        totals = log.snapshot_totals()
        assert totals["qwz_all_gather"]["wire_bytes"] == \
            totals["qwz_all_gather"]["bytes"]
    finally:
        set_registry(old_reg)
        configure_comms_logger(old_enabled)
        log.reset()


def test_hierarchical_pmean_dense_equals_flat_mean():
    """qspec=None: two dense hops (inner then outer) must equal the flat
    mean over the whole group to fp accuracy."""
    topo = Topology.build_virtual({"data": 8, "zshard": 2})
    n = 1024
    xs = jnp.asarray(np.random.default_rng(4).normal(size=(8, n)),
                     jnp.float32)

    def spmd(x):
        y = cc.hierarchical_pmean(x[0], outer_axis="data", outer_world=4,
                                  inner_axis="zshard", inner_world=2,
                                  qspec=None)
        return y[None]

    y = _run_spmd(topo, spmd, xs, axes={"data", "zshard"},
                  in_specs=(P(("data", "zshard")),),
                  out_specs=P(("data", "zshard")))
    dense = np.asarray(xs).mean(axis=0)
    np.testing.assert_allclose(np.asarray(y)[0], dense, rtol=1e-5,
                               atol=1e-6)
    # replicated result: every rank identical
    np.testing.assert_array_equal(np.asarray(y)[0], np.asarray(y)[-1])


def test_hierarchical_pmean_small_leaf_stays_dense_and_is_metered():
    """A leaf under tree_hierarchical_pmean's floor (4 * outer_world *
    block elements) is reduced dense: bit-equal to the plain pmean, and
    counted under comm/facade/fallbacks; the leaf over the floor is
    quantized (close, not equal)."""
    from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                         set_registry)

    topo = Topology.build_virtual({"data": 4})
    qspec = cc.QuantSpec(8, 32)
    floor = 4 * 4 * qspec.block
    rng = np.random.default_rng(9)
    # both leaves block- and chunk-divide: only the floor tells them apart
    grads = {"small": jnp.asarray(rng.normal(size=(4, floor // 2)),
                                  jnp.float32),
             "large": jnp.asarray(rng.normal(size=(4, floor)), jnp.float32)}
    assert qspec.divides(floor // 2, 4)
    old_reg = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        def spmd(g):
            local = jax.tree_util.tree_map(lambda x: x[0], g)
            red = cc.tree_hierarchical_pmean(local, outer_axis="data",
                                             outer_world=4, qspec=qspec)
            ref = jax.tree_util.tree_map(lambda x: cc.pmean(x, "data"),
                                         local)
            return jax.tree_util.tree_map(lambda x: x[None], (red, ref))

        red, ref = _run_spmd(topo, spmd, grads, in_specs=(P("data"),),
                             out_specs=P("data"))
        np.testing.assert_array_equal(np.asarray(red["small"]),
                                      np.asarray(ref["small"]))
        assert not np.array_equal(np.asarray(red["large"]),
                                  np.asarray(ref["large"]))
        np.testing.assert_allclose(np.asarray(red["large"]),
                                   np.asarray(ref["large"]), atol=0.05)
        assert reg.counter("comm/facade/fallbacks").value == 1
        assert reg.counter(
            "comm/facade/fallbacks/qgz_inter_reduce_dense").value == 1
    finally:
        set_registry(old_reg)


@pytest.mark.parametrize("bits,tol", [(8, 0.02), (4, 0.25)])
def test_hierarchical_quantized_close_to_single_hop(bits, tol):
    """The two-hop reduce (dense zshard + quantized data) must agree
    with the single-hop quantized reduce over the flat group within the
    quantization tolerance — hierarchy reshapes the wire, not the math."""
    n = 4096
    rng = np.random.default_rng(5)
    data = rng.normal(size=(8, n)).astype(np.float32)
    dense = data.mean(axis=0)
    spec = cc.QuantSpec(bits, 256)

    # hierarchical over data(4) x zshard(2)
    topo = Topology.build_virtual({"data": 8, "zshard": 2})

    def spmd_h(x):
        y = cc.hierarchical_pmean(x[0], outer_axis="data", outer_world=4,
                                  inner_axis="zshard", inner_world=2,
                                  qspec=spec)
        return y[None]

    yh = np.asarray(_run_spmd(topo, spmd_h, jnp.asarray(data),
                              axes={"data", "zshard"},
                              in_specs=(P(("data", "zshard")),),
                              out_specs=P(("data", "zshard"))))[0]
    mesh_mod.reset_topology()

    # single-hop over data(8)
    topo = Topology.build_virtual({"data": 8})

    def spmd_f(x):
        y = cc.hierarchical_pmean(x[0], outer_axis="data", outer_world=8,
                                  qspec=spec)
        return y[None]

    yf = np.asarray(_run_spmd(topo, spmd_f, jnp.asarray(data),
                              in_specs=(P("data"),),
                              out_specs=P("data")))[0]
    scale = np.abs(dense).max()
    assert np.abs(yh - dense).max() / scale < tol
    assert np.abs(yf - dense).max() / scale < tol
    assert np.abs(yh - yf).max() / scale < 2 * tol


# ------------------------------------------------------- staged schedule
def test_staged_schedule_serial_vs_overlapped_bitexact():
    """Identical dataflow, different issue order: results must be
    bit-identical — pins both paths against semantic drift."""
    model = SequentialBlockModel((16, 32, 32, 8))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": jnp.asarray(np.random.default_rng(0).normal(
                 size=(8, 16)), jnp.float32),
             "y": jnp.asarray(np.random.default_rng(1).normal(
                 size=(8, 8)), jnp.float32)}
    ident = lambda i, t: t  # noqa: E731 — no mesh: gather/reduce identity

    outs = {}
    for mode in (False, True):
        sched = Zero3BlockSchedule(ident, ident, overlapped=mode)
        prog = model.zero3_blocks(params, batch)
        loss, grads = jax.jit(lambda: sched.loss_and_grads(
            prog, jnp.ones([], jnp.float32)))()
        outs[mode] = (np.asarray(loss),
                      [np.asarray(l) for l in
                       jax.tree_util.tree_leaves(prog.merge(grads))])
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    for a, b in zip(outs[False][1], outs[True][1]):
        np.testing.assert_array_equal(a, b)


def test_staged_schedule_matches_jax_grad_reference():
    """The per-block vjp chain must equal jax.grad of the composed loss
    bit-for-bit (same primitives, same order within each block)."""
    model = SequentialBlockModel((16, 32, 32, 8))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": jnp.asarray(np.random.default_rng(0).normal(
                 size=(8, 16)), jnp.float32),
             "y": jnp.asarray(np.random.default_rng(1).normal(
                 size=(8, 8)), jnp.float32)}
    ident = lambda i, t: t  # noqa: E731

    sched = Zero3BlockSchedule(ident, ident, overlapped=True)
    prog = model.zero3_blocks(params, batch)
    loss, grads = jax.jit(lambda: sched.loss_and_grads(
        prog, jnp.ones([], jnp.float32)))()
    grads = prog.merge(grads)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch)))(params)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_staged_schedule_regathers_in_backward():
    """The memory contract: forward gathers each block once, backward
    RE-gathers it (2 gathers per block per step) instead of holding vjp
    residuals over the full unsharded model — the modeled_exposure
    booking and ZeRO-3 partitioning both depend on it."""
    model = SequentialBlockModel((16, 32, 32, 8))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": jnp.asarray(np.random.default_rng(0).normal(
                 size=(8, 16)), jnp.float32),
             "y": jnp.asarray(np.random.default_rng(1).normal(
                 size=(8, 8)), jnp.float32)}
    for overlapped in (False, True):
        gathers = []
        sched = Zero3BlockSchedule(
            lambda i, t: (gathers.append(i), t)[1],
            lambda i, t: t, overlapped=overlapped)
        prog = model.zero3_blocks(params, batch)
        sched.loss_and_grads(prog, jnp.ones([], jnp.float32))
        L = model.n_blocks
        assert len(gathers) == 2 * L, (overlapped, gathers)
        assert sorted(gathers) == sorted(list(range(L)) * 2)


def test_hierarchical_inter_slice_wire_is_chunked():
    """ZeRO++ hierarchy: the slow inter-slice exchange must run on the
    1/inner_world reduce-scattered chunk, not the full tensor — the
    ledger's logical bytes for the inter hop pin it."""
    log = get_comms_logger()
    old_enabled = log.enabled
    log.reset()
    configure_comms_logger(True)
    try:
        topo = Topology.build_virtual({"data": 8, "zshard": 2})
        n = 8192
        xs = jnp.asarray(np.random.default_rng(8).normal(size=(8, n)),
                         jnp.float32)
        spec = cc.QuantSpec(8, 256)

        def spmd(x):
            y = cc.hierarchical_pmean(x[0], outer_axis="data",
                                      outer_world=4, inner_axis="zshard",
                                      inner_world=2, qspec=spec)
            return y[None]

        y = _run_spmd(topo, spmd, xs, axes={"data", "zshard"},
                      in_specs=(P(("data", "zshard")),),
                      out_specs=P(("data", "zshard")))
        dense = np.asarray(xs).mean(axis=0)
        assert np.abs(np.asarray(y)[0] - dense).max() \
            / np.abs(dense).max() < 0.02
        totals = log.snapshot_totals()
        # inter hop carries the half-size chunk (n/inner_world fp32)
        assert totals["qgz_inter_reduce_scatter"]["bytes"] == n // 2 * 4
        assert "qgz_intra_reduce_scatter" in totals
        assert "qgz_intra_all_gather" in totals
    finally:
        configure_comms_logger(old_enabled)
        log.reset()


def test_facade_pmax_replicates_true_max():
    """Error-stat reduction: a per-rank local max must come back as the
    global max on every rank (regression: it was declared replicated
    without a pmax, handing the host an arbitrary shard's value)."""
    topo = Topology.build_virtual({"data": 4})

    def spmd(x):
        local = jnp.max(x[0])          # rank-dependent scalar
        return cc.pmax(local, ("data",))[None]

    xs = jnp.asarray(np.arange(4, dtype=np.float32).reshape(4, 1) * 10)
    out = _run_spmd(topo, spmd, xs, in_specs=(P("data"),),
                    out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(out), np.full((4,), 30.0))


# ------------------------------------------------------------ engine
def test_engine_staged_serial_vs_overlapped_bitexact_uncompressed():
    batch = _batch()
    e_ser = _staged_engine({"enabled": False, "overlap": "serial"})
    e_ovl = _staged_engine({"enabled": False, "overlap": "staged"})
    assert e_ser._staged_mode == "serial" and e_ovl._staged_mode == "staged"
    l_ser = [float(e_ser.train_batch(batch)["loss"]) for _ in range(4)]
    l_ovl = [float(e_ovl.train_batch(batch)["loss"]) for _ in range(4)]
    assert l_ser == l_ovl
    for a, b in zip(_param_leaves(e_ser), _param_leaves(e_ovl)):
        np.testing.assert_array_equal(a, b)


def test_engine_compressed_converges_close_to_dense():
    """Short seeded run: int8 weights + int8 grads track the dense
    trajectory; int4 grads stay finite and learning."""
    batch = _batch()
    dense = _staged_engine({"enabled": False})
    comp8 = _staged_engine({"enabled": True, "weight_bits": 8,
                            "grad_bits": 8})
    comp4 = _staged_engine({"enabled": True, "weight_bits": 8,
                            "grad_bits": 4})
    ld = [float(dense.train_batch(batch)["loss"]) for _ in range(6)]
    l8 = [float(comp8.train_batch(batch)["loss"]) for _ in range(6)]
    l4 = [float(comp4.train_batch(batch)["loss"]) for _ in range(6)]
    assert ld[-1] < ld[0] and l8[-1] < l8[0] and l4[-1] < l4[0]
    np.testing.assert_allclose(l8, ld, rtol=0.05, atol=0.01)
    np.testing.assert_allclose(l4, ld, rtol=0.25, atol=0.05)
    # quantization must actually be live (not silently fallen back)
    assert l8 != ld


def test_engine_staged_requires_model_own_loss():
    """A user-supplied loss_fn must disable the staged path: its loss
    comes from zero3_blocks' loss_tail, so engaging it silently would
    optimize a different objective than the one passed to initialize()."""
    mesh_mod.reset_topology()
    model = SequentialBlockModel((64, 256, 256, 64))

    def custom_loss(params, batch, rng):
        return model.loss(params, batch, rng) + 0.1

    engine, _, _, _ = dst.initialize(
        model=model, loss_fn=custom_loss,
        params=model.init(jax.random.PRNGKey(0)),
        config={
            "train_batch_size": 32,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0},
            "steps_per_print": 1000,
        })
    assert engine._staged_mode is None
    # the custom loss (with its +0.1 shift) is what actually trains
    batch = _batch()
    loss = float(engine.train_batch(batch)["loss"])
    mesh_mod.reset_topology()
    ref, _, _, _ = dst.initialize(model=SequentialBlockModel((64, 256, 256, 64)),
                                  config={
        "train_batch_size": 32,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "comm_compression": {"enabled": False, "overlap": "off"},
        "steps_per_print": 1000,
    }, rng=jax.random.PRNGKey(0))
    ref_loss = float(ref.train_batch(batch)["loss"])
    assert loss == pytest.approx(ref_loss + 0.1, abs=1e-6)


def test_engine_auto_threshold():
    """'auto' turns compression on exactly at the mesh-size threshold."""
    on = _staged_engine({"enabled": "auto", "mesh_size_threshold": 8})
    off = _staged_engine({"enabled": "auto", "mesh_size_threshold": 16})
    assert on._qwz and on._qgz
    assert not off._qwz and not off._qgz
    # explicit ZeRO++ knobs still opt in below the threshold
    explicit = _staged_engine(
        {"enabled": "auto", "mesh_size_threshold": 16},
        extra={"zero_optimization": {
            "stage": 3, "stage3_param_persistence_threshold": 0,
            "zero_quantized_gradients": True}})
    assert explicit._qgz and not explicit._qwz


def test_kernel_backend_is_an_unknown_key():
    """The facade has one implementation: a config that still names a
    kernel backend gets what every unknown key gets — one warning, and
    the same settings as without the key, whatever it names."""
    import dataclasses

    from deepspeed_tpu.config import CommCompressionConfig
    from deepspeed_tpu.utils.logging import logger

    records = []
    handler = logging.Handler()
    handler.emit = records.append  # the package logger does not propagate
    logger.addHandler(handler)
    try:
        got = [CommCompressionConfig.from_dict(
            {"grad_bits": 4, "kernel_backend": value})
            for value in ("pallas", "bogus")]
    finally:
        logger.removeHandler(handler)
    plain = CommCompressionConfig.from_dict({"grad_bits": 4})
    assert got == [plain, plain]
    assert "kernel_backend" not in {f.name for f in dataclasses.fields(plain)}
    assert [r.getMessage() for r in records] == [
        "Unknown config key 'kernel_backend' in section "
        "'comm_compression' — ignored"] * 2


def test_engine_staged_one_trace_in_fused_scan():
    """The staged schedule inside train_steps(k): one trace per program,
    zero recompile-guard hits across repeated calls."""
    from deepspeed_tpu.telemetry import MetricsRegistry, get_registry, set_registry

    old_reg = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        batch = _batch()
        e = _staged_engine({"enabled": True, "grad_bits": 4})
        e.train_steps([batch, batch])
        e.train_steps([batch, batch])
        e.train_steps([batch, batch])
        assert e.trace_count("train_steps_2") == 1
        assert reg.counter("train/recompiles").value == 0
    finally:
        set_registry(old_reg)


def test_engine_error_stats_within_bound():
    batch = _batch()
    e = _staged_engine({"enabled": True, "weight_bits": 8, "grad_bits": 4,
                        "error_stats": True})
    assert e._wants_quant_err
    m = e.train_batch(batch)
    err = float(m["quant_rel_err"])
    # per-tensor rel err is bounded by the per-block bound of the widest
    # hop (int4 here)
    assert 0.0 <= err <= cc.QuantSpec(4, 256).rel_error_bound + 1e-6


def test_engine_ledger_ratios():
    """The acceptance-criteria ratios, measured off the ledger: >= 2x on
    the weight all-gather wire, >= 4x on the inter-slice gradient hop."""
    log = get_comms_logger()
    old_enabled = log.enabled
    log.reset()
    configure_comms_logger(True)
    try:
        batch = _batch()
        e = _staged_engine({"enabled": True, "weight_bits": 8,
                            "grad_bits": 4})
        e.train_batch(batch)
        totals = log.snapshot_totals()
        wg = totals["qwz_all_gather"]
        gr = totals["qgz_inter_reduce_scatter"]
        assert wg["bytes"] / wg["wire_bytes"] >= 2.0
        assert gr["bytes"] / gr["wire_bytes"] >= 4.0
    finally:
        configure_comms_logger(old_enabled)
        log.reset()


def test_engine_degenerate_mesh_keeps_fast_hop_dense():
    """data=1 x zshard=N (hpZ partition == dp): there is no slow hop, so
    the facade must NOT quantize across the fast-ICI zshard axis — the
    documented intra-slice-stays-dense contract on degenerate meshes."""
    log = get_comms_logger()
    old_enabled = log.enabled
    log.reset()
    configure_comms_logger(True)
    try:
        mesh_mod.reset_topology()
        model = SequentialBlockModel((64, 256, 256, 64))
        engine, _, _, _ = dst.initialize(model=model, config={
            "train_batch_size": 32,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0,
                                  "zero_hpz_partition_size": 8},
            "comm_compression": {"enabled": True, "grad_bits": 4},
            "steps_per_print": 1000,
        }, rng=jax.random.PRNGKey(0))
        assert engine.topo.axis_size("data") == 1
        assert engine.topo.axis_size("zshard") == 8
        outer, outer_world, inner, inner_world = engine._facade_axes()
        assert outer is None and outer_world == 1
        assert inner == "zshard" and inner_world == 8
        batch = _batch()
        losses = [float(engine.train_batch(batch)["loss"])
                  for _ in range(3)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        totals = log.snapshot_totals()
        # nothing quantized crossed the wire; the zshard reduce is the
        # dense intra hop
        assert "qgz_inter_reduce_scatter" not in totals
        assert "qwz_all_gather" not in totals
        assert "qgz_intra_reduce" in totals
        intra = totals["qgz_intra_reduce"]
        assert intra["wire_bytes"] == intra["bytes"]
    finally:
        configure_comms_logger(old_enabled)
        log.reset()


def test_comm_step_delta_wire_bytes_not_double_counted():
    """First-step comm breakdown on the dense (non-facade) path: the
    synthetic grad-reduction record must be subtracted wire_bytes-
    included, so the emitted delta keeps wire == logical for dense ops
    (regression: the one-time append's wire_bytes survived the
    subtraction and was re-added by the per-step merge)."""
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from simple_model import init_mlp_params, mlp_loss

    log = get_comms_logger()
    old_enabled = log.enabled
    log.reset()
    configure_comms_logger(True)
    try:
        mesh_mod.reset_topology()
        params = init_mlp_params(jax.random.PRNGKey(0))
        engine, _, _, _ = dst.initialize(loss_fn=mlp_loss, params=params,
                                         config={
            "train_batch_size": 32,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 1000,
        })
        rng = np.random.default_rng(0)
        batch = {"x": rng.normal(size=(32, 8)).astype(np.float32),
                 "y": rng.normal(size=(32, 4)).astype(np.float32)}
        engine.train_batch(batch)
        delta, _ = engine._comm_step_delta()
        entry = delta["reduce_scatter"]
        assert entry["count"] == 1.0
        assert entry["wire_bytes"] == entry["bytes"]
    finally:
        configure_comms_logger(old_enabled)
        log.reset()


def test_measure_comm_latencies_backfills_facade_ops():
    """The timed replay must recognize the facade op names and backfill
    real (wire-sized) latencies — otherwise the shipped compressed path
    would report comm_s == 0 forever."""
    from deepspeed_tpu.comm.comm import measure_comm_latencies

    log = get_comms_logger()
    old_enabled = log.enabled
    log.reset()
    configure_comms_logger(True)
    topo = Topology.build_virtual({"data": 4})
    mesh_mod.set_topology(topo)
    try:
        n = 4096
        xs = jnp.asarray(np.random.default_rng(7).normal(size=(4, n)),
                         jnp.float32)

        def spmd(x):
            g = cc.quantized_all_gather(x[0], "data", dim=0,
                                        qspec=cc.QuantSpec(8, 256))
            y = cc.hierarchical_pmean(x[0], outer_axis="data",
                                      outer_world=4,
                                      qspec=cc.QuantSpec(4, 256))
            return g[None], y[None]

        _run_spmd(topo, spmd, xs, in_specs=(P("data"),),
                  out_specs=(P("data"), P("data")))
        measure_comm_latencies(mesh=topo.mesh, iters=2)
        totals = log.snapshot_totals()
        for op in ("qwz_all_gather", "qgz_inter_reduce_scatter",
                   "qgz_inter_all_gather"):
            assert totals[op]["time_s"] > 0.0, f"{op} not backfilled"
    finally:
        configure_comms_logger(old_enabled)
        log.reset()


# ------------------------------------------------------------- ledger
def test_snapshot_totals_v2_and_v1_backcompat():
    log = CommsLogger(enabled=True)
    log.append("all_gather", 1000, 0.0, 4, "data")
    log.append("qwz_all_gather", 1000, 0.0, 4, "data", wire_bytes=266)
    t = log.snapshot_totals()
    assert t["all_gather"]["wire_bytes"] == 1000      # dense: wire == logical
    assert t["qwz_all_gather"]["wire_bytes"] == 266

    from deepspeed_tpu.telemetry.spans import validate_step_record

    base = {"schema_version": 1, "step": 1, "timestamp": 0.0,
            "wall_time_s": 0.1, "tokens_per_s": 1.0, "samples_per_s": 1.0,
            "mfu": 0.0, "memory": {}, "stalled": False}
    # archived v1 record: comm entries without wire_bytes must validate
    v1 = dict(base, comm={"all_reduce": {"count": 1, "bytes": 8,
                                         "time_s": 0.0}})
    assert validate_step_record(v1) == []
    # v2 record with wire_bytes validates; junk wire_bytes is rejected
    v2 = dict(base, comm={"qwz_all_gather": {
        "count": 1, "bytes": 1000, "wire_bytes": 266, "time_s": 0.0}})
    assert validate_step_record(v2) == []
    bad = dict(base, comm={"qwz_all_gather": {
        "count": 1, "bytes": 1000, "wire_bytes": "nope", "time_s": 0.0}})
    assert any("wire_bytes" in e for e in validate_step_record(bad))
    # optional quant_rel_err field type-checks
    assert validate_step_record(dict(base, comm={},
                                     quant_rel_err=0.01)) == []
    assert validate_step_record(dict(base, comm={},
                                     quant_rel_err="x")) != []


def test_modeled_exposure_shape():
    """The analytic T3 exposure model: overlap + compression must cut
    exposed comm >= 50% vs the serial dense booking whenever per-block
    comm fits inside the per-block compute window (the NORTHSTAR
    geometry)."""
    out = cc.modeled_exposure(
        param_bytes=14e9, grad_bytes=14e9, n_blocks=32, compute_s=1.1,
        link_bps=300e9, world=64,
        weight_qspec=cc.QuantSpec(8, 256), grad_qspec=cc.QuantSpec(4, 256),
        weight_itemsize=2, grad_itemsize=2)
    assert out["overlapped_compressed_s"] < out["serial_dense_s"]
    assert out["exposure_reduction_vs_serial"] >= 0.5
    assert out["weight_wire_ratio"] > 1.9
    assert out["grad_wire_ratio"] > 3.8
