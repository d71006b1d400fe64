"""Quantization ops, compressed collectives, and 1-bit Adam
(reference: tests/unit/ops/quantizer, tests/onebit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.quantizer import (
    dequantize_blockwise,
    fake_quantize,
    quantize_blockwise,
    quantized_nbytes,
)
from deepspeed_tpu.parallel import mesh as mesh_mod


# ----------------------------------------------------------------------
# quantizer
@pytest.mark.parametrize("bits,symmetric", [(8, True), (8, False), (4, True)])
def test_quantize_roundtrip_error(bits, symmetric):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 512)) * 3
    q, s, z = quantize_blockwise(x, bits=bits, block=128, symmetric=symmetric)
    assert q.dtype in (jnp.int8, jnp.uint8)
    back = dequantize_blockwise(q, s, z, block=128)
    # quantization error bounded by ~scale/2 per element
    err = np.abs(np.asarray(back - x))
    max_scale = float(np.max(np.asarray(s)))
    assert err.max() <= max_scale * 0.51 + 1e-6


def test_quantize_int4_range():
    x = jax.random.normal(jax.random.PRNGKey(1), (1024,))
    q, s, _ = quantize_blockwise(x, bits=4, block=256)
    assert np.asarray(q).min() >= -8 and np.asarray(q).max() <= 7


def test_fake_quantize_straight_through():
    x = jax.random.normal(jax.random.PRNGKey(2), (512,))
    y = fake_quantize(x, bits=8, block=128)
    assert y.shape == x.shape and y.dtype == x.dtype
    g = jax.grad(lambda x: jnp.sum(fake_quantize(x, 8, 128) ** 2))(x)
    # STE: gradient passes through as 2*fq(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(y), rtol=1e-5)


def test_quantized_nbytes_volume():
    # int8 + fp32 scales per 256-block: ~4x smaller than fp32
    n = 1 << 20
    assert quantized_nbytes(n, 8, 256) < n * 4 / 3.9
    assert quantized_nbytes(n, 4, 256) < n * 4 / 7.5


# ----------------------------------------------------------------------
# compressed collectives
def test_onebit_allreduce_matches_dense_in_expectation():
    """Error feedback: averaged over steps, compressed allreduce tracks the
    dense mean (residuals don't accumulate)."""
    from deepspeed_tpu.parallel.compressed import onebit_allreduce

    topo = mesh_mod.Topology.build_virtual({"data": 4})
    n = 256
    world = 4

    def spmd(xs, we, se):
        red, nwe, nse = onebit_allreduce(xs[0], we[0], se[0], "data")
        return red[None], nwe[None], nse[None]

    f = jax.jit(jax.shard_map(
        spmd, mesh=topo.mesh, axis_names={"data"},
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")), check_vma=False))

    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)
    we = jnp.zeros((world, n), jnp.float32)
    se = jnp.zeros((world, n // world), jnp.float32)
    acc_comp = np.zeros(n)
    acc_dense = np.zeros(n)
    for step in range(30):
        xs_step = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)
        red, we, se = f(xs_step, we, se)
        acc_comp += np.asarray(red)[0]
        acc_dense += np.asarray(xs_step).mean(axis=0)
    # every rank sees the identical reduced tensor
    np.testing.assert_allclose(np.asarray(red)[0], np.asarray(red)[-1], rtol=1e-6)
    # error feedback keeps the running sums close
    err = np.abs(acc_comp - acc_dense) / (np.abs(acc_dense) + 1.0)
    assert np.median(err) < 0.6, np.median(err)


def test_int8_allreduce_close_to_dense():
    from deepspeed_tpu.parallel.compressed import int8_allreduce

    topo = mesh_mod.Topology.build_virtual({"data": 4})
    n, world = 2048, 4

    def spmd(xs, err):
        red, nerr = int8_allreduce(xs[0], err[0], "data", block=256)
        return red[None], nerr[None]

    f = jax.jit(jax.shard_map(
        spmd, mesh=topo.mesh, axis_names={"data"},
        in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
        check_vma=False))
    xs = jnp.asarray(np.random.default_rng(1).normal(size=(world, n)), jnp.float32)
    err = jnp.zeros((world, n), jnp.float32)
    red, _ = f(xs, err)
    dense = np.asarray(xs).mean(axis=0)
    np.testing.assert_allclose(np.asarray(red)[0], dense, atol=0.05)


# ----------------------------------------------------------------------
# 1-bit adam
def test_onebit_adam_converges():
    """Linear regression with 1-bit Adam: loss must drop through both the
    dense warmup and the compressed phase."""
    from deepspeed_tpu.runtime.onebit import OnebitAdam

    topo = mesh_mod.Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(16, 4))
    X = rng.normal(size=(64, 16)).astype(np.float32)
    Y = (X @ w_true).astype(np.float32)

    def loss_fn(params, batch, _):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = {"w": jnp.zeros((16, 4), jnp.float32)}
    # freeze after the variance has stabilized (the reference's contract:
    # freeze_step ends a long dense warmup); compression then adds bounded
    # sign-noise around the dense trajectory, not divergence
    opt = OnebitAdam(loss_fn, params, topo.mesh, lr=0.03, freeze_step=60)
    batch = {"x": jnp.asarray(X), "y": jnp.asarray(Y)}
    losses = [opt.step(batch) for _ in range(120)]
    assert losses[10] < losses[0]
    assert opt.compression_active
    compressed_phase = losses[60:]
    assert np.isfinite(compressed_phase).all()
    # stays in the neighborhood the dense phase reached, far below start
    assert min(compressed_phase) < losses[0] * 0.1
    assert max(compressed_phase) < losses[0]


def test_onebit_lamb_converges():
    """1-bit LAMB (reference onebit/lamb.py): trust-ratio update trains
    through warmup and the compressed phase."""
    from deepspeed_tpu.runtime.onebit import OnebitLamb

    topo = mesh_mod.Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(16, 4))
    X = rng.normal(size=(64, 16)).astype(np.float32)
    Y = (X @ w_true).astype(np.float32)

    def loss_fn(params, batch, _):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    # LAMB's trust ratio scales updates by ||p||/||u|| — zero-init params
    # would clamp it to the floor; start near the task's weight scale
    params = {"w": jnp.asarray(rng.normal(size=(16, 4)) * 0.3, jnp.float32)}
    opt = OnebitLamb(loss_fn, params, topo.mesh, lr=0.05, freeze_step=60)
    batch = {"x": jnp.asarray(X), "y": jnp.asarray(Y)}
    losses = [opt.step(batch) for _ in range(150)]
    assert losses[10] < losses[0]
    assert opt.compression_active
    assert np.isfinite(losses).all()
    assert min(losses[60:]) < losses[0] * 0.1


def test_zero_one_adam_local_steps_and_convergence():
    """0/1 Adam (reference onebit/zoadam.py): syncs run at growing
    intervals (real comm skipped on local steps), still converges."""
    from deepspeed_tpu.runtime.onebit import ZeroOneAdam

    topo = mesh_mod.Topology.build_virtual({"data": 4})
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(16, 4))
    X = rng.normal(size=(64, 16)).astype(np.float32)
    Y = (X @ w_true).astype(np.float32)

    def loss_fn(params, batch, _):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = {"w": jnp.zeros((16, 4), jnp.float32)}
    opt = ZeroOneAdam(loss_fn, params, topo.mesh, lr=0.03,
                      var_freeze_step=40, local_step_scaler=20,
                      local_step_clipper=8)
    batch = {"x": jnp.asarray(X), "y": jnp.asarray(Y)}
    losses = [opt.step(batch) for _ in range(100)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.1
    # local stepping really reduced sync frequency
    assert opt.sync_steps < opt.steps * 0.7
    assert opt.sync_steps >= 5


def test_pallas_quant_interpret_parity():
    """The fused Pallas quant/dequant kernels match the jnp reference
    bit-exactly in interpret mode (the compiled check lives in the
    on-chip lane, test_tpu_kernels.py)."""
    from deepspeed_tpu.ops.pallas.quant import (dequantize_blockwise_pallas,
                                                quantize_blockwise_pallas)
    from deepspeed_tpu.ops.quantizer import (dequantize_blockwise,
                                             quantize_blockwise)

    rng = np.random.default_rng(3)
    for rows in (32, 96, 288):
        x = jnp.asarray(rng.standard_normal(rows * 256), jnp.float32)
        qr, sr, _ = quantize_blockwise(x, block=256)
        qp, sp, _ = quantize_blockwise_pallas(x, block=256, interpret=True)
        np.testing.assert_array_equal(np.asarray(qr), np.asarray(qp))
        np.testing.assert_allclose(np.asarray(sr), np.asarray(sp), rtol=1e-6)
        dr = dequantize_blockwise(qr, sr, block=256)
        dp = dequantize_blockwise_pallas(qp, sp, block=256, interpret=True)
        np.testing.assert_allclose(np.asarray(dr), np.asarray(dp), rtol=1e-6)
