"""1-bit optimizers: communication-compressed Adam / LAMB.

Reference surface: ``runtime/fp16/onebit/`` — OnebitAdam (adam.py:14),
OnebitLamb (lamb.py), ZeroOneAdam (zoadam.py), all built on the
error-compensated compressed allreduce in ``runtime/comm/nccl.py:51``.

Algorithm (1-bit Adam, NeurIPS'21): a dense warmup phase runs standard
Adam; after ``freeze_step`` the variance term is FROZEN and each step
communicates the *momentum* through the error-compensated 1-bit collective
(parallel/compressed.py) instead of dense gradients — ~25x smaller wire
volume for the dominant traffic.

TPU-first: the whole step (local grad, momentum update, compressed
collective, Adam math) is ONE jitted shard_map program; warmup/compressed
phases are a ``lax.cond``-free select on a step counter so a single
compiled program serves both phases.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.compressed import init_error_feedback, tree_onebit_allreduce
from ..utils.logging import log_dist


class OnebitAdam:
    """Self-contained data-parallel trainer with 1-bit Adam semantics.

    Reference-parity knobs: lr, betas, eps, weight_decay, freeze_step
    (warmup length before compression kicks in). ``cuda_aware``/``comm_
    backend_name`` from the reference have no TPU analog.
    """

    def __init__(self, loss_fn: Callable, params: Any, mesh: Mesh,
                 axis_name: str = "data", lr: float = 1e-3,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, freeze_step: int = 100):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.axis_name = axis_name
        self.lr = lr
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.freeze_step = freeze_step
        self.world = mesh.shape[axis_name]

        repl = NamedSharding(mesh, P())
        err_shard = NamedSharding(mesh, P(axis_name))
        self.params = jax.device_put(params, repl)
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        self.m = jax.device_put(jax.tree_util.tree_map(zeros, params), repl)
        self.v = jax.device_put(jax.tree_util.tree_map(zeros, params), repl)
        we, se = init_error_feedback(params, self.world)
        self.worker_error = jax.device_put(we, err_shard)
        self.server_error = jax.device_put(se, err_shard)
        self.steps = 0
        self._step_fn = None
        log_dist(f"OnebitAdam: freeze_step={freeze_step} world={self.world}")

    @property
    def compression_active(self) -> bool:
        return self.steps >= self.freeze_step

    def _apply_update(self, p, mm, vv, bc1, bc2):
        upd = (mm / bc1) / (jnp.sqrt(vv / bc2) + self.eps)
        if self.weight_decay > 0:
            upd = upd + self.weight_decay * p.astype(jnp.float32)
        return (p - self.lr * upd).astype(p.dtype)

    def _build_step(self, compressed: bool):
        """Two SEPARATE compiled programs: the warmup one contains only the
        dense pmean, the compressed one only the 1-bit collective — a
        masked-out branch would still execute its collective every step and
        the wire-volume saving would be fiction."""
        b1, b2 = self.betas
        axis, world = self.axis_name, self.world
        loss_fn = self.loss_fn

        def spmd(params, m, v, we, se, batch, step):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, None))(params)
            loss = jax.lax.pmean(loss, axis)
            if compressed:
                # local momentum update; only the momentum crosses the wire,
                # 1-bit compressed; variance stays frozen
                m_new = jax.tree_util.tree_map(
                    lambda mm, g: b1 * mm + (1 - b1) * g.astype(jnp.float32),
                    m, grads)
                m_new, we, se = tree_onebit_allreduce(m_new, we, se, axis, world)
                v_new = v
            else:
                g_dense = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g.astype(jnp.float32), axis), grads)
                m_new = jax.tree_util.tree_map(
                    lambda mm, g: b1 * mm + (1 - b1) * g, m, g_dense)
                v_new = jax.tree_util.tree_map(
                    lambda vv, g: b2 * vv + (1 - b2) * g * g, v, g_dense)

            t = (step + 1).astype(jnp.float32)
            bc1 = 1 - b1 ** t
            bc2 = 1 - b2 ** t
            params_new = jax.tree_util.tree_map(
                lambda p, mm, vv: self._apply_update(p, mm, vv, bc1, bc2),
                params, m_new, v_new)
            return params_new, m_new, v_new, we, se, loss

        fn = jax.shard_map(
            spmd, mesh=self.mesh, axis_names={axis},
            in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P(), P(), P(axis), P(axis), P()),
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4))

    def step(self, batch) -> float:
        """One optimizer step over a global batch (dim 0 sharded over the
        data axis)."""
        if self._step_fn is None:
            self._step_fn = {False: self._build_step(False),
                             True: self._build_step(True)}
        fn = self._step_fn[self.compression_active]
        (self.params, self.m, self.v, self.worker_error, self.server_error,
         loss) = fn(self.params, self.m, self.v, self.worker_error,
                    self.server_error, batch,
                    jnp.asarray(self.steps, jnp.int32))
        self.steps += 1
        return float(loss)


class OnebitLamb(OnebitAdam):
    """1-bit LAMB (reference runtime/fp16/onebit/lamb.py): LAMB's layer-wise
    trust-ratio update on top of the 1-bit momentum collective. Warmup runs
    dense LAMB; after ``freeze_step`` the variance freezes and the momentum
    travels through the error-compensated 1-bit allreduce. Trust ratio is
    recomputed per step from the live params/update and clamped to the
    reference's [min_coeff, max_coeff]."""

    def __init__(self, *args, max_coeff: float = 10.0, min_coeff: float = 0.01,
                 **kwargs):
        self.max_coeff = max_coeff
        self.min_coeff = min_coeff
        super().__init__(*args, **kwargs)

    def _apply_update(self, p, mm, vv, bc1, bc2):
        u = (mm / bc1) / (jnp.sqrt(vv / bc2) + self.eps)
        if self.weight_decay > 0:
            u = u + self.weight_decay * p.astype(jnp.float32)
        # layer-wise trust ratio (LAMB), clamped like the reference
        pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
        un = jnp.sqrt(jnp.sum(jnp.square(u)))
        ratio = jnp.where((pn > 0) & (un > 0),
                          jnp.clip(pn / un, self.min_coeff, self.max_coeff),
                          1.0)
        return (p - self.lr * ratio * u).astype(p.dtype)


class ZeroOneAdam:
    """0/1 Adam (reference runtime/fp16/onebit/zoadam.py): communication
    further reduced via LOCAL STEPS — the cross-replica sync runs only at
    exponentially-growing intervals; between syncs each replica updates
    from its local gradients with no collective at all.

    At a sync step the momentum goes through the error-compensated 1-bit
    collective and the params are mean-reconciled (one dense allreduce per
    interval — a deviation from the reference, which lets params drift
    until checkpoint time; reconciling at sync bounds the drift with
    amortized-negligible cost on ICI). The variance learns until
    ``var_freeze_step`` then freezes. Two separate compiled programs (local
    / sync) make the skipped communication real, not a masked-out branch.

    Knobs (reference parity): var_freeze_step, local_step_scaler,
    local_step_clipper — the sync interval starts at 1 and doubles every
    ``local_step_scaler`` steps, clipped to ``local_step_clipper``.
    """

    def __init__(self, loss_fn: Callable, params: Any, mesh: Mesh,
                 axis_name: str = "data", lr: float = 1e-3,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, var_freeze_step: int = 100,
                 local_step_scaler: int = 100, local_step_clipper: int = 16):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.axis_name = axis_name
        self.lr = lr
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.var_freeze_step = var_freeze_step
        self.local_step_scaler = local_step_scaler
        self.local_step_clipper = local_step_clipper
        self.world = mesh.shape[axis_name]

        repl = NamedSharding(mesh, P())
        err_shard = NamedSharding(mesh, P(axis_name))
        self.params = jax.device_put(params, repl)
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        self.m = jax.device_put(jax.tree_util.tree_map(zeros, params), repl)
        self.v = jax.device_put(jax.tree_util.tree_map(zeros, params), repl)
        we, se = init_error_feedback(params, self.world)
        self.worker_error = jax.device_put(we, err_shard)
        self.server_error = jax.device_put(se, err_shard)
        self.steps = 0
        self.sync_steps = 0          # observability: collectives actually run
        self._next_sync = 0
        self._interval = 1
        self._last_double = 0        # step of the last interval doubling
        self._local_fn = None
        self._sync_fn = None
        log_dist(f"ZeroOneAdam: var_freeze={var_freeze_step} "
                 f"clipper={local_step_clipper} world={self.world}")

    def _adam_update(self, params, m, v, step):
        b1, b2 = self.betas
        t = (step + 1).astype(jnp.float32)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, mm, vv):
            u = (mm / bc1) / (jnp.sqrt(vv / bc2) + self.eps)
            if self.weight_decay > 0:
                u = u + self.weight_decay * p.astype(jnp.float32)
            return (p - self.lr * u).astype(p.dtype)

        return jax.tree_util.tree_map(upd, params, m, v)

    def _build(self, sync: bool):
        b1, b2 = self.betas
        axis, world = self.axis_name, self.world
        loss_fn = self.loss_fn
        var_freeze = self.var_freeze_step

        def spmd(params, m, v, we, se, batch, step):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, None))(params)
            m_new = jax.tree_util.tree_map(
                lambda mm, g: b1 * mm + (1 - b1) * g.astype(jnp.float32),
                m, grads)
            learn_var = step < var_freeze
            v_new = jax.tree_util.tree_map(
                lambda vv, g: jnp.where(
                    learn_var,
                    b2 * vv + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                    vv),
                v, grads)
            if sync:
                m_new, we, se = tree_onebit_allreduce(m_new, we, se, axis, world)
                loss = jax.lax.pmean(loss, axis)
            params_new = self._adam_update(params, m_new, v_new, step)
            if sync:
                # bounded-drift reconciliation (see class docstring)
                params_new = jax.tree_util.tree_map(
                    lambda p: jax.lax.pmean(p.astype(jnp.float32), axis)
                    .astype(p.dtype), params_new)
            return params_new, m_new, v_new, we, se, loss

        fn = jax.shard_map(
            spmd, mesh=self.mesh, axis_names={axis},
            in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P(), P(), P(axis), P(axis), P()),
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4))

    def step(self, batch) -> float:
        do_sync = self.steps >= self._next_sync
        if do_sync:
            # exponential local-step schedule (reference zoadam counters):
            # double once per local_step_scaler WINDOW (boundary-crossing
            # check — an exact-modulo test would stall whenever sync steps
            # drift off the scaler's phase)
            if self.steps - self._last_double >= self.local_step_scaler:
                self._interval = min(self._interval * 2,
                                     self.local_step_clipper)
                self._last_double = self.steps
            self._next_sync = self.steps + self._interval
            self.sync_steps += 1
            if self._sync_fn is None:
                self._sync_fn = self._build(sync=True)
            fn = self._sync_fn
        else:
            if self._local_fn is None:
                self._local_fn = self._build(sync=False)
            fn = self._local_fn
        (self.params, self.m, self.v, self.worker_error, self.server_error,
         loss) = fn(self.params, self.m, self.v, self.worker_error,
                    self.server_error, batch, jnp.asarray(self.steps, jnp.int32))
        self.steps += 1
        return float(loss)
