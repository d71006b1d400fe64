"""Shared comm-facade A/B lane (docs/communication.md).

One implementation of the serial-vs-overlapped staged ZeRO-3 check and
the bytes-on-wire ratio measurement, driven by BOTH evidence lanes — the
MULTICHIP dryrun (``__graft_entry__.py``) and the quant-comm CI gate
(``scripts/quant_comm_smoke.py``) — so the two cannot drift into
asserting different invariants. Callers apply their own gates to the
returned numbers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def build_comm_engine(cc_cfg: Dict[str, Any], *, batch_size: int,
                      seed: int = 0, lr: float = 1e-2,
                      dims=(64, 256, 256, 64)):
    """Fresh staged SequentialBlockModel engine on a reset topology with
    the given comm_compression block (ZeRO-3, persistence threshold 0)."""
    import jax

    import deepspeed_tpu as dst
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.zero import SequentialBlockModel

    mesh_mod.reset_topology()
    model = SequentialBlockModel(dims)
    engine, _, _, _ = dst.initialize(model=model, config={
        "train_batch_size": batch_size,
        "optimizer": {"type": "adamw", "params": {"lr": lr}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "comm_compression": cc_cfg,
        "steps_per_print": 1000,
    }, rng=jax.random.PRNGKey(seed))
    return engine


def wire_ratios(totals: Dict[str, Dict[str, float]]
                ) -> Optional[Dict[str, float]]:
    """(weight-gather, inter-slice-grad) logical/wire reductions off a
    CommsLogger snapshot; None when the facade ops are missing."""
    wg = totals.get("qwz_all_gather")
    gr = totals.get("qgz_inter_reduce_scatter")
    if not wg or not gr:
        return None
    return {"weight_allgather": wg["bytes"] / wg["wire_bytes"],
            "grad_inter_slice": gr["bytes"] / gr["wire_bytes"]}


def run_comm_ab(*, batch_size: int, steps_bitexact: int = 2,
                steps_compressed: int = 3, seed: int = 6,
                grad_bits: int = 4) -> Dict[str, Any]:
    """The A/B: (1) staged serial vs overlapped with compression OFF must
    be bit-exact (losses AND parameters); (2) the compressed engine must
    learn, with the ledger's measured wire ratios returned alongside.
    Raises AssertionError on bit-exactness/learning violations; callers
    gate the ratios themselves."""
    import jax
    import numpy as np

    from deepspeed_tpu.comm.comm import (configure_comms_logger,
                                         get_comms_logger)

    rng = np.random.default_rng(seed)
    batch = {"x": rng.normal(size=(batch_size, 64)).astype(np.float32),
             "y": rng.normal(size=(batch_size, 64)).astype(np.float32)}

    e_ser = build_comm_engine({"enabled": False, "overlap": "serial"},
                              batch_size=batch_size, seed=seed)
    e_ovl = build_comm_engine({"enabled": False, "overlap": "staged"},
                              batch_size=batch_size, seed=seed)
    l_ser = [float(e_ser.train_batch(batch)["loss"])
             for _ in range(steps_bitexact)]
    l_ovl = [float(e_ovl.train_batch(batch)["loss"])
             for _ in range(steps_bitexact)]
    assert l_ser == l_ovl, (
        f"staged overlap NOT bit-exact to serial: {l_ser} vs {l_ovl}")
    for a, b in zip(jax.tree_util.tree_leaves(e_ser.params),
                    jax.tree_util.tree_leaves(e_ovl.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "staged overlap params drifted from serial schedule")

    log = get_comms_logger()
    was_enabled = log.enabled
    configure_comms_logger(True)
    log.reset()
    e_cmp = build_comm_engine({"enabled": True, "weight_bits": 8,
                               "grad_bits": grad_bits, "overlap": "staged"},
                              batch_size=batch_size, seed=seed)
    l_cmp = [float(e_cmp.train_batch(batch)["loss"])
             for _ in range(steps_compressed)]
    assert np.isfinite(l_cmp).all() and l_cmp[-1] < l_cmp[0], (
        f"compressed run not learning: {l_cmp}")
    ratios = wire_ratios(log.snapshot_totals())
    assert ratios is not None, "facade ops missing from the ledger"
    if not was_enabled:
        configure_comms_logger(False)
    return {"overlap_bitexact_losses": l_ovl,
            "compressed_losses": l_cmp,
            "ratios": ratios,
            "engine": e_cmp, "batch": batch}
