"""Quant-comm gate (CPU evidence lane, docs/communication.md).

Gates the compressed-collectives facade + T3 staged schedule on a
virtual 8-device mesh:

1. **Bit-exact overlap** — the staged schedule with compression OFF must
   produce bit-identical losses and parameters in serial vs overlapped
   issue order (same dataflow, different issue position).
2. **Wire-byte ratios** — per the bytes-on-wire ledger, the int8 weight
   all-gather must cut wire volume >= 2x and the int4 inter-slice
   gradient hop >= 4x vs the uncompressed payload.
3. **Error bound** — the traced quantization round-trip error must stay
   within the documented QuantSpec bound (0.5/qmax of the block absmax).
4. **Zero recompiles** — the staged compressed path inside the fused
   train_steps(k) scan traces each program exactly once across repeated
   calls (train/recompiles stays 0).
5. **NORTHSTAR projection** — the committed NORTHSTAR artifact's
   overlapped zero3 comm exposure must be cut >= 50% vs the serial
   booking (the ROADMAP item-1 claim, modeled with the same
   comm.compressed.modeled_exposure the projection uses).

Exits nonzero on any violation. Wired into run_tests.sh.
Usage: python scripts/quant_comm_smoke.py
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

_CHILD = "_DST_QUANT_COMM_CHILD"


def _fail(msg: str) -> None:
    print(f"[quant-comm] GATE FAIL: {msg}", flush=True)
    sys.exit(1)


def _check_northstar() -> dict:
    """Newest committed NORTHSTAR artifact carrying the overlapped comm
    projection; its exposure reduction is the gated claim."""
    cands = sorted(glob.glob(os.path.join(HERE, "NORTHSTAR_r*.json")))
    for path in reversed(cands):
        with open(path) as fh:
            report = json.load(fh)
        rows = [c for c in report.get("configs", [])
                if isinstance(c.get("comm_compression"), dict)]
        if not rows:
            continue
        worst = min(r["comm_compression"]["exposure_reduction_vs_serial"]
                    for r in rows)
        if worst < 0.5:
            _fail(f"{os.path.basename(path)}: overlapped zero3 comm "
                  f"exposure reduced only {worst:.0%} (< 50%) vs the "
                  f"serial booking")
        print(f"[quant-comm] {os.path.basename(path)}: exposure reduction "
              f">= {worst:.0%} across {len(rows)} configs", flush=True)
        return {"artifact": os.path.basename(path),
                "min_exposure_reduction": worst}
    _fail("no NORTHSTAR_r*.json with a comm_compression projection found")


def _run_child() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from deepspeed_tpu.comm import compressed as cc
    from deepspeed_tpu.telemetry import MetricsRegistry, set_registry

    sys.path.insert(0, os.path.join(HERE, "scripts"))
    from _comm_lane import build_comm_engine, run_comm_ab

    assert len(jax.devices()) >= 8, len(jax.devices())
    reg = set_registry(MetricsRegistry())

    # -- legs 1+2: the shared A/B (scripts/_comm_lane.py — same lane the
    # MULTICHIP dryrun drives): serial-vs-overlapped bit-exactness with
    # compression off, then the compressed engine + ledger ratios
    try:
        ab = run_comm_ab(batch_size=32, steps_bitexact=4,
                         steps_compressed=4, seed=0)
    except AssertionError as e:
        _fail(str(e))
    print(f"[quant-comm] overlap bit-exact over 4 steps: "
          f"{ab['overlap_bitexact_losses']}", flush=True)
    w_ratio = ab["ratios"]["weight_allgather"]
    g_ratio = ab["ratios"]["grad_inter_slice"]
    if w_ratio < 2.0:
        _fail(f"weight all-gather wire reduction {w_ratio:.2f}x < 2x")
    if g_ratio < 4.0:
        _fail(f"inter-slice gradient hop wire reduction {g_ratio:.2f}x < 4x")

    # -- leg 3: error bound (fresh engine with stats on)
    batch = ab["batch"]
    e_c = build_comm_engine({"enabled": True, "weight_bits": 8,
                             "grad_bits": 4, "error_stats": True,
                             "overlap": "staged"}, batch_size=32, seed=0)
    m = e_c.train_batch(batch)
    err = float(m["quant_rel_err"])
    bound = cc.QuantSpec(4, 256).rel_error_bound
    if not 0.0 <= err <= bound + 1e-6:
        _fail(f"quant rel error {err:.4f} outside documented bound {bound:.4f}")

    # -- leg 4: one-trace fused scan + recompile guard
    e_c.train_steps([batch, batch])
    e_c.train_steps([batch, batch])
    if e_c.trace_count("train_steps_2") != 1:
        _fail(f"staged fused scan retraced: "
              f"{e_c.trace_count('train_steps_2')} traces")
    if reg.counter("train/recompiles").value != 0:
        _fail("recompile guard tripped in the staged scan")
    print(json.dumps({
        "weight_allgather_wire_reduction": round(w_ratio, 2),
        "grad_interhost_wire_reduction": round(g_ratio, 2),
        "quant_rel_err": round(err, 5),
        "quant_rel_err_bound": round(bound, 5),
        "losses_compressed": [round(l, 5)
                              for l in ab["compressed_losses"]],
        "fused_scan_traces": e_c.trace_count("train_steps_2"),
    }), flush=True)


def main() -> int:
    if os.environ.get(_CHILD) == "1":
        _run_child()
        return 0
    # the NORTHSTAR check needs no devices — do it in the parent
    _check_northstar()
    from __graft_entry__ import cpu_child_env

    env = cpu_child_env(8)
    env[_CHILD] = "1"
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, cwd=HERE, timeout=900)
    if proc.returncode == 0:
        print("[quant-comm] gate PASS", flush=True)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
