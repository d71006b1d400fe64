"""Autotuner tests (reference autotuning/autotuner.py:404 tune parity):
compile-time search over mesh x micro-batch x remat, no training runs."""

import jax
import pytest

from deepspeed_tpu.autotuning import Autotuner, TuningConstraints, autotune
from deepspeed_tpu.models import Llama


def _factory(remat=False):
    return Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 vocab_size=256, max_seq_len=64, use_flash=False, remat=remat)


def _constraints(**kw):
    base = dict(chip="cpu", global_batch=16, seq_len=64,
                micro_batches=[1, 2], tp_sizes=[1, 2],
                remat_options=[False, True])
    base.update(kw)
    return TuningConstraints(**base)


def test_autotune_returns_feasible_best():
    result = autotune(_factory, _constraints())
    assert result["mesh"]["data"] * result["mesh"]["model"] == len(jax.devices())
    report = result["report"]
    assert report["best"] is not None
    cands = report["candidates"]
    assert len(cands) >= 4
    feasible = [c for c in cands if c["feasible"]]
    assert feasible
    # best is the cheapest feasible candidate
    assert report["best"]["est_step_s"] == min(c["est_step_s"] for c in feasible)
    # every feasible candidate has a real compile-derived profile
    for c in feasible:
        assert c["flops"] > 0 and c["peak_bytes"] > 0


def test_autotune_beats_or_matches_naive():
    """The tuned config's estimated step cost must not exceed the naive
    (first-enumerated) feasible candidate's."""
    tuner = Autotuner(_factory, _constraints())
    report = tuner.tune()
    feasible = [c for c in report["candidates"] if c["feasible"]]
    naive = feasible[-1]  # candidates are ranked: last feasible = worst
    assert report["best"]["est_step_s"] <= naive["est_step_s"]


def test_memory_budget_marks_infeasible():
    """A absurdly small HBM budget must reject every candidate."""
    tuner = Autotuner(_factory, _constraints(hbm_bytes=1024.0))
    report = tuner.tune()
    assert report["best"] is None
    with pytest.raises(RuntimeError, match="no feasible"):
        autotune(_factory, _constraints(hbm_bytes=1024.0))


def test_remat_reduces_peak_memory():
    """Rematerialization must show up in the compiled memory profile."""
    tuner = Autotuner(_factory, _constraints(
        micro_batches=[4], tp_sizes=[1], global_batch=32, seq_len=64))
    report = tuner.tune()
    by_remat = {c["remat"]: c["peak_bytes"]
                for c in report["candidates"] if c["feasible"]}
    if len(by_remat) == 2:  # both compiled
        assert by_remat[True] <= by_remat[False] * 1.1


def test_autotune_sp_candidates():
    """sp_sizes adds Ulysses seq-axis candidates; infeasible tp*sp combos
    are skipped."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner, TuningConstraints
    from deepspeed_tpu.models import Llama

    tuner = Autotuner(
        lambda remat: Llama("tiny", n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=4, vocab_size=256, max_seq_len=64,
                            use_flash=False, remat=remat),
        TuningConstraints(n_devices=8, global_batch=8, seq_len=64,
                          micro_batches=[1], zero_stages=[2],
                          tp_sizes=[1, 2], sp_sizes=[1, 2],
                          remat_options=[False]))
    cands = tuner.candidates()
    meshes = [c["mesh"] for c in cands]
    assert {"data": 4, "model": 1, "seq": 2} in meshes
    assert {"data": 8, "model": 1} in meshes
    # and an sp candidate actually compiles + evaluates
    sp_cand = next(c for c in cands if c["mesh"].get("seq") == 2)
    r = tuner.evaluate(sp_cand)
    assert r.error is None, r.error
    assert r.feasible
