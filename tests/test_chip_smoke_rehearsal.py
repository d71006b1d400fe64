"""Rehearsal of ``chip_smoke.py``'s control flow at a tiny size on the CPU
mesh (on-chip-measurement guide §2, rehearsals 1 and 2): the same phase
functions the chip runs, Pallas kernels in interpret mode, dispatch taking
its off-TPU branches. What only the chip can show — that the compiled
kernels ran — is ``chip_smoke.require_*`` and is not rehearsed here; the
described-chip compiles are tests/test_tpu_compile.py."""

import json
import subprocess
import sys

import pytest

import chip_smoke


def _tiny() -> chip_smoke.Sizes:
    return chip_smoke.Sizes(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq_len=512, sliding_window=256,
        train_layers=2, train_batch=8, train_seq=64, train_steps=2,
        train_block=2,
        serve_layers=2, token_budget=64, max_seqs=8, kv_block_size=16,
        max_context=256, max_kv_blocks=64,
        prompt_lens=(9, 40, 100), shared_prefix=32, probe_len=20,
        new_tokens=4,
        kernel_seq=256, kernel_pages_per_seq=8, kernel_n_seqs=8,
        kernel_alt_heads=(3, 3), kernel_shared_heads=(4, 2, 64),
        kernel_delta_state=(3, 8, 16),
        kernel_ssd_state=(4, 8, 16, 2), kernel_ssd_periods=3,
        kernel_latent=(4, 256, 128), kernel_latent_pages=32,
        zero3_layers=2, zero3_batch=4, zero3_steps=2)


@pytest.fixture
def ledger():
    return chip_smoke.CompileLedger()


def test_kernels_phase_interpret(ledger, capsys):
    with chip_smoke.phase("kernels", ledger) as rec:
        chip_smoke.phase_kernels(_tiny(), 0, rec, interpret=True)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "kernels"
    paged = {f"paged_{shape}{variant}"
             for shape in ("prefill", "decode", "mixed")
             for variant in ("", "_h30", "_w64", "_int8", "_h2x64")}
    assert set(line["rel_err"]) == {"flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dk", "flash_bwd_dv",
                                    "delta_step_o", "delta_step_state",
                                    "ssd_step_y", "ssd_step_state",
                                    "latent_prefill", "latent_decode",
                                    "latent_mixed", "latent_long"} | paged
    # the delta-rule step kernel leaves the slots that do not decode alone,
    # and so does the state-space step kernel on its rolled leaf
    assert line["state_unequal"] == 0 and line["ssd_state_unequal"] == 0
    # the row writer against the scatter: no element differs
    assert line["rows_unequal"] == {
        f"rows_{shape}_h{heads}": 0
        for shape in ("prefill", "decode", "mixed")
        for heads in (8, 16, 30, "2x64")}
    # two KV heads of 64 a 128-lane row: the tiled grid took that pool
    assert line["shape"]["paged_variants"]["_h2x64"] == [4, 2, 64]


def test_train_phase(ledger):
    with chip_smoke.phase("train", ledger) as rec:
        chip_smoke.phase_train(_tiny(), 0, rec)
    assert len(rec["train_batch_losses"]) == 2
    assert len(rec["train_steps_losses"]) == 4
    # off-TPU the dispatcher takes the jnp path, and says so: this is
    # exactly what require_flash_kernel refuses on the chip
    assert rec["attention_dispatch"].get("flash_jnp", 0) > 0
    with pytest.raises(AssertionError, match="Pallas flash kernel"):
        chip_smoke.require_flash_kernel(rec)


def test_serve_phase(ledger):
    with chip_smoke.phase("serve", ledger) as rec:
        chip_smoke.phase_serve(_tiny(), 0, rec)
    assert rec["requests"] == 5
    assert rec["pages_held_by_sequences_after_drain"] == 0
    assert rec["attention_path"] == "gather"
    assert rec["host_packer"] in ("native", "numpy")
    with pytest.raises(AssertionError, match="Pallas paged kernel"):
        chip_smoke.require_paged_kernel(rec)


def test_zero3_phase_on_four_virtual_devices(ledger):
    with chip_smoke.phase("zero3", ledger) as rec:
        chip_smoke.phase_zero3(_tiny(), 0, rec)
    four = rec["zero3_four_devices"]
    assert four["fewest_distinct_shards_of_a_leaf"] == 4
    assert four["hlo"]["all-gather"] > 0
    assert rec["state_share_of_fullest_device"] <= 0.30


def test_refuses_to_run_without_a_tpu():
    """The script itself, as the driver's sandbox check runs it: non-zero,
    says why, prints no result line."""
    proc = subprocess.run([sys.executable, chip_smoke.__file__],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
