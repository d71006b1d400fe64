"""``model_type: sdar_moe`` against published modelling code: the key set of
SDAR-30B-A3B's config.json is Qwen3-MoE's, so a tiny
``transformers.Qwen3MoeForCausalLM`` (eager attention, float32), saved and
loaded through ``checkpoint/hf.py`` as ``sdar_moe``, has to give the repo's
model the same logits: under the causal mask (``attn_block`` 1) and under
SDAR's block-causal mask, which the torch model takes as a 4-D additive
mask. The QK-norm gains are moved off one first, or nothing would test
them."""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deepspeed_tpu.checkpoint.hf import from_pretrained, hf_config  # noqa: E402
from deepspeed_tpu.models.moe import MoETransformer  # noqa: E402

BLOCK = 4


def _save_tiny(tmp_path, n_experts, top_k):
    torch.manual_seed(0)
    cfg = transformers.Qwen3MoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, num_experts=n_experts,
        num_experts_per_tok=top_k, norm_topk_prob=True, decoder_sparse_step=1,
        mlp_only_layers=[], max_position_embeddings=128, rms_norm_eps=1e-6,
        rope_theta=1e6, tie_word_embeddings=False,
        attn_implementation="eager")
    hf = transformers.Qwen3MoeForCausalLM(cfg).eval()
    with torch.no_grad():
        for layer in hf.model.layers:
            for norm in (layer.self_attn.q_norm, layer.self_attn.k_norm):
                norm.weight.add_(0.3 * torch.randn_like(norm.weight))
    d = tmp_path / "sdar"
    hf.save_pretrained(str(d), safe_serialization=True)
    path = d / "config.json"
    hc = json.loads(path.read_text())
    hc.update(model_type="sdar_moe", architectures=["SDARMoeForCausalLM"],
              block_length=BLOCK, mask_token_id=255, denoising_steps=2)
    path.write_text(json.dumps(hc))
    return hf, str(d)


@pytest.mark.parametrize("n_experts,top_k", [(8, 2), (16, 4)])
def test_logits_match_qwen3_moe_under_both_masks(tmp_path, n_experts, top_k):
    hf, d = _save_tiny(tmp_path, n_experts, top_k)
    family, cfg = hf_config(d)
    assert family == "sdar_moe"
    assert (cfg.attn_block, cfg.mask_token_id, cfg.denoise_tokens) \
        == (BLOCK, 255, 2)
    assert (cfg.head_dim, cfg.n_experts, cfg.top_k, cfg.d_ff) \
        == (32, n_experts, top_k, 32)
    assert cfg.qk_norm and cfg.qk_norm_heads
    model, params = from_pretrained(d, dtype=jnp.float32)
    assert isinstance(model, MoETransformer)
    assert params["layers"]["q_norm_w"].shape == (2, 32)
    assert float(jnp.abs(params["layers"]["q_norm_w"] - 1).max()) > 0.1

    tokens = np.random.default_rng(0).integers(1, 250, (2, 22)).astype(np.int32)
    ids = torch.tensor(tokens, dtype=torch.long)
    s = tokens.shape[1]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = j // BLOCK <= i // BLOCK
    additive = torch.tensor(np.where(seen, 0.0, np.finfo(np.float32).min),
                            dtype=torch.float32)[None, None].expand(2, 1, s, s)
    with torch.no_grad():
        causal = hf(ids).logits.numpy()
        blockwise = hf(ids, attention_mask=additive).logits.numpy()
    # the two masks are told apart by the torch model itself
    assert np.abs(causal - blockwise).max() > 1e-2

    got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, blockwise, rtol=2e-3, atol=2e-3)
    plain = MoETransformer(dataclasses.replace(cfg, attn_block=1))
    got = np.asarray(plain.apply(params, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, causal, rtol=2e-3, atol=2e-3)


def test_unsupported_variants_and_the_message(tmp_path):
    from deepspeed_tpu.checkpoint.hf import sdar_moe_config

    hc = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4, num_experts=8,
              num_experts_per_tok=2)
    assert sdar_moe_config(hc).mask_token_id == 151669   # SDAR's own
    assert sdar_moe_config(hc, 1).n_layers == 1
    for bad in ({"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
                {"norm_topk_prob": False}, {"rope_scaling": {"type": "yarn"}},
                {"use_sliding_window": True}, {"attention_bias": True}):
        with pytest.raises(NotImplementedError, match="sdar_moe"):
            sdar_moe_config({**hc, **bad})
    (tmp_path / "config.json").write_text(json.dumps({"model_type": "nope"}))
    with pytest.raises(ValueError, match="sdar_moe"):
        hf_config(str(tmp_path))
