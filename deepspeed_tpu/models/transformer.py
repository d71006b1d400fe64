"""Decoder-only transformer core.

This is the model substrate the reference gets from HuggingFace + kernel
injection (``deepspeed/module_inject/containers/{llama,gptneo,opt,...}`` and
FastGen's ``inference/v2/model_implementations/``). Built TPU-first:

* **Stacked layer parameters + ``lax.scan`` over depth** — one compiled
  block regardless of layer count (compile time O(1) in depth, XLA pipelines
  the scan); the reference's per-layer Python modules have no TPU analog.
* **Tensor parallelism as PartitionSpecs** — Megatron-style column/row
  sharding over the ``model`` mesh axis is *data placement* here, not code:
  :meth:`Transformer.partition_specs` returns the spec tree and GSPMD
  inserts the one all-reduce per block the reference's AutoTP patches into
  forward (module_inject/auto_tp.py).
* **Sequence parallelism (Ulysses)** via ``parallel/ulysses.py`` — enabled
  when the mesh's ``seq`` axis > 1.
* fp32 accumulation in norms/softmax/logits; bf16 everywhere else.

Families supported via :class:`TransformerConfig`: Llama/Mistral-style
(RMSNorm + RoPE + gated-SiLU MLP + GQA), GPT-2/OPT-style (LayerNorm +
learned positions + GELU MLP, optional biases), with tied or untied
embeddings.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import dot_product_attention, flash_attention
from ..ops.norms import layer_norm, rms_norm
from ..ops.rotary import alibi_slopes, apply_rotary, rope_frequencies


@dataclass
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # GQA; None => MHA
    d_ff: Optional[int] = None        # default 4*d (gelu) or 8/3*d rounded (glu)
    max_seq_len: int = 2048
    norm: str = "rms"                 # rms | layer
    activation: str = "silu_glu"      # silu_glu | gelu | relu
    position: str = "rope"            # rope | learned
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    use_bias: bool = False
    norm_eps: float = 1e-6
    remat: bool = True                # activation checkpointing per block
    remat_policy: str = "full"        # full | selective | selective_flash
    #                                 # | dots_with_no_batch_dims | nothing
    use_flash: bool = True
    logits_softcap: float = 0.0
    z_loss: float = 0.0
    # chunked cross entropy: tokens per head+CE chunk (0 = whole batch).
    # Bounds the fp32 logits transient to [chunk, vocab] instead of
    # [b*s, vocab] (2.1 GB at b8 s2048 v32k) — the backward recomputes each
    # chunk's logits from the (small) hidden states via jax.checkpoint
    loss_chunk_size: int = 0
    # sequence-parallel attention when the mesh's seq axis > 1:
    # "auto" = ulysses when n_heads divides the seq axis, else ring
    sp_attention: str = "auto"        # auto | ulysses | ring
    # family coverage knobs (Bloom / GPT-J / GPT-NeoX):
    rope_pct: float = 1.0             # fraction of head_dim rotated (NeoX)
    rope_interleaved: bool = False    # GPT-J pairing instead of half-split
    parallel_residual: bool = False   # x + attn(ln1 x) + mlp(ln2 x)
    embed_norm: bool = False          # LayerNorm after token embed (Bloom)
    # encoder-family knobs (BERT / DistilBERT; reference
    # module_inject/containers/{bert,distil_bert}.py):
    causal: bool = True               # False = bidirectional encoder
    prenorm: bool = True              # False = post-LN (x = LN(x + sub(x)))
    type_vocab_size: int = 0          # >0 adds segment (token-type) embeddings
    mlm_head: bool = False            # BERT MLM head: dense+gelu+LN+decoder+bias
    pooler: bool = False              # [CLS] dense+tanh pooler
    # Sliding-window knobs (GPT-Neo alternating local layers, Mistral/
    # Mixtral uniform windows): per-layer window sizes, 0 = global causal.
    # At seq <= window the window is statically elided (flash path kept).
    # A BINDING uniform window dispatches the banded flash kernel
    # (O(s*window) compute, below-band tiles skipped); per-layer-VARYING
    # windows use the masked jnp path (O(s^2) score memory — GPT-Neo's
    # windows are small). attn_scale overrides the logit scale (GPT-Neo
    # uses UNSCALED qk^T, i.e. attn_scale=1.0).
    attn_windows: Optional[Tuple[int, ...]] = None
    attn_scale: Optional[float] = None
    qkv_bias: Optional[bool] = None   # None -> follow use_bias (Neo: False)
    # InternLM: attention projections carry biases (incl. o_proj) while the
    # gated MLP does not — reference module_inject/containers/internlm.py:20
    attn_o_bias: Optional[bool] = None  # None -> follow use_bias
    # Hybrid stacks (Olmo-Hybrid, Granite-4.0-H): a kind per layer, "full"
    # (softmax attention), "linear" (gated delta rule, ops/gated_delta.py,
    # with the linear_* sizes below) or "mamba" (Mamba-2 state space,
    # ops/mamba2.py, with the mamba_* sizes); None = every layer full. The
    # kinds of layer sit in the parameter tree as a stack each beside the
    # common one (Transformer.init), and the depth loop is unrolled. A
    # model has one recurrent kind at most: the state pool holds one shape.
    layer_types: Optional[Tuple[str, ...]] = None
    linear_n_k_heads: int = 0
    linear_n_v_heads: int = 0
    linear_k_dim: int = 0
    linear_v_dim: int = 0
    linear_conv_kernel: int = 4
    linear_neg_eigval: bool = False   # beta in (0, 2): negative eigenvalues
    mamba_n_heads: int = 0            # H heads of mamba_d_head channels
    mamba_d_head: int = 0
    mamba_d_state: int = 0            # N state channels a group
    mamba_n_groups: int = 1           # B and C are shared by H / G heads
    mamba_d_conv: int = 4
    mamba_chunk: int = 256            # tokens a piece of the SSD form covers
    # Granite's scalar multipliers (1 = none): the embedding's output times
    # embedding_multiplier, each branch's output times residual_multiplier
    # before it joins the residual stream (pre-norm wiring only), the
    # logits divided by logits_scaling; its attention_multiplier is
    # attn_scale above
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # OLMo-2/3 wiring: x + norm(sub(x)), no norm before a branch (not
    # prenorm=False, which is norm(x + sub(x))); the final norm stays
    branch_norm: bool = False
    qk_norm: bool = False             # RMSNorm over the whole projected q, k
    # Qwen3's form of it: over each head's head_dim numbers, one gain
    # vector [head_dim] shared by the heads (needs qk_norm)
    qk_norm_heads: bool = False
    # a head's size where it is not d_model / n_heads (Qwen3-MoE: 32 heads
    # of 128 over a hidden size of 2048); None = d_model // n_heads
    head_size: Optional[int] = None
    # Block diffusion (SDAR): attention is causal over blocks of attn_block
    # positions and sees both ways inside a block, so the query at position
    # p sees keys up to p | (attn_block - 1); a power of two, 1 = causal.
    # The generation settings the serving engine reads beside it: the id a
    # position not yet decided holds, and how many masked positions of a
    # block one denoise pass decides (those of highest confidence)
    attn_block: int = 1
    mask_token_id: int = -1
    denoise_tokens: int = 1
    # Looped stacks (Ouro / LoopLM): the whole stack of n_layers blocks runs
    # total_ut_steps times a token over ONE set of weights, the final norm
    # after every pass (the normed output of a pass is the next pass's
    # input, and the head's), each pass with K/V of its own. An exit gate
    # (one Linear(d, 1) on a pass's normed output, float32) gives each
    # token the pass whose output feeds the head: the first whose
    # cumulative exit probability reaches early_exit_threshold, else the
    # last; at a threshold of 1 or more the gate is not computed. Every
    # pass is always run. sandwich_norm: a norm before AND after each
    # branch, x + norm(sub(norm(x))) (four gains a layer)
    sandwich_norm: bool = False
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # Latent attention (MLA, DeepSeek-V2/V3's; kv_lora_rank > 0): queries
    # through a low-rank pair with an RMS norm between (q_lora_rank), keys
    # and values expanded from one normed latent row of kv_lora_rank values
    # a token, a head's query and key qk_nope_dim such values beside
    # qk_rope_dim rotated ones, the rotated key one for all heads, a head's
    # value v_head_dim wide. head_size is then qk_nope_dim + qk_rope_dim and
    # n_kv_heads n_heads. The served cache holds the latent row and the
    # rotated key, nothing a head (inference/kv_cache.py "latent")
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN (rope_yarn_factor > 1): each rotary frequency blended with itself
    # over the factor by a linear ramp between the correction dimensions of
    # beta_fast and beta_slow over rope_yarn_original positions
    # (ops/rotary.rope_frequencies); cos and sin are unscaled (the source's
    # mscale equal to its mscale_all_dim: the mappers refuse another) and,
    # with no attn_scale given, the softmax scale is multiplied by
    # (0.1 * mscale_all_dim * ln(factor) + 1) squared
    rope_yarn_factor: float = 1.0
    rope_yarn_original: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0

    def __post_init__(self):
        if self.kv_lora_rank:
            if not (self.q_lora_rank and self.qk_nope_dim and self.qk_rope_dim
                    and self.v_head_dim):
                raise ValueError("latent attention needs q_lora_rank, "
                                 "qk_nope_dim, qk_rope_dim and v_head_dim")
            if (self.layer_types is not None or self.attn_windows is not None
                    or self.qk_norm or self.qkv_bias or self.attn_o_bias
                    or self.attn_block > 1 or self.total_ut_steps > 1
                    or self.position != "rope" or not self.causal
                    or self.rope_interleaved):
                raise NotImplementedError(
                    "latent attention is a causal rotary model of one kind "
                    "of layer: no layer_types, windows, QK-norm, attention "
                    "biases, attn_block or looped stack")
            self.head_size = self.qk_nope_dim + self.qk_rope_dim
            self.n_kv_heads = self.n_heads
            if self.attn_scale is None:
                m = 1.0 if self.rope_yarn_factor <= 1.0 else 1.0 + 0.1 \
                    * self.rope_yarn_mscale_all_dim \
                    * float(np.log(self.rope_yarn_factor))
                self.attn_scale = self.head_size ** -0.5 * m ** 2
        if self.rope_yarn_factor > 1.0 and (
                self.rope_yarn_original <= 0 or not self.kv_lora_rank):
            raise NotImplementedError(
                "YaRN frequencies need rope_yarn_original, and are wired "
                "for latent attention (cos and sin unscaled) alone")
        if self.n_kv_heads is None:
            self.n_kv_heads = self.n_heads
        if self.qkv_bias is None:
            self.qkv_bias = self.use_bias
        if self.attn_o_bias is None:
            self.attn_o_bias = self.use_bias
        if self.attn_windows is not None:
            self.attn_windows = tuple(int(w) for w in self.attn_windows)
            assert len(self.attn_windows) == self.n_layers, (
                f"attn_windows has {len(self.attn_windows)} entries for "
                f"{self.n_layers} layers")
            if not self.causal:
                # every window path (banded kernel, masks, paged gather)
                # implements the CAUSAL band k > q - w; a bidirectional
                # model would silently get causal attention
                raise ValueError(
                    "attn_windows requires a causal model "
                    "(sliding windows are a decoder feature)")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            assert len(self.layer_types) == self.n_layers, (
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.n_layers} layers")
            assert set(self.layer_types) <= {"full", "linear", "mamba"}, \
                self.layer_types
            if "mamba" in self.layer_types:
                assert "linear" not in self.layer_types, \
                    "one recurrent kind a model: linear or mamba"
                assert self.mamba_n_heads and self.mamba_d_head \
                    and self.mamba_d_state, "mamba layers need mamba_* sizes"
                assert self.mamba_n_heads % self.mamba_n_groups == 0
            if "linear" in self.layer_types:
                assert self.linear_n_k_heads and self.linear_k_dim \
                    and self.linear_v_dim, "linear layers need linear_* sizes"
                self.linear_n_v_heads = self.linear_n_v_heads \
                    or self.linear_n_k_heads
                assert self.linear_n_v_heads % self.linear_n_k_heads == 0
        if self.qk_norm_heads and not self.qk_norm:
            raise ValueError("qk_norm_heads is a form of qk_norm")
        if self.attn_block < 1 or self.attn_block & (self.attn_block - 1):
            raise ValueError(
                f"attn_block {self.attn_block} is not a power of two")
        if self.attn_block > 1 and (
                not self.causal or self.attn_windows is not None
                or self.state_layers or self.total_ut_steps > 1
                or self.position == "alibi" or self.mask_token_id < 0
                or not 1 <= self.denoise_tokens <= self.attn_block):
            raise ValueError(
                "attn_block > 1 (block diffusion) needs a causal model of "
                "softmax-attention layers without sliding windows, a "
                "mask_token_id and 1 <= denoise_tokens <= attn_block")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps} < 1")
        if self.total_ut_steps > 1 and (
                self.state_layers or not self.prenorm or not self.causal):
            raise ValueError(
                "a looped stack (total_ut_steps > 1) needs a causal pre-norm "
                "model of softmax-attention layers: the final norm closes "
                "every pass, and a recurrent state a pass is not kept")
        if self.residual_multiplier != 1.0 and (
                self.branch_norm or self.sandwich_norm or not self.prenorm
                or self.parallel_residual):
            raise ValueError("residual_multiplier is plain pre-norm wiring: "
                             "not with branch_norm, sandwich_norm, post-LN "
                             "or parallel_residual")
        if self.sandwich_norm and (self.branch_norm or not self.prenorm
                                   or self.parallel_residual):
            raise ValueError("sandwich_norm is pre-norm wiring with a second "
                             "norm on each branch's output: not with "
                             "branch_norm, post-LN or parallel_residual")
        if self.d_ff is None:
            if self.activation == "silu_glu":
                self.d_ff = int(8 * self.d_model / 3 / 128 + 1) * 128
            else:
                self.d_ff = 4 * self.d_model
        assert self.d_model % self.n_heads == 0

    def window_binds(self, length: int) -> bool:
        """True if any per-layer sliding window actually trims attention
        at this sequence/context length (w == length attends everything)."""
        return self.attn_windows is not None \
            and any(0 < w < length for w in self.attn_windows)

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """Indices of the layers of that kind ("full" holds the KV pages,
        "linear" and "mamba" a recurrent state), in depth order."""
        kinds = self.layer_types or ("full",) * self.n_layers
        return tuple(i for i, k in enumerate(kinds) if k == kind)

    @property
    def layer_period(self) -> int:
        """Layers in one period of the stack as it is SERVED. A hybrid
        stack with mamba layers whose layer_types repeat (Granite-4.0-H:
        five mamba, one attention, four mamba layers, four times over) is
        one rolled loop over its periods: RaggedInferenceEngine's step
        traces and compiles one period's layers, takes each layer's
        weights out of their stacks by the period's index, and the cache
        keeps one leaf for each layer of a period, the periods' runs end
        to end in it (inference/kv_cache.py). This is the smallest p that
        layer_types repeats with; n_layers (one period, nothing rolled)
        for every other model: no layer_types, per-layer windows (static
        a layer), or the delta rule's layers, whose step kernel names
        slots and not a period's run of them. Transformer.apply stays
        unrolled."""
        n, kinds = self.n_layers, self.layer_types
        if kinds is None or "mamba" not in kinds \
                or self.attn_windows is not None:
            return n
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and kinds == kinds[:p] * (n // p))

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """Indices of the layers that hold a recurrent state a sequence
        (one kind a model, so one kind's list)."""
        return self.layers_of("linear") or self.layers_of("mamba")

    @property
    def rotary_dim(self) -> int:
        """Rotated dims per head (GPT-NeoX rope_pct), even-rounded; under
        latent attention the rotated part's own size."""
        if self.kv_lora_rank:
            return self.qk_rope_dim
        return int(self.head_dim * self.rope_pct) // 2 * 2

    @property
    def rope_yarn(self) -> Optional[Tuple[float, int, float, float]]:
        """``ops/rotary.rope_frequencies``'s ``yarn``: (factor, original
        positions, beta_fast, beta_slow), or None."""
        if self.rope_yarn_factor <= 1.0:
            return None
        return (self.rope_yarn_factor, self.rope_yarn_original,
                self.rope_yarn_beta_fast, self.rope_yarn_beta_slow)

    @property
    def latent_row(self) -> int:
        """Values the served cache holds a token a layer under latent
        attention: the normed latent and the rotated key, padded to whole
        lanes of 128 (512 + 64 -> 640: a page slab the paged kernel can
        copy, and what a [.., 576] leaf takes in the chip's tiled layout
        anyway); 0 for any other model."""
        used = self.kv_lora_rank + self.qk_rope_dim
        return -(-used // 128) * 128 if self.kv_lora_rank else 0

    def _shared_param_count(self) -> int:
        """Attention + norms + embeddings (everything but the FFN)."""
        d, v, n = self.d_model, self.vocab_size, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        if self.kv_lora_rank:    # the latent mixer's leaves (_init_latent)
            rq, rkv, h = self.q_lora_rank, self.kv_lora_rank, self.n_heads
            attn = d * rq + rq + rq * h * hd + d * (rkv + self.qk_rope_dim) \
                + rkv + rkv * h * (self.qk_nope_dim + self.v_head_dim) \
                + h * self.v_head_dim * d
        if self.qkv_bias:
            attn += self.n_heads * hd + 2 * self.n_kv_heads * hd
        if self.attn_o_bias:
            attn += d
        if self.qk_norm:
            attn += 2 * hd if self.qk_norm_heads \
                else (self.n_heads + self.n_kv_heads) * hd
        mixers = len(self.layers_of("full")) * attn
        n_lin = len(self.layers_of("linear"))
        if n_lin:   # the gated delta rule's leaves (_init_linear)
            hk, hv = self.linear_n_k_heads, self.linear_n_v_heads
            ch = 2 * hk * self.linear_k_dim + hv * self.linear_v_dim
            mixers += n_lin * (
                d * ch + self.linear_conv_kernel * ch + 2 * d * hv + 2 * hv
                + 2 * d * hv * self.linear_v_dim + self.linear_v_dim)
        n_mamba = len(self.layers_of("mamba"))
        if n_mamba:  # Mamba-2's leaves (_init_mamba)
            di = self.mamba_n_heads * self.mamba_d_head
            ch = di + 2 * self.mamba_n_groups * self.mamba_d_state
            mixers += n_mamba * (
                d * (di + ch + self.mamba_n_heads) + (self.mamba_d_conv + 1) * ch
                + 3 * self.mamba_n_heads + di + di * d)
        norms = (4 if self.sandwich_norm else 2) * d * n \
            + (d if self.prenorm else 0)
        if self.norm == "layer":
            norms *= 2  # weights + biases
        return mixers + norms + self._outside_stack_param_count()

    def _outside_stack_param_count(self) -> int:
        """Embeddings, heads and the exit gate: what a looped stack runs
        once a token, not once a pass."""
        d, v = self.d_model, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.position == "learned":
            emb += self.max_seq_len * d
        emb += self.type_vocab_size * d
        if self.embed_norm:
            emb += 2 * d
        head = 0
        if self.mlm_head:
            head += d * d + d + 2 * d + v  # transform + LN + decoder bias
        if self.pooler:
            head += d * d + d
        if self.total_ut_steps > 1:
            head += d + 1                  # the exit gate
        return emb + head

    def param_count(self) -> int:
        d, f, n = self.d_model, self.d_ff, self.n_layers
        mlp = (3 if self.activation == "silu_glu" else 2) * d * f
        if self.use_bias:
            mlp += f + d
        return self._shared_param_count() + n * mlp

    def _attn_flop_len(self, seq_len: int) -> int:
        """Summed per-layer attention lengths: sliding-window layers attend
        at most ``window`` keys, so min(seq, window) — keeps MFU honest for
        windowed models (shared by the dense and MoE flops counts)."""
        if self.attn_windows is not None:
            return sum(min(seq_len, w) if w > 0 else seq_len
                       for w in self.attn_windows)
        return len(self.layers_of("full")) * seq_len

    def flops_per_token(self, seq_len: int) -> float:
        """Forward+backward FLOPs/token (standard 6N + attention term); a
        looped stack runs its layers' share of both once a pass."""
        once = 6.0 * self._outside_stack_param_count()
        a_pass = 6.0 * self.param_count() - once \
            + 12.0 * self.d_model * self._attn_flop_len(seq_len)
        return once + self.total_ut_steps * a_pass


def _joined(a):
    """:meth:`Transformer._qkv`'s ``seam`` where a layer's leaves arrive
    already sliced (``lax.scan``: the training step): nothing."""
    return a


class Transformer:
    """Functional model: ``init`` -> params pytree; ``apply`` -> logits;
    ``loss`` -> scalar; ``partition_specs`` -> TP placement."""

    def __init__(self, config: TransformerConfig):
        self.config = config
        self._mesh = None
        self._seq_size = 1
        self._tp_size = 1
        self._pipe_size = 1

    def bind_topology(self, topo) -> "Transformer":
        """Attach the device mesh; activates Ulysses/ring sequence-parallel
        attention when the topology's seq axis > 1 (called by
        ``deepspeed_tpu.initialize``)."""
        self._mesh = topo.mesh
        self._seq_size = topo.sequence_parallel_size
        self._tp_size = topo.model_parallel_size
        self._pipe_size = topo.pipe_parallel_size
        self._batch_axes = topo.data_axes()
        if self._pipe_size > 1:
            assert self.config.n_layers % self._pipe_size == 0, (
                f"n_layers={self.config.n_layers} not divisible by "
                f"pipeline stages={self._pipe_size}")
        if self._seq_size > 1:
            impl = self.config.sp_attention
            if impl == "auto":
                # under TP the heads dim is already sharded over 'model', so
                # ulysses scatters the LOCAL n_heads/tp heads over the seq axis
                local_heads = self.config.n_heads // self._tp_size
                impl = "ulysses" if local_heads % self._seq_size == 0 else "ring"
            self._sp_impl = impl
        return self

    # ------------------------------------------------------------------
    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        c = self.config
        hd = c.head_dim
        k = iter(jax.random.split(rng, 16))

        def dense(key, shape, scale=None):
            scale = scale if scale is not None else 1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

        n = c.n_layers
        # the mixers' leaves are stacked over the layers of their kind: with
        # layer_types, attention's under "full" and the delta rule's under
        # "linear"; what every layer has stays stacked over all n
        nf = len(c.layers_of("full"))
        attn: Dict[str, Any] = {
            "wq": dense(next(k), (nf, c.d_model, c.n_heads * hd)),
            "wk": dense(next(k), (nf, c.d_model, c.n_kv_heads * hd)),
            "wv": dense(next(k), (nf, c.d_model, c.n_kv_heads * hd)),
            "wo": dense(next(k), (nf, c.n_heads * hd, c.d_model), scale=1.0 / np.sqrt(c.d_model * 2 * n)),
        } if not c.kv_lora_rank else self._init_latent(
            jax.random.fold_in(rng, 3), nf, dense, dtype)
        layers: Dict[str, Any] = {
            "attn_norm_w": jnp.ones((n, c.d_model), dtype),
            "mlp_norm_w": jnp.ones((n, c.d_model), dtype),
            "w_up": dense(next(k), (n, c.d_model, c.d_ff)),
            "w_down": dense(next(k), (n, c.d_ff, c.d_model), scale=1.0 / np.sqrt(c.d_ff * 2 * n)),
        }
        if c.activation == "silu_glu":
            layers["w_gate"] = dense(next(k), (n, c.d_model, c.d_ff))
        if c.norm == "layer":
            layers["attn_norm_b"] = jnp.zeros((n, c.d_model), dtype)
            layers["mlp_norm_b"] = jnp.zeros((n, c.d_model), dtype)
        if c.sandwich_norm:   # the norms on the two branches' outputs
            for name in ("attn_post_norm", "mlp_post_norm"):
                layers[name + "_w"] = jnp.ones((n, c.d_model), dtype)
                if c.norm == "layer":
                    layers[name + "_b"] = jnp.zeros((n, c.d_model), dtype)
        if c.qkv_bias:
            attn["bq"] = jnp.zeros((nf, c.n_heads * hd), dtype)
            attn["bk"] = jnp.zeros((nf, c.n_kv_heads * hd), dtype)
            attn["bv"] = jnp.zeros((nf, c.n_kv_heads * hd), dtype)
        if c.attn_o_bias:
            attn["bo"] = jnp.zeros((nf, c.d_model), dtype)
        if c.qk_norm:
            per = c.qk_norm_heads
            attn["q_norm_w"] = jnp.ones(
                (nf, hd if per else c.n_heads * hd), dtype)
            attn["k_norm_w"] = jnp.ones(
                (nf, hd if per else c.n_kv_heads * hd), dtype)
        if c.use_bias:
            layers["b_up"] = jnp.zeros((n, c.d_ff), dtype)
            layers["b_down"] = jnp.zeros((n, c.d_model), dtype)
        if c.layer_types is None:
            layers.update(attn)
        else:
            if nf:
                layers["full"] = attn
            nl = len(c.layers_of("linear"))
            if nl:
                layers["linear"] = self._init_linear(
                    jax.random.fold_in(rng, 1), nl, dense, dtype)
            nm = len(c.layers_of("mamba"))
            if nm:
                layers["mamba"] = self._init_mamba(
                    jax.random.fold_in(rng, 2), nm, dense, dtype)

        params: Dict[str, Any] = {
            "tok_embed": dense(next(k), (c.vocab_size, c.d_model), scale=0.02),
            "layers": layers,
        }
        if c.prenorm:  # post-LN blocks end in their own norm — no final norm
            params["final_norm_w"] = jnp.ones((c.d_model,), dtype)
            if c.norm == "layer":
                params["final_norm_b"] = jnp.zeros((c.d_model,), dtype)
        if c.position == "learned":
            params["pos_embed"] = dense(next(k), (c.max_seq_len, c.d_model), scale=0.02)
        if c.type_vocab_size > 0:
            params["type_embed"] = dense(next(k), (c.type_vocab_size, c.d_model), scale=0.02)
        if c.embed_norm:
            params["embed_norm_w"] = jnp.ones((c.d_model,), dtype)
            params["embed_norm_b"] = jnp.zeros((c.d_model,), dtype)
        if not c.tie_embeddings:
            params["lm_head"] = dense(next(k), (c.d_model, c.vocab_size))
        if c.total_ut_steps > 1:   # the exit gate, Linear(d, 1)
            params["exit_gate_w"] = dense(next(k), (c.d_model, 1))
            params["b_exit_gate"] = jnp.zeros((1,), dtype)
        if c.mlm_head:
            params["mlm_dense_w"] = dense(next(k), (c.d_model, c.d_model))
            params["mlm_dense_b"] = jnp.zeros((c.d_model,), dtype)
            params["mlm_norm_w"] = jnp.ones((c.d_model,), dtype)
            params["mlm_norm_b"] = jnp.zeros((c.d_model,), dtype)
            params["mlm_bias"] = jnp.zeros((c.vocab_size,), dtype)
        if c.pooler:
            params["pooler_w"] = dense(next(k), (c.d_model, c.d_model))
            params["pooler_b"] = jnp.zeros((c.d_model,), dtype)
        return params

    def _init_latent(self, rng, nf: int, dense, dtype) -> Dict[str, Any]:
        """The latent-attention mixer's leaves, stacked over the layers:
        the two low-rank pairs with their norms' gains between, the
        up-projection of the latent kept as its two parts (``w_uk`` a
        head's un-rotated key, ``w_uv`` its value: the served step absorbs
        the first into the query and applies the second after attention,
        so neither is ever sliced), and the output projection."""
        c = self.config
        d, h = c.d_model, c.n_heads
        rq, rkv = c.q_lora_rank, c.kv_lora_rank
        k = iter(jax.random.split(rng, 6))
        return {
            "w_dq": dense(next(k), (nf, d, rq)),
            "q_lora_norm_w": jnp.ones((nf, rq), dtype),
            "w_uq": dense(next(k), (nf, rq, h * c.head_dim)),
            "w_dkv": dense(next(k), (nf, d, rkv + c.qk_rope_dim)),
            "kv_lora_norm_w": jnp.ones((nf, rkv), dtype),
            "w_uk": dense(next(k), (nf, rkv, h * c.qk_nope_dim)),
            "w_uv": dense(next(k), (nf, rkv, h * c.v_head_dim)),
            "wo": dense(next(k), (nf, h * c.v_head_dim, d),
                        scale=1.0 / np.sqrt(d * 2 * c.n_layers)),
        }

    def _init_linear(self, rng, nl: int, dense, dtype) -> Dict[str, Any]:
        """The gated-delta-rule mixer's leaves (ops/gated_delta.py), stacked
        over the ``nl`` linear layers. ``A_log`` and ``dt_bias`` follow the
        layer's published initialisation: A uniform in (0, 16), the step
        softplus(dt_bias) log-uniform in (1e-3, 1e-1)."""
        c = self.config
        hk, hv = c.linear_n_k_heads, c.linear_n_v_heads
        dk, dv, d = c.linear_k_dim, c.linear_v_dim, c.d_model
        k = iter(jax.random.split(rng, 10))
        dt = jnp.exp(jax.random.uniform(next(k), (nl, hv), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return {
            "wq": dense(next(k), (nl, d, hk * dk)),
            "wk": dense(next(k), (nl, d, hk * dk)),
            "wv": dense(next(k), (nl, d, hv * dv)),
            "conv_w": dense(next(k), (nl, c.linear_conv_kernel,
                                      2 * hk * dk + hv * dv)),
            "w_a": dense(next(k), (nl, d, hv)),
            "w_beta": dense(next(k), (nl, d, hv)),
            "A_log": jnp.log(jax.random.uniform(
                next(k), (nl, hv), jnp.float32, 1e-3, 16.0)).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "w_z": dense(next(k), (nl, d, hv * dv)),
            "o_norm_w": jnp.ones((nl, dv), dtype),
            "wo": dense(next(k), (nl, hv * dv, d),
                        scale=1.0 / np.sqrt(d * 2 * c.n_layers)),
        }

    def _init_mamba(self, rng, nm: int, dense, dtype) -> Dict[str, Any]:
        """The Mamba-2 mixer's leaves (ops/mamba2.py), stacked over the
        ``nm`` mamba layers, named after the published module's tensors
        (``in_proj``, ``conv1d``, ``norm``, ``out_proj``). ``A_log``, ``D``
        and ``dt_bias`` follow its initialisation: A = 1 .. H, D = 1, the
        step softplus(dt_bias) log-uniform in (1e-3, 1e-1)."""
        c = self.config
        H, d = c.mamba_n_heads, c.d_model
        di = H * c.mamba_d_head
        ch = di + 2 * c.mamba_n_groups * c.mamba_d_state
        k = iter(jax.random.split(rng, 4))
        dt = jnp.exp(jax.random.uniform(next(k), (nm, H), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return {
            "w_in": dense(next(k), (nm, d, di + ch + H)),   # z | x B C | dt
            "conv_w": dense(next(k), (nm, c.mamba_d_conv, ch)),
            "conv_b": jnp.zeros((nm, ch), dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, H + 1, dtype=jnp.float32)), (nm, H)).astype(dtype),
            "D": jnp.ones((nm, H), dtype),
            "ssm_norm_w": jnp.ones((nm, di), dtype),
            "w_out": dense(next(k), (nm, di, d),
                           scale=1.0 / np.sqrt(d * 2 * c.n_layers)),
        }

    #: leaves of the common stack that are operands of an operation which
    #: indexes the stack itself (layer_params, ``in_place``)
    stacked_operands: Tuple[str, ...] = ()

    def layer_params(self, layers, li: int, in_place: bool = False,
                     period=None) -> Tuple[str, Dict[str, Any]]:
        """(kind, the leaves of layer ``li``) out of the stacked tree: the
        common stack at ``li`` and, with layer_types, its kind's stack at
        the layer's index among its kind. ``li`` is a python int, so the
        slice is static. A dense product fuses it into its own operand
        read (``wo``, the feed-forward's matrices), unless the compiler
        has folded a reshape of the product's result onto the weight: a
        slice under that bitcast is written out first, every layer's on
        every call, so a caller that takes its leaves from here keeps the
        head split of q / k / v on the results (:meth:`_qkv`'s
        ``seam``). An operation that cannot fuse a slice (the
        experts' ``ragged_dot``) has the layer's matrices copied on every
        call: with ``in_place`` the ``stacked_operands`` are handed over
        whole, with ``lp["layer"] = li`` beside them, for the operation to
        index the stack itself. A caller passes ``in_place`` only where
        those leaves' leading two axes are unsharded (merging the layer
        axis with a sharded expert axis is no bitcast). Under a rolled
        stack (``layer_period``) ``period`` is the period's index, traced,
        and ``li`` the layer's place inside a period: the slices are then
        dynamic, and a dense product reads them in place all the same."""
        c = self.config
        whole = self.stacked_operands if in_place else ()
        assert period is None or not whole, "no rolled stack of experts"
        take = (lambda a, i: a[i]) if period is None else (
            lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False))
        p = c.layer_period
        lp = jax.tree_util.tree_map(
            lambda a: take(a, li if period is None else period * p + li),
            {k: v for k, v in layers.items()
             if k not in whole + ("full", "linear", "mamba")})
        if whole:
            lp.update({k: layers[k] for k in whole if k in layers}, layer=li)
        if c.layer_types is None:
            return "full", lp
        kind = c.layer_types[li]
        at = c.layers_of(kind).index(li)     # its place among its kind
        if period is not None:               # ... and the periods before it
            at = period * sum(k == kind for k in c.layer_types[:p]) + at
        lp.update({k: take(v, at) for k, v in layers[kind].items()})
        return kind, lp

    # ------------------------------------------------------------------
    def _norm(self, x, w, b=None):
        if self.config.norm == "rms":
            return rms_norm(x, w, self.config.norm_eps)
        return layer_norm(x, w, b, self.config.norm_eps)

    def _local_flash(self, q, k, v, *, causal, scale=None, window=0):
        """Flash attention that stays device-local on multi-device meshes.

        GSPMD cannot partition a ``pallas_call`` — with batch/head-sharded
        operands it would replicate the kernel (silent pod-scale perf
        cliff). Standard practice: run the kernel INSIDE a shard_map whose
        specs name the operands' existing sharding (batch over the data
        axes, heads over 'model'), so each device runs the kernel on its
        local shard with zero collectives. Single-device (the bench) and
        unbound-mesh paths call the dispatcher directly."""
        from ..ops.attention import flash_attention

        # the scope names the Pallas custom calls flash_attention.N in a
        # profiler trace, forward and backward
        fa = jax.named_scope("flash_attention")(flash_attention)
        kw = {"causal": causal, "scale": scale}
        if window:
            kw["window"] = window
        mesh = self._mesh
        multi = mesh is not None and any(
            mesh.shape[a] > 1 for a in ("data", "zshard", "model")
            if a in mesh.shape)
        if not multi:
            return fa(q, k, v, **kw)
        from jax.sharding import PartitionSpec as P_

        batch_axes = getattr(self, "_batch_axes", None) or ()
        tp = self._tp_size
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape.get(a, 1)
        # the wrapper needs every named dim to divide its axes; GQA counts
        # must shard TOGETHER (sharding q but replicating kv would invert
        # the local q:kv ratio). Unwrappable corners (kv heads < tp, odd
        # batch) fall back to the jnp path, which GSPMD partitions fine —
        # correctness kept, and still no opaque pallas_call in the graph.
        heads_ok = tp == 1 or (q.shape[2] % tp == 0 and k.shape[2] % tp == 0)
        batch_ok = dp == 1 or q.shape[0] % dp == 0
        if not (heads_ok and batch_ok):
            return dot_product_attention(
                q, k, v, causal=causal, scale=scale, window=window)
        ha = "model" if tp > 1 else None
        spec = P_(tuple(batch_axes) or None, None, ha, None)
        return jax.shard_map(lambda q, k, v: fa(q, k, v, **kw), mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)

    def _sp_attention(self, q, k, v, window=None, causal=True):
        """Sequence-parallel attention over the bound mesh's seq axis."""
        batch_axes = getattr(self, "_batch_axes", None) or None
        head_axes = "model" if self._tp_size > 1 else None
        if self._sp_impl == "ring":
            from ..parallel.ring import ring_attention_sharded

            assert window is None and self.config.attn_scale is None \
                and causal, \
                "ring attention is causal-only, no window/scale — caller " \
                "must reject"
            return ring_attention_sharded(q, k, v, self._mesh, causal=True,
                                          batch_axes=batch_axes,
                                          head_axes=head_axes)
        from ..parallel.ulysses import DistributedAttention

        # after the a2a each device holds FULL sequences for a head subset —
        # exactly the flash kernel's shape (so a static sliding window and
        # scale override apply cleanly, and bidirectional encoders work
        # unchanged); the dispatcher falls back to the jnp path off-TPU /
        # on odd shapes
        local_attn = (flash_attention if self.config.use_flash
                      else dot_product_attention)
        kw = {}
        if window is not None:
            kw["window"] = window
        if self.config.attn_scale is not None:
            kw["scale"] = self.config.attn_scale
        if kw:
            local_attn = partial(local_attn, **kw)
        return DistributedAttention(local_attn, self._mesh,
                                    batch_axes=batch_axes,
                                    head_axes=head_axes)(q, k, v,
                                                         causal=causal)

    def _block(self, x, lp, angles, positions, kv_cache=None, rng=None, training=False,
               attn_mask=None, attn_window=None, kind: str = "full"):
        """One transformer block. x: [b, s, d]. Returns (x, new_kv, aux).

        ``attn_mask``: optional [b, s] padding mask (1 = attend) for the
        bidirectional (causal=False) encoder path.
        ``attn_window``: sliding-window size for local attention (<= 0
        means global causal). A STATIC python int (uniform windows,
        Mistral) dispatches the banded flash kernel — keep it static, a
        traced scalar silently falls to the O(s^2) masked path reserved
        for per-layer-varying windows (GPT-Neo)."""
        c = self.config
        s = x.shape[1]
        if attn_mask is not None and c.causal:
            raise NotImplementedError(
                "attn_mask with a causal model is not supported (padding "
                "masks are an encoder feature; causal batches should pack "
                "or left-trim instead)")
        if kind == "linear":
            # a recurrent layer: the gated delta rule takes attention's
            # place, under a scope of its own (conv, delta_chunk inside)
            from ..ops import gated_delta

            with jax.named_scope("linear_attn"):
                attn = gated_delta.mix_dense(x, lp, c)
            return self._after_mixer(x, attn, None, lp, rng, training)
        if kind == "mamba":
            # a state-space layer: the norm before the branch is the
            # block's, the mixer under the scope ssm (conv, ssd_chunk)
            from ..ops import mamba2

            with jax.named_scope("ssm"):
                attn = mamba2.mix_dense(self._mixer_input(x, lp), lp, c)
            return self._after_mixer(x, attn, None, lp, rng, training)

        # device scopes (jax.named_scope: metadata only) name the block's
        # parts in a profiler trace: attn (flash_attention around the
        # kernel), ffn (docs/observability.md)
        with jax.named_scope("attn"):
            q, kk, vv = self._qkv(x, lp, angles, positions)

            def _alibi_bias(skv):
                # ALiBi (Bloom): logits += slopes * (k_pos - q_pos); the per-row
                # -slopes*q_pos shift is constant along the softmax axis and
                # cancels, so slopes * k_pos alone is exact under row softmax
                slopes = alibi_slopes(c.n_heads)
                return (slopes[:, None, None]
                        * jnp.arange(skv, dtype=jnp.float32)[None, None, :])

            new_kv = None
            if c.attn_block > 1:
                # block diffusion: causal over blocks, both ways inside one
                # (the whole sequence at once; generation is the ragged
                # engine's, which writes a block before it is read)
                if kv_cache is not None or self._seq_size > 1:
                    raise NotImplementedError(
                        "attn_block > 1 has no dense KV cache and no "
                        "sequence-parallel attention: serve it through "
                        "RaggedInferenceEngine")
                attn = dot_product_attention(q, kk, vv, causal=True,
                                             scale=c.attn_scale,
                                             attn_block=c.attn_block)
            elif kv_cache is not None:
                ck, cv, cache_pos = kv_cache
                ck = jax.lax.dynamic_update_slice_in_dim(ck, kk, cache_pos, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, vv, cache_pos, axis=1)
                new_kv = (ck, cv)
                # query i sits at absolute position cache_pos + i: it may attend
                # every cache slot up to and including itself (this also masks
                # the unwritten zero tail of the cache)
                q_abs = cache_pos + jnp.arange(s)                   # [s]
                k_pos = jnp.arange(ck.shape[1])                     # [max_len]
                mask = k_pos[None, :] <= q_abs[:, None]             # [s, max_len]
                if attn_window is not None:  # local layers trim the left edge
                    mask = mask & ((attn_window <= 0)
                                   | (k_pos[None, :] > q_abs[:, None] - attn_window))
                bias = _alibi_bias(ck.shape[1]) if c.position == "alibi" else None
                attn = dot_product_attention(q, ck, cv, causal=False,
                                             mask=mask[None, None], bias=bias,
                                             scale=c.attn_scale)
            elif self._seq_size > 1:
                if c.position == "alibi":
                    raise NotImplementedError(
                        "ALiBi + sequence-parallel attention not supported yet")
                # attn_window is None here whenever no window binds at this
                # length (_encode elides them). Ulysses supports static
                # (uniform) binding windows, scale overrides, and
                # bidirectional encoders — the a2a yields full local
                # sequences; traced per-layer windows and the (causal-only)
                # ring path do not.
                if attn_window is not None and not isinstance(attn_window, int):
                    raise NotImplementedError(
                        "per-layer-varying attention windows + sequence-"
                        "parallel attention not supported")
                if (attn_window is not None or c.attn_scale is not None
                        or not c.causal) and self._sp_impl != "ulysses":
                    raise NotImplementedError(
                        "binding attention windows / scale overrides / "
                        "bidirectional encoders require ulysses sequence "
                        "parallelism (ring is causal-only)")
                if not c.causal and attn_mask is not None:
                    raise NotImplementedError(
                        "encoder padding masks not threaded through sequence-"
                        "parallel attention yet — drop the seq axis or pack "
                        "unpadded batches")
                attn = self._sp_attention(q, kk, vv, window=attn_window,
                                          causal=c.causal)
            elif c.position == "alibi":
                # flash kernel carries no additive bias — use the jnp path
                attn = dot_product_attention(q, kk, vv, causal=True,
                                             bias=_alibi_bias(s))
            elif not c.causal and attn_mask is not None:
                # encoder with padding: keys at padded positions are masked for
                # every query ([b, 1, 1, s] broadcast)
                key_mask = attn_mask.astype(bool)[:, None, None, :]
                attn = dot_product_attention(q, kk, vv, causal=False, mask=key_mask,
                                             scale=c.attn_scale)
            elif attn_window is not None and isinstance(attn_window, int):
                # uniform static window (Mistral/Mixtral): banded flash kernel
                # on TPU (tiles below the band skipped), banded jnp otherwise
                if c.use_flash:
                    attn = self._local_flash(q, kk, vv, causal=True,
                                             scale=c.attn_scale,
                                             window=attn_window)
                else:
                    attn = dot_product_attention(q, kk, vv, causal=True,
                                                 scale=c.attn_scale,
                                                 window=attn_window)
            elif attn_window is not None:
                # per-layer-varying (traced) windows — alternating global/local
                # causal attention (GPT-Neo): numeric banded mask
                q_pos = jnp.arange(s)[:, None]
                k_pos = jnp.arange(s)[None, :]
                m = (k_pos <= q_pos) & ((attn_window <= 0)
                                        | (k_pos > q_pos - attn_window))
                attn = dot_product_attention(q, kk, vv, causal=False,
                                             mask=m[None, None], scale=c.attn_scale)
            elif c.use_flash and not c.kv_lora_rank:
                attn = self._local_flash(q, kk, vv, causal=c.causal,
                                         scale=c.attn_scale)
            else:
                # (latent attention too: its values are v_head_dim wide,
                # its keys head_dim, and the flash kernel takes one size)
                attn = dot_product_attention(q, kk, vv, causal=c.causal,
                                             scale=c.attn_scale)

            attn = self._attn_out(attn, lp)

        return self._after_mixer(x, attn, new_kv, lp, rng, training)

    def _mixer_input(self, x, lp):
        """What a mixer reads: pre-LN normalizes the branch input; post-LN
        (BERT-era, prenorm=False) and branch_norm run the branch on x and
        norm after it."""
        c = self.config
        return self._norm(x, lp["attn_norm_w"], lp.get("attn_norm_b")) \
            if c.prenorm and not c.branch_norm else x

    def _qkv(self, x, lp, angles, positions, seam=_joined):
        """The attention block from its input to (q, k, v) split by heads
        and rotated: x [..., s, d] -> q [..., s, h, hd], k and v [..., s,
        hkv, hd]. The half of the block that does not depend on where K
        and V live, written once: :meth:`_block` (x [b, s, d]) and the
        ragged step (inference/ragged.py, x [T, d] with a position a lane)
        call it around their own attention. Order: bias, then QK-norm,
        then rotary. Two halves, joined by ``seam``: the three products
        with their bias and the whole-projection QK-norm, each result
        ``[..., s, heads * hd]``; then the head split, the per-head norm
        and rotary. ``seam`` takes the results across. XLA folds a reshape
        that follows a product onto the product's weight operand, and a
        weight that is a slice of a stacked leaf (:meth:`layer_params`)
        no longer fuses into the operand read under that bitcast: the
        slices are written out first, every layer's on every call (768 MB
        read and written a tick of Mistral's 16-layer step, the three
        stacks copied whole before Ouro's loop: PERF.md section 6, PR
        54). So a caller whose ``lp`` came out of a stack, the served
        step, passes ``jax.lax.optimization_barrier``: the results exist
        in the leaf's dtype, as ``h @ w`` says, before anything splits
        them, and the weights are read in place."""
        c = self.config
        if c.kv_lora_rank:
            # the expanded form: a head's key is its part of the latent's
            # up-projection beside the one rotated key, its value the other
            # part (v_head_dim wide: a flash kernel of one head size does
            # not take it, _block)
            q_nope, q_rope, latent, k_rope = self._latent_parts(
                x, lp, angles, positions, seam)
            h_ = c.n_heads
            k_nope = (latent @ lp["w_uk"]).reshape(
                latent.shape[:-1] + (h_, c.qk_nope_dim))
            vv = (latent @ lp["w_uv"]).reshape(
                latent.shape[:-1] + (h_, c.v_head_dim))
            kk = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[..., None, :],
                                          k_nope.shape[:-1] + (c.qk_rope_dim,))],
                axis=-1)
            return jnp.concatenate([q_nope, q_rope], axis=-1), kk, vv
        heads = lambda a, n: a.reshape(a.shape[:-1] + (n, c.head_dim))
        h = self._mixer_input(x, lp)
        q, kk, vv = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if c.qkv_bias:
            q, kk, vv = q + lp["bq"], kk + lp["bk"], vv + lp["bv"]
        if c.qk_norm and not c.qk_norm_heads:
            # over the whole projection, heads unsplit
            q = rms_norm(q, lp["q_norm_w"], c.norm_eps)
            kk = rms_norm(kk, lp["k_norm_w"], c.norm_eps)
        q, kk, vv = seam((q, kk, vv))
        q, kk, vv = (heads(q, c.n_heads), heads(kk, c.n_kv_heads),
                     heads(vv, c.n_kv_heads))
        if c.qk_norm_heads:   # a head at a time, one gain for all of them
            q = rms_norm(q, lp["q_norm_w"], c.norm_eps)
            kk = rms_norm(kk, lp["k_norm_w"], c.norm_eps)
        if c.position == "rope":
            # apply_rotary no-ops the partial slice when rotary_dim == hd
            q = apply_rotary(q, angles, positions, rotary_dim=c.rotary_dim,
                             interleaved=c.rope_interleaved)
            kk = apply_rotary(kk, angles, positions, rotary_dim=c.rotary_dim,
                              interleaved=c.rope_interleaved)
        return q, kk, vv

    def _latent_parts(self, x, lp, angles, positions, seam=_joined):
        """Latent attention from the block's input to what both its forms
        start from: a head's un-rotated and rotated query parts ``[..., s,
        h, qk_nope_dim]`` / ``[..., s, h, qk_rope_dim]``, the normed latent
        ``[..., s, kv_lora_rank]`` and the rotated key all heads share
        ``[..., s, qk_rope_dim]``. The expanded form (:meth:`_qkv`) expands
        the latent by head; the served step absorbs ``w_uk`` into the
        query and caches (latent | rotated key) as one row
        (inference/ragged.py). ``seam``: :meth:`_qkv`'s, between the
        query's up-projection and its head split."""
        c = self.config
        h = self._mixer_input(x, lp)
        cq = rms_norm(h @ lp["w_dq"], lp["q_lora_norm_w"], c.norm_eps)
        q = seam(cq @ lp["w_uq"]).reshape(
            cq.shape[:-1] + (c.n_heads, c.head_dim))
        q_nope, q_rope = q[..., :c.qk_nope_dim], q[..., c.qk_nope_dim:]
        down = h @ lp["w_dkv"]
        latent = rms_norm(down[..., :c.kv_lora_rank], lp["kv_lora_norm_w"],
                          c.norm_eps)
        k_rope = down[..., None, c.kv_lora_rank:]       # one head, for all
        q_rope = apply_rotary(q_rope, angles, positions)
        k_rope = apply_rotary(k_rope, angles, positions)[..., 0, :]
        return q_nope, q_rope, latent, k_rope

    def _attn_out(self, attn, lp):
        """Attention's output [..., s, h, hd] through the output
        projection. attn_o_bias, not use_bias: InternLM has use_bias=False
        with a real o_proj bias."""
        attn = attn.reshape(attn.shape[:-2] + (-1,)) @ lp["wo"]
        return attn + lp["bo"] if self.config.attn_o_bias else attn

    def _after_mixer(self, x, attn, new_kv, lp, rng, training):
        """The block from its mixer's output on: residual wiring and the
        feed-forward. Returns (x, new_kv, aux)."""
        c = self.config
        if c.sandwich_norm:  # Ouro: a norm before the branch and after it
            x = x + self._norm(attn, lp["attn_post_norm_w"],
                               lp.get("attn_post_norm_b"))
            with jax.named_scope("ffn"):
                h = self._norm(x, lp["mlp_norm_w"], lp.get("mlp_norm_b"))
                down, aux = self._mlp(h, lp, rng, training)
                return x + self._norm(down, lp["mlp_post_norm_w"],
                                      lp.get("mlp_post_norm_b")), new_kv, aux

        if c.branch_norm:  # OLMo-2/3: the norm sits on the branch's output
            x = x + self._norm(attn, lp["attn_norm_w"], lp.get("attn_norm_b"))
            with jax.named_scope("ffn"):
                down, aux = self._mlp(x, lp, rng, training)
                return x + self._norm(down, lp["mlp_norm_w"],
                                      lp.get("mlp_norm_b")), new_kv, aux

        if c.parallel_residual:
            # GPT-J / GPT-NeoX: both branches read the SAME input x
            # (GPT-J's single shared LN arrives as duplicated norm params)
            with jax.named_scope("ffn"):
                h2 = self._norm(x, lp["mlp_norm_w"], lp.get("mlp_norm_b"))
                down, aux = self._mlp(h2, lp, rng, training)
            return x + attn + down, new_kv, aux

        if not c.prenorm:  # post-LN: norm AFTER each residual add
            x = self._norm(x + attn, lp["attn_norm_w"], lp.get("attn_norm_b"))
            with jax.named_scope("ffn"):
                down, aux = self._mlp(x, lp, rng, training)
                x = self._norm(x + down, lp["mlp_norm_w"], lp.get("mlp_norm_b"))
            return x, new_kv, aux

        rm = c.residual_multiplier
        x = x + (attn if rm == 1.0 else attn * rm)
        with jax.named_scope("ffn"):
            h = self._norm(x, lp["mlp_norm_w"], lp.get("mlp_norm_b"))
            down, aux = self._mlp(h, lp, rng, training)
            return x + (down if rm == 1.0 else down * rm), new_kv, aux

    def _mlp(self, h, lp, rng=None, training=False):
        """Dense FFN. Subclasses (MoE) override; returns (out, aux_loss)."""
        c = self.config
        if c.activation == "silu_glu":
            up = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
        else:
            up = h @ lp["w_up"]
            if c.use_bias:
                up = up + lp["b_up"]
            if c.activation == "relu":
                up = jax.nn.relu(up)
            elif c.activation == "gelu_exact":   # erf GELU (GPT-NeoX/Pythia)
                up = jax.nn.gelu(up, approximate=False)
            elif c.activation == "quick_gelu":   # x*sigmoid(1.702x) (CLIP)
                up = up * jax.nn.sigmoid(1.702 * up)
            else:
                up = jax.nn.gelu(up)             # tanh approx (GPT-2 family)
        down = up @ lp["w_down"]
        if c.use_bias:
            down = down + lp["b_down"]
        return down, jnp.zeros((), jnp.float32)

    def _encode(self, params, x, angles=None, positions=None, rng=None,
                training=False, attn_mask=None):
        """Scan the block stack over already-embedded inputs x: [b, s, d].
        Returns (hidden, summed aux loss). Shared by the token path
        (:meth:`apply`) and non-token towers (vision patch embeddings)."""
        c = self.config
        layer_rng = rng if rng is not None else jax.random.PRNGKey(0)
        # when no window binds at this (static) length, windowed causal ==
        # plain causal: keep the flash path (Mistral at seq <= window).
        # A BINDING uniform window stays a static python int so _block can
        # dispatch the banded flash kernel; only per-layer-varying windows
        # (GPT-Neo) ride the scan as traced scalars.
        aw = c.attn_windows if c.window_binds(x.shape[1]) else None
        static_window = None
        if aw is not None and len(set(aw)) == 1:
            static_window, aw = aw[0], None
        windows = jnp.asarray(aw, jnp.int32) if aw is not None else None

        def block_of(kind):
            def block(x, lp, r, w):
                return self._block(x, lp, angles, positions, None, r,
                                   training, attn_mask,
                                   static_window if w is None else w, kind)

            if c.remat:
                from ..runtime.activation_checkpointing import \
                    checkpoint_wrapper

                return checkpoint_wrapper(block, policy=c.remat_policy)
            return block

        if c.layer_types is not None or getattr(c, "first_dense_layers", 0):
            # two kinds of layer (or of feed-forward: leading dense layers
            # before the expert layers) have two shapes of leaves: no one
            # scan body fits both, so depth is unrolled (compile time grows
            # with it)
            blocks = {kind: block_of(kind)
                      for kind in set(c.layer_types or ("full",))}
            aux_total = jnp.zeros((), jnp.float32)
            for li in range(c.n_layers):
                kind, lp = self.layer_params(params["layers"], li)
                layer_rng, sub = jax.random.split(layer_rng)
                x, _, aux = blocks[kind](
                    x, lp, sub, None if windows is None else windows[li])
                aux_total = aux_total + aux
            return x, aux_total
        block = block_of("full")

        def scan_fn(carry, xs):
            y, r = carry
            lp, w = (xs, None) if windows is None else xs
            r, sub = jax.random.split(r)
            y, _, aux = block(y, lp, sub, w)
            return (y, r), aux

        xs = params["layers"] if windows is None else (params["layers"], windows)
        (x, _), auxes = jax.lax.scan(scan_fn, (x, layer_rng), xs)
        return x, jnp.sum(auxes)

    # -- a looped stack: what closes a pass -----------------------------
    def exit_init(self, x) -> Tuple:
        """The exit's running state before the first pass, for hidden
        states shaped like ``x`` [..., d]: (the rows chosen so far, the
        probability of having stayed, who has left); empty where no gate
        is computed (one pass, or a threshold no cumulative probability
        short of the last pass's reaches)."""
        c = self.config
        if c.total_ut_steps == 1 or c.early_exit_threshold >= 1.0:
            return ()
        return (jnp.zeros_like(x), jnp.ones(x.shape[:-1], jnp.float32),
                jnp.zeros(x.shape[:-1], bool))

    @jax.named_scope("loop")
    def end_pass(self, params, x, t, state: Tuple):
        """What the recurrence adds to pass ``t`` (its index from 0, traced
        or not), under the scope ``loop``: the final norm on the pass's
        output ``x`` [..., d], which is the next pass's input, and, with a
        running ``state`` (:meth:`exit_init`), the gate in float32, the
        product and the select: a token leaves at the first pass whose
        cumulative exit probability ``1 - prod_j (1 - lambda_j)`` reaches
        the threshold, else at the last. Returns (x, state)."""
        c = self.config
        x = self._norm(x, params["final_norm_w"], params.get("final_norm_b"))
        if not state:
            return x, state
        chosen, stay, left = state
        f32 = jnp.float32
        # a multiply and a sum, not a product on the MXU: float32 as stated
        lam = jax.nn.sigmoid(
            jnp.sum(x.astype(f32) * params["exit_gate_w"].astype(f32)[:, 0],
                    axis=-1) + params["b_exit_gate"].astype(f32)[0])
        stay = stay * (1.0 - lam)
        leave = ~left & ((1.0 - stay >= c.early_exit_threshold)
                         | (t == c.total_ut_steps - 1))
        return x, (jnp.where(leave[..., None], x, chosen), stay,
                   left | leave)

    @staticmethod
    def exit_hidden(x, state: Tuple):
        """The rows that feed the head: each token's chosen pass's, or the
        last pass's ``x`` where no gate ran."""
        return state[0] if state else x

    def _encode_looped(self, params, x, angles, positions, rng, training,
                       attn_mask):
        """:meth:`_encode` under an outer scan over the passes: the same
        blocks and the same final norm every pass."""
        c = self.config
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        def one_pass(carry, t):
            x, r, state = carry
            r, sub = jax.random.split(r)
            x, aux = self._encode(params, x, angles, positions, sub,
                                  training, attn_mask)
            x, state = self.end_pass(params, x, t, state)
            return (x, r, state), aux

        (x, _, state), auxes = jax.lax.scan(
            one_pass, (x, rng, self.exit_init(x)),
            jnp.arange(c.total_ut_steps))
        return self.exit_hidden(x, state), jnp.sum(auxes)

    def apply(self, params, tokens, positions=None, kv_caches=None, cache_pos=None,
              rng=None, training=False, return_aux=False, last_token_only=False,
              return_hidden=False, token_type_ids=None, attn_mask=None):
        """Forward. tokens: [b, s] int32 -> logits [b, s, vocab] (fp32).

        ``kv_caches``: optional stacked (k,v) cache [n_layers, b, max_s, hkv, hd]
        pair for decode; returns (logits, new_caches) then.
        ``return_aux``: also return the summed auxiliary loss (MoE load
        balancing) accumulated across layers.
        ``return_hidden``: return the pre-head hidden states [b, s, d]
        instead of logits (the chunked-CE loss runs the head itself).
        ``token_type_ids``: [b, s] segment ids (encoder families; defaults
        to zeros when the config has type embeddings).
        ``attn_mask``: [b, s] padding mask for the bidirectional path.
        """
        c = self.config
        if kv_caches is not None and not c.causal:
            raise ValueError("KV-cache decode requires a causal model")
        if kv_caches is not None and c.layer_types is not None:
            raise NotImplementedError(
                "the dense KV cache holds no recurrent state: serve a model "
                "with linear layers through RaggedInferenceEngine")
        if kv_caches is not None and c.kv_lora_rank:
            raise NotImplementedError(
                "the dense KV cache holds K and V a head: serve latent "
                "attention through RaggedInferenceEngine, whose cache holds "
                "the latent row")
        if kv_caches is not None and c.total_ut_steps > 1:
            raise NotImplementedError(
                "the dense KV cache holds one K/V a layer and a looped "
                "stack writes one a layer a pass: serve it through "
                "RaggedInferenceEngine")
        x = self._embed(params, tokens, positions, token_type_ids)  # [b, s, d]
        angles = rope_frequencies(c.rotary_dim, c.max_seq_len, c.rope_theta,
                                  c.rope_yarn) \
            if c.position == "rope" else None

        aux_total = jnp.zeros((), jnp.float32)
        if kv_caches is None:
            encode = self._encode_looped if c.total_ut_steps > 1 \
                else self._encode
            x, aux_total = encode(params, x, angles, positions, rng,
                                  training, attn_mask)
            new_caches = None
        else:
            ks, vs = kv_caches
            windows = jnp.asarray(c.attn_windows, jnp.int32) \
                if c.attn_windows is not None else None

            def scan_fn(carry, layer_in):
                if windows is None:
                    (lp, ck, cv), w = layer_in, None
                else:
                    lp, ck, cv, w = layer_in
                y, (nk, nv), _aux = self._block(
                    carry, lp, angles, positions, (ck, cv, cache_pos),
                    attn_window=w)
                return y, (nk, nv)

            xs = (params["layers"], ks, vs) if windows is None \
                else (params["layers"], ks, vs, windows)
            x, (nks, nvs) = jax.lax.scan(scan_fn, x, xs)
            new_caches = (nks, nvs)

        if last_token_only:
            x = x[:, -1:]
        if return_hidden:
            out = x
        else:
            out = self._head(params, x)
        if new_caches is not None:
            return out, new_caches
        if return_aux:
            return out, aux_total
        return out

    # ------------------------------------------------------------------
    def _targets_from_batch(self, batch):
        """(inputs, targets, mask) for next-token CE. batch:
        {"input_ids": [b, s]} with optional "labels" (shifted internally when
        absent) and "loss_mask"."""
        tokens = batch["input_ids"]
        if "labels" in batch:
            mask = batch.get("loss_mask")
            if mask is not None:
                mask = mask.astype(jnp.float32)
            return tokens, batch["labels"], mask
        if not self.config.causal:
            # next-token shift is degenerate under bidirectional attention
            # (position i sees token i+1 directly — loss collapses to a
            # copy task); encoders must train on explicit labels (MLM)
            raise ValueError(
                "bidirectional (causal=False) models require explicit "
                "'labels' (+ 'loss_mask') in the batch — next-token "
                "prediction is not a valid encoder objective")
        # keep the full sequence length (it must stay divisible by the
        # seq mesh axis); predict shift-left targets and mask the final
        # position instead of slicing
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        last_off = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
        mask = batch.get("loss_mask")
        mask = last_off if mask is None else mask.astype(jnp.float32) * last_off
        return tokens, targets, mask

    def _ce_terms(self, logits, targets, mask):
        """(weighted nll sum, weight sum, z-loss sum) for one [b, s, v]
        logits block — fp32 accumulation."""
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if mask is not None:
            mask = mask[:, : nll.shape[1]].astype(jnp.float32)
            nll_sum = jnp.sum(nll * mask)
            denom = jnp.sum(mask)
        else:
            nll_sum = jnp.sum(nll)
            denom = jnp.asarray(float(np.prod(nll.shape)), jnp.float32)
        z_sum = jnp.zeros([], jnp.float32)
        if self.config.z_loss > 0:
            z = jnp.square(jax.scipy.special.logsumexp(logits, axis=-1))
            if mask is not None:
                z = z * mask
            z_sum = jnp.sum(z)
        return nll_sum, denom, z_sum

    def loss(self, params, batch, rng=None):
        """Next-token (or masked-LM, via explicit labels) cross entropy
        (+ z-loss + MoE aux). Encoder batches may carry "attention_mask"
        (padding) and "token_type_ids" (segments); both flow into the
        forward."""
        inputs, targets, mask = self._targets_from_batch(batch)
        # only encoder configs consume these; causal models ignore them the
        # way HF-tokenizer batches expect (all-ones attention_mask is the
        # decoder norm and must not trip the causal+mask guard)
        fwd_kw = {}
        if not self.config.causal and "attention_mask" in batch:
            fwd_kw["attn_mask"] = batch["attention_mask"]
        if self.config.type_vocab_size > 0 and "token_type_ids" in batch:
            fwd_kw["token_type_ids"] = batch["token_type_ids"]
        cs = self.config.loss_chunk_size
        # chunked: apply returns the hidden states and the scan runs the head
        out, aux = self.apply(params, inputs, rng=rng, training=True,
                              return_aux=True, return_hidden=cs > 0, **fwd_kw)
        with jax.named_scope("head"):  # the output head with the loss
            nll_sum, denom, z_sum = \
                self._ce_chunked(params, out, targets, mask, cs) if cs > 0 \
                else self._ce_terms(out, targets, mask)
            loss = nll_sum / jnp.maximum(denom, 1.0)
            if self.config.z_loss > 0:
                loss = loss + self.config.z_loss * z_sum \
                    / jnp.maximum(denom, 1.0)
        return loss + aux

    def _ce_chunked(self, params, x, targets, mask, chunk):
        """Head + CE over flattened token chunks under a scan, so the full
        [b*s, vocab] fp32 logits never materialize; ``jax.checkpoint`` on
        the body makes the backward recompute each chunk's logits from its
        [chunk, d] hidden slice instead of storing them."""
        d = x.shape[-1]
        xf = x.reshape(-1, d)
        tf = targets.reshape(-1)
        mf = jnp.ones_like(tf, jnp.float32) if mask is None \
            else mask.reshape(-1).astype(jnp.float32)
        n = xf.shape[0]
        pad = (-n) % chunk
        if pad:
            xf = jnp.pad(xf, ((0, pad), (0, 0)))
            tf = jnp.pad(tf, (0, pad))
            mf = jnp.pad(mf, (0, pad))  # padded lanes carry zero weight
        xc = xf.reshape(-1, 1, chunk, d)
        tc = tf.reshape(-1, 1, chunk)
        mc = mf.reshape(-1, 1, chunk)

        @jax.checkpoint
        def body(carry, xtm):
            xcb, tcb, mcb = xtm
            logits = self._head(params, xcb)          # [1, chunk, vocab] fp32
            ns, dn, zs = self._ce_terms(logits, tcb, mcb)
            a, b, c_ = carry
            return (a + ns, b + dn, c_ + zs), None

        init = (jnp.zeros([], jnp.float32),) * 3
        (nll_sum, denom, z_sum), _ = jax.lax.scan(body, init, (xc, tc, mc))
        return nll_sum, denom, z_sum

    # ------------------------------------------------------------------
    # pipeline-parallel path (reference: runtime/pipe/engine.py train_batch)
    @jax.named_scope("embed")
    def _embed(self, params, tokens, positions=None, token_type_ids=None):
        """Token (+ learned position) embedding: [b, s] -> [b, s, d] in the
        compute dtype.

        With the table vocab-sharded over 'model' (partition_specs), a plain
        gather forces SPMD "involuntary full rematerialization" (replicate
        the table, then repartition). The one-hot contraction keeps the
        lookup sharded: each shard contracts its vocab slice on the MXU and
        GSPMD inserts one psum of [b, s, d] — never materializing the full
        table on any chip (the Megatron VocabParallelEmbedding semantics,
        expressed as a matmul instead of masked gather + allreduce).
        """
        c = self.config
        compute_dtype = params["layers"]["w_up"].dtype
        if self._tp_size > 1:
            # clip for parity with the gather branch (jnp indexing clamps
            # out-of-range ids; unclipped one_hot would zero them instead)
            safe = jnp.clip(tokens, 0, c.vocab_size - 1)
            one_hot = jax.nn.one_hot(safe, c.vocab_size, dtype=compute_dtype)
            x = one_hot @ params["tok_embed"].astype(compute_dtype)
        else:
            x = params["tok_embed"][tokens]
        x = x.astype(compute_dtype)
        if c.embedding_multiplier != 1.0:
            x = x * c.embedding_multiplier
        if c.position == "learned":
            s = tokens.shape[-1]
            pos_emb = params["pos_embed"][:s] if positions is None else params["pos_embed"][positions]
            x = x + pos_emb.astype(compute_dtype)
        if c.type_vocab_size > 0:
            # segment embeddings (BERT); embed_norm below then normalizes
            # the SUM of word+position+type, matching BertEmbeddings
            tt = jnp.zeros_like(tokens) if token_type_ids is None else token_type_ids
            x = x + params["type_embed"][tt].astype(compute_dtype)
        if c.embed_norm:
            x = layer_norm(x, params["embed_norm_w"], params["embed_norm_b"],
                           c.norm_eps)
        return x

    @jax.named_scope("head")
    def _head(self, params, x):
        """Final norm + LM head: [..., s, d] -> fp32 logits [..., s, vocab].
        (A looped stack hands over the rows its passes chose, already
        normed.)

        Encoder MLM head (mlm_head): dense + gelu + LN transform before the
        tied decoder, plus a vocab bias (BertLMPredictionHead)."""
        c = self.config
        # a looped stack's final norm closes every pass (end_pass): its
        # hidden states arrive here normed
        if c.prenorm and c.total_ut_steps == 1:
            x = self._norm(x, params["final_norm_w"], params.get("final_norm_b"))
        if c.mlm_head:
            x = x @ params["mlm_dense_w"].astype(x.dtype) + params["mlm_dense_b"].astype(x.dtype)
            # HF BertPredictionHeadTransform reuses config.hidden_act —
            # follow the model's FFN activation, not a hardcoded GELU
            if c.activation == "relu":
                x = jax.nn.relu(x)
            else:
                x = jax.nn.gelu(x, approximate=(c.activation != "gelu_exact"))
            x = layer_norm(x, params["mlm_norm_w"], params["mlm_norm_b"], c.norm_eps)
        w_out = params["tok_embed"].T if c.tie_embeddings else params["lm_head"]
        logits = (x @ w_out.astype(x.dtype)).astype(jnp.float32)
        if c.mlm_head:
            logits = logits + params["mlm_bias"].astype(jnp.float32)
        if "lm_head_b" in params:  # GPT-J carries an LM-head bias
            logits = logits + params["lm_head_b"].astype(jnp.float32)
        if c.logits_scaling != 1.0:
            logits = logits / c.logits_scaling
        if c.logits_softcap > 0:
            logits = jnp.tanh(logits / c.logits_softcap) * c.logits_softcap
        return logits

    def pooled(self, params, hidden):
        """BertPooler: tanh dense on the [CLS] (first) token of the final
        hidden states ([b, s, d] from apply(..., return_hidden=True))."""
        if not self.config.pooler:
            raise ValueError("model config has pooler=False")
        cls = hidden[:, 0]
        return jnp.tanh(cls @ params["pooler_w"].astype(cls.dtype)
                        + params["pooler_b"].astype(cls.dtype))

    def pipeline_loss(self, params, batch, rng, num_microbatches: int):
        """Pipelined training loss over the whole global batch.

        Splits the batch into ``num_microbatches`` (= gradient-accumulation
        steps, as in the reference PipelineEngine where GAS is the number of
        in-flight micro-batches), embeds, pipelines the block stack over the
        ``pipe`` mesh axis via the rotating-microbatch executor, then runs
        the head + CE per micro-batch under a scan (so full-batch logits are
        never materialized at once).
        """
        from ..parallel.pipeline import microbatch, pipeline_apply, stack_stage_params

        c = self.config
        assert self._pipe_size > 1 and self._mesh is not None, \
            "pipeline_loss requires a bound topology with pipe axis > 1"
        if c.layer_types is not None:
            raise NotImplementedError(
                "the pipeline stage scan runs one kind of layer; a hybrid "
                "stack (layer_types) is not plumbed through it")
        if c.total_ut_steps > 1:
            raise NotImplementedError(
                "the pipeline runs its stages once a micro-batch; a looped "
                "stack (total_ut_steps > 1) is not plumbed through it")
        if self._seq_size > 1:
            raise NotImplementedError(
                "pipe x seq parallel composition not supported yet; "
                "use Ulysses/ring SP without the pipe axis")
        if not self.config.causal and (
                "attention_mask" in batch or "token_type_ids" in batch):
            raise NotImplementedError(
                "encoder attention_mask/token_type_ids not plumbed through "
                "the pipeline path yet — drop the pipe axis for BERT-style "
                "training")
        if rng is None:
            rng = jax.random.PRNGKey(0)

        inputs, targets, mask = self._targets_from_batch(batch)
        if self.config.window_binds(inputs.shape[1]):
            # the stage scan does not thread per-layer windows; a window
            # that never binds at this length is plain causal and fine
            raise NotImplementedError(
                "binding attention windows not plumbed through the "
                "pipeline stage scan yet — drop the pipe axis or keep "
                "seq_len <= window")
        mb = microbatch(
            {"inputs": inputs, "targets": targets,
             **({"mask": mask} if mask is not None else {})},
            num_microbatches)
        # lax.map (sequential) under TP bounds the one-hot embed transient to
        # one micro-batch's [b/M, s, vocab]; vmap would materialize all M at
        # once — a ~vocab/d_model blowup at the pipeline entrance
        if self._tp_size > 1:
            xs = jax.lax.map(lambda t: self._embed(params, t), mb["inputs"])
        else:
            xs = jax.vmap(lambda t: self._embed(params, t))(mb["inputs"])
        # xs: [M, b/M, s, d]
        angles = rope_frequencies(c.rotary_dim, c.max_seq_len, c.rope_theta,
                                  c.rope_yarn) \
            if c.position == "rope" else jnp.zeros((1, 1), jnp.float32)
        stage_params = stack_stage_params(params["layers"], self._pipe_size)

        # fp32 at the pipe boundary: inter-stage transfers and the
        # replicated-input cotangent reductions shard_map's autodiff inserts
        # accumulate in fp32 (sub-fp32 psum also miscompiles on XLA:CPU);
        # block compute stays in the params' compute dtype.
        compute_dtype = params["layers"]["wq"].dtype
        xs = xs.astype(jnp.float32)

        def stage_fn(lp_stage, x, consts, sub_rng, valid):
            x = x.astype(compute_dtype)

            def body(carry, lp):
                y, r = carry
                r, sub = jax.random.split(r)
                y, _, aux = self._block(y, lp, consts["angles"], None, None, sub, True)
                return (y, r), aux

            (y, _), auxes = jax.lax.scan(body, (x, sub_rng), lp_stage)
            return y.astype(jnp.float32), jnp.sum(auxes)

        ys, aux = pipeline_apply(
            stage_fn, stage_params, xs, rng, self._mesh,
            consts={"angles": angles}, remat=c.remat)

        # head + CE per micro-batch, scanned to bound logits memory
        def head_ce(carry, mb_t):
            logits = self._head(params, mb_t["x"].astype(compute_dtype))
            nll_sum, denom, z_sum = self._ce_terms(
                logits, mb_t["targets"], mb_t.get("mask"))
            nll_acc, den_acc, z_acc = carry
            return (nll_acc + nll_sum, den_acc + denom, z_acc + z_sum), None

        head_ce = jax.checkpoint(head_ce)
        zeros = (jnp.zeros([], jnp.float32),) * 3
        scan_in = {"x": ys, "targets": mb["targets"]}
        if mask is not None:
            scan_in["mask"] = mb["mask"]
        (nll_sum, denom, z_sum), _ = jax.lax.scan(head_ce, zeros, scan_in)
        loss = nll_sum / jnp.maximum(denom, 1.0)
        if c.z_loss > 0:
            loss = loss + c.z_loss * z_sum / jnp.maximum(denom, 1.0)
        return loss + aux

    # ------------------------------------------------------------------
    def partition_specs(self, params, topo=None) -> Dict[str, Any]:
        """Tensor-parallel PartitionSpecs over the 'model' axis.

        Megatron-style: column-parallel QKV/up/gate (shard output features),
        row-parallel O/down (shard input features), vocab-sharded embedding.
        This is the training-TP capability the reference delegates to an
        external mpu (SURVEY.md §2.2 "TP (training)") and implements for
        inference as AutoTP (module_inject/auto_tp.py) — here it is native.
        """
        c = self.config
        # pipeline parallelism: the stacked-layer leading dim is sharded over
        # 'pipe' so each stage group holds only its layers (reference:
        # PipelineModule assigns layer ranges to stage ranks, module.py:86)
        pipe_size = topo.pipe_parallel_size if topo is not None else self._pipe_size
        pipe = "pipe" if pipe_size > 1 else None
        layer_specs = {
            "attn_norm_w": P(pipe, None),
            "wq": P(pipe, None, "model"),
            "wk": P(pipe, None, "model"),
            "wv": P(pipe, None, "model"),
            "wo": P(pipe, "model", None),
            "mlp_norm_w": P(pipe, None),
            "w_up": P(pipe, None, "model"),
            "w_down": P(pipe, "model", None),
        }
        if c.activation == "silu_glu":
            layer_specs["w_gate"] = P(pipe, None, "model")
        if c.norm == "layer":
            layer_specs["attn_norm_b"] = P(pipe, None)
            layer_specs["mlp_norm_b"] = P(pipe, None)
        if c.qkv_bias:
            layer_specs.update({
                "bq": P(pipe, "model"), "bk": P(pipe, "model"),
                "bv": P(pipe, "model"),
            })
        if c.attn_o_bias:
            layer_specs["bo"] = P(pipe, None)
        if c.use_bias:
            layer_specs.update({
                "b_up": P(pipe, "model"), "b_down": P(pipe, None),
            })
        if c.qk_norm:
            layer_specs.update({"q_norm_w": P(pipe, None),
                                "k_norm_w": P(pipe, None)})
        if c.kv_lora_rank:
            # latent attention: the down-projections and their norms whole
            # on every device, the per-head up-projections by head
            for name in ("wq", "wk", "wv"):
                del layer_specs[name]
            layer_specs.update({
                "w_dq": P(pipe, None, None), "q_lora_norm_w": P(pipe, None),
                "w_uq": P(pipe, None, "model"), "w_dkv": P(pipe, None, None),
                "kv_lora_norm_w": P(pipe, None),
                "w_uk": P(pipe, None, "model"),
                "w_uv": P(pipe, None, "model")})
        if c.sandwich_norm:
            for name in ("attn_post_norm", "mlp_post_norm"):
                layer_specs[name + "_w"] = P(pipe, None)
                if c.norm == "layer":
                    layer_specs[name + "_b"] = P(pipe, None)
        if c.layer_types is not None:
            # the mixers' stacks: attention's leaves move under "full", the
            # delta rule's are column-parallel in, row-parallel out, and
            # its small per-head leaves replicated
            attn_keys = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
                         "q_norm_w", "k_norm_w")
            full = {k: layer_specs.pop(k) for k in attn_keys
                    if k in layer_specs}
            if c.layers_of("full"):
                layer_specs["full"] = full
            if c.layers_of("linear"):
                col, rep = P(pipe, None, "model"), P(pipe, None)
                layer_specs["linear"] = {
                    "wq": col, "wk": col, "wv": col, "w_z": col,
                    "conv_w": P(pipe, None, None), "w_a": P(pipe, None, None),
                    "w_beta": P(pipe, None, None), "A_log": rep,
                    "dt_bias": rep, "o_norm_w": rep,
                    "wo": P(pipe, "model", None)}
            if c.layers_of("mamba"):
                # replicated: in_proj's columns are z | x B C | dt side by
                # side and the gated norm runs over all of y, so no one
                # split of a leaf's columns follows the heads
                rep2, rep3 = P(pipe, None), P(pipe, None, None)
                layer_specs["mamba"] = {
                    "w_in": rep3, "conv_w": rep3, "conv_b": rep2,
                    "dt_bias": rep2, "A_log": rep2, "D": rep2,
                    "ssm_norm_w": rep2, "w_out": rep3}
        specs: Dict[str, Any] = {
            "tok_embed": P("model", None),
            "layers": layer_specs,
        }
        if c.prenorm:
            specs["final_norm_w"] = P(None)
            if c.norm == "layer":
                specs["final_norm_b"] = P(None)
        if c.position == "learned":
            specs["pos_embed"] = P(None, None)
        if c.type_vocab_size > 0:
            specs["type_embed"] = P(None, None)
        if c.embed_norm:
            specs["embed_norm_w"] = P(None)
            specs["embed_norm_b"] = P(None)
        if not c.tie_embeddings:
            specs["lm_head"] = P(None, "model")
            if isinstance(params, dict) and "lm_head_b" in params:
                specs["lm_head_b"] = P("model")  # GPT-J ingests carry one
        if c.total_ut_steps > 1:
            specs["exit_gate_w"] = P(None, None)
            specs["b_exit_gate"] = P(None)
        if c.mlm_head:
            # transform stays replicated (its output feeds a LayerNorm over
            # full d); the vocab bias follows the vocab-sharded embedding
            specs.update({"mlm_dense_w": P(None, None), "mlm_dense_b": P(None),
                          "mlm_norm_w": P(None), "mlm_norm_b": P(None),
                          "mlm_bias": P("model")})
        if c.pooler:
            specs["pooler_w"] = P(None, None)
            specs["pooler_b"] = P(None)
        return specs
