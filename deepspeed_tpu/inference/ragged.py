"""Ragged / continuous-batching inference with a paged KV cache
(FastGen v2 parity).

Reference surface (deepspeed/inference/v2/):
* ``InferenceEngineV2.put(uids, tokens)`` ragged decode step (engine_v2.py:107)
  and the ``query`` / ``can_schedule`` / ``flush`` scheduling API (:153-:228),
* ``DSStateManager`` + ``DSSequenceDescriptor`` (ragged/ragged_manager.py:19,
  ragged/sequence_descriptor.py),
* ``BlockedAllocator`` paged-KV block pool (ragged/blocked_allocator.py),
* the ragged-batch atom building the reference does in C++
  (ragged/csrc/fast_host_buffer.cpp) — here plain numpy on the host feeding
  ONE jitted step with static shapes,
* Dynamic-SplitFuse token scheduling (the FastGen blog's core idea):
  every step packs all pending decodes (1 token each) plus as many prompt
  tokens as fit into a fixed token budget, so the compiled program sees one
  shape regardless of the prefill/decode mix.

TPU-first redesign: CUDA FastGen builds variable "ragged atoms" per step and
launches paged-attention kernels over them. Under XLA every shape must be
static, so the step program is fixed at ``[token_budget]`` tokens and
``[max_seqs]`` sequence slots; inactive lanes are masked. Paged attention
dispatches to the Pallas kernel with scalar-prefetched block tables
(``ops/pallas/paged_attention.py``) on TPU; elsewhere a jnp gather
formulation with identical semantics serves as fallback and oracle.
"""

from __future__ import annotations

import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..ops.ragged_host import build_batch, fill_tables
from ..ops.rotary import rope_frequencies
from ..profiling.trace import annotate
from ..utils.logging import log_dist
from . import kv_cache
from .drafter import NgramIndex
# re-exported for the benchmark, which imports five names from here: these
# three, RaggedConfig and RaggedInferenceEngine. Everything else of the
# cache is imported from its home, kv_cache.py
from .kv_cache import (assert_block_balance,  # noqa: F401
                       kv_blocks_for_bytes, kv_page_bytes)
from .sampling import decide_masked, sample


def _paged_kernel_blocker(row: int, block: int, dtype, scalar_ints: int = 0,
                          latent: bool = False) -> Optional[str]:
    """Why the compiled Pallas paged kernel cannot serve this engine, or
    None when it can: it needs a real TPU, a tileable page shape (``row``:
    the values of a page's row, head_dim or, ``latent``, the latent row,
    which the kernel copies in whole lanes of 128) and prefetched scalars
    (per-seq tables, slots, positions) that fit SMEM (1 MB/core; keep them
    under half)."""
    from ..ops.attention import _on_tpu

    if not _on_tpu():
        return "not on a TPU"
    if scalar_ints * 4 > 512 * 1024:
        return (f"prefetched scalars ({scalar_ints * 4} B) exceed the "
                "512 KiB SMEM bound")
    sublane = 32 // jnp.dtype(dtype).itemsize  # 8 fp32 / 16 any 16-bit dtype
    if latent and row % 128:
        return f"latent row {row} not whole lanes of 128"
    if not latent and row not in (64, 128, 256):
        return f"head_dim {row} not in (64, 128, 256)"
    if block % sublane:
        return f"kv_block_size {block} not a multiple of {sublane}"
    return None


@dataclass
class SequenceDescriptor:
    """Reference DSSequenceDescriptor: uid, slot, tokens seen/scheduled,
    owned KV blocks."""

    uid: int
    slot: int
    tokens: List[int] = field(default_factory=list)  # full known token stream
    seen: int = 0                                    # tokens already in KV
    blocks: List[int] = field(default_factory=list)
    # telemetry clocks: t_admitted is cleared once TTFT is recorded;
    # t_created survives until flush() reports end-to-end latency
    t_admitted: Optional[float] = None
    t_created: Optional[float] = None
    prompt_len: int = 0
    # block diffusion only: the length the caller wants the stream to reach
    # (``limit_stream``; None = to the context bound)
    limit: Optional[int] = None

    @property
    def pending(self) -> int:
        return len(self.tokens) - self.seen


@dataclass
class RaggedConfig:
    """Knobs mirroring reference DSStateManagerConfig + RaggedBatchConfig
    (inference/v2/ragged/manager_configs.py): max_ragged_batch_size =
    token_budget, max_tracked_sequences = max_seqs, memory_config block
    count/size."""

    token_budget: int = 256
    max_seqs: int = 8
    kv_block_size: int = 16
    n_kv_blocks: int = 256
    max_context: int = 2048
    dtype: Any = jnp.bfloat16
    # sampling (parity: FastGen sampler, inference/sampling.py); 0.0 = greedy
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # automatic prefix caching (beyond the reference: FastGen has no KV
    # reuse across requests): completed sequences publish their full KV
    # blocks into an LRU cache keyed by the token prefix; new prompts
    # sharing a full-block prefix skip its prefill entirely. Shared pages
    # are refcounted; cache entries are evicted under pool pressure.
    enable_prefix_cache: bool = False
    # quantized KV storage ("none" | "int8" | "int4"): pages hold
    # blockwise-quantized payloads (one fp32 scale per K/V head-vector,
    # ops/quantizer.quantize_kv) — quantize on page write, dequantize in
    # the paged-attention read path. At a fixed pool BYTE budget this
    # roughly doubles (int8) / quadruples (int4) the page count, i.e.
    # concurrent sequences; export_kv/import_kv move the quantized
    # payload + scales on the wire (docs/serving.md "KV quantization")
    kv_quant: str = "none"


class _Program:
    """A jitted program built on the engine's core, called as it has
    always been: the live-page bucket is its static argument ``pages_at``.
    Before the jit's cache sees that argument the engine makes it the
    value its programs are keyed by (``_program_pages``: one value on the
    tiled attention path, whose kernel reads no bucket), so whoever calls,
    wraps or warms the program with whatever bucket lands on the program
    the serving tick runs. Everything else (``lower``, ``_cache_size``)
    is the jitted function's."""

    def __init__(self, engine, jitted, pages_at: int):
        self._engine = engine
        self._jitted = jitted
        self._pages_at = pages_at

    def __call__(self, *args):
        at = self._pages_at
        return self._jitted(*args[:at],
                            self._engine._program_pages(args[at]),
                            *args[at + 1:])

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def pack_fields(fields) -> np.ndarray:
    """A tick's five int32 arrays (tokens [T], slots [T], positions [T],
    block tables [max_seqs, max_pages], the rows the head reads [max_seqs]
    or [max_seqs, k]) end to end in one new int32 buffer, the form in which
    they go to the device: one transfer costs what each of five did
    (0.27-0.29 ms whatever it carries, PERF.md section 6). An array already
    on the device is fetched first (a warm-up's)."""
    return np.concatenate([np.asarray(a, np.int32).reshape(-1)
                           for a in fields])


class _Field:
    """One of a tick's five arrays as whoever stands in a step program's
    place sees it: its ``shape``, and ``packed``, the one device buffer
    that holds all five (``pack_fields``). ``_launch`` hands the step five
    of these; a wrapper reads their shapes and passes them on."""

    __slots__ = ("shape", "packed")

    def __init__(self, shape, packed):
        self.shape = shape
        self.packed = packed


def _packed_of(tokens, slots, positions, block_tables, sel):
    """The one device buffer behind a step call's five arrays: the one
    ``_launch``'s handles share, or five arrays (a warm-up's, a test's:
    numpy's or the device's) packed as ``_launch`` packs and sent."""
    if type(tokens) is _Field:
        return tokens.packed
    return jnp.asarray(pack_fields((tokens, slots, positions, block_tables,
                                    sel)))


class _Packed(_Program):
    """A program over a tick's batch (the SplitFuse step, the verify step)
    behind the eight arguments it has always been called with: ``(params,
    pools, tokens, slots, positions, block_tables, sel, live_pages)``. The
    jitted program takes ``(params, pools, packed, lanes, live_pages)``,
    ``lanes`` and ``live_pages`` static, and cuts the five fields out of
    ``packed`` itself (``RaggedInferenceEngine._fields``). A call with
    ``_launch``'s handles runs on the buffer they share; a call with five
    arrays (a warm-up, a test: numpy's or the device's) packs them with the
    function ``_launch`` uses and sends the buffer, so both find one
    compiled program. ``lower`` takes the eight too, abstract or real."""

    def __call__(self, params, pools, tokens, slots, positions,
                 block_tables, sel, live_pages):
        return self._jitted(
            params, pools,
            _packed_of(tokens, slots, positions, block_tables, sel),
            int(tokens.shape[0]), self._engine._program_pages(live_pages))

    def lower(self, params, pools, tokens, slots, positions, block_tables,
              sel, live_pages):
        size = sum(int(np.prod(a.shape)) for a in (
            tokens, slots, positions, block_tables, sel))
        # an abstract argument's sharding says where to compile for
        packed = jax.ShapeDtypeStruct(
            (size,), jnp.int32, sharding=tokens.sharding if isinstance(
                tokens, jax.ShapeDtypeStruct) else None)
        return self._jitted.lower(params, pools, packed,
                                  int(tokens.shape[0]), live_pages)


class _Step(_Packed):
    """The jitted SplitFuse step behind the two-result call it has always
    answered: ``(logits, pools)``. The program has a third result, each
    slot's greedy token id; a call leaves it on the engine (``_step_ids``)
    for ``_put``. It also says how many step programs the engine holds
    (gauge ``inference/step_programs``)."""

    def __call__(self, params, pools, tokens, slots, positions,
                 block_tables, sel, live_pages):
        # the base's call written out: one frame between the caller and the
        # jitted step, as there has always been (every traced operation's
        # location, and with it the compile cache's key, holds the stack)
        eng = self._engine
        logits, eng._step_ids, pools = self._jitted(
            params, pools,
            _packed_of(tokens, slots, positions, block_tables, sel),
            int(tokens.shape[0]), eng._program_pages(live_pages))
        t = eng._telemetry
        if t.enabled:
            t.registry.gauge("inference/step_programs").set(
                self._jitted._cache_size())
        return logits, pools


class RaggedInferenceEngine:
    """Continuous-batching engine over a deepspeed_tpu Transformer.

    ``put(uids, tokens)`` runs ONE compiled ragged step mixing prefill
    chunks and decodes (Dynamic SplitFuse); returns next-token logits per
    uid (NaN rows for uids whose prompt is still being prefilled across
    steps), or, for a caller that has declared greedy decoding
    (``return_token_ids``), the token ids the step chose (``-1`` there).
    ``generate`` drives put/flush to completion.

    A model that generates by diffusion over blocks (``attn_block`` > 1:
    SDAR) is served by the same scheduler, allocator, pool and step
    builder; ``put`` then feeds a stream once and ``[]`` after it, and its
    result is a block's (``_put_blocks``; docs/serving.md "The engine's
    result").
    """

    def __init__(self, model, config: Optional[RaggedConfig] = None,
                 params: Any = None, rng: Any = None, topology=None):
        self.config = config or RaggedConfig()
        self.model = model
        self.topo = topology
        c = model.config
        tp = topology.model_parallel_size if topology is not None else 1
        if tp > 1 and c.n_kv_heads % tp:
            raise ValueError(
                f"n_kv_heads {c.n_kv_heads} not divisible by the model "
                f"axis {tp} — TP serving shards the KV pool by head")
        self._tp_size = tp
        if self.config.max_context > c.max_seq_len:
            raise ValueError(
                f"max_context {self.config.max_context} exceeds model "
                f"max_seq_len {c.max_seq_len} (RoPE/position table bound)")
        if c.position == "alibi" or getattr(c, "parallel_residual", False):
            # the ragged step inlines the block math without ALiBi bias /
            # parallel-residual wiring; loud failure beats wrong logits
            raise NotImplementedError(
                "RaggedInferenceEngine does not support ALiBi or parallel-"
                "residual families yet; use InferenceEngine (dense KV cache)")
        # layers that hold a recurrent state (ops/gated_delta.py,
        # ops/mamba2.py), whatever their kind
        self._state_layers = kv_cache.state_layers(c)
        # whole-page moves (export, import, copy-on-write); a looped
        # stack's passes each have K/V of their own under one page id
        self._pages = kv_cache.PageMoves(c)
        self._passes = kv_cache.cache_passes(c)
        # a rolled hybrid stack: the step loops over its periods, and a
        # leaf of the pool serves the layer at its place in every period
        self._periods = kv_cache.cache_periods(c)
        # layers of K/V the cache holds for a token (an export's n_layers)
        self._kv_layers = kv_cache.cache_layers(c)
        if self.config.enable_prefix_cache:
            kv_cache.refuse_without_snapshot(
                c, "enable_prefix_cache (a new prompt adopting a cached "
                   "prefix's pages)")
        if self._state_layers and tp > 1:
            raise NotImplementedError(
                "recurrent layers are not sharded over the model axis yet")
        # latent attention (MLA): the attention layers' pages hold one row
        # a token for all heads (kv_cache.KVPool ``latent``), and the step
        # runs the absorbed form over them (_build_core ``latent_block``)
        self._latent = int(getattr(c, "latent_row", 0))
        if tp > 1:
            kv_cache.refuse_latent(c, "tensor-parallel serving")
        if self.config.kv_quant != "none":
            kv_cache.refuse_latent(c, f"kv_quant={self.config.kv_quant!r}")
        # an expert share (experts_held): the layer routes over all the
        # router's experts and computes its own; the step counts the held
        # experts its live lanes reached (``ragged.put``'s experts_touched)
        self._held = getattr(c, "experts_held", None)
        # ... out of this many: the held experts of every expert layer
        self._held_total = 0 if self._held is None else \
            c.n_held * (c.n_layers - c.first_dense_layers)
        # block diffusion (SDAR): a sequence that generates holds a block of
        # ``_block`` lanes a step, the step decides several of its tokens or
        # none, and a block's K/V is final only after a pass over the
        # finished block (the class docstring). 1 = one token a step
        self._block = int(getattr(c, "attn_block", 1))
        self._limits: Dict[int, int] = {}       # limit_stream, before admission
        self._decided_last = 0                  # tokens the last pass decided
        if self._block > 1:
            if tp > 1:
                self._refuse_in_blocks("tensor-parallel serving")
            if self.config.enable_prefix_cache:
                self._refuse_in_blocks("enable_prefix_cache")
            if self.config.kv_block_size % self._block:
                raise ValueError(
                    f"kv_block_size {self.config.kv_block_size} must be a "
                    f"multiple of the model's attn_block {self._block}")
            if self.config.temperature != 0.0:
                self._refuse_in_blocks("sampling (temperature > 0)")
        if c.window_binds(self.config.max_context):
            log_dist("RaggedInferenceEngine: binding sliding window — "
                     "banded paged kernel on TPU, banded gather elsewhere")
        if self.config.max_context % self.config.kv_block_size != 0:
            raise ValueError(
                f"max_context {self.config.max_context} must be a multiple of "
                f"kv_block_size {self.config.kv_block_size}")
        if self.config.kv_quant not in ("none", "int8", "int4"):
            raise ValueError(
                f"kv_quant must be 'none', 'int8' or 'int4', got "
                f"'{self.config.kv_quant}'")
        self._kv_bits = kv_cache.KV_BITS[self.config.kv_quant]
        if self._kv_bits == 4 and c.head_dim % 2:
            raise ValueError(
                f"kv_quant='int4' packs two channels per byte and needs an "
                f"even head_dim, got {c.head_dim}")
        # which paged-attention implementation the compiled step traces,
        # decided (and said) once at construction: "pallas" (compiled
        # kernel), "pallas_interpret" (DST_RAGGED_FORCE_PALLAS=interpret —
        # the CPU-lane token-exactness tests for the sharded kernel ride
        # this) or "gather" (XLA gather formulation, the off-TPU oracle)
        cfg = self.config
        self.max_pages = cfg.max_context // cfg.kv_block_size
        # the step's block table, kept between ticks (_host_tables): for
        # each slot the descriptor its row mirrors, how many leading
        # entries still equal that descriptor's blocks, and how many may
        # be non-zero
        self._table = np.zeros((cfg.max_seqs, self.max_pages), np.int32)
        self._table_owner: List[Optional[SequenceDescriptor]] = \
            [None] * cfg.max_seqs
        self._table_good = [0] * cfg.max_seqs
        self._table_filled = [0] * cfg.max_seqs
        blocker = _paged_kernel_blocker(
            self._latent or c.head_dim, cfg.kv_block_size, cfg.dtype,
            scalar_ints=cfg.max_seqs * self.max_pages + 2 * cfg.token_budget,
            latent=bool(self._latent))
        if os.environ.get("DST_RAGGED_FORCE_PALLAS", "") == "interpret":
            self.attention_path = "pallas_interpret"
        elif blocker is None:
            self.attention_path = "pallas"
        else:
            self.attention_path = "gather"
            log_dist(f"RaggedInferenceEngine: paged attention on the XLA "
                     f"gather path ({blocker})")
        if self._kv_bits == 4 and self.attention_path == "pallas":
            from ..ops.pallas.paged_attention import Int4KVKernelUnsupported

            raise Int4KVKernelUnsupported()
        self.params = params if params is not None else model.init(
            rng if rng is not None else jax.random.PRNGKey(0))
        self.params = jax.tree_util.tree_map(
            lambda x: x.astype(self.config.dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating) else x,
            self.params)
        if topology is not None and topology.world_size > 1:
            # sharded serving (FastGen v2's TP configuration, plus expert
            # parallelism for MoE): place params under the model's
            # partition specs; GSPMD shards every projection + the vocab
            # head (and routes expert dispatch over the 'expert' axis) and
            # inserts the collectives. The KV pool shards by head below
            # when a 'model' axis is present.
            from jax.sharding import NamedSharding

            specs = model.partition_specs(self.params, topology)
            self.params = jax.device_put(
                self.params,
                jax.tree_util.tree_map(
                    lambda sp: NamedSharding(topology.mesh, sp), specs,
                    is_leaf=lambda x: isinstance(x, PartitionSpec)))
        # the expert stacks stay whole in the step and ragged_dot indexes
        # the layer (Transformer.layer_params); under expert parallelism
        # the layer axis cannot merge with the sharded expert axis, and
        # the step slices as it does for every other leaf
        self._experts_in_place = (topology is None
                                  or topology.expert_parallel_size == 1)
        whole = [self.params["layers"][k] for k in model.stacked_operands
                 if self._experts_in_place
                 and k in self.params.get("layers", {})]
        self.expert_bytes_in_place = sum(
            a.size * a.dtype.itemsize for a in whole)
        # routed experts whose stacks the step reads in place: how many a
        # token takes and how many a layer holds (what _expert_product
        # needs)
        self._routed = (c.top_k, c.n_held) \
            if self._experts_in_place and model.stacked_operands else None
        if self._telemetry.enabled:
            self._telemetry.registry.gauge(
                "inference/expert_bytes_in_place").set(
                    self.expert_bytes_in_place)
        # the cache (kv_cache.py): the host's books of pages, prefixes and
        # slots, and the device leaves by name
        self.cache = kv_cache.KVLedger(cfg)
        self.allocator = self.cache.allocator
        self.prefix_cache = self.cache.prefix_cache
        self.seqs: Dict[int, SequenceDescriptor] = {}
        # uids whose next admission is a RESUME (post-preempt/discard):
        # their fresh descriptors must not re-record TTFT/latency — the
        # serving layer's request spans carry the true end-to-end numbers
        self._resume_uids: set = set()
        self.kv_pool = kv_cache.new_pool(c, cfg, topology)
        # whether the live-page bucket is a key of the step programs
        # (_program_pages): not where the paged kernel walks query tiles
        from ..ops.pallas.paged_attention import tiled_grid

        self._pages_key = not (
            self.attention_path != "gather"
            and tiled_grid(*self.kv_pool.k, *self.kv_pool.k_scale,
                           *self.kv_pool.latent))
        self.kv_bytes_per_token = \
            kv_cache.kv_page_bytes(c, cfg) // cfg.kv_block_size
        # what a live sequence holds beside its pages: its slot of every
        # recurrent layer's state and convolution rows
        self.state_bytes_per_slot = kv_cache.state_slot_bytes(c, cfg)
        if self._telemetry.enabled:
            self._telemetry.registry.gauge(
                "inference/kv_bytes_per_token").set(self.kv_bytes_per_token)
            if self._state_layers:
                self._telemetry.registry.gauge(
                    "inference/state_bytes_per_slot").set(
                        self.state_bytes_per_slot)
        self._rows_buf: Optional[np.ndarray] = None    # _rows_out
        self._token_ids = False     # return_token_ids
        self._step_fn = None
        self._step_ids = None       # the last step's ids, on the device
        self._call_leaves = 0       # _count_call_leaves, when a step is built
        self._core_fn = None
        self._decode_fn = None
        self._verify_fn = None
        # speculative-decoding acceptance stats (generate_speculative and
        # the serving tick's verify rounds; mirrored into the shared
        # MetricsRegistry by record_spec)
        self.spec_stats = {"proposed": 0, "accepted": 0, "rounds": 0}
        # per-uid memoized n-gram draft indices (draft_tokens): extended
        # lazily on append, truncated by trim(), dropped on flush/discard
        self._ngram_idx: Dict[int, NgramIndex] = {}
        # global-KV-tier seams (docs/serving.md "Global KV tier"), all
        # inert until enable_kv_tier() attaches them: the fleet's
        # host-memory cold tier, the directory-invalidate callback
        # (fired synchronously on eviction so a directory entry never
        # outlives its pages), and the per-engine adoption counters the
        # DST auditor reads
        self._cold_tier = None
        self._on_prefix_invalidate = None
        self._kv_tier_member = ""
        self.kvtier_cold_spills = 0
        self.kvtier_cold_readmits = 0
        self.kvtier_adopt_imports = 0
        self.kvtier_corrupt_landed = 0
        # sampling streams: decode steps fold a GLOBAL step counter into the
        # decode key, so sampled output is invariant to how decode_steps
        # calls chunk the token budget; prefill first-tokens get their own
        # stream (counter per put-round)
        base = rng if rng is not None else jax.random.PRNGKey(0)
        self._rng_prefill, self._rng_decode = jax.random.split(
            jax.random.fold_in(base, 7919))
        self._decode_step_counter = 0
        self._prefill_round_counter = 0
        # ragged-step token buckets (ascending, capped by the budget): a
        # decode-heavy step compiles + runs at the smallest fitting width
        self._buckets = [b for b in (64, 256, 1024) if b < cfg.token_budget] \
            + [cfg.token_budget]
        # the rule's outcome a lane bucket (None: no experts in place)
        products = self._routed and {
            b: self._expert_product(b) for b in self._buckets}
        log_dist(f"RaggedInferenceEngine: budget={cfg.token_budget} "
                 f"blocks={cfg.n_kv_blocks}x{cfg.kv_block_size} "
                 f"expert_bytes_in_place={self.expert_bytes_in_place} "
                 f"expert_products={products} "
                 f"page_bucket_keys_programs={self._pages_key} "
                 f"passes={self._passes} periods={self._periods} "
                 f"kv_bytes_per_token={self.kv_bytes_per_token}")

    def _refuse_in_blocks(self, what: str) -> None:
        """What a model that generates by blocks (``attn_block`` > 1) does
        not serve, until someone needs it."""
        if self._block > 1:
            raise NotImplementedError(
                f"{what} is not supported for a model that generates by "
                f"diffusion over blocks (attn_block={self._block}): a step "
                "decides several tokens of a sequence or none, and a block's "
                "K/V is provisional until its commit pass")

    def _refuse_share(self, what: str) -> None:
        """What an expert share (``experts_held``) and a latent pool do
        not serve, until someone needs and tests it: the verify program
        counts no experts, and its chains were never run over latent
        rows."""
        if self._held is not None or self._latent:
            raise NotImplementedError(
                f"{what} is not supported for a model that holds a share "
                "of its experts (experts_held) or caches latent rows "
                "(kv_lora_rank): only the SplitFuse step counts the "
                "experts reached and is tested over latent pages")

    @property
    def block_length(self) -> int:
        """Tokens a sequence's step decides together: the model's
        ``attn_block`` where it generates by diffusion over blocks (the
        server then feeds streams and reads committed blocks: ``put``),
        else 1."""
        return self._block

    @property
    def _writes_pages(self) -> bool:
        """Who writes a step's new K/V rows: one Pallas call a layer over
        the live tiles (``write_kv_pages``) wherever the paged kernel walks
        its tiles on one chip; else a scatter a leaf (``write_kv_rows``):
        off the TPU, under tensor parallelism (GSPMD partitions the scatter
        by its head index), for a quantized pool (its scale rows are 16
        elements wide) and for a page slab under 128 lanes wide (a head
        geometry ``kv_cache.pool_leaves`` could not lay out in whole rows:
        one KV head of 64), the two shapes Mosaic refuses a copy of; and
        for a latent leaf, whose one "head" makes the scatter an index a
        lane (the writer moves K and V leaves in pairs). Read off the
        pool's shapes, as the kernel's grid is."""
        from ..ops.pallas.paged_attention import tiled_grid

        return (self.attention_path != "gather" and self._tp_size == 1
                and not self._kv_bits and not self._latent
                and tiled_grid(*self.kv_pool.k))

    def _program_pages(self, live_pages: int) -> int:
        """The page count a compiled program of this engine is keyed by,
        from a tick's live-page bucket (``_live_pages_bucket``), for every
        jitted program built on ``_core`` (:class:`_Program`). Where the
        paged kernel takes the grid over query tiles, a tile walks its own
        positions' chunks and the bucket bounds nothing, so every bucket
        is one program, keyed ``max_pages``: a program a lane bucket. That
        is the kernel paths with a pool the kernel itself sends to that
        grid (``paged_attention.tiled_grid``: every leaf it copies a whole
        number of 128 lanes wide), read off the pool's shapes. Elsewhere
        the bucket is the key as it was: the lane grid's steps are lanes x
        chunks of the bucket (a quantized pool's scale rows; heads under
        128 wide that do not fill whole rows, as one KV head of 64 — eight
        of 64 do, so Granite holds a program a lane bucket: PR 50), and the
        ``gather`` path keeps its programs as they were."""
        return int(live_pages) if self._pages_key else self.max_pages

    def _expert_product(self, lanes: int) -> Optional[str]:
        """Which grouped product the ``lanes``-wide step program holds for
        its routed experts, ``"kernel"`` or ``"ragged_dot"``
        (``parallel/moe.expert_product``, the rule ``no_drop_moe`` traces
        by: from the path and the product's static shape); None for a
        model without routed experts, and where the expert stacks are
        sharded and the step slices them (GSPMD's ``ragged_dot``)."""
        if self._routed is None:
            return None
        from ..parallel.moe import expert_product

        top_k, n_experts = self._routed
        return expert_product(self.attention_path, lanes * top_k, n_experts)

    @property
    def _steps_live_slots(self) -> bool:
        """Whether a recurrent layer's one-token step is a kernel over the
        slots that decode (the delta rule's and Mamba-2's, each on the
        kernel paths) or XLA's form over every slot (off the TPU: the
        oracle of both)."""
        return bool(self._state_layers) and self.attention_path != "gather"

    @property
    def _telemetry(self):
        # resolved per call: the global pipeline may be installed after
        # this engine is constructed
        from ..telemetry import get_telemetry

        return get_telemetry()

    # -- scheduling API (parity engine_v2.query/can_schedule) -----------
    def query(self, uid: int) -> Tuple[int, int]:
        """(max new tokens schedulable for uid now, free kv blocks) —
        reference engine_v2.query :153. Accounts for the uid's remaining
        context window and the blocks it could still claim."""
        seen = self.seqs[uid].seen if uid in self.seqs else 0
        owned = len(self.seqs[uid].blocks) if uid in self.seqs else 0
        ctx_room = self.config.max_context - seen
        slack_in_blocks = owned * self.config.kv_block_size - seen
        avail = self.cache.available_blocks()
        kv_room = slack_in_blocks + avail * self.config.kv_block_size
        return (max(0, min(self.config.token_budget, ctx_room, kv_room)),
                avail)

    def blocks_needed(self, n_tokens: int) -> int:
        """KV pages a fresh sequence of ``n_tokens`` is charged at
        admission (its pages at full length, +1 write scratch). The ONE
        place this formula lives: the serving layer's admission oracle
        and submit-time over-pool reject must agree with the allocator,
        or admission either over-rejects feasible requests or admits
        requests that hit PoolExhausted mid-decode every tick."""
        return -(-int(n_tokens) // self.config.kv_block_size) + 1

    def can_schedule(self, uids: Sequence[int], lengths: Sequence[int]) -> bool:
        """Whether prompts of the given lengths fit (slots + kv blocks) —
        reference engine_v2.can_schedule :179."""
        bs = self.config.kv_block_size
        new = [u for u in uids if u not in self.seqs]
        need_blocks = 0
        for uid, length in zip(uids, lengths):
            if uid in self.seqs:
                seq = self.seqs[uid]
                total = seq.seen + length
                need_blocks += max(0, -(-total // bs) - len(seq.blocks))
            else:
                need_blocks += self.blocks_needed(length)
        return (len(new) <= self.cache.free_slots
                and need_blocks <= self.cache.available_blocks())

    def flush(self, uids: Sequence[int]) -> None:
        """Release sequence state + KV blocks (reference engine_v2.flush :228).
        With the prefix cache on, the sequence's full KV blocks are
        published (cache-retained) before its own refs drop."""
        now = time.perf_counter()
        for uid in uids:
            seq = self.seqs.pop(uid, None)
            self._ngram_idx.pop(uid, None)
            self._limits.pop(uid, None)
            if seq is not None:
                if seq.t_created is not None:
                    # request retires here: end-to-end latency + tokens the
                    # engine generated beyond the admitted prompt
                    self._telemetry.record_request(
                        latency_s=now - seq.t_created,
                        new_tokens=max(0, len(seq.tokens) - seq.prompt_len))
                if self.prefix_cache is not None:
                    self.prefix_cache.publish(seq.tokens, seq.blocks,
                                              seq.seen, self.allocator)
                self.allocator.free(seq.blocks)
                self.cache.give_slot(seq.slot)

    def preempt(self, uid: int) -> List[int]:
        """Release ``uid``'s slot + KV blocks WITHOUT retiring it as a
        completed request (no latency record) — the serving layer's
        eviction hook. Full KV blocks are published into the prefix cache
        first (when enabled), so the preempted prompt + generated tokens
        re-prefill mostly from cached pages on resume. Returns the
        KV-backed token stream (tokens actually prefilled/decoded; a
        mid-prefill tail that never reached the KV pool is excluded)."""
        seq = self.seqs.get(uid)
        if seq is None:
            return []
        toks = list(seq.tokens[:seq.seen])
        seq.t_created = None          # suppress request-retired telemetry
        self.flush([uid])
        self._resume_uids.add(uid)
        return toks

    def discard(self, uid: int) -> None:
        """Drop ``uid`` releasing its blocks + slot while publishing
        NOTHING into the prefix cache — the recovery hook for a failed
        step whose KV integrity is unknown (``seen`` may have advanced
        without the scatter landing). Zero-leak either way."""
        seq = self.seqs.pop(uid, None)
        self._ngram_idx.pop(uid, None)
        self._limits.pop(uid, None)
        if seq is None:
            return
        self.allocator.free(seq.blocks)
        self.cache.give_slot(seq.slot)
        self._resume_uids.add(uid)

    def clear_resume(self, uid: int) -> None:
        """Forget a ``preempt()``/``discard()`` resume marker for a uid
        that will never be re-admitted (it went terminal in the serving
        layer). Without this, a LATER unrelated sequence reusing the uid
        would silently skip its TTFT/latency telemetry, and the marker
        set would grow without bound under preempt-then-cancel churn."""
        self._resume_uids.discard(uid)

    # -- speculative drafting -------------------------------------------
    def draft_tokens(self, uid: int, next_token: Optional[int],
                     ngram: int, k: int) -> List[int]:
        """Prompt-lookup draft for ``uid``'s next decode step: up to ``k``
        proposal tokens continuing ``tokens + [next_token]`` (the not-yet-
        fed pending token rides as a virtual suffix). Memoized per uid:
        the n-gram index extends incrementally on append and truncates on
        ``trim``, so a draft round costs O(new tokens), not O(context)."""
        seq = self.seqs[uid]
        idx = self._ngram_idx.get(uid)
        if idx is None or idx.ngram != int(ngram):
            idx = NgramIndex(ngram)
            self._ngram_idx[uid] = idx
        idx.sync(seq.tokens)
        return idx.lookup([] if next_token is None else [int(next_token)], k)

    def record_spec(self, proposed: int = 0, accepted: int = 0,
                    rounds: int = 0) -> None:
        """Fold one speculative verify outcome into ``spec_stats`` AND the
        shared MetricsRegistry (inference/spec_* counters + acceptance
        gauge) — the one place the stats dict and the registry stay in
        sync. Host-side only; called by generate_speculative and the
        serving tick's verify dispatch."""
        s = self.spec_stats
        s["proposed"] += int(proposed)
        s["accepted"] += int(accepted)
        s["rounds"] += int(rounds)
        t = self._telemetry
        if not t.enabled:
            return
        r = t.registry
        if rounds:
            r.counter("inference/spec_rounds").inc(rounds)
        if proposed:
            r.counter("inference/spec_proposed").inc(proposed)
        if accepted:
            r.counter("inference/spec_accepted").inc(accepted)
        if s["proposed"]:
            r.gauge("inference/spec_acceptance").set(
                s["accepted"] / s["proposed"])

    # -- KV export/import (disaggregated prefill/decode hand-off) --------
    def export_kv(self, uid: int) -> "kv_cache.KVExport":
        """Snapshot ``uid``'s KV pages + token stream for hand-off to
        ANOTHER engine (disaggregated serving: a prefill replica computes
        the KV, a decode replica continues the stream). Host copy today —
        this is the explicit seam where an ICI/DMA page transfer plugs in
        later; the importer's accounting is identical either way.

        The sequence must be fully prefilled (``pending == 0``): exporting
        mid-prefill would hand off context whose tail has no KV. The
        export does NOT release anything — the caller decides whether to
        ``preempt`` (publish into this engine's prefix cache) or
        ``discard`` the local copy afterwards."""
        kv_cache.refuse_without_snapshot(self.model.config, "export_kv")
        kv_cache.refuse_latent(self.model.config, "export_kv")
        self._refuse_in_blocks("export_kv")
        seq = self.seqs.get(uid)
        if seq is None:
            raise KeyError(f"uid {uid} has no live sequence to export")
        if seq.pending:
            raise ValueError(
                f"uid {uid}: {seq.pending} tokens still pending prefill — "
                "a mid-prefill export would hand off torn context")
        if seq.seen == 0 or not seq.blocks:
            raise ValueError(f"uid {uid}: nothing prefilled yet")
        c = self.model.config
        # rows past ``seen`` in the last page are never-read scratch and
        # ride along
        k, v, ks, vs = self._pages.gather(self.kv_pool, seq.blocks)
        export = kv_cache.KVExport(
            uid=uid, tokens=list(seq.tokens), seen=seq.seen,
            prompt_len=seq.prompt_len,
            kv_block_size=self.config.kv_block_size,
            n_layers=self._kv_layers,
            n_kv_heads=c.n_kv_heads, head_dim=c.head_dim,
            dtype=str(jnp.dtype(self.config.dtype)), k_pages=k, v_pages=v,
            kv_quant=self.config.kv_quant, k_scales=ks, v_scales=vs)
        t = self._telemetry
        if t.enabled:
            t.registry.counter("inference/kv_exports").inc()
            t.registry.counter("inference/kv_export_pages").inc(
                len(seq.blocks))
            t.registry.counter("inference/kv_export_bytes").inc(
                export.nbytes)
        # bytes-on-wire ledger (comm/comm.py): the hand-off is a wire
        # transfer like any collective — logical = what an fp export of
        # the same pages would move, wire = the (quantized) payload +
        # scales actually shipped, so the disaggregated hand-off's
        # compression ratio is auditable next to the collective ops'
        from ..comm.comm import record_collective

        logical = (2 * len(seq.blocks) * export.n_layers * c.n_kv_heads
                   * self.config.kv_block_size * c.head_dim
                   * jnp.dtype(self.config.dtype).itemsize)
        record_collective("kv_handoff", logical, export.nbytes)
        return export

    def import_kv(self, uid: int, export: "kv_cache.KVExport") -> None:
        """Adopt an exported sequence: allocate pages from THIS engine's
        pool (evicting cached prefixes under pressure, same discipline as
        admission), scatter the pages in, and create a live descriptor at
        ``seen`` — so the next ``put(uid, [next_token])`` continues the
        stream bit-exactly without re-prefilling. Pages are charged and
        refcounted exactly like locally-computed ones: ``seq.blocks``
        holds one allocator ref each and ``assert_block_balance`` holds.

        Raises :class:`PoolExhausted` (recoverable — the caller can fall
        back to the re-prefill resume path) or ``ValueError`` on geometry
        mismatch. On any failure nothing is mutated."""
        kv_cache.refuse_without_snapshot(self.model.config, "import_kv")
        self._refuse_in_blocks("import_kv")
        cfg = self.config
        c = self.model.config
        if uid in self.seqs:
            raise ValueError(f"uid {uid} already live in this engine")
        want = (cfg.kv_block_size, self._kv_layers, c.n_kv_heads,
                c.head_dim, str(jnp.dtype(cfg.dtype)), cfg.kv_quant)
        have = (export.kv_block_size, export.n_layers, export.n_kv_heads,
                export.head_dim, export.dtype, export.kv_quant)
        if want != have:
            raise ValueError(
                f"KV geometry mismatch: engine (block,layers,hkv,hd,dtype,"
                f"kv_quant)={want} vs export {have}")
        if self._kv_bits and (export.k_scales is None
                              or export.v_scales is None):
            raise ValueError(
                f"export tagged kv_quant={export.kv_quant} carries no scales")
        if export.seen != len(export.tokens):
            raise ValueError(
                f"export seen {export.seen} != tokens {len(export.tokens)}")
        if export.seen > cfg.max_context:
            raise ValueError(
                f"export context {export.seen} exceeds max_context "
                f"{cfg.max_context}")
        need = export.n_pages
        if need != -(-export.seen // cfg.kv_block_size):
            raise ValueError(
                f"export carries {need} pages for {export.seen} tokens")
        if not self.cache.free_slots:
            raise RuntimeError("no free sequence slots; flush() first")
        self.cache.make_room(need)                    # may raise PoolExhausted
        blocks = self.allocator.allocate(need)
        try:
            self.kv_pool = self._pages.write(
                self.kv_pool, blocks,
                (export.k_pages, export.v_pages, export.k_scales,
                 export.v_scales), self.max_pages)
        except BaseException:
            self.allocator.release(blocks)
            raise
        # telemetry suppressed like a resume: the serving layer's request
        # span owns the end-to-end TTFT/latency story for handed-off work
        self.seqs[uid] = SequenceDescriptor(
            uid=uid, slot=self.cache.take_slot(),
            tokens=[int(t) for t in export.tokens], seen=int(export.seen),
            blocks=blocks, t_admitted=None, t_created=None,
            prompt_len=int(export.prompt_len))
        self._resume_uids.discard(uid)
        t = self._telemetry
        if t.enabled:
            t.registry.counter("inference/kv_imports").inc()

    # -- global KV tier (docs/serving.md "Global KV tier") ---------------
    def enable_kv_tier(self, *, member: str = "", cold_tier=None,
                       on_invalidate=None) -> None:
        """Attach this engine to the fleet's global KV tier:
        ``cold_tier`` receives evicted prefixes (host-memory spill),
        ``on_invalidate(hash)`` drops the directory entry synchronously
        at eviction time (an entry must never outlive its pages). Both
        hooks are leaf-locked, so firing them under the driver's
        serving lock is legal in the documented lock order."""
        kv_cache.refuse_without_snapshot(self.model.config, "the KV tier")
        self._refuse_in_blocks("the KV tier")
        self._kv_tier_member = str(member)
        self._cold_tier = cold_tier
        self._on_prefix_invalidate = on_invalidate
        if self.prefix_cache is not None and (
                cold_tier is not None or on_invalidate is not None):
            self.prefix_cache.on_evict = self._on_prefix_evict

    def _on_prefix_evict(self, key: Tuple[int, ...],
                         blocks: List[int]) -> None:
        """PrefixCache eviction hook: directory invalidation FIRST (the
        entry must be gone before the pages can be reused), then the
        cold-tier spill (a host copy gathered while the evicted entry's
        refs still pin the pages)."""
        if self._on_prefix_invalidate is not None:
            from ..serving.kvtier import prefix_hash

            self._on_prefix_invalidate(prefix_hash(key))
        cold = self._cold_tier
        if cold is not None:
            export = self._gather_prefix_export(key, list(blocks))
            if cold.put(export):
                self.kvtier_cold_spills += 1

    def prefix_residency_hashes(self) -> List[int]:
        """Hashes of every resident prefix-cache entry — the residency
        set a replica publishes into the fleet's prefix directory.
        Driver-thread only (reads the cache's entry map directly)."""
        if self.prefix_cache is None:
            return []
        from ..serving.kvtier import prefix_hash

        return [prefix_hash(k) for k in self.prefix_cache.keys()]

    def _gather_prefix_export(self, key: Tuple[int, ...],
                              blocks: List[int]):
        """Host-copy ``blocks`` (one gather per layer leaf, quantized
        payload + scales exactly as pooled) into a checksummed
        :class:`~deepspeed_tpu.serving.kvtier.PrefixExport`."""
        from ..serving.kvtier import PrefixExport

        c = self.model.config
        cfg = self.config
        kv_cache.refuse_latent(c, "a prefix export (the KV tier)")
        k, v, *scales = self._pages.gather(self.kv_pool, blocks)
        scales = tuple(scales) if self._kv_bits else None
        wire = sum(int(a.nbytes) for a in (k, v) + (scales or ()))
        n_layers = self._kv_layers
        # logical = the dense (unquantized) bytes the same pages would
        # move — the CommsLogger compression-ratio denominator
        logical = (2 * len(blocks) * n_layers * c.n_kv_heads
                   * cfg.kv_block_size * c.head_dim
                   * jnp.dtype(cfg.dtype).itemsize)
        return PrefixExport(
            tokens=key, n_pages=len(blocks),
            block_size=cfg.kv_block_size, n_layers=n_layers,
            n_kv_heads=c.n_kv_heads, head_dim=c.head_dim,
            dtype=str(jnp.dtype(cfg.dtype)), kv_quant=cfg.kv_quant,
            pages=(k, v), scales=scales,
            wire_bytes=wire, logical_bytes=logical,
            source=self._kv_tier_member)

    def export_prefix(self, tokens: Sequence[int]):
        """Snapshot the longest cached full-block prefix of ``tokens``
        for cross-replica adoption (quantized pages + scales on the
        wire, ZeRO++-style). Returns None on a cache miss. The pages
        are retained across the host gather so an eviction mid-export
        cannot free them under the copy; the transfer lands in the
        bytes-on-wire ledger as a ``kv_adopt`` row next to the
        disaggregated hand-off's ``kv_handoff``."""
        if self.prefix_cache is None:
            return None
        key, blocks = self.prefix_cache.lookup(tokens)
        if not blocks:
            return None
        blocks = list(blocks)
        self.allocator.retain(blocks)
        try:
            export = self._gather_prefix_export(key, blocks)
        finally:
            self.allocator.release(blocks)
        t = self._telemetry
        if t.enabled:
            t.registry.counter("inference/prefix_exports").inc()
            t.registry.counter("inference/prefix_export_pages").inc(
                len(blocks))
            t.registry.counter("inference/prefix_export_bytes").inc(
                export.wire_bytes)
        from ..comm.comm import record_collective

        record_collective("kv_adopt", export.logical_bytes,
                          export.wire_bytes)
        from ..resilience.chaos import get_fault_injector

        inj = get_fault_injector()
        if inj is not None and inj.on_prefix_export():
            # adoption-wire corruption: flip one token AFTER the
            # checksum was stamped — the importer's verify() must catch
            # the mismatch and fall back to local prefill
            export.tokens = ((export.tokens[0] ^ 0x1,)
                             + export.tokens[1:])
        return export

    def import_prefix(self, export) -> bool:
        """Adopt an exported PREFIX into this engine's prefix cache (no
        live sequence — the counterpart of :meth:`import_kv` for the
        global KV tier; cold-tier re-admission uses the same path).
        Verifies the checksum FIRST (a corrupted adoption must never
        land — DST invariant #19), then geometry, then allocates,
        scatters and publishes; the cache ends holding the only refs,
        so ``block_balance_report`` stays exact. Returns False when the
        prefix is already resident; raises ValueError / PoolExhausted
        (recoverable: the caller degrades to local prefill)."""
        if self.prefix_cache is None:
            raise ValueError("prefix cache disabled; nothing to adopt into")
        cfg = self.config
        c = self.model.config
        if not export.verify():
            if not getattr(self, "_kvtier_skip_verify", False):
                from ..serving.kvtier import CorruptExport
                raise CorruptExport(
                    "prefix export failed checksum verification "
                    "(corrupted in transit)")
            # planted-bug seam (tests/DST only): verification disabled —
            # the landed-corruption counter is invariant #19's witness
            self.kvtier_corrupt_landed += 1
        want = (cfg.kv_block_size, self._kv_layers, c.n_kv_heads,
                c.head_dim, str(jnp.dtype(cfg.dtype)), cfg.kv_quant)
        if want != export.geometry():
            raise ValueError(
                f"prefix KV geometry mismatch: engine (block,layers,hkv,"
                f"hd,dtype,kv_quant)={want} vs export {export.geometry()}")
        if self._kv_bits and export.scales is None:
            raise ValueError(
                f"export tagged kv_quant={export.kv_quant} carries no "
                f"scales")
        need = export.n_pages
        if need <= 0 or need != len(export.tokens) // cfg.kv_block_size \
                or len(export.tokens) % cfg.kv_block_size:
            raise ValueError(
                f"prefix export carries {need} pages for "
                f"{len(export.tokens)} tokens (full blocks required)")
        if len(export.tokens) > cfg.max_context:
            raise ValueError(
                f"prefix length {len(export.tokens)} exceeds max_context "
                f"{cfg.max_context}")
        if export.tokens in self.prefix_cache:
            return False            # already resident
        self.cache.make_room(need)                # may raise PoolExhausted
        blocks = self.allocator.allocate(need)
        try:
            self.kv_pool = self._pages.write(
                self.kv_pool, blocks,
                tuple(export.pages) + tuple(export.scales or (None, None)),
                self.max_pages)
        except BaseException:
            self.allocator.release(blocks)
            raise
        # publish takes the cache's own retains (one per nested level),
        # then the allocation ref drops — the cache holds the ONLY refs
        self.prefix_cache.publish(export.tokens, blocks,
                                  len(export.tokens), self.allocator)
        self.allocator.release(blocks)
        self.kvtier_adopt_imports += 1
        t = self._telemetry
        if t.enabled:
            t.registry.counter("inference/prefix_imports").inc()
            t.registry.counter("inference/prefix_import_pages").inc(need)
        return True

    def _cold_readmit(self, tokens: Sequence[int]) -> None:
        """Probe the cold tier for the longest spilled full-block prefix
        of ``tokens`` that is not already device-resident, and re-admit
        it through :meth:`import_prefix` (the same checksum/geometry
        path as remote adoption) so the admission match finds it.
        Best-effort: pool pressure or a failed verify degrades to plain
        prefill — degraded, never lost."""
        bs = self.config.kv_block_size
        for k in range((len(tokens) - 1) // bs, 0, -1):
            key = tuple(int(t) for t in tokens[: k * bs])
            if key in self.prefix_cache:
                return              # device cache already at least as good
            export = self._cold_tier.get(key)
            if export is None:
                continue
            try:
                if self.import_prefix(export):
                    self.kvtier_cold_readmits += 1
                    t = self._telemetry
                    if t.enabled:
                        t.registry.counter(
                            "inference/prefix_cold_readmits").inc()
            except (ValueError, RuntimeError):
                # PoolExhausted / corrupted entry: drop to plain prefill
                pass
            return

    def trim(self, uid: int, length: int) -> None:
        """Rewind ``uid`` to its first ``length`` tokens, freeing now-unused
        KV blocks. Attention reads are position-bounded, so stale KV past
        the trim point is never read; the next put()/decode overwrites it.
        Use after observing EOS inside a ``decode_steps`` chunk when the
        sequence will keep being served (post-EOS tokens were admitted by
        that chunk and would otherwise pollute further continuations)."""
        kv_cache.refuse_without_snapshot(self.model.config, "trim")
        self._refuse_in_blocks("trim")
        seq = self.seqs[uid]
        if not 0 <= length <= seq.seen:
            raise ValueError(
                f"uid {uid}: trim length {length} outside [0, seen={seq.seen}]")
        bs = self.config.kv_block_size
        keep = -(-length // bs) if length else 0
        # prefix-cache copy-on-write: after a mid-block trim the next
        # scatter targets rows INSIDE the boundary block; if that page is
        # shared (cache or another sequence holds it), writing would
        # corrupt the other holders — give this sequence a private copy.
        # Allocate it BEFORE mutating any state (evicting LRU prefixes if
        # the pool is dry): a failed trim must leave the sequence intact,
        # never pointed at a still-shared page it will scatter into.
        cow_new = None
        if (length % bs and keep <= len(seq.blocks)
                and self.allocator.refcount(seq.blocks[keep - 1]) > 1):
            if (self.allocator.free_blocks < 1
                    and self.prefix_cache is not None):
                self.prefix_cache.evict_for(self.allocator, 1)
            # eviction may have dropped the cache's own ref on the
            # boundary page, making it private — re-check before copying
            if self.allocator.refcount(seq.blocks[keep - 1]) > 1:
                cow_new = self.allocator.allocate(1)[0]   # may raise: state
                # untouched so far
        seq.tokens = seq.tokens[:length]
        seq.seen = length
        ngi = self._ngram_idx.get(uid)
        if ngi is not None:
            ngi.truncate(length)
        # the table's row is re-read from the boundary page on (_host_tables)
        self._table_good[seq.slot] = min(self._table_good[seq.slot],
                                         max(keep - 1, 0))
        if keep < len(seq.blocks):
            self.allocator.free(seq.blocks[keep:])
            del seq.blocks[keep:]
        if cow_new is not None:
            old = seq.blocks[keep - 1]
            self.kv_pool = self._pages.copy(self.kv_pool, old, cow_new)
            self.allocator.release([old])
            seq.blocks[keep - 1] = cow_new

    # -- step ------------------------------------------------------------
    def _admit_tokens(self, uids: Sequence[int],
                      tokens: Sequence[Sequence[int]]) -> None:
        """Admit new tokens into sequence descriptors — put()'s first
        phase, shared with :meth:`put_spec`: fresh uids get a slot (and
        adopt the longest cached full-block prefix), existing ones append
        their chunk. Span ``ragged.admit``: ``prompt`` tokens admitted
        for fresh uids, ``matched`` of them adopted from the prefix
        cache; returns the two."""
        with annotate("ragged.admit") as span:
            matched = prompt = 0
            for uid, toks in zip(uids, tokens):
                new = uid not in self.seqs
                if self._block > 1 and not new and len(toks):
                    raise ValueError(
                        f"uid {uid}: a model that generates by blocks decides "
                        "its own tokens in the step; continue it with []")
                if new:
                    slot = self.cache.take_slot()    # may raise: none free
                    now = time.perf_counter()
                    resumed = uid in self._resume_uids
                    self._resume_uids.discard(uid)
                    self.seqs[uid] = SequenceDescriptor(
                        uid=uid, slot=slot,
                        t_admitted=None if resumed else now,
                        t_created=None if resumed else now)
                    if self._state_layers and self._telemetry.enabled:
                        # the slot's recurrent state starts again from
                        # zeros (the step does it, at position 0)
                        self._telemetry.registry.counter(
                            "inference/state_resets").inc()
                seq = self.seqs[uid]
                seq.tokens.extend(int(t) for t in toks)
                if new:
                    seq.prompt_len = len(seq.tokens)
                    prompt += seq.prompt_len
                    if self._block > 1:
                        seq.limit = self._limits.pop(uid, None)
                        self._open_block(seq)
                if new and self.prefix_cache is not None and seq.tokens:
                    if self._cold_tier is not None:
                        # cold-tier re-admission first, so the match below
                        # can adopt a spilled prefix the device pool lost
                        self._cold_readmit(seq.tokens)
                    # adopt the longest cached full-block prefix: its KV
                    # pages are shared (retained), and prefill starts past
                    # them
                    shared, blocks = self.prefix_cache.match(seq.tokens)
                    if shared:
                        self.allocator.retain(blocks)
                        seq.blocks = list(blocks)
                        seq.seen = shared
                        matched += shared
            span.set_metadata(matched=matched, prompt=prompt)
        if matched and self._telemetry.enabled:
            self._telemetry.registry.counter(
                "inference/prefix_tokens_matched").inc(matched)
        return matched, prompt

    # -- generation by diffusion over blocks (attn_block > 1) -------------
    def limit_stream(self, uid: int, n_tokens: int) -> None:
        """Declared by the caller before ``uid`` is admitted: the length
        (prompt and answer) its stream should reach. The block that holds
        position ``n_tokens - 1`` is the last one opened, computed whole
        and committed by a pass of its own; without a limit blocks are
        opened up to ``max_context``. Only for a model that generates by
        blocks, whose engine and not whose caller extends the stream."""
        if self._block > 1:
            self._limits[int(uid)] = int(n_tokens)  # dslint: disable=races -- the engine is driven by one thread at a time: the server calls this from its ticking thread while it builds the feed, as it calls put and flush

    def _open_block(self, seq: SequenceDescriptor) -> bool:
        """Extends ``seq``'s stream to the end of the next block with the
        mask id: the block under way. A prompt's last ``len % block``
        tokens open the first one. False where the stream has reached its
        limit or the context bound."""
        B = self._block
        n = len(seq.tokens)
        end = n - n % B + B
        limit = self.config.max_context if seq.limit is None \
            else min(seq.limit, self.config.max_context)
        if n >= limit or end > self.config.max_context:
            return False
        seq.tokens.extend([self.model.config.mask_token_id] * (end - n))
        return True

    def _masked(self, seq: SequenceDescriptor) -> List[int]:
        """Positions of the block under way that are not decided yet."""
        mask = self.model.config.mask_token_id
        n = len(seq.tokens)
        return [p for p in range(n - self._block, n) if seq.tokens[p] == mask]

    def _pass_of(self, seq: SequenceDescriptor, take: int
                 ) -> Tuple[List[int], int]:
        """What a pass over ``seq``'s next ``take`` lanes does: (the masked
        positions it denoises, none unless it reaches the block under way;
        the position up to which K/V is final after it: the block under
        way stays open while a mask id is left in it)."""
        end = seq.seen + take
        masked = self._masked(seq) if end == len(seq.tokens) else []
        return masked, end - (self._block if masked else 0)

    def _pack_splitfuse(self) -> List[Tuple[SequenceDescriptor, int]]:
        """Dynamic SplitFuse packing: decodes (and short prompt tails)
        first, then the longest-pending prefill fills the leftover
        budget."""
        sched: List[Tuple[SequenceDescriptor, int]] = []
        budget = self.config.token_budget
        pending = sorted((s for s in self.seqs.values() if s.pending > 0),
                         key=lambda s: s.pending)
        for seq in pending:
            take = min(seq.pending, budget)
            if take < seq.pending:
                # a chunk is cut at whole blocks only: cut inside one, a
                # query would be shown keys that are not written yet
                take -= take % self._block
            if take == 0:
                break
            sched.append((seq, take))
            budget -= take
        return sched

    def return_token_ids(self, on: bool = True) -> None:
        """Declared by a caller that decodes greedily (``ServingEngine``
        does, at construction): from now on the first result of
        :meth:`put` and :meth:`put_spec` is ``[len(uids)]`` int32, each
        sequence's greedy next token as the step computed it on the
        device (``argmax`` of its float32 logits, first index on a tie,
        as ``np.argmax``), and ``-1`` where the logits form has a NaN row.
        A tick then brings back ``4 * max_seqs`` bytes, not ``max_seqs *
        vocab * 4`` (span ``ragged.fetch``'s ``bytes``, counter
        ``inference/fetch_bytes``). The engine's own ``generate`` /
        ``stream`` read logits whatever is declared here."""
        self._token_ids = bool(on)

    def put(self, uids: Sequence[int], tokens: Sequence[Sequence[int]]) -> np.ndarray:
        """Admit new tokens for ``uids`` and run one ragged step.

        Returns [len(uids), vocab] fp32 logits of each sequence's latest
        processed token; rows are NaN while a long prompt is still
        mid-prefill (call put(uid, []) again to continue it). After
        :meth:`return_token_ids`: [len(uids)] int32 greedy token ids,
        ``-1`` for those rows.
        """
        with annotate("ragged.put") as span:
            return self._put(span, uids, tokens, self._token_ids)

    def _put_logits(self, uids, tokens) -> np.ndarray:
        """:meth:`put` in the logits form, whatever a server declared."""
        with annotate("ragged.put") as span:
            return self._put(span, uids, tokens, False)

    def _put(self, span, uids, tokens, as_ids: bool) -> np.ndarray:
        """:meth:`put` under its span; the phases are spans of their own
        (docs/observability.md "Program spans and device scopes")."""
        if self._block > 1:
            return self._put_blocks(span, uids, tokens, as_ids)
        last_index = {}  # uid -> index in flat batch of its last token

        def select(sched, last_idx):
            # per-slot index of the row whose logits we need (sequences not
            # in this schedule keep a harmless 0 — their rows are never read)
            sel_idx = np.zeros((self.config.max_seqs,), np.int32)
            for (seq, take), li in zip(sched, last_idx):
                seq.seen += take
                last_index[seq.uid] = int(li)
                sel_idx[seq.slot] = li
            return sel_idx

        sched, attrs, logits = self._schedule_and_launch(span, uids, tokens,
                                                         select)
        # only what the caller reads comes back: [max_seqs] ids, or the
        # [max_seqs, vocab] logits (which otherwise never leave the device)
        got = self._step_ids if as_ids else logits
        with annotate("ragged.fetch", bytes=int(got.nbytes)):
            got = np.asarray(got)
            if self._held is not None:
                # an expert share's count, behind the ids (_build_step)
                tail = got[-2:] if as_ids else np.asarray(self._step_ids[-2:])
                attrs.update(experts_touched=int(tail[0]),
                             pairs_kept=int(tail[1]))
                span.set_metadata(experts_touched=attrs["experts_touched"],
                                  pairs_kept=attrs["pairs_kept"])
        with annotate("ragged.rows"):
            out = self._hand_back(uids, last_index, got.__getitem__,
                                  None if as_ids else got.shape[-1])
            self._record_step_telemetry(sched, got.nbytes, attrs)
        return out

    def _schedule_and_launch(self, span, uids, tokens, select):
        """A tick from the admission of ``tokens`` to the step's launch,
        the same for every model: the schedule packed, validated and
        allocated for WHOLE before any sequence moves, so an exhausted pool
        leaves every descriptor consistent (``seen`` never advances without
        its KV being written); ``ragged.put``'s attributes; then
        ``select(sched, last_idx)``, which moves the scheduled sequences
        and returns the rows the step's head reads, a slot each; then the
        launch. Returns (sched, attrs, the step's logits, still on the
        device)."""
        matched, prompt = self._admit_tokens(uids, tokens)
        with annotate("ragged.pack"):
            sched = self._pack_splitfuse()
            if not sched:
                raise ValueError("put() called with no pending tokens")
            needs = self._validate_sched(sched)
            flat_tokens, flat_slot, flat_pos, last_idx = \
                self._allocate_and_build(sched, needs)
            live_pages = self._live_pages_bucket()
            attrs = self._sched_attrs(sched, len(flat_tokens), live_pages)
            if self.prefix_cache is not None:
                # prompt tokens this tick admitted, and those of them whose
                # pages came from the prefix cache (``ragged.admit``'s too)
                attrs.update(matched=matched, prompt=prompt)
            span.set_metadata(**attrs)
            sel = select(sched, last_idx)
            block_tables = self._host_tables()
        with annotate("ragged.dispatch"):
            if self._step_fn is None:
                self._step_fn = self._build_step()
            self._step_ids = None    # never a stale step's, whatever runs
            logits, self.kv_pool = self._launch(
                self._step_fn, (flat_tokens, flat_slot, flat_pos,
                                block_tables, sel), live_pages)
        return sched, attrs, logits

    def _put_blocks(self, span, uids, tokens, as_ids: bool) -> np.ndarray:
        """:meth:`_put` for a model that generates by diffusion over blocks
        of ``B = attn_block`` positions (SDAR). A sequence's lanes in a
        pass run from its first position whose K/V is not final (``seen``,
        a multiple of B) to the end of the block under way, cut at whole
        blocks where the budget is short: prompt blocks (their K/V final
        once written), a finished block being committed, and the block
        under way, whose undecided positions hold the mask id. Every
        lane's row is written; ``seen`` moves over what is final. A pass
        that reaches the block under way is a denoise pass: the step
        decides ``denoise_tokens`` of its masked positions on the device
        (``_decide``) and the host writes them into the stream; when none
        is left the next block is opened at once, so the finished block's
        commit rides the next block's first pass (2B lanes), or, at the
        stream's limit, a pass of its own.

        Returns, for each uid, in the ids form int32 [len(uids), B]: the
        generated tokens whose K/V this pass made final (a block at most;
        ``-1`` at a prompt's positions and where nothing was committed);
        in the logits form float32 [len(uids), B, vocab]: the logits at the
        block under way's positions as this pass fed them, NaN for a uid
        this pass did not denoise."""
        B = self._block
        denoised: Dict[int, List[int]] = {}   # uid -> masked positions
        committed: Dict[int, List[int]] = {}  # uid -> its row of tokens

        def select(sched, last_idx):
            # the step's head reads each slot's last B lanes: the block
            # under way wherever the pass reached it
            sel_rows = np.zeros((self.config.max_seqs, B), np.int32)
            for (seq, take), li in zip(sched, last_idx):
                sel_rows[seq.slot] = np.arange(int(li) - B + 1, int(li) + 1)
                masked, end = self._pass_of(seq, take)
                if masked:
                    denoised[seq.uid] = masked
                if end > max(seq.seen, seq.prompt_len):
                    committed[seq.uid] = [
                        seq.tokens[p] if p >= seq.prompt_len else -1
                        for p in range(end - B, end)]
                seq.seen = end
            return sel_rows

        sched, attrs, logits = self._schedule_and_launch(span, uids, tokens,
                                                         select)
        got = (self._step_ids,) if as_ids else (self._step_ids, logits)
        n_bytes = sum(int(a.nbytes) for a in got)
        with annotate("ragged.fetch", bytes=n_bytes):
            got = [np.asarray(a) for a in got]
        with annotate("ragged.rows"):
            ids = got[0]                                   # [max_seqs, B]
            decided = 0
            for uid, masked in denoised.items():
                seq = self.seqs[uid]
                blk0 = len(seq.tokens) - B
                for p in masked:
                    tok = int(ids[seq.slot, p - blk0])
                    if tok >= 0:
                        seq.tokens[p] = tok
                        decided += 1
                if not self._masked(seq):
                    self._open_block(seq)
            self._decided_last = decided
            if as_ids:
                out = np.full((len(uids), B), -1, np.int32)
            else:
                out = np.full((len(uids), B, got[1].shape[-1]), np.nan,
                              np.float32)
            now = time.perf_counter()
            for i, uid in enumerate(uids):
                seq = self.seqs[uid]
                if as_ids and uid in committed:
                    out[i] = committed[uid]
                elif not as_ids and uid in denoised:
                    out[i] = got[1][seq.slot]
                if seq.t_admitted is not None and (
                        uid in denoised or uid in committed):
                    # the prompt is through and its first block's logits
                    # are on the host: TTFT, as in _hand_back
                    self._telemetry.record_request(
                        ttft_s=now - seq.t_admitted)
                    seq.t_admitted = None
            self._record_step_telemetry(sched, n_bytes, attrs)
            t = self._telemetry
            if t.enabled:
                r = t.registry
                r.counter("inference/denoise_passes").inc(len(denoised))
                r.counter("inference/blocks_committed").inc(attrs["commits"])
                r.counter("inference/tokens_decided").inc(decided)
                r.gauge("inference/block_length").set(B)
        return out

    def warm_step(self, lanes: int, pages: int) -> None:
        """Compiles (or loads) and runs the step program of one shape, a
        lane bucket and a live-page bucket, on an empty batch: every lane
        is inactive and its writes land on the scratch page. A server
        calls it at start-up for every shape its traffic can reach, so
        that no tick compiles. What that is: on the tiled attention path
        a program a lane bucket, whatever ``pages`` (``_program_pages``:
        the bucket is no key there, so any one value warms the bucket's
        program and the others find it compiled); on the lane grid (a
        quantized pool, heads that fill no whole row of the pool: one KV
        head of 64) and the ``gather`` path a program a
        (lanes, pages) pair. ``_step_fn._cache_size()`` and the gauge
        ``inference/step_programs`` say how many the engine holds."""
        cfg = self.config
        if self._step_fn is None:
            self._step_fn = self._build_step()
        sel = np.zeros((cfg.max_seqs,) + ((self._block,) if self._block > 1
                                          else ()), np.int32)
        tables = fill_tables([], [], cfg.max_seqs, self.max_pages)
        tok, slot, pos, _ = build_batch([], [], [], int(lanes))
        logits, self.kv_pool = self._step_fn(
            self.params, self.kv_pool, tok, slot, pos, tables, sel,
            int(pages))
        jax.block_until_ready(logits)

    def _launch(self, step, host, live_pages: int):
        """``ragged.dispatch``'s two kinds of work, a span each: the five
        host arrays of a tick laid end to end and sent to the device as
        one buffer in one transfer (``ragged.h2d``: ``arrays`` 1), then
        the jitted ``step`` called on it until it returns (``ragged.call``:
        the call flattens ``leaves`` arrays of parameters and pool and the
        one buffer, and returns once the program is enqueued, not when it
        has run). ``step`` is called with the eight arguments it has
        always had, the five arrays as handles onto the buffer
        (``_Field``), so a wrapper set in its place still reads a tick's
        shapes."""
        with annotate("ragged.h2d", arrays=1,
                      bytes=sum(a.nbytes for a in host)):
            packed = jnp.asarray(pack_fields(host))
        with annotate("ragged.call", leaves=self._call_leaves):
            return step(self.params, self.kv_pool,
                        *(_Field(a.shape, packed) for a in host), live_pages)

    def _hand_back(self, uids, last_index, pick,
                   width: Optional[int]) -> np.ndarray:
        """A step's first result in the form its caller declared: float32
        rows of ``width``, or with no ``width`` int32 ids (nothing is
        copied but the ids). A sequence whose prompt is through gets what
        ``pick(slot)`` reads of the step's result on the host (and its
        TTFT is recorded, once); any other NaN, or ``-1``."""
        if width is None:
            out = np.full((len(uids),), -1, np.int32)
        else:
            out = self._rows_out(len(uids), width)
        now = time.perf_counter()
        for i, uid in enumerate(uids):
            seq = self.seqs[uid]
            if seq.pending == 0 and uid in last_index:
                out[i] = pick(seq.slot)
                if seq.t_admitted is not None:
                    # prompt fully prefilled and its first result on host:
                    # TTFT. End-to-end latency is reported at flush(),
                    # when the request actually completes.
                    self._telemetry.record_request(
                        ttft_s=now - seq.t_admitted)
                    seq.t_admitted = None
            elif width is not None:
                out[i] = np.nan
        return out

    def _rows_out(self, n: int, width: int) -> np.ndarray:
        """[n, width] float32 for the rows a step hands back in the logits
        form (the ids form copies no row): the last call's buffer again
        once the caller has let go of it (nothing else refers to it or to
        a view of it), else a new one. A new [n, vocab] array every tick
        is megabytes of pages touched for the first time (20 MB at a
        vocabulary of 100k), which on the chip's host took 28 ms a tick in
        most processes (PERF.md, PR 28)."""
        buf = self._rows_buf
        # references when free: the attribute, this local, the argument
        if buf is None or buf.shape[1] != width or n > buf.shape[0] \
                or sys.getrefcount(buf) > 3:
            buf = self._rows_buf = np.empty(
                (max(n, self.config.max_seqs), width), np.float32)
        return buf[:n]

    def _sched_attrs(self, sched, lanes: int, live_pages: int
                     ) -> Dict[str, int]:
        """``ragged.put``'s attributes, read after allocation and before
        ``seen`` advances: the lane bucket, the live-page bucket, entries
        scheduled, lanes given to sequences still inside their prompt,
        single-token entries past it, pages left free, and the work the
        paged kernel is asked for in each layer: query tiles (of
        ``LATENT_TILE`` lanes under latent attention), and KV steps
        (a tile's live chunks, summed), to set beside the ``lanes * pages
        / 16`` steps of a grid over lanes and the page bucket; the passes
        the stack makes over a token (1 unless the model is looped) and
        the paged kernel's calls a step (``kv_layers``: the layers that
        hold pages, times the passes); and what the row writer
        (``write_kv_pages``) serves in each of those layers: its live
        tiles and the page slabs it moves (0 where a scatter writes the
        rows). With recurrent layers: how many there are
        (``state_layers``), the slots whose state is live, and the entries
        of one lane (a decode token, or a prompt's last) that the step
        kernel of their kind serves in each (``step_slots``; 0 where the
        step runs in XLA over every slot: ``_steps_live_slots``). With
        routed experts read in place: ``expert_kernel``, 1 where this
        program's grouped products are the Pallas kernel's and 0 where
        they are ``ragged_dot``'s (``_expert_product``). Under latent
        attention: ``latent_layers`` and ``ctx_rows``, the rows of the
        latent leaf the kernel reads in each (the scheduled contexts,
        summed). With a prefix cache: ``matched`` / ``prompt``
        (``_schedule_and_launch``). An expert share adds
        ``experts_held`` (the held experts of every expert layer) and, once
        the step's result is on the host, ``experts_touched`` and
        ``pairs_kept`` (``_put``)."""
        from ..ops.pallas.paged_attention import (LATENT_TILE, query_tile,
                                                  tile_counts)

        prefill = decode = single = 0
        B = self._block
        block_seqs = commits = 0
        for seq, take in sched:
            single += take == 1
            if B > 1:
                # lanes inside the prompt's whole blocks, and lanes of
                # generated blocks (one being committed, the one under way)
                whole = seq.prompt_len - seq.prompt_len % B
                n = min(take, max(0, whole - seq.seen))
                prefill += n
                decode += take - n
                masked, end = self._pass_of(seq, take)
                block_seqs += bool(masked)
                commits += end > max(seq.seen, seq.prompt_len)
            elif seq.seen < seq.prompt_len:
                prefill += take
            elif take == 1:
                decode += 1
        q_tiles, kv_steps, pages = tile_counts(
            [(take, seq.seen) for seq, take in sched],
            LATENT_TILE if self._latent else query_tile(lanes),
            self.config.kv_block_size, B)
        attrs = {"lanes": lanes, "pages": live_pages, "seqs": len(sched),
                 "prefill": prefill, "decode": decode,
                 "free": self.allocator.free_blocks,
                 "q_tiles": q_tiles, "kv_steps": kv_steps,
                 "passes": self._passes,
                 "kv_layers": self._kv_layers,
                 "write_tiles": q_tiles if self._writes_pages else 0,
                 "write_pages": pages if self._writes_pages else 0}
        if self._latent:
            # latent attention: the layers whose pages are latent rows, and
            # the rows the kernel reads in each: the scheduled sequences'
            # contexts after this step, summed
            attrs["latent_layers"] = self._kv_layers
            attrs["ctx_rows"] = sum(seq.seen + take for seq, take in sched)
        if self._state_layers:
            attrs["state_layers"] = len(self._state_layers)
            attrs["state_slots"] = len(self.seqs)
            attrs["step_slots"] = single if self._steps_live_slots else 0
        if self._routed:
            # routed experts: whether this program's grouped products are
            # the Pallas kernel's (1) or ragged_dot's (0)
            attrs["expert_kernel"] = int(
                self._expert_product(lanes) == "kernel")
        if self._held is not None:
            # an expert share: the experts held, over the expert layers
            # (what ``experts_touched`` is a share of)
            attrs["experts_held"] = self._held_total
        if B > 1:
            # block diffusion: the block length, the sequences whose block
            # under way this pass denoises, the tokens the pass before
            # decided, and the generated blocks this pass commits
            attrs.update(block=B, block_seqs=block_seqs,
                         decided=self._decided_last, commits=commits)
        return attrs

    def put_spec(self, uids: Sequence[int], tokens: Sequence[Sequence[int]],
                 drafts: Sequence[Sequence[int]]
                 ) -> Tuple[np.ndarray, Dict[int, Tuple[List[int], np.ndarray]]]:
        """One ragged step that ALSO verifies speculative draft chains —
        the serving tick's spec-decode entry point: prefill chunks,
        plain decodes and draft-extended decodes all pack into the ONE
        static verify shape (a superset of put()'s program returning
        per-chain-row logits).

        ``drafts[i]`` proposes continuation tokens AFTER ``tokens[i]``
        (which must then be exactly one pending decode token). Returns
        ``(out, verified)``: ``out`` is put()'s [len(uids), vocab]
        last-row logits (NaN mid-prefill rows unchanged), or after
        :meth:`return_token_ids` put()'s [len(uids)] ids; ``verified``
        maps each drafted uid to ``(chain, rows)`` — the chain actually
        scheduled (first element = the fed next token) and fp32 logits
        [len(chain), vocab] for every chain position, in either form
        (the one place logits still come back). The caller accepts
        the longest greedy-matching prefix and MUST ``trim`` the
        rejected tail before the uid's next step.

        Chains are all-or-strip under the token budget: a chain the
        budget cannot hold whole is SHORTENED (unscheduled proposals are
        stripped from the stream), never split into fake pending
        context. On PoolExhausted every remaining draft token is
        stripped before the raise, so the recovery retry (plain ``put``
        with empty chunks) sees exactly put()'s admitted state."""
        kv_cache.refuse_without_snapshot(self.model.config, "put_spec")
        self._refuse_in_blocks("put_spec (speculation)")
        self._refuse_share("put_spec (speculation)")
        with annotate("ragged.put") as span:
            return self._put_spec(span, uids, tokens, drafts)

    def _put_spec(self, span, uids, tokens, drafts):
        """:meth:`put_spec` under its span, in :meth:`_put`'s phases."""
        cfg = self.config
        self._admit_tokens(uids, tokens)
        with annotate("ragged.pack"):
            # validate EVERY chain before appending ANY draft token: a raise
            # mid-append would leave earlier uids' unverified drafts in their
            # streams, and the next plain put() would schedule them as real
            # context
            for uid, d in zip(uids, drafts):
                if d and self.seqs[uid].pending != 1:
                    raise ValueError(
                        f"uid {uid}: a draft chain continues exactly one "
                        f"pending decode token, found "
                        f"pending={self.seqs[uid].pending}")
            appended: Dict[int, int] = {}  # uid -> draft tokens on the stream
            for uid, d in zip(uids, drafts):
                if not d:
                    continue
                self.seqs[uid].tokens.extend(int(t) for t in d)
                appended[uid] = len(d)
            try:
                sched = self._pack_splitfuse()
                if not sched:
                    raise ValueError(
                        "put_spec() called with no pending tokens")
                # all-or-strip: drop draft proposals the budget left behind
                take_of = {seq.uid: take for seq, take in sched}
                for uid in list(appended):
                    seq = self.seqs[uid]
                    chain_len = 1 + appended[uid]
                    take = take_of.get(uid, 0)
                    if take < chain_len:
                        strip = chain_len - max(take, 1)
                        if strip:
                            del seq.tokens[len(seq.tokens) - strip:]
                            appended[uid] -= strip
                        if appended[uid] <= 0:
                            appended.pop(uid)
                sched = [(seq, min(take, seq.pending))
                         for seq, take in sched if seq.pending > 0]
                needs = self._validate_sched(sched)
            except BaseException:
                for uid, n in appended.items():
                    seq = self.seqs[uid]
                    del seq.tokens[len(seq.tokens) - n:]
                raise
            flat_tokens, flat_slot, flat_pos, last_idx = \
                self._allocate_and_build(sched, needs)
            live_pages = self._live_pages_bucket()
            attrs = self._sched_attrs(sched, len(flat_tokens), live_pages)
            span.set_metadata(**attrs)
            k_max = 1
            for seq, take in sched:
                if seq.uid in appended:
                    while k_max < take:
                        k_max *= 2
            sel_rows = np.zeros((cfg.max_seqs, k_max), np.int32)
            last_index: Dict[int, int] = {}
            for (seq, take), li in zip(sched, last_idx):
                li = int(li)
                sel_rows[seq.slot, :] = li        # padding rows: never read
                if seq.uid in appended:
                    sel_rows[seq.slot, :take] = np.arange(li - take + 1,
                                                          li + 1)
                seq.seen += take
                last_index[seq.uid] = li
            block_tables = self._host_tables()
        with annotate("ragged.dispatch"):
            if self._verify_fn is None:
                self._verify_fn = self._build_verify()
            logits, self.kv_pool = self._launch(
                self._verify_fn, (flat_tokens, flat_slot, flat_pos,
                                  block_tables, sel_rows), live_pages)
        # the chains' rows are read as logits by the caller, so the whole
        # result comes back in either form
        with annotate("ragged.fetch", bytes=int(logits.nbytes)):
            logits = np.asarray(logits)       # [max_seqs, k_max, vocab]

        with annotate("ragged.rows"):
            # sel_rows[slot, -1] is the last scheduled row whether or not
            # the slot carried a chain — put()'s contract holds
            as_ids = self._token_ids
            last = lambda slot: logits[slot, -1]
            out = self._hand_back(
                uids, last_index,
                (lambda slot: np.argmax(last(slot))) if as_ids else last,
                None if as_ids else logits.shape[-1])
            verified: Dict[int, Tuple[List[int], np.ndarray]] = {}
            for seq, take in sched:
                if seq.uid in appended:
                    chain = [int(t) for t in seq.tokens[seq.seen - take:
                                                        seq.seen]]
                    verified[seq.uid] = (chain, logits[seq.slot, :take])
            self._record_step_telemetry(sched, logits.nbytes, attrs)
        return out, verified

    def _record_step_telemetry(self, sched, fetched: int,
                               attrs: Dict[str, int]) -> None:
        """Per-ragged-step series: scheduled tokens, bytes of the step's
        result brought to the host, page slabs the row writer moved (the
        span's ``attrs``), pool occupancy. Host dict updates only —
        nothing here touches the device."""
        t = self._telemetry
        if not t.enabled:
            return
        r = t.registry
        r.counter("inference/ragged_steps").inc()
        r.counter("inference/fetch_bytes").inc(fetched)
        r.counter("inference/kv_pages_written").inc(
            attrs["write_pages"] * attrs["kv_layers"])
        r.counter("inference/scheduled_tokens").inc(
            sum(take for _, take in sched))
        r.gauge("inference/kv_occupancy").set(self.cache.occupancy())
        r.gauge("inference/live_sequences").set(len(self.seqs))
        if self._state_layers:
            r.gauge("inference/state_slots_live").set(len(self.seqs))
            r.counter("inference/state_slots_stepped").inc(
                attrs["step_slots"] * len(self._state_layers))
        if self._routed:
            r.counter("inference/expert_kernel_ticks"
                      if attrs["expert_kernel"]
                      else "inference/expert_ragged_dot_ticks").inc()
        if "experts_touched" in attrs:
            r.counter("inference/experts_touched").inc(
                attrs["experts_touched"])
            r.counter("inference/pairs_kept").inc(attrs["pairs_kept"])

    def _validate_sched(self, sched) -> List[int]:
        """Validate a (seq, take) schedule WITHOUT mutating anything:
        context bound, pool demand (evicting cached prefixes if needed),
        and batch-width fit. Returns per-entry new-block needs."""
        cfg = self.config
        needs = []
        for seq, take in sched:
            new_total = seq.seen + take
            if new_total > cfg.max_context:
                raise ValueError(
                    f"uid {seq.uid}: context {new_total} exceeds "
                    f"max_context {cfg.max_context}")
            needs.append(-(-new_total // cfg.kv_block_size) - len(seq.blocks))
        # the whole schedule's new-block demand must fit the pool before
        # ANY uid is granted blocks (validate, then allocate)
        self.cache.make_room(sum(n for n in needs if n > 0))
        scheduled = sum(take for _, take in sched)
        if scheduled > cfg.token_budget:
            raise ValueError(f"scheduled tokens {scheduled} exceed "
                             f"token_budget {cfg.token_budget}")
        return needs

    def _allocate_and_build(self, sched, needs):
        """Grant blocks and build the flat step batch (reference: C++
        fast_host_buffer). T rounds the scheduled token count up to a
        bucket, not the full budget: a pure-decode step with 32 live seqs
        must not pay a 4096-lane forward (one compile per bucket, cached
        by jit). The numpy fallback of build_batch is bit-identical to
        the native builder."""
        scheduled = sum(take for _, take in sched)
        T = next(b for b in self._buckets if b >= scheduled)
        chunks, seens_l, slots_l = [], [], []
        for (seq, take), need in zip(sched, needs):
            if need > 0:
                seq.blocks.extend(self.allocator.allocate(need))
            chunks.append(seq.tokens[seq.seen:seq.seen + take])
            seens_l.append(seq.seen)
            slots_l.append(seq.slot)
        return build_batch(chunks, seens_l, slots_l, T)

    def _put_verify(self, uids: Sequence[int],
                    chains: Sequence[List[int]]) -> List[np.ndarray]:
        """Speculative-verify step: admit each uid's token chain and return
        the logits of EVERY chain row (vs put(), which selects only the
        last). One device call verifies all proposals; the caller accepts
        the longest matching prefix and trims the rest. k is pow2-bucketed
        so the jit cache stays O(log k) wide."""
        kv_cache.refuse_without_snapshot(self.model.config, "speculative verification")
        self._refuse_in_blocks("speculative verification")
        cfg = self.config
        sched = [(self.seqs[u], len(c)) for u, c in zip(uids, chains)]
        # validate BEFORE touching seq.tokens: a failed round must not
        # leave unverified draft tokens in any sequence's stream
        needs = self._validate_sched(sched)
        for u, c in zip(uids, chains):
            self.seqs[u].tokens.extend(int(t) for t in c)
        flat_tokens, flat_slot, flat_pos, last_idx = \
            self._allocate_and_build(sched, needs)
        k_max = 1
        while k_max < max(take for _, take in sched):
            k_max *= 2
        sel_rows = np.zeros((cfg.max_seqs, k_max), np.int32)
        for (seq, take), li in zip(sched, last_idx):
            li = int(li)
            sel_rows[seq.slot, :take] = np.arange(li - take + 1, li + 1)
            sel_rows[seq.slot, take:] = li      # padding rows: never read
            seq.seen += take
        if self._verify_fn is None:
            self._verify_fn = self._build_verify()
        logits, self.kv_pool = self._verify_fn(
            self.params, self.kv_pool, flat_tokens, flat_slot, flat_pos,
            self._host_tables(), sel_rows, self._live_pages_bucket())
        logits = np.asarray(logits)             # [max_seqs, k_max, vocab]
        return [logits[seq.slot, :take] for seq, take in sched]

    def _count_call_leaves(self) -> None:
        """``ragged.call``'s ``leaves``: the arrays a step call flattens
        (parameters and pool), counted here once a built step, not by a
        tree walk a tick."""
        self._call_leaves = len(jax.tree_util.tree_leaves(
            (self.params, self.kv_pool)))

    def _build_verify(self):
        self._count_call_leaves()
        core = self._core
        model = self.model

        fields = self._fields

        def step(params, pools, packed, lanes, live_pages):
            tokens, slots, positions, block_tables, sel_rows = fields(
                packed, lanes)
            sel_rows = sel_rows.reshape(block_tables.shape[0], -1)  # [S, k]
            x, pools = core(params, pools, tokens, slots, positions,
                            block_tables, live_pages)
            with jax.named_scope("head"):
                x_sel = x[sel_rows.reshape(-1)]             # [S*k, d]
                logits = model._head(params, x_sel[None, :])[0]
            return logits.reshape(sel_rows.shape + (-1,)), pools

        return _Packed(self, jax.jit(step, donate_argnums=(1,),
                                     static_argnums=(3, 4)), 4)

    def _fields(self, packed, lanes: int):
        """A step program's five fields cut out of its one int32 argument
        (``pack_fields``' order) at offsets static at trace time:
        tokens, slots, positions ``[lanes]`` each at 0, ``lanes`` and
        ``2 * lanes``; the block tables ``[max_seqs, max_pages]`` at
        ``3 * lanes``; and all that is behind them, flat: the rows the
        head reads, ``[max_seqs]`` for the plain step, for the others
        ``[max_seqs, k]`` to reshape (a block's lanes; a verify step's
        ``k_max``, which the buffer's length gives)."""
        S, P = self.config.max_seqs, self.max_pages
        tables = 3 * lanes
        return (packed[:lanes], packed[lanes:2 * lanes],
                packed[2 * lanes:tables],
                packed[tables:tables + S * P].reshape(S, P),
                packed[tables + S * P:])

    def _host_tables(self) -> np.ndarray:
        """The dense [max_seqs, max_pages] block table of the live
        sequences (zero-padded rows, zero rows for free slots), as
        ``fill_tables`` would build it, patched from the tick before: a
        row takes only the pages its sequence gained since (a block list
        grows at its end; :meth:`trim`, the one place that rewrites one,
        says from where), so a tick's host work follows its new pages and
        not the contexts' length (converting 26 lists of ~540 pages was
        1.1 ms of a 5 ms gap in ``a.x-k1.docs``). A copy is handed out:
        the transfer may still read it when the next tick patches."""
        table, owner = self._table, self._table_owner
        good, filled = self._table_good, self._table_filled
        for slot, seq in enumerate(owner):
            if seq is not None and self.seqs.get(seq.uid) is not seq:
                table[slot, :filled[slot]] = 0
                owner[slot], good[slot], filled[slot] = None, 0, 0
        for seq in self.seqs.values():
            slot, n = seq.slot, len(seq.blocks)
            if n > self.max_pages:
                raise ValueError(f"sequence owns {n} blocks > max_pages "
                                 f"{self.max_pages}")
            if owner[slot] is not seq:
                owner[slot], good[slot] = seq, 0
            have = min(good[slot], n)
            if have < n:
                table[slot, have:n] = seq.blocks[have:n]
            if n < filled[slot]:
                table[slot, n:filled[slot]] = 0
            good[slot] = filled[slot] = n
        return table.copy()

    def _live_pages_bucket(self) -> int:
        """Static page-walk bound for this step: smallest power of two >=
        the longest live sequence's page count (pow2-bucketed so the jit
        cache holds O(log max_pages) variants, not one per context len).
        The spans' ``pages``; a program's key only where the kernel walks
        it (``_program_pages``)."""
        most = max((len(s.blocks) for s in self.seqs.values()), default=1)
        b = 1
        while b < most:
            b *= 2
        return min(b, self.max_pages)

    def decode_steps(self, first_tokens: Dict[int, int], k: int,
                     eos_token_id: Optional[int] = None) -> Dict[int, List[int]]:
        """Decode ``k`` tokens (greedy or sampled per config) for every uid
        in ``first_tokens`` in ONE device call (see _build_decode).

        ``first_tokens[uid]`` is the next input token (produced by the
        previous step's logits, not yet admitted). Returns uid -> the k
        tokens generated after it; the last one is returned un-processed —
        feed it as the next call's first token (exactly like the
        one-token-at-a-time put() contract). Every uid must be fully
        prefilled (pending == 0).

        EOS caveat: all k tokens are admitted to the sequence's context
        (KV + token stream) before the caller can observe EOS inside the
        chunk. ``generate()`` handles this by flushing finished uids; a
        caller that keeps serving a uid via put()/decode_steps after an
        in-chunk EOS must first ``trim(uid, ...)`` back to the EOS
        position, or the post-EOS tokens become permanent context."""
        cfg = self.config
        self._refuse_in_blocks("decode_steps (one token a step)")
        if k < 1:
            raise ValueError(f"decode_steps needs k >= 1, got {k}")
        # validate every uid before allocating anything (same two-phase
        # discipline as put()): a rejected uid must not leave earlier uids
        # holding blocks with no KV written
        needs = []
        for uid in first_tokens:
            seq = self.seqs[uid]
            if seq.pending:
                raise ValueError(f"uid {uid} still has pending prefill")
            total = seq.seen + k
            if total > cfg.max_context:
                raise ValueError(
                    f"uid {uid}: decode chunk to {total} exceeds "
                    f"max_context {cfg.max_context}")
            needs.append(-(-total // cfg.kv_block_size) - len(seq.blocks))
        self.cache.make_room(sum(n for n in needs if n > 0))
        for uid, need in zip(first_tokens, needs):
            if need > 0:
                self.seqs[uid].blocks.extend(self.allocator.allocate(need))

        S = cfg.max_seqs
        toks = np.zeros((S,), np.int32)
        pos = np.zeros((S,), np.int32)
        slots = np.full((S,), -1, np.int32)
        for uid, first in first_tokens.items():
            seq = self.seqs[uid]
            toks[seq.slot] = first
            pos[seq.slot] = seq.seen
            slots[seq.slot] = seq.slot

        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        steps_xs = np.arange(self._decode_step_counter,
                             self._decode_step_counter + k, dtype=np.int32)
        self._decode_step_counter += k
        eos = -1 if eos_token_id is None else int(eos_token_id)
        gen, self.kv_pool = self._decode_fn(
            self.params, self.kv_pool, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(slots), jnp.asarray(self._host_tables()),
            jnp.asarray(steps_xs), self._rng_decode,
            self._live_pages_bucket(), eos)
        gen = np.asarray(gen)                                   # [S, k]

        out = {}
        for uid, first in first_tokens.items():
            seq = self.seqs[uid]
            chain = gen[seq.slot].tolist()
            if eos >= 0:
                # device-side freeze: only tokens actually FED are context.
                # first==eos feeds nothing; eos at chain[j] means first +
                # chain[:j] were fed (the EOS itself is emitted, not fed)
                if first == eos:
                    fed = []
                elif eos in chain:
                    j = chain.index(eos)
                    fed = [first] + chain[:j]
                else:
                    fed = [first] + chain[:-1]
                seq.tokens.extend(fed)
                seq.seen += len(fed)
            else:
                # positions seen..seen+k-1 now hold first + chain[:-1]
                seq.tokens.extend([first] + chain[:-1])
                seq.seen += k
            out[uid] = chain
        return out

    # -- generation convenience -----------------------------------------
    def _sample_first(self, rows) -> List[int]:
        """First decode token(s) from resolved prefill logits rows —
        greedy on host, else one sampled draw per prefill round (the
        round counter advances ONLY when sampling, so greedy calls never
        shift the seeded streams of later sampled calls)."""
        if self.config.temperature == 0.0:
            return [int(np.argmax(r)) for r in rows]
        key = jax.random.fold_in(self._rng_prefill,
                                 self._prefill_round_counter)
        self._prefill_round_counter += 1
        toks = np.asarray(sample(jnp.asarray(np.stack(rows)), key,
                                  self.config.temperature,
                                  self.config.top_k, self.config.top_p))
        return [int(t) for t in toks]

    def stream(self, uid: int, prompt: Sequence[int], *,
               max_new_tokens: int = 128,
               eos_token_id: Optional[int] = None,
               decode_chunk: int = 8):
        """Incremental generation: yields decoded tokens as chunks
        complete (the MII/FastGen streaming-response surface). Drives the
        same put()/decode_steps machinery as generate(); the uid is
        flushed when the stream ends — including early consumer breaks
        and mid-prefill failures (no slot/block leak)."""
        self._refuse_in_blocks("stream (use generate, or put)")
        logits = self._put_logits([uid], [list(prompt)])
        try:
            while np.isnan(logits[0]).any():
                logits = self._put_logits([uid], [[]])
            tok = self._sample_first([logits[0]])[0]
            produced = 0
            yield tok
            produced += 1
            if eos_token_id is not None and tok == eos_token_id:
                return
            while produced < max_new_tokens:
                room = self.config.max_context - self.seqs[uid].seen
                if room <= 0:
                    return
                k = max(1, min(decode_chunk, max_new_tokens - produced, room))
                chain = self.decode_steps({uid: tok}, k,
                                          eos_token_id=eos_token_id)[uid]
                for t in chain:
                    yield t
                    produced += 1
                    if eos_token_id is not None and t == eos_token_id:
                        return
                tok = chain[-1]
        finally:
            if uid in self.seqs:
                self.flush([uid])

    def generate(self, prompts: Dict[int, Sequence[int]], max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 decode_chunk: int = 16) -> Dict[int, List[int]]:
        """Generation: SplitFuse put() steps until every prompt is
        prefilled, then ``decode_steps`` chunks of up to ``decode_chunk``
        tokens per device call. Greedy when config.temperature == 0, else
        temperature/top-k/top-p sampling (chunk-invariant streams).
        Returns uid -> generated tokens."""
        if self._block > 1:
            return self._generate_blocks(prompts, max_new_tokens,
                                         eos_token_id)
        done: Dict[int, List[int]] = {u: [] for u in prompts}
        first = self._prefill_first(prompts, done)

        live = {u: t for u, t in first.items()
                if len(done[u]) < max_new_tokens
                and not (eos_token_id is not None and t == eos_token_id)}
        while live:
            budget = min(max_new_tokens - len(done[u]) for u in live)
            room = min(self.config.max_context - self.seqs[u].seen
                       for u in live)
            k = max(1, min(decode_chunk, budget, room))
            gens = self.decode_steps(live, k, eos_token_id=eos_token_id)
            nxt = {}
            for u, chain in gens.items():
                stop = False
                for t in chain:
                    done[u].append(t)
                    if eos_token_id is not None and t == eos_token_id:
                        stop = True
                        break
                if (not stop and len(done[u]) < max_new_tokens
                        and self.seqs[u].seen < self.config.max_context):
                    nxt[u] = chain[-1]
            live = nxt
        for u in done:
            done[u] = done[u][:max_new_tokens]
        self.flush(list(prompts))
        return done

    def _generate_blocks(self, prompts, max_new_tokens: int,
                         eos_token_id: Optional[int]) -> Dict[int, List[int]]:
        """:meth:`generate` for a model that generates by blocks: passes
        until every stream has its tokens committed (greedy; the last block
        is computed whole and cut to ``max_new_tokens``, and at an EOS)."""
        done: Dict[int, List[int]] = {u: [] for u in prompts}
        for u, p in prompts.items():
            self.limit_stream(u, len(p) + max_new_tokens)
        with annotate("ragged.put") as span:
            rows = self._put_blocks(span, list(prompts),
                                    [list(p) for p in prompts.values()], True)
        live = list(prompts)
        while True:
            for u, row in zip(live, rows):
                done[u].extend(int(t) for t in row if t >= 0)
            live = [u for u in live if self.seqs[u].pending
                    and len(done[u]) < max_new_tokens
                    and (eos_token_id is None or eos_token_id not in done[u])]
            if not live:
                break
            with annotate("ragged.put") as span:
                rows = self._put_blocks(span, live, [[] for _ in live], True)
        for u, toks in done.items():
            if eos_token_id is not None and eos_token_id in toks:
                toks = toks[:toks.index(eos_token_id) + 1]
            done[u] = toks[:max_new_tokens]
        self.flush(list(prompts))
        return done

    def _prefill_first(self, prompts: Dict[int, Sequence[int]],
                       done: Dict[int, List[int]]) -> Dict[int, int]:
        """Run SplitFuse prefill to completion for ``prompts``, collecting
        each uid's first decode token as its row resolves (long prompts
        span multiple put() steps). Appends the first token to ``done``
        and returns uid -> first token. Shared by generate() and
        generate_speculative() (identical under greedy; sampled first
        tokens ride the seeded prefill stream)."""
        uids = list(prompts)
        logits = self._put_logits(uids, [list(p) for p in prompts.values()])
        first: Dict[int, int] = {}
        while True:
            pending, resolved = [], []
            for u, row in zip(uids, logits):
                if np.isnan(row).any():
                    pending.append(u)
                else:
                    resolved.append((u, row))
            if resolved:
                toks_out = self._sample_first([r for _, r in resolved])
                for (u, _), t in zip(resolved, toks_out):
                    first[u] = t
            if not pending:
                break
            uids = pending
            logits = self._put_logits(pending, [[] for _ in pending])
        for u, t in first.items():
            done[u].append(t)
        return first

    def generate_speculative(self, prompts: Dict[int, Sequence[int]],
                             max_new_tokens: int = 32,
                             eos_token_id: Optional[int] = None,
                             ngram: int = 3,
                             lookahead: int = 4) -> Dict[int, List[int]]:
        """Prompt-lookup speculative decoding (greedy only; beyond the
        reference — FastGen decodes strictly one token per step).

        Each round drafts up to ``lookahead`` continuation tokens per
        sequence by matching its trailing ``ngram`` against earlier
        context (zero-cost n-gram draft; no draft model), verifies the
        whole chain in ONE ragged step via per-row logits, accepts the
        longest matching prefix, and trims the rejected tail's KV.
        Greedy acceptance makes the output TOKEN-IDENTICAL to
        ``generate()`` — acceptance rate only changes how many device
        round trips it takes. Stats land in ``self.spec_stats``.
        """
        self._refuse_share("generate_speculative")
        self._refuse_in_blocks("generate_speculative")
        if self.config.temperature != 0.0:
            raise NotImplementedError(
                "speculative decoding is greedy-only (temperature == 0); "
                "sampled acceptance needs rejection sampling")
        done: Dict[int, List[int]] = {u: [] for u in prompts}
        first = self._prefill_first(prompts, done)

        live = {u: t for u, t in first.items()
                if len(done[u]) < max_new_tokens
                and not (eos_token_id is not None and t == eos_token_id)}
        while live:
            # fair-share the token budget across live chains so the
            # verify round always fits one step batch
            share = max(1, self.config.token_budget // len(live))
            v_uids, v_chains = [], []
            for u, t0 in live.items():
                seq = self.seqs[u]
                room = self.config.max_context - seq.seen
                if room <= 0:
                    continue
                k = max(0, min(lookahead, room - 1, share - 1,
                               max_new_tokens - len(done[u]) - 1))
                # memoized n-gram draft (NgramIndex): O(new tokens) per
                # round instead of rescanning the whole context
                guesses = self.draft_tokens(u, t0, ngram, k)
                v_uids.append(u)
                v_chains.append([t0] + guesses)
            if not v_uids:
                break
            rows = self._put_verify(v_uids, v_chains)
            round_proposed = round_accepted = 0
            nxt: Dict[int, int] = {}
            for u, chain, lr in zip(v_uids, v_chains, rows):
                a = np.argmax(lr, axis=-1)            # [len(chain)]
                matched = 0
                while (matched < len(chain) - 1
                       and int(a[matched]) == chain[matched + 1]):
                    matched += 1
                round_proposed += len(chain) - 1
                round_accepted += matched
                emitted = [int(x) for x in a[:matched + 1]]
                seq = self.seqs[u]
                seen0 = seq.seen - len(chain)
                stop_at = None
                if eos_token_id is not None and eos_token_id in emitted:
                    stop_at = emitted.index(eos_token_id)
                    emitted = emitted[:stop_at + 1]
                # rewind KV/tokens to the validated prefix (rejected rows
                # are never read — attention is position-bounded — but the
                # token stream must stay clean for further serving)
                keep = seen0 + (stop_at if stop_at is not None
                                else matched) + 1
                if keep < seq.seen:
                    self.trim(u, keep)
                done[u].extend(emitted)
                if (stop_at is None and len(done[u]) < max_new_tokens
                        and seq.seen < self.config.max_context):
                    nxt[u] = emitted[-1]
            self.record_spec(proposed=round_proposed,
                             accepted=round_accepted, rounds=1)
            live = nxt
        for u in done:
            done[u] = done[u][:max_new_tokens]
        self.flush(list(prompts))
        return done

    # -- the compiled ragged step ----------------------------------------
    def _build_core(self):
        """The shared ragged forward: (params, pools, tokens, slots,
        positions, block_tables) -> (hidden [T, d], pools). Traced inside
        both the SplitFuse ``put`` step and the multi-step decode loop."""
        from ..ops.pallas.paged_attention import (LATENT_TILE,
                                                  latent_attention,
                                                  latent_attention_reference,
                                                  paged_attention,
                                                  paged_attention_reference,
                                                  work_list, write_kv_pages,
                                                  write_kv_rows)

        model = self.model
        c = model.config
        cfg = self.config
        bs = cfg.kv_block_size
        # per-layer sliding windows (static tuple; 0 = global causal);
        # binding windows ride the banded Pallas kernel per layer on TPU
        # (window passed statically below) and the banded gather elsewhere
        aw = getattr(c, "attn_windows", None)
        windows = tuple(int(w) if 0 < int(w) < cfg.max_context else 0
                        for w in aw) if aw is not None \
            else (0,) * c.n_layers
        state_layers = self._state_layers
        passes = self._passes
        # TP shards the pool/heads. GSPMD cannot partition a pallas_call,
        # so under TP the kernel runs INSIDE a shard_map whose specs name
        # the operands' existing sharding (heads/pool over 'model', tables/
        # positions replicated) — each device runs the kernel on its local
        # head shard with zero collectives, exactly the treatment the
        # training flash wrapper got (models/transformer.py _attention;
        # reference frame: FastGen's TP4 headline,
        # blogs/deepspeed-fastgen/README.md:163). Attention is head-local,
        # so no psum is needed; the o-proj contraction after it is GSPMD's.
        # Binding sliding windows ride the kernel too: the per-layer window
        # is STATIC (the python layer loop is unrolled), and the kernel
        # skips + DMA-dedups chunks below the band (O(window) traffic).
        # (no indivisible-heads fallback needed here: __init__ rejects
        # n_kv_heads % tp != 0 outright, and n_heads is a multiple of
        # n_kv_heads, so any engine that reaches this point shards cleanly)
        interp = self.attention_path == "pallas_interpret"
        use_pallas = self.attention_path != "gather"

        kv_bits = self._kv_bits
        use_writer = self._writes_pages
        # block diffusion: the mask's rule, an argument only where it is
        # not today's (with attn_block 1 the programs are the ones they were)
        blockwise = {"attn_block": self._block} if self._block > 1 else {}

        def _paged_attn_sharded(q, kp, vp, tables, positions, slots, work,
                                live_pages, window, k_scale=None,
                                v_scale=None, kv_bits=0):
            """shard_map the paged kernel over the bound mesh: heads and
            pool (payload AND scale leaves; dim 1 is heads) sharded on
            'model', scalars (the step's work list among them) replicated.
            Every mesh axis is manual: Mosaic refuses to lower a kernel
            under a partly automatic mesh."""
            from jax.sharding import PartitionSpec as P_

            sharded = (q, kp, vp) + ((k_scale, v_scale) if kv_bits else ())
            heads = lambda a: P_(None, "model", *(None,) * (a.ndim - 2))

            def local(q, kp, vp, *rest):
                *sc, tb, pos, sl, wk = rest
                quant = dict(k_scale=sc[0], v_scale=sc[1],
                             kv_bits=kv_bits) if sc else {}
                return paged_attention(q, kp, vp, tb, pos, seq_slots=sl,
                                       work=wk, scale=c.attn_scale,
                                       live_pages=live_pages,
                                       window=window, interpret=interp,
                                       **quant)

            return jax.shard_map(
                local, mesh=self.topo.mesh,
                in_specs=tuple(map(heads, sharded)) + (P_(),) * 4,
                out_specs=heads(q), check_vma=False)(
                    *sharded, tables, positions, slots, work)

        def core(params, pools, tokens, slots, positions, block_tables,
                 live_pages, tally=None):
            # tally: a list that an expert share's layers leave their
            # (experts touched, pairs kept) in, for a step that counts them
            # live_pages: static python int, as _program_pages gave it: the
            # lane grid's page walk; the tiled kernel reads none
            # tokens/slots/positions: [T]; embeddings via the model's path
            # device scopes (jax.named_scope: metadata only) name the
            # step's parts in a profiler trace: embed, weights, attn with
            # paged_attention around the kernel, ffn, head
            # (docs/observability.md); _embed, _mlp and _head bring theirs
            x = model._embed(params, tokens[None, :],
                             positions=positions[None, :])[0]  # [T, d]
            angles = rope_frequencies(c.rotary_dim, c.max_seq_len,
                                      c.rope_theta, c.rope_yarn) \
                if c.position == "rope" else None
            active = slots >= 0                                   # [T]
            safe_slot = jnp.maximum(slots, 0)
            # the Pallas kernel takes the per-seq tables + slot indirection
            # directly (scalar prefetch stays O(seqs * pages), SMEM-sized);
            # only the gather fallback expands to per-token [T, max_pages]
            tables = None if use_pallas else block_tables[safe_slot]
            # the kernel's query tiles, from slots and positions: once a
            # step, for every layer's call
            # (latent attention folds 64 heads into a tile's rows: its
            # tiles are LATENT_TILE lanes whatever the bucket)
            work = work_list(slots, positions, cfg.max_seqs,
                             LATENT_TILE if self._latent else None) \
                if use_pallas else None
            if state_layers:
                from ..ops import gated_delta, mamba2

                # one schedule of runs for every recurrent layer, cut into
                # the pieces its kind's chunked form takes
                runs = gated_delta.runs_of(
                    slots, positions, cfg.max_seqs,
                    mamba2.piece_lanes(c, tokens.shape[0])
                    if c.layers_of("mamba") else gated_delta.CHUNK)

            def after_mixer(x, attn, lp):
                """Residual wiring and the feed-forward: the model's own
                (its _mlp honors relu/gelu/gelu_exact/silu_glu and the MoE
                override, top-k routed experts, uniformly)."""
                return model._after_mixer(x[None], attn[None], None, lp,
                                          None, False)[0][0]

            # the step's mixers, one a kind of layer: each takes its
            # layer's leaves of the pool by name and returns them written

            def linear_block(x, lp, own, base=None):
                assert base is None   # layer_period: never rolled
                with jax.named_scope("linear_attn"):
                    attn, state, rows = gated_delta.mix_ragged(
                        x, lp, c, own["state"], own["conv_rows"], runs,
                        self.attention_path)
                return after_mixer(x, attn, lp), \
                    {"state": state, "conv_rows": rows}

            def mamba_block(x, lp, own, base=None):
                with jax.named_scope("ssm"):
                    attn, state, rows = mamba2.mix_ragged(
                        model._mixer_input(x, lp), lp, c, own["state"],
                        own["conv_rows"], runs, base, self.attention_path)
                return after_mixer(x, attn, lp), \
                    {"state": state, "conv_rows": rows}

            recurrent = {"linear": linear_block, "mamba": mamba_block}

            def scatter_at(block_tables, sink):
                """(page, row) [T] a scatter writes lane t's row at."""
                page = block_tables[safe_slot, positions // bs]       # [T]
                row = positions % bs
                # inactive lanes — and any lane past the context window
                # (possible in the tail of a multi-step decode) — scatter
                # into the scratch sink page, never a live one
                page = jnp.where(active & (positions < cfg.max_context),
                                 page, sink)
                return page, row

            def write_pages(own, kk, vv, block_tables, sink):
                """This layer's leaves with the step's new rows in them;
                pool layout [pages, hkv, block, hd] (heads under 128
                wide side by side in rows of 128: ``kv_cache.pool_leaves``),
                kk / vv [T, hkv, hd]."""
                if use_writer:
                    k, v = write_kv_pages(own["k"], own["v"], kk, vv,
                                          block_tables, work, interpret=interp)
                    return {"k": k, "v": v}
                page, row = scatter_at(block_tables, sink)
                # kv_quant: quantize each head-vector on the way in (one
                # fp32 scale per row, ops/quantizer.quantize_kv) and
                # scatter payload + scale; reads dequantize inside the
                # paged-attention path, so fp K/V never round-trips
                # through HBM at full width
                new = {"k": kk, "v": vv}
                if kv_bits:
                    from ..ops.quantizer import quantize_kv

                    new["k"], new["k_scale"] = quantize_kv(kk, kv_bits)
                    new["v"], new["v_scale"] = quantize_kv(vv, kv_bits)
                return {f: write_kv_rows(leaf, page, row, new[f])
                        for f, leaf in own.items()}

            def block(x, lp, own, window, at):
                # ``at``: this pass's pages (the tables, their per-lane
                # form for the gather path, the sink page)
                block_tables, tables, sink = at
                with jax.named_scope("attn"):
                    # the model's own projection (norm, q / k / v, bias,
                    # QK-norm, heads, rotary), a position a lane; the
                    # barrier keeps the head split off the weights, which
                    # the products then read in place in their stacks
                    q, kk, vv = model._qkv(x, lp, angles, positions,
                                           jax.lax.optimization_barrier)
                    # write the new K/V rows into this layer's pages, in
                    # place and in the kernel's layout: page =
                    # table[pos // bs], row = pos % bs (_writes_pages says
                    # by whom; the scope is what loop_device_ms.serve reads)
                    with jax.named_scope("scatter"):
                        own = write_pages(own, kk, vv, block_tables, sink)
                    quant = dict(k_scale=own["k_scale"],
                                 v_scale=own["v_scale"],
                                 kv_bits=kv_bits) if kv_bits else {}
                    # paged attention: Pallas kernel on TPU (scalar-prefetched
                    # block tables, zero gather); jnp gather path elsewhere.
                    # (positions <= ctx-1 always, so the causal mask subsumes the
                    # context-length mask; inactive lanes produce ignored
                    # rows: zeros from the kernel's tiles, junk elsewhere)
                    with jax.named_scope("paged_attention"):
                        if use_pallas and self._tp_size > 1:
                            attn = _paged_attn_sharded(
                                q, own["k"], own["v"], block_tables,
                                positions, slots, work, live_pages, window,
                                **quant)
                        elif use_pallas:
                            attn = paged_attention(
                                q, own["k"], own["v"], block_tables,
                                positions, seq_slots=slots, work=work,
                                scale=c.attn_scale, live_pages=live_pages,
                                window=window, interpret=interp, **quant,
                                **blockwise)
                        else:
                            attn = paged_attention_reference(
                                q, own["k"], own["v"], tables, positions,
                                scale=c.attn_scale, window=window, **quant,
                                **blockwise)
                    attn = model._attn_out(attn.astype(x.dtype), lp)
                return after_mixer(x, attn, lp), own

            def latent_block(x, lp, own, at):
                """Latent attention in its absorbed form, the one form of
                every lane (a prompt chunk's too): the cache row of a token
                is its normed latent beside the one rotated key, a head's
                query its un-rotated part through ``w_uk`` beside its
                rotated part, so attention is 64 query heads over ONE
                shared row a token (scores over the whole row, the
                weighted sum over the latent), and ``w_uv`` expands each
                head's result after it. The same function as the expanded
                form (``Transformer._qkv``), without K or V a head."""
                block_tables, tables, sink = at
                r, h = c.kv_lora_rank, c.n_heads
                with jax.named_scope("attn"):
                    q_nope, q_rope, latent, k_rope = model._latent_parts(
                        x, lp, angles, positions,
                        jax.lax.optimization_barrier)   # as in block
                    pad = self._latent - r - c.qk_rope_dim
                    with jax.named_scope("scatter"):
                        row = jnp.concatenate(
                            [latent, k_rope.astype(latent.dtype),
                             jnp.zeros(latent.shape[:-1] + (pad,),
                                       latent.dtype)], axis=-1)
                        leaf = write_kv_rows(
                            own["latent"], *scatter_at(block_tables, sink),
                            row[:, None, :])
                    with jax.named_scope("absorb"):
                        q_lat = jnp.einsum(
                            "thn,chn->thc", q_nope,
                            lp["w_uk"].reshape(r, h, c.qk_nope_dim))
                        q_row = jnp.concatenate(
                            [q_lat.astype(x.dtype), q_rope.astype(x.dtype),
                             jnp.zeros(q_rope.shape[:-1] + (pad,), x.dtype)],
                            axis=-1)                          # [T, h, row]
                    with jax.named_scope("latent"):
                        if use_pallas:
                            o_lat = latent_attention(
                                q_row, leaf, block_tables, positions, slots,
                                work, scale=c.attn_scale, v_dim=r,
                                interpret=interp)
                        else:
                            o_lat = latent_attention_reference(
                                q_row, leaf, tables, positions,
                                scale=c.attn_scale, v_dim=r)
                    with jax.named_scope("absorb"):
                        attn = jnp.einsum(
                            "thc,chv->thv", o_lat.astype(x.dtype),
                            lp["w_uv"].reshape(r, h, c.v_head_dim))
                    attn = model._attn_out(attn.astype(x.dtype), lp)
                return after_mixer(x, attn, lp), {"latent": leaf}

            def stack(x, leaves, at, period=None):
                """The stack's blocks once (one period's, under a rolled
                stack), each on its layer's leaves of the pool;
                python-unrolled over depth (two kinds of layer have two
                shapes of leaves, and a scanned depth would want the pool
                stacked: KVPool's docstring). A leaf is indexed by its
                layer's place among the layers of its kind; in period
                ``period`` (traced) a layer takes its weights by that
                index and its slots ``period`` runs into its leaf (its
                pages likewise, through ``at``)."""
                leaves = {f: list(ls) for f, ls in leaves.items()}
                base = None if period is None else \
                    period * (cfg.max_seqs + 1)
                for li in range(c.layer_period):
                    with jax.named_scope("weights"):
                        kind, lp = model.layer_params(
                            params["layers"], li,
                            self._experts_in_place and period is None, period)
                    if "layer" in lp:
                        # expert stacks handed over whole: the products'
                        # form follows the step's path (no_drop_moe)
                        lp["experts_path"] = self.attention_path
                    if self._held is not None:
                        # an expert share: lanes that are not live reach
                        # no expert, and the step counts what was reached
                        lp["live"], lp["tally"] = active, tally
                    i = c.layers_of(kind).index(li)
                    own = {f: leaves[f][i] for f in kv_cache.OWNS[kind]
                           if leaves[f]}
                    if kind == "full" and self._latent:
                        x, own = latent_block(x, lp, own, at)
                    elif kind == "full":
                        x, own = block(x, lp, own, windows[li], at)
                    else:
                        x, own = recurrent[kind](x, lp, own, base)
                    for f, leaf in own.items():
                        leaves[f][i] = leaf
                return x, {f: tuple(ls) for f, ls in leaves.items()}

            leaves = pools._asdict()
            stride = cfg.n_kv_blocks + 1
            if self._periods > 1:
                # a rolled hybrid stack: ONE loop over the periods carries
                # x and the pool's leaves (40 unrolled layers of
                # granite-4.0-h-micro were 955 s of a cold warm-up's 24
                # programs and 180 s of a warm one's tracing, PERF.md
                # section 6). Period t reads and writes page p at
                # p + t * stride and slot s at s + t * (max_seqs + 1)
                def one_period(t, carry):
                    off = t * stride
                    return stack(*carry, (
                        block_tables + off,
                        None if tables is None else tables + off,
                        cfg.n_kv_blocks + off), t)

                x, leaves = jax.lax.fori_loop(0, self._periods, one_period,
                                              (x, leaves))
                return x, kv_cache.KVPool(**leaves)
            if passes == 1:
                x, leaves = stack(x, leaves,
                                  (block_tables, tables, cfg.n_kv_blocks))
                return x, kv_cache.KVPool(**leaves)

            # a looped stack: the same blocks under ONE rolled loop over
            # the passes (``passes`` unrolled bodies of n_layers blocks
            # would multiply the program and its compile time), which
            # carries x and the pool's leaves; each is written in place by
            # its row scatter, so the loop copies none
            # (tests/test_tpu_compile.py). Pass t reads and writes page p
            # at p + t * stride (kv_cache.Leaves), its own sink too: the
            # tables, the kernel's work list, the allocator's ids and the
            # prefix hashes are one pass's, as for any other model
            def one_pass(t, carry):
                x, leaves, leaving = carry
                off = t * stride
                x, leaves = stack(
                    x, leaves,
                    (block_tables + off,
                     None if tables is None else tables + off,
                     cfg.n_kv_blocks + off))
                x, leaving = model.end_pass(params, x, t, leaving)
                return x, leaves, leaving

            x, leaves, leaving = jax.lax.fori_loop(
                0, passes, one_pass, (x, leaves, model.exit_init(x)))
            return model.exit_hidden(x, leaving), kv_cache.KVPool(**leaves)

        return core

    @property
    def _core(self):
        if self._core_fn is None:
            self._core_fn = self._build_core()
        return self._core_fn

    def _build_step(self):
        self._count_call_leaves()
        core = self._core
        model = self.model
        c = model.config

        tallies = self._held is not None

        fields = self._fields

        def step(params, pools, packed, lanes, live_pages):
            tokens, slots, positions, block_tables, sel_idx = fields(
                packed, lanes)
            tally = [] if tallies else None
            x, pools = core(params, pools, tokens, slots, positions,
                            block_tables, live_pages, tally)
            # head only on each sequence's selected (last) token: the full
            # [token_budget, vocab] fp32 logits are 512 MB at T=4096 v=32k
            # and were previously fetched to host every step — select the
            # [max_seqs] rows on-device before the (remote) host transfer,
            # and make the greedy choice here too: a caller that decodes
            # greedily (return_token_ids) fetches [max_seqs] int32, and
            # the logits it never converts stay in HBM
            with jax.named_scope("head"):
                x_sel = x[sel_idx]                                 # [S, d]
                logits = model._head(params, x_sel[None, :])[0]    # [S, vocab]
                ids = sample(logits, None, 0.0, 0, 1.0)            # [S] int32
            if tallies:
                # an expert share: the held experts the step's live lanes
                # reached and the pairs kept, summed over the layers, ride
                # behind the ids ([S + 2] int32: one fetch brings both)
                ids = jnp.concatenate([ids, jnp.stack(
                    [sum(t for t, _ in tally), sum(k for _, k in tally)]
                ).astype(ids.dtype)])
            return logits, ids, pools

        if self._block > 1:
            mask_id, n_decide = c.mask_token_id, c.denoise_tokens

            def step(params, pools, packed, lanes, live_pages):
                # block diffusion: the head on each slot's block of lanes
                # ([S, B] rows), and under ``decide`` the choice of which
                # masked positions this pass decides: [S, B] ids come back,
                # not [S, B, vocab] logits
                tokens, slots, positions, block_tables, sel_rows = fields(
                    packed, lanes)
                sel_rows = sel_rows.reshape(block_tables.shape[0], -1)
                x, pools = core(params, pools, tokens, slots, positions,
                                block_tables, live_pages)
                with jax.named_scope("head"):
                    x_sel = x[sel_rows.reshape(-1)]            # [S * B, d]
                    logits = model._head(params, x_sel[None, :])[0] \
                        .reshape(sel_rows.shape + (-1,))       # [S, B, vocab]
                    with jax.named_scope("decide"):
                        ids = decide_masked(
                            logits, tokens[sel_rows] == mask_id, n_decide,
                            mask_id)
                return logits, ids, pools

        return _Step(self, jax.jit(step, donate_argnums=(1,),
                                   static_argnums=(3, 4)), 4)

    def _build_decode(self):
        """Multi-step decode entirely on device: one token per live slot
        per step (argmax, or temperature/top-k/top-p sampled), fed straight
        into the next step, KV scattered
        into pre-allocated pages. The host round trip (the dominant cost of
        one-token-at-a-time serving through a remote runtime) amortizes over
        the whole chunk. Reference analog: FastGen schedules one engine call
        per forward (inference/v2/ragged/ragged_manager.py) — on TPU the
        chunked loop is the idiomatic shape."""
        core = self._core
        model = self.model

        cfg = self.config

        def decode(params, pools, tokens0, positions0, slots, block_tables,
                   steps_xs, rng_key, live_pages, eos_id):
            # steps_xs: [k] GLOBAL decode-step ids — the per-step sample key
            # is fold_in(rng_key, global_step), so token streams do not
            # depend on the chunking of decode calls.
            # eos_id >= 0 freezes a lane ON DEVICE once it samples EOS:
            # its token is never fed, its KV scatter routes to the sink
            # page (slot -1), its position stops advancing, and it emits
            # eos fillers — post-EOS context pollution cannot happen
            # (reference ragged manager retires finished sequences
            # host-side per step; the compiled chunk does it in-loop).
            alive0 = slots >= 0
            if eos_id >= 0:
                alive0 = jnp.logical_and(alive0, tokens0 != eos_id)

            def one(carry, step_i):
                pools, toks, pos, alive = carry
                slots_eff = jnp.where(alive, slots, -1)
                x, pools = core(params, pools, toks, slots_eff, pos,
                                block_tables, live_pages)
                logits = model._head(params, x[None, :])[0]    # [S, vocab]
                nxt = sample(logits, jax.random.fold_in(rng_key, step_i),
                              cfg.temperature, cfg.top_k, cfg.top_p)
                if eos_id >= 0:
                    nxt = jnp.where(alive, nxt, eos_id)
                    new_alive = jnp.logical_and(alive, nxt != eos_id)
                else:
                    new_alive = alive
                pos = pos + alive.astype(pos.dtype)
                return (pools, nxt, pos, new_alive), nxt

            (pools, _, _, _), gen = jax.lax.scan(
                one, (pools, tokens0, positions0, alive0), steps_xs)
            return gen.T, pools                                 # [S, k]

        return _Program(self, jax.jit(decode, donate_argnums=(1,),
                                      static_argnums=(8, 9)), 8)
