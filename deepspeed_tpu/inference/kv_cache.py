"""The serving cache, and its one owner: what a layer keeps on the device
between ticks (K/V pages, their quantization scales, a recurrent state and
convolution rows a slot), how many bytes that is, how pages move in and
out, what such a cache refuses, and the host's books of pages and slots.

* :class:`KVPool` names the device leaves; :func:`pool_leaves` is their one
  description (shape, dtype, sharding a kind) from which :func:`new_pool`
  builds them and :func:`kv_page_bytes` / :func:`state_pool_bytes` count
  them. A new kind of layer cache is a field here and an entry there.
* :class:`PageMoves` (``gather``, ``write``, ``copy``) moves pages between
  the pool and the host; the engine's export / import / adopt keep the
  bookkeeping and call it.
* A looped stack (``total_ut_steps`` passes over one set of layers) keeps
  K/V for every pass: :func:`cache_passes` is how many cache layers a
  weight layer has, and a page id then names one page a pass in every leaf
  (:class:`Leaves`' ``passes``; :class:`PageMoves` moves them together).
  The books below never learn of it.
* :class:`KVLedger` holds the allocator, the prefix cache and the slots, and
  answers the capacity questions the scheduler asks.
* :func:`refuse_without_snapshot` is what a cache with recurrent state
  refuses.

Reference: ragged/blocked_allocator.py, ragged_manager.py, kv_cache.py of
deepspeed/inference/v2."""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


# ----------------------------------------------------------------------
# the host's books (reference: ragged/blocked_allocator.py, ragged_manager.py)

class PoolExhausted(RuntimeError):
    """The KV page pool cannot satisfy a schedule's block demand.
    A dedicated type so recovery code (the serving driver preempts a
    decode and retries) can distinguish this RECOVERABLE condition from
    arbitrary device RuntimeErrors — substring-matching the message
    would misfire on e.g. XLA's 'Resource exhausted' device OOM."""

class BlockedAllocator:
    """Refcounted free-list allocator over ``n_blocks`` KV pages
    (reference blocked_allocator.py — same capability, python list instead
    of a torch tensor free-list; refcounts added for prefix-cache block
    sharing: a page returns to the free list only when every holder —
    sequences and the cache — has released it)."""

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks))
        self._ref: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PoolExhausted(
                f"KV pool exhausted: need {n}, have {len(self._free)}")
        out, self._free = self._free[:n], self._free[n:]  # dslint: disable=races -- the cache's books belong to the engine's ticking thread: the serving layer mutates them only inside a tick, under ServingEngine's lock; the fleet / region monitors read occupancy() and demand() lock-free as gauges (len() and dict reads are atomic, and a stale value misreports one poll)
        for b in out:
            self._ref[b] = 1  # dslint: disable=races -- as _free above: tick-confined writes, lock-free gauge reads
        return out

    def retain(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self._ref[int(b)] += 1

    def release(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            b = int(b)
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)

    def refcount(self, block: int) -> int:
        return self._ref.get(int(block), 0)

    # historical name used throughout the engine/tests: a release, not an
    # unconditional free — shared pages survive until the last holder
    free = release


class PrefixCache:
    """LRU cache of computed KV pages keyed by full-block token prefixes.

    Beyond-reference capability (FastGen recomputes every prompt; vLLM
    calls this automatic prefix caching): when a sequence is flushed, its
    full KV blocks are published under the token prefix they encode; a
    new prompt sharing that prefix adopts the pages (refcounted via
    :class:`BlockedAllocator`) and skips their prefill. Correctness rests
    on immutability of shared pages: sharing covers FULL blocks only and
    is capped at ``len(prompt) - 1`` tokens, so the engine's scatters only
    ever write positions at-or-after the shared region's end — except the
    benign case of re-writing the final shared position with bit-identical
    K/V (same tokens, same absolute positions, same params)."""

    def __init__(self, block_size: int, on_evict=None):
        import collections

        self.block_size = block_size
        # prefix tuple -> list of block ids (cache holds one retain each);
        # ordered oldest-used first: O(1) LRU via move_to_end/popitem
        self._entries: "collections.OrderedDict[Tuple[int, ...], List[int]]" \
            = collections.OrderedDict()
        # per-block count of CACHE references (across nested entries) —
        # lets reclaimable_blocks() tell cache-only pages from shared ones
        self._block_refs: Dict[int, int] = {}
        self.hits = 0
        self.misses = 0
        # optional eviction hook ``(key_tuple, blocks) -> None`` fired
        # BEFORE the evicted entry's refs release (its pages are still
        # valid to read) — the global KV tier's directory-invalidate +
        # cold-spill seam. None (the default) changes nothing.
        self.on_evict = on_evict

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, tokens: Sequence[int]) -> bool:
        return tuple(tokens) in self._entries

    def keys(self) -> List[Tuple[int, ...]]:
        """The published prefixes, least recently used first."""
        return list(self._entries)

    @staticmethod
    def _key(tokens: Sequence[int]) -> Tuple[int, ...]:
        """A token stream in the keys' form, made ONCE a call: a level's key
        is then a slice of it (a copy of references) and not a fresh walk
        over the tokens. Building every level's tuple from the list cost an
        admission of a 4k-15k-token prompt 6 ms at the median and 0.6 s
        where nothing matched (PERF.md section 6, PR 49)."""
        return tokens if isinstance(tokens, tuple) else tuple(map(int, tokens))

    def match(self, prompt: Sequence[int]) -> Tuple[int, List[int]]:
        """Longest cached full-block prefix of ``prompt``, capped so at
        least one prompt token remains to prefill (its logits seed
        generation). Returns (shared_token_count, blocks) — blocks are NOT
        yet retained for the caller."""
        bs = self.block_size
        whole = self._key(prompt)
        for k in range((len(whole) - 1) // bs, 0, -1):
            key = whole[: k * bs]
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return k * bs, ent
        self.misses += 1
        return 0, []

    def lookup(self, tokens: Sequence[int]) -> Tuple[Optional[Tuple[int, ...]],
                                                     List[int]]:
        """Longest full-block prefix ENTRY covering ``tokens`` — unlike
        :meth:`match` there is no leave-one-token-to-prefill cap, because
        adoption/export wants whole cache entries (the requester's
        routing key is already a full-block prefix). Refreshes LRU
        recency (a donor should not evict what it is donating) but does
        not count hits/misses. Returns (key, blocks) or (None, [])."""
        bs = self.block_size
        whole = self._key(tokens)
        for k in range(len(whole) // bs, 0, -1):
            key = whole[: k * bs]
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                return key, ent
        return None, []

    def _hold(self, key, blocks, allocator: BlockedAllocator) -> None:
        allocator.retain(blocks)
        for b in blocks:
            self._block_refs[b] = self._block_refs.get(b, 0) + 1  # dslint: disable=races -- as BlockedAllocator._free: tick-confined writes, lock-free gauge reads
        self._entries[key] = blocks

    def publish(self, tokens: Sequence[int], blocks: Sequence[int], seen: int,
                allocator: BlockedAllocator) -> None:
        """Offer a flushed sequence's full blocks to the cache (the cache
        retains them; the sequence's own refs are released separately)."""
        bs = self.block_size
        k = min(seen, len(tokens)) // bs
        if k <= 0:
            return
        key = self._key(tokens[: k * bs])
        if key in self._entries:
            self._entries.move_to_end(key)
            return
        held = [int(b) for b in blocks[:k]]
        self._hold(key, held, allocator)
        # keys are exact tuples, so a shorter shared prefix needs its own
        # entry — publish every nested full-block level too (same pages,
        # one retain per level)
        for kk in range(k - 1, 0, -1):
            kkey = key[: kk * bs]
            if kkey in self._entries:
                break
            self._hold(kkey, held[:kk], allocator)

    def _evict_one(self, allocator: BlockedAllocator) -> None:
        key, blocks = self._entries.popitem(last=False)   # LRU
        if self.on_evict is not None:
            # hook runs while the entry's pages are still referenced:
            # the cold-spill copy must read them before they can return
            # to the free list and be overwritten
            self.on_evict(key, blocks)
        allocator.release(blocks)
        for b in blocks:
            self._block_refs[b] -= 1
            if self._block_refs[b] == 0:
                del self._block_refs[b]

    def evict_for(self, allocator: BlockedAllocator, need: int) -> None:
        """LRU-evict entries until ``need`` blocks are free (or empty)."""
        while allocator.free_blocks < need and self._entries:
            self._evict_one(allocator)

    def reclaimable_blocks(self, allocator: BlockedAllocator) -> int:
        """Distinct pages that would return to the free list if the whole
        cache dropped: pages whose every reference is the cache's own.
        Admission checks (can_schedule/query) count these as available —
        without this, a cache that has absorbed the pool starves admission
        forever while KVLedger.make_room could evict its way out."""
        return sum(1 for b, n in self._block_refs.items()
                   if allocator.refcount(b) == n)

    def drop_all(self, allocator: BlockedAllocator) -> None:
        while self._entries:
            self._evict_one(allocator)


def block_balance_report(engine) -> Dict[str, Any]:
    """Audit the engine's KV-page accounting: every page must be exactly
    one of free / sequence-held / cache-held, and the allocator's
    refcount for each held page must equal the number of holders
    (sequence occurrences + prefix-cache entry references).

    Returns ``{"free": int, "held": int, "problems": [str, ...]}`` —
    ``problems`` empty means zero leaks and exact refcount balance. The
    serving drain check and the cancellation tests assert on this; it is
    pure host-side dict walking (never touches the device)."""
    alloc = engine.allocator
    free = set(alloc._free)
    held = set(alloc._ref)
    problems: List[str] = []
    if len(free) != len(alloc._free):
        problems.append("duplicate pages in the free list")
    overlap = free & held
    if overlap:
        problems.append(f"pages both free and referenced: "
                        f"{sorted(overlap)[:8]}")
    vanished = set(range(alloc.n_blocks)) - free - held
    if vanished:
        problems.append(f"pages leaked (not free, not referenced): "
                        f"{sorted(vanished)[:8]}")
    expected: Dict[int, int] = {}
    for seq in engine.seqs.values():
        for b in seq.blocks:
            expected[int(b)] = expected.get(int(b), 0) + 1
    if engine.prefix_cache is not None:
        for b, n in engine.prefix_cache._block_refs.items():
            expected[int(b)] = expected.get(int(b), 0) + n
    for b in sorted(held | set(expected)):
        have, want = alloc._ref.get(b, 0), expected.get(b, 0)
        if have != want:
            problems.append(f"page {b}: allocator refcount {have} != "
                            f"{want} holders")
    return {"free": len(free), "held": len(held), "problems": problems}


def assert_block_balance(engine, expect_free: Optional[int] = None) -> None:
    """Raise AssertionError on any block-accounting imbalance (and, when
    given, on ``free != expect_free``)."""
    rep = block_balance_report(engine)
    if rep["problems"]:
        raise AssertionError("KV block balance violated: "
                             + "; ".join(rep["problems"]))
    if expect_free is not None and rep["free"] != expect_free:
        raise AssertionError(
            f"KV free-page count {rep['free']} != expected {expect_free} "
            f"({rep['held']} pages still referenced)")


class KVLedger:
    """The host's books of one engine's cache: the page allocator, the
    prefix cache (None when off) and the free slots, built from any config
    with ``n_kv_blocks``, ``kv_block_size``, ``max_seqs`` and
    ``enable_prefix_cache``. Whether there is room is asked here: a slot
    is the unit of the recurrent-state pool as a page is of K/V."""

    def __init__(self, config):
        self.allocator = BlockedAllocator(config.n_kv_blocks)
        self.prefix_cache = (PrefixCache(config.kv_block_size)
                             if config.enable_prefix_cache else None)
        self._slots: List[int] = list(range(config.max_seqs))

    @property
    def free_slots(self) -> int:
        return len(self._slots)

    def take_slot(self) -> int:
        if not self._slots:
            raise RuntimeError("no free sequence slots; flush() first")
        return self._slots.pop()

    def give_slot(self, slot: int) -> None:
        self._slots.append(slot)  # dslint: disable=races -- as BlockedAllocator._free: tick-confined writes; free_slots is read on the ticking thread

    def available_blocks(self) -> int:
        """Free pages plus cache-only-held pages (:meth:`make_room` evicts
        those on demand, so admission must count them or it starves once
        the prefix cache has absorbed the pool)."""
        free = self.allocator.free_blocks
        if self.prefix_cache is not None:
            free += self.prefix_cache.reclaimable_blocks(self.allocator)
        return free

    def occupancy(self) -> float:
        """Fraction of the page pool currently held by live sequences or
        the prefix cache (1.0 = exhausted)."""
        return 1.0 - self.allocator.free_blocks / self.allocator.n_blocks

    def demand(self) -> float:
        """Fraction of the pool that live DEMAND holds: pages the cache
        could reclaim on allocation pressure don't count. This is the
        capacity-planning signal (a warm LRU cache legitimately absorbs
        the whole pool at idle — raw :meth:`occupancy` would read that as
        permanent pressure and an autoscaler could never scale down)."""
        return 1.0 - self.available_blocks() / self.allocator.n_blocks

    def evictable_blocks(self, blocks: Sequence[int]) -> int:
        """Pages that actually become schedulable if the sequence holding
        ``blocks`` is evicted: those whose every non-cache reference is
        this sequence's own (they end up free, or cache-only-held — which
        admission reclaims on demand). Pages shared with another live
        sequence stay held and must not be credited, or preemption evicts
        decodes without making the candidate fit."""
        cache = self.prefix_cache
        cache_refs = cache._block_refs if cache is not None else {}
        counts: Dict[int, int] = {}
        for b in blocks:
            counts[int(b)] = counts.get(int(b), 0) + 1
        return sum(1 for b, n in counts.items()
                   if self.allocator.refcount(b) <= n + cache_refs.get(b, 0))

    def make_room(self, need: int) -> None:
        """Have ``need`` pages free, evicting LRU prefixes if it takes
        that (cache-held pages are reclaimable), or raise PoolExhausted
        with nothing granted: the check half of validate-then-allocate."""
        if need > self.allocator.free_blocks and self.prefix_cache is not None:
            self.prefix_cache.evict_for(self.allocator, need)
        if need > self.allocator.free_blocks:
            raise PoolExhausted(
                f"KV pool exhausted: need {need} blocks, have "
                f"{self.allocator.free_blocks}; flush() finished "
                "sequences first")


# ----------------------------------------------------------------------
# the device leaves

KV_BITS = {"none": 0, "int8": 8, "int4": 4}

#: the pool's fields keyed by page; ``state`` and ``conv_rows`` are keyed
#: by slot
PAGED = ("k", "v", "k_scale", "v_scale", "latent")
#: those of them a hand-off carries (``KVExport``, ``PageMoves.gather`` /
#: ``write``): K/V a head and their scales; latent pages are shared and
#: copied (the prefix cache, copy-on-write) and not exported yet
KV_FIELDS = PAGED[:4]
#: the fields a kind of layer owns a leaf of (those its model has: an
#: attention layer K/V pages a head and, quantized, their scales, or under
#: latent attention the one ``latent`` leaf and none of the others)
OWNS = {"full": PAGED, "linear": ("state", "conv_rows"),
        "mamba": ("state", "conv_rows")}

#: what a model with recurrent layers refuses, and why, in one place
_NO_SNAPSHOT = (
    "{what} needs a snapshot of the recurrent state: a recurrent layer's state "
    "cannot be rewound to, or rebuilt from, a token position the way KV "
    "pages can, and the engine keeps no state snapshot yet")


class KVPool(NamedTuple):
    """What the engine keeps on the device between ticks, by name: each
    field a tuple of per-layer leaves, empty where the model has none.

    ``k`` / ``v``: one ``[n_blocks + 1, hkv, block, hd]`` leaf a layer that
    holds pages (heads under 128 wide share a row of 128 lanes where they
    fill whole rows, ``[.., hkv / pack, block, pack * hd]``:
    :func:`pool_leaves`; last page = scratch sink for masked-out batch lanes where
    a scatter writes the rows; duplicate scatters with mixed old/new
    values are undefined — inactive lanes must never alias a live page).
    (block, hd) stay minor-most so each page is a native VMEM tile for the
    Pallas kernel, which pins this row-major layout; every write into a
    leaf must keep it, or XLA:TPU transposes the whole leaf and back, every
    tick. Who writes a step's new rows: on the TPU one Pallas call a layer,
    K and V together, over the step's live query tiles
    (ops/pallas/paged_attention.write_kv_pages: a tile's page slabs
    [hkv, block, hd] come into VMEM, take the rows and go back; a lane
    that is not live costs nothing and the sink is not written); off the
    TPU, under tensor parallelism and for a quantized pool a scatter a
    leaf with the head an index (write_kv_rows). One array PER LAYER: earlier rounds
    measured pool-sized copies under a stacked [L, pages, ...] tensor
    (100 ms a decode step) and a flat [L*(P+1), ...] one (16-18 GB compile
    OOM) and blamed the shapes, but the per-layer leaves were copied too,
    by the row scatter's (hkv, hd) window (PR 26). Stacked and flat were
    not tried again; per-layer leaves keep any transient to one leaf.
    A looped stack's leaf holds one such run of pages a pass, end to end
    (:class:`Leaves`), and the step's loop over the passes carries the
    leaves and copies none (PR 35; ``tests/test_tpu_compile.py``).

    ``k_scale`` / ``v_scale`` (kv_quant): pages are stored as blockwise
    payload + per-row fp32 scales (scale block = one K/V head-vector): int8
    payload [.., hd] or int4 nibble-packed uint8 [.., hd//2], scale leaf
    [P+1, hkv, bs]. The sink page's zeros dequantize to zeros, so
    masked-lane scatters stay harmless exactly as in the fp layout.

    ``latent`` (latent attention, ``TransformerConfig.kv_lora_rank``): the
    third kind, one ``[n_blocks + 1, 1, block, latent_row]`` leaf a layer
    and no ``k`` / ``v``: a token's row is its normed latent beside the one
    rotated key all heads share, 512 + 64 values at A.X-K1's sizes, padded
    with zeros to whole lanes of 128 (640: 1,280 B in bfloat16, where K and
    V a head would be 64 x (192 + 128) x 2 = 40,960 B). The pad is what a
    [.., 576] leaf takes in the chip's tiled layout anyway, and it makes a
    page slab [block, 640] a copy the paged kernel can issue. Keyed by page
    like ``k`` / ``v``, so the allocator, the prefix cache and the page
    moves carry it unchanged. The step's rows reach it by
    ``write_kv_rows``' scatter, one index a lane (the leaf has one "head").

    ``state`` / ``conv_rows``: the second kind of cache, for each recurrent
    layer a float32 state leaf [max_seqs + 1, ...] (the gated delta rule's
    [H, dk, dv], Mamba-2's [H, P, N]: :func:`state_shapes`) and the
    convolution's last inputs [max_seqs + 1, K - 1, channels], keyed by
    SLOT (the last one the sink of lanes that are not live, as the scratch
    page is for KV). Nothing zeroes a slot: the step starts a run at
    position 0 from zeros (a fresh admission, a resume after preempt and a
    reused slot all re-prefill from position 0), carries the state over
    ticks when a prompt is split, and never rewinds."""

    k: Tuple = ()
    v: Tuple = ()
    k_scale: Tuple = ()
    v_scale: Tuple = ()
    latent: Tuple = ()
    state: Tuple = ()
    conv_rows: Tuple = ()


def paged_leaf(pool: "KVPool"):
    """A leaf keyed by page, for what every such leaf agrees on: axis 0,
    pages and sink, a run a pass."""
    return (pool.k or pool.latent)[0]


class Leaves(NamedTuple):
    """One field of the pool: ``n`` leaves (one a layer that has the kind)
    of ``shape``, whose axis 0 is pages or slots plus the sink, ``dtype``
    and, under a model axis, ``spec`` (K/V shard by head). Under a looped
    stack axis 0 holds ``passes`` such runs end to end: pass ``t`` reads
    and writes page ``p`` at ``p + t * (shape[0] // passes)``, its own sink
    at the end of its run, so one page id is ``passes`` physical pages a
    leaf. A rolled stack's periods (:func:`cache_periods`) are laid out
    the same way, pages and slots alike: a leaf then serves the layer at
    its place in every period."""

    n: int
    shape: Tuple[int, ...]
    dtype: Any
    spec: PartitionSpec = PartitionSpec()
    passes: int = 1

    @property
    def unit_bytes(self) -> int:
        """Bytes one page or slot takes across the ``n`` leaves."""
        return (self.n * self.passes * int(np.prod(self.shape[1:]))
                * jnp.dtype(self.dtype).itemsize)


def _layers_of(model_config, kind: str) -> Tuple[int, ...]:
    """Indices of the layers that hold KV pages ("full") or a recurrent
    state ("linear", "mamba"). The sizing functions take any object with
    the KV geometry (n_layers, n_kv_heads, head_dim): one without
    ``layers_of`` is all full."""
    if hasattr(model_config, "layers_of"):
        return model_config.layers_of(kind)
    return tuple(range(model_config.n_layers)) if kind == "full" else ()


def _leaves_of(model_config, layers: Tuple[int, ...]) -> int:
    """Leaves the pool keeps for ``layers``: one a layer, or under a rolled
    stack one for each of them in a period."""
    return len(layers) // cache_periods(model_config)


def state_layers(model_config) -> Tuple[int, ...]:
    """Indices of the layers that hold a recurrent state, whatever their
    kind (``TransformerConfig.state_layers``; none for an object that only
    has the KV geometry)."""
    return tuple(getattr(model_config, "state_layers", ()))


def cache_periods(model_config) -> int:
    """Periods of a rolled hybrid stack (``TransformerConfig.layer_period``:
    mamba layers whose ``layer_types`` repeat): the cache keeps
    one leaf for each layer of ONE period and lays the periods' runs of
    pages or slots end to end in it, as a looped stack's passes; 1 for any
    other model."""
    period = getattr(model_config, "layer_period", model_config.n_layers)
    return model_config.n_layers // period


def state_shapes(model_config) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Per sequence and recurrent layer: (the float32 state's shape, the
    convolution rows' [K - 1, channels]), from the layer's kind."""
    if _layers_of(model_config, "mamba"):
        from ..ops import mamba2 as mixer
    else:
        from ..ops import gated_delta as mixer
    return mixer.state_shapes(model_config)


def cache_passes(model_config) -> int:
    """Cache layers a weight layer has: the passes of a looped stack, each
    with K/V of its own; 1 for any other model."""
    return int(getattr(model_config, "total_ut_steps", 1))


def cache_runs(model_config) -> int:
    """Runs of pages (or slots) end to end in a leaf's axis 0: a looped
    stack's passes, or a rolled stack's periods."""
    return cache_passes(model_config) * cache_periods(model_config)


def cache_layers(model_config) -> int:
    """Layers of K/V the cache holds for a token: what an export's
    ``n_layers`` counts."""
    return len(_layers_of(model_config, "full")) * cache_passes(model_config)


def pool_leaves(model_config, ragged_config, model_parallel: int = 1
                ) -> KVPool:
    """The pool's description, one :class:`Leaves` a field: the one place
    a leaf's shape, dtype and sharding are written. :func:`new_pool`
    allocates from it and the byte arithmetic counts from it.

    A K/V payload leaf's rows are whole lanes of 128 wherever the head
    geometry allows: heads under 128 wide that divide it lie
    ``paged_attention.heads_a_row`` side by side, ``[pages, hkv / pack,
    block, pack * head_dim]`` (eight heads of 64: four rows of 128), the
    same bytes a page and the shape class of a 128-wide head's leaf, which
    the paged kernel's tiled grid and the row writer copy slabs of and the
    compiler keeps in place at the step's boundary (a ``[.., 8, 16, 64]``
    leaf it copied in and out, every tick: PERF.md, PR 50). The heads of
    one device (``model_parallel``: the model axis the leaf shards by
    head) have to fill whole rows; a quantized pool keeps a head a row,
    beside its scale rows."""
    from ..ops.pallas.paged_attention import heads_a_row

    c, cfg = model_config, ragged_config
    bits = KV_BITS[cfg.kv_quant]
    full = _leaves_of(c, _layers_of(c, "full"))
    recurrent = _leaves_of(c, state_layers(c))
    periods = cache_periods(c)
    passes = cache_passes(c) * periods
    rows = (passes * (cfg.n_kv_blocks + 1), c.n_kv_heads, cfg.kv_block_size)
    # latent attention: the attention layers' pages are rows of the latent
    # leaf, and there is no K / V a head
    row = int(getattr(c, "latent_row", 0))
    pack = 1 if bits else heads_a_row(c.n_kv_heads // model_parallel,
                                      c.head_dim)
    payload = Leaves(
        0 if row else full,
        (rows[0], c.n_kv_heads // pack, rows[2],
         c.head_dim // 2 if bits == 4 else pack * c.head_dim),
        {0: cfg.dtype, 8: jnp.int8, 4: jnp.uint8}[bits],
        PartitionSpec(None, "model", None, None), passes)
    scale = Leaves(full if bits else 0, rows, jnp.float32,
                   PartitionSpec(None, "model", None), passes)
    state, conv = state_shapes(c) if recurrent else ((), ())
    slots = (periods * (cfg.max_seqs + 1),)
    return KVPool(k=payload, v=payload, k_scale=scale, v_scale=scale,
                  latent=Leaves(full if row else 0,
                                (rows[0], 1, rows[2], row), cfg.dtype,
                                passes=passes),
                  state=Leaves(recurrent, slots + state, jnp.float32,
                               passes=periods),
                  conv_rows=Leaves(recurrent, slots + conv, cfg.dtype,
                                   passes=periods))


def new_pool(model_config, ragged_config, topology=None) -> KVPool:
    """The zeroed pool of :func:`pool_leaves`; under a model axis K/V and
    their scales are placed sharded by head."""
    tp = topology.model_parallel_size if topology is not None else 1

    def zeros(kind: Leaves):
        def one():
            leaf = jnp.zeros(kind.shape, kind.dtype)
            return leaf if tp == 1 else jax.device_put(
                leaf, NamedSharding(topology.mesh, kind.spec))

        return tuple(one() for _ in range(kind.n))

    return KVPool(*map(zeros, pool_leaves(model_config, ragged_config, tp)))


def kv_page_bytes(model_config, ragged_config) -> int:
    """Bytes ONE KV page (K + V, all layers that hold pages) occupies in the pool under
    ``ragged_config.kv_quant`` — payload plus per-row fp32 scales. The
    capacity arithmetic behind "quantization roughly doubles concurrent
    sequences per pool": size two pools to the same byte budget with
    :func:`kv_blocks_for_bytes` and the int8 pool holds ~2x the pages."""
    kinds = pool_leaves(model_config, ragged_config)
    return sum(getattr(kinds, f).unit_bytes for f in PAGED)


def state_pool_bytes(model_config, ragged_config) -> int:
    """Bytes of the recurrent-state pool: for every recurrent layer and
    every slot (and the sink), the float32 state and the convolution's
    rows. A fixed cost of ``max_seqs``, whatever the contexts' lengths."""
    return (ragged_config.max_seqs + 1) * state_slot_bytes(model_config,
                                                           ragged_config)


def state_slot_bytes(model_config, ragged_config) -> int:
    """Bytes one sequence's slot takes across the recurrent layers: what a
    live sequence costs beside its pages."""
    kinds = pool_leaves(model_config, ragged_config)
    return kinds.state.unit_bytes + kinds.conv_rows.unit_bytes


def kv_blocks_for_bytes(budget_bytes: int, model_config,
                        ragged_config) -> int:
    """Pages a ``budget_bytes`` KV pool holds under the config's
    ``kv_quant`` mode (the fixed-byte-budget sizing the serve bench's
    kv-quant leg and capacity tests use). The recurrent-state pool, a
    fixed cost, comes out of the budget first."""
    left = int(budget_bytes) - state_pool_bytes(model_config, ragged_config)
    return max(1, left // max(1, kv_page_bytes(model_config, ragged_config)))


def refuse_latent(model_config, what: str) -> None:
    """What a cache of latent rows refuses until someone needs it: a
    quantized pool (the row's two parts want scales of their own), a pool
    sharded over the model axis (the row has no head to shard by: every
    device would hold all of it), and the hand-offs that name K and V
    pages (``KVExport``)."""
    if getattr(model_config, "latent_row", 0):
        raise NotImplementedError(
            f"{what} is not supported over a latent (MLA) page pool: its "
            "pages hold one row a token for all heads, with no K / V leaf "
            "a head to quantize, shard by head or export")


def refuse_without_snapshot(model_config, what: str) -> None:
    """What a cache with recurrent state refuses: anything that rewinds a
    sequence to, or rebuilds it at, a token position (prefix adoption,
    trim, speculative verification, KV export / import, the KV tier).
    Loud, as ALiBi fails at construction."""
    if state_layers(model_config):
        raise NotImplementedError(_NO_SNAPSHOT.format(what=what))


# ----------------------------------------------------------------------
# page moves (the half of export / import / copy-on-write that knows the
# format; descriptors, refcounts and telemetry stay with the engine)

@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(pool: KVPool, dst, pages) -> KVPool:
    """``dst`` [passes, B] physical pages; ``pages`` [passes * layers, B,
    ...] a field, as :meth:`PageMoves.gather` lays them."""
    def put(leaves, new):
        new = new.reshape((dst.shape[0], len(leaves)) + new.shape[1:])
        return tuple(leaf.at[dst].set(new[:, i].astype(leaf.dtype))
                     for i, leaf in enumerate(leaves))

    return pool._replace(**{f: put(getattr(pool, f), new)
                            for f, new in zip(KV_FIELDS, pages)
                            if new is not None})


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool: KVPool, src, dst) -> KVPool:
    return pool._replace(**{
        f: tuple(p.at[dst].set(p[src]) for p in getattr(pool, f))
        for f in PAGED})


class PageMoves:
    """Moves whole pages of one model's pool by page id. It holds what a
    page id means physically (:class:`Leaves`' ``passes``: one page a pass
    of a looped stack, each pass's run of pages end to end in a leaf's
    axis 0), so the engine that calls it names pages as the allocator
    does and nothing else."""

    def __init__(self, model_config):
        self.passes = cache_runs(model_config)
        # K / V pages travel a head a row, [.., hkv, block, hd], however
        # this pool lays its heads out (:func:`pool_leaves`): two engines
        # of one model exchange them whatever their model axes
        self.head_dim = model_config.head_dim

    def _physical(self, pool: KVPool, ids) -> np.ndarray:
        """[passes, len(ids)]: each pass's page of every id."""
        run = paged_leaf(pool).shape[0] // self.passes
        starts = np.arange(self.passes, dtype=np.int32) * run
        return np.asarray(ids, np.int32)[None, :] + starts[:, None]

    def gather(self, pool: KVPool, blocks: Sequence[int]) -> Tuple:
        """Host copies of pages ``blocks``, one ``[cache layers,
        len(blocks), ...]`` array a :data:`KV_FIELDS` field (None where the
        pool has no such leaves): one device gather per layer leaf, then
        the transfer. The quantized payload and its scales travel exactly
        as pooled; K / V pages whose heads share rows of the pool travel a
        head a row (``[.., hkv, block, hd]``, what :class:`KVExport`
        documents). Under a looped stack a page id brings every pass's
        page: cache layer ``t * layers + l`` is pass ``t`` of layer
        ``l``."""
        from ..ops.pallas.paged_attention import unpack_heads

        idx = jnp.asarray(self._physical(pool, blocks))

        def field(f, leaves):
            got = np.stack([np.asarray(leaf[idx]) for leaf in leaves], 1)
            got = got.reshape((-1,) + got.shape[2:])  # [passes * layers, ..]
            return unpack_heads(got, self.head_dim) if f in ("k", "v") else got

        return tuple(field(f, getattr(pool, f)) if getattr(pool, f) else None
                     for f in KV_FIELDS)

    def write(self, pool: KVPool, blocks: Sequence[int], pages: Tuple,
              max_pages: int) -> KVPool:
        """Scatter ``pages`` (:meth:`gather`'s tuple) into pages ``blocks``
        of every layer's leaves: one jitted donated program over the named
        leaves present, so the quantized payload AND its scale pages land
        together — bit-identical pool state, never a requantization. The
        page count is pow2-bucketed (one compiled writer per bucket, not
        one per hand-off length); padding lanes scatter zeros into the
        sink page (each pass's own), which is never read."""
        need = len(blocks)
        B = 1
        while B < need:
            B *= 2
        B = min(B, max_pages)
        dst = np.full((B,), paged_leaf(pool).shape[0] // self.passes - 1,
                      np.int32)
        dst[:need] = blocks

        from ..ops.pallas.paged_attention import share_rows

        def padded(f, a):
            if a is None:
                return a
            if f in ("k", "v"):  # a head a row on the wire: as pooled here
                a = share_rows(a, getattr(pool, f)[0].shape[1])
            if B == need:
                return a
            pad = np.zeros((a.shape[0], B - need) + a.shape[2:], a.dtype)
            return np.concatenate([a, pad], axis=1)

        return _scatter_pages(pool, jnp.asarray(self._physical(pool, dst)),
                              tuple(map(padded, KV_FIELDS, pages)))

    def copy(self, pool: KVPool, src: int, dst: int) -> KVPool:
        """Device-side copy of page ``src`` onto ``dst`` across every
        layer's leaves, every pass's page of it (one jitted donated
        program; trim's copy-on-write)."""
        return _copy_page(pool, jnp.asarray(self._physical(pool, [src])[:, 0]),
                          jnp.asarray(self._physical(pool, [dst])[:, 0]))


@dataclass
class KVExport:
    """Host-side snapshot of one sequence's KV state, the unit of the
    disaggregated prefill→decode hand-off (``export_kv``/``import_kv``).
    Today the pages travel as numpy arrays (CPU copy); the dataclass is
    the explicit seam where an ICI transfer replaces the host hop later —
    importers validate geometry, never provenance."""

    uid: int
    tokens: List[int]          # fed context (prompt + any decoded tokens)
    seen: int                  # tokens whose KV the pages actually hold
    prompt_len: int
    kv_block_size: int
    n_layers: int
    n_kv_heads: int
    head_dim: int
    dtype: str
    k_pages: np.ndarray        # [n_layers, n_pages, hkv, block, hd]; n_layers
    #                            counts cache layers (kv_cache.cache_layers)
    v_pages: np.ndarray
    # quantized hand-off (kv_quant != "none"): k/v_pages hold the POOL's
    # quantized payload (int8, or int4 nibble-packed uint8 [.., hd//2])
    # and the per-row fp32 scales ride along — the wire moves ~half
    # (int8) / ~quarter (int4) the fp bytes, and the importer adopts the
    # payload bit-identically (no re-quantization, no extra error)
    kv_quant: str = "none"
    k_scales: Optional[np.ndarray] = None   # [n_layers, n_pages, hkv, block]
    v_scales: Optional[np.ndarray] = None

    @property
    def n_pages(self) -> int:
        return int(self.k_pages.shape[1])

    @property
    def nbytes(self) -> int:
        n = int(self.k_pages.nbytes + self.v_pages.nbytes)
        if self.k_scales is not None:
            n += int(self.k_scales.nbytes + self.v_scales.nbytes)
        return n

