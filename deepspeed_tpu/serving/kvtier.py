"""Global KV tier: the region-scoped prefix-reuse plane.

At scale the hot KV working set (system prompts, few-shot preambles,
multi-turn histories) is massively shared, yet each replica's
:class:`~deepspeed_tpu.inference.kv_cache.PrefixCache` is private. This
module promotes prefix residency to a fleet/region resource with three
cooperating pieces (docs/serving.md "Global KV tier"):

* :class:`PrefixDirectory` — a bounded-staleness map of *full-block
  prefix hash -> holders*. Replicas publish their residency set on the
  existing digest/health poll cadence (one locked swap per replica per
  publish — per-tick work independent of replica count), and entries
  are invalidated synchronously on eviction and dropped wholesale on
  replica death/migration, so a directory entry never outlives its
  pages. The directory is advisory: routing treats it as a hint with a
  freshness bound and falls back to the affinity ring when it lies.
* :class:`PrefixExport` — the wire form of an adopted prefix: quantized
  pages + scales (the PR-14 KV wire format) plus geometry and a
  checksum, so adoption-wire corruption is *detected* at the importer
  and degrades to local re-prefill instead of landing poisoned pages.
* :class:`ColdTier` — a host-memory LRU of evicted prefixes, capacity-
  accounted in KV pages. Entries are immutable host copies holding NO
  device-pool references (spill copies pages out before the device
  blocks are released), so no double-free across tiers is possible by
  construction; re-admission goes through the same import/checksum path
  as remote adoption.

Locking: ``PrefixDirectory._lock`` and ``ColdTier._lock`` are LEAF
locks (locksan-registered): nothing blocking runs under them and no
other lock is ever taken inside them, so they may be entered from any
point in the documented Region -> Cell -> Fleet -> Engine order —
including the eviction hook that fires under a driver's serving lock.

Everything here is deterministic: no RNG, no wall-clock reads (callers
pass ``now``), stable iteration orders — the DST auditor's directory
and cold-tier invariants (docs/dst.md #17/#18/#19) replay bit-
identically per seed.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ..resilience.locksan import named_lock
from .router import _hash64

__all__ = ["PrefixExport", "PrefixDirectory", "ColdTier", "KVTier",
           "CorruptExport", "prefix_hash"]


class CorruptExport(ValueError):
    """An adopted export failed its checksum at the importer — the
    corruption gate fired. Subclasses ValueError so every existing
    "degrade to local re-prefill" handler already covers it; callers
    that want to meter corruption separately catch this first."""


def prefix_hash(tokens: Sequence[int]) -> int:
    """Directory key for a full-block prefix: the SAME process-stable
    64-bit hash the affinity ring walks (router._hash64 over the
    comma-joined tokens), so a router-side key and an engine-side
    residency publication meet on identical values."""
    return _hash64(",".join(map(str, tokens)))


def _fold64(acc: int, value: int) -> int:
    """One FNV-1a fold step over a 64-bit accumulator."""
    return ((acc ^ (value & 0xFFFFFFFFFFFFFFFF))
            * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF


def export_checksum(tokens: Sequence[int],
                    payloads: Iterable[bytes] = ()) -> int:
    """Content checksum for a :class:`PrefixExport`: FNV-1a over the
    token stream, then over each payload buffer's bytes. Payload-free
    exports (the DST sim) checksum the tokens alone — enough to catch
    the injected wire corruption, which flips a token."""
    import hashlib

    acc = 0xCBF29CE484222325
    for t in tokens:
        acc = _fold64(acc, int(t))
    for buf in payloads:
        digest = hashlib.sha256(buf).digest()[:8]
        acc = _fold64(acc, int.from_bytes(digest, "big"))
    return acc


class PrefixExport:
    """A prefix's KV pages in wire form, for cross-replica adoption and
    cold-tier storage. ``pages``/``scales`` are host arrays in the
    engine's quantized layout (None in the payload-free DST sim); the
    geometry tuple mirrors ``SimKVExport``/``KVExport`` so the importer
    can refuse a mismatched donor before touching its pool."""

    __slots__ = ("tokens", "n_pages", "block_size", "n_layers",
                 "n_kv_heads", "head_dim", "dtype", "kv_quant",
                 "pages", "scales", "checksum", "wire_bytes",
                 "logical_bytes", "source")

    def __init__(self, tokens: Sequence[int], n_pages: int,
                 block_size: int, n_layers: int, n_kv_heads: int,
                 head_dim: int, dtype: str, kv_quant: str,
                 pages: Optional[Any] = None,
                 scales: Optional[Any] = None,
                 wire_bytes: int = 0, logical_bytes: int = 0,
                 source: str = "", checksum: Optional[int] = None):
        self.tokens = tuple(int(t) for t in tokens)
        self.n_pages = int(n_pages)
        self.block_size = int(block_size)
        self.n_layers = int(n_layers)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = str(dtype)
        self.kv_quant = str(kv_quant)
        self.pages = pages
        self.scales = scales
        self.wire_bytes = int(wire_bytes)
        self.logical_bytes = int(logical_bytes)
        self.source = source
        self.checksum = (int(checksum) if checksum is not None
                         else self.compute_checksum())

    def geometry(self) -> Tuple[int, int, int, int, str, str]:
        return (self.block_size, self.n_layers, self.n_kv_heads,
                self.head_dim, self.dtype, self.kv_quant)

    def _payload_buffers(self) -> List[bytes]:
        out: List[bytes] = []
        for arr in (self.pages, self.scales):
            if arr is None:
                continue
            if isinstance(arr, (list, tuple)):
                out.extend(a.tobytes() for a in arr if a is not None)
            else:
                out.append(arr.tobytes())
        return out

    def compute_checksum(self) -> int:
        return export_checksum(self.tokens, self._payload_buffers())

    def verify(self) -> bool:
        """True when the content still matches the stamped checksum —
        the importer's corruption gate (invariant #19: a corrupted
        export must never land)."""
        return self.compute_checksum() == self.checksum

    @property
    def key(self) -> Tuple[int, ...]:
        return self.tokens

    @property
    def hash(self) -> int:
        return prefix_hash(self.tokens)


class PrefixDirectory:
    """Bounded-staleness map of prefix hash -> {holder: t_published}.

    ``publish`` is a full replacement of one member's residency set
    (snapshot semantics: the set is whatever the replica's driver saw
    at its last publish tick), ``invalidate`` removes one entry
    synchronously (the eviction hook), ``drop_member`` removes a dead
    or migrated replica wholesale. ``holders`` answers routing: the
    fresh holder list plus a flag for "entries exist but all exceeded
    the staleness bound" — the router's signal to count a
    ``directory_stale`` outcome and fall back to the affinity ring.

    The lock is a private LEAF (see module docstring).
    """

    def __init__(self, staleness_s: float):
        self.staleness_s = float(staleness_s)
        self._lock = named_lock("PrefixDirectory._lock")
        # hash -> {member: t_published}
        self._holders: Dict[int, Dict[str, float]] = {}
        # member -> set of hashes (reverse index for O(set) publish/drop)
        self._by_member: Dict[str, set] = {}
        self.publishes = 0
        self.invalidations = 0

    # -- writes ----------------------------------------------------------
    def publish(self, member: str, hashes: Iterable[int],
                now: float) -> None:
        new = set(int(h) for h in hashes)
        with self._lock:
            self.publishes += 1
            old = self._by_member.get(member, set())
            for h in old - new:
                ent = self._holders.get(h)
                if ent is not None:
                    ent.pop(member, None)
                    if not ent:
                        del self._holders[h]
            for h in new:
                self._holders.setdefault(h, {})[member] = float(now)
            if new:
                self._by_member[member] = new
            else:
                self._by_member.pop(member, None)

    def invalidate(self, member: str, h: int) -> None:
        """Synchronous single-entry removal — the eviction/spill hook.
        Fires under the evicting driver's serving lock; legal because
        this lock is a leaf."""
        h = int(h)
        with self._lock:
            self.invalidations += 1
            ent = self._holders.get(h)
            if ent is not None and member in ent:
                del ent[member]
                if not ent:
                    del self._holders[h]
            mh = self._by_member.get(member)
            if mh is not None:
                mh.discard(h)
                if not mh:
                    del self._by_member[member]

    def drop_member(self, member: str) -> int:
        """Remove every entry a dead/migrated replica published (its
        pages are gone or untrusted — the entry must not outlive them).
        Returns the number of entries dropped."""
        with self._lock:
            hashes = self._by_member.pop(member, set())
            for h in hashes:
                ent = self._holders.get(h)
                if ent is not None:
                    ent.pop(member, None)
                    if not ent:
                        del self._holders[h]
            return len(hashes)

    # -- reads -----------------------------------------------------------
    def holders(self, h: int, now: float) -> Tuple[List[str], bool]:
        """(fresh holder names sorted, stale_only) for a prefix hash.
        ``stale_only`` is True when the directory HAS entries for the
        hash but every one exceeded the staleness bound — distinct from
        "no entry" so routing can meter directory lies separately from
        plain misses."""
        with self._lock:
            ent = self._holders.get(int(h))
            if not ent:
                return [], False
            fresh = sorted(m for m, t in ent.items()
                           if now - t <= self.staleness_s)
            return fresh, not fresh

    def has_fresh(self, h: int, now: float) -> bool:
        return bool(self.holders(h, now)[0])

    def entries_for(self, member: str) -> set:
        with self._lock:
            return set(self._by_member.get(member, set()))

    def members(self) -> List[str]:
        with self._lock:
            return sorted(self._by_member)

    def size(self) -> int:
        with self._lock:
            return len(self._holders)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "entries": len(self._holders),
                "members": {m: len(hs)
                            for m, hs in sorted(self._by_member.items())},
                "publishes": self.publishes,
                "invalidations": self.invalidations,
            }


class ColdTier:
    """Host-memory LRU of evicted prefixes, capacity-accounted in KV
    pages (the ZeRO-Offload discipline: host DRAM is a slower, bigger
    pool with its own explicit budget). Entries are immutable
    :class:`PrefixExport` host copies — no device references, so cold
    eviction is a plain ``del`` and cross-tier double-free cannot
    exist. ``put`` evicts LRU victims until the newcomer fits and
    refuses (counted) entries bigger than the whole tier; the chaos
    ``cold_pressure`` knob drops every Nth put, modelling a host under
    memory pressure. The lock is a private LEAF (module docstring)."""

    def __init__(self, capacity_pages: int):
        if capacity_pages < 1:
            raise ValueError(
                f"cold-tier capacity must be >= 1 page, got "
                f"{capacity_pages}")
        self.capacity_pages = int(capacity_pages)
        self._lock = named_lock("ColdTier._lock")
        self._entries: "OrderedDict[Tuple[int, ...], PrefixExport]" = \
            OrderedDict()
        self._used = 0
        self.puts = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.rejects = 0
        self.chaos_drops = 0

    def put(self, export: PrefixExport) -> bool:
        """Admit an evicted prefix. Returns False when refused (bigger
        than the tier, or dropped by injected cold pressure)."""
        from ..resilience.chaos import get_fault_injector

        inj = get_fault_injector()
        if inj is not None and inj.on_cold_put():
            with self._lock:
                self.chaos_drops += 1
            return False
        with self._lock:
            self.puts += 1
            if export.n_pages > self.capacity_pages:
                self.rejects += 1
                return False
            old = self._entries.pop(export.key, None)
            if old is not None:
                self._used -= old.n_pages
            while self._used + export.n_pages > self.capacity_pages:
                _, victim = self._entries.popitem(last=False)
                self._used -= victim.n_pages
                self.evictions += 1
            self._entries[export.key] = export
            self._used += export.n_pages
            return True

    def get(self, tokens: Sequence[int]) -> Optional[PrefixExport]:
        key = tuple(int(t) for t in tokens)
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return ent

    def contains(self, tokens: Sequence[int]) -> bool:
        with self._lock:
            return tuple(int(t) for t in tokens) in self._entries

    def invalidate(self, tokens: Sequence[int]) -> bool:
        key = tuple(int(t) for t in tokens)
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is None:
                return False
            self._used -= ent.n_pages
            return True

    @property
    def used_pages(self) -> int:
        with self._lock:
            return self._used

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def entry_pages(self) -> List[int]:
        """Per-entry page counts in LRU order — the DST accounting
        invariant's witness (#18: used == sum(entries), used <=
        capacity)."""
        with self._lock:
            return [e.n_pages for e in self._entries.values()]

    def keys(self) -> List[Tuple[int, ...]]:
        with self._lock:
            return list(self._entries.keys())

    def entries_snapshot(self) -> List[PrefixExport]:
        """Entries in LRU order WITHOUT touching recency or hit
        counters — the invariant auditor's read-only view (``get``
        would reorder the LRU and perturb replay determinism)."""
        with self._lock:
            return list(self._entries.values())

    def drop_all(self) -> None:
        with self._lock:
            self._entries.clear()
            self._used = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries), "used_pages": self._used,
                    "capacity_pages": self.capacity_pages,
                    "puts": self.puts, "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "rejects": self.rejects,
                    "chaos_drops": self.chaos_drops}


class KVTier:
    """One fleet's slice of the global KV tier: the shared directory
    plus (optionally) the shared host cold tier, built from a validated
    :class:`~deepspeed_tpu.config.KVTierConfig`. The fleet owns one and
    hands it to every replica at spawn; the cold tier is fleet-wide
    (one host pool per node), so a prefix spilled by one replica can be
    re-admitted by any sibling."""

    def __init__(self, config: Any):
        self.config = config
        self.directory = PrefixDirectory(config.directory_staleness_s)
        self.cold: Optional[ColdTier] = (
            ColdTier(config.cold_capacity_pages) if config.cold_tier
            else None)

    def drop_member(self, member: str) -> int:
        """Death/migration hook: the member's directory entries must not
        outlive its pages. The cold tier is NOT dropped — its entries
        are host copies that survived the donor by construction."""
        return self.directory.drop_member(member)

    def snapshot(self) -> Dict[str, Any]:
        out = {"directory": self.directory.snapshot()}
        if self.cold is not None:
            out["cold"] = self.cold.stats()
        return out
