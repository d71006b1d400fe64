"""The n-gram drafter of speculative decoding: prompt-lookup proposals
from a sequence's own token stream (no draft model). It shares nothing
with the cache or the compiled step; the engine (``ragged.py``) and the
DST stand-in memoize one :class:`NgramIndex` per live sequence."""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def _prompt_lookup(ctx: Sequence[int], ngram: int, k: int) -> List[int]:
    """Prompt-lookup drafting: if the trailing ``ngram`` of ``ctx`` occurred
    earlier, propose the (up to ``k``) tokens that followed its most recent
    earlier occurrence. The zero-cost draft model of prompt-lookup /
    n-gram speculative decoding — strong on the summarization/code/RAG
    workloads where outputs quote their inputs."""
    if k <= 0 or ngram <= 0 or len(ctx) <= ngram:
        return []
    arr = np.asarray(ctx, np.int32)
    pat = arr[-ngram:]
    win = np.lib.stride_tricks.sliding_window_view(arr[:-1], ngram)
    hits = np.nonzero((win == pat).all(axis=1))[0]
    if len(hits) == 0:
        return []
    # prefer the most recent occurrence that still has k continuation
    # tokens; fall back to whichever hit offers the longest continuation
    cont_len = np.minimum(len(arr) - (hits + ngram), k)
    full = np.nonzero(cont_len == k)[0]
    j = int(hits[full[-1]] if len(full) else hits[np.argmax(cont_len)])
    return arr[j + ngram: j + ngram + k].tolist()


class NgramIndex:
    """Incremental n-gram position index over one sequence's token stream
    — the memoized form of :func:`_prompt_lookup`, bit-identical in what
    it proposes but O(new tokens) per draft round instead of O(context):
    every fully-formed window's start position is recorded once (dict
    key -> ascending position list) as the stream grows, and a trim of
    the stream's tail pops exactly the invalidated entries off an
    append-ordered stack. ``lookup`` then answers "most recent earlier
    occurrence of the trailing n-gram with a k-token continuation, else
    the earliest occurrence" with two bisects plus an O(ngram + extra)
    scan of the windows that overlap the virtual ``extra`` suffix."""

    def __init__(self, ngram: int):
        self.ngram = int(ngram)
        self._toks: List[int] = []
        self._pos: Dict[Tuple[int, ...], List[int]] = {}
        self._order: List[Tuple[int, Tuple[int, ...]]] = []  # (start, key)

    def sync(self, tokens: Sequence[int]) -> None:
        """Index tokens appended since the last call. The caller
        guarantees the previously-indexed prefix is unchanged — the
        engine's only tail mutation (``trim``) calls :meth:`truncate`."""
        n = self.ngram
        if len(tokens) < len(self._toks):        # untracked truncation
            self.truncate(len(tokens))
        self._toks.extend(int(t) for t in tokens[len(self._toks):])
        start = self._order[-1][0] + 1 if self._order else 0
        for h in range(start, len(self._toks) - n + 1):
            key = tuple(self._toks[h:h + n])
            self._pos.setdefault(key, []).append(h)
            self._order.append((h, key))

    def truncate(self, length: int) -> None:
        """Drop the stream's tail: O(removed) — pops only entries whose
        window extends past ``length``."""
        del self._toks[length:]
        n = self.ngram
        while self._order and self._order[-1][0] + n > length:
            h, key = self._order.pop()
            lst = self._pos[key]
            lst.pop()                            # ascending: h is last
            if not lst:
                del self._pos[key]

    def lookup(self, extra: Sequence[int], k: int) -> List[int]:
        """Draft proposal for the stream + virtual ``extra`` suffix —
        exactly :func:`_prompt_lookup`'s answer for
        ``ctx = tokens + extra`` without rescanning ``tokens``."""
        import bisect

        n = self.ngram
        toks = self._toks
        ctx_len = len(toks) + len(extra)
        if k <= 0 or n <= 0 or ctx_len <= n:
            return []

        def at(i: int) -> int:
            return toks[i] if i < len(toks) else int(extra[i - len(toks)])

        pat = tuple(at(ctx_len - n + j) for j in range(n))
        limit = ctx_len - 1 - n          # last admissible window start
        base = self._pos.get(pat, [])
        hi = bisect.bisect_right(base, min(limit, len(toks) - n))
        # windows overlapping ``extra`` (or the trailing pattern region)
        # are not in the index — check the handful directly
        manual = [h for h in range(max(0, len(toks) - n + 1), limit + 1)
                  if all(at(h + j) == pat[j] for j in range(n))]
        if hi == 0 and not manual:
            return []
        # prefer the most recent start with a full k-token continuation;
        # manual starts are all later than indexed ones
        full_limit = ctx_len - n - k
        j = next((h for h in reversed(manual) if h <= full_limit), None)
        if j is None:
            idx = bisect.bisect_right(base, full_limit, 0, hi)
            if idx:
                j = base[idx - 1]
        if j is None:                    # no full hit: longest continuation
            j = base[0] if hi else manual[0]
        return [at(i) for i in range(j + n, min(j + n + k, ctx_len))]

